"""Share of the traced window in which the chips ran no operation:
1 - busy / window, busy being the union of the device's op intervals."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.get("n_devices") or not tr["window_s"]:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
