"""Device time of the accuracy evaluation per scheduler step, in ms: the
``jit_eval_accuracy`` program's events on the trace's ``XLA Modules``
line, summed over the chips."""
MODULE = "jit_eval_accuracy"


def read(ctx):
    dev_s = ctx["trace"].get("module_s", {}).get(MODULE)
    return None if dev_s is None or not ctx["steps"] \
        else 1e3 * dev_s / ctx["steps"]
