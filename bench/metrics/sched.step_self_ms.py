"""Host self time of the scheduler's STEP span per scheduler step, in ms:
the host work of a step that falls under none of the named spans nested
in it (the engine's phases, RETIRE, GC)."""
from tracefile import self_seconds


def read(ctx):
    s = self_seconds(ctx["spans"], "STEP", "step")
    return None if s is None or not ctx["steps"] else 1e3 * s / ctx["steps"]
