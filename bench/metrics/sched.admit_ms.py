"""Host self time of the scheduler's ADMIT span (polling the queue and
building each admitted trial's server and initial model) per scheduler
step, in ms."""
from tracefile import self_seconds


def read(ctx):
    s = self_seconds(ctx["spans"], "ADMIT", "admit")
    return None if s is None or not ctx["steps"] else 1e3 * s / ctx["steps"]
