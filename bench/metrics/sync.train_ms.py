"""Host self time of the sync engine's TRAIN span (stacking the buckets,
dispatching the cohort steps and waiting on their losses) per scheduler
step, in ms."""
from tracefile import self_seconds


def read(ctx):
    s = self_seconds(ctx["spans"], "TRAIN", "train")
    return None if s is None or not ctx["steps"] else 1e3 * s / ctx["steps"]
