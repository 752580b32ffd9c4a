"""Host self time of the sync engine's PACK span (drawing each selected
client's data and its batch order) per scheduler step, in ms."""
from tracefile import self_seconds


def read(ctx):
    s = self_seconds(ctx["spans"], "PACK", "pack")
    return None if s is None or not ctx["steps"] else 1e3 * s / ctx["steps"]
