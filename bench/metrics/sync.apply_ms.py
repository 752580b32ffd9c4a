"""Host self time of the sync engine's APPLY span per scheduler step, in
ms: selector updates and the per-trial bookkeeping of aggregation, with
the nested REDUCE, account_sync_round and GC spans carved out."""
from tracefile import self_seconds


def read(ctx):
    s = self_seconds(ctx["spans"], "APPLY", "apply")
    return None if s is None or not ctx["steps"] else 1e3 * s / ctx["steps"]
