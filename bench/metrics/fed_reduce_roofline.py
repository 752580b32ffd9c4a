"""Share of its roofline that the fused FedAvg reduction reaches, in %.

The least time of each call is the larger of its bytes over the HBM
bandwidth and its FLOPs over the bfloat16 peak, from the call's (M rows,
T lanes) as the program's REDUCE span records them and N from the
configuration (``work.fed_reduce_traffic``); the time is the device time
of the ``jit_fed_reduce`` program's events in the trace. The memory term
sets the bound at every served shape."""
import work

MODULE = "jit_fed_reduce"


def read(ctx):
    dev_s = ctx["trace"].get("module_s", {}).get(MODULE)
    calls = [s for s in ctx["spans"] if s.name == "REDUCE"]
    if not dev_s or not calls or ctx["peak"] is None:
        return None
    n = work.param_count(ctx["config"]["model"])
    least = 0.0
    for s in calls:
        t = 1 << (s.attrs["n_lanes"] - 1).bit_length()
        nbytes, flops = work.fed_reduce_traffic(s.attrs["n_rows"], n, t)
        least += work.roofline_seconds(nbytes, flops, ctx["peak"])[0]
    return 100.0 * least / dev_s
