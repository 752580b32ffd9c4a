"""Model FLOPs of the examples trained in the window (6 per weight per
example, from the configuration's widths) over the window, the chips and
the chip's bfloat16 peak, in %. Padding lanes and masked batch rows are
not counted; the examples come from the trials' CompL counters."""
import work


def read(ctx):
    if not ctx["examples"] or ctx["peak"] is None:
        return None
    flops = ctx["examples"] * work.train_flops_per_example(
        ctx["config"]["model"])
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"]
                            * ctx["peak"]["peak_flops_bf16"])
