"""Host time of the Python garbage collector's generation-1 and
generation-2 collections per scheduler step, in ms: the summed length of
the program's GC spans. 0.0 when the window held none; None when the
program records no scheduler STEP spans, for then it has no GC hook
either."""


def read(ctx):
    spans = ctx["spans"]
    if not ctx["steps"] or not any(s.name == "STEP" for s in spans):
        return None
    gc_s = sum(s.wall_t1 - s.wall_t0 for s in spans if s.name == "GC")
    return 1e3 * gc_s / ctx["steps"]
