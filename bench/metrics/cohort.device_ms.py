"""Device time of the packed cohort step per scheduler step, in ms: the
``jit_cohort_step`` and ``jit_cohort_step_sharded`` programs' events on
the trace's ``XLA Modules`` line, summed over the chips."""
MODULES = ("jit_cohort_step", "jit_cohort_step_sharded")


def read(ctx):
    mods = ctx["trace"].get("module_s", {})
    if not ctx["steps"] or not any(m in mods for m in MODULES):
        return None
    return 1e3 * sum(mods.get(m, 0.0) for m in MODULES) / ctx["steps"]
