"""Device time of every program but the named ones (the cohort step, the
evaluation and the fused reduction) per scheduler step, in ms: the eager
slices, concatenations and broadcasts around them. With those three and
``jit_fed_reduce`` it adds up to the window's device module time. None
when the trace holds no ``jit_cohort_step`` program, for then the cohort
step carries another name and would count as glue."""
NAMED = ("jit_cohort_step", "jit_cohort_step_sharded", "jit_eval_accuracy",
         "jit_fed_reduce")


def read(ctx):
    mods = ctx["trace"].get("module_s", {})
    if not ctx["steps"] or not any(m in mods for m in NAMED[:2]):
        return None
    glue = sum(v for m, v in mods.items() if m not in NAMED)
    return 1e3 * glue / ctx["steps"]
