"""The per-step host-span and device-program metrics on a hand-built
``ctx``: two scheduler steps whose spans and module times are counted by
hand, and a program without STEP spans or stable program names, where
each reads nothing."""
import importlib.util
from pathlib import Path

import pytest

METRICS = Path(__file__).resolve().parents[1] / "metrics"


class _Span:
    def __init__(self, name, phase, t0, t1):
        self.name, self.phase, self.wall_t0, self.wall_t1 = \
            name, phase, t0, t1


def _read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


SPANS = [
    _Span("ADMIT", "admit", 0.0, 0.5),
    _Span("GC", "gc", 0.1, 0.2),                   # under ADMIT
    _Span("STEP", "step", 1.0, 3.0),
    _Span("APPLY", "apply", 1.5, 2.5),
    _Span("REDUCE", "apply", 1.6, 1.8),
    _Span("account_sync_round", "account", 1.9, 2.0),
    _Span("GC", "gc", 2.1, 2.4),                   # the APPLY stall
    _Span("RETIRE", "retire", 2.6, 2.9),
    _Span("ADMIT", "admit", 3.0, 3.2),
    _Span("STEP", "step", 3.2, 4.2),
    _Span("retire", "retire", 4.0, 4.0),           # an instant record
]
MODULES = {"jit_cohort_step": 0.010, "jit_eval_accuracy": 0.002,
           "jit_fed_reduce": 0.004, "jit_dynamic_slice": 0.030,
           "jit_concatenate": 0.006}
OLD_MODULES = {"jit_run": 0.010, "jit_accuracy": 0.002,
               "jit_fed_reduce": 0.004, "jit_dynamic_slice": 0.030}


def _ctx(spans=SPANS, modules=MODULES, steps=2):
    return {"spans": spans, "steps": steps, "trace": {"module_s": modules}}


@pytest.mark.parametrize("name,ctx,want", [
    ("host.gc_ms", _ctx(), 1e3 * (0.1 + 0.3) / 2),
    ("host.gc_ms", _ctx([s for s in SPANS if s.name != "GC"]), 0.0),
    ("host.gc_ms", _ctx([s for s in SPANS if s.name != "STEP"]), None),
    ("sched.admit_ms", _ctx(), 1e3 * (0.4 + 0.2) / 2),
    ("sched.admit_ms", _ctx([s for s in SPANS if s.name != "ADMIT"]), None),
    # STEP 1: 2.0 less APPLY 1.0 and RETIRE 0.3; STEP 2: 1.0, its instant
    # record takes nothing
    ("sched.step_self_ms", _ctx(), 1e3 * (0.7 + 1.0) / 2),
    # APPLY 1.0 less REDUCE 0.2, account 0.1 and GC 0.3
    ("sync.apply_ms", _ctx(), 1e3 * 0.4 / 2),
    ("cohort.device_ms", _ctx(), 1e3 * 0.010 / 2),
    ("cohort.device_ms",
     _ctx(modules=dict(MODULES, jit_cohort_step_sharded=0.020)),
     1e3 * 0.030 / 2),
    ("cohort.device_ms", _ctx(modules=OLD_MODULES), None),
    ("eval.device_ms", _ctx(), 1e3 * 0.002 / 2),
    ("eval.device_ms", _ctx(modules=OLD_MODULES), None),
    ("device.glue_ms", _ctx(), 1e3 * 0.036 / 2),
    ("device.glue_ms", _ctx(modules=OLD_MODULES), None),
    ("device.glue_ms", _ctx(steps=0), None),
    ("sum", _ctx(), 1e3 * sum(MODULES.values()) / 2),
])
def test_step_metric_reads_the_hand_counted_value(name, ctx, want):
    if name == "sum":
        # the named programs, the reduction and the glue tile the module
        # time of the window
        reduce_ms = 1e3 * ctx["trace"]["module_s"]["jit_fed_reduce"] \
            / ctx["steps"]
        got = sum(_read(m, ctx) for m in ("cohort.device_ms",
                                          "eval.device_ms",
                                          "device.glue_ms")) + reduce_ms
    else:
        got = _read(name, ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)
