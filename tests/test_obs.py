"""Observability subsystem: zero-cost-when-disabled, bit-parity-neutral
when enabled (sync + async sweeps, served drains with the GC hook on), a
schema-valid Perfetto trace with one track per trial lane on both clocks,
GC/STEP/ADMIT/RETIRE spans on the profiler's clock, stable device program
names, and the trace_report CLI round-trip."""

import gc
import importlib.util
import json
import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.experiments.grid import TrialSpec
from repro.experiments.runner import run_vectorized
from repro.experiments.scheduler import serve
from repro.obs.export import (VIRTUAL_PID, WALL_PID, chrome_trace,
                              load_schema, read_metrics_jsonl,
                              trace_paths_for, validate_chrome_trace,
                              write_chrome_trace, write_metrics_jsonl)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_SPAN, Tracer, self_durations

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracing off and empty buffers, so
    span/metric state cannot leak across tests (or into other files)."""
    obs.disable()
    obs.tracer.clear()
    obs.registry.reset()
    yield
    obs.disable()
    obs.tracer.clear()
    obs.registry.reset()


def tiny_spec(**kw):
    base = dict(dataset="emnist", aggregator="fedavg", seed=0,
                tuner="fedtune", m0=3, e0=1.0, rounds=3,
                target_accuracy=0.99, batch_size=5, eval_points=128)
    base.update(kw)
    return TrialSpec(**base)


def assert_bitexact(plain, traced):
    for p, t in zip(plain, traced):
        assert p.history_acc == t.history_acc
        assert p.history_m == t.history_m
        assert p.history_e == t.history_e
        assert p.final_accuracy == t.final_accuracy
        assert (p.final_m, p.final_e) == (t.final_m, t.final_e)
        np.testing.assert_allclose(p.cost, t.cost, rtol=0, atol=0)
        assert p.reached == t.reached and p.rounds == t.rounds
        assert p.dispatch_log == t.dispatch_log
        assert p.staleness_log == t.staleness_log


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms_series():
    reg = MetricsRegistry()
    reg.inc("a")
    reg.inc("a", 2.5)
    reg.gauge("g", 7)
    for v in range(10):
        reg.observe("h", v)
    reg.sample("s", 4, step=0, engine="sync")
    assert reg.counter_value("a") == 3.5
    assert reg.gauges()["g"] == 7.0
    h = reg.histogram_summary("h")
    assert h["count"] == 10 and h["min"] == 0 and h["max"] == 9
    assert h["mean"] == pytest.approx(4.5)
    assert reg.series("s") == [{"name": "s", "value": 4.0, "step": 0,
                               "engine": "sync"}]
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3.5 and snap["n_series"] == 1
    reg.reset()
    assert reg.counter_value("a") == 0.0 and reg.series() == []


def test_tagged_counters_keep_one_value_per_tag_set():
    reg = MetricsRegistry()
    reg.inc("gc_s", 0.5, generation=0)
    reg.inc("gc_s", 0.25, generation=0)
    reg.inc("gc_s", 2.0, generation=2)
    assert reg.counter_value("gc_s", generation=0) == 0.75
    assert reg.counter_value("gc_s", generation=2) == 2.0
    assert reg.counter_value("gc_s") == 0.0 and reg.counters() == {}
    assert reg.tagged_counters() == [
        {"name": "gc_s", "value": 0.75, "generation": 0},
        {"name": "gc_s", "value": 2.0, "generation": 2}]
    reg.reset()
    assert reg.tagged_counters() == []


def test_no_phase_timer_is_left():
    """Host time by phase is span self time: the always-on phase timers
    and the ``repro.perf`` module that fronted them are gone."""
    assert importlib.util.find_spec("repro.perf") is None
    assert not any(n.startswith("phase") for n in dir(MetricsRegistry))


# ---------------------------------------------------------------------------
# zero-cost-when-disabled
# ---------------------------------------------------------------------------

def test_disabled_tracer_hands_out_the_shared_null_span():
    assert not obs.enabled()
    s = obs.span("anything", phase="train", trial="t", n=3)
    assert s is NULL_SPAN
    with s as inner:
        inner.set(more=1)      # attribute sink, no storage
    obs.record("x", virtual=(0, 1))
    obs.counter("c", 1)
    assert obs.tracer.spans == [] and obs.tracer.counters == []


def test_disabled_fast_path_is_cheap():
    """A sweep makes a handful of span calls per round; 100k disabled
    calls finishing in well under a second means the per-round cost is
    unmeasurable (generous bound to stay robust on loaded CI workers)."""
    t0 = time.perf_counter()
    for _ in range(100_000):
        with obs.span("s"):
            pass
    assert time.perf_counter() - t0 < 1.0


def test_enabled_spans_capture_dual_clocks():
    class FakeClock:
        now = 2.0
    obs.enable()
    clk = FakeClock()
    with obs.span("round", phase="round", trial="t0", round_idx=3,
                  clock=clk, n=5):
        clk.now = 6.0
    obs.disable()
    (sp,) = obs.tracer.spans
    assert sp.name == "round" and sp.trial == "t0" and sp.round_idx == 3
    assert sp.virtual_t0 == 2.0 and sp.virtual_t1 == 6.0
    assert sp.virtual_dur == 4.0 and sp.wall_dur >= 0.0
    assert sp.attrs == {"n": 5}


def test_enable_resets_previous_buffers():
    obs.enable()
    obs.record("a", virtual=(0, 1))
    obs.enable()               # default reset=True: fresh capture window
    assert obs.tracer.spans == []
    obs.enable(reset=False)
    obs.record("b", virtual=(0, 1))
    assert len(obs.tracer.spans) == 1


# ---------------------------------------------------------------------------
# bit-parity: traced == untraced, pinned for sync and async sweeps
# ---------------------------------------------------------------------------

def test_traced_sync_sweep_is_bit_exact():
    specs = [tiny_spec(seed=s, rounds=2) for s in range(4)]
    plain = run_vectorized(specs)
    obs.enable()
    traced = run_vectorized(specs)
    obs.disable()
    assert_bitexact(plain, traced)
    assert len(obs.tracer.spans) > 0     # tracing actually happened
    assert obs.registry.counter_value("pack_dispatches") > 0


def test_traced_async_sweep_is_bit_exact_and_fills_staleness():
    specs = [tiny_spec(seed=s, mode="async", m0=2, rounds=3)
             for s in range(4)]
    plain = run_vectorized(specs)
    obs.enable()
    traced = run_vectorized(specs)
    obs.disable()
    assert_bitexact(plain, traced)
    stale = obs.registry.histogram_summary("staleness")
    assert stale["count"] == sum(len(t.staleness_log) for t in traced)
    assert obs.registry.counter_value("event_dispatched") > 0
    assert obs.registry.series("lanes_live")


# ---------------------------------------------------------------------------
# chrome trace export + checked-in schema
# ---------------------------------------------------------------------------

def _traced_sweep_trace(tmp_path):
    specs = [tiny_spec(seed=s, rounds=2) for s in range(2)]
    obs.enable()
    run_vectorized(specs)
    obs.disable()
    path = str(tmp_path / "sweep.trace.json")
    trace = write_chrome_trace(path)
    return specs, path, trace


def test_exported_trace_validates_and_has_per_lane_tracks(tmp_path):
    specs, path, trace = _traced_sweep_trace(tmp_path)
    assert validate_chrome_trace(trace) == []
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) == []
    # one named track per trial lane, on BOTH clock processes
    names = {(ev["pid"], ev["args"]["name"])
             for ev in trace["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    for spec in specs:
        for pid in (WALL_PID, VIRTUAL_PID):
            assert any(p == pid and spec.key() in n for p, n in names), \
                (pid, spec.key())
    # the virtual-clock process carries per-round spans for each lane
    virt = [ev for ev in trace["traceEvents"]
            if ev["ph"] == "X" and ev["pid"] == VIRTUAL_PID]
    assert {ev["name"] for ev in virt} >= {"round"}
    # and the counter track samples simulated time on the wall process
    assert any(ev["ph"] == "C" and ev["name"] == "t_sim"
               for ev in trace["traceEvents"])


def test_schema_validator_catches_breakage():
    schema = load_schema()
    ok = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 0.0,
         "dur": 1.0, "args": {}},
        {"ph": "X", "pid": 1, "tid": 0, "name": "b", "ts": 2.0,
         "dur": 1.0, "args": {}},
    ]}
    assert validate_chrome_trace(ok, schema) == []
    assert validate_chrome_trace({}, schema)                  # no traceEvents
    missing_pid = {"traceEvents": [
        {"ph": "X", "tid": 0, "name": "a", "ts": 0.0, "dur": 1.0,
         "args": {}}]}
    assert any("missing" in e for e in
               validate_chrome_trace(missing_pid, schema))
    unknown_ph = {"traceEvents": [
        {"ph": "Z", "pid": 1, "tid": 0, "name": "a", "args": {}}]}
    assert any("ph" in e for e in validate_chrome_trace(unknown_ph, schema))
    backwards = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 5.0,
         "dur": 1.0, "args": {}},
        {"ph": "X", "pid": 1, "tid": 0, "name": "b", "ts": 1.0,
         "dur": 1.0, "args": {}}]}
    assert any("track" in e for e in
               validate_chrome_trace(backwards, schema))
    # monotonicity is PER track: interleaved tracks may each restart
    two_tracks = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 5.0,
         "dur": 1.0, "args": {}},
        {"ph": "X", "pid": 1, "tid": 1, "name": "b", "ts": 1.0,
         "dur": 1.0, "args": {}}]}
    assert validate_chrome_trace(two_tracks, schema) == []
    negative = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 0.0,
         "dur": -1.0, "args": {}}]}
    assert any("negative" in e for e in
               validate_chrome_trace(negative, schema))


def test_every_track_ts_is_monotonic_in_export(tmp_path):
    _specs, _path, trace = _traced_sweep_trace(tmp_path)
    last = {}
    for ev in trace["traceEvents"]:
        if ev["ph"] == "M":
            continue
        track = (ev["pid"], ev["tid"])
        assert ev["ts"] >= last.get(track, -1.0)
        last[track] = ev["ts"]


# ---------------------------------------------------------------------------
# metrics JSONL + path derivation
# ---------------------------------------------------------------------------

def test_metrics_jsonl_round_trip(tmp_path):
    obs.enable()
    obs.registry.sample("lanes_live", 4, step=0, engine="sync")
    obs.registry.inc("pack_steps_real", 30)
    obs.registry.inc("pack_steps_padded", 40)
    obs.registry.observe("staleness", 2)
    obs.registry.inc("gc_s", 0.5, generation=0)
    obs.disable()
    path = str(tmp_path / "m.jsonl")
    n = write_metrics_jsonl(path)
    rows = read_metrics_jsonl(path)
    assert len(rows) == n
    by_kind = {}
    for r in rows:
        by_kind.setdefault(r["kind"], []).append(r)
    assert {"kind": "sample", "name": "lanes_live", "value": 4.0,
            "step": 0, "engine": "sync"} in by_kind["sample"]
    counters = {r["name"]: r["value"] for r in by_kind["counter"]}
    assert counters["pack_steps_real"] == 30.0
    (h,) = by_kind["histogram"]
    assert h["name"] == "staleness" and h["count"] == 1
    assert {"kind": "counter", "name": "gc_s", "value": 0.5,
            "generation": 0} in by_kind["counter"]
    assert "phase" not in by_kind


def test_trace_paths_derive_from_the_store():
    assert trace_paths_for("runs/sweep.jsonl") == (
        "runs/sweep.trace.json", "runs/sweep.metrics.jsonl")
    assert trace_paths_for("runs/sweep.jsonl", "x/t.trace.json") == (
        "x/t.trace.json", "x/t.metrics.jsonl")
    assert trace_paths_for("out", "t.json") == ("t.json", "t.metrics.jsonl")


# ---------------------------------------------------------------------------
# trace_report CLI round-trip
# ---------------------------------------------------------------------------

def _load_trace_report():
    path = os.path.join(REPO, "tools", "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_round_trips_a_traced_sweep(tmp_path, capsys):
    specs = [tiny_spec(seed=s, rounds=2) for s in range(2)]
    obs.enable()
    run_vectorized(specs)
    obs.disable()
    trace_path, metrics_path = trace_paths_for(str(tmp_path / "s.jsonl"))
    write_chrome_trace(trace_path)
    write_metrics_jsonl(metrics_path)

    tr = _load_trace_report()
    rep = tr.report(trace_path, metrics_path)
    assert rep["valid"] and not rep["errors"]
    assert len(rep["lanes"]) == len(specs)
    for lane in rep["lanes"]:
        assert 0.0 < lane["occupancy"] <= 1.0
        assert lane["t_sim_s"] > 0
    assert rep["phases"]["train"]["calls"] > 0
    # self time: nested spans are not counted twice, so the phases add up
    # to no more than the traced stretch of wall time
    ph = rep["phases"]
    assert all(p["self_ms"] >= -1e-6 for p in ph.values())
    assert ph["train"]["self_ms"] > 0 and ph["eval"]["self_ms"] > 0
    wall = [ev for ev in json.load(open(trace_path))["traceEvents"]
            if ev["ph"] == "X" and ev["pid"] == WALL_PID]
    extent_ms = (max(ev["ts"] + ev["dur"] for ev in wall)
                 - min(ev["ts"] for ev in wall)) / 1e3
    assert sum(p["self_ms"] for p in ph.values()) <= extent_ms + 1e-6
    met = rep["metrics"]
    assert met["mean_lanes_live"] == pytest.approx(2.0)
    assert 0.0 <= met["padding_waste"] < 1.0

    assert tr.main([trace_path, "--metrics", metrics_path]) == 0
    out = capsys.readouterr().out
    assert "wall-clock phases" in out and "virtual-clock lanes" in out
    assert tr.main([trace_path, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["valid"]


def test_trace_report_rejects_an_invalid_trace(tmp_path, capsys):
    bad = str(tmp_path / "bad.trace.json")
    with open(bad, "w") as f:
        json.dump({"traceEvents": [
            {"ph": "X", "tid": 0, "name": "a", "ts": 0.0, "dur": 1.0,
             "args": {}}]}, f)
    tr = _load_trace_report()
    assert tr.main([bad]) == 2
    assert "SCHEMA VIOLATION" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# engine-level span taxonomy
# ---------------------------------------------------------------------------

def test_sync_sweep_emits_the_macro_and_round_span_taxonomy():
    # one seed, two preferences: the trials share a dataset (and test
    # set), so their per-aggregation evals stack into one dispatch
    specs = [tiny_spec(rounds=2, preference=p)
             for p in ((1.0, 0.0, 0.0, 0.0), (0.25, 0.25, 0.25, 0.25))]
    obs.enable()
    run_vectorized(specs)
    obs.disable()
    names = {sp.name for sp in obs.tracer.spans}
    assert {"PLAN", "PACK", "TRAIN", "APPLY", "EVAL",
            "plan_sync_round", "round", "eval_stacked"} <= names
    rounds = [sp for sp in obs.tracer.spans if sp.name == "round"]
    assert all(sp.virtual_dur is not None and sp.virtual_dur > 0
               for sp in rounds)
    assert {sp.trial for sp in rounds} == {s.key() for s in specs}


def test_event_sweep_emits_collect_pack_apply_and_inflight_spans():
    specs = [tiny_spec(seed=s, mode="async", m0=2, rounds=2)
             for s in range(2)]
    obs.enable()
    run_vectorized(specs)
    obs.disable()
    names = {sp.name for sp in obs.tracer.spans}
    assert {"COLLECT", "TRAIN", "APPLY", "EVAL", "plan_event", "apply_event",
            "finish_event_round", "inflight", "agg_window"} <= names
    # the event engine trains under TRAIN/train; PACK is the sync engine's
    # data drawing alone
    assert "PACK" not in names
    assert {sp.phase for sp in obs.tracer.spans
            if sp.name == "TRAIN"} == {"train"}
    infl = [sp for sp in obs.tracer.spans if sp.name == "inflight"]
    # in-flight windows are virtual-only: comp+trans long, zero wall width
    assert all(sp.virtual_dur > 0 and sp.wall_dur == 0.0 for sp in infl)


# ---------------------------------------------------------------------------
# GC spans, begin/end and step spans on the profiler's clock
# ---------------------------------------------------------------------------

def _inside(inner, outer):
    return outer.wall_t0 <= inner.wall_t0 and inner.wall_t1 <= outer.wall_t1


def test_forced_collection_inside_a_span_is_one_nested_gc_span():
    before = list(gc.callbacks)
    was_auto = gc.isenabled()
    gc.disable()           # only the forced collection below may strike
    try:
        obs.enable()
        with obs.span("outer"):
            gc.collect()
        obs.disable()
    finally:
        if was_auto:
            gc.enable()
    assert gc.callbacks == before
    (outer,) = [sp for sp in obs.tracer.spans if sp.name == "outer"]
    (g,) = [sp for sp in obs.tracer.spans if sp.name == "GC"]
    assert g.phase == "gc" and _inside(g, outer) and g.wall_dur > 0
    assert g.attrs["generation"] == 2
    assert g.attrs["collected"] >= 0 and g.attrs["uncollectable"] >= 0
    assert obs.registry.counter_value("gc_collections", generation=2) == 1
    assert obs.registry.counter_value("gc_s", generation=2) > 0


def test_gc_hook_is_registered_only_while_tracing():
    before = list(gc.callbacks)
    gc.collect()                         # tracing off: nothing recorded
    assert gc.callbacks == before and obs.tracer.spans == []
    obs.enable()
    obs.enable(reset=False)              # a second enable adds no hook
    assert len(gc.callbacks) == len(before) + 1
    obs.disable()
    assert gc.callbacks == before
    obs.disable()                        # disabling twice is harmless
    assert gc.callbacks == before


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records enter/exit
    with the tracer's own clock reading between them."""
    log = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name, self.kw))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name, self.kw))
        return False


@pytest.fixture
def fake_profiler(monkeypatch):
    _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation.log


def test_begin_end_spans_enter_and_exit_the_profiler_annotation(
        fake_profiler):
    obs.enable(jax_annotations=True)
    h = obs.tracer.begin("GC", phase="gc", generation=1)
    assert fake_profiler == [("enter", "GC", {})]
    obs.tracer.end(h, collected=3, uncollectable=0)
    assert fake_profiler == [("enter", "GC", {}), ("exit", "GC", {})]
    was_auto = gc.isenabled()
    gc.disable()
    try:
        gc.collect()                     # the hook goes the same way
    finally:
        if was_auto:
            gc.enable()
    obs.disable()
    assert fake_profiler[2:] == [("enter", "GC", {}), ("exit", "GC", {})]
    first, forced = [sp for sp in obs.tracer.spans if sp.name == "GC"]
    assert first.attrs == {"generation": 1, "collected": 3,
                           "uncollectable": 0}
    assert first.wall_t0 <= first.wall_t1 <= forced.wall_t0
    # off: begin hands back the shared no-op and end accepts it
    assert obs.tracer.begin("GC", phase="gc") is NULL_SPAN
    obs.tracer.end(NULL_SPAN, collected=0)


def test_step_span_opens_a_step_annotation_around_its_own(fake_profiler):
    assert obs.step_span("STEP", 7, annotation="serve_step") is NULL_SPAN
    obs.enable(jax_annotations=True)
    with obs.step_span("STEP", 7, annotation="serve_step", phase="step"):
        pass
    obs.disable()
    assert fake_profiler == [("enter", "serve_step", {"step_num": 7}),
                             ("enter", "STEP", {}), ("exit", "STEP", {}),
                             ("exit", "serve_step", {"step_num": 7})]
    (sp,) = obs.tracer.spans
    assert (sp.name, sp.phase, sp.attrs) == ("STEP", "step", {"step": 7})


def test_self_durations_subtract_direct_children_once():
    spans = [(0.0, 10.0), (1.0, 4.0), (2.0, 3.0), (5.0, 5.0),
             (6.0, 9.0), (20.0, 21.0)]
    assert self_durations(spans) == pytest.approx(
        [10.0 - 3.0 - 3.0, 3.0 - 1.0, 1.0, 0.0, 3.0, 1.0])
    assert self_durations([]) == []


# ---------------------------------------------------------------------------
# served drains: STEP / ADMIT / RETIRE
# ---------------------------------------------------------------------------

SYNC_ENGINE_SPANS = {"PLAN", "PACK", "TRAIN", "APPLY", "EVAL", "REDUCE",
                     "eval_stacked", "plan_sync_round", "account_sync_round"}


def _served_specs():
    return [tiny_spec(seed=s % 2, rounds=1 + s % 3,
                      preference=((1.0, 0.0, 0.0, 0.0),
                                  (0.25, 0.25, 0.25, 0.25))[s // 2])
            for s in range(4)]


def test_served_drain_emits_step_admit_and_retire_intervals():
    specs = _served_specs()
    obs.enable()
    got = serve(specs, max_lanes=2)
    obs.disable()
    spans = obs.tracer.spans
    steps = [sp for sp in spans if sp.name == "STEP"]
    admits = [sp for sp in spans if sp.name == "ADMIT"]
    retires = [sp for sp in spans if sp.name == "RETIRE"]
    assert steps and admits and len(retires) == len(got) == len(specs)
    assert {sp.phase for sp in steps} == {"step"}
    assert [sp.attrs["step"] for sp in steps] == list(
        range(1, len(steps) + 1))
    assert {sp.phase for sp in admits} == {"admit"}
    assert {sp.phase for sp in retires} == {"retire"}
    assert all(sp.wall_dur > 0 for sp in steps + admits + retires)
    # every admission happens under an ADMIT span, every retirement under
    # a RETIRE span inside the STEP that finished the trial
    for inst, outer in (("admit", admits), ("retire", retires)):
        recs = [sp for sp in spans if sp.name == inst]
        assert len(recs) == len(specs)
        assert all(any(_inside(r, o) for o in outer) for r in recs)
    assert all(any(_inside(r, s) for s in steps) for r in retires)
    engine = [sp for sp in spans if sp.name in SYNC_ENGINE_SPANS]
    assert {sp.name for sp in engine} >= {"PLAN", "PACK", "TRAIN", "APPLY",
                                          "EVAL", "REDUCE"}
    assert all(any(_inside(sp, s) for s in steps) for sp in engine)


def test_traced_served_drain_is_bit_exact_with_the_gc_hook_on():
    specs = _served_specs()

    def collect(_res):
        gc.collect()         # a generation-2 collection in every retirement

    plain = serve(specs, max_lanes=2, on_result=collect)
    before = list(gc.callbacks)
    obs.enable(jax_annotations=True)
    assert len(gc.callbacks) == len(before) + 1
    traced = serve(specs, max_lanes=2, on_result=collect)
    obs.disable()
    assert gc.callbacks == before
    key = lambda r: r.spec.key()      # noqa: E731
    assert_bitexact(sorted(plain, key=key), sorted(traced, key=key))
    retires = [sp for sp in obs.tracer.spans if sp.name == "RETIRE"]
    gcs = [sp for sp in obs.tracer.spans
           if sp.name == "GC" and sp.attrs["generation"] == 2]
    assert len(gcs) >= len(specs)
    assert all(any(_inside(g, r) for r in retires) for g in gcs[:len(specs)])


# ---------------------------------------------------------------------------
# stable device program names
# ---------------------------------------------------------------------------

def _tiny_model():
    from repro.configs.paper_models import MLPConfig
    from repro.models import build_model
    from repro.optim.optimizers import get_optimizer
    model = build_model(MLPConfig(name="mlp_names", in_dim=8, hidden=(4,),
                                  n_classes=3))
    return model, get_optimizer("sgd", 0.1, momentum=0.9)


def _lower_program(which):
    from jax.sharding import Mesh
    from repro.experiments import runner
    from repro.federated.evaluation import EvalFnCache
    from repro.runtime import sharded
    model, opt = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    t, m, b, d = 2, 4, 5, 8
    sds = jax.ShapeDtypeStruct
    stacked = jax.tree.map(lambda p: sds((m,) + p.shape, p.dtype), params)
    xs, ys = sds((t, m, b, d), jnp.float32), sds((t, m, b), jnp.int32)
    masks, active = sds((t, m, b), jnp.float32), sds((t, m), jnp.bool_)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("clients",))
    if which == "runner.cohort_step":
        fn = runner._multi_cohort_fn(model, opt, 0.0)
        return fn.lower(stacked, xs, ys, masks, active)
    if which == "runner.cohort_step_sharded":
        n = sum(p.size for p in jax.tree.leaves(params))
        leaf_sizes = tuple(p.size for p in jax.tree.leaves(params))
        fn = runner._sharded_multi_fn(model, opt, 0.0, mesh, 2, leaf_sizes)
        return fn.lower(stacked, xs, ys, masks, active,
                        sds((m,), jnp.float32), sds((m,), jnp.int32),
                        sds((2, n), jnp.float32), sds((m,), jnp.bool_))
    if which == "sharded.cohort_step_sharded":
        fn = sharded._make_sharded_cohort_fn(model, opt, 0.0, mesh)
        return fn.lower(xs, ys, masks, active, sds((m,), jnp.float32),
                        params)
    x, y = sds((b, d), jnp.float32), sds((b,), jnp.int32)
    fn = EvalFnCache().get(model, stacked=which == "eval.stacked")
    return fn.lower(stacked if which == "eval.stacked" else params, x, y)


@pytest.mark.parametrize("which,module", [
    ("runner.cohort_step", "jit_cohort_step"),
    ("runner.cohort_step_sharded", "jit_cohort_step_sharded"),
    ("sharded.cohort_step_sharded", "jit_cohort_step_sharded"),
    ("eval.single", "jit_eval_accuracy"),
    ("eval.stacked", "jit_eval_accuracy"),
])
def test_device_programs_lower_to_stable_module_names(which, module):
    """The profiler's ``XLA Modules`` line names each device program by
    its module; the per-step device metrics read these names."""
    text = _lower_program(which).as_text()
    assert re.search(r"module @(\S+)", text).group(1) == module
