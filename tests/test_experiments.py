"""Tests for the sweep-orchestration subsystem (repro.experiments):
grid validation at expansion time, vectorized-vs-independent trial parity,
store resume semantics, and the paper-style table emitter."""

import jax
import numpy as np
import pytest

from repro.experiments import (CANONICAL_PREFERENCE, ResultStore, SweepSpec,
                               TrialSpec, paper_table, parse_preferences,
                               run_sweep, run_trial, run_vectorized)
from repro.experiments.grid import spec_from_dict

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs a multi-device mesh (XLA_FLAGS="
           "--xla_force_host_platform_device_count=4)")


def tiny_spec(**kw):
    base = dict(dataset="emnist", aggregator="fedavg", seed=0,
                tuner="fedtune", m0=3, e0=1.0, rounds=3,
                target_accuracy=0.99, batch_size=5, eval_points=128)
    base.update(kw)
    return TrialSpec(**base)


# ---------------------------------------------------------------------------
# grid expansion + validation
# ---------------------------------------------------------------------------

def test_grid_expands_product_and_collapses_fixed_baselines():
    sweep = SweepSpec(datasets=("emnist",),
                      aggregators=("fedavg", "fedadam"),
                      preferences=parse_preferences("0,14"),
                      seeds=(0, 1), base=tiny_spec())
    specs = sweep.expand()
    # fedtune: 2 agg x 2 pref x 2 seeds = 8; fixed: 2 agg x 2 seeds = 4
    assert len(specs) == 12
    assert len({s.key() for s in specs}) == 12
    fixed = [s for s in specs if s.tuner == "fixed"]
    assert len(fixed) == 4
    assert all(s.preference == CANONICAL_PREFERENCE for s in fixed)
    # every fedtune trial's baseline twin is in the grid
    keys = {s.key() for s in specs}
    for s in specs:
        if s.tuner == "fedtune":
            assert s.baseline_key() in keys


def test_grid_unknown_aggregator_raises_at_expansion():
    sweep = SweepSpec(aggregators=("fedavg", "fedsgd"), base=tiny_spec())
    with pytest.raises(ValueError, match="fedavg"):
        sweep.expand()


def test_grid_unknown_client_exec_and_mode_raise():
    with pytest.raises(ValueError, match="sequential"):
        tiny_spec(client_exec="warp").validate()
    with pytest.raises(ValueError, match="sync"):
        tiny_spec(mode="psychic").validate()
    with pytest.raises(ValueError, match="emnist"):
        tiny_spec(dataset="mnist").validate()
    with pytest.raises(ValueError, match="preference"):
        tiny_spec(preference=(1.0, 1.0, 0.0, 0.0)).validate()


def test_spec_key_roundtrip_through_dict():
    s = tiny_spec(aggregator="fednova", preference=(0.5, 0.5, 0.0, 0.0))
    assert spec_from_dict(s.to_dict()) == s


def test_parse_preferences_forms():
    assert len(parse_preferences("all")) == 15
    assert parse_preferences("0") == [(1.0, 0.0, 0.0, 0.0)]
    assert parse_preferences("1,0,0,0;0,1,0,0") == [(1.0, 0.0, 0.0, 0.0),
                                                   (0.0, 1.0, 0.0, 0.0)]
    # a bare 4-element list: a quad only when it sums to 1, else indices
    # (paper_tables.py's default '0,1,4,14' is four indices)
    assert parse_preferences("1,0,0,0") == [(1.0, 0.0, 0.0, 0.0)]
    assert len(parse_preferences("0,1,4,14")) == 4
    assert parse_preferences("0,1,4,14")[0] == (1.0, 0.0, 0.0, 0.0)
    with pytest.raises(ValueError):
        parse_preferences("99")


# ---------------------------------------------------------------------------
# vectorized multi-trial parity: T=4 packed == 4 independent FLServer.run()
# ---------------------------------------------------------------------------

def assert_trial_parity(base, vec):
    """Round records must be identical: accuracies, FedTune (M, E)
    trajectories, cost totals — and for event-driven (async/buffered)
    trials, the full dispatch schedule and staleness sequence."""
    assert base.history_acc == vec.history_acc
    assert base.history_m == vec.history_m
    assert base.history_e == vec.history_e
    assert base.final_accuracy == vec.final_accuracy
    assert (base.final_m, base.final_e) == (vec.final_m, vec.final_e)
    np.testing.assert_allclose(base.cost, vec.cost, rtol=0, atol=0)
    assert base.reached == vec.reached
    assert base.rounds == vec.rounds
    assert base.dispatch_log == vec.dispatch_log
    assert base.staleness_log == vec.staleness_log


def test_vectorized_matches_independent_runs_fedavg():
    specs = [tiny_spec(seed=s) for s in range(4)]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert_trial_parity(b, v)


def test_vectorized_matches_independent_runs_fedadam():
    """One adaptive-server aggregator: per-trial optimizer state (m, v) must
    stay private to each packed trial."""
    specs = [tiny_spec(seed=s, aggregator="fedadam") for s in range(4)]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert_trial_parity(b, v)


def test_vectorized_mixed_aggregators_and_fixed_tuner():
    """Trials with different aggregators and tuners pack into one cohort
    without cross-talk."""
    specs = [tiny_spec(seed=0, aggregator="fedavg"),
             tiny_spec(seed=1, aggregator="fednova"),
             tiny_spec(seed=0, tuner="fixed",
                       preference=CANONICAL_PREFERENCE)]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert_trial_parity(b, v)


def test_vectorized_rejects_unknown_pack_and_accepts_compression():
    with pytest.raises(ValueError, match="pack"):
        run_vectorized([tiny_spec()], pack="origami")
    # upload-compressed trials vectorize (lane-wise quantization) — the
    # old sequential-only rejection is gone
    res = run_vectorized([tiny_spec(compression="int8", rounds=2)])
    assert res[0].engine.startswith("vectorized")


# ---------------------------------------------------------------------------
# compression as a lane transform: compressed trials run through BOTH
# vectorized engines bit-identically to independent FLServer.run() calls
# (the PR-5 acceptance bar) — no sequential fallback remains
# ---------------------------------------------------------------------------

def test_vectorized_compressed_sync_matches_independent_runs():
    specs = [tiny_spec(seed=s, compression="int8") for s in range(4)]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert v.engine.startswith("vectorized/")
        assert_trial_parity(b, v)


def test_vectorized_compressed_events_match_independent_runs():
    """Compressed async AND buffered trials off the merged event queue:
    each lane quantizes against its dispatch snapshot, exactly as
    _client_update does per arrival."""
    specs = [tiny_spec(seed=0, mode="async", compression="int8"),
             tiny_spec(seed=1, mode="async", compression="int8"),
             tiny_spec(seed=0, mode="buffered", rounds=2,
                       compression="int8"),
             tiny_spec(seed=1, mode="buffered", rounds=2,
                       compression="int8")]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert v.engine.startswith("vectorized-events/")
        assert_trial_parity(b, v)


def test_vectorized_mixed_compression_lanes_one_pack():
    """Compressed and uncompressed trials pack into ONE cohort: the lane
    mask applies the round trip only to compressed lanes, and neither
    side perturbs the other."""
    specs = [tiny_spec(seed=0),
             tiny_spec(seed=0, compression="int8"),
             tiny_spec(seed=1, mode="async"),
             tiny_spec(seed=1, mode="async", compression="int8")]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert_trial_parity(b, v)


def test_run_sweep_compressed_stays_vectorized(capsys):
    """run_sweep no longer routes compressed trials through the
    sequential fallback (and no longer says so)."""
    specs = [tiny_spec(seed=s, compression="int8", rounds=2)
             for s in range(2)]
    res = run_sweep(specs)
    out = capsys.readouterr().out
    assert "sequentially" not in out
    assert all(r.engine.startswith("vectorized") for r in res)


# ---------------------------------------------------------------------------
# merged-event-queue parity: T=4 vectorized async/buffered == 4 independent
# FLServer.run() calls (accuracies, costs, dispatch/staleness records,
# (M, E) trajectories) — the PR-4 acceptance bar
# ---------------------------------------------------------------------------

def test_vectorized_async_matches_independent_runs():
    specs = [tiny_spec(seed=s, mode="async") for s in range(4)]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert b.staleness_log, "async trials must record staleness"
        assert b.dispatch_log, "async trials must record dispatches"
        assert_trial_parity(b, v)


def test_vectorized_buffered_matches_independent_runs():
    """FedBuff trials: K-deep delta buffers stay private per trial, and
    flush-round records replay exactly."""
    specs = [tiny_spec(seed=s, mode="buffered", rounds=2) for s in range(4)]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert_trial_parity(b, v)


def test_vectorized_async_heterogeneous_fleet_parity():
    """A straggler fleet exercises the merged queue's dropout path (loads
    charged, concurrency refilled inline) and wide arrival-time spreads."""
    specs = [tiny_spec(seed=s, mode="async", het="stragglers")
             for s in range(3)]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert_trial_parity(b, v)


def test_vectorized_event_rerun_reproduces_exactly():
    """Re-running a merged-queue sweep replays the identical event order:
    same dispatch schedule, staleness sequence, and round records (the
    resume/re-run determinism the merged queue's (time, trial_key, seq)
    tie order exists to guarantee)."""
    specs = [tiny_spec(seed=s, mode="async") for s in range(3)]
    first = run_vectorized(specs)
    second = run_vectorized(specs)
    for a, b in zip(first, second):
        assert_trial_parity(a, b)


def test_vectorized_mixed_modes_one_sweep():
    """One run_vectorized call spanning all three runtime regimes: sync
    trials pack per round, async/buffered off the merged queue, results in
    input order, every trial bit-matching its standalone run."""
    specs = [tiny_spec(seed=0, mode="sync"),
             tiny_spec(seed=1, mode="async"),
             tiny_spec(seed=2, mode="buffered", rounds=2),
             tiny_spec(seed=3, mode="async", aggregator="fedadam")]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for s, b, v in zip(specs, base, vec):
        assert v.spec == s
        assert_trial_parity(b, v)


@multidevice
def test_sharded_pack_matches_batched_pack():
    """The clients-mesh packed cohort (per-trial segment sum + psum) agrees
    with the single-device pack up to float reassociation — including
    compressed lanes (quantized in the shard body) and the 'none'
    spelling, which must NOT be treated as compression enabled."""
    specs = [tiny_spec(seed=0), tiny_spec(seed=1, compression="none"),
             tiny_spec(seed=2, compression="int8")]
    vb = run_vectorized(specs, pack="batched")
    vs = run_vectorized(specs, pack="sharded")
    for b, s in zip(vb, vs):
        assert b.history_m == s.history_m
        assert b.history_e == s.history_e
        np.testing.assert_allclose(b.history_acc, s.history_acc, atol=1e-3)
        np.testing.assert_allclose(b.cost, s.cost, rtol=1e-6)


# ---------------------------------------------------------------------------
# the FedAvg row hand-off: each bucket's trained lanes placed straight into
# the group's reduce matrix (runner._reduce_layout / _place_rows)
# ---------------------------------------------------------------------------

@pytest.fixture
def place_calls(monkeypatch):
    """Every ``_place_rows`` dispatch as (matrix rows, lanes, zero-step:
    lanes are trial globals), beside the jitted program itself."""
    from types import SimpleNamespace
    from repro.experiments import runner
    spied = SimpleNamespace(calls=[], program=runner._place_rows)

    def spy(rows, params_b, lane_of_row):
        lanes = jax.tree.leaves(params_b)[0].shape[0]
        spied.calls.append((rows.shape[0], lanes,
                            isinstance(params_b, jax.Array)))
        return spied.program(rows, params_b, lane_of_row)

    monkeypatch.setattr(runner, "_place_rows", spy)
    return spied


@pytest.fixture
def traced():
    from repro import obs
    obs.disable()
    obs.tracer.clear()
    obs.registry.reset()
    obs.enable()
    yield obs
    obs.disable()
    obs.tracer.clear()
    obs.registry.reset()


# E = 0.5 at M = 64 draws one-example clients (round(0.5) = 0 steps)
ZERO_STEP = dict(seed=0, m0=64, e0=0.5)


@pytest.mark.parametrize("specs", [
    [tiny_spec(seed=1, compression="int8"),
     tiny_spec(seed=2, aggregator="fednova"), tiny_spec(**ZERO_STEP)],
    [tiny_spec(seed=1, batch_size=10), tiny_spec(**ZERO_STEP),
     tiny_spec(seed=2, compression="int8", batch_size=10),
     tiny_spec(seed=3, aggregator="fednova", batch_size=10)],
], ids=["zero_step_int8_fednova_one_pack", "one_reduce_two_train_groups"])
def test_fedavg_row_handoff_edge_cases_match_independent_runs(specs,
                                                              place_calls):
    """Zero-step FedAvg clients (placed from their trial's globals in one
    more placement), an int8 FedAvg trial (round trip fused in the
    reduce) and a FedNova trial (per-client pytrees) share buckets; in
    the second case one model's reduce matrix also spans two TRAIN groups
    (batch sizes 5 and 10).  Every trial stays bit-identical to a
    standalone run."""
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert_trial_parity(b, v)
    assert any(zero for _, _, zero in place_calls.calls)


@pytest.mark.parametrize("kw", [dict(m0=6), ZERO_STEP],
                         ids=["trained_only", "with_zero_step"])
def test_fedavg_rows_placed_once_per_bucket(kw, traced, monkeypatch):
    """One traced round over FedAvg trials: one placement per bucket (every
    bucket holds FedAvg lanes) plus one for zero-step rows, one placed row
    per client, and no per-client row or pytree kept on the cohort."""
    from dataclasses import fields
    from repro.experiments import runner
    seen = []
    real = runner._fused_sync_reduce

    def spy(groups):
        seen.extend(tr.cohort for grp in groups for tr in grp.trials)
        return real(groups)

    monkeypatch.setattr(runner, "_fused_sync_reduce", spy)
    live = [runner._make_live(tiny_spec(**{**kw, "seed": s}))
            for s in range(3)]
    n_entries = runner._sync_round_step(live)
    reg = traced.registry
    n_zero = n_entries - reg.counter_value("pack_lanes_real")
    assert (n_zero > 0) == (kw is ZERO_STEP)
    assert reg.counter_value("reduce_row_places") == (
        reg.counter_value("pack_dispatches") + (n_zero > 0))
    assert reg.counter_value("reduce_rows_placed") == n_entries
    assert reg.counter_value("reduce_fused_dispatches") == 1
    assert len(seen) == len(live)
    assert "flat_rows" not in {f.name for f in fields(runner._Cohort)}
    assert all(t is None for co in seen for t in co.trained)


def test_row_placement_compiles_per_pow2_pair_only(place_calls):
    """Over a sweep in which FedTune moves M, the placement program
    compiles once per (matrix rows, bucket lanes) pow2 pair, never per
    round."""
    place_calls.program.clear_cache()
    specs = [tiny_spec(seed=s, m0=5, rounds=6) for s in range(3)]
    res = run_vectorized(specs)
    assert any(len(set(r.history_m)) > 1 for r in res)
    pairs = set(place_calls.calls)
    assert all(n & (n - 1) == 0 for r, m, _ in pairs for n in (r, m))
    assert (place_calls.program._cache_size() <= len(pairs)
            < len(place_calls.calls))


# ---------------------------------------------------------------------------
# the stacked evaluation subsystem (federated/evaluation.py)
# ---------------------------------------------------------------------------

def test_stacked_evaluator_bitmatches_single_evaluator():
    """Lane i of a stacked evaluation equals Evaluator.evaluate on that
    trial's params EXACTLY — the float sequence the parity contract needs."""
    from repro.experiments.runner import build_server
    from repro.federated.evaluation import Evaluator, StackedEvaluator
    srv = build_server(tiny_spec())
    params = [srv.model.init(jax.random.PRNGKey(s)) for s in range(5)]
    single = Evaluator(srv.model, srv.dataset, 128)
    stacked = StackedEvaluator(srv.model, srv.dataset, 128)
    expect = [single.evaluate(p) for p in params]
    got = stacked.evaluate(params)
    assert got == expect
    # and through the grouping entry point, in item order
    from repro.federated.evaluation import evaluate_stacked
    items = [(srv.model, srv.dataset, 128, p) for p in params]
    assert evaluate_stacked(items) == expect


def test_stacked_eval_parity_every_aggregator():
    """Vectorized per-round accuracies bit-match standalone runs for every
    aggregator the grid accepts — the stacked eval sits on the round path
    of all of them."""
    from repro.federated.aggregation import AGGREGATORS
    specs = [tiny_spec(seed=0, rounds=2, aggregator=a)
             for a in sorted(AGGREGATORS)]
    base = [run_trial(s) for s in specs]
    vec = run_vectorized(specs)
    for b, v in zip(base, vec):
        assert_trial_parity(b, v)


def test_eval_fn_cache_eviction_never_changes_results():
    """Regression for the old module-level FIFO dict: a capacity-1 LRU
    forced to evict and recompile must reproduce the identical accuracy."""
    from repro.experiments.runner import build_server
    from repro.federated.evaluation import EvalFnCache, Evaluator
    srv_a = build_server(tiny_spec())
    srv_b = build_server(tiny_spec(dataset="cifar100"))
    cache = EvalFnCache(capacity=1)
    ev_a = Evaluator(srv_a.model, srv_a.dataset, 128, fn_cache=cache)
    ev_b = Evaluator(srv_b.model, srv_b.dataset, 128, fn_cache=cache)
    pa = srv_a.model.init(jax.random.PRNGKey(0))
    pb = srv_b.model.init(jax.random.PRNGKey(0))
    first_a = ev_a.evaluate(pa)
    first_b = ev_b.evaluate(pb)          # evicts a's jitted fn
    assert len(cache) == 1
    assert ev_a.evaluate(pa) == first_a  # recompiled, identical result
    assert ev_b.evaluate(pb) == first_b
    with pytest.raises(ValueError):
        EvalFnCache(capacity=0)


# ---------------------------------------------------------------------------
# store: resume + table emission
# ---------------------------------------------------------------------------

def test_store_resume_skips_completed_keys(tmp_path):
    store = ResultStore(str(tmp_path / "r.jsonl"))
    specs = [tiny_spec(seed=s, rounds=2) for s in range(2)]
    run_sweep(specs, store=store)
    assert store.completed_keys() == {s.key() for s in specs}
    # a re-invocation would filter on completed_keys: nothing pending
    pending = [s for s in specs if s.key() not in store.completed_keys()]
    assert pending == []
    # corrupt tail (killed mid-write) is skipped, earlier records survive
    with open(store.path, "a") as f:
        f.write('{"key": "trunc')
    assert len(store.load()) == 2


def test_paper_table_reports_fedtune_vs_fixed(tmp_path):
    store = ResultStore(str(tmp_path / "t.jsonl"))
    specs = [tiny_spec(rounds=2),
             tiny_spec(rounds=2, tuner="fixed",
                       preference=CANONICAL_PREFERENCE)]
    run_sweep(specs, store=store)
    table = paper_table(store.load())
    assert "emnist" in table and "fedavg" in table and "%" in table
    # unpaired records tabulate to nothing, not an error
    assert "no fedtune" in paper_table([])


def test_store_resume_covers_event_trials(tmp_path):
    """Async trials run through run_sweep land in the store and resume by
    key exactly like sync ones."""
    store = ResultStore(str(tmp_path / "a.jsonl"))
    specs = [tiny_spec(seed=s, mode="async", rounds=2) for s in range(2)]
    res = run_sweep(specs, store=store)
    assert all(r.engine.startswith("vectorized-events") for r in res)
    assert store.completed_keys() == {s.key() for s in specs}


# ---------------------------------------------------------------------------
# fleet-profile axes + het-aware / legacy-tolerant table emission
# ---------------------------------------------------------------------------

def test_sweep_hets_axis_expands_and_keys_distinct():
    sweep = SweepSpec(datasets=("emnist",), aggregators=("fedavg",),
                      preferences=parse_preferences("14"), seeds=(0,),
                      hets=("homogeneous", "stragglers"), base=tiny_spec())
    specs = sweep.expand()
    # (fedtune + fixed) x 2 profiles, all distinct keys
    assert len(specs) == 4
    assert {s.het for s in specs} == {"homogeneous", "stragglers"}
    assert len({s.key() for s in specs}) == 4


def _fake_record(spec, cost, drop_spec_keys=()):
    d = spec.to_dict()
    for k in drop_spec_keys:
        d.pop(k, None)
    return {"key": spec.key(), "status": "done",
            "baseline_key": spec.baseline_key(), "spec": d,
            "reached": False, "rounds": spec.rounds,
            "final_accuracy": 0.4, "final_m": spec.m0, "final_e": spec.e0,
            "cost": cost, "sim_time": 1.0, "wall": 0.1, "engine": "test",
            "history_m": [], "history_e": [], "history_acc": []}


def test_paper_table_renders_het_profile_columns():
    rows = []
    for het in ("homogeneous", "stragglers"):
        tuned = tiny_spec(het=het)
        fixed = tiny_spec(het=het, tuner="fixed",
                          preference=CANONICAL_PREFERENCE)
        rows.append(_fake_record(tuned, [80.0, 80.0, 80.0, 80.0]))
        rows.append(_fake_record(fixed, [100.0, 100.0, 100.0, 100.0]))
    table = paper_table(rows)
    assert "fedavg·homogeneous" in table
    assert "fedavg·stragglers" in table


def test_sweep_compressions_axis_expands_and_keys_distinct():
    sweep = SweepSpec(datasets=("emnist",), aggregators=("fedavg",),
                      preferences=parse_preferences("14"), seeds=(0,),
                      compressions=(None, "int8"), base=tiny_spec())
    specs = sweep.expand()
    # (fedtune + fixed) x 2 compression methods, all distinct keys
    assert len(specs) == 4
    assert {s.compression for s in specs} == {None, "int8"}
    assert len({s.key() for s in specs}) == 4
    # "none" normalizes to None so keys stay stable across spellings
    alias = SweepSpec(datasets=("emnist",), aggregators=("fedavg",),
                      preferences=parse_preferences("14"), seeds=(0,),
                      compressions=("none", "int8"), base=tiny_spec())
    assert {s.key() for s in alias.expand()} == {s.key() for s in specs}


def test_paper_table_renders_compression_columns():
    rows = []
    for comp in (None, "int8"):
        tuned = tiny_spec(compression=comp)
        fixed = tiny_spec(compression=comp, tuner="fixed",
                          preference=CANONICAL_PREFERENCE)
        rows.append(_fake_record(tuned, [80.0, 80.0, 80.0, 80.0]))
        rows.append(_fake_record(fixed, [100.0, 100.0, 100.0, 100.0]))
    table = paper_table(rows)
    assert "fedavg·int8" in table
    assert "fedavg·none" in table
    # legacy rows without the compression field tabulate as uncompressed
    legacy = [_fake_record(tiny_spec(), [80.0] * 4,
                           drop_spec_keys=("compression",)),
              _fake_record(tiny_spec(tuner="fixed",
                                     preference=CANONICAL_PREFERENCE),
                           [100.0] * 4, drop_spec_keys=("compression",))]
    assert "fedavg" in paper_table(legacy)


def test_paper_table_tolerates_legacy_rows_missing_het():
    """Records written before the het/preference fields existed (pre-PR-4
    stores) must tabulate under the defaults, not KeyError."""
    tuned = tiny_spec()
    fixed = tiny_spec(tuner="fixed", preference=CANONICAL_PREFERENCE)
    rows = [_fake_record(tuned, [80.0] * 4,
                         drop_spec_keys=("het", "preference")),
            _fake_record(fixed, [100.0] * 4, drop_spec_keys=("het",))]
    table = paper_table(rows)
    assert "fedavg" in table and "%" in table
    # and a record with no spec dict at all is skipped, not fatal
    assert "no fedtune" in paper_table([{"key": "x", "status": "done",
                                         "cost": [1, 1, 1, 1]}])
