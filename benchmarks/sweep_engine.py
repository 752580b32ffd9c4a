"""Benchmark: vectorized T-trials-at-once vs T sequential FLServer.run().

The sweep engine's claim is that trials are an *axis*, not a queue: packing
every live trial's cohort into one scan/vmap amortizes the per-step
dispatch overhead that dominates T independent runs on small FL models —
and, since the stacked evaluation subsystem, the per-aggregation evals of
all live trials execute as one dispatch too.  This benchmark runs the same
T-trial grid (emnist-reduced, FedTune over the paper's preference vectors
so all trials share one dataset and one test set) both ways and reports
wall-clock, phase split, speedup, and parity:

  sequential — T full ``FLServer.run()`` calls, one after another (the
               pre-sweep-engine workflow)
  vectorized — ``run_vectorized`` packing all T trials: per virtual round
               (sync) or per merged-event-queue macro-step with one
               arrival-lane per trial (``--mode async|buffered``)

Wall-clock is split into ``train_s`` (cohort/client training dispatches),
``eval_s`` (accuracy dispatches), and ``other_s`` (host orchestration):
every timed run executes with ``repro.obs`` on (parity-neutral), and
``train_s``/``eval_s`` are the self time of its ``TRAIN`` and
``eval``/``eval_stacked`` spans, so the eval-amortization win of the
stacked evaluator is visible separately from the training win.
``--compression int8`` runs the same grid with upload-compressed trials —
they vectorize lane-wise, so ``sequential_trials`` must stay 0.

Both engines are warmed once (same shapes, so the second run measures
steady state, not XLA compilation) and parity is checked on the per-trial
round records: identical accuracies, costs, FedTune (M, E) trajectories —
and, for the event-driven modes, identical dispatch and staleness logs ==
the vectorized engine is a faithful T-way replica.

Emits the usual CSV rows plus one BENCH-format JSON line (and ``--json``
writes it to a file for CI artifact upload):

  BENCH {"bench": "sweep_engine", "mode": "sync", "t": 8, "seq_s": ...,
         "vec_s": ..., "speedup": ..., "bitmatch": true,
         "train_s": ..., "eval_s": ..., "other_s": ...,
         "seq_phases": {...}, "vec_phases": {...},
         "occupancy": ..., "padding_waste": ..., "phase_calls": {...},
         "sequential_trials": 0, ...}

``occupancy`` is the mean fraction of the T lanes still live per
macro-step of the timed vectorized run, ``padding_waste`` the fraction of
packed cohort steps spent on pow2 padding, and ``phase_calls`` the number
of ``TRAIN``/``eval*`` spans behind the phase seconds (the amortization
factor).

Usage: PYTHONPATH=src:. python benchmarks/sweep_engine.py [--t 8]
       [--rounds 4] [--mode async] [--compression int8]
       [--json sweep_bench.json]
"""

from __future__ import annotations

import argparse
import json
import time

from benchmarks.common import emit
from repro import obs
from repro.core.preferences import PAPER_PREFERENCES
from repro.experiments import TrialSpec, run_trial, run_vectorized, serve


def _specs(t: int, rounds: int, mode: str, compression: str = None):
    # event-driven modes run E0=2.0: each arrival is one client's training,
    # so deeper local runs are the regime where packing arrivals pays.
    # Trials span the paper's preference vectors at one seed: they share a
    # dataset (and test set), so the stacked evaluator amortizes their
    # per-aggregation evals into one dispatch.
    e0 = 1.0 if mode == "sync" else 2.0
    return [TrialSpec(dataset="emnist", aggregator="fedavg", seed=0,
                      preference=PAPER_PREFERENCES[
                          s % len(PAPER_PREFERENCES)].as_tuple(),
                      tuner="fedtune", m0=10, e0=e0, rounds=rounds,
                      target_accuracy=0.99, batch_size=5, eval_points=256,
                      mode=mode, compression=compression)
            for s in range(t)]


def _staggered_specs(t: int, rounds: int, mode: str):
    """A staggered-target grid: round budgets cycle 1..rounds, so trials
    finish at different virtual times — the drain shape where a fixed
    pack idles lanes and continuous batching refills them."""
    e0 = 1.0 if mode == "sync" else 2.0
    return [TrialSpec(dataset="emnist", aggregator="fedavg", seed=0,
                      preference=PAPER_PREFERENCES[
                          s % len(PAPER_PREFERENCES)].as_tuple(),
                      tuner="fedtune", m0=10, e0=e0,
                      rounds=1 + s % rounds,
                      target_accuracy=0.99, batch_size=5, eval_points=256,
                      mode=mode)
            for s in range(t)]


def _run_sequential(specs):
    return [run_trial(s) for s in specs]


_TRAIN_SPANS = ("TRAIN",)
_EVAL_SPANS = ("eval", "eval_stacked")


def _timed_phases(fn):
    """Run ``fn`` traced, from fresh span and metric buffers; returns
    (result, seconds, phase dict).  ``train_s``/``eval_s`` are the self
    time of the run's ``TRAIN`` and ``eval``/``eval_stacked`` spans; the
    call counts are how many of each it opened — for the vectorized
    engine packed cohort / stacked eval dispatches, for sequential
    per-client / per-trial calls, the amortization factor in one number.
    Tracing stays on afterwards if it was on before."""
    was_on = obs.enabled()
    obs.enable()
    obs.registry.reset()
    t0 = time.perf_counter()
    res = fn()
    total = time.perf_counter() - t0
    spans = list(obs.tracer.spans)
    if not was_on:
        obs.disable()
    own = obs.self_durations([(sp.wall_t0, sp.wall_t1) for sp in spans])
    train = sum(d for sp, d in zip(spans, own) if sp.name in _TRAIN_SPANS)
    ev = sum(d for sp, d in zip(spans, own) if sp.name in _EVAL_SPANS)
    return res, total, {
        "total_s": round(total, 4), "train_s": round(train, 4),
        "eval_s": round(ev, 4),
        "other_s": round(max(total - train - ev, 0.0), 4),
        "train_calls": sum(sp.name in _TRAIN_SPANS for sp in spans),
        "eval_calls": sum(sp.name in _EVAL_SPANS for sp in spans)}


def main(settings=None, *, t: int = 8, rounds: int = 4, mode: str = "sync",
         pack: str = "batched", compression: str = None,
         json_path: str = None):
    del settings    # reduced scale only: the sweep is over T, not data size
    import jax
    specs = _specs(t, rounds, mode, compression)

    # warm both engines (compilation + dataset materialization), then time
    # the steady state — grids are deterministic, so shapes repeat exactly
    _run_sequential(specs)
    seq, seq_s, seq_phases = _timed_phases(lambda: _run_sequential(specs))

    run_vectorized(specs, pack=pack)
    # the timed vectorized run's metrics (occupancy, padding waste) land in
    # BENCH.  Instrumentation is per-round host-side bookkeeping (gated,
    # parity-neutral), so vec_s stays an honest engine timing.
    obs.enable()
    vec, vec_s, vec_phases = _timed_phases(
        lambda: run_vectorized(specs, pack=pack))
    snap = obs.registry.snapshot()
    lanes = [r["value"] for r in obs.registry.series("lanes_live")]
    obs.disable()
    occupancy = (sum(lanes) / len(lanes) / t) if lanes else 0.0
    steps_pad = snap["counters"].get("pack_steps_padded", 0.0)
    padding_waste = (1.0 - snap["counters"].get("pack_steps_real", 0.0)
                     / steps_pad) if steps_pad else 0.0

    bitmatch = True
    max_acc_diff = 0.0
    for b, v in zip(seq, vec):
        if (b.history_m, b.history_e) != (v.history_m, v.history_e):
            bitmatch = False
        for a, c in zip(b.history_acc, v.history_acc):
            d = abs(a - c)
            max_acc_diff = max(max_acc_diff, d)
            if d > 0:
                bitmatch = False
        if tuple(b.cost) != tuple(v.cost):
            bitmatch = False
        # event-driven modes: the full dispatch schedule and staleness
        # sequence must replay exactly too
        if (b.dispatch_log, b.staleness_log) != (v.dispatch_log,
                                                 v.staleness_log):
            bitmatch = False

    speedup = seq_s / vec_s if vec_s > 0 else float("inf")
    emit(f"sweep_engine/{mode}_sequential_t{t}", seq_s * 1e6, "baseline")
    emit(f"sweep_engine/{mode}_vectorized_t{t}", vec_s * 1e6,
         f"speedup_vs_seq={speedup:.2f}x")
    payload = {"bench": "sweep_engine", "mode": mode, "t": t,
               "rounds": rounds, "pack": pack,
               "compression": compression,
               "devices": jax.device_count(),
               "seq_s": round(seq_s, 4), "vec_s": round(vec_s, 4),
               "speedup": round(speedup, 3), "bitmatch": bitmatch,
               "max_acc_diff": max_acc_diff,
               # the vectorized run's phase split (+ both engines' full
               # splits): eval_s amortization is the stacked evaluator's win
               "train_s": vec_phases["train_s"],
               "eval_s": vec_phases["eval_s"],
               "other_s": vec_phases["other_s"],
               "seq_phases": seq_phases, "vec_phases": vec_phases,
               # observability of the timed vectorized run: mean live-lane
               # occupancy (fraction of T still running per macro-step) and
               # the pow2-padding waste of its packed cohort dispatches
               "occupancy": round(occupancy, 4),
               "padding_waste": round(padding_waste, 4),
               "phase_calls": {"train": vec_phases["train_calls"],
                               "eval": vec_phases["eval_calls"]},
               # compressed grids must vectorize: no trial may have taken
               # the one-at-a-time path
               "sequential_trials": sum(
                   not r.engine.startswith("vectorized") for r in vec)}
    print("BENCH " + json.dumps(payload), flush=True)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f)
            f.write("\n")
    return payload


def serve_main(*, t: int = 12, max_lanes: int = 4, rounds: int = 3,
               mode: str = "sync", pack: str = "batched",
               json_path: str = None):
    """Fixed-pack vs continuous-batching on a staggered-target grid.

    Three timed runs over the SAME t trials (round budgets cycling
    1..rounds so they finish at different times): sequential baseline,
    the fixed-set vectorized engine (its ``lanes_live`` occupancy decays
    as trials finish), and the continuous-batching scheduler with
    ``max_lanes`` lanes (its ``pool_occupancy`` stays near 1.0 until the
    queue runs dry).  Bitmatch compares every served trial against its
    sequential twin — admission order and lane reuse must never change a
    trial's floats."""
    import jax
    specs = _staggered_specs(t, rounds, mode)
    assert len({s.key() for s in specs}) == t, "staggered grid keys collide"

    _run_sequential(specs)
    seq, seq_s, seq_phases = _timed_phases(lambda: _run_sequential(specs))

    # fixed pack: all t trials admitted at once, lanes idle as they finish
    run_vectorized(specs, pack=pack)
    obs.enable()
    _fixed, fixed_s, fixed_phases = _timed_phases(
        lambda: run_vectorized(specs, pack=pack))
    lanes = [r["value"] for r in obs.registry.series("lanes_live")]
    obs.disable()
    occupancy_fixed = (sum(lanes) / len(lanes) / t) if lanes else 0.0

    # continuous batching: max_lanes lanes, freed slots refill mid-flight
    serve(list(specs), max_lanes=max_lanes, pack=pack)
    obs.enable()
    srv, serve_s, serve_phases = _timed_phases(
        lambda: serve(list(specs), max_lanes=max_lanes, pack=pack))
    occ = [r["value"] for r in obs.registry.series("pool_occupancy")]
    snap = obs.registry.snapshot()
    obs.disable()
    occupancy_serve = sum(occ) / len(occ) if occ else 0.0

    by_key = {r.spec.key(): r for r in srv}
    bitmatch = True
    max_acc_diff = 0.0
    for b in seq:
        v = by_key.get(b.spec.key())
        if v is None:
            bitmatch = False
            continue
        if (b.history_m, b.history_e) != (v.history_m, v.history_e):
            bitmatch = False
        for a, c in zip(b.history_acc, v.history_acc):
            d = abs(a - c)
            max_acc_diff = max(max_acc_diff, d)
            if d > 0:
                bitmatch = False
        if tuple(b.cost) != tuple(v.cost):
            bitmatch = False
        if (b.dispatch_log, b.staleness_log) != (v.dispatch_log,
                                                 v.staleness_log):
            bitmatch = False

    emit(f"sweep_engine/{mode}_fixed_pack_t{t}", fixed_s * 1e6,
         f"occupancy={occupancy_fixed:.2f}")
    emit(f"sweep_engine/{mode}_serve_t{t}_l{max_lanes}", serve_s * 1e6,
         f"occupancy={occupancy_serve:.2f}")
    payload = {"bench": "sweep_engine", "serve": True, "mode": mode,
               "t": t, "max_lanes": max_lanes, "rounds": rounds,
               "pack": pack, "devices": jax.device_count(),
               "seq_s": round(seq_s, 4), "fixed_s": round(fixed_s, 4),
               "serve_s": round(serve_s, 4),
               "speedup_vs_seq": round(seq_s / serve_s, 3) if serve_s else 0,
               "bitmatch": bitmatch, "max_acc_diff": max_acc_diff,
               # sustained lane occupancy: fixed pack over its t lanes vs
               # the scheduler's pool — the continuous-batching claim
               "occupancy_fixed": round(occupancy_fixed, 4),
               "occupancy_serve": round(occupancy_serve, 4),
               "trials_admitted": snap["counters"].get("trials_admitted", 0),
               "trials_retired": snap["counters"].get("trials_retired", 0),
               "seq_phases": seq_phases, "fixed_phases": fixed_phases,
               "serve_phases": serve_phases}
    print("BENCH " + json.dumps(payload), flush=True)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f)
            f.write("\n")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--t", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--mode", default="sync",
                    choices=("sync", "async", "buffered"),
                    help="runtime mode of the benchmarked trials (async/"
                         "buffered exercise the merged event-queue engine)")
    ap.add_argument("--pack", default="batched",
                    choices=("batched", "sharded"))
    ap.add_argument("--compression", default=None,
                    choices=(None, "none", "int8"),
                    help="upload compression for every trial (int8 trials "
                         "vectorize lane-wise)")
    ap.add_argument("--serve", action="store_true",
                    help="benchmark continuous batching: fixed-pack vs the "
                         "lane-pool scheduler on a staggered-target grid")
    ap.add_argument("--max-lanes", type=int, default=4,
                    help="scheduler lane-pool capacity for --serve")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    if args.serve:
        serve_main(t=args.t, max_lanes=args.max_lanes, rounds=args.rounds,
                   mode=args.mode, pack=args.pack, json_path=args.json)
    else:
        main(t=args.t, rounds=args.rounds, mode=args.mode, pack=args.pack,
             compression=None if args.compression in (None, "none")
             else args.compression,
             json_path=args.json)
