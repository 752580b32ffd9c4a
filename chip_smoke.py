#!/usr/bin/env python3
"""Chip smoke test: drive the trial-serving path once on a TPU and check it.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a four-chip host: the sharded pack

One chip, all phases in this one process:

  1. device   — refuses anything but a TPU; never carries on on the CPU.
  2. serve    — 19 trials at paper scale (the EMNIST-like federation with
                2,520 training and 1,080 test clients, the served
                784-48-62 MLP) drained through ``experiments.scheduler.serve``
                with 16 lanes and the batched pack, cold and then warm: the
                paper's 15 preference vectors (FedAvg, FedTune, M0 = 20,
                E0 = 1, 3 rounds) plus one int8-upload, one async, one
                buffered and one FedAdam trial.
  3. reference— the same specs one at a time through ``run_trial`` (a
                standalone ``FLServer.run()``), compared with the served
                results round record by round record.
  4. kernel   — ``ops.fed_reduce`` at the served (M, T, N), plain and int8:
                the compiled program must hold the Pallas custom call, and
                its output is compared with the jitted jnp reference.

With ``--four-chips`` only the 15 sync FedAvg trials run, through the sharded
pack (the ``clients`` mesh over 4 chips) and through the batched pack, and
are compared by the sharded-pack criterion of tests/test_experiments.py.

Times printed are host wall time.  The compile cache is placed by
``repro.compile_cache``.  The last line of stdout is one JSON object naming
the device; any failure exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_LANES = 16
ROUNDS = 3
PARITY_FIELDS = ("history_acc", "history_m", "history_e", "final_accuracy",
                 "final_m", "final_e", "cost", "reached", "rounds",
                 "dispatch_log", "staleness_log")


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check_device(n_chips: int | None) -> dict:
    """Platform, kind and count as JAX reports them; exits unless the
    devices are TPUs (and, when asked, exactly ``n_chips`` of them)."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        fail(f"JAX found no TPU (platform {dev['platform']!r}); this check "
             "runs on the chip only")
    if n_chips is not None and dev["count"] != n_chips:
        fail(f"needs {n_chips} chips, JAX sees {dev['count']}")
    return dev


def served_specs():
    """The served queue: 15 preference trials, then the four variants."""
    from dataclasses import replace

    from repro.core.preferences import PAPER_PREFERENCES
    from repro.experiments import TrialSpec
    base = TrialSpec(dataset="emnist", aggregator="fedavg", tuner="fedtune",
                     m0=20, e0=1.0, rounds=ROUNDS, reduced=False)
    prefs = [replace(base, preference=p.as_tuple())
             for p in PAPER_PREFERENCES]
    return prefs + [replace(base, compression="int8"),
                    replace(base, mode="async"),
                    replace(base, mode="buffered"),
                    replace(base, aggregator="fedadam")]


def check_results(specs, results, label: str):
    """Every spec retired once, with finite round records."""
    keys = [r.spec.key() for r in results]
    if sorted(keys) != sorted(s.key() for s in specs):
        fail(f"{label}: retired {len(keys)} of {len(specs)} trials")
    for r in results:
        vals = list(r.history_acc) + list(r.cost) + [r.final_accuracy]
        if not r.rounds or not all(math.isfinite(v) for v in vals):
            fail(f"{label}: {r.spec.key()} has rounds={r.rounds} or a "
                 "non-finite record")
        if not all(0.0 <= a <= 1.0 for a in r.history_acc):
            fail(f"{label}: {r.spec.key()} accuracy outside [0, 1]")


def divergence(a, b):
    """None when two TrialResults agree on every parity field; else the
    first round whose (accuracy, M, E) differs (== the round count when
    only totals differ) and the fields that differ."""
    fields = [f for f in PARITY_FIELDS if getattr(a, f) != getattr(b, f)]
    if not fields:
        return None
    per_round = list(zip(a.history_acc, a.history_m, a.history_e))
    other = list(zip(b.history_acc, b.history_m, b.history_e))
    first = next((r for r, (x, y) in enumerate(zip(per_round, other))
                  if x != y), min(len(per_round), len(other)))
    return first, fields


def serve_phase(specs):
    """Drain the queue twice (cold, then warm); returns the cold results
    keyed by trial and both host wall times."""
    from repro.experiments.scheduler import serve
    walls, runs = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        runs.append(serve(specs, max_lanes=N_LANES, pack="batched"))
        walls.append(time.perf_counter() - t0)
    for label, res in zip(("cold", "warm"), runs):
        check_results(specs, res, f"serve/{label}")
    cold = {r.spec.key(): r for r in runs[0]}
    warm = {r.spec.key(): r for r in runs[1]}
    rounds = sum(r.rounds for r in runs[0])
    engines = sorted({r.engine for r in runs[0]})
    print(f"serve: retired {len(cold)}/{len(specs)} trials, {rounds} rounds, "
          f"{N_LANES} lanes, engines {engines}", flush=True)
    print(f"serve: drain host wall time cold={walls[0]:.3f}s "
          f"warm={walls[1]:.3f}s", flush=True)
    repeat = [k for k in cold if divergence(cold[k], warm[k])]
    if repeat:
        fail(f"serve: warm drain differs from cold drain for {repeat}")
    return cold, walls


def reference_phase(specs, served) -> list:
    """Standalone runs of the same specs; returns one line per mismatch."""
    from repro.experiments import run_trial
    t0 = time.perf_counter()
    refs = [run_trial(s) for s in specs]
    wall = time.perf_counter() - t0
    bad = []
    for ref in refs:
        d = divergence(ref, served[ref.spec.key()])
        if d is not None:
            bad.append(f"{ref.spec.key()}: first divergent round {d[0]}, "
                       f"fields {d[1]}")
    print(f"reference: {len(refs)} standalone runs in {wall:.3f}s host wall "
          f"time; {len(refs) - len(bad)} bit-identical to the served "
          f"results, {len(bad)} differ", flush=True)
    for line in bad:
        print(f"reference: {line}", flush=True)
    return bad


def kernel_phase(specs) -> list:
    """``ops.fed_reduce`` at the served shape against ``fed_reduce_ref``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.experiments.runner import _model_for
    from repro.federated.aggregation import _flatten
    from repro.kernels import ops, ref
    from repro.runtime.batched import _pow2

    sync = [s for s in specs if s.mode == "sync" and s.aggregator == "fedavg"]
    t, m = _pow2(len(sync)), _pow2(sum(s.m0 for s in sync))
    flat, meta = _flatten(_model_for(specs[0]).init(jax.random.PRNGKey(0)))
    n, leaf_sizes = flat.shape[0], tuple(meta[2])
    rng = np.random.default_rng(0)
    rows = flat + jnp.asarray(0.05 * rng.standard_normal((m, n), np.float32))
    w = jnp.asarray(rng.integers(1, 317, m).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(0, t, m)).astype(np.int32))
    qref = jnp.broadcast_to(flat, (t, n))
    enabled = jnp.ones(m, bool)
    ref_jit = jax.jit(ref.fed_reduce_ref, static_argnames=(
        "num_segments", "normalize", "leaf_sizes"))
    bad = []
    for quant in (False, True):
        qkw = (dict(leaf_sizes=leaf_sizes, quant_ref=qref,
                    quant_enabled=enabled) if quant else {})

        def kern(w, rows, seg, qkw=qkw):
            return ops.fed_reduce(w, rows, seg, t, normalize=True, **qkw)

        compiled = jax.jit(kern).lower(w, rows, seg).compile()
        has_kernel = "tpu_custom_call" in compiled.as_text()
        got = compiled(w, rows, seg)
        want = ref_jit(w, rows, seg, t, normalize=True, **qkw)
        diff = float(jnp.max(jnp.abs(got - want)))
        bitwise = bool(np.array_equal(np.asarray(got).view(np.uint32),
                                      np.asarray(want).view(np.uint32)))
        name = "int8" if quant else "plain"
        print(f"kernel: fed_reduce {name} M={m} T={t} N={n}: "
              f"tpu_custom_call={has_kernel} max_abs_diff={diff!r} "
              f"bitwise_equal={bitwise}", flush=True)
        if not has_kernel:
            bad.append(f"{name}: no tpu_custom_call in the compiled program")
        if got.shape != (t, n) or not bool(jnp.all(jnp.isfinite(got))):
            bad.append(f"{name}: output shape {got.shape} or non-finite")
        if not bitwise:
            bad.append(f"{name}: kernel differs from fed_reduce_ref "
                       f"(max abs diff {diff!r})")
    return bad


def four_chip_phase(specs) -> list:
    """The 15 sync FedAvg trials through the sharded pack and the batched
    pack; returns one line per mismatch."""
    import numpy as np

    from repro.experiments import run_vectorized
    out = {}
    for pack in ("sharded", "batched"):
        t0 = time.perf_counter()
        out[pack] = run_vectorized(specs, pack=pack)
        print(f"four-chips: {pack} pack, {len(specs)} trials in "
              f"{time.perf_counter() - t0:.3f}s host wall time", flush=True)
        check_results(specs, out[pack], pack)
    engines = sorted({r.engine for r in out["sharded"]})
    print(f"four-chips: sharded engines {engines}", flush=True)
    bad = []
    if engines != ["vectorized/sharded"]:
        bad.append(f"sharded pack ran as {engines}")
    n_bitwise, max_acc = 0, 0.0
    for b, s in zip(out["batched"], out["sharded"]):
        if divergence(b, s) is None:
            n_bitwise += 1
        acc = np.abs(np.subtract(b.history_acc, s.history_acc))
        max_acc = max(max_acc, float(acc.max()))
        if (b.history_m != s.history_m or b.history_e != s.history_e
                or not np.allclose(b.history_acc, s.history_acc, rtol=0,
                                   atol=1e-3)
                or not np.allclose(b.cost, s.cost, rtol=1e-6, atol=0)):
            bad.append(f"{s.spec.key()}: sharded differs from batched")
    print(f"four-chips: {n_bitwise}/{len(specs)} trials bit-identical, "
          f"max accuracy difference {max_acc!r}, "
          f"{len(specs) - len(bad)} within the sharded-pack tolerance",
          flush=True)
    return bad


class CompileCounter:
    """Counts compiles and persistent-cache traffic via jax.monitoring."""

    def __init__(self):
        import jax
        self.events: dict = {}
        self.compile_s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.events[name] = self.events.get(name, 0) + 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def report(self, cache_dir: str):
        ev = "/jax/compilation_cache/"
        print(f"compile: {self.compiles} XLA compilations (cache hits "
              f"included), {self.compile_s:.3f}s; persistent cache "
              f"{cache_dir}: "
              f"{self.events.get(ev + 'cache_hits', 0)} hits, "
              f"{self.events.get(ev + 'cache_misses', 0)} writes, "
              f"{self.events.get(ev + 'compile_requests_use_cache', 0)} "
              "requests", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded pack over 4 chips and its "
                         "batched comparison")
    args = ap.parse_args(argv)

    dev = check_device(4 if args.four_chips else None)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro next to {Path(__file__).name}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counter = CompileCounter()

    specs = served_specs()
    if args.four_chips:
        bad = four_chip_phase(specs[:15])
    else:
        served, _ = serve_phase(specs)
        bad = reference_phase(specs, served)
        bad += kernel_phase(specs)
    counter.report(cache_dir)
    if bad:
        fail("; ".join(bad))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
