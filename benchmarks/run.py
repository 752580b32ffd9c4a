"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Default scale is REDUCED so the
suite completes on one CPU core; ``--full`` uses paper-scale datasets.

  table2/fig5  model complexity           (paper Table 2 / Fig. 5)
  fig4/table3  M x E measurement sweep    (paper Fig. 4 / Table 3)
  table4       FedTune x 15 preferences   (paper Table 4)
  table5       FedTune x datasets         (paper Table 5)
  table6       FedTune x aggregators      (paper Table 6)
  fig8/fig9    penalty mechanism          (paper Fig. 8 / 9)
  kernels      kernel micro-benchmarks (incl. fused fed_reduce BENCH json)
  roofline     dry-run roofline table     (EXPERIMENTS.md source)
  runtime      heterogeneous runtime: batched cohorts + mode sweep
  sharded_cohort  client-exec backends (sequential|batched|sharded) at
                  M in {16, 64, 256} over the host-local device mesh
  sweep_engine vectorized T-trials-at-once vs T sequential FLServer runs
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated benchmark keys")
    args = ap.parse_args()

    from benchmarks import (async_runtime, beyond_paper,
                            fedtune_aggregators, fedtune_datasets,
                            fedtune_preferences, kernel_bench,
                            measurement_sweep, model_complexity,
                            penalty_study, roofline_report, sharded_cohort,
                            sweep_engine)
    from benchmarks.common import BenchSettings, emit
    from repro.compile_cache import enable_compile_cache

    settings = BenchSettings(full=args.full, seeds=args.seeds)
    benches = {
        "complexity": lambda: model_complexity.main(settings),
        "sweep": lambda: measurement_sweep.main(settings),
        "preferences": lambda: fedtune_preferences.main(settings),
        "datasets": lambda: fedtune_datasets.main(settings),
        "aggregators": lambda: fedtune_aggregators.main(settings),
        "penalty": lambda: penalty_study.main(settings),
        "beyond": lambda: beyond_paper.main(settings),
        "kernels": lambda: kernel_bench.main(settings),
        "roofline": lambda: roofline_report.main(settings),
        "runtime": lambda: async_runtime.main(settings),
        "sharded_cohort": lambda: sharded_cohort.main(settings),
        "sweep_engine": lambda: sweep_engine.main(settings),
    }
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()

    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    failed = []
    for key, fn in benches.items():
        if only and key not in only:
            continue
        t = time.perf_counter()
        try:
            fn()
            emit(f"section/{key}", (time.perf_counter() - t) * 1e6, "ok")
        except Exception as e:  # keep the suite running, fail at the end
            traceback.print_exc()
            failed.append(key)
            emit(f"section/{key}", (time.perf_counter() - t) * 1e6,
                 f"ERROR:{type(e).__name__}:{str(e)[:120]}")
    emit("total", (time.perf_counter() - t0) * 1e6, "")
    if failed:
        sys.exit(f"benchmarks: {len(failed)} section(s) failed: "
                 + ", ".join(failed))


if __name__ == "__main__":
    main()
