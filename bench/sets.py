"""Sets of whole runs of one cell, each a process of its own, and their
spreads: what the bounds in ``BENCHMARK.json`` are set from.

    python3 bench/sets.py --workload <cell> --seeds 1,2,3 --sets 2 \
        --seconds 51 [--trace 0] --out <file.jsonl>

Runs ``bench/run.py`` once per seed in each set, one after another (this
process never touches JAX, so each run has the chip to itself), writes every
result line, with the seed, the set and the end of standard error, to
``--out`` as it comes, and prints for each set and metric the median and
the spread: the distance between the first and third quartiles, as
``statistics.quantiles(values, n=4)`` gives them, over the median.
"""

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def spread(values: list) -> tuple:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, (q3 - q1) / med if med else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    with open(args.out, "a") as f:
        for k in range(args.sets):
            for seed in seeds:
                t0 = time.perf_counter()
                p = subprocess.run(
                    [sys.executable, str(RUN), "--workload", args.workload,
                     "--seed", str(seed), "--seconds", str(args.seconds),
                     "--trace", str(args.trace)],
                    capture_output=True, text=True)
                lines = p.stdout.strip().splitlines()
                try:
                    result = json.loads(lines[-1])
                except (IndexError, ValueError):
                    result = None
                row = {"set": k, "seed": seed, "rc": p.returncode,
                       "wall_s": time.perf_counter() - t0, "result": result,
                       "stdout_tail": "\n".join(lines[-8:-1]),
                       "stderr_tail": p.stderr[-1500:]}
                rows.append(row)
                f.write(json.dumps(row) + "\n")
                f.flush()
                short = {n: m["value"] for n, m in
                         (result or {}).get("metrics", {}).items()}
                print(f"set {k} seed {seed} rc {p.returncode} "
                      f"correct {(result or {}).get('correct')} "
                      f"attempted {(result or {}).get('attempted')} "
                      f"{json.dumps(short)} "
                      f"check {json.dumps((result or {}).get('check'))}",
                      flush=True)
    for k in range(args.sets):
        got = [r["result"] for r in rows if r["set"] == k and r["result"]]
        names = sorted({n for g in got for n in g["metrics"]})
        for n in names:
            vals = [g["metrics"][n]["value"] for g in got
                    if n in g["metrics"]]
            if len(vals) >= 2:
                med, sp = spread(vals)
                print(f"set {k} {n}: median {med!r} spread {sp!r} "
                      f"over {len(vals)} runs", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
