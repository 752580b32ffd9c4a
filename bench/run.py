"""Benchmark of the served FedTune trial path on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``src/``. One process: it checks
that JAX sees a TPU with as many chips as the cell asks for (and exits with
a non-zero code, printing no result, otherwise), builds the served path,
warms it up on the cell's own traffic, measures ``--seconds`` of serving,
checks the trials the window retired against the plain reference, and
prints one JSON line last. With ``--trace 1`` the window is profiled and
the line carries the per-layer metrics instead of the end-to-end ones.
The numbers compared for ``correct`` end standard error, each beside its
limit.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import BenchError, run
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start_process=T_START,
                  log=lambda s: print(s, flush=True))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
