"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --seconds 20 --control-seeds 3 --out <file.json>

For every seed, one whole run of the cell (``harness.run``, untraced):
the numbers of the trials its window retired are the program's readings,
the lower end of each limit. For the first ``--control-seeds`` seeds the
same sampled specs are also run by the control, the reference one
precision step down (bfloat16 model, float32 accounting), in the
program's place and held against the reference as the program is: the
upper end. Needs the chip, as ``run.py`` does.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import harness
    config = harness.load_cell(args.workload)["config"]
    out = {"workload": args.workload, "seconds": args.seconds,
           "program": [], "control": []}

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        def on_checked(picked, per_trial, seed=seed, i=i):
            out["program"].append({"seed": seed, "per_trial": per_trial})
            if i < args.control_seeds:
                out["control"].append(
                    {"seed": seed, "per_trial": control(picked, config)})

        t0 = time.perf_counter()
        res = harness.run(args.workload, seed, args.seconds, False,
                          t_start_process=t0, on_checked=on_checked,
                          log=lambda s: print(s, flush=True))
        out["program"][-1].update(attempted=res["attempted"],
                                  correct=res["correct"],
                                  metrics=res["metrics"])
        print(f"seed {seed}: {json.dumps(out['program'][-1])}", flush=True)
        if i < args.control_seeds:
            print(f"control seed {seed}: {json.dumps(out['control'][-1])}",
                  flush=True)
        out["seconds_total"] = time.perf_counter() - T_START
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


def control(picked: list, config: dict) -> list:
    """The control's numbers on the sampled specs: the bfloat16 reference
    serves each trial, and the float32 reference checks it."""
    import jax.numpy as jnp

    from compare import trial_numbers
    from reference import run_trial
    per = []
    for spec, _ in picked:
        ctl = run_trial(spec, config, dtype=jnp.bfloat16)
        ref = run_trial(spec, config, forced_acc=ctl["history_acc"],
                        served=ctl["models"])
        per.append(trial_numbers(ctl, ref))
    return per


if __name__ == "__main__":
    sys.exit(main())
