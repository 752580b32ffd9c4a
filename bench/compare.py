"""The numbers that decide ``correct``: a served trial's record and its
global model after every round, held against the plain reference's run of
the same spec, each reference round started from the served model of the
round before. A cell compares the numbers its limits file names;
``trial_numbers`` reads them for one trial and ``worst`` pools the trials
of a run.

- ``param_gap_median``: a round's gap is the global model's worst leaf
  after that round, as the norm of the difference over the reference's
  norm of that leaf or of the median leaf, whichever is larger; the number
  is the median of that gap over every round of every trial checked. It
  carries the cohort training step and the server reduction. The median,
  not the widest round: a round whose clients train for hundreds of steps
  at the backend's default matmul precision drifts chaotically on both
  sides, so the widest round of a sound run reaches the bfloat16 control's
  (``param_gap_max``, logged beside it, is not compared).
- ``acc_gap``: the largest gap, over rounds, between the accuracy the
  served run reported and the reference's accuracy of the same served
  model on the same test points. It carries the stacked evaluation. A count
  of correct predictions, compared exactly.
- ``cost_gap``: the largest relative gap among the four total overheads
  (CompT, TransT, CompL, TransL).
- ``hp_mismatch``: rounds whose (M, E) differ, plus a differing round
  count, final (M, E) or target flag. An exact comparison: its limit is 0.
"""

from __future__ import annotations

import numpy as np

NAMES = ("param_gap_median", "param_gap_max", "acc_gap", "cost_gap",
         "hp_mismatch")


def leaf_gap(got: list, ref: list) -> float:
    norms = [float(np.linalg.norm(r)) for r in ref]
    floor = float(np.median(norms))
    return max(float(np.linalg.norm(np.asarray(g, np.float64) - r))
               / max(n, floor) for g, r, n in zip(got, ref, norms))


def trial_numbers(got: dict, ref: dict) -> dict:
    """One trial's numbers; ``round_gaps`` holds each round's model gap."""
    gaps = [leaf_gap(g, r) for g, r in zip(got["models"], ref["models"])]
    acc_gap = max((abs(a - b) for a, b in
                   zip(got["history_acc"], ref["served_acc"])), default=0.0)
    cost_gap = max(abs(a - b) / max(abs(b), 1e-12)
                   for a, b in zip(got["cost"], ref["cost"]))
    hp = sum(1 for a in zip(got["history_m"], got["history_e"],
                            ref["history_m"], ref["history_e"])
             if (a[0], a[1]) != (a[2], a[3]))
    hp += abs(got["rounds"] - ref["rounds"])
    hp += int(got["reached"] != ref["reached"])
    hp += int((got["final_m"], got["final_e"])
              != (ref["final_m"], ref["final_e"]))
    hp += abs(len(got["models"]) - len(ref["models"]))
    return {"round_gaps": gaps, "acc_gap": acc_gap,
            "cost_gap": cost_gap, "hp_mismatch": float(hp)}


def worst(per_trial: list) -> dict:
    """A run's numbers over the trials it checked."""
    gaps = [g for t in per_trial for g in t["round_gaps"]]
    out = {"param_gap_median": float(np.median(gaps)) if gaps else 0.0,
           "param_gap_max": max(gaps, default=0.0)}
    out.update({k: max(t[k] for t in per_trial)
                for k in ("acc_gap", "cost_gap", "hp_mismatch")})
    return out


def judge(values: dict, limits: dict):
    """(every number the limits name within its limit, {name: {"value",
    "limit"}})."""
    check = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    return all(values[k] <= limits[k] for k in limits), check
