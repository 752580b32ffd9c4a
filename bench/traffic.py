"""The one generator of trial traffic: reads a traffic file and a
configuration and yields grids of trial specs, as plain dicts.

A grid is one user's tuning sweep over one federation: one spec per
preference vector, sharing a federation seed. Grid ``g`` takes the fields
of ``alternate[g % len(alternate)]`` on top of ``grid``, so a mix can
alternate runtime modes.

The federations are fixed by the traffic file, not by ``--seed``: grid
``g`` is served over the ``g``-th of ``federations["count"]`` distinct
federation seeds drawn from ``federations["seed"]``. The synthetic
federation is the data set, and how much a trial trains depends on it, so
every run serves the same grids in the same order and the same amount of
work; ``--seed`` orders the preference vectors within each grid (which
trial takes which lane, and when). No two grids share a trial key.

Fields of a spec that name the model come from the configuration: its
``model["spec"]``, when there is one, is merged into every spec.
"""

from __future__ import annotations

import numpy as np

SEED_SPACE = 2 ** 31 - 1


class TrafficExhausted(Exception):
    """Every grid of the traffic file has been submitted."""


def federation_seeds(spec: dict) -> list:
    rng = np.random.default_rng(spec["seed"])
    out, seen = [], set()
    while len(out) < spec["count"]:
        s = int(rng.integers(0, SEED_SPACE))
        if s not in seen:
            seen.add(s)
            out.append(s)
    return out


class GridStream:
    def __init__(self, traffic: dict, config: dict, seed: int):
        self.traffic = traffic
        self.config = config
        self.rng = np.random.default_rng(seed)
        self.feds = federation_seeds(traffic["federations"])
        self.n_grids = 0

    def next_grid(self) -> list:
        t, c = self.traffic, self.config
        if self.n_grids >= len(self.feds):
            raise TrafficExhausted(
                f"all {len(self.feds)} grids of the traffic file submitted")
        fed_seed = self.feds[self.n_grids]
        alt = t["alternate"][self.n_grids % len(t["alternate"])]
        self.n_grids += 1
        base = {
            "dataset": c["dataset"], "reduced": c["reduced_dataset"],
            "batch_size": c["train"]["batch_size"], "lr": c["train"]["lr"],
            "eval_points": c["train"]["eval_points"],
            "target_accuracy": c["train"]["target_accuracy"],
            **c["model"].get("spec", {}),
            "seed": fed_seed, **t["grid"], **alt,
        }
        order = self.rng.permutation(len(t["preferences"]))
        return [dict(base, preference=list(t["preferences"][i]))
                for i in order]
