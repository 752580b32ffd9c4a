"""A cell small enough for the CPU: the reduced EMNIST-like federation (128
training clients, 16 classes) under the configuration's model, 4 lanes,
grids of 3 trials of 3 sync rounds. Used by the self-tests, which run the
harness with its chip check off."""

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

PREFERENCES = [[1.0, 0.0, 0.0, 0.0], [0.0, 0.0, 0.5, 0.5],
               [0.25, 0.25, 0.25, 0.25]]


def tiny_cell(limits=None) -> dict:
    config = json.loads((BENCH / "configs" / "emnist-mlp200.json").read_text())
    config["reduced_dataset"] = True
    config["data"].update(n_classes=16, n_train_clients=128,
                          n_test_clients=32)
    config["model"]["n_classes"] = 16
    mix = json.loads((BENCH / "traffic" / "sync_grid.json").read_text())
    mix.update(preferences=PREFERENCES, lanes=4, backlog_min=3,
               warmup_retired=3, rehearse_steps=2, check_sample=3,
               alternate=[{"mode": "sync", "rounds": 3}])
    if limits is None:
        limits = json.loads(
            (BENCH / "limits" / "emnist.sync_grid.json").read_text())
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return {"cell": {"name": "tiny", "config": "tiny", "traffic": "tiny",
                     "chips": 1},
            "config": config, "traffic": mix, "limits": limits,
            "per_layer": [],
            "end_to_end": [m for m in bench["end_to_end"]
                           if "workloads" not in m]}
