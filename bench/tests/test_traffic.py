"""The generator serves every seed the same grids, in another order."""

import json
from pathlib import Path

import pytest

from traffic import GridStream, TrafficExhausted

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "emnist-mlp200.json").read_text())
MIX = json.loads((BENCH / "traffic" / "sync_grid.json").read_text())


def grids(seed: int, n: int, mix=MIX) -> list:
    stream = GridStream(mix, CONFIG, seed)
    return [stream.next_grid() for _ in range(n)]


def test_seeds_share_the_work_and_differ_in_order():
    a, b = grids(7, 4), grids(2 ** 31 + 11, 4)
    for ga, gb in zip(a, b):
        key = lambda d: json.dumps(d, sort_keys=True)  # noqa: E731
        assert sorted(map(key, ga)) == sorted(map(key, gb))
        assert len({d["seed"] for d in ga}) == 1
    assert [d["preference"] for g in a for d in g] != \
        [d["preference"] for g in b for d in g]
    assert len({g[0]["seed"] for g in a}) == 4
    assert grids(7, 4) == a


def test_exhausted_traffic_raises():
    mix = dict(MIX, federations=dict(MIX["federations"], count=2))
    stream = GridStream(mix, CONFIG, 3)
    stream.next_grid()
    stream.next_grid()
    with pytest.raises(TrafficExhausted):
        stream.next_grid()
