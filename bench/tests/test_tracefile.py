"""The trace reduction on events whose answers are counted by hand, and on
an excerpt of a trace recorded on a TPU v5e (three scheduler steps of the
``emnist.sync_grid`` cell)."""
import json
from pathlib import Path

import pytest

import tracefile
from tracefile import reduce_trace, self_seconds, union

HOST, DEV0, DEV1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
OPS, MODS = tracefile.OPS_LINE, tracefile.MODULES_LINE
EXCERPT = Path(__file__).resolve().parent / "data" / "tpu_trace_excerpt.json"

EVENTS = [
    (HOST, "main", "bench.window", 0, 1000),
    (HOST, "main", "PACK", 0, 300),
    (HOST, "main", "TRAIN", 300, 900),
    (HOST, "main", "stack", 350, 380),        # not a span of the program
    (DEV0, OPS, "copy", -50, 20),             # clipped to the window
    (DEV0, OPS, "fusion.1", 100, 200),
    (DEV0, OPS, "fusion.2", 150, 250),        # overlaps fusion.1
    (DEV0, OPS, "custom-call.3", 400, 700),
    (DEV0, MODS, "jit_x(3)", -50, 20),
    (DEV0, MODS, "jit_run(1)", 100, 250),
    (DEV0, MODS, "jit_fed_reduce(2)", 400, 700),
    (DEV1, OPS, "fusion.1", 0, 500),
    (DEV1, MODS, "jit_run(1)", 0, 500),
    (DEV0, OPS, "late", 1200, 1300),          # after the window
]


def test_hand_counted_trace():
    r = reduce_trace(EVENTS, span_names={"PACK", "TRAIN"})
    assert r["n_devices"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    # chip 0: 20 + (100..250) + (400..700) = 470 ns; chip 1: 500 ns
    assert r["busy_s"] == pytest.approx(485e-9)
    assert r["module_s"]["jit_fed_reduce"] == pytest.approx(300e-9)
    assert r["module_s"]["jit_run"] == pytest.approx(650e-9)
    # gaps of chip 0: 700..1000, 250..400, 20..100, named at their middles
    assert [g[0] for g in r["idle_gaps"]] == ["TRAIN", "TRAIN", "PACK"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [300e-9, 150e-9, 80e-9])
    ops = dict(r["device_ops"])
    assert ops["jit_fed_reduce:custom-call.3"] == pytest.approx(150e-9)
    assert ops["jit_run:fusion.1"] == pytest.approx((100 + 500) / 2 * 1e-9)


def test_union_and_window():
    assert union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]
    with pytest.raises(ValueError):
        reduce_trace([e for e in EVENTS if e[2] != "bench.window"])


class _Span:
    def __init__(self, name, phase, t0, t1):
        self.name, self.phase, self.wall_t0, self.wall_t1 = \
            name, phase, t0, t1


def test_self_time_leaves_out_nested_spans():
    spans = [_Span("TRAIN", "train", 0.0, 10.0),
             _Span("a", None, 1.0, 3.0), _Span("b", None, 2.0, 4.0),
             _Span("c", None, 8.0, 8.0),           # an instant record
             _Span("TRAIN", "train", 20.0, 21.0),
             _Span("PACK", "train", 30.0, 31.0)]   # another engine's span
    assert self_seconds(spans, "TRAIN", "train") == pytest.approx(8.0)
    assert self_seconds(spans, "EVAL", "eval") is None


def _union_by_sweep(intervals):
    """Busy time by an event sweep, a second way to the same union."""
    edges = sorted([(a, 1) for a, _ in intervals]
                   + [(b, -1) for _, b in intervals])
    busy, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_tpu_excerpt():
    rec = json.loads(EXCERPT.read_text())
    events = [tuple(e) for e in rec["events"]]
    r = reduce_trace(events, span_names=set(rec["span_names"]))
    (w0, w1), = [(a, b) for _, _, n, a, b in events if n == "bench.window"]
    ops = [(max(a, w0), min(b, w1)) for p, l, _, a, b in events
           if p == "/device:TPU:0" and l == OPS and b > w0 and a < w1]
    assert r["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(_union_by_sweep(ops) * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert "jit_fed_reduce" in r["module_s"]
    names = set(rec["span_names"]) | {"outside any span"}
    assert all(g[0] in names for g in r["idle_gaps"])
    for key, want in rec["expected"].items():
        assert r[key] == pytest.approx(want, rel=1e-12), key
