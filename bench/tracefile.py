"""From a profiler trace to device numbers.

``read_events`` flattens an ``.xplane.pb`` into ``(plane, line, name,
start_ns, end_ns)`` tuples; ``reduce_trace`` works on such tuples alone, so
it can be checked against a recorded excerpt without a chip.

- The window is the host event the harness opens around its measured loop.
- Busy time is the union of the intervals of the device's operations (the
  ``XLA Ops`` line) inside the window, averaged over the chips.
- An idle gap is a stretch of the window in which the first chip runs no
  operation. It is named after the innermost host span open at its middle,
  among the spans the program recorded.
- A module's device time is the summed length of its events on the
  ``XLA Modules`` line inside the window, over every chip.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path) -> list:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.end_ns)))
    return out


def union(intervals) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction; keep the
    instruction's name (``fusion.12``, ``copy-done``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce_trace(events, span_names=(), top: int = 10) -> dict:
    wins = [(a, b) for p, l, n, a, b in events
            if n == WINDOW and not p.startswith(DEVICE_PREFIX)]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW!r} host event, found "
                         f"{len(wins)}")
    w0, w1 = wins[0]
    ops, mods = defaultdict(list), defaultdict(list)
    host = []
    for p, l, n, a, b in events:
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        if p.startswith(DEVICE_PREFIX):
            if l == OPS_LINE:
                ops[p].append((a, b, op_name(n)))
            elif l == MODULES_LINE:
                mods[p].append((a, b, module_name(n)))
        elif n in span_names:
            host.append((a, b, n))
    devices = sorted(set(ops) | set(mods))
    if not devices:
        return {"window_s": (w1 - w0) * 1e-9, "n_devices": 0}
    busy = [sum(b - a for a, b in union((a, b) for a, b, _ in ops[d]))
            for d in devices]
    op_time = defaultdict(int)
    for d in devices:
        spans = sorted(mods[d])
        starts = [a for a, _, _ in spans]
        for a, b, n in ops[d]:
            op_time[f"{_module_at(spans, starts, (a + b) // 2)}:{n}"] += b - a
    mod_time = defaultdict(int)
    for d in devices:
        for a, b, n in mods[d]:
            mod_time[n] += b - a
    gaps, t = [], w0
    for a, b in union((a, b) for a, b, _ in ops[devices[0]]):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    n_dev = len(devices)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "n_devices": n_dev,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "module_s": {k: v * 1e-9 for k, v in mod_time.items()},
        "device_ops": [[k, v / n_dev * 1e-9] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_innermost(host, (a + b) // 2), (b - a) * 1e-9]
                      for a, b in gaps[:top]],
    }


def _module_at(spans, starts, t) -> str:
    """The module running at ``t``; a chip runs one module at a time."""
    i = bisect.bisect_right(starts, t) - 1
    return spans[i][2] if i >= 0 and t < spans[i][1] else "?"


def _innermost(host, t) -> str:
    inside = [(b - a, n) for a, b, n in host if a <= t < b]
    return min(inside)[1] if inside else "outside any span"


def self_seconds(spans, name: str, phase: str):
    """Summed self time of the program's spans of one name and phase: each
    span's length less the part of it that nested spans cover. None when
    the window holds no such span."""
    timed = sorted((s.wall_t0, s.wall_t1, id(s)) for s in spans
                   if s.wall_t1 > s.wall_t0)
    starts = [a for a, _, _ in timed]
    mine = [s for s in spans if s.name == name and s.phase == phase]
    if not mine:
        return None
    total = 0.0
    for s in mine:
        lo = bisect.bisect_left(starts, s.wall_t0)
        hi = bisect.bisect_right(starts, s.wall_t1)
        covered, end = 0.0, s.wall_t0
        for a, b, k in timed[lo:hi]:
            if k == id(s) or b > s.wall_t1:
                continue
            a = max(a, end)
            if b > a:
                covered += b - a
                end = b
        total += s.wall_t1 - s.wall_t0 - covered
    return total
