#!/usr/bin/env python
"""Summarize a --trace run: phase/occupancy tables from a Chrome trace.

Reads the trace-event JSON written by ``repro.obs.export`` (plus,
optionally, the metrics JSONL written next to it) and prints:

  * schema validation (exit status 2 if the trace violates
    src/repro/obs/trace_schema.json),
  * a wall-clock phase table (span counts and self ms per phase: each
    span's length less the spans nested in it, so no time counts twice),
  * a per-trial-lane virtual-time table: simulated span, busy time
    (round / agg_window spans), occupancy = busy / span,
  * a metrics summary (pack widths, padding waste, staleness, caches)
    when a metrics file is given.

Usage:
  python tools/trace_report.py out.trace.json [--metrics out.metrics.jsonl]
  python tools/trace_report.py out.trace.json --json    # machine-readable

Run by the CI sweep-smoke job against the traced smoke sweep's artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

from repro.obs.export import (VIRTUAL_PID, VIRTUAL_US_PER_S, WALL_PID,
                              read_metrics_jsonl, validate_chrome_trace)
from repro.obs.trace import self_durations

# virtual spans whose union tiles a lane's busy time: sync rounds and
# async/buffered aggregation windows (in-flight spans overlap; excluded)
_BUSY_SPANS = ("round", "agg_window")


def report(trace_path: str,
           metrics_path: Optional[str] = None) -> Dict[str, Any]:
    with open(trace_path, encoding="utf-8") as f:
        trace = json.load(f)
    errors = validate_chrome_trace(trace)
    events = trace.get("traceEvents", [])

    track_names: Dict[tuple, str] = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            track_names[(ev["pid"], ev["tid"])] = ev["args"]["name"]

    phases: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"calls": 0, "self_ms": 0.0})
    wall: List[Dict[str, Any]] = []
    lanes: Dict[int, Dict[str, float]] = defaultdict(
        lambda: {"t0": float("inf"), "t1": 0.0, "busy": 0.0})
    # scheduler admit/retire instants: wall events sharing the trial's tid
    # (the continuous-batching scheduler emits one of each per trial)
    sched: Dict[int, Dict[str, Any]] = defaultdict(dict)
    for ev in events:
        # tolerate malformed events here: they still land in ``errors``
        # via the validator, and main() exits 2 on any violation
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        if ev.get("pid") == WALL_PID and "dur" in ev and "ts" in ev:
            wall.append(ev)
            if ev.get("name") in ("admit", "retire") and "tid" in ev:
                args = ev.get("args") or {}
                sched[ev["tid"]][f"{ev['name']}_ms"] = ev["ts"] / 1e3
                if "lane" in args:
                    sched[ev["tid"]]["pool_lane"] = args["lane"]
        elif (ev.get("pid") == VIRTUAL_PID and "tid" in ev
              and "ts" in ev and "dur" in ev):
            lane = lanes[ev["tid"]]
            lane["t0"] = min(lane["t0"], ev["ts"])
            lane["t1"] = max(lane["t1"], ev["ts"] + ev["dur"])
            if ev.get("name") in _BUSY_SPANS:
                lane["busy"] += ev["dur"]

    # host spans nest by time whatever track they sit on (one host thread)
    own = self_durations([(ev["ts"], ev["ts"] + ev["dur"]) for ev in wall])
    for ev, self_us in zip(wall, own):
        p = phases[ev.get("cat", "span")]
        p["calls"] += 1
        p["self_ms"] += self_us / 1e3

    lane_rows: List[Dict[str, Any]] = []
    for tid in sorted(set(lanes) | set(sched)):
        lane = lanes[tid]
        span_us = lane["t1"] - lane["t0"]
        row = {
            "track": track_names.get((VIRTUAL_PID, tid),
                                     track_names.get((WALL_PID, tid),
                                                     f"tid {tid}")),
            "t_sim_s": lane["t1"] / VIRTUAL_US_PER_S,
            "busy_s": lane["busy"] / VIRTUAL_US_PER_S,
            "occupancy": lane["busy"] / span_us if span_us > 0 else 0.0,
        }
        row.update(sched.get(tid, {}))
        lane_rows.append(row)

    out: Dict[str, Any] = {
        "trace": trace_path,
        "valid": not errors,
        "errors": errors,
        "n_events": len(events),
        "phases": {k: dict(v) for k, v in sorted(phases.items())},
        "lanes": lane_rows,
    }

    if metrics_path:
        rows = read_metrics_jsonl(metrics_path)
        counters = {r["name"]: r["value"] for r in rows    # untagged
                    if r.get("kind") == "counter"
                    and set(r) == {"kind", "name", "value"}}
        gc_rows = [r for r in rows if r.get("kind") == "counter"
                   and r["name"] in ("gc_collections", "gc_s")]
        hists = {r["name"]: r for r in rows if r.get("kind") == "histogram"}
        samples = defaultdict(list)
        for r in rows:
            if r.get("kind") == "sample":
                samples[r["name"]].append(r["value"])
        steps_pad = counters.get("pack_steps_padded", 0.0)
        out["metrics"] = {
            "counters": counters,
            "histograms": hists,
            "gc": {f"{r['name']}.gen{r.get('generation')}": r["value"]
                   for r in gc_rows},
            "mean_lanes_live": (sum(samples["lanes_live"])
                                / len(samples["lanes_live"])
                                if samples["lanes_live"] else 0.0),
            "mean_pack_width": (sum(samples["pack_width"])
                                / len(samples["pack_width"])
                                if samples["pack_width"] else 0.0),
            "padding_waste": (1.0 - counters.get("pack_steps_real", 0.0)
                              / steps_pad if steps_pad else 0.0),
            "mean_pool_occupancy": (sum(samples["pool_occupancy"])
                                    / len(samples["pool_occupancy"])
                                    if samples["pool_occupancy"] else None),
            "mean_queue_depth": (sum(samples["queue_depth"])
                                 / len(samples["queue_depth"])
                                 if samples["queue_depth"] else None),
        }
    return out


def _print_tables(rep: Dict[str, Any]):
    print(f"trace: {rep['trace']}  ({rep['n_events']} events, "
          f"{'valid' if rep['valid'] else 'INVALID'})")
    print("\nwall-clock phases")
    print(f"  {'phase':<10} {'calls':>7} {'self ms':>10}")
    for name, p in rep["phases"].items():
        print(f"  {name:<10} {int(p['calls']):>7} {p['self_ms']:>10.2f}")
    if rep["lanes"]:
        served = any("admit_ms" in lane for lane in rep["lanes"])
        print("\nvirtual-clock lanes")
        if served:
            # scheduler drain view: pool lane + wall admit/retire instants
            print(f"  {'t_sim s':>9} {'busy s':>9} {'occup':>6} "
                  f"{'pool':>4} {'admit ms':>9} {'retire ms':>9}  track")
            for lane in rep["lanes"]:
                pool = lane.get("pool_lane")
                adm, ret = lane.get("admit_ms"), lane.get("retire_ms")
                print(f"  {lane['t_sim_s']:>9.3g} {lane['busy_s']:>9.3g} "
                      f"{lane['occupancy']:>6.1%} "
                      f"{pool if pool is not None else '-':>4} "
                      f"{adm if adm is not None else float('nan'):>9.1f} "
                      f"{ret if ret is not None else float('nan'):>9.1f}  "
                      f"{lane['track']}")
        else:
            print(f"  {'t_sim s':>9} {'busy s':>9} {'occup':>6}  track")
            for lane in rep["lanes"]:
                print(f"  {lane['t_sim_s']:>9.3g} {lane['busy_s']:>9.3g} "
                      f"{lane['occupancy']:>6.1%}  {lane['track']}")
    met = rep.get("metrics")
    if met:
        print("\nmetrics")
        print(f"  mean lanes live : {met['mean_lanes_live']:.2f}")
        print(f"  mean pack width : {met['mean_pack_width']:.2f}")
        print(f"  padding waste   : {met['padding_waste']:.1%}")
        if met.get("mean_pool_occupancy") is not None:
            print(f"  pool occupancy  : {met['mean_pool_occupancy']:.1%}")
        if met.get("mean_queue_depth") is not None:
            print(f"  mean queue depth: {met['mean_queue_depth']:.2f}")
        for name, value in sorted(met["gc"].items()):
            print(f"  {name:<20}: {value:.4g}")
        for name in ("staleness", "store_write_s"):
            h = met["histograms"].get(name)
            if h and h.get("count"):
                print(f"  {name:<15} : n={h['count']} mean={h['mean']:.4g} "
                      f"p90={h['p90']:.4g} max={h['max']:.4g}")
        for name in ("sync_dispatched", "sync_dropouts", "sync_stragglers_cut",
                     "event_dispatched", "event_dropouts",
                     "trials_admitted", "trials_retired",
                     "eval_fn_cache_hits", "eval_fn_cache_misses"):
            if name in met["counters"]:
                print(f"  {name:<20}: {met['counters'][name]:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize a Chrome trace + metrics JSONL emitted by "
                    "repro --trace runs")
    ap.add_argument("trace", help="path to the .trace.json file")
    ap.add_argument("--metrics", default=None,
                    help="path to the companion .metrics.jsonl")
    ap.add_argument("--json", action="store_true",
                    help="print the report as JSON instead of tables")
    args = ap.parse_args(argv)

    rep = report(args.trace, args.metrics)
    if args.json:
        print(json.dumps(rep, indent=2))
    else:
        _print_tables(rep)
    if not rep["valid"]:
        for err in rep["errors"][:20]:
            print(f"SCHEMA VIOLATION: {err}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
