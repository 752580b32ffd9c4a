"""One run of one cell: build the served path, warm it up on the cell's own
traffic, measure a window, check what the window retired against the plain
reference, and return the result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``models/<kind>.py`` for the configuration's model
(``kinds.py``), ``traffic/<traffic>.json``, ``limits/<cell>.json`` and
``metrics/<metric>.py`` for each per-layer metric that lists the cell.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import kinds

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A run that cannot produce a result (no chip, no program, bad cell)."""


def load_cell(workload: str, root: Path = ROOT) -> dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; cells: "
                         + ", ".join(cells))
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in spec["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return {
        "cell": cell,
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(
            (BENCH / "limits" / f"{workload}.json").read_text()),
        "per_layer": per_layer,
        "end_to_end": end_to_end,
    }


def check_device(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and dev["platform"] != "tpu":
        raise BenchError(f"JAX found no TPU (platform {dev['platform']!r}); "
                         "the benchmark runs on the chip only")
    if require_tpu and dev["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{dev['count']}")
    return dev


def peak_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["chips"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class CompileCounter:
    """Backend compiles seen through ``jax.monitoring`` (persistent-cache
    hits included)."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs


class Server:
    """The cell's traffic driven through the program's ``TrialScheduler``:
    a closed-loop backlog topped up with whole grids, one scheduler step
    (admit, then advance every live trial one round) per ``step``. It keeps
    each trial's global model after every round, as device arrays, for the
    check."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        from repro.experiments.scheduler import TrialQueue, TrialScheduler

        from traffic import GridStream
        kinds.of(config["model"]).place(config)
        self.traffic = traffic
        self.stream = GridStream(traffic, config, seed)
        self.sched = TrialScheduler(TrialQueue(), max_lanes=traffic["lanes"],
                                    pack=traffic["pack"],
                                    on_result=self._on_result)
        self.submitted = {}      # key -> (submit time, spec dict)
        self.live = {}           # key -> the program's live trial
        self.retired = []        # (key, retire time, TrialResult)
        self.done = set()        # keys retired
        self.models = {}         # key -> global model after each round

    def _note_round(self, key, tr):
        models = self.models.setdefault(key, [])
        if len(tr.history) == len(models) + 1:
            models.append(tr.params)
        elif len(tr.history) != len(models):
            raise BenchError(f"trial {key} moved from round {len(models)} "
                             f"to {len(tr.history)} in one step")

    def _on_result(self, res):
        key = res.spec.key()
        self._note_round(key, self.live.pop(key))
        self.done.add(key)
        self.retired.append((key, time.perf_counter(), res))

    def step(self):
        from repro.experiments.grid import spec_from_dict

        from traffic import TrafficExhausted
        q = self.sched.queue
        while len(q) < self.traffic["backlog_min"]:
            try:
                grid = self.stream.next_grid()
            except TrafficExhausted as e:
                raise BenchError(str(e)) from None
            for d in grid:
                spec = spec_from_dict(d)
                if q.submit(spec):
                    self.submitted[spec.key()] = (time.perf_counter(), d)
        self.sched.admit_pending()
        for tr in self.sched._sync_live:
            self.live.setdefault(tr.spec.key(), tr)
        self.sched.step()
        for key, tr in self.live.items():
            self._note_round(key, tr)

    def forget_retired(self):
        """Drop the kept models of every trial retired so far."""
        self.models = {k: v for k, v in self.models.items()
                       if k in self.live}

    def rounds_done(self) -> int:
        live = sum(len(tr.history) for tr in self.live.values())
        return live + sum(r.rounds for _, _, r in self.retired)

    def examples_trained(self) -> float:
        """E x n summed over every client update charged so far, read from
        the trials' CompL counters (CompL = C1 x E x n)."""
        live = sum(tr.srv.cost_model.total.comp_l
                   / tr.srv.cost_model.train_flops_per_example
                   for tr in self.live.values())
        c1 = next(iter(self.live.values())).srv.cost_model \
            .train_flops_per_example if self.live else None
        done = sum(r.cost[2] for _, _, r in self.retired)
        return live + (done / c1 if c1 else 0.0)


def _record(res, models, leaves) -> dict:
    return {"history_m": list(res.history_m),
            "history_e": [float(e) for e in res.history_e],
            "history_acc": list(res.history_acc), "cost": list(res.cost),
            "rounds": res.rounds, "reached": res.reached,
            "final_m": res.final_m, "final_e": float(res.final_e),
            "models": [leaves(p) for p in models]}


def _finite(res) -> bool:
    vals = list(res.history_acc) + list(res.cost) + [res.final_accuracy]
    return res.rounds > 0 and all(math.isfinite(v) for v in vals)


def _load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def sample(retired: list, k: int, seed: int) -> list:
    """``k`` of the retired trials drawn from the seed, always with the
    longest (most rounds, then most client updates) among them."""
    if not retired:
        return []
    order = sorted(range(len(retired)), key=lambda i: (
        -retired[i][2].rounds, -sum(retired[i][2].history_m), retired[i][0]))
    rest = order[1:]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [retired[order[0]]] + [retired[rest[int(i)]] for i in pick]


def warm_up(server: Server, warmup_retired: int) -> None:
    """Serve until the first ``warmup_retired`` trials have retired, then on
    until every trial submitted before that point has retired too, so that
    each latency the window reads starts after the warm-up."""
    while len(server.retired) < warmup_retired:
        server.step()
    early = set(server.submitted)
    while not early <= server.done:
        server.step()
    server.forget_retired()


def rehearse(traffic: dict, config: dict, seed: int) -> int:
    """Serve the run's own sequence of scheduler steps, the warm-up's and
    ``rehearse_steps`` more, in a server that is then thrown away. Which
    trials share a step, and so every shape the program compiles, follows
    from the seed and not from the clock, so the window that follows
    replays steps whose programs are all compiled: nothing compiles in it
    while it lasts no more than ``rehearse_steps`` steps. Returns the steps
    served."""
    server = Server(traffic, config, seed)
    warm_up(server, traffic["warmup_retired"])
    for _ in range(traffic["rehearse_steps"]):
        server.step()
    return server.sched.stats.steps


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start_process: float, require_tpu: bool = True,
        cell_override: dict | None = None, log=print,
        on_checked=None) -> dict:
    """One run; returns the result line as a dict. ``cell_override`` takes
    the place of what ``load_cell`` reads (the self-tests' small cells).
    ``on_checked(picked, per_trial)``, when given, sees the sampled
    ``(spec, record)`` pairs and their numbers (``calibrate.py``)."""
    c = cell_override or load_cell(workload)
    cell, config, traffic = c["cell"], c["config"], c["traffic"]
    dev = check_device(cell["chips"], require_tpu)
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"no program: {ROOT / 'src' / 'repro'} is missing")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax

    from repro import obs
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    peak = peak_for(dev["kind"]) if require_tpu else None

    rehearsed = rehearse(traffic, config, seed)
    gc.collect()
    server = Server(traffic, config, seed)
    warm_up(server, traffic["warmup_retired"])
    log(f"setup: rehearsed {rehearsed} steps; warm-up retired "
        f"{len(server.retired)} trials in {server.sched.stats.steps} steps; "
        f"{counter.compiles} compiles ({counter.compile_s:.3f} s); "
        f"compile cache {cache_dir}")

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        obs.enable(jax_annotations=True)
        obs.registry.reset()
    compiles0 = counter.compiles
    stats = server.sched.stats
    steps0, occ0 = stats.steps, stats.occupancy_sum
    n_retired0 = len(server.retired)
    rounds0, examples0 = server.rounds_done(), server.examples_trained()
    t0 = time.perf_counter()
    setup_s = t0 - t_start_process
    with (jax.profiler.TraceAnnotation("bench.window") if trace
          else contextlib.nullcontext()):
        while time.perf_counter() - t0 < seconds:
            server.step()
    t1 = time.perf_counter()
    window_s = t1 - t0
    rounds = server.rounds_done() - rounds0
    examples = server.examples_trained() - examples0
    compiles = counter.compiles - compiles0
    steps = stats.steps - steps0
    occupancy = (stats.occupancy_sum - occ0) / steps if steps else 0.0
    spans = list(obs.tracer.spans) if trace else []
    if trace:
        obs.disable()
        jax.profiler.stop_trace()
    done = server.retired[n_retired0:]
    if not done:
        raise BenchError(f"no trial retired in the {window_s:.1f} s window")
    lat = [t - server.submitted[k][0] for k, t, _ in done]
    failed = sum(1 for _, _, r in done if not _finite(r))
    engines = sorted({r.engine for _, _, r in done})
    mem = [d.memory_stats() or {} for d in jax.devices()[:cell["chips"]]]
    mem_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    log(f"window: {window_s:.3f} s, {steps} scheduler steps, {rounds} rounds, "
        f"{len(done)} trials retired, {failed} failed, engines {engines}")
    log(f"window: {compiles} compiles inside the window")
    log(f"device: peak HBM {mem_peak} bytes on the fullest chip")

    leaves = kinds.of(config["model"]).leaves
    picked = [(server.submitted[k][1], _record(r, server.models[k], leaves))
              for k, _, r in sample(done, traffic["check_sample"], seed)]
    del server
    from reference import run_trial
    from compare import judge, trial_numbers, worst
    t_ref = time.perf_counter()
    per_trial = [trial_numbers(got, run_trial(
        spec, config, forced_acc=got["history_acc"], served=got["models"]))
        for spec, got in picked]
    log(f"check: reference ran {len(picked)} trials in "
        f"{time.perf_counter() - t_ref:.3f} s")
    if on_checked is not None:
        on_checked(picked, per_trial)
    values = worst(per_trial)
    log("check: every number " + json.dumps(values))
    ok, check = judge(values, c["limits"])
    correct = bool(ok and not failed)

    device = dict(dev, memory_peak_bytes=int(mem_peak))
    out = {"correct": correct, "attempted": len(done), "failed": failed,
           "metrics": {}, "device": device}
    if not trace:
        values_e2e = {
            "rounds_per_s": (rounds / window_s, "rounds/s"),
            "trial_result_p90_s": (
                statistics.quantiles(lat, n=10, method="inclusive")[-1]
                if len(lat) >= 2 else lat[0], "s"),
            "setup_s": (setup_s, "s"),
        }
        for m in c["end_to_end"]:
            v, unit = values_e2e[m["name"]]
            out["metrics"][m["name"]] = {"value": v, "unit": unit}
    else:
        import tracefile
        events = tracefile.read_events(tracefile.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = tracefile.reduce_trace(
            events, span_names={s.name for s in spans})
        device["busy_s"] = summary.get("busy_s", 0.0)
        device["window_s"] = summary["window_s"]
        ctx = {"config": config, "traffic": traffic, "peak": peak,
               "chips": cell["chips"], "window_s": window_s, "steps": steps,
               "occupancy": occupancy, "spans": spans,
               "compiles_in_window": compiles, "examples": examples,
               "trace": summary}
        for m in c["per_layer"]:
            v = _load_metric(m["name"])(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": summary.get("device_ops", []),
                            "idle_gaps": summary.get("idle_gaps", [])}
    out["check"] = check
    return out
