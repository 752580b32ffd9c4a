"""Multi-trial sweep execution: T independent FL trainings as ONE workload.

The unit of progress for FL hyper-parameter research is the *trial* — one
(preference, aggregator, dataset, seed, M0/E0) cell of the paper's tables —
and trials are embarrassingly parallel: they share no state, only hardware.
The sequential engine here runs them one ``FLServer.run()`` at a time; the
vectorized engine adds a **trials axis** on top of the cohort machinery from
PR 1-2 and runs all of them per virtual round:

  1. PLAN   — every live trial plans its sync round through the engine's own
              ``plan_sync_round`` (selection, availability, deadline cut),
              consuming its private server/system rngs exactly as a
              standalone run would.
  2. PACK   — every trial's included clients are materialized
              (``materialize_streams``, same rng contract as the
              sequential/batched paths) and packed into one flat cohort:
              grouped by model, size-bucketed by pow2 step count
              (``bucket_by_steps``), the client axis padded to a pow2 so the
              set of compiled (T, M) shapes stays small as FedTune moves
              each trial's M.  One ``cohort_scan`` per bucket trains clients
              of MANY trials side by side — each vmap lane carries its own
              trial's global params (``global_in_axis=0``).
  3. REDUCE — aggregation.  Every FedAvg trial's weighted mean runs as ONE
              fused ``fed_reduce`` dispatch per model group over that
              group's (M_pad, N) row matrix (segment ids = trial slots, raw
              example counts normalized in-kernel, the int8 upload round
              trip of compressed trials fused in against each trial's
              dispatch-time globals).  Each FedAvg client's row is fixed
              after PACK (trial after trial in live order, each trial's
              clients in ``cids`` order), and each bucket's trained lanes
              are written into the matrix by ONE placement program right
              after its cohort step; zero-step clients (their trial's
              globals) take one more placement.  No row is ever sliced
              out or re-stacked on the host.  Bit-identical per lane to a
              standalone run because the kernel folds each segment's rows
              left-to-right in pack order (see kernels/ref.py).  Non-FedAvg
              trials hand their per-client pytrees to their own
              aggregator, which itself reduces through a T=1
              ``fed_reduce``.  The ``sharded`` packing lays the flat
              cohort over the ``clients`` mesh axis
              (runtime/sharded.py's mesh) and runs the same fused segment
              sum per device slice, completed by a psum — so per-client
              params never reach the host.
  4. STEP   — every due trial's evaluation runs as ONE stacked dispatch
              per (model, dataset) group (federated/evaluation.py's
              ``StackedEvaluator``), then each trial's own FedTune
              controller sees its round cost and accuracy and steps its
              (M, E) independently; finished trials drop out of the pack.

  Upload-compressed trials are packed like any others: FedAvg lanes defer
  the quantize->dequantize round trip into the fused reduce (one dispatch
  covers roundtrip + weighting + segment sum), other aggregators' lanes
  run it as a per-lane transform on the packed rows
  (``compress_delta_lanes``, masked per lane by each trial's
  ``TrialSpec.compression``) — both bit-identical to the sequential
  path's per-client ``compress_delta``.

Async/buffered trials vectorize through a second path (``run_vectorized_
events``) built on ONE merged virtual-clock event queue spanning all live
trials (events tagged with trial id, ties ordered (time, trial_key,
per-trial push seq) — see runtime/events.py).  Each macro-step advances
every live trial to its next pending client completion (dropouts handled
inline), packs those arrivals into one flat cohort — each vmap lane
training from ITS trial's dispatch-snapshot params via ``global_in_axis=0``
— then routes each trained lane back to its trial's FedAsync mixer or
FedBuff buffer on the host, exactly as the standalone event loop would
(the loop's plan/apply/account/finish phases are the engine's own
``plan_event``/``apply_event``/``finish_event_round`` methods).

Parity contract (pinned in tests/test_experiments.py): a T-trial vectorized
sweep — sync, async, or buffered — produces per-trial round records
(accuracies, costs, FedTune (M, E) trajectories, dispatch/staleness logs)
identical to T independent ``FLServer.run()`` calls with matching seeds.
Lanes of a vmapped cohort are computed independently, so packing MORE
clients around a trial does not change its floats.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.paper_models import MLPConfig
from repro.core import CostModel, FedTune, FedTuneConfig, Preference
from repro.core.tuner import FixedTuner, HyperParams
from repro.data import cifar100_like, emnist_like, speech_command_like
from repro.experiments.grid import TrialSpec
from repro.federated import FLConfig, FLServer, get_aggregator
from repro.federated.aggregation import ClientUpdate, _flatten, _unflatten
from repro.federated.compression import compress_delta_lanes, lane_mask
from repro.federated.evaluation import eval_due, evaluate_stacked
from repro.federated.server import FLResult, RoundRecord
from repro.models import build_model
from repro.optim.optimizers import get_optimizer
from repro.runtime.batched import (_pow2, _stack_streams, bucket_by_steps,
                                   cohort_scan, make_client_step,
                                   materialize_streams, note_pack_metrics)
from repro.runtime.engine import EventDrivenRuntime, RuntimeConfig
from repro.runtime.events import FAILURE, MergedEventQueue, TrialQueueView
from repro.runtime.profiles import ChurnSchedule, sample_fleet

ENGINES = ("vectorized", "sequential")
PACKS = ("batched", "sharded")

_DATASET_FNS = {"speech_command": speech_command_like, "emnist": emnist_like,
                "cifar100": cifar100_like}
_dataset_cache: Dict[tuple, Any] = {}
_model_cache: Dict[tuple, Any] = {}
_optimizer_cache: Dict[tuple, Any] = {}
_multi_cohort_cache: Dict[tuple, Any] = {}
_sharded_multi_cache: Dict[tuple, Any] = {}


# ---------------------------------------------------------------------------
# trial construction (shared caches so T trials over one dataset family share
# one Model/Optimizer object — and therefore one set of compiled cohort fns)
# ---------------------------------------------------------------------------

def _dataset_for(spec: TrialSpec):
    key = (spec.dataset, spec.reduced, spec.seed)
    if key not in _dataset_cache:
        _dataset_cache[key] = _DATASET_FNS[spec.dataset](
            reduced=spec.reduced, seed=spec.seed)
    return _dataset_cache[key]


def _model_for(spec: TrialSpec):
    ds = _dataset_for(spec)
    key = (spec.dataset, spec.reduced)
    if key not in _model_cache:
        in_dim = int(np.prod(ds.spec.shape))
        _model_cache[key] = build_model(MLPConfig(
            name=f"mlp_{spec.dataset}{'_r' if spec.reduced else ''}",
            in_dim=in_dim, hidden=(48,), n_classes=ds.spec.n_classes))
    return _model_cache[key]


def _optimizer_for(spec: TrialSpec):
    key = ("sgd", spec.lr, 0.9)
    if key not in _optimizer_cache:
        _optimizer_cache[key] = get_optimizer("sgd", spec.lr, momentum=0.9)
    return _optimizer_cache[key]


def build_server(spec: TrialSpec) -> FLServer:
    """A fresh FLServer for one trial (fresh aggregator/tuner/selector/rng
    state; model, optimizer, and dataset shared through the caches)."""
    ds = _dataset_for(spec)
    model = _model_for(spec)
    n_params = sum(p.size for p in jax.tree.leaves(
        model.init(jax.random.PRNGKey(0))))
    flops = model.flops_per_example or 2 * n_params
    tuner = (FedTune(FedTuneConfig(preference=Preference(*spec.preference)),
                     HyperParams(spec.m0, spec.e0))
             if spec.tuner == "fedtune" else FixedTuner())
    # a fleet exists iff the trial has any system heterogeneity OR a
    # failure/churn model to hang onto it — a plain homogeneous trial keeps
    # fleet=None so its selector/est_times behavior (and thus bit-parity
    # with every earlier PR) is untouched
    needs_fleet = (spec.het != "homogeneous" or spec.failure_rate > 0.0
                   or spec.churn is not None)
    fleet = (sample_fleet(spec.het, ds.n_clients, seed=spec.seed)
             if needs_fleet else None)
    if fleet is not None and spec.failure_rate > 0.0:
        fleet.failure = np.full(ds.n_clients, spec.failure_rate)
        fleet.failure_seed = spec.seed
    if fleet is not None and spec.churn is not None:
        fleet.churn = ChurnSchedule.from_string(spec.churn, seed=spec.seed)
    return FLServer(
        model, ds, get_aggregator(spec.aggregator), _optimizer_for(spec),
        CostModel(flops_per_example=flops, param_count=n_params),
        FLConfig(m=spec.m0, e=spec.e0, batch_size=spec.batch_size,
                 target_accuracy=spec.target_accuracy,
                 max_rounds=spec.rounds, eval_points=spec.eval_points,
                 prox_mu=spec.prox_mu, seed=spec.seed,
                 compression=spec.compression),
        tuner=tuner, fleet=fleet,
        runtime_config=RuntimeConfig(mode=spec.mode,
                                     client_exec=spec.client_exec))


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

@dataclass
class TrialResult:
    """One finished trial, flattened for the JSONL store.

    ``history_*`` are the per-round trajectories the parity tests compare;
    ``dispatch_log``/``staleness_log`` (async/buffered runtime modes only,
    empty otherwise) record every dispatch as (virtual time, client id,
    model version) and the staleness of every applied arrival — they are
    compared in the event-engine parity tests but deliberately NOT
    serialized by ``to_record`` (the store schema stays stable and small).
    ``engine`` names the execution path that produced the result
    (``sequential``, ``vectorized/<pack>``, ``vectorized-events/<pack>``);
    it is informational — engines are result-parity-equal."""
    spec: TrialSpec
    reached: bool
    rounds: int
    final_accuracy: float
    final_m: int
    final_e: float
    cost: Tuple[float, float, float, float]
    sim_time: float
    wall: float
    engine: str
    history_m: List[int]
    history_e: List[float]
    history_acc: List[float]
    dispatch_log: List[tuple] = field(default_factory=list)
    staleness_log: List[int] = field(default_factory=list)

    @classmethod
    def from_flresult(cls, spec: TrialSpec, res: FLResult, wall: float,
                      engine: str) -> "TrialResult":
        return cls(
            spec=spec, reached=res.reached_target, rounds=res.rounds,
            final_accuracy=float(res.final_accuracy), final_m=res.final_m,
            final_e=float(res.final_e), cost=res.total_cost.as_tuple(),
            sim_time=float(res.sim_time), wall=wall, engine=engine,
            history_m=[r.m for r in res.history],
            history_e=[float(r.e) for r in res.history],
            history_acc=[float(r.accuracy) for r in res.history],
            dispatch_log=list(res.dispatch_log or []),
            staleness_log=list(res.staleness_log or []))

    def to_record(self) -> dict:
        return {
            "key": self.spec.key(), "status": "done",
            "baseline_key": self.spec.baseline_key(),
            "spec": self.spec.to_dict(),
            "reached": self.reached, "rounds": self.rounds,
            "final_accuracy": self.final_accuracy,
            "final_m": self.final_m, "final_e": self.final_e,
            "cost": list(self.cost), "sim_time": self.sim_time,
            "wall": self.wall, "engine": self.engine,
            "history_m": self.history_m, "history_e": self.history_e,
            "history_acc": self.history_acc,
        }


def run_trial(spec: TrialSpec) -> TrialResult:
    """One trial, the single-process way: a full ``FLServer.run()``."""
    srv = build_server(spec)
    t0 = time.perf_counter()  # noqa: REPRO004 -- TrialResult.wall is informational; parity compares params/history only
    res = srv.run()
    return TrialResult.from_flresult(spec, res,
                                     time.perf_counter() - t0, "sequential")  # noqa: REPRO004 -- TrialResult.wall is informational


# ---------------------------------------------------------------------------
# the vectorized multi-trial engine
# ---------------------------------------------------------------------------

def _tree_stack(trees: Sequence[Any]):
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


def _multi_cohort_fn(model, optimizer, prox_mu: float):
    """The packed-cohort step: the shared scan/vmap body with PER-CLIENT
    reference params (``global_in_axis=0``), each lane starting local
    training from its own trial's global model."""
    key = (id(model), id(optimizer), prox_mu)
    if key in _multi_cohort_cache:
        return _multi_cohort_cache[key]
    one_client = make_client_step(model, optimizer, prox_mu)

    @jax.jit
    def cohort_step(global_b, xs, ys, masks, active):
        opt_b = jax.vmap(optimizer.init)(global_b)
        return cohort_scan(one_client, global_b, opt_b, xs, ys, masks,
                           active, global_b, global_in_axis=0)

    _multi_cohort_cache[key] = cohort_step
    return cohort_step


def _flatten_cohort(params_b):
    leaves = jax.tree.leaves(params_b)
    m = leaves[0].shape[0]
    return jnp.concatenate([l.reshape(m, -1) for l in leaves], axis=1)


@functools.partial(jax.jit, donate_argnums=0)
def _place_rows(rows, params_b, lane_of_row):
    """Row r of the reduce matrix ``rows`` takes lane ``lane_of_row[r]`` of
    the flattened cohort ``params_b``; rows marked -1 keep what they hold.
    A gather and one select over the matrix, in place: a row scatter
    rewrites the whole matrix once per row on the TPU, whose default
    layout of an (M_pad, N) matrix puts the row index minor.  Compiled per
    (rows, lanes) pow2 pair."""
    flat = _flatten_cohort(params_b)
    take = flat[jnp.maximum(lane_of_row, 0)]
    return jnp.where((lane_of_row < 0)[:, None], rows, take)


def _sharded_multi_fn(model, optimizer, prox_mu: float, mesh, n_seg: int,
                      leaf_sizes: tuple, compressed: bool = False):
    """Packed cohort over the ``clients`` mesh axis with per-trial FedAvg
    fused on device: each device trains its slice of the flat cohort,
    then ONE ``fed_reduce`` call per slice fuses the int8 upload round
    trip of compressed lanes (against ``qref[seg]``, the lane's trial
    globals) with the (T, N) segment partial sum, and a psum across the
    axis completes every trial's weighted mean at once.  Per-client
    params never reach the host."""
    from repro.kernels import ops as kernel_ops
    from repro.sharding.specs import clients_spec
    key = (id(model), id(optimizer), prox_mu, id(mesh), n_seg, compressed)
    if key in _sharded_multi_cache:
        return _sharded_multi_cache[key]
    from jax.sharding import PartitionSpec as P

    one_client = make_client_step(model, optimizer, prox_mu)
    axis = mesh.axis_names[0]

    def shard_body(global_b, xs, ys, masks, active, weights, seg, qref,
                   enabled):
        opt_b = jax.vmap(optimizer.init)(global_b)
        params_b, last_loss = cohort_scan(
            one_client, global_b, opt_b, xs, ys, masks, active, global_b,
            global_in_axis=0)
        flat = _flatten_cohort(params_b)                  # (M_loc, N)
        partial = kernel_ops.fed_reduce(                  # (T, N) segment sum
            weights, flat, seg, n_seg,
            leaf_sizes=leaf_sizes if compressed else None,
            quant_ref=qref if compressed else None,
            quant_enabled=enabled if compressed else None)
        return jax.lax.psum(partial, axis), last_loss

    @jax.jit
    def cohort_step_sharded(global_b, xs, ys, masks, active, weights, seg,
                            qref, enabled):
        in_specs = (jax.tree.map(lambda l: clients_spec(l.ndim, 0, axis),
                                 global_b),
                    clients_spec(xs.ndim, 1, axis),
                    clients_spec(ys.ndim, 1, axis),
                    clients_spec(masks.ndim, 1, axis),
                    clients_spec(active.ndim, 1, axis),
                    clients_spec(1, 0, axis),
                    clients_spec(1, 0, axis),
                    P(),                                  # qref replicated
                    clients_spec(1, 0, axis))
        # check_vma off: cohort_scan's carry starts device-invariant (the
        # zero losses, replicated globals) and turns per-device after one
        # step, which the varying-axes type check rejects as a carry change
        return jax.shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                             out_specs=(P(), clients_spec(1, 0, axis)),
                             check_vma=False)(
                                 global_b, xs, ys, masks, active, weights,
                                 seg, qref, enabled)

    _sharded_multi_cache[key] = cohort_step_sharded
    return cohort_step_sharded


@dataclass
class _Cohort:
    cids: List[int]
    streams: List[list]
    n_steps: List[int]
    sizes: List[int]
    trained: List[Any] = field(default_factory=list)   # per-client pytrees
    losses: List[float] = field(default_factory=list)
    agg_params: Any = None    # set when aggregation was fused on device
    reduce: Optional[_ReduceRows] = None  # FedAvg: the matrix of its rows
    row0: int = 0             # the row of client 0 in ``reduce.rows``


@dataclass(eq=False)
class _ReduceRows:
    """One model group's fused FedAvg reduce: the (M_pad, N) row matrix
    that every client of its trials is placed into, trial after trial and
    each trial's clients in ``cids`` order (the kernel's pack order).
    Padding rows stay zero and get weight 0."""
    trials: List[_LiveTrial]
    rows: Any

    def place(self, params_b, lane_of_row: np.ndarray):
        """ONE placement dispatch: row r takes lane ``lane_of_row[r]`` of
        ``params_b``; rows marked -1 are left as they are."""
        self.rows = _place_rows(self.rows, params_b,
                                jnp.asarray(lane_of_row))
        if obs.enabled():
            obs.registry.inc("reduce_row_places")
            obs.registry.inc("reduce_rows_placed",
                             int((lane_of_row >= 0).sum()))


def _reduce_layout(trials: List[_LiveTrial]) -> List[_ReduceRows]:
    """Fix, before training, the row every client of the given FedAvg
    trials takes in its model group's reduce matrix, and allocate one
    zero matrix per group (a group may span several TRAIN groups: those
    are keyed finer than the model)."""
    by_model: Dict[int, List[_LiveTrial]] = {}
    for tr in trials:
        by_model.setdefault(id(tr.srv.model), []).append(tr)
    out = []
    for grp in by_model.values():
        rg = _ReduceRows(trials=grp, rows=None)
        n_rows = 0
        for tr in grp:
            tr.cohort.reduce, tr.cohort.row0 = rg, n_rows
            n_rows += len(tr.cohort.cids)
        leaves = jax.tree.leaves(grp[0].params)
        rg.rows = jnp.zeros((_pow2(n_rows), sum(l.size for l in leaves)),
                            jnp.result_type(*leaves))
        out.append(rg)
    return out


@dataclass(eq=False)     # identity semantics: trials are packed by object
class _LiveTrial:
    spec: TrialSpec
    srv: FLServer
    eng: EventDrivenRuntime
    hp: HyperParams
    params: Any
    round_idx: int = 0
    accuracy: float = 0.0
    reached: bool = False
    done: bool = False
    wall: float = 0.0
    history: List[RoundRecord] = field(default_factory=list)
    plan: Any = None
    cohort: Optional[_Cohort] = None
    round_cost: Any = None     # set by _reduce_round, consumed by _finish_round
    _meta: Any = None          # cached _flatten meta (model-constant)


def _make_live(spec: TrialSpec) -> _LiveTrial:
    srv = build_server(spec)
    eng = EventDrivenRuntime(srv, fleet=srv.fleet,
                             config=srv.runtime_config or RuntimeConfig())
    eng.trace_label = spec.key()
    params = srv.model.init(jax.random.PRNGKey(srv.config.seed))
    return _LiveTrial(spec=spec, srv=srv, eng=eng,
                      hp=HyperParams(m=spec.m0, e=spec.e0), params=params)


def _group_key(tr: _LiveTrial) -> tuple:
    return (id(tr.srv.model), id(tr.srv.optimizer), tr.srv.config.prox_mu,
            tr.srv.config.batch_size)


_note_pack = note_pack_metrics      # pack-shape metrics, see batched.py


def _run_group_batched(ents: List[Tuple[_LiveTrial, int]]):
    """Train one model-group's packed entries; results land back in each
    trial's cohort.  FedAvg lanes of each bucket are written, in one
    placement dispatch, straight into the rows ``_reduce_layout`` fixed
    for them in their group's reduce matrix (their aggregation runs as
    one fused ``fed_reduce`` over it in ``_fused_sync_reduce``); other
    aggregators get per-client pytree slices.  Each trial's global params
    enter the pack through ONE per-round stack + an on-device gather per
    bucket, so host-side tree work stays O(trials), not O(clients).
    Upload-compressed lanes of non-FedAvg trials go through the
    quantize->dequantize round trip against their trial's global params
    (``compress_delta_lanes``) before unpacking — bit-identical per lane
    to the sequential path's ``compress_delta``, and masked off for
    uncompressed lanes so mixed grids pack together.  Compressed FedAvg
    lanes are masked off too: their round trip is fused into the segment
    reduce (same bits, one fewer dispatch)."""
    tr0 = ents[0][0]
    model, opt = tr0.srv.model, tr0.srv.optimizer
    bs = tr0.srv.config.batch_size
    run = _multi_cohort_fn(model, opt, tr0.srv.config.prox_mu)

    trials: List[_LiveTrial] = []
    slot: Dict[int, int] = {}
    for tr, _ in ents:
        if id(tr) not in slot:
            slot[id(tr)] = len(trials)
            trials.append(tr)
    stacked = _tree_stack([tr.params for tr in trials])

    n_steps = [tr.cohort.n_steps[j] for tr, j in ents]
    for t_pad, idx in sorted(bucket_by_steps(n_steps).items()):
        sel = [ents[i] for i in idx]
        m_pad = _pow2(len(sel))    # bound the compiled (T, M) shape set
        if obs.enabled():
            _note_pack(t_pad, m_pad, len(sel),
                       sum(n_steps[i] for i in idx))
        streams = [tr.cohort.streams[j] for tr, j in sel]
        xs, ys, masks, active = _stack_streams(
            streams + [[]] * (m_pad - len(sel)), bs, t_pad)
        slots = np.array([slot[id(tr)] for tr, _ in sel]
                         + [0] * (m_pad - len(sel)), np.int32)
        global_b = jax.tree.map(lambda s: s[slots], stacked)
        params_b, last_loss = run(global_b, jnp.asarray(xs), jnp.asarray(ys),
                                  jnp.asarray(masks), jnp.asarray(active))
        mask = lane_mask([tr.srv.config.compression
                          if tr.srv.aggregator.name != "fedavg" else None
                          for tr, _ in sel]
                         + [None] * (m_pad - len(sel)))
        if mask is not None:
            params_b = compress_delta_lanes(global_b, params_b, mask)
        # every FedAvg lane of a group shares one model, so one matrix
        rg = next((tr.cohort.reduce for tr, _ in sel
                   if tr.cohort.reduce is not None), None)
        if rg is not None:
            lane_of_row = np.full(rg.rows.shape[0], -1, np.int32)
            for k, (tr, j) in enumerate(sel):
                if tr.cohort.reduce is not None:
                    lane_of_row[tr.cohort.row0 + j] = k
            rg.place(params_b, lane_of_row)
        ll = np.asarray(last_loss)
        for k, (tr, j) in enumerate(sel):
            if tr.cohort.reduce is None:
                tr.cohort.trained[j] = jax.tree.map(
                    lambda p, k=k: p[k], params_b)
            tr.cohort.losses[j] = float(ll[k])


def _run_group_sharded(ents: List[Tuple[_LiveTrial, int]], mesh):
    """Train one all-FedAvg model-group's packed entries over the
    ``clients`` mesh axis; every trial's FedAvg aggregate comes back from
    the device directly (segment sum + psum)."""
    tr0 = ents[0][0]
    model, opt = tr0.srv.model, tr0.srv.optimizer
    bs = tr0.srv.config.batch_size
    n_dev = int(np.prod(mesh.devices.shape))
    compressed = any(tr.srv.config.compression not in (None, "none")
                     for tr, _ in ents)

    trials: List[_LiveTrial] = []
    slot: Dict[int, int] = {}
    for tr, _ in ents:
        if id(tr) not in slot:
            slot[id(tr)] = len(trials)
            trials.append(tr)
    n_t = len(trials)
    # FedAvg weights within each trial: n_j / n_total
    totals = [float(sum(tr.cohort.sizes)) for tr in trials]

    flat0, meta = _flatten(trials[0].params)
    t_seg = _pow2(n_t)     # segment count padded pow2: bounded shape set
    run = _sharded_multi_fn(model, opt, tr0.srv.config.prox_mu, mesh,
                            t_seg, tuple(meta[2]), compressed)
    # each lane's quant reference = its trial's dispatch-time globals
    qref = jnp.stack([_flatten(tr.params)[0] for tr in trials]
                     + [jnp.zeros_like(flat0)] * (t_seg - n_t))
    agg = jnp.zeros((n_t, flat0.shape[0]), flat0.dtype)
    n_steps = [tr.cohort.n_steps[j] for tr, j in ents]
    for t_pad, idx in sorted(bucket_by_steps(n_steps).items()):
        sel = [ents[i] for i in idx]
        m_pad = _pow2(len(sel))
        m_pad = int(np.ceil(m_pad / n_dev) * n_dev)   # shard-divisible
        if obs.enabled():
            _note_pack(t_pad, m_pad, len(sel),
                       sum(n_steps[i] for i in idx))
        pad = m_pad - len(sel)
        xs, ys, masks, active = _stack_streams(
            [tr.cohort.streams[j] for tr, j in sel] + [[]] * pad, bs, t_pad)
        global_b = _tree_stack([tr.params for tr, _ in sel]
                               + [sel[0][0].params] * pad)
        w = np.zeros(m_pad, np.float32)
        seg = np.zeros(m_pad, np.int32)    # pad lanes: seg 0, weight 0
        enabled = np.zeros(m_pad, bool)
        for k, (tr, j) in enumerate(sel):
            s = slot[id(tr)]
            w[k] = tr.cohort.sizes[j] / totals[s]
            seg[k] = s
            enabled[k] = tr.srv.config.compression not in (None, "none")
        if obs.enabled():
            obs.registry.inc("reduce_fused_dispatches")
            obs.registry.sample("reduce_rows", m_pad)
            obs.registry.sample("reduce_lanes", n_t)
        partial, last_loss = run(global_b, jnp.asarray(xs), jnp.asarray(ys),
                                 jnp.asarray(masks), jnp.asarray(active),
                                 jnp.asarray(w), jnp.asarray(seg), qref,
                                 jnp.asarray(enabled))
        agg = agg + partial[:n_t]
        ll = np.asarray(last_loss)
        for k, (tr, j) in enumerate(sel):
            tr.cohort.losses[j] = float(ll[k])
    # zero-step clients never trained: their weight enters at the trial's
    # own global params, as in every other execution path
    for tr, j in ents:
        if tr.cohort.n_steps[j] == 0:
            s = slot[id(tr)]
            zw = tr.cohort.sizes[j] / totals[s]
            agg = agg.at[s].add(zw * _flatten(tr.params)[0])  # noqa: REPRO001 -- mirrors the sequential engines' eager zero-step contribution op-for-op; jitting would change FMA contraction vs the pinned parity
    for tr in trials:
        tr.cohort.agg_params = _unflatten(agg[slot[id(tr)]], meta)


def _fused_sync_reduce(groups: List[_ReduceRows]):
    """ONE ``fed_reduce`` dispatch per model group covering every FedAvg
    trial's aggregation: each trial is a segment (lane) of the group's
    placed (M_pad, N) row matrix, raw example counts are normalized per
    segment in-kernel, and compressed trials' int8 upload round trips run
    against their own stacked global params inside the same dispatch.
    Zero-step clients' rows (their trial's globals) are placed first, in
    one more placement.  Fills ``cohort.agg_params``; ``_reduce_round``
    consumes it.  Bit-identical per trial to the standalone
    ``FedAvg.__call__`` path because the kernel's per-segment fold only
    ever sees that trial's rows, in the same client order
    (kernels/ref.py's packing-invariance contract)."""
    from repro.kernels import ops as kernel_ops
    for grp in groups:
        t_pad = _pow2(len(grp.trials))
        m_pad = grp.rows.shape[0]
        w, seg, en, qrefs = [], [], [], []
        zero_slot = np.full(m_pad, -1, np.int32)    # row -> trial slot
        meta = None
        for s, tr in enumerate(grp.trials):
            co = tr.cohort
            gflat, meta = _flatten(tr.params)
            if tr._meta is None:
                tr._meta = meta
            qrefs.append(gflat)
            comp = tr.srv.config.compression not in (None, "none")
            for j, t in enumerate(co.n_steps):
                w.append(co.sizes[j])
                seg.append(s)
                en.append(comp)
                if t == 0:                # never trained: stays at global
                    zero_slot[co.row0 + j] = s
        pad = m_pad - len(w)
        w += [0.0] * pad                  # zero-weight rows are bit-neutral
        seg += [0] * pad
        en += [False] * pad
        quant = any(en)
        zero = bool((zero_slot >= 0).any())
        if quant or zero:
            qrefs += [jnp.zeros_like(qrefs[0])] * (t_pad - len(qrefs))
            qref = jnp.stack(qrefs)       # (T_pad, N) dispatch-time globals
        if zero:
            grp.place(qref, zero_slot)
        if obs.enabled():
            obs.registry.inc("reduce_fused_dispatches")
            obs.registry.sample("reduce_rows", m_pad)
            obs.registry.sample("reduce_lanes", len(grp.trials))
        with obs.span("REDUCE", phase="apply", n_lanes=len(grp.trials),
                      n_rows=m_pad):
            out = kernel_ops.fed_reduce(
                jnp.asarray(np.asarray(w, np.float32)), grp.rows,
                jnp.asarray(np.asarray(seg, np.int32)), t_pad,
                normalize=True,
                leaf_sizes=tuple(meta[2]) if quant else None,
                quant_ref=qref if quant else None,
                quant_enabled=jnp.asarray(np.asarray(en)) if quant else None)
        for s, tr in enumerate(grp.trials):
            tr.cohort.agg_params = _unflatten(out[s], tr._meta)


def _reduce_round(tr: _LiveTrial):
    """Per-trial selector updates, aggregation, and cost accounting — the
    pre-evaluation half of the engine's sync round sequence.  Evaluation
    is deliberately NOT here: the sweep loop batches every due trial's
    eval into one stacked dispatch between reduce and finish."""
    srv = tr.srv
    if tr.cohort is not None and tr.cohort.cids:
        co = tr.cohort
        for j, cid in enumerate(co.cids):
            srv.selector.update(int(cid), co.losses[j], co.sizes[j])
        if co.agg_params is not None:   # fused reduce (or sharded pack)
            tr.params = co.agg_params
        else:
            updates = [
                ClientUpdate(
                    params=(co.trained[j] if co.trained[j] is not None
                            else tr.params),
                    n_examples=co.sizes[j], n_steps=co.n_steps[j],
                    last_loss=co.losses[j], client_id=int(cid))
                for j, cid in enumerate(co.cids)]
            tr.params = srv.aggregator(tr.params, updates)
    tr.round_cost = tr.eng.account_sync_round(tr.plan, tr.hp)


def _finish_round(tr: _LiveTrial, wall: float,
                  accuracy: Optional[float] = None):
    """Record the round and step the trial's own controller — the
    post-evaluation half of the engine's sync round sequence.
    ``accuracy`` is the trial's lane of the stacked evaluation (None when
    this round is not on the eval schedule: the last measured accuracy
    carries forward, as in the standalone loop)."""
    srv, cfg = tr.srv, tr.srv.config
    round_cost = tr.round_cost
    r = tr.round_idx
    if accuracy is not None:
        tr.accuracy = accuracy
    tr.history.append(RoundRecord(
        r, tr.hp.m, tr.hp.e, tr.accuracy, round_cost, wall,
        sim_time=tr.eng.clock.now, n_updates=len(tr.plan.included)))
    tr.round_idx += 1
    tr.cohort = None
    tr.plan = None
    tr.round_cost = None
    if tr.accuracy >= cfg.target_accuracy:
        tr.reached = True
        tr.done = True
        return
    tr.hp = srv.tuner.on_round(r, tr.accuracy, round_cost,
                               srv.cost_model.total, tr.hp)
    tr.hp = tr.hp.clamped(srv.dataset.n_clients, 100.0)
    if tr.round_idx >= cfg.max_rounds:
        tr.done = True


def _to_result(tr: _LiveTrial, engine: str) -> TrialResult:
    res = FLResult(
        reached_target=tr.reached, rounds=len(tr.history),
        final_accuracy=tr.accuracy,
        total_cost=tr.srv.cost_model.total.copy(), history=tr.history,
        final_m=tr.hp.m, final_e=tr.hp.e, params=tr.params,
        sim_time=tr.eng.clock.now)
    return TrialResult.from_flresult(tr.spec, res, tr.wall, engine)


def _resolve_sync_pack(pack: str):
    """Resolve the requested pack against the host topology: the sharded
    pack needs a real multi-device mesh, single-device hosts fall back to
    batched.  Returns ``(pack, mesh)``."""
    mesh = None
    if pack == "sharded":
        if jax.device_count() == 1:
            print("experiments: sharded packing needs a multi-device mesh "
                  "(jax.device_count() == 1); falling back to batched "
                  "packing", flush=True)
            pack = "batched"
        else:
            from repro.runtime.sharded import default_clients_mesh
            mesh = default_clients_mesh()
    return pack, mesh


def _sync_round_step(live: List[_LiveTrial], *, pack: str = "batched",
                     mesh=None, step_idx: int = 0) -> int:
    """Advance the given live sync trials by ONE packed virtual round
    (plan -> pack -> train -> apply -> eval -> finish, as described in the
    module docstring).  The live set is whatever the caller says it is —
    the fixed-set sweep passes every unfinished trial, the continuous-
    batching scheduler (experiments/scheduler.py) passes the pool's
    currently-admitted lanes — and every pack/eval shape is keyed off that
    live set, never off an initial trial count.  Trials that end this
    round come back with ``done`` set; retiring them (result emission,
    lane release) is the caller's job.  Returns the number of packed
    client entries."""
    t0 = time.perf_counter()  # noqa: REPRO004 -- per-macro-step wall share for TrialResult.wall; round accounting uses virtual clocks
    if obs.enabled():
        obs.registry.sample("lanes_live", len(live), step=step_idx,
                            engine="sync")
    # 1. plan every live trial's round (per-trial rng streams)
    with obs.span("PLAN", phase="plan", n_trials=len(live)):
        for tr in live:
            v0 = tr.eng.clock.now
            tr.plan = tr.eng.plan_sync_round(tr.hp)
            tr.eng.clock.advance_to(tr.eng.clock.now
                                    + tr.plan.round_time)
            if obs.enabled():
                obs.record("round", phase="round", trial=tr.spec.key(),
                           round_idx=tr.round_idx,
                           virtual=(v0, tr.eng.clock.now),
                           n_included=len(tr.plan.included),
                           n_active=len(tr.plan.active))
    # 2. materialize batch streams (the rng contract) and pack
    entries: List[Tuple[_LiveTrial, int]] = []
    with obs.span("PACK", phase="pack", n_trials=len(live)):
        for tr in live:
            cids = tr.plan.train_cids
            if not cids:
                tr.cohort = None
                continue
            data = [tr.srv.dataset.client_data(c) for c in cids]
            streams, n_steps = materialize_streams(
                data, tr.srv.config.batch_size, tr.hp.e, tr.srv.rng)
            sizes = [len(y) for _, y in data]
            tr.cohort = _Cohort(cids=cids, streams=streams,
                                n_steps=n_steps, sizes=sizes,
                                trained=[None] * len(cids),
                                losses=[0.0] * len(cids))
            entries.extend((tr, j) for j in range(len(cids)))
    # 3. group by model and train each group's packed cohort; every
    #    FedAvg client of the batched groups gets its reduce row first
    groups: Dict[tuple, List[Tuple[_LiveTrial, int]]] = {}
    for ent in entries:
        groups.setdefault(_group_key(ent[0]), []).append(ent)
    sharded = set()    # trials of all-FedAvg groups, reduced on the mesh
    if pack == "sharded":
        for ents in groups.values():
            if all(tr.srv.aggregator.name == "fedavg" for tr, _ in ents):
                sharded.update(id(tr) for tr, _ in ents)
    with obs.span("TRAIN", phase="train", n_entries=len(entries),
                  n_groups=len(groups)):
        reduce_groups = _reduce_layout(
            [tr for tr in live if tr.cohort is not None
             and tr.srv.aggregator.name == "fedavg"
             and id(tr) not in sharded])
        for ents in groups.values():
            if id(ents[0][0]) in sharded:
                _run_group_sharded(ents, mesh)
            else:
                _run_group_batched(ents)
    # 4. per-trial aggregation + accounting, then ONE stacked eval of
    #    every due trial (grouped by model/dataset), then per-trial
    #    record + controller step
    with obs.span("APPLY", phase="apply", n_trials=len(live)):
        _fused_sync_reduce(reduce_groups)   # one dispatch per model group
        for tr in live:
            _reduce_round(tr)
    due = [tr for tr in live
           if eval_due(tr.round_idx, tr.srv.config.eval_every,
                       tr.srv.config.max_rounds)]
    with obs.span("EVAL", phase="eval", n_due=len(due)):
        # pad_pow2: stacked eval shapes keyed off the live due count's
        # pow2, so lane churn (drain or continuous admission) does not
        # recompile per distinct count — parity-safe, lanes are independent
        accs = evaluate_stacked(
            [(tr.srv.model, tr.srv.dataset, tr.srv.config.eval_points,
              tr.params) for tr in due], mesh=mesh, pad_pow2=True)
    acc_of = {id(tr): a for tr, a in zip(due, accs)}
    wall = time.perf_counter() - t0  # noqa: REPRO004 -- wall shares are informational; parity compares params/history only
    if obs.enabled():
        obs.counter("t_sim", max(tr.eng.clock.now for tr in live))
    for tr in live:
        tr.wall += wall / len(live)
        _finish_round(tr, wall / len(live), acc_of.get(id(tr)))
    return len(entries)


def _run_vectorized_sync(specs: Sequence[TrialSpec], *,
                         pack: str = "batched",
                         on_result: Optional[Callable] = None,
                         verbose: bool = False) -> List[TrialResult]:
    """Run every sync-mode trial concurrently, one packed cohort per
    virtual round (``_sync_round_step``) over the set of unfinished
    trials until all are done."""
    pack, mesh = _resolve_sync_pack(pack)
    trials = [_make_live(s) for s in specs]
    results: List[TrialResult] = [None] * len(trials)
    engine = f"vectorized/{pack}"
    n_rounds = 0
    while True:
        live = [tr for tr in trials if not tr.done]
        if not live:
            break
        n_entries = _sync_round_step(live, pack=pack, mesh=mesh,
                                     step_idx=n_rounds)
        for tr in live:
            if tr.done:
                res = _to_result(tr, engine)
                results[trials.index(tr)] = res
                if on_result is not None:
                    on_result(res)
        n_rounds += 1
        if verbose and n_rounds % 10 == 0:
            done = sum(tr.done for tr in trials)
            print(f"  sweep round {n_rounds}: {done}/{len(trials)} trials "
                  f"done, {n_entries} clients packed", flush=True)
    return results


# ---------------------------------------------------------------------------
# the merged-queue event engine (async / buffered trials)
# ---------------------------------------------------------------------------

@dataclass(eq=False)     # identity semantics: trials are packed by object
class _EventTrial:
    """One live async/buffered trial of a merged-queue sweep: its server,
    runtime engine, event-loop state, and the facade binding it onto the
    sweep's merged event queue."""
    spec: TrialSpec
    srv: FLServer
    eng: EventDrivenRuntime
    view: TrialQueueView
    st: Any = None             # repro.runtime.engine.EventLoopState
    done: bool = False
    wall: float = 0.0


@dataclass
class _Lane:
    """One packed arrival: trial + its in-flight record + the batch stream
    materialized at the standalone loop's exact rng point.  ``params`` and
    ``loss`` are filled by the cohort training."""
    tr: _EventTrial
    fl: Any                    # repro.runtime.engine._InFlight
    stream: list
    n_steps: int
    params: Any = None
    loss: float = 0.0


def _make_event_live(spec: TrialSpec, merged: MergedEventQueue,
                     trial_ord: int) -> _EventTrial:
    srv = build_server(spec)
    eng = EventDrivenRuntime(srv, fleet=srv.fleet,
                             config=srv.runtime_config or RuntimeConfig())
    eng.trace_label = spec.key()
    view = TrialQueueView(merged, trial_ord)
    tr = _EventTrial(spec=spec, srv=srv, eng=eng, view=view)
    params = srv.model.init(jax.random.PRNGKey(srv.config.seed))
    # initial concurrency dispatches straight into the merged queue
    tr.st = eng.init_event_state(params, queue=view)
    return tr


def _coalesce_buckets(buckets: Dict[int, List[int]],
                      min_lanes: int = 4) -> Dict[int, List[int]]:
    """Merge under-filled step buckets upward into the next-larger one.

    The event pack holds at most one lane per trial, so strict
    ``bucket_by_steps`` grouping would often produce singleton buckets —
    one compiled dispatch per lane, which is exactly the overhead packing
    exists to amortize.  Promoting a small bucket's lanes into a larger
    t_pad only adds masked (frozen-state) steps, so results are unchanged;
    for big packs the original waste bound still applies because full
    buckets are left alone."""
    out: Dict[int, List[int]] = {}
    pending: List[int] = []
    for t_pad in sorted(buckets):
        pending.extend(buckets[t_pad])
        if len(pending) >= min_lanes or t_pad == max(buckets):
            out[t_pad] = pending     # the max bucket absorbs any tail
            pending = []
    return out


def _run_event_group(lanes: List[_Lane], min_lanes: int = 4):
    """Train one model-group's packed arrivals: one vmap lane per trial,
    each lane starting local training from ITS trial's dispatch-snapshot
    params (``global_in_axis=0`` also anchors the FedProx term there, as
    ``local_train`` does).  Buckets by pow2 step count (small buckets
    coalesced upward — see ``_coalesce_buckets``; the caller keys
    ``min_lanes`` off the LIVE lane count, not the sweep's initial T, so
    a draining or continuously-batched pool coalesces against what is
    actually resident) and pads the lane axis to a pow2 so compiled
    (T, M) shapes repeat across macro-steps — and are SHARED with the
    sync sweep path (same ``_multi_cohort_fn``)."""
    tr0 = lanes[0].tr
    model, opt = tr0.srv.model, tr0.srv.optimizer
    bs = tr0.srv.config.batch_size
    run = _multi_cohort_fn(model, opt, tr0.srv.config.prox_mu)
    buckets = _coalesce_buckets(
        bucket_by_steps([ln.n_steps for ln in lanes]), min_lanes=min_lanes)
    for t_pad, idx in sorted(buckets.items()):
        sel = [lanes[i] for i in idx]
        m_pad = _pow2(len(sel))    # bound the compiled (T, M) shape set
        if obs.enabled():
            _note_pack(t_pad, m_pad, len(sel),
                       sum(ln.n_steps for ln in sel))
        xs, ys, masks, active = _stack_streams(
            [ln.stream for ln in sel] + [[]] * (m_pad - len(sel)),
            bs, t_pad)
        global_b = _tree_stack([ln.fl.params for ln in sel]
                               + [sel[0].fl.params] * (m_pad - len(sel)))
        params_b, last_loss = run(global_b, jnp.asarray(xs), jnp.asarray(ys),
                                  jnp.asarray(masks), jnp.asarray(active))
        # upload-compressed lanes: quantize->dequantize against the lane's
        # dispatch snapshot, exactly what _client_update does per arrival
        mask = lane_mask([ln.tr.srv.config.compression for ln in sel]
                         + [None] * (m_pad - len(sel)))
        if mask is not None:
            params_b = compress_delta_lanes(global_b, params_b, mask)
        ll = np.asarray(last_loss)
        # one host transfer per leaf, then free numpy views per lane — much
        # cheaper than a device-slice dispatch per (lane, leaf)
        leaves, treedef = jax.tree.flatten(params_b)
        np_leaves = [np.asarray(l) for l in leaves]
        for k, ln in enumerate(sel):
            ln.params = jax.tree.unflatten(treedef, [l[k] for l in np_leaves])
            ln.loss = float(ll[k])


class _EventEngine:
    """Merged-queue engine state shared by the fixed-set wrapper
    (``run_vectorized_events``) and the continuous-batching scheduler
    (experiments/scheduler.py): ONE merged virtual-clock event queue
    spanning every live trial, with trial ordinals handed out at
    admission.  Admission order IS the merged queue's cross-trial tie
    order — the fixed-set wrapper admits in sorted-key order (so its tie
    order stays independent of caller spec order), the scheduler admits
    in queue order (so a drain is deterministic given the submission
    sequence).  Either way a trial's own event sequence — and therefore
    its floats — depends only on its private rngs and clock, never on
    which other trials share the queue."""

    def __init__(self):
        self.merged = MergedEventQueue()
        self.by_ord: Dict[int, _EventTrial] = {}
        self.n_steps = 0
        # ordinals are handed out monotonically and never reused — a
        # snapshot restore repopulates by_ord with only the live ordinals,
        # so len(by_ord) would hand a recycled ordinal to the next admit
        self.next_ord = 0

    def admit(self, spec: TrialSpec) -> _EventTrial:
        """Bring one async/buffered trial live on the merged queue (its
        initial concurrency dispatches push events immediately)."""
        if spec.mode not in ("async", "buffered"):
            raise ValueError(
                f"trial {spec.key()!r} is not an event-driven trial "
                "(the merged-queue engine covers the async/buffered modes; "
                "sync trials pack per round via run_vectorized)")
        trial_ord = self.next_ord
        self.next_ord += 1
        tr = _make_event_live(spec, self.merged, trial_ord)
        self.by_ord[trial_ord] = tr
        return tr

    def end_trial(self, tr: _EventTrial) -> None:
        """Retire one trial: account its tail window, mark it done, and
        drop its pending events so the merged queue never carries a
        retired trial's traffic into later macro-steps."""
        tr.eng.account_event_tail(tr.st)
        tr.done = True
        self.merged.drop_trial(tr.view.trial_ord)

    def macro_step(self, live: List[_EventTrial],
             on_done: Callable[[_EventTrial], None]) -> int:
        """One COLLECT/PACK/APPLY macro-step over the given live trials.

        (1) COLLECT — pop the merged queue in deterministic (time,
        admission ordinal, seq) order, advancing every live trial to its
        next pending arrival; dropouts are handled inline (loads charged,
        concurrency refilled), and events of trials that already
        contributed an arrival are deferred untouched (an arrival must be
        trained and applied before its trial's later events may be
        processed — FedAsync/FedBuff state is sequential per trial).
        Each collected arrival's batch stream is materialized at the
        exact point the standalone loop would consume the trial's server
        rng.  (2) PACK — all collected arrivals train as one flat cohort
        (one vmap lane per trial, each from its own dispatch snapshot),
        with bucket coalescing keyed off the live-lane count.  (3) APPLY
        — per trial on the host: selector update, FedAsync mixing /
        FedBuff buffering, accounting, evaluation, FedTune step, and
        concurrency refill, via the engine's own event-loop methods.

        ``on_done(tr)`` fires for every trial that ends during the step
        (after its tail accounting + event drop); the caller emits the
        result and releases the lane.  Returns the number of packed
        arrivals."""
        step_idx = self.n_steps
        self.n_steps += 1
        merged, by_ord = self.merged, self.by_ord

        def end(tr: _EventTrial):
            self.end_trial(tr)
            on_done(tr)

        t0 = time.perf_counter()  # noqa: REPRO004 -- per-macro-step wall share for TrialResult.wall; event order uses the merged virtual queue
        if obs.enabled():
            obs.registry.sample("lanes_live", len(live), step=step_idx,
                                engine="events")
        # 1. COLLECT one pending arrival per live trial
        lanes: List[_Lane] = []
        packed = set()
        stash = []
        with obs.span("COLLECT", phase="collect", n_live=len(live)) as _sp:
            while merged and len(packed) < len(live):
                ev = merged.pop()
                tr = by_ord[ev.trial_ord]
                if tr.done:
                    continue           # stale event of a finished trial
                if id(tr) in packed:
                    stash.append(ev)   # defer: this trial already packed
                    continue
                tr.eng.clock.advance_to(ev.time)
                if ev.kind == FAILURE:  # hard failure: retry inline, refill
                    tr.eng.handle_failure(tr.st, ev, queue=tr.view)
                    tr.eng.fill_event_concurrency(tr.st, tr.eng.clock.now,
                                                  queue=tr.view)
                    continue
                fl = tr.eng.plan_event(tr.st, ev)
                if fl is None:         # dropout: refill and keep collecting
                    tr.eng.fill_event_concurrency(tr.st, tr.eng.clock.now,
                                                  queue=tr.view)
                    continue
                data = [tr.srv.dataset.client_data(fl.client_id)]
                streams, n_steps = materialize_streams(
                    data, tr.srv.config.batch_size, fl.e, tr.srv.rng)
                lanes.append(_Lane(tr=tr, fl=fl, stream=streams[0],
                                   n_steps=n_steps[0]))
                packed.add(id(tr))
            for ev in stash:
                merged.requeue(ev)
            _sp.set(n_lanes=len(lanes), n_deferred=len(stash))
        # a live trial with nothing queued ends exactly as the standalone
        # loop does on an empty queue (the dispatch deadlock guard makes
        # this unreachable in practice, but the semantics must match)
        for tr in live:
            if not tr.done and id(tr) not in packed and not tr.view:
                end(tr)
        # 2. PACK: train all collected arrivals as one cohort per model group
        groups: Dict[tuple, List[_Lane]] = {}
        for ln in lanes:
            if ln.n_steps == 0:        # zero-step client: stays at snapshot
                ln.params, ln.loss = ln.fl.params, 0.0
                continue
            groups.setdefault(_group_key(ln.tr), []).append(ln)
        with obs.span("TRAIN", phase="train", n_lanes=len(lanes),
                      n_groups=len(groups)):
            for group in groups.values():
                _run_event_group(group, min_lanes=min(4, len(live)))
        # 3. APPLY per trial, in collect (= merged pop) order: first fold
        #    every lane into its trial's global model, then evaluate every
        #    aggregating-and-due trial in ONE stacked dispatch (grouped by
        #    model/dataset), then finish/refill per trial.  Evaluation
        #    consumes no rng and each trial's clock is private, so hoisting
        #    the evals between apply and finish preserves the standalone
        #    loop's per-trial operation order exactly.
        wall = time.perf_counter() - t0  # noqa: REPRO004 -- wall shares are informational; parity compares params/history only
        share = wall / max(len(lanes), 1)
        applied = []
        with obs.span("APPLY", phase="apply", n_lanes=len(lanes)):
            for ln in lanes:
                tr, fl = ln.tr, ln.fl
                tr.wall += share
                tr.srv.selector.update(int(fl.client_id), ln.loss,
                                       fl.n_examples)
                aggregated, staleness = tr.eng.apply_event(tr.st, fl,
                                                           ln.params)
                applied.append((ln, aggregated, staleness))
        due = [ln.tr for ln, aggregated, _s in applied
               if aggregated and eval_due(len(ln.tr.st.history),
                                          ln.tr.srv.config.eval_every,
                                          ln.tr.srv.config.max_rounds)]
        with obs.span("EVAL", phase="eval", n_due=len(due)):
            accs = evaluate_stacked(
                [(tr.srv.model, tr.srv.dataset, tr.srv.config.eval_points,
                  tr.st.params) for tr in due], pad_pow2=True)
        acc_of = {id(tr): a for tr, a in zip(due, accs)}
        for ln, aggregated, staleness in applied:
            tr = ln.tr
            if aggregated:
                tr.eng.finish_event_round(tr.st, staleness, share,
                                          accuracy=acc_of.get(id(tr)))
                if tr.st.reached:
                    end(tr)
                    continue
            tr.eng.fill_event_concurrency(tr.st, tr.eng.clock.now,
                                          queue=tr.view)
            if len(tr.st.history) >= tr.srv.config.max_rounds:
                end(tr)
        if obs.enabled() and live:
            obs.counter("t_sim", max(tr.eng.clock.now for tr in live))
        return len(lanes)


def run_vectorized_events(specs: Sequence[TrialSpec], *,
                          pack: str = "batched",
                          on_result: Optional[Callable] = None,
                          verbose: bool = False) -> List[TrialResult]:
    """Run T async/buffered trials concurrently off ONE merged event queue
    (``_EventEngine`` macro-steps over the set of unfinished trials).

    Parity: bit-identical to each trial's standalone ``FLServer.run()``
    (accuracies, costs, dispatch/staleness logs, (M, E) trajectories)."""
    for s in specs:
        if s.mode not in ("async", "buffered"):
            raise ValueError(
                f"trial {s.key()!r} is not an event-driven trial "
                "(run_vectorized_events covers the async/buffered modes; "
                "sync trials pack per round via run_vectorized)")
    if pack == "sharded":
        # event packs are one-arrival-per-trial wide and FedAsync/FedBuff
        # mixing is per-trial host state — there is no cross-client
        # aggregation to fuse on device, so the mesh layout buys nothing
        print("experiments: sharded packing does not apply to event-driven "
              "(async/buffered) trials — per-trial mixing is host-side; "
              "using the batched pack", flush=True)
        pack = "batched"

    ev = _EventEngine()
    # trial ordinals from sorted keys: the merged queue's cross-trial tie
    # order is then independent of the caller's spec order
    order = sorted(range(len(specs)), key=lambda i: specs[i].key())
    trials: List[_EventTrial] = [None] * len(specs)
    for i in order:
        trials[i] = ev.admit(specs[i])
    results: List[TrialResult] = [None] * len(specs)
    engine = f"vectorized-events/{pack}"

    def on_done(tr: _EventTrial):
        res = TrialResult.from_flresult(tr.spec, tr.eng.event_result(tr.st),
                                        tr.wall, engine)
        results[trials.index(tr)] = res
        if on_result is not None:
            on_result(res)

    while True:
        live = [tr for tr in trials if not tr.done]
        if not live:
            break
        n_lanes = ev.macro_step(live, on_done)
        if verbose and ev.n_steps % 20 == 0:
            done = sum(tr.done for tr in trials)
            print(f"  event sweep step {ev.n_steps}: {done}/{len(trials)}"
                  f" trials done, {n_lanes} arrivals packed", flush=True)
    return results


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def run_vectorized(specs: Sequence[TrialSpec], *, pack: str = "batched",
                   on_result: Optional[Callable[[TrialResult], None]] = None,
                   verbose: bool = False) -> List[TrialResult]:
    """Run every trial concurrently: sync trials through the round-packed
    engine (one cohort per virtual round), async/buffered trials through
    the merged-event-queue engine (one cohort per macro-step).  Both reuse
    the same compiled ``_multi_cohort_fn`` shapes.  Results come back in
    input-spec order; ``on_result`` fires per trial as it finishes.

    Upload-compressed trials vectorize like any others: the quantize->
    dequantize round trip is a per-lane transform inside the cohort
    packers (``compress_delta_lanes``), masked off for uncompressed lanes,
    so mixed grids pack into one cohort."""
    if pack not in PACKS:
        raise ValueError(f"unknown pack {pack!r}; valid packs: "
                         + ", ".join(PACKS))
    sync_specs = [s for s in specs if s.mode == "sync"]
    event_specs = [s for s in specs if s.mode != "sync"]
    out: Dict[str, TrialResult] = {}

    def keep(res: TrialResult):
        out[res.spec.key()] = res
        if on_result is not None:
            on_result(res)

    if sync_specs:
        _run_vectorized_sync(sync_specs, pack=pack, on_result=keep,
                             verbose=verbose)
    if event_specs:
        run_vectorized_events(event_specs, pack=pack, on_result=keep,
                              verbose=verbose)
    return [out[s.key()] for s in specs]


def run_sweep(specs: Sequence[TrialSpec], *, store=None,
              engine: str = "vectorized", pack: str = "batched",
              verbose: bool = False) -> List[TrialResult]:
    """Run a list of trials and (optionally) append each finished trial to
    ``store`` as it completes — the unit of resume is the trial, so a killed
    sweep restarts exactly at the first unfinished key.

    ``engine='vectorized'`` packs EVERY trial (sync trials per virtual
    round, async/buffered trials off the merged event queue; compressed
    trials quantize per lane inside the pack — nothing falls back).
    ``engine='sequential'`` runs everything one ``FLServer.run()`` at a
    time — engines are result-parity-equal, so stores can mix them."""
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; valid engines: "
                         + ", ".join(ENGINES))
    results: List[TrialResult] = []

    def emit(res: TrialResult):
        results.append(res)
        if store is not None:
            store.append(res.to_record())

    if engine == "sequential":
        for spec in specs:
            emit(run_trial(spec))
        return results

    if specs:
        run_vectorized(specs, pack=pack, on_result=emit, verbose=verbose)
    return results
