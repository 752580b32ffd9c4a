"""FL server: round orchestration, participant selection, cost accounting,
evaluation, and the tuner hook (FedTune plugs in here).

This is the *simulation* loop used for the paper's experiments (small
models, CPU).  Since the event-driven runtime landed (repro.runtime), the
server is a thin facade: ``run()`` hands orchestration to the runtime engine
(sync / async / buffered execution over a device fleet), and the original
synchronous-homogeneous loop survives as ``run_legacy()`` — the runtime's
sync mode over a homogeneous fleet reproduces it round for round, which
``tests/test_runtime.py`` pins down.

The datacenter execution path — participants as mesh shards with psum
aggregation — lives in launch/train.py and is what the multi-pod dry-run
lowers.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Tuple

import jax
import numpy as np

from repro import obs
from repro.core.costs import CostModel, SystemCost
from repro.core.tuner import HyperParams, Tuner
from repro.data.synthetic import FederatedDataset
from repro.federated.aggregation import Aggregator, ClientUpdate
from repro.federated.client import local_train
from repro.federated.evaluation import eval_due
from repro.models.registry import Model
from repro.optim.optimizers import Optimizer


@dataclass
class FLConfig:
    m: int = 20                    # initial participants per round
    e: float = 20.0                # initial local passes
    batch_size: int = 5
    target_accuracy: float = 0.8
    max_rounds: int = 500
    eval_points: int = 1024
    prox_mu: float = 0.0
    seed: int = 0
    eval_every: int = 1
    log_every: int = 0             # 0 = silent
    selection: str = "random"      # random | guided | smallest | deadline
    compression: Optional[str] = None  # None | "int8" upload deltas


@dataclass
class RoundRecord:
    round_idx: int
    m: int
    e: float
    accuracy: float
    cost: SystemCost
    wall_time: float
    sim_time: float = 0.0          # virtual clock at the end of the round
    n_updates: int = -1            # arrivals aggregated (-1 = legacy loop)


@dataclass
class FLResult:
    reached_target: bool
    rounds: int
    final_accuracy: float
    total_cost: SystemCost
    history: List[RoundRecord]
    final_m: int
    final_e: float
    params: Any = None             # final global model parameters
    sim_time: float = 0.0          # total virtual wall-clock (runtime modes)
    dispatch_log: Optional[List[tuple]] = None   # async/buffered: every
                                   # dispatch as (virtual t, cid, version)
    staleness_log: Optional[List[int]] = None    # async/buffered: staleness
                                   # of each applied (non-dropout) arrival


class FLServer:
    def __init__(self, model: Model, dataset: FederatedDataset,
                 aggregator: Aggregator, optimizer: Optimizer,
                 cost_model: CostModel, config: FLConfig,
                 tuner: Optional[Tuner] = None,
                 fleet=None, runtime_config=None):
        self.model = model
        self.dataset = dataset
        self.aggregator = aggregator
        self.optimizer = optimizer
        self.cost_model = cost_model
        self.config = config
        self.tuner = tuner or Tuner()
        self.rng = np.random.default_rng(config.seed)
        self._evaluator = None
        self.fleet = fleet
        self.runtime_config = runtime_config
        from repro.federated.selection import get_selector
        est_times = None
        if fleet is not None:
            # deadline-aware selection signal: expected dispatch->arrival
            # time per client (download + E passes of compute + upload)
            from repro.federated.compression import upload_factor
            c1 = cost_model.train_flops_per_example
            down, up = cost_model.traffic_halves(
                upload_factor(config.compression))
            # one vectorized pass (bit-identical per element to the scalar
            # est_round_time loop it replaced; works for VirtualFleet too,
            # where per-cid scalar indexing would draw one hash at a time)
            est_times = np.asarray(fleet.est_round_times(
                np.arange(dataset.n_clients),
                np.asarray(dataset.client_sizes, np.float64),
                config.e, c1, down, up))
        self.selector = get_selector(config.selection, dataset.n_clients,
                                     self.rng,
                                     client_sizes=dataset.client_sizes,
                                     est_times=est_times)

    # ------------------------------------------------------------------
    @property
    def evaluator(self):
        """This trial's ``Evaluator`` (federated/evaluation.py): the jitted
        accuracy kernel comes from the shared bounded LRU and the test
        batches from the per-dataset staging cache, so the T servers of a
        sweep share one compilation and one on-device test set."""
        if self._evaluator is None:
            from repro.federated.evaluation import Evaluator
            self._evaluator = Evaluator(self.model, self.dataset,
                                        self.config.eval_points)
        return self._evaluator

    def _evaluate(self, params) -> float:
        return self.evaluator.evaluate(params)

    # ------------------------------------------------------------------
    def _client_update(self, params, cid: int, e: float
                       ) -> Tuple[ClientUpdate, int]:
        """Run one client's local training against ``params``.  Shared by the
        legacy loop and the event-driven runtime so both consume the server
        rng stream identically (batch permutations)."""
        cfg = self.config
        x, y = self.dataset.client_data(int(cid))
        with obs.span("TRAIN", phase="train", n_lanes=1):
            upd = local_train(
                self.model, params, x, y, passes=e,
                batch_size=cfg.batch_size, optimizer=self.optimizer,
                rng=self.rng, prox_mu=cfg.prox_mu)
            if cfg.compression:
                from repro.federated.compression import compress_delta
                upd = upd._replace(params=compress_delta(
                    params, upd.params, cfg.compression))
        upd = upd._replace(client_id=int(cid))
        self.selector.update(int(cid), upd.last_loss, len(y))
        return upd, len(y)

    # ------------------------------------------------------------------
    def run(self, params=None) -> FLResult:
        """Execute FL through the event-driven runtime.  Mode and fleet come
        from ``runtime_config`` / ``fleet`` (defaults: sync execution over a
        homogeneous unit fleet == the legacy loop's behavior)."""
        from repro.runtime.engine import EventDrivenRuntime, RuntimeConfig
        rt = EventDrivenRuntime(self, fleet=self.fleet,
                                config=self.runtime_config or RuntimeConfig())
        return rt.run(params)

    # ------------------------------------------------------------------
    def run_legacy(self, params=None) -> FLResult:
        """The original synchronous, homogeneous round loop (paper setting).
        Kept as the reference the runtime's sync mode is verified against."""
        cfg = self.config
        if params is None:
            params = self.model.init(jax.random.PRNGKey(cfg.seed))
        hp = HyperParams(m=cfg.m, e=cfg.e)
        history: List[RoundRecord] = []
        accuracy = 0.0
        reached = False

        for r in range(cfg.max_rounds):
            t0 = time.perf_counter()  # noqa: REPRO004 -- measures the RoundRecord.wall info field only; costs come from the cost model
            m = min(hp.m, self.dataset.n_clients)
            participants = self.selector.select(m)
            updates: List[ClientUpdate] = []
            examples = []
            for cid in participants:
                upd, n = self._client_update(params, int(cid), hp.e)
                updates.append(upd)
                examples.append(n)
            params = self.aggregator(params, updates)
            from repro.federated.compression import upload_factor
            round_cost = self.cost_model.add_round(
                examples, hp.e,
                upload_factor=upload_factor(cfg.compression))

            if eval_due(r, cfg.eval_every, cfg.max_rounds):
                accuracy = self._evaluate(params)
            wall = time.perf_counter() - t0  # noqa: REPRO004 -- RoundRecord.wall is informational; parity ignores it
            history.append(RoundRecord(r, hp.m, hp.e, accuracy,
                                       round_cost, wall))
            if cfg.log_every and (r + 1) % cfg.log_every == 0:
                print(f"  round {r+1:4d}  acc={accuracy:.4f}  M={hp.m} "
                      f"E={hp.e:g}  wall={wall:.2f}s", flush=True)
            if accuracy >= cfg.target_accuracy:
                reached = True
                break
            hp = self.tuner.on_round(r, accuracy, round_cost,
                                     self.cost_model.total, hp)
            hp = hp.clamped(self.dataset.n_clients, 100.0)

        return FLResult(
            reached_target=reached,
            rounds=len(history),
            final_accuracy=accuracy,
            total_cost=self.cost_model.total.copy(),
            history=history,
            final_m=hp.m,
            final_e=hp.e,
            params=params,
        )
