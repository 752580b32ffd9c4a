"""Evaluation subsystem: jitted accuracy kernels, per-dataset staged test
batches, and stacked multi-trial evaluation.

Evaluation used to live inline on ``FLServer`` (``_evaluate`` plus a
module-level FIFO cache of jitted eval fns).  After the sweep engines
vectorized *training* (trials as vmap lanes), the per-aggregation
evaluation became the dominant cost of vectorized sweeps: T live trials
meant T separate eval dispatches per round even though they share one
model architecture and (per seed) one test set.  This module makes the
trial boundary explicit:

  ``Evaluator``        — one trial's evaluation: a jitted accuracy kernel
                         (shared through a bounded LRU so the T servers of
                         a sweep compile it once) over test batches staged
                         on device once per (dataset, eval_points).
  ``StackedEvaluator`` — T trials' params stacked into one pytree and
                         evaluated by ``jit(vmap(accuracy))`` over the SAME
                         staged batches: one dispatch per test batch
                         evaluates every trial.
  ``evaluate_stacked`` — the grouping entry point the sweep engines call:
                         items grouped by (model, dataset, eval_points),
                         one stacked dispatch per group.

Parity contract (pinned in tests/test_experiments.py): lane i of a stacked
evaluation is BIT-identical to ``Evaluator.evaluate`` on that trial's
params — vmap lanes are computed independently, and the host-side
accumulation (``correct += float(acc) * n`` per batch) is the same float
sequence.  This is what lets the vectorized sweep engines route their
per-aggregation evals through one dispatch while staying bit-identical to
standalone ``FLServer.run()`` calls.

With a multi-device mesh (the sweep's ``--pack sharded``), the stacked
params' trial axis can be laid over the mesh's ``clients`` axis
(``mesh=``): lanes are padded to a multiple of the device count and each
device evaluates its slice of the trials.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

EVAL_BATCH = 256               # test batch staging granularity (bounds memory)


class EvalFnCache:
    """Bounded LRU of jitted accuracy kernels, keyed per model object and
    variant (single vs stacked).

    Replaces the module-level FIFO dict that used to live in
    federated/server.py: entries move to the back on every hit, so the
    models of a live sweep cannot be evicted mid-sweep by a burst of
    one-shot constructions the way FIFO order allowed.  The cached closure
    keeps ``model`` alive, so an ``id()`` key cannot be recycled while its
    entry exists; the bound keeps a long-lived process looping over fresh
    models from pinning them all forever.
    """

    def __init__(self, capacity: int = 32):
        if capacity < 1:
            raise ValueError(f"EvalFnCache capacity must be >= 1, "
                             f"got {capacity}")
        self.capacity = capacity
        self._fns: "OrderedDict[tuple, Any]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._fns)

    def get(self, model, stacked: bool = False):
        """The jitted accuracy kernel for ``model``: ``(params, x, y) ->
        scalar accuracy`` (or, stacked, ``(T-stacked params, x, y) -> (T,)
        accuracies via vmap lane per trial)."""
        key = (id(model), stacked)
        fn = self._fns.get(key)
        if fn is not None:
            self._fns.move_to_end(key)
            if obs.enabled():
                obs.registry.inc("eval_fn_cache_hits")
            return fn
        if obs.enabled():
            obs.registry.inc("eval_fn_cache_misses")

        def eval_accuracy(params, x, y):
            logits = model.forward(params, x)
            return (logits.argmax(-1) == y).mean()

        fn = (jax.jit(jax.vmap(eval_accuracy, in_axes=(0, None, None)))
              if stacked else jax.jit(eval_accuracy))
        while len(self._fns) >= self.capacity:
            self._fns.popitem(last=False)
        self._fns[key] = fn
        return fn


_SHARED_FN_CACHE = EvalFnCache()

# staged test batches, shared across every Evaluator over one dataset: the
# test set never changes across rounds OR trials, so it goes to the device
# once per (dataset, eval_points) instead of once per server.  Entries pin
# the dataset object so the id() key cannot be recycled while they live.
_BATCH_CACHE_MAX = 16
_batch_cache: "OrderedDict[tuple, tuple]" = OrderedDict()


def staged_batches(dataset, eval_points: int,
                   batch_size: int = EVAL_BATCH) -> List[tuple]:
    """The dataset's test set as a list of on-device ``(x, y, n)`` batches,
    staged once per (dataset, eval_points) and shared by every evaluator."""
    key = (id(dataset), eval_points, batch_size)
    hit = _batch_cache.get(key)
    if hit is not None:
        _batch_cache.move_to_end(key)
        if obs.enabled():
            obs.registry.inc("eval_batch_cache_hits")
        return hit[1]
    if obs.enabled():
        obs.registry.inc("eval_batch_cache_misses")
    x, y = dataset.test_data(eval_points)
    batches = [
        (jnp.asarray(x[i:i + batch_size]), jnp.asarray(y[i:i + batch_size]),
         len(y[i:i + batch_size])) for i in range(0, len(y), batch_size)]
    while len(_batch_cache) >= _BATCH_CACHE_MAX:
        _batch_cache.popitem(last=False)
    _batch_cache[key] = (dataset, batches)
    return batches


def eval_due(round_idx: int, eval_every: int, max_rounds: int) -> bool:
    """The shared evaluation schedule: every ``eval_every`` rounds and on
    the final round of the budget.  One definition for the legacy loop,
    the runtime engine, and the sweep engines — the schedule is part of
    the bit-parity contract."""
    return (round_idx + 1) % eval_every == 0 or round_idx == max_rounds - 1


def _tree_stack(trees: Sequence[Any]):
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


class Evaluator:
    """One trial's evaluation: jitted accuracy kernel + staged test batches.

    ``fn_cache`` defaults to the process-wide shared LRU so the T servers
    of a sweep (or repeated benchmark constructions over one model) share
    a single compilation; tests inject a tiny cache to pin eviction
    behavior."""

    def __init__(self, model, dataset, eval_points: int,
                 fn_cache: Optional[EvalFnCache] = None):
        self.model = model
        self.dataset = dataset
        self.eval_points = eval_points
        self.fn_cache = fn_cache if fn_cache is not None else _SHARED_FN_CACHE

    def evaluate(self, params) -> float:
        """Accuracy of ``params`` over the staged test batches."""
        fn = self.fn_cache.get(self.model)
        correct, total = 0.0, 0
        with obs.span("eval", phase="eval", n_lanes=1):
            for bx, by, n in staged_batches(self.dataset, self.eval_points):
                correct += float(fn(params, bx, by)) * n
                total += n
        return correct / total


class StackedEvaluator:
    """T trials' evaluation as one workload: a T-stacked params pytree
    through ``jit(vmap(accuracy))`` over the shared staged batches — one
    dispatch per test batch instead of one per (trial, batch).

    Lane i is bit-identical to ``Evaluator.evaluate(params_list[i])``:
    vmap lanes are independent and the per-batch host accumulation is the
    same float sequence."""

    def __init__(self, model, dataset, eval_points: int,
                 fn_cache: Optional[EvalFnCache] = None):
        self.model = model
        self.dataset = dataset
        self.eval_points = eval_points
        self.fn_cache = fn_cache if fn_cache is not None else _SHARED_FN_CACHE

    def evaluate(self, params_list: Sequence[Any],
                 mesh=None, pad_to: Optional[int] = None) -> List[float]:
        """Per-trial accuracies for a list of params pytrees.  With
        ``mesh``, the trial axis is laid over the mesh's first axis
        (lanes padded to a multiple of the device count).  ``pad_to``
        pads the lane axis up to a caller-chosen width first (extra lanes
        repeat lane 0 and are discarded) — the sweep engines key it off
        the live-lane mask (pow2 of the due count) so the compiled
        stacked shape stays stable as trials retire and fresh ones are
        admitted mid-flight, instead of recompiling for every distinct
        live count.  Padding is bit-parity-safe: vmap lanes are
        independent, so lane i never sees the padding."""
        t = len(params_list)
        if t == 0:
            return []
        if t == 1:
            # a singleton group gains nothing from the stacked variant;
            # route it through the single-trial kernel (bit-identical)
            return [Evaluator(self.model, self.dataset, self.eval_points,
                              self.fn_cache).evaluate(params_list[0])]
        stacked_list = list(params_list)
        if pad_to is not None and pad_to > t:
            stacked_list = stacked_list + [stacked_list[0]] * (pad_to - t)
        if mesh is not None:
            n_dev = int(np.prod(mesh.devices.shape))
            pad = (-len(stacked_list)) % n_dev
            stacked_list = stacked_list + [stacked_list[0]] * pad
        stacked = _tree_stack(stacked_list)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            stacked = jax.device_put(
                stacked, NamedSharding(mesh, P(mesh.axis_names[0])))
        fn = self.fn_cache.get(self.model, stacked=True)
        correct = [0.0] * t
        total = 0
        with obs.span("eval_stacked", phase="eval", n_lanes=t):
            for bx, by, n in staged_batches(self.dataset, self.eval_points):
                accs = np.asarray(fn(stacked, bx, by))
                for i in range(t):
                    correct[i] += float(accs[i]) * n
                total += n
        return [c / total for c in correct]


def _pow2_lanes(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def evaluate_stacked(items: Sequence[Tuple[Any, Any, int, Any]],
                     mesh=None, pad_pow2: bool = False) -> List[float]:
    """Batch-evaluate many trials: ``items`` holds one ``(model, dataset,
    eval_points, params)`` per trial; trials sharing a (model, dataset,
    eval_points) group execute as ONE stacked dispatch per test batch.
    Returns accuracies in item order.

    ``pad_pow2`` pads each group's lane axis to a pow2 of its LIVE size
    (parity-safe — see ``StackedEvaluator.evaluate``), bounding the set
    of compiled stacked shapes as a draining or continuously-batched
    pool's due count churns."""
    groups: Dict[tuple, List[int]] = {}
    for i, (model, dataset, eval_points, _params) in enumerate(items):
        groups.setdefault((id(model), id(dataset), eval_points),
                          []).append(i)
    out: List[float] = [0.0] * len(items)
    for idx in groups.values():
        model, dataset, eval_points, _ = items[idx[0]]
        pad_to = _pow2_lanes(len(idx)) if pad_pow2 else None
        accs = StackedEvaluator(model, dataset, eval_points).evaluate(
            [items[i][3] for i in idx], mesh=mesh, pad_to=pad_to)
        for i, acc in zip(idx, accs):
            out[i] = acc
    return out
