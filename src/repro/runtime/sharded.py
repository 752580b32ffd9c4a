"""Clients-as-mesh-axis sharded cohort execution.

The batched path (batched.py) scales the cohort with ONE device's FLOPs:
scan-over-steps, vmap-over-clients, the whole cohort resident on a single
chip — beyond M ~ 64 that chip is the bottleneck.  Here the same
size-bucketed cohort is laid out along a 1-D ``clients`` mesh axis
(launch/mesh.py: ``make_clients_mesh``) under ``shard_map``: every device
holds M/D client slots, runs the identical ``cohort_scan`` body (shared
with batched.py) on its slice, reduces its slots' trained params to a
weighted partial sum through the fused ``fed_reduce`` kernel path (the
int8 upload round trip of compressed cohorts runs inside the same
dispatch), and a ``lax.psum`` over the ``clients`` axis completes the
FedAvg weighted mean ON DEVICE.  The host only ever receives the aggregated (N,) parameter
vector plus per-client scalar losses — a round never materializes (M, N)
per-client params off-device, so cohort size scales with device count.

Parity contract (pinned in tests/test_sharded.py the same way
tests/test_runtime.py pins batched-vs-sequential): batch streams are
materialized in client order from the same rng as the sequential/batched
paths, bucketing is shared with batched.py, and the on-device weighted
mean equals FedAvg over the batched path's per-client results up to float
reassociation.

Each bucket's cohort is padded up to a multiple of the axis size with
zero-weight client slots (all-False step masks freeze them at the global
params, zero aggregation weight erases them), so every shard is
shape-identical; padding waste per bucket is under one device row.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.federated.aggregation import _flatten, _unflatten
from repro.kernels import ops as kernel_ops
from repro.launch.mesh import make_clients_mesh
from repro.optim.optimizers import Optimizer
from repro.models.registry import Model
from repro.runtime.batched import (_stack_streams, bucket_by_steps,
                                   cohort_scan, make_client_step,
                                   materialize_streams)
from repro.sharding.specs import clients_spec

_sharded_fn_cache = {}
_default_mesh_cache = None


def default_clients_mesh():
    """The process-wide ``clients`` mesh over every addressable device.
    Cached so repeated rounds reuse one mesh object (and therefore one
    compiled cohort program per (T, M) shape)."""
    global _default_mesh_cache
    if _default_mesh_cache is None:
        _default_mesh_cache = make_clients_mesh()
    return _default_mesh_cache


class ShardedRound(NamedTuple):
    """Result of one sharded cohort round (input client order)."""
    params: Any                # FedAvg weighted mean over the cohort
    last_losses: np.ndarray    # per-client final local loss
    n_steps: List[int]         # local steps actually taken per client
    n_examples: List[int]      # client dataset sizes (the FedAvg weights)


def _flatten_cohort(params_b):
    """A (M, ...) stacked params pytree -> (M, N) row matrix, leaf order
    matching ``aggregation._flatten`` so flat vectors interconvert."""
    leaves = jax.tree.leaves(params_b)
    m = leaves[0].shape[0]
    return jnp.concatenate([l.reshape(m, -1) for l in leaves], axis=1)


def _make_sharded_cohort_fn(model: Model, optimizer: Optimizer,
                            prox_mu: float, mesh,
                            compression: Optional[str] = None):
    key = (id(model), id(optimizer), prox_mu, id(mesh),
           compression if compression not in (None, "none") else None)
    if key in _sharded_fn_cache:
        return _sharded_fn_cache[key]

    one_client = make_client_step(model, optimizer, prox_mu)
    axis = mesh.axis_names[0]
    compressed = compression not in (None, "none")

    def shard_body(xs, ys, masks, active, weights, global_params):
        """Runs on one device with its slice of the cohort: the shared
        scan/vmap body over the local client slots, then ONE ``fed_reduce``
        call fusing the upload round trip when compression is on (the
        aggregate must be formed from what the server would reconstruct)
        with the local weighted partial sum, completed by a psum across
        the clients axis."""
        m_loc = active.shape[1]
        global_b = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (m_loc,) + p.shape), global_params)
        opt_b = jax.vmap(optimizer.init)(global_b)
        params_b, last_loss = cohort_scan(
            one_client, global_b, opt_b, xs, ys, masks, active,
            global_params)
        flat = _flatten_cohort(params_b)                   # (M_loc, N)
        seg = jnp.zeros(m_loc, jnp.int32)
        # static at trace time: per-leaf widths for the fused quant scales
        leaf_sizes = tuple(int(np.prod(p.shape))
                           for p in jax.tree.leaves(global_params))
        qref = _flatten_cohort(jax.tree.map(
            lambda p: p[None], global_params))             # (1, N)
        partial = kernel_ops.fed_reduce(                   # (1, N)
            weights, flat, seg, 1,
            leaf_sizes=leaf_sizes if compressed else None,
            quant_ref=qref if compressed else None)
        return jax.lax.psum(partial[0], axis), last_loss

    @jax.jit
    def cohort_step_sharded(xs, ys, masks, active, weights, global_params):
        in_specs = (clients_spec(xs.ndim, 1, axis),
                    clients_spec(ys.ndim, 1, axis),
                    clients_spec(masks.ndim, 1, axis),
                    clients_spec(active.ndim, 1, axis),
                    clients_spec(1, 0, axis),
                    jax.tree.map(lambda _: P(), global_params))
        # check_vma off: cohort_scan's carry starts device-invariant (the
        # zero losses, replicated globals) and turns per-device after one
        # step, which the varying-axes type check rejects as a carry change
        return jax.shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                             out_specs=(P(), clients_spec(1, 0, axis)),
                             check_vma=False)(
                                 xs, ys, masks, active, weights,
                                 global_params)

    _sharded_fn_cache[key] = cohort_step_sharded
    return cohort_step_sharded


def sharded_fedavg_train(model: Model, global_params,
                         data: Sequence[Tuple[np.ndarray, np.ndarray]], *,
                         passes: float, batch_size: int,
                         optimizer: Optimizer, rng: np.random.Generator,
                         prox_mu: float = 0.0,
                         client_ids: Optional[Sequence[int]] = None,
                         mesh=None,
                         compression: Optional[str] = None) -> ShardedRound:
    """Train the whole cohort sharded over the ``clients`` mesh axis and
    return the FedAvg aggregate directly (weights n_k / n_total), without
    materializing per-client params on the host.  ``client_ids`` is
    accepted for signature symmetry with ``batched_local_train``; results
    come back in input order regardless.  ``compression`` applies the
    upload round trip per lane on device, before the fused aggregation."""
    del client_ids
    mesh = mesh if mesh is not None else default_clients_mesh()
    n_dev = int(np.prod(mesh.devices.shape))
    run = _make_sharded_cohort_fn(model, optimizer, prox_mu, mesh,
                                  compression)
    streams, n_steps = materialize_streams(data, batch_size, passes, rng)
    assert max(n_steps) > 0, "cohort with zero local steps"
    sizes = [len(y) for _, y in data]
    w = np.asarray(sizes, np.float64) / float(sum(sizes))  # FedAvg weights

    global_flat, meta = _flatten(global_params)
    agg = jnp.zeros_like(global_flat)
    losses = np.zeros(len(data), np.float64)
    for t_pad, idx in sorted(bucket_by_steps(n_steps).items()):
        pad_m = (-len(idx)) % n_dev
        xs, ys, masks, active = _stack_streams(
            [streams[i] for i in idx] + [[]] * pad_m, batch_size, t_pad)
        wb = np.zeros(len(idx) + pad_m, np.float32)
        wb[:len(idx)] = w[idx]
        part, last_loss = run(jnp.asarray(xs), jnp.asarray(ys),
                              jnp.asarray(masks), jnp.asarray(active),
                              jnp.asarray(wb), global_params)
        agg = agg + part
        losses[idx] = np.asarray(last_loss)[:len(idx)]

    # 0-step clients never trained: they enter the FedAvg mean at the
    # global params, exactly as the batched/sequential paths include them
    zero_w = float(sum(w[i] for i, t in enumerate(n_steps) if t == 0))
    if zero_w > 0.0:
        agg = agg + zero_w * global_flat
    return ShardedRound(params=_unflatten(agg, meta), last_losses=losses,
                        n_steps=n_steps, n_examples=sizes)
