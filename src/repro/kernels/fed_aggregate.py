"""Pallas TPU kernel: weighted FL aggregation  out = base + sum_m w_m * delta_m.

This is the server-side hot spot of every FL round (paper eq. 1 aggregation):
a memory-bound weighted reduction over M participant deltas of N parameters.
Tiling: a 2-D grid (column blocks, row blocks).  The parameter axis is cut
into lane-aligned BLOCK_N columns; the rows axis into BLOCK_M-row blocks
walked in order, so VMEM holds one (BLOCK_M, BLOCK_N) tile of deltas and its
(BLOCK_M, 1) weight column, never the whole cohort.  Each step reduces its
tile over M in VREGs into the f32 (1, BLOCK_N) output block, which stays
resident across the row axis; the base tile is added after the last block.
Arithmetic intensity is ~1 FLOP / 2 bytes -> firmly HBM-bandwidth-bound, so
the only job of the kernel is to stream deltas exactly once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fed_reduce import BLOCK_M, BLOCK_N


def _kernel(w_ref, base_ref, x_ref, o_ref):
    # w: (BM, 1) f32, base: (1, BLOCK_N), x: (BM, BLOCK_N), o: (1, BLOCK_N) f32
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.sum(w_ref[...] * x, axis=0, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = o_ref[...] + base_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fed_aggregate(weights, deltas, base=None, *, block_n: int = BLOCK_N,
                  interpret: bool = False):
    """weights: (M,); deltas: (M, N); base: (N,) or None -> (N,)."""
    m, n = deltas.shape
    dtype = deltas.dtype
    if base is None:
        base = jnp.zeros((n,), dtype)
    pad = (-n) % block_n
    if pad:
        deltas = jnp.pad(deltas, ((0, 0), (0, pad)))
        base = jnp.pad(base, (0, pad))
    n_pad = n + pad
    w = weights.astype(jnp.float32)
    bm = min(m, BLOCK_M)
    m_pad = m + (-m) % bm
    if m_pad != m:        # fill the last row block with zero-weight rows
        deltas = jnp.pad(deltas, ((0, m_pad - m), (0, 0)))
        w = jnp.pad(w, (0, m_pad - m))

    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // block_n, m_pad // bm),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((bm, block_n), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w.reshape(m_pad, 1), base.reshape(1, n_pad), deltas)
    return out[0, :n].astype(dtype)
