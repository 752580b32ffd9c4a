"""Pallas TPU kernel: fused segment aggregation over a packed cohort.

    out[t] = base[t] + sum_{m : seg[m] == t} w_m * roundtrip(row_m)

This is the server-side hot path of the multi-trial sweep engines: every
lane (trial slot) of the packed flat cohort reduces to its own (N,)
parameter vector in ONE dispatch, where the pre-fusion code issued a
jitted call per lane (per-trial ``fed_aggregate``) plus separate jitted
weight-normalization and int8-dequant round trips.

Layout: a 2-D grid (column blocks, row blocks).  The parameter axis is cut
into lane-aligned BLOCK_N columns ("parallel"); the rows axis is cut into
BLOCK_M-row blocks walked in order ("arbitrary"), so VMEM holds one
(BLOCK_M, BLOCK_N) row tile, never the whole cohort.  Per grid step the
kernel sees that row tile, its (BLOCK_M, 1) weight column, the (T, BLOCK_N)
base tile and the (T, BLOCK_N) f32 output block, which stays resident
across the row axis and carries the accumulator; the base is added after
the last row block.  Segment ids reach the kernel through SMEM (scalar
prefetch) and each row is read from a VMEM scratch with ``pl.ds(m, 1)``.
Scoped VMEM is about 26 KiB per tile row (the double-buffered row tile,
the ``w * x`` scratch, the lane-padded weight column), about 6.5 MiB at
BLOCK_M = 256, whatever the cohort size.

Bit-exactness: the weight multiply is stored to the scratch tile before
the fold reads it, so no mul+add pair exists to contract; the fold then
adds rows one at a time in pack order (``jnp.where`` lane select), block
after block — the exact op sequence of ``ref.fed_reduce_ref``'s scan.  So
Pallas output matches the reference bitwise, and lane t of a fused call
matches a standalone T=1 call.  Rows the wrapper appends to fill the last
row block carry segment id -1, which selects no lane.  The quantization
round trip and weight normalization are shared jnp pre-passes from
``kernels/ref.py`` inside the same jit: per-leaf quant scales are a
full-row reduction, which cannot be formed inside a column-blocked grid
step, so they are computed once up front and the whole program still
lowers to a single XLA dispatch around the pallas_call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

BLOCK_N = 2048  # lane-aligned (16 x 128) f32 tile per cohort row
BLOCK_M = 256   # cohort rows per grid step (bounds scoped VMEM)


def _kernel(seg_ref, w_ref, base_ref, x_ref, o_ref, wx_ref):
    # seg: (M_pad,) i32 in SMEM, w: (BM, 1) f32 (normalized),
    # base: (T, BLOCK_N), x: (BM, BLOCK_N), o: (T, BLOCK_N) f32 accumulator,
    # wx: (BM, BLOCK_N) f32 scratch
    j = pl.program_id(1)
    bm = x_ref.shape[0]

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    wx_ref[...] = w_ref[...] * x_ref[...]        # stored before the fold: no
    lanes = jax.lax.broadcasted_iota(            # mul+add to contract
        jnp.int32, (o_ref.shape[0], 1), 0)

    def fold(m, acc):
        s = seg_ref[j * bm + m]
        return jnp.where(lanes == s, acc + wx_ref[pl.ds(m, 1), :], acc)

    o_ref[...] = jax.lax.fori_loop(0, bm, fold, o_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = o_ref[...] + base_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "normalize", "leaf_sizes", "block_n", "interpret"))
def fed_reduce(weights, rows, segments, num_segments, base=None, *,
               normalize: bool = False, leaf_sizes=None, quant_ref=None,
               quant_enabled=None, block_n: int = BLOCK_N,
               interpret: bool = False):
    """weights: (M,); rows: (M, N); segments: (M,) -> (num_segments, N).
    Same contract as ``ref.fed_reduce_ref`` (its bit-matching oracle)."""
    m, n = rows.shape
    t = num_segments
    seg = segments.astype(jnp.int32)
    x = rows.astype(jnp.float32)
    if quant_ref is not None:
        x = _ref._quant_rows(x, seg, quant_ref, quant_enabled, leaf_sizes)
    w = _ref._norm_weights(weights, seg, t, normalize)
    if base is None:
        base = jnp.zeros((t, n), rows.dtype)
    pad = (-n) % block_n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        base = jnp.pad(base, ((0, 0), (0, pad)))
    n_pad = n + pad
    bm = min(m, BLOCK_M)
    m_pad = m + (-m) % bm
    if m_pad != m:        # fill the last row block: weight 0, no lane
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
        w = jnp.pad(w, (0, m_pad - m))
        seg = jnp.pad(seg, (0, m_pad - m), constant_values=-1)

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_pad // block_n, m_pad // bm),
            in_specs=[
                pl.BlockSpec((bm, 1), lambda i, j, s: (j, 0)),
                pl.BlockSpec((t, block_n), lambda i, j, s: (0, i)),
                pl.BlockSpec((bm, block_n), lambda i, j, s: (j, i)),
            ],
            out_specs=pl.BlockSpec((t, block_n), lambda i, j, s: (0, i)),
            scratch_shapes=[pltpu.VMEM((bm, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(seg, w.reshape(m_pad, 1), base, x)
    return out[:, :n].astype(rows.dtype)
