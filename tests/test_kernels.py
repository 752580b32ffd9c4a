"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels import ops as kernel_ops
from repro.kernels.fed_aggregate import fed_aggregate
from repro.kernels.fed_reduce import BLOCK_M
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("m,n", [(1, 256), (4, 1000), (16, 8192), (50, 4097),
                                 (BLOCK_M + 44, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fed_aggregate_sweep(m, n, dtype):
    ks = jax.random.split(KEY, 3)
    w = jax.random.uniform(ks[0], (m,), jnp.float32)
    w = w / w.sum()
    d = jax.random.normal(ks[1], (m, n)).astype(dtype)
    base = jax.random.normal(ks[2], (n,)).astype(dtype)
    got = fed_aggregate(w, d, base, interpret=True)
    want = ref.fed_aggregate_ref(w, d, base)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_fed_aggregate_is_weighted_mean():
    # aggregating identical deltas with normalized weights is identity
    d = jnp.ones((5, 100)) * 3.0
    w = jnp.full((5,), 0.2)
    got = fed_aggregate(w, d, interpret=True)
    np.testing.assert_allclose(np.asarray(got), 3.0, rtol=1e-6)


@pytest.mark.parametrize("b,h,kh,s,d", [
    (1, 2, 1, 128, 32), (2, 4, 2, 256, 64), (1, 4, 4, 256, 128),
])
@pytest.mark.parametrize("window,cap", [
    (None, None), (64, None), (None, 50.0), (96, 30.0),
])
def test_flash_attention_sweep(b, h, kh, s, d, window, cap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, kh, s, d))
    v = jax.random.normal(ks[2], (b, kh, s, d))
    got = flash_attention(q, k, v, window=window, cap=cap,
                          block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtype(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(dtype)
    got = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,t,w", [(1, 128, 128), (2, 256, 128),
                                   (4, 128, 512), (3, 192, 384)])
def test_rglru_scan_sweep(b, t, w):
    ks = jax.random.split(KEY, 2)
    a = jax.random.uniform(ks[0], (b, t, w), minval=0.5, maxval=0.999)
    x = jax.random.normal(ks[1], (b, t, w)) * 0.1
    got = rglru_scan(a, x, block_b=1, block_w=128, chunk_t=64, interpret=True)
    want = ref.rglru_scan_ref(a, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_rglru_scan_decay_property():
    """With b=0 everywhere, h stays 0; with a=0, h_t = b_t."""
    a = jnp.full((1, 64, 128), 0.9)
    z = jnp.zeros((1, 64, 128))
    out = rglru_scan(a, z, chunk_t=32, block_b=1, block_w=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 0.0)
    b = jax.random.normal(KEY, (1, 64, 128))
    out2 = rglru_scan(jnp.zeros_like(b), b, chunk_t=32, block_b=1,
                      block_w=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(b), rtol=1e-6)


# ---------------------------------------------------------------------------
# fed_reduce: fused segment aggregation (normalize + int8 round trip +
# segment-sum + base), PR-10.  The contract under test is twofold:
#   * Pallas kernel == the jitted jnp reference, bit for bit (both are
#     production dispatch targets of kernels/ops.fed_reduce);
#   * packing invariance — lane t of a T-segment call equals a standalone
#     T=1 call over that lane's rows, bit for bit (what lets the sweep
#     engines fuse T trials into one dispatch while staying parity-pinned
#     against the one-trial-at-a-time FLServer).
# ---------------------------------------------------------------------------

def _reduce_case(m, n, t, seed, *, interleave=False, zero_w=0):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    w = jnp.asarray(rng.uniform(1.0, 100.0, m).astype(np.float32))
    if zero_w:
        w = w.at[jnp.asarray(rng.choice(m, zero_w, replace=False))].set(0.0)
    if interleave:
        seg = jnp.asarray(rng.integers(0, t, m).astype(np.int32))
    else:
        seg = jnp.asarray(np.sort(rng.integers(0, t, m)).astype(np.int32))
    base = jnp.asarray(rng.standard_normal((t, n)).astype(np.float32))
    return w, rows, seg, base


@pytest.mark.parametrize("m,n,t", [(1, 256, 1), (7, 300, 3), (16, 1024, 4),
                                   (33, 4097, 8), (BLOCK_M + 44, 300, 3),
                                   (2 * BLOCK_M, 300, 3)])
@pytest.mark.parametrize("mode", ["plain", "normalize", "base", "quant"])
def test_fed_reduce_pallas_matches_ref_bitwise(m, n, t, mode):
    """Interpret-mode Pallas == jitted reference, bit for bit, in every
    fusion mode — including non-pow2 row counts and column tails (the
    kernel pads N to its column block and M to its row block).  Past
    BLOCK_M rows the fold walks several row blocks, segments straddle a
    block edge, and the filler rows of a part-filled last block select no
    lane."""
    w, rows, seg, base = _reduce_case(m, n, t, seed=m * 1000 + n)
    kw = {}
    if mode == "normalize":
        kw["normalize"] = True
    if mode == "base":
        kw = {"normalize": True}
    if mode == "quant":
        kw = {"normalize": True, "leaf_sizes": (n // 3, n - n // 3),
              "quant_ref": base, "quant_enabled": jnp.ones(m, bool)}
    b = base if mode in ("base", "quant") else None
    got = kernel_ops.fed_reduce(w, rows, seg, t, b,
                                force_pallas=True, interpret=True, **kw)
    want = kernel_ops.fed_reduce(w, rows, seg, t, b, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("interleave", [False, True])
@pytest.mark.parametrize("quant", [False, True])
def test_fed_reduce_packing_invariance(interleave, quant):
    """Lane t of a fused T-segment call == a standalone T=1 call over that
    lane's rows in pack order, bit for bit — even when segments are
    interleaved rather than contiguous."""
    m, n, t = 24, 513, 5
    w, rows, seg, base = _reduce_case(m, n, t, seed=42,
                                      interleave=interleave)
    kw = dict(normalize=True)
    if quant:
        kw.update(leaf_sizes=(200, n - 200), quant_ref=base,
                  quant_enabled=jnp.ones(m, bool))
    fused = kernel_ops.fed_reduce(w, rows, seg, t, base, **kw)
    segs = np.asarray(seg)
    for s in range(t):
        idx = np.nonzero(segs == s)[0]
        kw1 = dict(normalize=True)
        if quant:
            kw1.update(leaf_sizes=(200, n - 200),
                       quant_ref=base[s][None],
                       quant_enabled=jnp.ones(len(idx), bool))
        if len(idx) == 0:
            # empty segment: base passes through untouched
            np.testing.assert_array_equal(np.asarray(fused[s]),
                                          np.asarray(base[s]))
            continue
        alone = kernel_ops.fed_reduce(
            w[idx], rows[idx], jnp.zeros(len(idx), jnp.int32), 1,
            base[s][None], **kw1)
        np.testing.assert_array_equal(np.asarray(fused[s]),
                                      np.asarray(alone[0]))


def test_fed_reduce_singleton_and_empty_segments():
    """T=4 with one singleton lane, one empty lane: the singleton reduces
    to its (normalized) row + base, the empty lane passes base through."""
    n = 128
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((3, n)).astype(np.float32))
    base = jnp.asarray(rng.standard_normal((4, n)).astype(np.float32))
    w = jnp.asarray([5.0, 2.0, 3.0], jnp.float32)
    seg = jnp.asarray([0, 0, 2], jnp.int32)       # lane 1 and 3 empty
    out = kernel_ops.fed_reduce(w, rows, seg, 4, base, normalize=True)
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(base[1]))
    np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(base[3]))
    # singleton lane: w/tot == 1 exactly, so lane 2 is row + base
    one = kernel_ops.fed_reduce(w[2:], rows[2:],
                                jnp.zeros(1, jnp.int32), 1, base[2][None],
                                normalize=True)
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(one[0]))


def test_fed_reduce_zero_weight_rows_are_bit_neutral():
    """Padding rows with weight 0 (what the engines append to reach pow2
    lane counts) leave every lane bit-identical — the fold adds +/-0.0."""
    m, n, t = 12, 257, 3
    w, rows, seg, base = _reduce_case(m, n, t, seed=7)
    out = kernel_ops.fed_reduce(w, rows, seg, t, base, normalize=True)
    rng = np.random.default_rng(8)
    pad = jnp.asarray(rng.standard_normal((5, n)).astype(np.float32))
    w2 = jnp.concatenate([w, jnp.zeros(5, jnp.float32)])
    rows2 = jnp.concatenate([rows, pad])
    seg2 = jnp.concatenate([seg, jnp.asarray([0, 1, 2, 0, 1], jnp.int32)])
    out2 = kernel_ops.fed_reduce(w2, rows2, seg2, t, base, normalize=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_fed_reduce_per_lane_quant_mask():
    """quant_enabled gates the round trip per ROW: disabled rows pass
    through untouched, and a mixed-mask call equals quantizing exactly the
    enabled rows up front, bit for bit."""
    m, n, t = 10, 300, 2
    w, rows, seg, base = _reduce_case(m, n, t, seed=11)
    ls = (100, n - 100)
    en = jnp.asarray(np.arange(m) % 2 == 0)
    mixed = kernel_ops.fed_reduce(w, rows, seg, t, base, normalize=True,
                                  leaf_sizes=ls, quant_ref=base,
                                  quant_enabled=en)
    pre = jax.jit(ref._quant_rows, static_argnames=("leaf_sizes",))(
        rows, seg, base, en, ls)
    want = kernel_ops.fed_reduce(w, pre, seg, t, base, normalize=True)
    np.testing.assert_array_equal(np.asarray(mixed), np.asarray(want))


def test_fed_reduce_quant_matches_tree_roundtrip():
    """The fused in-kernel round trip == the per-tree compress_delta path
    (both jitted — the production oracle pair), bit for bit through the
    weighted reduce."""
    from repro.federated.aggregation import _flatten, _unflatten
    from repro.federated.compression import _tree_roundtrip

    rng = np.random.default_rng(21)
    gtree = {"w": jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32)),
             "b": jnp.asarray(rng.standard_normal(16).astype(np.float32))}
    gflat, meta = _flatten(gtree)
    leaf_sizes = tuple(meta[2])
    m = 6
    rows = jnp.stack([
        gflat + jnp.asarray(
            rng.standard_normal(gflat.size).astype(np.float32)) * 0.1
        for _ in range(m)])
    w = jnp.asarray(rng.uniform(1, 50, m).astype(np.float32))
    seg = jnp.zeros(m, jnp.int32)

    fused = kernel_ops.fed_reduce(
        w, rows, seg, 1, gflat[None], normalize=True,
        leaf_sizes=leaf_sizes, quant_ref=gflat[None],
        quant_enabled=jnp.ones(m, bool))

    rt_rows = jnp.stack([
        _flatten(_tree_roundtrip(gtree, _unflatten(rows[i], meta)))[0]
        for i in range(m)])
    want = kernel_ops.fed_reduce(w, rt_rows, seg, 1, gflat[None],
                                 normalize=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(want))


def test_fed_reduce_packing_invariance_property():
    """Property form of the packing-invariance contract over random
    segment layouts, weights (including zeros), and row counts."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 20), st.integers(1, 6), st.integers(0, 3),
           st.randoms(use_true_random=False))
    def prop(m, t, zero_w, rnd):
        seed = rnd.randint(0, 2**31 - 1)
        w, rows, seg, base = _reduce_case(
            m, 65, t, seed, interleave=True, zero_w=min(zero_w, m - 1))
        fused = kernel_ops.fed_reduce(w, rows, seg, t, base,
                                      normalize=True)
        segs = np.asarray(seg)
        for s in range(t):
            idx = np.nonzero(segs == s)[0]
            if len(idx) == 0:
                np.testing.assert_array_equal(np.asarray(fused[s]),
                                              np.asarray(base[s]))
                continue
            alone = kernel_ops.fed_reduce(
                w[idx], rows[idx], jnp.zeros(len(idx), jnp.int32), 1,
                base[s][None], normalize=True)
            np.testing.assert_array_equal(np.asarray(fused[s]),
                                          np.asarray(alone[0]))

    prop()
