"""jit'd dispatch wrappers for the Pallas kernels.

On a TPU backend (``on_tpu()``) the Pallas kernels always run natively.
On any other backend — CPU hosts, and the test suite under
``JAX_PLATFORMS=cpu`` — they run in interpret mode when a caller forces
them (``force_pallas=True``), and otherwise take the pure-jnp reference
paths of ``kernels/ref.py``.  The reference branch is therefore never
taken on the chip: ``chip_smoke.py`` refuses to run unless JAX reports a
TPU, and on a TPU ``on_tpu()`` is true for every call.
"""

from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref
from repro.kernels.fed_aggregate import fed_aggregate as _fed_aggregate_pallas
from repro.kernels.fed_reduce import fed_reduce as _fed_reduce_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.rglru_scan import rglru_scan as _rglru_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fed_aggregate(weights, deltas, base=None, *, force_pallas: bool = False,
                  interpret: Optional[bool] = None):
    """Weighted aggregation of participant deltas (server-side hot spot)."""
    if on_tpu() or force_pallas:  # noqa: REPRO003 -- host-side backend dispatch flag, never traced; this wrapper runs eagerly and jits its target
        itp = (not on_tpu()) if interpret is None else interpret
        return _fed_aggregate_pallas(weights, deltas, base, interpret=itp)
    return ref.fed_aggregate_ref(weights, deltas, base)


_fed_reduce_ref_jit = jax.jit(
    ref.fed_reduce_ref,
    static_argnames=("num_segments", "normalize", "leaf_sizes"))


def fed_reduce(weights, rows, segments, num_segments, base=None, *,
               normalize: bool = False, leaf_sizes=None, quant_ref=None,
               quant_enabled=None, force_pallas: bool = False,
               interpret: Optional[bool] = None):
    """Fused segment aggregation of a packed multi-trial cohort: weight
    normalization + optional int8 round trip + segment-sum + per-lane base
    add, one dispatch for all lanes.  Lane t is BIT-identical to a
    standalone ``num_segments=1`` call over that lane's rows (the parity
    contract every sweep engine leans on; see kernels/ref.py)."""
    if on_tpu() or force_pallas:
        itp = (not on_tpu()) if interpret is None else interpret
        return _fed_reduce_pallas(
            weights, rows, segments, num_segments, base,
            normalize=normalize, leaf_sizes=leaf_sizes, quant_ref=quant_ref,
            quant_enabled=quant_enabled, interpret=itp)
    return _fed_reduce_ref_jit(
        weights, rows, segments, num_segments, base, normalize=normalize,
        leaf_sizes=leaf_sizes, quant_ref=quant_ref,
        quant_enabled=quant_enabled)


def flash_attention(q, k, v, *, causal=True, window=None, cap=None,
                    force_pallas: bool = False,
                    interpret: Optional[bool] = None):
    """(B,H,S,D) x (B,Kh,T,D) -> (B,H,S,D)."""
    if on_tpu() or force_pallas:
        itp = (not on_tpu()) if interpret is None else interpret
        return _flash_pallas(q, k, v, causal=causal, window=window, cap=cap,
                             interpret=itp)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   cap=cap)


def rglru_scan(a, b, *, force_pallas: bool = False,
               interpret: Optional[bool] = None):
    """Diagonal linear recurrence (RecurrentGemma mixer)."""
    if on_tpu() or force_pallas:
        itp = (not on_tpu()) if interpret is None else interpret
        return _rglru_pallas(a, b, interpret=itp)
    return ref.rglru_scan_ref(a, b)
