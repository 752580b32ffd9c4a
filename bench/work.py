"""The work a cell asks of the chip, counted from the configuration's widths.

These are the benchmark's own counts: a change to the program cannot change
how much work a step is said to hold.

- ``param_count`` and ``train_flops_per_example``: the model's, counted by
  its kind's module (``models/<kind>.py``), which states how.
- ``fed_reduce_traffic``: what the fused FedAvg reduction has to move and
  compute for one call over (M, N) rows into T lanes, whatever implements
  it. Rows are read once; the (T, N) base is read and the (T, N) result
  written; with the int8 round trip the (T, N) quantization reference is
  read too. One multiply and one add per row element, plus six elementwise
  operations per element for the round trip.
"""

from __future__ import annotations

import kinds

F32 = 4


def param_count(model: dict) -> int:
    """Every parameter the server reduces."""
    return kinds.of(model).param_count(model)


def train_flops_per_example(model: dict) -> float:
    """Forward and backward of one example."""
    return kinds.of(model).train_flops_per_example(model)


def fed_reduce_traffic(m: int, n: int, t: int, *, quant: bool = False):
    """(bytes, flops) of one fused reduction call."""
    nbytes = m * n * F32 + 2 * t * n * F32
    if quant:
        nbytes += t * n * F32
    flops = 2.0 * m * n + (6.0 * m * n if quant else 0.0)
    return float(nbytes), flops


def roofline_seconds(nbytes: float, flops: float, peak: dict):
    """Least time on the chip and the bound that sets it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_flop = flops / peak["peak_flops_bf16"]
    return (t_mem, "bytes") if t_mem >= t_flop else (t_flop, "flops")
