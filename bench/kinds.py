"""The model of a configuration, found by its ``kind``.

Everything that depends on which model a configuration serves sits in
``models/<kind>.py``, one module per kind, imported by path as the metric
readers are. A module holds:

- ``place(config)``: make the program serve the configuration's model
  (program imports inside the function);
- ``leaves(params) -> list``: a served model as float32 numpy arrays, in the
  reference's leaf order;
- ``init_params(model, seed)`` and ``logits(params, x, prec, model)``: the
  plain reference's model, ``x`` the flat ``(batch, features)`` batch that
  ``reference.batches`` makes;
- ``param_count(model)``, ``forward_flops(model)`` and
  ``train_flops_per_example(model)``: the benchmark's own work counts.

A kind module imports nothing of the program at its top level, so that the
reference, which loads it, stays independent of the program.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

MODELS = Path(__file__).resolve().parent / "models"

_loaded: dict = {}


def load(kind: str):
    """The module of ``kind``: ``MODELS / f"{kind}.py"``."""
    path = MODELS / f"{kind}.py"
    if path not in _loaded:
        if not path.is_file():
            from harness import BenchError
            raise BenchError(f"no module for model kind {kind!r}: "
                             f"{path} is missing")
        spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def of(model: dict):
    """The module of a configuration's ``model`` entry."""
    return load(model["kind"])
