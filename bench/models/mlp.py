"""Model kind ``mlp``: a ReLU MLP, ``in_dim`` -> ``hidden`` ... ->
``n_classes``, weights and biases in pairs (see ``kinds.py`` for what a kind
module holds).

Work counts: forward and backward of one example, 6 FLOPs per weight (2
forward, 4 backward), biases left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def place(config: dict) -> None:
    """Serve the configuration's model. The served path builds one model per
    (dataset, reduced) in ``runner._model_for``, always with one hidden
    layer of 48 units; the configuration's widths are built by the
    program's own ``build_model`` and placed where that function looks
    first."""
    from repro.configs.paper_models import MLPConfig
    from repro.experiments import runner
    from repro.models.registry import build_model
    m = config["model"]
    cfg = MLPConfig(name=m["name"], in_dim=m["in_dim"],
                    hidden=tuple(m["hidden"]), n_classes=m["n_classes"])
    key = (config["dataset"], config["reduced_dataset"])
    if getattr(runner._model_cache.get(key), "config", None) != cfg:
        runner._model_cache[key] = build_model(cfg)


def leaves(params) -> list:
    return [np.asarray(x, np.float32) for layer in params["layers"]
            for x in (layer["w"], layer["b"])]


def dims(model: dict) -> list:
    return [model["in_dim"], *model["hidden"], model["n_classes"]]


def init_params(model: dict, seed: int):
    """He-normal weights and zero biases from the trial's seed."""
    d = dims(model)
    ks = jax.random.split(jax.random.PRNGKey(seed), len(d) - 1)
    out = []
    for k, a, b in zip(ks, d, d[1:]):
        out.append((jax.random.normal(k, (a, b)) * jnp.sqrt(2.0 / a))
                   .astype(jnp.float32))
        out.append(jnp.zeros((b,), jnp.float32))
    return out


def logits(params, x, prec, model: dict):
    h = x
    for i in range(0, len(params) - 2, 2):
        h = jax.nn.relu(jnp.dot(h, params[i], precision=prec) + params[i + 1])
    return jnp.dot(h, params[-2], precision=prec) + params[-1]


def weight_count(model: dict) -> int:
    """Weights of the MLP's matrices, biases left out."""
    d = dims(model)
    return sum(a * b for a, b in zip(d, d[1:]))


def param_count(model: dict) -> int:
    """Every parameter the server reduces: weights and biases."""
    d = dims(model)
    return sum(a * b + b for a, b in zip(d, d[1:]))


def forward_flops(model: dict) -> float:
    return float(2 * weight_count(model))


def train_flops_per_example(model: dict) -> float:
    return 6.0 * weight_count(model)
