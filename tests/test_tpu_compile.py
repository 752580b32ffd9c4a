"""Compile the main path's kernels and steps for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with jaxlib
compiles for a ``v5e:2x2`` topology that is described, not attached, and
refuses what the chip would refuse (unlowerable primitives, scoped VMEM
overruns, programs that do not fit HBM).  Interpret-mode kernel tests
(tests/test_kernels.py) cannot see any of that.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and under pytest-xdist every worker
imports this file.  Keep these tests in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.experiments import TrialSpec
from repro.experiments import runner
from repro.kernels import ops as kernel_ops
from repro.kernels.fed_aggregate import fed_aggregate
from repro.kernels.fed_reduce import fed_reduce

HBM_BYTES = 16 * 2**30          # one v5e chip
SERVED = TrialSpec(dataset="emnist", m0=20, e0=1.0, reduced=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def served_model():
    """The served MLP (784-48-62) and its optimizer, as the runner builds
    them for a paper-scale EMNIST trial."""
    model = runner._model_for(SERVED)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, runner._optimizer_for(SERVED), shapes


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one chip's HBM"


@pytest.mark.parametrize("m,t,int8", [(16, 1, False), (512, 16, False),
                                      (512, 16, True), (4096, 16, False)])
def test_fed_reduce_compiles(one_chip, served_model, m, t, int8):
    """The fused reduction at the served width, from one lane to a 4,096-row
    cohort: the row axis is tiled, so scoped VMEM does not grow with M."""
    _, _, shapes = served_model
    leaf_sizes = tuple(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    n = sum(leaf_sizes)
    assert n == 40_718

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def reduce(w, rows, seg, qref, enabled):
        return fed_reduce(w, rows, seg, t, normalize=True,
                          leaf_sizes=leaf_sizes if int8 else None,
                          quant_ref=qref if int8 else None,
                          quant_enabled=enabled if int8 else None)

    compiled = jax.jit(reduce).lower(
        sds((m,), jnp.float32), sds((m, n), jnp.float32),
        sds((m,), jnp.int32), sds((t, n), jnp.float32),
        sds((m,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("lanes", [128, 16],
                         ids=["bucket_lanes", "zero_step_globals"])
def test_row_placement_compiles_in_place(one_chip, served_model, lanes):
    """The FedAvg row hand-off at the served width: a 128-lane bucket (or
    16 trials' flat globals) placed into a 512-row reduce matrix, which is
    donated, so the write aliases it on the chip."""
    _, _, shapes = served_model
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cohort = (sds((lanes, n)) if lanes == 16 else
              jax.tree.map(lambda s: sds((lanes,) + s.shape, s.dtype), shapes))
    compiled = runner._place_rows.lower(
        sds((512, n)), cohort, sds((512,), jnp.int32)).compile()
    assert "input_output_alias" in compiled.as_text()
    _fits(compiled)


def test_fed_aggregate_async_mix_compiles(one_chip, served_model):
    """``fed_aggregate`` as FedAsync mixing calls it: one (1, N) row."""
    _, _, shapes = served_model
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(fed_aggregate).lower(
        sds((1,)), sds((1, n)), sds((n,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_served_cohort_step_compiles(one_chip, served_model, monkeypatch):
    """The packed cohort step (``cohort_scan`` over ``make_client_step``)
    for the served MLP at one bucket: 8 steps x 128 client lanes x batch
    10."""
    model, opt, shapes = served_model
    monkeypatch.setattr(runner, "_multi_cohort_cache", {})
    run = runner._multi_cohort_fn(model, opt, SERVED.prox_mu)
    t_pad, m_pad, b = 8, 128, SERVED.batch_size

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    global_b = jax.tree.map(lambda s: sds((m_pad,) + s.shape, s.dtype),
                            shapes)
    compiled = run.lower(global_b, sds((t_pad, m_pad, b, 784)),
                         sds((t_pad, m_pad, b), jnp.int32),
                         sds((t_pad, m_pad, b)),
                         sds((t_pad, m_pad), jnp.bool_)).compile()
    _fits(compiled)


def test_sharded_pack_compiles_on_four_chips(topo, served_model,
                                             monkeypatch):
    """``_sharded_multi_fn`` over a ``clients`` mesh of the 4 described
    chips, with the Pallas branch of ``ops.fed_reduce`` inside the
    shard_map body and the psum that completes each lane's mean."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    model, opt, shapes = served_model
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("clients",))
    monkeypatch.setattr(kernel_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(runner, "_sharded_multi_cache", {})
    leaf_sizes = tuple(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    n, t_seg, t_pad, m_pad, b = sum(leaf_sizes), 16, 8, 64, 10
    run = runner._sharded_multi_fn(model, opt, 0.0, mesh, t_seg, leaf_sizes,
                                   compressed=True)

    def sds(shape, dtype=jnp.float32, dim=None):
        spec = [None] * len(shape)
        if dim is not None:
            spec[dim] = "clients"
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    global_b = jax.tree.map(lambda s: sds((m_pad,) + s.shape, s.dtype, 0),
                            shapes)
    compiled = run.lower(
        global_b, sds((t_pad, m_pad, b, 784), dim=1),
        sds((t_pad, m_pad, b), jnp.int32, 1), sds((t_pad, m_pad, b), dim=1),
        sds((t_pad, m_pad), jnp.bool_, 1), sds((m_pad,), dim=0),
        sds((m_pad,), jnp.int32, 0), sds((t_seg, n)),
        sds((m_pad,), jnp.bool_, 0)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    _fits(compiled)
