"""Ahead-of-time compiles of the search's device programs for a TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these tests run without one. They catch what
CPU runs and interpret mode cannot: layouts, tilings and dtypes the chip's
compiler refuses. Shapes are pendigits' (16-20-10 over its full seeded
train/test split) at a population bucket of 16: the widest schema the
search runs.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
must all collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import circuit
from repro.configs.printed_mlp import PRINTED_MLPS
from repro.core import batch_eval as BE
from repro.data import uci
from repro.kernels import netlist_sim as NS
from repro.kernels.netlist_sim import ops as NSO
from repro.nn import mlp as M

from test_circuit import synth_compiled

CFG = PRINTED_MLPS["pendigits"]
P = 16          # population bucket
EPOCHS = 60     # the example's GA finetune length
WINDOW = 256    # simulate_population's default wave width
BLOCK_B = 2048  # simulate_population's default batch tile


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def levels_shapes():
    """Schedule shapes of a real pendigits-width population: P dense 8-bit
    16-20-10 netlists, packed and wave-scheduled as the engine does."""
    nets = [circuit.compile_netlist(synth_compiled(CFG.layer_dims, 8,
                                                   seed=s))
            for s in range(P)]
    sched = NSO._global_schedule(NS.pack_population(nets), WINDOW)
    _, _, xte, _ = uci.dataset_for(CFG)
    return sched, min(NSO._bucket(len(xte)), BLOCK_B)


@pytest.mark.parametrize("lane", ["int32", "int64"])
def test_levels_engine_compiles_for_v5e(one_chip, levels_shapes, lane):
    sched, bt = levels_shapes
    dtype = jnp.int32 if lane == "int32" else jnp.int64
    with jax.enable_x64(lane == "int64"):
        args = [_sds(a.shape, jnp.int32, one_chip) for a in
                (sched.OP, sched.AI, sched.BI, sched.SH, sched.OUT)]
        args += [_sds(sched.vals0.shape, dtype, one_chip),
                 _sds(sched.inp_cols.shape, jnp.int32, one_chip),
                 _sds(sched.am_cols.shape, jnp.int32, one_chip),
                 _sds((bt, sched.inp_cols.size), dtype, one_chip)]
        compiled = NSO._run_levels.lower(*args).compile()
    out = compiled.out_info
    assert out.shape == (bt, P, CFG.n_classes) and out.dtype == dtype


def test_population_finetune_compiles_for_v5e(one_chip):
    xtr, _, _, _ = uci.dataset_for(CFG)
    params0 = jax.eval_shape(
        lambda k: M.mlp_init(k, CFG.layer_dims), jax.random.PRNGKey(0))
    params0 = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), params0)
    n_layers = len(CFG.layer_dims) - 1
    masks = tuple(_sds((P, d_in, d_out), jnp.float32, one_chip)
                  for d_in, d_out in zip(CFG.layer_dims[:-1],
                                         CFG.layer_dims[1:]))
    compiled = BE._population_finetune.lower(
        params0,
        _sds((P, n_layers), jnp.int32, one_chip),
        _sds((P, n_layers), jnp.int32, one_chip),
        masks,
        _sds(xtr.shape, jnp.float32, one_chip),
        _sds(xtr.shape[:1], jnp.int32, one_chip),
        epochs=EPOCHS, lr=2e-3).compile()
    w0 = compiled.out_info["layers"][0]["w"]
    assert w0.shape == (P, CFG.n_features, CFG.hidden[0])
    assert np.dtype(w0.dtype) == np.float32
