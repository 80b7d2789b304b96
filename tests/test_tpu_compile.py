"""Compile the GA's main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses here what it would refuse on the chip (unaligned
kernel tiles, scoped-VMEM overflow, programs that do not fit, kernels GSPMD
cannot partition), so these tests guard the device path at real sizes
without chip time. Nothing runs; no result or time comes from them.

The topology is described only inside a fixture: one process at a time
may load the TPU library, and under pytest-xdist every worker imports this
file. ``_is_tpu`` is steered with monkeypatch, because JAX still reports
the CPU backend here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.base import GAConfig
from repro.core.engine import GAEngine
from repro.core.population import init_population
from repro.fitness import get_benchmark
from repro.kernels.genetic import ops as gk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")      # else libtpu logs under /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        env.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(gk, "_is_tpu", lambda: True)


def _rastrigin_cfg():
    return GAConfig(num_genes=18, pop_per_island=1024, num_islands=8,
                    generations_per_epoch=5, lower=-5.12, upper=5.12,
                    mutation_prob=0.7, mutation_eta=20.0,
                    crossover_prob=0.9, crossover_eta=15.0)


def _pop_shapes(cfg, pick):
    """Population shapes, each leaf placed by ``pick(leaf_shape)``."""
    shapes = jax.eval_shape(lambda: init_population(cfg,
                                                    jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=pick(s)),
        shapes)


def test_fused_kernel_compiles_for_v5e(one_chip, on_tpu):
    kw = dict(eta_cx=15.0, prob_cx=0.9, eta_mut=20.0, prob_mut=0.7,
              indpb=1.0 / 18, lower=-5.12, upper=5.12)
    fn = jax.jit(lambda k, p: gk.fused_variation(k, p, **kw))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((8192, 18), jnp.float32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rastrigin_epoch_step_compiles_for_v5e(one_chip, on_tpu):
    cfg = _rastrigin_cfg()
    eng = GAEngine(cfg, get_benchmark("rastrigin"))
    pop = _pop_shapes(cfg, lambda _: one_chip)
    compiled = eng._epoch_step.lower(pop).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_island_epoch_step_compiles_sharded_over_four_v5e_chips(topo,
                                                                on_tpu):
    """The Pallas kernel runs per device inside shard_map (GSPMD cannot
    partition it); migration is a collective permute; each chip holds a
    quarter of the population."""
    from repro.launch.mesh import make_mesh
    from repro.models.sharding import ShardingCtx

    cfg = _rastrigin_cfg()
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices[:4])
    ctx = ShardingCtx(mesh=mesh, dp=("data",), tp="model", fsdp=())
    eng = GAEngine(cfg, get_benchmark("rastrigin"), ctx=ctx)
    islands, replicated = NamedSharding(mesh, P("data")), \
        NamedSharding(mesh, P())
    pop = _pop_shapes(cfg, lambda s: islands if s.ndim else replicated)
    compiled = eng._epoch_step.lower(pop).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "collective-permute" in hlo
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    whole = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                for s in jax.tree_util.tree_leaves(pop))
    assert per_chip < whole / 2


def test_german_grid_hvdc_evaluation_compiles_for_v5e(one_chip):
    """A 2-genome batch at German-grid counts, solved one genome at a
    time (a vmapped batch overflows the LU's scoped VMEM)."""
    from repro.fitness.powerflow import HVDCDispatchFitness
    from repro.powerflow.grid import make_german_grid

    fit = HVDCDispatchFitness(make_german_grid())
    compiled = jax.jit(fit.evaluate).lower(
        jax.ShapeDtypeStruct((2, fit.num_genes), jnp.float32,
                             sharding=one_chip)).compile()
    # one genome's solve at a time: temporaries stay near one solve's
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
