"""Each cell's programs compiled for a described TPU v5e, at the cell's
real size, with no chip attached: the epoch step the window drives, and
the reference that checks it. Nothing runs here; the compiler refuses what
it would refuse on the chip (VMEM overflow, programs that do not fit,
kernels the compiler cannot place). The topology is described only inside
a fixture, as one process at a time may load the TPU library."""
import contextlib
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench import run
from chipbench.configs import hvdc_german
from chipbench.reference import ga, powerflow
from chipbench.tests.test_harness_cpu import MIX
from repro.core.engine import GAEngine
from repro.core.population import init_population
from repro.kernels.genetic import ops as gk


def _conf(name):
    return json.load(open(os.path.join(run.HERE, "configs", name + ".json")))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")
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


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(gk, "_is_tpu", lambda: True)


def _compile_epoch(dep, pick):
    shapes = jax.eval_shape(lambda: init_population(
        dep.cfg, jax.random.PRNGKey(0)))
    pop = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=pick(s)),
        shapes)
    eng = GAEngine(dep.cfg, dep.fitness, cost_fn=dep.cost_fn, ctx=dep.ctx)
    return eng._epoch_step.lower(pop).compile()


@pytest.mark.parametrize("control", [False, True])
def test_hvdc_epoch_compiles(topo, on_tpu, control):
    """The window's epoch step, and the control's (its Newton at high)."""
    one = SingleDeviceSharding(topo.devices[0])
    dep = hvdc_german.build(_conf("hvdc_german"), MIX, seed=1, chips=1)
    with dep.control() if control else contextlib.nullcontext():
        compiled = _compile_epoch(dep, lambda s: one)
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
    assert "tpu_custom_call" in compiled.as_text()


def test_references_compile(topo):
    one = SingleDeviceSharding(topo.devices[0])
    conf = _conf("hvdc_german")
    ref = powerflow.Powerflow(
        hvdc_german.make_grid(**conf["grid"]), loss=0.015, tol=5e-4,
        max_iter=10)
    arrays = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        ref.arrays)
    n, n_genes = (conf["islands_per_chip"] * conf["pop_per_island"],
                  conf["num_genes"])
    genomes = jax.ShapeDtypeStruct((n, n_genes), jnp.float32, sharding=one)
    powerflow._solve.lower(arrays, genomes, loss=0.015, tol=5e-4,
                           max_iter=10).compile()
    i, p, g = conf["islands_per_chip"], conf["pop_per_island"], n_genes
    args = (jax.ShapeDtypeStruct((i, p, g), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((i, p), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((i, 2), jnp.uint32, sharding=one))
    scal = jax.ShapeDtypeStruct((5,), jnp.float32, sharding=one)
    ga._offspring.lower(*args, scal, bounds=(-1.0, 1.0)).compile()
    ga._survivors.lower(*args[:2], *args[:2]).compile()
    ga._migrate.lower(*args[:2], args[2]).compile()
