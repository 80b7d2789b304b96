"""Profiler scopes, host spans and counters of the GA program, on the CPU:
the scope names in a compiled HVDC epoch step's HLO metadata, the
engine's published Newton and epoch counters against direct solves of the
same genomes, the no-op seam, the compile listener, and the engine's
spans in a profile of a two-epoch run."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GAConfig
from repro.core.broker import Broker
from repro.core.engine import GAEngine
from repro.core.island import make_epoch_step
from repro.core.population import init_population
from repro.fitness.powerflow import HVDCDispatchFitness
from repro.launch.compile_cache import register_compile_listener
from repro.obs import MetricsRegistry
from repro.powerflow.grid import make_synthetic_grid
from repro.powerflow.hvdc import apply_hvdc, scale_genome_to_dispatch
from repro.powerflow.newton import newton_powerflow
from repro.runtime import metrics as runtime_metrics

SCOPES = ("chambga.selection", "chambga.variation", "chambga.dispatch",
          "chambga.fitness", "chambga.survivor", "chambga.migration",
          "chambga.newton.mismatch", "chambga.newton.jacobian",
          "chambga.newton.lu")
SCOPE = re.compile(r"chambga\.[a-z_.]*[a-z]")
NEWTON_ITERS = 6
EPOCHS = 2


@pytest.fixture(scope="module")
def grid():
    return make_synthetic_grid(n_bus=60, n_line=110, n_gen=15, n_hvdc=4,
                               seed=1)


def _cfg(grid) -> GAConfig:
    return GAConfig(num_genes=grid.n_hvdc, pop_per_island=4, num_islands=2,
                    generations_per_epoch=2, lower=-1.0, upper=1.0, seed=3)


class _Recording(HVDCDispatchFitness):
    """The HVDC fitness, keeping a host copy of every batch it solves."""

    def __init__(self, grid, **kw):
        super().__init__(grid, **kw)
        self.seen = []

    def evaluate_with_stats(self, genomes):
        jax.debug.callback(lambda g: self.seen.append(np.asarray(g)),
                           genomes)
        return super().evaluate_with_stats(genomes)


class _Spy:
    """A disabled registry that records any call that reaches it."""

    enabled = False

    def __init__(self):
        self.calls = []

    def __getattr__(self, name):
        return lambda *a, **kw: self.calls.append((name, a, kw))


def _direct_iters(grid, genomes) -> np.ndarray:
    gridj = grid.to_jax()

    def solve(g):
        p_extra = apply_hvdc(gridj, scale_genome_to_dispatch(gridj, g))
        return newton_powerflow(gridj, p_extra=p_extra,
                                num_iters=NEWTON_ITERS).iters

    return np.asarray(jax.jit(jax.vmap(solve))(jnp.asarray(genomes)))


@pytest.fixture(scope="module")
def traced_run(grid, tmp_path_factory):
    """Two epochs of the engine under the profiler, with a registry."""
    fit = _Recording(grid, newton_iters=NEWTON_ITERS)
    eng = GAEngine(_cfg(grid), fit)
    pop = jax.block_until_ready(eng.init())
    jax.effects_barrier()
    fit.seen.clear()                    # the initial evaluation
    reg = MetricsRegistry()
    runtime_metrics.set_registry(reg)
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            pop, hist = eng.run(pop, epochs=EPOCHS)
            jax.block_until_ready(pop)
        finally:
            jax.profiler.stop_trace()
    finally:
        runtime_metrics.set_registry(None)
    jax.effects_barrier()
    return {"engine": eng, "pop": pop, "history": hist, "registry": reg,
            "genomes": np.concatenate(fit.seen), "trace_dir": trace_dir}


def test_epoch_step_hlo_carries_every_scope(grid):
    """A small HVDC epoch step with cost-balanced dispatch over two
    workers, compiled: every scope is the innermost of some op, and the
    Newton scopes nest inside the fitness scope."""
    fit = HVDCDispatchFitness(grid, newton_iters=NEWTON_ITERS)
    cfg = _cfg(grid)
    broker = Broker(fit, fit.cost_model(), num_workers=2)
    pop = init_population(cfg, jax.random.PRNGKey(0))
    hlo = jax.jit(make_epoch_step(cfg, broker)).lower(pop).compile().as_text()
    # a scope under a transform reads ``vmap(chambga.variation)``
    paths = [SCOPE.findall(n)
             for n in set(re.findall(r'op_name="([^"]*)"', hlo))]
    assert {p[-1] for p in paths if p} == set(SCOPES)
    newton = [p for p in paths if p and p[-1].startswith("chambga.newton.")]
    assert all("chambga.fitness" in p[:-1] for p in newton)


def test_newton_counters_match_direct_solves(grid, traced_run):
    reg, genomes = traced_run["registry"], traced_run["genomes"]
    cfg = _cfg(grid)
    assert len(genomes) == EPOCHS * cfg.generations_per_epoch * cfg.global_pop
    iters = _direct_iters(grid, genomes)
    assert 1 <= iters.min() and iters.max() <= NEWTON_ITERS
    assert reg.counter_total("chambga_newton_iterations_total") == \
        int(iters.sum())
    assert reg.counter_total("chambga_newton_solves_total") == len(genomes)
    assert reg.counter_total("chambga_newton_unconverged_total") == 0
    hist = traced_run["history"]
    assert sum(h["newton_iterations"] for h in hist) == int(iters.sum())
    assert [h["newton_solves"] for h in hist] == \
        [cfg.generations_per_epoch * cfg.global_pop] * EPOCHS


def test_balanced_dispatch_forwards_fitness_stats(grid):
    """Cost-balanced dispatch pads 8 genomes to 9 over 3 workers: the
    padded lane is solved too, and counts."""
    fit = HVDCDispatchFitness(grid, newton_iters=NEWTON_ITERS)
    broker = Broker(fit, fit.cost_model(), num_workers=3)
    genomes = jax.random.uniform(jax.random.PRNGKey(4), (8, grid.n_hvdc),
                                 minval=-1.0, maxval=1.0)
    out, stats = jax.jit(broker.evaluate)(genomes)
    assert int(stats["padded"]) == 1
    assert int(stats["fitness"]["newton_solves"]) == 9
    iters = _direct_iters(grid, np.concatenate([genomes, genomes[:1]]))
    assert int(stats["fitness"]["newton_iterations"]) == int(iters.sum())
    assert Broker(lambda g: g[:, :1]).evaluate(genomes)[1]["fitness"] == {}


def test_epoch_and_evaluation_counters(grid, traced_run):
    reg, cfg = traced_run["registry"], _cfg(grid)
    assert reg.counter_total("chambga_epochs_total") == EPOCHS
    assert reg.counter_total("chambga_evaluations_total") == \
        EPOCHS * cfg.generations_per_epoch * cfg.global_pop


def test_noop_seam_emits_nothing(traced_run):
    """With a disabled registry installed, an epoch and a fresh compile
    reach none of its methods."""
    spy = _Spy()
    register_compile_listener()
    runtime_metrics.set_registry(spy)
    try:
        pop, hist = traced_run["engine"].run(traced_run["pop"], epochs=1)
        jax.block_until_ready(pop)
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(5.0)).block_until_ready()
    finally:
        runtime_metrics.set_registry(None)
    assert len(hist) == 1 and hist[0]["newton_solves"] > 0
    assert spy.calls == []


def test_compile_listener_counts_one_compile():
    x = jnp.arange(7.0)                 # made before the registry
    register_compile_listener()

    def listener_probe(v):
        return jnp.sin(v) * 2.0

    f = jax.jit(listener_probe)
    reg = MetricsRegistry()
    runtime_metrics.set_registry(reg)
    try:
        f(x).block_until_ready()
        f(x).block_until_ready()        # cached: no second compile
    finally:
        runtime_metrics.set_registry(None)
    series = {dict(labels)["phase"]: v for (name, labels), v
              in reg.snapshot()["counters"].items()
              if name == "chambga_compile_seconds_total"
              and dict(labels)["fun"] == "listener_probe"}
    assert set(series) == {"trace", "lower", "compile"}
    assert all(v > 0 for v in series.values())
    # jnp.sin is itself jitted and traced inside listener_probe: its
    # seconds are the caller's, not a series of their own
    funs = {dict(labels)["fun"] for (name, labels)
            in reg.snapshot()["counters"]
            if name == "chambga_compile_seconds_total"}
    assert funs == {"listener_probe"}


def test_profile_holds_engine_spans(traced_run):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(traced_run["trace_dir"], "**",
                                   "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    spans = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("chambga."):
                    spans.setdefault(ev.name, []).append(
                        dict(ev.stats).get("epoch"))
    start = traced_run["history"][0]["epoch"]
    want = [start + k for k in range(EPOCHS)]
    assert sorted(spans["chambga.dispatch"]) == want
    assert sorted(spans["chambga.drain"]) == want
