"""On one objective the comparison and the reference epoch give exactly
what they gave before they took populations of several objectives.

The oracle is their one-objective form, kept here as it was: an epoch
that orders by scalar fitness, and ``numbers`` on (I, P) fitness with a
scalar sort for the migrant test. Both run on the populations of real
runs (the 60-bus HVDC grid, and Rastrigin) and on a population whose best
fitness is shared by several copies."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, run
from chipbench.configs import hvdc_german
from chipbench.deployment import (Deployment, ga_seed, ga_settings,
                                  program_config)
from chipbench.reference import ga
from chipbench.tests.test_harness_cpu import MIX, tiny_hvdc
from repro.fitness import benchmarks


# -- the oracle: the one-objective reference epoch and comparison ----------

def _order(fitness):
    by_f = jnp.argsort(fitness, stable=True)
    f = fitness[by_f]
    edge = (jnp.concatenate([jnp.array([True]), f[1:] != f[:-1]])
            | jnp.concatenate([f[:-1] != f[1:], jnp.array([True])]))
    inner = jnp.zeros(fitness.shape, bool).at[by_f].set(~edge)
    return jnp.lexsort((jnp.arange(fitness.shape[0]), inner, fitness))


def _island_offspring(genomes, fitness, key, scal, lo, hi):
    p, g = genomes.shape
    k_sel, k_var = jax.random.split(key)
    rank = jnp.argsort(_order(fitness))
    cand = jnp.floor(jax.random.uniform(k_sel, (p, 2))
                     * jnp.float32(p)).astype(jnp.int32)
    parents = genomes[jnp.where(rank[cand[:, 0]] <= rank[cand[:, 1]],
                                cand[:, 0], cand[:, 1])]
    r = ga._uniforms(k_var, p, g)
    o1, o2 = ga._sbx(parents[0::2], parents[1::2], r, scal[0], scal[1], lo,
                     hi)
    off = jnp.stack([o1, o2], axis=1).reshape(p, g)
    return ga._mutate(off, r, scal[2], scal[3], scal[4], lo, hi)


@functools.partial(jax.jit, static_argnames="bounds")
def _offspring(genomes, fitness, rng, scal, *, bounds):
    dt = genomes.dtype
    lo, hi = jnp.asarray(bounds[0], dt), jnp.asarray(bounds[1], dt)
    keys = jax.vmap(jax.random.split)(rng)
    off = jax.vmap(lambda gg, ff, kk: _island_offspring(
        gg, ff, kk, scal, lo, hi))(genomes, fitness, keys[:, 0])
    return off, keys[:, 1]


@jax.jit
def _survivors(genomes, fitness, offspring, off_fit):
    p = genomes.shape[1]
    pool_g = jnp.concatenate([genomes, offspring], axis=1)
    pool_f = jnp.concatenate([fitness, off_fit], axis=1)
    keep = jax.vmap(_order)(pool_f)[:, :p]
    return (jnp.take_along_axis(pool_g, keep[..., None], axis=1),
            jnp.take_along_axis(pool_f, keep, axis=1))


@jax.jit
def _migrate(genomes, fitness, rng):
    p = genomes.shape[1]
    keys = jax.vmap(jax.random.split)(rng)
    order = jax.vmap(_order)(fitness)
    isl = jnp.arange(genomes.shape[0])
    send_g = genomes[isl, order[:, 0]]
    send_f = fitness[isl, order[:, 0]]
    u = jax.vmap(lambda k: jax.random.uniform(
        jax.random.fold_in(k, 0), (1,))[0])(keys[:, 0])
    victim = order[isl, (1 + jnp.floor(u * (p - 1))).astype(jnp.int32)]
    return (genomes.at[isl, victim].set(jnp.roll(send_g, 1, axis=0)),
            fitness.at[isl, victim].set(jnp.roll(send_f, 1, axis=0)),
            keys[:, 1], send_g)


def oracle_epoch(genomes, fitness, rng, fit_fn, conf):
    genomes = jnp.asarray(genomes, jnp.float32)
    fitness = jnp.asarray(fitness, jnp.float32)
    rng = jnp.asarray(rng)
    n_isl, p, g = genomes.shape
    scal = jnp.asarray([conf["crossover_eta"], conf["crossover_prob"],
                        conf["mutation_eta"], conf["mutation_prob"], 1.0 / g],
                       jnp.float32)
    for _ in range(conf["generations_per_epoch"]):
        off, rng = _offspring(genomes, fitness, rng, scal,
                              bounds=(conf["lower"], conf["upper"]))
        off_fit = jnp.asarray(fit_fn(off.reshape(n_isl * p, g)), jnp.float32)
        genomes, fitness = _survivors(genomes, fitness, off,
                                      off_fit.reshape(n_isl, p))
    return _migrate(genomes, fitness, rng)


def oracle_numbers(fitness_name, fit, ga_conf, start, window, replay,
                   match_tol, gap_limit):
    n_isl, p, g = window["genomes"].shape
    gaps = []
    for pop in (window, replay):
        ref_f = np.asarray(fit(pop["genomes"].reshape(-1, g)), np.float64)
        got = pop["fitness"].reshape(-1).astype(np.float64)
        gap = np.abs(got - ref_f) / np.maximum(1.0, np.abs(ref_f))
        gaps.append(np.where(np.isfinite(gap), gap, np.inf))
    ref_g, _, _, sent = oracle_epoch(start["genomes"], start["fitness"],
                                     start["rng"], fit, ga_conf)
    ref_g = np.asarray(ref_g, np.float32)
    near = np.asarray(compare._nearest_gap(jnp.asarray(replay["genomes"]),
                                           jnp.asarray(ref_g)))
    immigrant = np.roll(np.asarray(sent, np.float32), 1, axis=0)
    arrived = np.asarray(compare._nearest_gap(
        jnp.asarray(immigrant[:, None]),
        jnp.asarray(replay["genomes"])))[:, 0]
    f = replay["fitness"]
    top = f <= np.sort(f, axis=1)[:, 1:2]
    kept = np.asarray(compare._nearest_gap(
        jnp.asarray(np.roll(replay["genomes"], 1, axis=0)),
        jnp.asarray(replay["genomes"])))
    home = np.any(np.roll(top, 1, axis=0) & (kept <= match_tol), axis=1)
    gaps = np.concatenate(gaps)
    return ({f"{fitness_name}_gap": float(np.max(gaps)),
             "unmatched_share": float(np.mean(~(near <= match_tol))),
             "migrants_missing": int(np.sum(~(arrived <= match_tol)
                                            & ~home))},
            int(np.sum(~(gaps <= gap_limit))))


# -- the populations ---------------------------------------------------------

RASTRIGIN = dict(num_genes=6, islands_per_chip=2, pop_per_island=8,
                 generations_per_epoch=5, mutation_prob=0.7,
                 mutation_eta=34.6, crossover_prob=1.0, crossover_eta=97.5,
                 lower=-5.12, upper=5.12, mutation_indpb="1/num_genes",
                 num_migrants=1, migration_pattern="ring")


def rastrigin_reference(x):
    x = np.asarray(x, np.float64)
    return 10.0 * x.shape[1] + np.sum(x * x - 10.0 * np.cos(2 * np.pi * x),
                                      axis=1)


def rastrigin_deployment(seed):
    cfg = program_config(RASTRIGIN, 1, ga_seed(seed))
    return Deployment(
        cfg=cfg, fitness=benchmarks.rastrigin, cost_fn=None, ctx=None,
        ga_seed=cfg.seed, fitness_name="objective",
        reference=rastrigin_reference, control=None,
        ga=ga_settings(RASTRIGIN),
        limits={"objective_gap": 2e-4, "unmatched_share": 0.5,
                "migrants_missing": 0},
        match_tol=1e-3, trace_epochs=1,
        shapes=dict(islands_per_chip=2, pop_per_island=8,
                    generations_per_epoch=5))


def _runs():
    yield "hvdc", hvdc_german.build(tiny_hvdc(), MIX, seed=41, chips=1)
    for seed in (42, 43):
        yield f"rastrigin-{seed}", rastrigin_deployment(seed)


@pytest.fixture(scope="module")
def runs():
    """(deployment, the harness's populations of a short window) each."""
    return {name: (dep, run.drive(jax, dep, 1.0, False))
            for name, dep in _runs()}


def _one_objective(pop):
    return dict(pop, fitness=pop["fitness"][..., 0])


def _both(dep, start, window, replay):
    new = compare.numbers(dep.fitness_name, dep.reference, dep.ga, start,
                          window, replay, dep.match_tol, 2e-4)
    old = oracle_numbers(dep.fitness_name, dep.reference, dep.ga,
                         *map(_one_objective, (start, window, replay)),
                         dep.match_tol, 2e-4)
    return new, old


@pytest.mark.parametrize("name", ["hvdc", "rastrigin-42", "rastrigin-43"])
def test_numbers_are_the_one_objective_numbers(runs, name):
    dep, out = runs[name]
    assert out["replay_pop"]["fitness"].shape[2] == 1
    new, old = _both(dep, out["start_pop"], out["window_pop"],
                     out["replay_pop"])
    assert new == old


@pytest.mark.parametrize("name", ["hvdc", "rastrigin-42", "rastrigin-43"])
def test_epoch_is_the_one_objective_epoch(runs, name):
    dep, out = runs[name]
    start = out["start_pop"]
    new = ga.epoch(start["genomes"], start["fitness"], start["rng"],
                   dep.reference, dep.ga)
    old = oracle_epoch(start["genomes"], start["fitness"][..., 0],
                       start["rng"], dep.reference, dep.ga)
    for a, b in zip(new, old):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _tied(seed):
    """A Rastrigin population in which copies share each island's best
    fitness, and the next epochs the reference makes from it; one of them
    with its migration left out."""
    rng = np.random.default_rng(seed)
    genomes = rng.uniform(-5.12, 5.12, (2, 8, 6)).astype(np.float32)
    genomes[:, 1:4] = genomes[:, :1]                  # 4 copies, each island
    genomes[:, :4] = 0.01 * rng.integers(0, 3, (2, 1, 6))   # near the best
    fitness = rastrigin_reference(genomes.reshape(-1, 6)).reshape(2, 8, 1)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed), 2))
    start = {"genomes": genomes, "fitness": fitness.astype(np.float32),
             "rng": keys}
    g, f, r, _ = oracle_epoch(genomes, fitness[..., 0], keys,
                              rastrigin_reference, ga_settings(RASTRIGIN))
    replay = {"genomes": np.asarray(g), "fitness": np.asarray(f)[..., None],
              "rng": np.asarray(r)}
    kept = dict(replay, genomes=np.concatenate(
        [replay["genomes"][:1], start["genomes"][1:]]),
        fitness=np.concatenate([replay["fitness"][:1],
                                start["fitness"][1:]]))
    return start, replay, kept


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_numbers_are_the_one_objective_numbers_on_ties(seed):
    dep = rastrigin_deployment(seed)
    start, replay, kept = _tied(seed)
    for other in (replay, kept):
        new, old = _both(dep, start, other, other)
        assert new == old
