"""Plain reference of one island-model NSGA-II epoch on a single objective.

It follows the published operators and nothing of the program under test:

* selection key: NSGA-II's (front, crowding) order, which on one objective
  is ascending fitness; among equal fitness the crowding distance puts the
  group's first and last members by position (infinite distance) before
  its interior ones (zero distance), each in position order;
* binary tournament on that key;
* simulated binary crossover (Deb & Agrawal 1995) over consecutive parent
  pairs, bounded, gated per pair and per gene;
* polynomial mutation (Deb et al. 2002), bounded, gated per individual and
  per gene;
* (mu + lambda) survivors: the best ``P`` of parents and offspring;
* after ``M`` generations, ring migration: the best of island ``k - 1``
  replaces one random non-elite slot of island ``k``.

The random numbers are the ones the deployment draws: the same
``jax.random`` keys, split in the same order, give the same uniforms, so
the reference and the program make the same random decisions. The
arithmetic is float32. Everything is ``jax.numpy``, jitted per shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EPS = 1e-14


def _uniforms(key, p: int, g: int) -> dict:
    ks = jax.random.split(key, 6)
    return {"u_cx": jax.random.uniform(ks[0], (p // 2, g)),
            "m_pair": jax.random.uniform(ks[1], (p // 2, 1)),
            "m_gene": jax.random.uniform(ks[2], (p // 2, g)),
            "u_mut": jax.random.uniform(ks[3], (p, g)),
            "m_ind": jax.random.uniform(ks[4], (p, 1)),
            "m_genem": jax.random.uniform(ks[5], (p, g))}


def _sbx(x1, x2, r, eta, prob, lo, hi):
    u = r["u_cx"].astype(x1.dtype)
    y1, y2 = jnp.minimum(x1, x2), jnp.maximum(x1, x2)
    span = jnp.maximum(y2 - y1, EPS)
    e1 = eta.astype(x1.dtype) + 1.0

    def betaq(beta):
        alpha = 2.0 - beta ** (-e1)
        return jnp.where(u <= 1.0 / alpha, (u * alpha) ** (1.0 / e1),
                         (1.0 / jnp.maximum(2.0 - u * alpha, EPS))
                         ** (1.0 / e1))

    c1 = 0.5 * ((y1 + y2) - betaq(1.0 + 2.0 * (y1 - lo) / span) * (y2 - y1))
    c2 = 0.5 * ((y1 + y2) + betaq(1.0 + 2.0 * (hi - y2) / span) * (y2 - y1))
    cross = (r["m_pair"] < prob) & (r["m_gene"] < 0.5)
    return (jnp.where(cross, jnp.clip(c1, lo, hi), x1),
            jnp.where(cross, jnp.clip(c2, lo, hi), x2))


def _mutate(x, r, eta, prob, indpb, lo, hi):
    u = r["u_mut"].astype(x.dtype)
    span = hi - lo
    e1 = eta.astype(x.dtype) + 1.0
    below = (jnp.maximum(2.0 * u + (1.0 - 2.0 * u)
                         * (1.0 - (x - lo) / span) ** e1, EPS)
             ** (1.0 / e1) - 1.0)
    above = 1.0 - (jnp.maximum(2.0 * (1.0 - u) + 2.0 * (u - 0.5)
                               * (1.0 - (hi - x) / span) ** e1, EPS)
                   ** (1.0 / e1))
    moved = jnp.clip(x + jnp.where(u < 0.5, below, above) * span, lo, hi)
    return jnp.where((r["m_ind"] < prob) & (r["m_genem"] < indpb), moved, x)


def _order(fitness):
    """Positions in NSGA-II selection order, best first, for (P,) fitness."""
    by_f = jnp.argsort(fitness, stable=True)
    f = fitness[by_f]
    edge = (jnp.concatenate([jnp.array([True]), f[1:] != f[:-1]])
            | jnp.concatenate([f[:-1] != f[1:], jnp.array([True])]))
    inner = jnp.zeros(fitness.shape, bool).at[by_f].set(~edge)
    return jnp.lexsort((jnp.arange(fitness.shape[0]), inner, fitness))


def _island_offspring(genomes, fitness, key, scal, lo, hi):
    """One island's offspring. genomes (P, G), fitness (P,); ``scal`` is
    (crossover eta, crossover prob, mutation eta, mutation prob, indpb)."""
    p, g = genomes.shape
    k_sel, k_var = jax.random.split(key)
    rank = jnp.argsort(_order(fitness))
    cand = jnp.floor(jax.random.uniform(k_sel, (p, 2))
                     * jnp.float32(p)).astype(jnp.int32)
    parents = genomes[jnp.where(rank[cand[:, 0]] <= rank[cand[:, 1]],
                                cand[:, 0], cand[:, 1])]
    r = _uniforms(k_var, p, g)
    o1, o2 = _sbx(parents[0::2], parents[1::2], r, scal[0], scal[1], lo, hi)
    off = jnp.stack([o1, o2], axis=1).reshape(p, g)
    return _mutate(off, r, scal[2], scal[3], scal[4], lo, hi)


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
    """(mu + lambda): the best P of parents and offspring, per island."""
    p = genomes.shape[1]
    pool_g = jnp.concatenate([genomes, offspring], axis=1)
    pool_f = jnp.concatenate([fitness, off_fit], axis=1)
    keep = jax.vmap(_order)(pool_f)[:, :p]
    return (jnp.take_along_axis(pool_g, keep[..., None], axis=1),
            jnp.take_along_axis(pool_f, keep, axis=1))


@jax.jit
def _migrate(genomes, fitness, rng):
    """Ring migration of the single best individual per island."""
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


def epoch(genomes, fitness, rng, fit_fn, ga: dict):
    """One epoch from a population.

    genomes (I, P, G), fitness (I, P), rng (I, 2) uint32 per-island keys;
    ``fit_fn`` maps (N, G) genomes to (N,) fitness; ``ga`` holds the
    configuration's GA settings (``crossover_eta``, ``crossover_prob``,
    ``mutation_eta``, ``mutation_prob``, ``lower``, ``upper``,
    ``generations_per_epoch``). Returns (genomes, fitness, rng, sent): the
    next population and each island's emigrant before migration.
    """
    genomes = jnp.asarray(genomes, jnp.float32)
    fitness = jnp.asarray(fitness, jnp.float32)
    rng = jnp.asarray(rng)
    n_isl, p, g = genomes.shape
    # the operators' settings enter as run-time float32 scalars
    scal = jnp.asarray([ga["crossover_eta"], ga["crossover_prob"],
                        ga["mutation_eta"], ga["mutation_prob"], 1.0 / g],
                       jnp.float32)
    for _ in range(ga["generations_per_epoch"]):
        off, rng = _offspring(genomes, fitness, rng, scal,
                              bounds=(ga["lower"], ga["upper"]))
        off_fit = jnp.asarray(fit_fn(off.reshape(n_isl * p, g)), jnp.float32)
        genomes, fitness = _survivors(genomes, fitness, off,
                                      off_fit.reshape(n_isl, p))
    return _migrate(genomes, fitness, rng)
