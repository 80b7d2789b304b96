"""Plain reference of one island-model NSGA-II epoch (Deb et al. 2002).

It follows the published operators and nothing of the program under test:

* selection key: NSGA-II's crowded-comparison order, best first: front
  (fast non-dominated sort, where ``a`` dominates ``b`` if it is no worse
  in every objective and better in one), then crowding distance within
  the front, larger first, then the boundary points' order set out
  below, then position. Crowding sums, over the
  objectives, the gap between a point's two neighbours in the front's
  order by that objective over the front's range of it (0 where the range
  is 0); the front's first and last points by each objective (position
  breaking ties) lie at infinite distance. On one objective the order is
  ascending fitness; among equal fitness the group's first and last
  members by position (infinite distance) come before its interior ones
  (zero distance), each in position order;
* binary tournament on that key;
* simulated binary crossover (Deb & Agrawal 1995) over consecutive parent
  pairs, bounded, gated per pair and per gene;
* polynomial mutation (Deb et al. 2002), bounded, gated per individual and
  per gene;
* (mu + lambda) survivors: the best ``P`` of parents and offspring;
* after ``M`` generations, ring migration: the first of island ``k - 1``
  in that order replaces one random non-elite slot of island ``k``.

The published rule ties a front's boundary points at infinite distance,
and the program departs from it there: its crowding gives a boundary
point ``1e30 / range`` per objective, not infinity, which orders boundary
points among themselves. The reference follows that documented departure:
it breaks the tie first by the sum, over the objectives in which a point
is a boundary, of one over the front's range of that objective, larger
first, then by position. On one objective every boundary point is one of
a group of equal fitness, and the order is the published one.

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


def _dominates(fitness):
    """(P, P): [i, j] where ``i`` is no worse than ``j`` in every objective
    and better in one, for (P, M) fitness."""
    p = fitness.shape[0]
    leq = jnp.ones((p, p), bool)
    lt = jnp.zeros((p, p), bool)
    for m in range(fitness.shape[1]):
        a, b = fitness[:, None, m], fitness[None, :, m]
        leq &= a <= b
        lt |= a < b
    return leq & lt


@jax.jit
def fronts(fitness):
    """Front per individual (0: non-dominated) for (P, M) fitness: each
    pass takes the individuals that none of those left dominates."""
    p = fitness.shape[0]
    dom = _dominates(fitness)

    def peel(state):
        front, k = state
        left = front < 0
        beaten = jnp.any(dom & left[:, None], axis=0)
        return jnp.where(left & ~beaten, k, front), k + 1

    front, _ = jax.lax.while_loop(lambda s: jnp.any(s[0] < 0), peel,
                                  (jnp.full((p,), -1, jnp.int32),
                                   jnp.int32(0)))
    return front


@jax.jit
def crowding(fitness, front):
    """Crowding distance within each front, for (P, M) fitness, and each
    boundary point's sum of one over the front's range of the objectives
    in which it is a boundary (0 for the others)."""
    p = fitness.shape[0]
    dist = jnp.zeros((p,), jnp.float32)
    weight = jnp.zeros((p,), jnp.float32)
    for m in range(fitness.shape[1]):
        f = fitness[:, m]
        by = jnp.lexsort((f, front))                # front, value, position
        fs, fr = f[by], front[by]
        first = jnp.concatenate([jnp.array([True]), fr[1:] != fr[:-1]])
        last = jnp.concatenate([fr[:-1] != fr[1:], jnp.array([True])])
        span = (jax.ops.segment_max(f, front, num_segments=p)
                - jax.ops.segment_min(f, front, num_segments=p))[fr]
        gap = (jnp.concatenate([fs[1:], fs[-1:]])
               - jnp.concatenate([fs[:1], fs[:-1]]))
        inner = jnp.where(span > 0, gap / jnp.where(span > 0, span, 1.0), 0.0)
        edge = first | last
        dist = dist + jnp.zeros((p,), jnp.float32).at[by].set(
            jnp.where(edge, jnp.inf, inner))
        weight = weight + jnp.zeros((p,), jnp.float32).at[by].set(
            jnp.where(edge, 1.0 / span, 0.0))
    return dist, weight


def _order(fitness):
    """Positions in NSGA-II selection order, best first, for (P,) or
    (P, M) fitness."""
    if fitness.ndim == 2:
        front = fronts(fitness)
        dist, weight = crowding(fitness, front)
        return jnp.lexsort((jnp.arange(fitness.shape[0]), -weight, -dist,
                            front))
    by_f = jnp.argsort(fitness, stable=True)
    f = fitness[by_f]
    edge = (jnp.concatenate([jnp.array([True]), f[1:] != f[:-1]])
            | jnp.concatenate([f[:-1] != f[1:], jnp.array([True])]))
    inner = jnp.zeros(fitness.shape, bool).at[by_f].set(~edge)
    return jnp.lexsort((jnp.arange(fitness.shape[0]), inner, fitness))


def _island_offspring(genomes, fitness, key, scal, lo, hi):
    """One island's offspring. genomes (P, G), fitness (P,) or (P, M);
    ``scal`` is (crossover eta, crossover prob, mutation eta, mutation
    prob, indpb)."""
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
            jnp.take_along_axis(
                pool_f, keep.reshape(keep.shape + (1,) * (pool_f.ndim - 2)),
                axis=1))


@jax.jit
def _migrate(genomes, fitness, rng):
    """Ring migration of the first individual in selection order."""
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


def generations(genomes, fitness, rng, fit_fn, ga: dict):
    """An epoch's generations from a population, before its migration.

    genomes (I, P, G); fitness (I, P) or (I, P, M), where (I, P, 1) is
    taken as (I, P); rng (I, 2) uint32 per-island keys; ``fit_fn`` maps
    (N, G) genomes to (N, M) fitness, or to (N,) for one objective; ``ga``
    holds the configuration's GA settings (``crossover_eta``,
    ``crossover_prob``, ``mutation_eta``, ``mutation_prob``, ``lower``,
    ``upper``, ``generations_per_epoch``). Returns (genomes, fitness,
    rng).
    """
    genomes = jnp.asarray(genomes, jnp.float32)
    fitness = jnp.asarray(fitness, jnp.float32)
    if fitness.ndim == 3 and fitness.shape[2] == 1:
        fitness = fitness[..., 0]
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
                                      off_fit.reshape(fitness.shape))
    return genomes, fitness, rng


def migrate(genomes, fitness, rng):
    """The epoch's ring migration, from what ``generations`` returns.
    Returns (genomes, fitness, rng, sent): the next population and each
    island's emigrant."""
    return _migrate(genomes, fitness, rng)


def epoch(genomes, fitness, rng, fit_fn, ga: dict):
    """One epoch from a population, as ``generations`` takes it. Returns
    (genomes, fitness, rng, sent): the next population and each island's
    emigrant before migration."""
    return migrate(*generations(genomes, fitness, rng, fit_fn, ga))


@jax.jit
def boundaries(fitness):
    """(P,) mask: the first front's boundary points (those at infinite
    crowding distance), for (P, M) fitness; rows of infinite fitness are
    dominated by every finite row and so never among them."""
    front = fronts(fitness)
    dist, _ = crowding(fitness, front)
    return (front == 0) & jnp.isinf(dist)
