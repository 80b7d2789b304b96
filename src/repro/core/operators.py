"""Genetic variation operators (Deb's NSGA-II forms, bounded).

* binary tournament selection on (rank, -crowding) lexicographic keys
* simulated binary crossover (SBX) [Deb & Agrawal 1995]
* polynomial mutation [Deb et al. 2002]

All operators act on one island's (P, G) genome block and are vmapped over
islands by `island.py`. Hyperparameters (eta, probabilities) may be traced
scalars — required by the meta-GA, whose genomes *are* these parameters.

The fused Pallas kernel in ``repro.kernels.genetic`` implements
select->SBX->mutate->clip in one VMEM pass; ``ops.variation`` dispatches to
it when enabled, with these functions as the oracle.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

EPS = 1e-14


def tournament_select(rng: jax.Array, key: jax.Array, num: int,
                      active: jax.Array | None = None,
                      tsize: int = 2) -> jax.Array:
    """Select `num` indices by binary tournament on minimizing `key` (P,).

    `active`: optional traced scalar — candidates are drawn from
    [0, active) (meta-GA variable population size).
    """
    p = key.shape[0]
    hi = jnp.asarray(p if active is None else active, jnp.float32)
    u = jax.random.uniform(rng, (num, tsize))
    cand = jnp.floor(u * hi).astype(jnp.int32)            # (num, tsize)
    cand_keys = key[cand]                                 # (num, tsize)
    winner = jnp.argmin(cand_keys, axis=1)
    return jnp.take_along_axis(cand, winner[:, None], axis=1)[:, 0]


def sbx_crossover(rng: jax.Array, x1: jax.Array, x2: jax.Array, *,
                  eta, prob, lower, upper) -> Tuple[jax.Array, jax.Array]:
    """Bounded simulated binary crossover. x1/x2: (N, G)."""
    k_pair, k_gene, k_u = jax.random.split(rng, 3)
    do_pair = jax.random.uniform(k_pair, x1.shape[:1]) < prob     # (N,)
    do_gene = jax.random.uniform(k_gene, x1.shape) < 0.5          # per-gene
    u = jax.random.uniform(k_u, x1.shape)

    y1 = jnp.minimum(x1, x2)
    y2 = jnp.maximum(x1, x2)
    span = jnp.maximum(y2 - y1, EPS)

    def betaq_for(beta):
        alpha = 2.0 - jnp.power(beta, -(eta + 1.0))
        inside = u <= 1.0 / alpha
        bq = jnp.where(
            inside,
            jnp.power(u * alpha, 1.0 / (eta + 1.0)),
            jnp.power(1.0 / jnp.maximum(2.0 - u * alpha, EPS),
                      1.0 / (eta + 1.0)))
        return bq

    beta1 = 1.0 + 2.0 * (y1 - lower) / span
    beta2 = 1.0 + 2.0 * (upper - y2) / span
    c1 = 0.5 * ((y1 + y2) - betaq_for(beta1) * (y2 - y1))
    c2 = 0.5 * ((y1 + y2) + betaq_for(beta2) * (y2 - y1))
    c1 = jnp.clip(c1, lower, upper)
    c2 = jnp.clip(c2, lower, upper)

    apply = do_pair[:, None] & do_gene
    o1 = jnp.where(apply, c1, x1)
    o2 = jnp.where(apply, c2, x2)
    return o1, o2


def polynomial_mutation(rng: jax.Array, x: jax.Array, *,
                        eta, prob, indpb, lower, upper) -> jax.Array:
    """Bounded polynomial mutation. x: (N, G).

    `prob` gates whole individuals (paper Tab. 3/4 semantics); `indpb`
    gates genes within a mutating individual (DEAP's indpb).
    """
    k_ind, k_gene, k_u = jax.random.split(rng, 3)
    do_ind = jax.random.uniform(k_ind, x.shape[:1]) < prob
    do_gene = jax.random.uniform(k_gene, x.shape) < indpb
    u = jax.random.uniform(k_u, x.shape)

    span = upper - lower
    d1 = (x - lower) / span
    d2 = (upper - x) / span
    mut_pow = 1.0 / (eta + 1.0)

    lo_branch = jnp.power(
        jnp.maximum(2.0 * u + (1.0 - 2.0 * u)
                    * jnp.power(1.0 - d1, eta + 1.0), EPS), mut_pow) - 1.0
    hi_branch = 1.0 - jnp.power(
        jnp.maximum(2.0 * (1.0 - u) + 2.0 * (u - 0.5)
                    * jnp.power(1.0 - d2, eta + 1.0), EPS), mut_pow)
    deltaq = jnp.where(u < 0.5, lo_branch, hi_branch)

    x_new = jnp.clip(x + deltaq * span, lower, upper)
    apply = do_ind[:, None] & do_gene
    return jnp.where(apply, x_new, x)


def variation(rng: jax.Array, parents: jax.Array, *, eta_cx, prob_cx,
              eta_mut, prob_mut, indpb, lower, upper,
              use_kernel: bool = False) -> jax.Array:
    """SBX over consecutive parent pairs, then polynomial mutation.

    parents: (P, G) -> offspring (P, G). With P odd the unpaired last
    parent skips crossover and goes through mutation only (the fused
    kernel pairs parents, so odd P always takes the unfused path).
    """
    p = parents.shape[0]
    if use_kernel and p % 2 == 0:
        from repro.kernels.genetic import ops as gk
        return gk.fused_variation(
            rng, parents, eta_cx=eta_cx, prob_cx=prob_cx,
            eta_mut=eta_mut, prob_mut=prob_mut, indpb=indpb,
            lower=lower, upper=upper)
    k1, k2 = jax.random.split(rng)
    paired = parents[:p - 1] if p % 2 else parents
    p1, p2 = paired[0::2], paired[1::2]
    o1, o2 = sbx_crossover(k1, p1, p2, eta=eta_cx, prob=prob_cx,
                           lower=lower, upper=upper)
    off = jnp.stack([o1, o2], axis=1).reshape(paired.shape)
    if p % 2:
        off = jnp.concatenate([off, parents[p - 1:]], axis=0)
    return polynomial_mutation(k2, off, eta=eta_mut, prob=prob_mut,
                               indpb=indpb, lower=lower, upper=upper)
