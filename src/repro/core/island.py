"""Asynchronous island-model GA (paper §3, Fig. 2).

One jitted ``epoch_step`` runs M generations of island-local evolution —
compiled HLO for the generation body contains **no cross-island
collectives** (the paper's "removal of synchronization barriers") — then a
single ring migration. The island axis shards over the mesh `data` axis, so
migration lowers to a CollectivePermute and the broker's balanced dispatch
to an all-to-all; everything else is island-local.

Each stage carries a profiler scope (``jax.named_scope``), so a device
trace names its ops: ``chambga.selection`` (NSGA-II keys and the
tournament), ``chambga.variation``, ``chambga.fitness`` and
``chambga.dispatch`` (the broker), ``chambga.survivor`` and
``chambga.migration``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import GAConfig
from repro.core import nsga2, operators
from repro.core.broker import Broker
from repro.core.population import Population
from repro.models.sharding import ShardingCtx


def _island_spec(ctx: Optional[ShardingCtx]):
    return None if ctx is None or ctx.mesh is None else ctx.dp_spec


def constrain_pop(pop: Population, ctx: Optional[ShardingCtx]) -> Population:
    if ctx is None or ctx.mesh is None:
        return pop
    isp = ctx.dp_spec
    return pop._replace(
        genomes=ctx.cs(pop.genomes, isp, None, None),
        fitness=ctx.cs(pop.fitness, isp, None, None),
        rng=ctx.cs(pop.rng, isp, None))


def make_generation_step(cfg: GAConfig, broker: Broker,
                         ctx: Optional[ShardingCtx] = None,
                         hyper: Optional[dict] = None) -> Callable:
    """One NSGA-II generation for all islands (no cross-island sync).

    `hyper` optionally overrides {eta_cx, prob_cx, eta_mut, prob_mut,
    pop_active} with traced values (meta-GA path).
    """
    lo, hi = cfg.bounds()
    lo, hi = jnp.asarray(lo), jnp.asarray(hi)
    h = hyper or {}
    eta_cx = h.get("eta_cx", cfg.crossover_eta)
    prob_cx = h.get("prob_cx", cfg.crossover_prob)
    eta_mut = h.get("eta_mut", cfg.mutation_eta)
    prob_mut = h.get("prob_mut", cfg.mutation_prob)
    pop_active = h.get("pop_active", None)
    indpb = cfg.indpb

    def one_island_variation(rng, genomes, key):
        k_sel, k_var = jax.random.split(rng)
        with jax.named_scope("chambga.selection"):
            parents_idx = operators.tournament_select(
                k_sel, key.astype(jnp.float32), cfg.pop_per_island,
                active=pop_active, tsize=cfg.tournament_size)
            parents = genomes[parents_idx]
        with jax.named_scope("chambga.variation"):
            return operators.variation(
                k_var, parents, eta_cx=eta_cx, prob_cx=prob_cx,
                eta_mut=eta_mut, prob_mut=prob_mut, indpb=indpb,
                lower=lo, upper=hi, use_kernel=cfg.fused_operators)

    def generation(pop: Population, _=None) -> Tuple[Population, dict]:
        i, p, g = pop.genomes.shape
        rngs = jax.vmap(jax.random.split)(pop.rng)          # (I, 2, 2)
        step_rng, next_rng = rngs[:, 0], rngs[:, 1]

        # island-local selection keys (rank, crowding)
        with jax.named_scope("chambga.selection"):
            _, _, keys = jax.vmap(nsga2.nsga2_keys)(pop.fitness)
            if pop_active is not None:
                slot = jnp.arange(p)[None, :]
                keys = jnp.where(slot < pop_active, keys, 2 ** 30)

        variation = jax.vmap(one_island_variation)
        if ctx is not None and ctx.mesh is not None:
            # variation is island-local, and GSPMD cannot partition the
            # Pallas kernel: each device varies its own islands
            isp = P(ctx.dp_spec)
            variation = jax.shard_map(variation, mesh=ctx.mesh,
                                      in_specs=(isp, isp, isp),
                                      out_specs=isp, check_vma=False)
        offspring = variation(step_rng, pop.genomes, keys)

        # shared-pool evaluation (the broker = the paper's queue)
        flat = offspring.reshape(i * p, g)
        fit_flat, stats = broker.evaluate(flat)
        off_fit = fit_flat.reshape(i, p, -1)
        if pop_active is not None:
            slot = jnp.arange(p)[None, :, None]
            off_fit = jnp.where(slot < pop_active, off_fit, jnp.inf)

        # (mu+lambda) island-local survivor selection
        with jax.named_scope("chambga.survivor"):
            comb_g = jnp.concatenate([pop.genomes, offspring], axis=1)
            comb_f = jnp.concatenate([pop.fitness, off_fit], axis=1)
            new_g, new_f = jax.vmap(lambda gg, ff: nsga2.survivor_select(
                gg, ff, p))(comb_g, comb_f)

        newpop = Population(
            genomes=new_g, fitness=new_f, rng=next_rng,
            generation=pop.generation + 1, epoch=pop.epoch,
            evals=pop.evals + i * p)
        newpop = constrain_pop(newpop, ctx)
        metrics = {"best": jnp.min(new_f[..., 0], axis=1),   # per island
                   "skew": stats["skew"],
                   "balanced": stats["balanced"],
                   "fitness": stats["fitness"]}
        return newpop, metrics

    return generation


def _migration_shifts(topology: str, num_islands: int) -> list:
    """Island-axis shifts per topology (generalized island model,
    Izzo et al. 2012 — cited by the paper). Each shift s means: island k
    sends its elites to island (k+s) mod I."""
    if topology == "ring":
        return [1]
    if topology == "bidirectional":
        return [1, -1]
    if topology == "torus":
        # 2D neighbors on a near-square factorization of I
        a = max(1, int(num_islands ** 0.5))
        while num_islands % a:
            a -= 1
        return [1, num_islands // a] if a > 1 else [1]
    if topology == "all":
        return list(range(1, num_islands))
    raise ValueError(topology)


def migrate_ring(cfg: GAConfig, pop: Population,
                 ctx: Optional[ShardingCtx] = None) -> Population:
    """Migration: best `m` of island k replace random slots of each
    neighbor per the configured topology (paper §4 uses "ring": "sending
    out the best individual and replacing a randomly selected individual").
    On a sharded island axis each shift lowers to a CollectivePermute —
    the ICI ring IS the migration ring.
    """
    m = cfg.num_migrants
    i, p, g = pop.genomes.shape
    shifts = _migration_shifts(cfg.migration_pattern, i)
    rngs = jax.vmap(jax.random.split)(pop.rng)
    mig_rng, next_rng = rngs[:, 0], rngs[:, 1]

    genomes, fitness = pop.genomes, pop.fitness
    for si, shift in enumerate(shifts):
        _, _, keys = jax.vmap(nsga2.nsga2_keys)(fitness)
        order = jnp.argsort(keys, axis=1)                  # best first
        best_idx = order[:, :m]                            # (I, m)
        send_g = jnp.take_along_axis(genomes, best_idx[..., None], axis=1)
        send_f = jnp.take_along_axis(fitness, best_idx[..., None], axis=1)

        recv_g = jnp.roll(send_g, shift, axis=0)           # permute on ICI
        recv_f = jnp.roll(send_f, shift, axis=0)

        # random non-elite victims: positions >= m in sorted order
        k = jax.vmap(lambda r, s=si: jax.random.fold_in(r, s))(mig_rng)
        u = jax.vmap(lambda r: jax.random.uniform(r, (m,)))(k)
        victim_rank = (m + jnp.floor(u * (p - m))).astype(jnp.int32)
        victim = jnp.take_along_axis(order, victim_rank, axis=1)   # (I, m)

        def replace(gm, fm, vid, rg, rf):
            return gm.at[vid].set(rg), fm.at[vid].set(rf)

        genomes, fitness = jax.vmap(replace)(genomes, fitness, victim,
                                             recv_g, recv_f)
    newpop = pop._replace(genomes=genomes, fitness=fitness, rng=next_rng,
                          epoch=pop.epoch + 1)
    return constrain_pop(newpop, ctx)


def make_epoch_step(cfg: GAConfig, broker: Broker,
                    ctx: Optional[ShardingCtx] = None,
                    hyper: Optional[dict] = None) -> Callable:
    """M island-local generations + one ring migration, as one jit unit."""
    generation = make_generation_step(cfg, broker, ctx, hyper)

    def epoch_step(pop: Population) -> Tuple[Population, dict]:
        pop, metrics = jax.lax.scan(
            generation, pop, None, length=cfg.generations_per_epoch)
        with jax.named_scope("chambga.migration"):
            pop = migrate_ring(cfg, pop, ctx)
        # metrics: (M, I) best trace per generation
        return pop, metrics

    return epoch_step


def evaluate_population(cfg: GAConfig, broker: Broker,
                        pop: Population) -> Population:
    """Initial fitness evaluation of a fresh population."""
    i, p, g = pop.genomes.shape
    with jax.named_scope("chambga.fitness"):
        fit, _ = broker.evaluate(pop.genomes.reshape(i * p, g))
    return pop._replace(fitness=fit.reshape(i, p, -1),
                        evals=pop.evals + i * p)
