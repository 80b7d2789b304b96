"""The harness on a population of three objectives, on the CPU: DTLZ2
(Deb, Thiele, Laumanns & Zitzler 2002) with M = 3 and k = 10, so 12 genes,
under NSGA-II's settings (Deb et al. 2002: p_c 0.9, eta_c 20, p_m 1/n,
eta_m 20), 2 islands of 16, ring migration every 5 generations.

A sound epoch reads ``correct``; faults planted in the program's timed
path each fail it, by the number that watches their layer. Then the
count of choices in which the program's order of a front's boundary
points, which the reference follows, departs from the published ties
broken by position (``published_order``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.deployment import (Deployment, ga_seed, ga_settings,
                                  program_config)
from chipbench.reference import ga
from repro.core import island, nsga2
from repro.core.engine import GAEngine

DTLZ2 = dict(num_genes=12, num_objectives=3, islands_per_chip=2,
             pop_per_island=16, generations_per_epoch=5, mutation_prob=1.0,
             mutation_eta=20.0, crossover_prob=0.9, crossover_eta=20.0,
             lower=0.0, upper=1.0, mutation_indpb="1/num_genes",
             num_migrants=1, migration_pattern="ring")
LIMITS = {"objective_gap": 2e-4, "unmatched_share": 0.5,
          "migrants_missing": 0}


def dtlz2(x):
    """The program's fitness: (N, 12) -> (N, 3), float32 on the device."""
    g = jnp.sum((x[:, 2:] - 0.5) ** 2, axis=1)
    a, b = x[:, 0] * jnp.pi / 2, x[:, 1] * jnp.pi / 2
    return jnp.stack([(1 + g) * jnp.cos(a) * jnp.cos(b),
                      (1 + g) * jnp.cos(a) * jnp.sin(b),
                      (1 + g) * jnp.sin(a)], axis=1)


def dtlz2_reference(x):
    """The same in float64 on the host."""
    x = np.asarray(x, np.float64)
    g = np.sum((x[:, 2:] - 0.5) ** 2, axis=1)
    a, b = x[:, 0] * np.pi / 2, x[:, 1] * np.pi / 2
    return np.stack([(1 + g) * np.cos(a) * np.cos(b),
                     (1 + g) * np.cos(a) * np.sin(b),
                     (1 + g) * np.sin(a)], axis=1)


def deployment(seed, **conf):
    conf = dict(DTLZ2, **conf)
    cfg = program_config(conf, 1, ga_seed(seed))
    return Deployment(
        cfg=cfg, fitness=dtlz2, cost_fn=None, ctx=None, ga_seed=cfg.seed,
        fitness_name="objective", reference=dtlz2_reference, control=None,
        ga=ga_settings(conf), limits=LIMITS, match_tol=1e-3, trace_epochs=1,
        shapes=dict(islands_per_chip=conf["islands_per_chip"],
                    pop_per_island=cfg.pop_per_island,
                    generations_per_epoch=cfg.generations_per_epoch))


def _check(dep):
    return run.check(dep, run.drive(jax, dep, 0.5, False))


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_sound_epoch_is_correct(seed):
    correct, failed, checks = _check(deployment(seed))
    assert correct and failed == 0, checks
    assert checks["unmatched_share"]["value"] == 0.0, checks


def test_host_population_with_one_objective(monkeypatch):
    def first_only(jax_, pop):
        g, f, r = jax_.device_get((pop.genomes, pop.fitness, pop.rng))
        return {"genomes": g, "fitness": f[..., 0], "rng": r}
    monkeypatch.setattr(run, "host_pop", first_only)
    correct, failed, checks = _check(deployment(7))
    assert not correct and failed == 2 * 2 * 16, checks
    assert checks["objective_gap"]["value"] == np.inf, checks


def test_survivors_by_the_first_objective_alone(monkeypatch):
    def by_first(genomes, fitness, mu):
        _, _, key = nsga2.nsga2_keys(fitness[:, :1])
        keep = jnp.argsort(key)[:mu]
        return genomes[keep], fitness[keep]
    monkeypatch.setattr(nsga2, "survivor_select", by_first)
    correct, _, checks = _check(deployment(8))
    assert not correct, checks
    assert checks["unmatched_share"]["value"] > LIMITS["unmatched_share"]


def test_migration_left_out(monkeypatch):
    def no_exchange(cfg, pop, ctx=None):
        rngs = jax.vmap(jax.random.split)(pop.rng)
        return pop._replace(rng=rngs[:, 1], epoch=pop.epoch + 1)
    monkeypatch.setattr(island, "migrate_ring", no_exchange)
    correct, _, checks = _check(deployment(9))
    assert not correct, checks
    assert checks["migrants_missing"]["value"] == 2, checks


def last_sent(cfg, pop, ctx=None):
    """Ring migration that sends each island's last individual in
    NSGA-II's order, not its first."""
    n_isl, p, _ = pop.genomes.shape
    rngs = jax.vmap(jax.random.split)(pop.rng)
    _, _, keys = jax.vmap(nsga2.nsga2_keys)(pop.fitness)
    order = jnp.argsort(keys, axis=1)
    isl = jnp.arange(n_isl)
    send = order[:, -1]
    u = jax.vmap(lambda r: jax.random.uniform(
        jax.random.fold_in(r, 0), (1,))[0])(rngs[:, 0])
    victim = order[isl, (1 + jnp.floor(u * (p - 1))).astype(jnp.int32)]
    genomes = pop.genomes.at[isl, victim].set(
        jnp.roll(pop.genomes[isl, send], 1, axis=0))
    fitness = pop.fitness.at[isl, victim].set(
        jnp.roll(pop.fitness[isl, send], 1, axis=0))
    return pop._replace(genomes=genomes, fitness=fitness, rng=rngs[:, 1],
                        epoch=pop.epoch + 1)


@pytest.mark.parametrize("seed", [2**31 + 4, 2**31 + 5, 2**31 + 6])
def test_last_in_crowded_order_sent(monkeypatch, seed):
    monkeypatch.setattr(island, "migrate_ring", last_sent)
    correct, _, checks = _check(deployment(seed))
    assert not correct, checks
    assert checks["migrants_missing"]["value"] == 2, checks
    assert checks["unmatched_share"]["value"] <= LIMITS["unmatched_share"]


def published_order(fitness):
    """NSGA-II's crowded-comparison order as published: front, crowding
    distance larger first, and the boundary points' tie at infinity
    broken by position alone."""
    front = ga.fronts(fitness)
    dist, _ = ga.crowding(fitness, front)
    return jnp.lexsort((jnp.arange(fitness.shape[0]), -dist, front))


def departures(pop_per_island, seeds):
    """Over one generation from each seed's population after one epoch of
    the program: the tournaments, and the islands' survivor selections, in
    which the program's order picks otherwise than the published ties
    broken by position (``published_order``, ``"position"``), and than
    the reference (``"range"``); with the number of decisions. A survivor selection keeps
    another set, or the same set in another order, which puts survivors
    at other positions. Every tournament that departs from ``"position"``
    departs between boundary points of one front."""
    counts = {"tournaments": 0, "tournament_position": 0,
              "tournament_range": 0, "survivors": 0,
              "survivor_set_position": 0, "survivor_order_position": 0,
              "survivor_set_range": 0, "survivor_order_range": 0}
    for seed in seeds:
        dep = deployment(seed, pop_per_island=pop_per_island)
        eng = GAEngine(dep.cfg, dep.fitness)
        pop, _ = eng.run(eng.init(dep.ga_seed), epochs=1)
        f, rng = jnp.asarray(pop.fitness), jnp.asarray(pop.rng)
        p = pop_per_island
        scal = jnp.asarray([20.0, 0.9, 20.0, 1.0, 1 / 12], jnp.float32)
        off, _ = ga._offspring(pop.genomes, f, rng, scal, bounds=(0.0, 1.0))
        off_f = dtlz2(off.reshape(-1, 12)).reshape(f.shape)
        keys = jax.vmap(jax.random.split)(rng)[:, 0]
        for i in range(f.shape[0]):
            k_sel = jax.random.split(keys[i])[0]
            cand = np.asarray(jnp.floor(jax.random.uniform(k_sel, (p, 2))
                                        * jnp.float32(p)).astype(jnp.int32))
            pool = jnp.concatenate([f[i], off_f[i]])
            for fit, what in ((f[i], "tournament"), (pool, "survivor")):
                _, _, key = nsga2.nsga2_keys(fit)
                prog = np.argsort(np.asarray(key), kind="stable")
                front = np.asarray(ga.fronts(fit))
                dist, _ = ga.crowding(fit, jnp.asarray(front))
                for rule, order in (("position", published_order),
                                    ("range", ga._order)):
                    ref = np.asarray(order(fit))
                    if what == "survivor":
                        counts[f"survivor_set_{rule}"] += (
                            set(prog[:p]) != set(ref[:p]))
                        counts[f"survivor_order_{rule}"] += (
                            not np.array_equal(prog[:p], ref[:p]))
                        continue
                    rank_p, rank_r = np.argsort(prog), np.argsort(ref)
                    won_p = np.where(rank_p[cand[:, 0]] <= rank_p[cand[:, 1]],
                                     cand[:, 0], cand[:, 1])
                    won_r = np.where(rank_r[cand[:, 0]] <= rank_r[cand[:, 1]],
                                     cand[:, 0], cand[:, 1])
                    counts[f"tournament_{rule}"] += int(np.sum(won_p != won_r))
                    for a, b in zip(won_p[won_p != won_r],
                                    won_r[won_p != won_r]):
                        assert front[a] == front[b], (a, b)
                        assert np.isinf(dist[a]) and np.isinf(dist[b])
                counts["tournaments" if what == "tournament"
                       else "survivors"] += p if what == "tournament" else 1
    return counts


@pytest.mark.parametrize("pop_per_island", [16, 256])
def test_boundary_order_departures(pop_per_island):
    counts = departures(pop_per_island, seeds=range(11, 15))
    print(pop_per_island, counts)
    assert (counts["tournament_range"] == counts["survivor_order_range"]
            == 0), counts
