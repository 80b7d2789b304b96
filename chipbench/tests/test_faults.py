"""Faults planted underneath a run: each must make ``correct`` false.

The run is the harness's own set-up, window, replay epoch and check, on
the CPU at a tiny size, with the device check skipped and one fault
planted in the program's timed path."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.configs import hvdc_german
from chipbench.tests.test_harness_cpu import MIX, tiny_hvdc
from repro.core import engine, island


def _correct(dep):
    out = run.drive(jax, dep, 1.0, False)
    return run.check(dep, out)


@pytest.fixture
def dep():
    return hvdc_german.build(tiny_hvdc(), MIX, seed=22, chips=1)


def test_sound_run_is_correct(dep):
    correct, _, checks = _correct(dep)
    assert correct, checks


def test_step_returning_its_state_unchanged(dep, monkeypatch):
    def make_epoch_step(cfg, broker, ctx=None, hyper=None):
        def step(pop):
            m, i = cfg.generations_per_epoch, pop.genomes.shape[0]
            best = jnp.broadcast_to(jnp.min(pop.fitness[..., 0], 1), (m, i))
            return pop, {"best": best, "skew": jnp.ones((m,)),
                         "balanced": jnp.zeros((m,))}
        return step
    monkeypatch.setattr(engine, "make_epoch_step", make_epoch_step)
    correct, _, checks = _correct(dep)
    assert not correct, checks


def test_half_of_the_batch_left_out(dep):
    full = dep.fitness

    def half(genomes):
        n = genomes.shape[0] // 2
        f = full(genomes[:n])
        return jnp.concatenate([f, f[: genomes.shape[0] - n]])
    dep.fitness = half
    correct, failed, checks = _correct(dep)
    assert not correct and failed > 0, checks


def test_migration_left_out(dep, monkeypatch):
    def no_exchange(cfg, pop, ctx=None):
        rngs = jax.vmap(jax.random.split)(pop.rng)
        return pop._replace(rng=rngs[:, 1], epoch=pop.epoch + 1)
    monkeypatch.setattr(island, "migrate_ring", no_exchange)
    correct, _, checks = _correct(dep)
    assert not correct, checks
    assert checks["migrants_missing"]["value"] == dep.cfg.num_islands, checks


def test_an_answer_altered_where_it_is_produced(dep):
    full = dep.fitness

    def altered(genomes):
        return full(genomes).at[0].add(-1.0)
    dep.fitness = altered
    correct, _, checks = _correct(dep)
    assert not correct, checks

