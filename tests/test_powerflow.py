"""Powerflow substrate tests: Newton solve, contingencies, DC/LODF, HVDC."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.powerflow.contingency import (contingency_loadings,
                                         penalized_objective)
from repro.powerflow.dc import build_dc_model, dc_flows, screen_contingencies
from repro.powerflow.grid import make_synthetic_grid
from repro.powerflow.hvdc import HVDC_LOSS, apply_hvdc
from repro.powerflow.newton import newton_powerflow, line_flows


@pytest.fixture(scope="module")
def small_grid():
    return make_synthetic_grid(n_bus=60, n_line=110, n_gen=15, n_hvdc=4,
                               seed=1)


@pytest.fixture(scope="module")
def gj(small_grid):
    return small_grid.to_jax()


class TestNewton:
    def test_converges(self, gj):
        res = newton_powerflow(gj, num_iters=12)
        assert bool(res.converged)
        assert float(res.mismatch) < 5e-4
        assert int(res.iters) <= 8

    def test_voltages_physical(self, gj):
        res = newton_powerflow(gj, num_iters=12)
        vm = np.asarray(res.vm)
        assert vm.min() > 0.85 and vm.max() < 1.15

    def test_power_balance(self, gj, small_grid):
        """Slack absorbs imbalance: total injection ~ losses > 0."""
        res = newton_powerflow(gj, num_iters=12)
        v = np.asarray(res.vm) * np.exp(1j * np.asarray(res.va))
        ybus = small_grid.ybus()
        s = v * np.conj(ybus @ v)
        losses = np.real(s).sum()
        assert 0.0 < losses < 0.1 * small_grid.p_load.sum()

    def test_flat_start_zero_injection(self):
        g = make_synthetic_grid(n_bus=20, n_line=35, n_gen=5, n_hvdc=2,
                                seed=4, total_load_pu=0.0)
        g.p_gen[:] = 0.0
        g.v_set[:] = 1.0
        g.b_sh[:] = 0.0            # no line charging: exact flat solution
        res = newton_powerflow(g.to_jax(), num_iters=6)
        assert bool(res.converged)
        np.testing.assert_allclose(np.asarray(res.va), 0.0, atol=1e-4)

    def test_contingency_mask_changes_solution(self, gj):
        base = newton_powerflow(gj, num_iters=12)
        mask = jnp.ones(gj["rate"].shape[0]).at[3].set(0.0)
        out = newton_powerflow(gj, num_iters=12, line_mask=mask)
        assert bool(out.converged)
        assert not np.allclose(np.asarray(base.va), np.asarray(out.va))
        fl = line_flows(gj, out.vm, out.va, line_mask=mask)
        assert float(fl[3]) == 0.0               # outaged line carries nothing


class TestHVDC:
    def test_injection_balance(self, gj):
        d = jnp.asarray([1.0, -0.5, 0.25, 0.0])
        inj = apply_hvdc(gj, d)
        # withdraw - inject = loss * |transfer| (net consumption)
        np.testing.assert_allclose(float(jnp.sum(inj)),
                                   -HVDC_LOSS * float(jnp.sum(d)),
                                   rtol=1e-5)

    def test_dispatch_changes_flows(self, gj):
        r0 = newton_powerflow(gj, num_iters=12)
        inj = apply_hvdc(gj, jnp.asarray([5.0, 0.0, 0.0, 0.0]))
        r1 = newton_powerflow(gj, p_extra=inj, num_iters=12)
        f0 = line_flows(gj, r0.vm, r0.va)
        f1 = line_flows(gj, r1.vm, r1.va)
        assert float(jnp.max(jnp.abs(f0 - f1))) > 1e-3


class TestDCScreening:
    def test_dc_ac_correlation(self, gj):
        dc = build_dc_model(gj)
        f_dc = np.abs(np.asarray(dc_flows(dc, gj["p_inj"])))
        res = newton_powerflow(gj, num_iters=12)
        f_ac = np.asarray(line_flows(gj, res.vm, res.va))
        corr = np.corrcoef(f_dc, f_ac)[0, 1]
        assert corr > 0.95

    def test_lodf_screening_finds_critical(self, gj):
        """Screened top-K must cover the truly critical outages (by AC):
        the non-converging (islanding) cases and the worst overload."""
        dc = build_dc_model(gj)
        nl = gj["rate"].shape[0]
        top = set(np.asarray(screen_contingencies(
            dc, gj["p_inj"], gj["rate"], top_k=12)).tolist())
        # brute-force by full AC
        cases = jnp.arange(nl)
        loadings = contingency_loadings(gj, cases, num_iters=10)
        worst_ac = np.asarray(jnp.max(loadings, axis=1))
        nonconv = set(np.where(worst_ac >= 9.99)[0].tolist())
        # screening must catch most islanding outages ...
        assert len(nonconv & top) >= max(1, len(nonconv) - 1)
        # ... and the single worst converged overload
        conv = np.where(worst_ac < 9.99)[0]
        worst_overload = int(conv[np.argmax(worst_ac[conv])])
        assert worst_overload in top or worst_ac[worst_overload] < 1.0

    def test_penalty_formula(self):
        """Paper eq. (3): +10% per critical, +1% per near-critical case."""
        loadings = jnp.asarray([
            [0.5, 1.2],        # critical (any line > 1.0)
            [0.97, 0.5],       # near-critical (>= 0.95, none > 1)
            [0.5, 0.5],        # fine
        ])
        out = penalized_objective(jnp.asarray(100.0), loadings)
        np.testing.assert_allclose(float(out), 100.0 * 1.11, rtol=1e-6)


class TestFitnessBackend:
    def test_hvdc_fitness_batched(self, small_grid):
        from repro.fitness.powerflow import HVDCDispatchFitness
        fit = HVDCDispatchFitness(small_grid, newton_iters=10)
        out = jax.jit(fit)(jnp.zeros((3, 4)))
        assert out.shape == (3, 1)
        assert bool(jnp.all(jnp.isfinite(out)))
        # zero dispatch beats a large random one on this objective
        big = jax.jit(fit)(jnp.ones((1, 4)))
        assert float(out[0, 0]) < float(big[0, 0])

    def test_cost_model_monotone(self, small_grid):
        from repro.fitness.powerflow import HVDCDispatchFitness
        fit = HVDCDispatchFitness(small_grid, newton_iters=8)
        cost = fit.cost_model()
        c0 = cost(jnp.zeros((1, 4)))
        c1 = cost(jnp.ones((1, 4)))
        assert float(c1[0]) > float(c0[0])

    def test_hvdc_one_genome_at_a_time_matches_vmap(self, small_grid):
        """The per-genome ``lax.map`` form gives the batched-vmap values,
        and reports base-case convergence per genome."""
        from repro.fitness.powerflow import HVDCDispatchFitness
        fit = HVDCDispatchFitness(small_grid, newton_iters=10)
        genomes = jax.random.uniform(jax.random.PRNGKey(5), (6, 4),
                                     minval=-1.0, maxval=1.0)
        out, converged = jax.jit(fit.evaluate)(genomes)
        batched = jax.jit(jax.vmap(lambda g: fit._one(g)[0]))(genomes)
        np.testing.assert_allclose(np.asarray(out), np.asarray(batched),
                                   rtol=1e-5)
        assert converged.shape == (6,) and bool(jnp.all(converged))
        np.testing.assert_array_equal(np.asarray(jax.jit(fit)(genomes)),
                                      np.asarray(out))
