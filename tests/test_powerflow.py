"""Powerflow substrate tests: Newton solve, contingencies, DC/LODF, HVDC."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.powerflow.contingency import (contingency_loadings,
                                         penalized_objective)
from repro.powerflow.dc import build_dc_model, dc_flows, screen_contingencies
from repro.powerflow.grid import make_synthetic_grid
from repro.powerflow.hvdc import (HVDC_LOSS, apply_hvdc,
                                  scale_genome_to_dispatch)
from repro.powerflow.newton import (PFResult, _ds_dv, _sbus, line_flows,
                                    newton_powerflow)


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


def _masked_scan_powerflow(gridj, p_extra, num_iters, tol=5e-4,
                           line_mask=None) -> PFResult:
    """The fixed-schedule form of the solve: all ``num_iters`` iterations
    run, and a convergence mask freezes the voltages once converged."""
    bt = gridj["bus_type"]
    n = bt.shape[0]
    is_slack, is_pv, is_pq = bt == 2, bt == 1, bt == 0
    cdtype = gridj["ybus"].dtype
    if line_mask is None:
        ybus = gridj["ybus"]
    else:
        ys = gridj["y_series"] * line_mask.astype(gridj["y_series"].dtype)
        bc = (1j * gridj["b_sh"] / 2.0).astype(cdtype) * line_mask
        f, t = gridj["f_bus"], gridj["t_bus"]
        ybus = jnp.zeros((n, n), cdtype)
        ybus = ybus.at[f, f].add(ys + bc)
        ybus = ybus.at[t, t].add(ys + bc)
        ybus = ybus.at[f, t].add(-ys)
        ybus = ybus.at[t, f].add(-ys)
        ybus = ybus + 1e-6j * jnp.eye(n, dtype=cdtype)
    p_spec = gridj["p_inj"] + p_extra
    q_spec = gridj["q_inj"]
    vm0 = jnp.where(is_slack | is_pv, gridj["v_set"], 1.0)
    va0 = jnp.zeros((n,), jnp.float32)
    p_row, q_row = ~is_slack, is_pq

    def mismatch(vm, va):
        v = (vm * jnp.exp(1j * va)).astype(cdtype)
        s = _sbus(ybus, v)
        dp = jnp.real(s) - p_spec
        dq = jnp.imag(s) - q_spec
        return jnp.where(p_row, dp, 0.0), jnp.where(q_row, dq, 0.0), v

    def jacobian(v):
        ds_dva, ds_dvm = _ds_dv(ybus, v)
        pr = p_row.astype(jnp.float32)
        qr = q_row.astype(jnp.float32)
        j11 = jnp.real(ds_dva) * pr[:, None] * pr[None, :]
        j12 = jnp.real(ds_dvm) * pr[:, None] * qr[None, :]
        j21 = jnp.imag(ds_dva) * qr[:, None] * pr[None, :]
        j22 = jnp.imag(ds_dvm) * qr[:, None] * qr[None, :]
        j11 = j11 + jnp.diag(1.0 - pr)
        j22 = j22 + jnp.diag(1.0 - qr)
        return jnp.block([[j11, j12], [j21, j22]])

    def body(carry, _):
        vm, va, done, it = carry
        dp, dq, v = mismatch(vm, va)
        dx = jnp.linalg.solve(jacobian(v), -jnp.concatenate([dp, dq]))
        err = jnp.maximum(jnp.max(jnp.abs(dp)), jnp.max(jnp.abs(dq)))
        upd = jnp.where(done, 0.0, 1.0)
        vm = vm + dx[n:] * q_row * upd
        va = va + dx[:n] * p_row * upd
        it = it + jnp.where(done, 0, 1).astype(jnp.int32)
        return (vm, va, done | (err < tol), it), err

    with jax.default_matmul_precision("highest"):
        (vm, va, _, iters), _ = jax.lax.scan(
            body, (vm0, va0, jnp.zeros((), bool), jnp.zeros((), jnp.int32)),
            None, length=num_iters)
        dp, dq, _ = mismatch(vm, va)
    final_err = jnp.maximum(jnp.max(jnp.abs(dp)), jnp.max(jnp.abs(dq)))
    return PFResult(vm=vm, va=va, mismatch=final_err,
                    converged=final_err < tol, iters=iters)


class TestNewtonStopsAtConvergence:
    """The solve stops once converged and returns, bit for bit, what the
    fixed masked schedule returns, one genome at a time or batched."""

    @pytest.mark.parametrize("batching,num_iters,outage", [
        ("map", 10, False), ("vmap", 10, False), ("map", 2, False),
        ("vmap", 2, False), ("map", 10, True)])
    def test_bit_identical_to_masked_schedule(self, gj, batching, num_iters,
                                              outage):
        genomes = jax.random.uniform(jax.random.PRNGKey(11), (6, 4),
                                     minval=-1.0, maxval=1.0)
        mask = (jnp.ones(gj["rate"].shape[0]).at[3].set(0.0) if outage
                else None)

        def solves(solve):
            def one(g):
                p_extra = apply_hvdc(gj, scale_genome_to_dispatch(gj, g))
                return solve(p_extra)
            if batching == "map":
                return jax.jit(lambda gs: jax.lax.map(one, gs))(genomes)
            return jax.jit(jax.vmap(one))(genomes)

        got = solves(lambda p: newton_powerflow(
            gj, p_extra=p, num_iters=num_iters, line_mask=mask))
        want = solves(lambda p: _masked_scan_powerflow(
            gj, p, num_iters, line_mask=mask))
        for field in PFResult._fields:
            np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                          np.asarray(getattr(want, field)),
                                          err_msg=field)
        iters = np.asarray(got.iters)
        if num_iters == 2:
            assert not np.any(got.converged) and np.all(iters == 2)
        else:
            assert np.all(got.converged) and iters.max() < num_iters

    def test_loop_is_a_while_not_a_scan(self, gj):
        def primitives(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn.primitive.name
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from primitives(sub)

        closed = jax.make_jaxpr(
            lambda p: newton_powerflow(gj, p_extra=p, num_iters=10))(
                jnp.zeros_like(gj["p_inj"]))
        assert "while" in [e.primitive.name for e in closed.jaxpr.eqns]
        assert "scan" not in set(primitives(closed.jaxpr))


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
