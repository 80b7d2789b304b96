"""Unit + property tests for the genetic variation operators."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import operators

KEY = jax.random.PRNGKey(0)


class TestTournament:
    def test_selects_better_more_often(self):
        key = jnp.arange(32, dtype=jnp.float32)            # 0 is best
        idx = operators.tournament_select(KEY, key, 4096)
        # winners skew low: mean selected key < population mean
        assert float(jnp.mean(key[idx])) < float(jnp.mean(key))

    def test_active_bound(self):
        key = jnp.zeros(64)
        idx = operators.tournament_select(KEY, key, 1000, active=10)
        assert int(jnp.max(idx)) < 10

    def test_indices_in_range(self):
        idx = operators.tournament_select(KEY, jnp.zeros(17), 100)
        assert int(jnp.min(idx)) >= 0 and int(jnp.max(idx)) < 17


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(2, 16),
    g=st.integers(1, 12),
    eta=st.floats(0.02, 100.0),
    prob=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**30),
)
def test_sbx_bounds_property(n, g, eta, prob, seed):
    """SBX offspring always within bounds, any eta/prob/bounds."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    lo, hi = -2.0, 3.0
    x1 = jax.random.uniform(k1, (n, g), minval=lo, maxval=hi)
    x2 = jax.random.uniform(k2, (n, g), minval=lo, maxval=hi)
    o1, o2 = operators.sbx_crossover(k3, x1, x2, eta=eta, prob=prob,
                                     lower=lo, upper=hi)
    for o in (o1, o2):
        assert bool(jnp.all(o >= lo - 1e-5)) and bool(jnp.all(o <= hi + 1e-5))
        assert bool(jnp.all(jnp.isfinite(o)))


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 16),
    g=st.integers(1, 12),
    eta=st.floats(0.02, 100.0),
    prob=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**30),
)
def test_mutation_bounds_property(n, g, eta, prob, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    lo, hi = -1.5, 0.5
    x = jax.random.uniform(k1, (n, g), minval=lo, maxval=hi)
    y = operators.polynomial_mutation(k2, x, eta=eta, prob=prob,
                                      indpb=0.5, lower=lo, upper=hi)
    assert bool(jnp.all(y >= lo - 1e-6)) and bool(jnp.all(y <= hi + 1e-6))
    assert bool(jnp.all(jnp.isfinite(y)))


def test_zero_prob_identity():
    x = jax.random.uniform(KEY, (8, 5))
    o1, o2 = operators.sbx_crossover(KEY, x, x[::-1], eta=15.0, prob=0.0,
                                     lower=0.0, upper=1.0)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(x))
    y = operators.polynomial_mutation(KEY, x, eta=15.0, prob=0.0, indpb=1.0,
                                      lower=0.0, upper=1.0)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_variation_shape_and_bounds():
    parents = jax.random.uniform(KEY, (32, 7), minval=-1, maxval=1)
    off = operators.variation(KEY, parents, eta_cx=15.0, prob_cx=0.9,
                              eta_mut=20.0, prob_mut=0.7, indpb=0.3,
                              lower=-1.0, upper=1.0, use_kernel=False)
    assert off.shape == parents.shape
    assert bool(jnp.all((off >= -1) & (off <= 1)))


@settings(max_examples=25, deadline=None)
@given(
    p=st.integers(1, 17),
    g=st.integers(1, 8),
    seed=st.integers(0, 2**30),
)
def test_variation_any_pop_size(p, g, seed):
    """Regression: odd P crashed SBX pairing (parents[0::2] vs
    parents[1::2] shape mismatch). The unpaired last parent now goes
    through mutation-only."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    parents = jax.random.uniform(k1, (p, g), minval=-1, maxval=1)
    off = operators.variation(k2, parents, eta_cx=15.0, prob_cx=0.9,
                              eta_mut=20.0, prob_mut=0.7, indpb=0.3,
                              lower=-1.0, upper=1.0, use_kernel=False)
    assert off.shape == (p, g)
    assert bool(jnp.all(jnp.isfinite(off)))
    assert bool(jnp.all((off >= -1) & (off <= 1)))


def test_variation_odd_pop_under_jit_and_kernel_flag():
    """Odd P must work jitted and with use_kernel=True (the fused kernel
    pairs parents, so odd P falls back to the unfused path)."""
    parents = jax.random.uniform(KEY, (15, 4), minval=-1, maxval=1)
    for use_kernel in (False, True):
        run = jax.jit(lambda pp, uk=use_kernel: operators.variation(
            KEY, pp, eta_cx=15.0, prob_cx=0.9, eta_mut=20.0, prob_mut=0.7,
            indpb=0.3, lower=-1.0, upper=1.0, use_kernel=uk))
        off = run(parents)
        assert off.shape == (15, 4)
        assert bool(jnp.all(jnp.isfinite(off)))


def test_variation_kernel_failure_raises(monkeypatch):
    """A failing fused kernel surfaces; variation never swaps in the
    unfused operators behind the caller's back."""
    from repro.kernels.genetic import ops as gk

    def broken(*args, **kwargs):
        raise RuntimeError("fused kernel failed")

    monkeypatch.setattr(gk, "fused_variation", broken)
    parents = jax.random.uniform(KEY, (16, 4), minval=-1, maxval=1)
    with pytest.raises(RuntimeError, match="fused kernel failed"):
        operators.variation(KEY, parents, eta_cx=15.0, prob_cx=0.9,
                            eta_mut=20.0, prob_mut=0.7, indpb=0.3,
                            lower=-1.0, upper=1.0, use_kernel=True)


def test_traced_hyperparams():
    """Operators must accept traced eta/prob (meta-GA requirement)."""
    parents = jax.random.uniform(KEY, (8, 3))

    @jax.jit
    def run(eta, prob):
        return operators.variation(KEY, parents, eta_cx=eta, prob_cx=prob,
                                   eta_mut=eta, prob_mut=prob, indpb=0.5,
                                   lower=0.0, upper=1.0, use_kernel=False)

    out = run(jnp.float32(20.0), jnp.float32(0.5))
    assert bool(jnp.all(jnp.isfinite(out)))
