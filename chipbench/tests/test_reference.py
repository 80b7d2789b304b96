"""The plain references against the program at small sizes on the CPU."""
import jax.numpy as jnp
import numpy as np

from chipbench.reference import ga
from chipbench.reference.grid import make_grid
from chipbench.reference.powerflow import Powerflow
from repro.core import nsga2
from repro.powerflow.grid import make_synthetic_grid


def test_selection_order_is_nsga2_order_on_ties():
    rng = np.random.default_rng(0)
    for _ in range(200):
        f = rng.integers(0, 6, size=int(rng.integers(2, 40))).astype(np.float32)
        _, _, key = nsga2.nsga2_keys(jnp.asarray(f)[:, None])
        assert np.array_equal(np.argsort(np.asarray(key), kind="stable"),
                              np.asarray(ga._order(jnp.asarray(f))))


def test_grid_is_the_programs_grid():
    spec = dict(n_bus=120, n_line=230, n_gen=30, n_hvdc=4,
                hvdc_pmax_mw=[1300, 1300, 2000, 2000], grid_seed=3)
    ours = make_grid(**spec)
    theirs = make_synthetic_grid(n_bus=120, n_line=230, n_gen=30, n_hvdc=4,
                                 hvdc_pmax_mw=[1300, 1300, 2000, 2000],
                                 seed=3)
    for k, v in ours.items():
        assert np.array_equal(np.asarray(v), np.asarray(getattr(theirs, k))), k


def test_powerflow_matches_the_programs_objective():
    from repro.fitness.powerflow import HVDCDispatchFitness
    from repro.powerflow.grid import Grid

    grid = make_grid(n_bus=60, n_line=114, n_gen=15, n_hvdc=4,
                     hvdc_pmax_mw=[1300, 1300, 2000, 2000], grid_seed=0)
    genomes = np.random.default_rng(1).uniform(-1, 1, (6, 4)).astype(
        np.float32)
    ref = Powerflow(grid, loss=0.015, tol=5e-4, max_iter=10)
    ours = ref.objective(genomes)
    prog, converged = HVDCDispatchFitness(Grid(**grid)).evaluate(
        jnp.asarray(genomes))
    # one of these set-points has no solution on the small grid: both
    # sides fail to converge on it and multiply its objective by 100
    assert ref.unconverged == int(np.sum(~np.asarray(converged))) == 1
    np.testing.assert_allclose(ours, np.asarray(prog)[:, 0], rtol=1e-3)
