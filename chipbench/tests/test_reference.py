"""The plain references against the program at small sizes on the CPU."""
import jax.numpy as jnp
import numpy as np

from chipbench.reference import ga
from chipbench.reference.grid import make_grid
from chipbench.reference.powerflow import Powerflow
from chipbench.tests.test_multiobjective import published_order
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


def _pareto_ranks(f):
    """Front per point by the definition: front 0 holds the points no other
    point dominates; front k those that only points of fronts below k do."""
    def dominates(a, b):
        return all(x <= y for x, y in zip(a, b)) and any(
            x < y for x, y in zip(a, b))
    rank, left, k = [None] * len(f), set(range(len(f))), 0
    while left:
        front = {j for j in left
                 if not any(dominates(f[i], f[j]) for i in left)}
        for j in front:
            rank[j] = k
        left, k = left - front, k + 1
    return rank


def _crowding(f, rank):
    """Deb et al. 2002, Fig. 4, per front; ties in an objective by
    position."""
    n, m = f.shape
    dist = [0.0] * n
    for k in set(rank):
        front = [i for i in range(n) if rank[i] == k]
        for o in range(m):
            by = sorted(front, key=lambda i: (f[i, o], i))
            span = float(f[by[-1], o] - f[by[0], o])
            dist[by[0]] = dist[by[-1]] = np.inf
            for a, i, b in zip(by, by[1:-1], by[2:]):
                if dist[i] != np.inf and span > 0:
                    dist[i] += float(np.float32(f[b, o] - f[a, o])
                                     / np.float32(span))
    return dist


def test_fronts_and_crowding_by_the_definition():
    rng = np.random.default_rng(5)
    for trial in range(120):
        n, m = (1, 5, 13)[trial % 3], (2, 3)[trial % 4 // 2]
        # few levels: duplicate points, and ties in one objective
        f = rng.integers(0, 4 if trial % 2 else 9, (n, m)).astype(np.float32)
        if trial % 3 == 0:
            f[:, 0] = f[0, 0]
        front = np.asarray(ga.fronts(jnp.asarray(f)))
        assert front.tolist() == _pareto_ranks(f.tolist()), f
        dist, weight = ga.crowding(jnp.asarray(f), jnp.asarray(front))
        np.testing.assert_allclose(np.asarray(dist), _crowding(f, front),
                                   rtol=1e-6)
        assert np.all(np.isinf(dist) == (np.asarray(weight) > 0)), f


def test_boundary_points_are_infinitely_crowded():
    # one front: (0, 3), (1, 2), (2, 1), (3, 0); the ends in both
    # objectives are the first and the last point
    f = jnp.asarray([[1, 2], [0, 3], [3, 0], [2, 1]], jnp.float32)
    dist, weight = ga.crowding(f, ga.fronts(f))
    assert np.isinf(np.asarray(dist)).tolist() == [False, True, True, False]
    np.testing.assert_allclose(np.asarray(dist)[[0, 3]], [4 / 3, 4 / 3])
    np.testing.assert_allclose(np.asarray(weight)[[1, 2]], [2 / 3, 2 / 3])
    # boundary points first, tied in their ranges, then by position
    assert np.asarray(ga._order(f)).tolist() == [1, 2, 0, 3]
    # one front of three objectives, ranges 5, 5 and 9: point 2 bounds all
    # three (1/5 + 1/5 + 1/9), points 0 and 1 one of range 5, point 3 the
    # one of range 9; point 4 is dominated by point 3
    g = jnp.asarray([[0, 5, 5], [5, 0, 5], [5, 5, 0], [1, 1, 9],
                     [2, 2, 9]], jnp.float32)
    assert np.asarray(ga.fronts(g)).tolist() == [0, 0, 0, 0, 1]
    # the published ties by position alone, and the reference's order by
    # the ranges first, which the program's follows
    assert np.asarray(published_order(g)).tolist() == [0, 1, 2, 3, 4]
    assert np.asarray(ga._order(g)).tolist() == [2, 0, 1, 3, 4]
    _, _, key = nsga2.nsga2_keys(g)
    assert np.argsort(np.asarray(key)).tolist() == [2, 0, 1, 3, 4]
