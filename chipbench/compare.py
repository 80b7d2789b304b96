"""The comparison that decides ``correct`` for a GA cell.

After the window the harness holds three populations of the program:
``start``, the window's first input; ``window``, the population the window
ended on; and ``replay``, the window's compiled epoch step run once more
from ``start``, which is the window's first epoch again (the step is
deterministic). The reference is given ``start`` and computes what that
epoch must give. The comparison is of one epoch because a near-tie of two
fitness values, which rounding may order either way, sends a lineage down
another path, and such forks pile up over epochs. Three numbers, each with
its limit in the configuration file:

``<fitness>_gap``  the largest gap between a fitness the program stored and
    the reference's fitness of that genome, over every individual of both
    populations and every objective, as a share of ``max(1, |reference|)``;
    a population that stores another number of objectives than the
    reference computes reads infinite.  Fitness layer.
``unmatched_share``  the share of ``replay``'s individuals that have no
    counterpart within ``match_tol`` (largest gene gap) among the
    reference's population of the same island.  Selection, variation and
    survivors: a reference that makes the same random decisions lands on
    the same genomes; rounding may flip a near-tie and send a few down
    another path.
``migrants_missing``  the number of islands in ``replay`` that hold
    neither the reference's immigrant (the first individual, in NSGA-II's
    order, of the island before it on the ring) nor one of the leaders of
    the island before it in ``replay`` itself. Migration copies an
    island's first to the next and keeps it at home, where only its own
    immigrant can rank above it; the second test keeps a sound run whose
    lineage a near-tie sent apart from the reference's at 0.  Migration.
    On one objective the leaders are the two best by value, ties
    included. On more, the second test is only for an island whose
    island before it parted from the reference's lineage (some
    individual of the reference's island, its immigrant aside, is not in
    the program's): one that kept to it had to send the reference's
    emigrant. The emigrant at home is a boundary point of its island's
    first front once the island's own immigrant is set aside (the
    immigrant may dominate it, or bound the front beyond it), so the
    leaders are the first front's boundary points of the island as it
    stands, and of the island without any one of its first-front members
    that the island before it also holds (its immigrant is one of them).

``calibrate.py`` reads these numbers for the program and for the control,
the program in the precision below the configuration's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import ga


@jax.jit
def _nearest_gap(prog, ref):
    """(I, P, G) x (I, Q, G) -> (I, P): per program individual, the
    smallest largest-gene-gap to any reference individual of its island."""
    def island(a, b):
        return jax.lax.map(lambda x: jnp.min(jnp.max(jnp.abs(b - x), -1)), a)
    return jax.lax.map(lambda ab: island(*ab), (prog, ref))


def objectives(fitness, n: int) -> np.ndarray:
    """(n, M) float64 from fitness of shape (..., M) or, for one
    objective, of shape (...,) with ``n`` elements."""
    f = np.asarray(fitness, np.float64)
    return f.reshape(n, -1)


def leaders(genomes, fitness, match_tol: float) -> np.ndarray:
    """(I, P) mask of each island's leaders for (I, P, G) genomes and
    (I, P, M) fitness of several objectives: the first front's boundary
    points of the island, and of the island without any one of its
    first-front members that the island before it on the ring also holds
    (within ``match_tol``), which are the candidates for its immigrant."""
    shared = np.asarray(_nearest_gap(
        jnp.asarray(genomes), jnp.asarray(np.roll(genomes, 1, axis=0))))
    out = np.zeros(shared.shape, bool)
    for k, f in enumerate(np.asarray(fitness, np.float32)):
        f = jnp.asarray(f)
        out[k] = np.asarray(ga.boundaries(f))
        first = np.asarray(ga.fronts(f)) == 0
        for y in np.flatnonzero(first & (shared[k] <= match_tol)):
            out[k] |= np.asarray(ga.boundaries(f.at[y].set(jnp.inf)))
    return out


def numbers(fitness_name: str, fit, ga_conf: dict, start: dict,
            window: dict, replay: dict, match_tol: float,
            gap_limit: float) -> tuple:
    """``fit(genomes (N, G)) -> (N, M)``, or ``(N,)`` for one objective, is
    the reference fitness. The populations hold host arrays ``genomes``
    (I, P, G), ``fitness`` (I, P, M) and ``rng`` (I, 2). Returns the three
    numbers, and how many individuals' fitness gaps exceed ``gap_limit``."""
    n_isl, p, g = window["genomes"].shape
    n = n_isl * p
    gaps = []
    for pop in (window, replay):
        ref_f = objectives(fit(pop["genomes"].reshape(-1, g)), n)
        got = objectives(pop["fitness"], n)
        if got.shape != ref_f.shape:
            gaps.append(np.full((n,), np.inf))
            continue
        gap = np.abs(got - ref_f) / np.maximum(1.0, np.abs(ref_f))
        gaps.append(np.max(np.where(np.isfinite(gap), gap, np.inf), axis=1))
    start_f = objectives(start["fitness"], n)
    if ref_f.shape[1] > 1 or start_f.shape != ref_f.shape:
        # crowding sets a copy of a genome apart from its original where
        # one objective differs in the last bit, as one stored by the
        # program and one computed by the reference do: the reference
        # computes the first population's objectives too
        start_f = objectives(fit(start["genomes"].reshape(-1, g)), n)
    pre_g, pre_f, pre_rng = ga.generations(
        start["genomes"], start_f.reshape(n_isl, p, -1), start["rng"], fit,
        ga_conf)
    ref_g, _, _, sent = ga.migrate(pre_g, pre_f, pre_rng)
    ref_g = np.asarray(ref_g, np.float32)
    near = np.asarray(_nearest_gap(jnp.asarray(replay["genomes"]),
                                   jnp.asarray(ref_g)))
    immigrant = np.roll(np.asarray(sent, np.float32), 1, axis=0)
    arrived = np.asarray(_nearest_gap(jnp.asarray(immigrant[:, None]),
                                      jnp.asarray(replay["genomes"])))[:, 0]
    f = objectives(replay["fitness"], n).reshape(n_isl, p, -1)
    if f.shape[2] == 1:                 # each island's two best
        top = f[..., 0] <= np.sort(f[..., 0], axis=1)[:, 1:2]
    else:
        top = leaders(replay["genomes"], f, match_tol)
    kept = np.asarray(_nearest_gap(
        jnp.asarray(np.roll(replay["genomes"], 1, axis=0)),
        jnp.asarray(replay["genomes"])))           # (I, P): found in island k
    home = np.any(np.roll(top, 1, axis=0) & (kept <= match_tol), axis=1)
    if f.shape[2] > 1:
        # an island whose lineage kept to the reference's, all of the
        # reference's island but its immigrant found in the program's,
        # sent the reference's emigrant: the home test is for the others
        pre_g = np.asarray(pre_g, np.float32)
        moved = np.any(ref_g != pre_g, axis=2)   # the reference's immigrant
        held = np.asarray(_nearest_gap(jnp.asarray(ref_g),
                                       jnp.asarray(replay["genomes"])))
        kept_lineage = np.all(moved | (held <= match_tol), axis=1)
        home &= ~np.roll(kept_lineage, 1)
    gaps = np.concatenate(gaps)
    return ({f"{fitness_name}_gap": float(np.max(gaps)),
             "unmatched_share": float(np.mean(~(near <= match_tol))),
             "migrants_missing": int(np.sum(~(arrived <= match_tol)
                                            & ~home))},
            int(np.sum(~(gaps <= gap_limit))))
