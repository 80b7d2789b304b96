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
    populations, as a share of ``max(1, |reference|)``.  Fitness layer.
``unmatched_share``  the share of ``replay``'s individuals that have no
    counterpart within ``match_tol`` (largest gene gap) among the
    reference's population of the same island.  Selection, variation and
    survivors: a reference that makes the same random decisions lands on
    the same genomes; rounding may flip a near-tie and send a few down
    another path.
``migrants_missing``  the number of islands in ``replay`` that hold
    neither the reference's immigrant (the best individual of the island
    before it on the ring) nor one of the two best of the island before
    it in ``replay`` itself. Migration copies an island's best to the
    next and keeps it at home, where only its own immigrant can rank
    above it; the second test keeps a sound run whose lineage a near-tie
    sent apart from the reference's at 0.  Migration.

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


def numbers(fitness_name: str, fit, ga_conf: dict, start: dict,
            window: dict, replay: dict, match_tol: float,
            gap_limit: float) -> tuple:
    """``fit(genomes (N, G)) -> (N,)`` is the reference fitness. The
    populations hold host arrays ``genomes`` (I,P,G), ``fitness`` (I,P)
    and ``rng`` (I,2). Returns the three numbers, and how many
    individuals' fitness gaps exceed ``gap_limit``."""
    n_isl, p, g = window["genomes"].shape
    gaps = []
    for pop in (window, replay):
        ref_f = np.asarray(fit(pop["genomes"].reshape(-1, g)), np.float64)
        got = pop["fitness"].reshape(-1).astype(np.float64)
        gap = np.abs(got - ref_f) / np.maximum(1.0, np.abs(ref_f))
        gaps.append(np.where(np.isfinite(gap), gap, np.inf))
    ref_g, _, _, sent = ga.epoch(start["genomes"], start["fitness"],
                                 start["rng"], fit, ga_conf)
    ref_g = np.asarray(ref_g, np.float32)
    near = np.asarray(_nearest_gap(jnp.asarray(replay["genomes"]),
                                   jnp.asarray(ref_g)))
    immigrant = np.roll(np.asarray(sent, np.float32), 1, axis=0)
    arrived = np.asarray(_nearest_gap(jnp.asarray(immigrant[:, None]),
                                      jnp.asarray(replay["genomes"])))[:, 0]
    f = replay["fitness"]
    top = f <= np.sort(f, axis=1)[:, 1:2]          # each island's two best
    kept = np.asarray(_nearest_gap(
        jnp.asarray(np.roll(replay["genomes"], 1, axis=0)),
        jnp.asarray(replay["genomes"])))           # (I, P): found in island k
    home = np.any(np.roll(top, 1, axis=0) & (kept <= match_tol), axis=1)
    gaps = np.concatenate(gaps)
    return ({f"{fitness_name}_gap": float(np.max(gaps)),
             "unmatched_share": float(np.mean(~(near <= match_tol))),
             "migrants_missing": int(np.sum(~(arrived <= match_tol)
                                            & ~home))},
            int(np.sum(~(gaps <= gap_limit))))

