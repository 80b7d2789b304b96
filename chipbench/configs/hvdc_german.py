"""HVDC dispatch on the German-grid counts: the paper's section 4.2.

``build`` makes the grid from the config file's ``grid_seed`` (the
benchmark's own generator), hands it to the program's HVDC fitness, and
gives the plain reference, a textbook Newton powerflow in float32 at
``highest`` matmul precision. The control is the program with its Newton
solve at ``high``, the precision below the configuration's.
"""
from __future__ import annotations

import contextlib
import inspect

import jax

from chipbench.deployment import (Deployment, ga_seed, ga_settings,
                                  program_config)
from chipbench.reference.grid import make_grid
from chipbench.reference.powerflow import Powerflow


@contextlib.contextmanager
def newton_at(precision: str = "high"):
    """While the context holds, the program's HVDC fitness runs its Newton
    solve at ``precision`` (``high``: three bfloat16 passes) instead of
    ``highest``. Build and run the engine inside it."""
    from repro.fitness import powerflow as fitness
    from repro.powerflow import newton

    def at_high(gridj, *, p_extra=None, num_iters=12, tol=5e-4,
                line_mask=None):
        with jax.default_matmul_precision(precision):
            return newton._newton_powerflow(gridj, p_extra, num_iters, tol,
                                            line_mask)

    real = fitness.newton_powerflow
    fitness.newton_powerflow = at_high
    try:
        yield
    finally:
        fitness.newton_powerflow = real


def build(conf: dict, mix: dict, seed: int, chips: int) -> Deployment:
    from repro.fitness.powerflow import HVDCDispatchFitness
    from repro.powerflow import hvdc
    from repro.powerflow.grid import Grid
    from repro.powerflow.newton import newton_powerflow

    if mix["backend"] != "inline":
        raise ValueError(f"mix {mix['name']}: only inline fitness is built")
    if chips != 1:
        raise ValueError("the HVDC dispatch is built for one chip")
    grid = make_grid(**conf["grid"])
    tol = inspect.signature(newton_powerflow).parameters["tol"].default
    if (hvdc.HVDC_LOSS, tol) != (conf["grid"]["hvdc_loss"],
                                 conf["newton_tol"]):
        raise ValueError("the program's HVDC loss or Newton tolerance "
                         "differs from the config file's")
    fit = HVDCDispatchFitness(Grid(**grid), newton_iters=conf["newton_iters"])
    ref = Powerflow(grid, loss=conf["grid"]["hvdc_loss"],
                    tol=conf["newton_tol"], max_iter=conf["newton_iters"])
    cfg = program_config(conf, chips, ga_seed(seed))
    return Deployment(
        cfg=cfg, fitness=fit, cost_fn=fit.cost_model(), ctx=None,
        ga_seed=cfg.seed, fitness_name="objective",
        reference=ref.objective, control=newton_at,
        ga=ga_settings(conf), limits=conf["limits"],
        match_tol=conf["match_tol"], trace_epochs=conf["trace_epochs"],
        shapes=dict(islands_per_chip=conf["islands_per_chip"],
                    pop_per_island=cfg.pop_per_island,
                    generations_per_epoch=cfg.generations_per_epoch,
                    solves_per_eval=1),
        unconverged=lambda: ref.unconverged)
