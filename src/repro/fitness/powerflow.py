"""HVDC dispatch fitness (paper §4.2, eqs. 2-3).

Objective: total transmitted power over all AC lines (grid-usage-fee
proxy), computed from a full AC Newton solve with the genome's HVDC
injections. With ``contingencies=True`` the paper's N-1 penalty multiplies
the objective (+10% per critical case, +1% per near-critical).

Scaling axes (paper Fig. 3):
  horizontal — the genome batch N shards over the mesh data axis (broker)
  vertical   — the contingency batch shards over the mesh model axis

``screen_top_k > 0`` enables the beyond-paper LODF screening: DC-rank all
candidate outages, full-AC only the top-K.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sharding import ShardingCtx
from repro.powerflow.contingency import (contingency_loadings,
                                         penalized_objective,
                                         select_contingency_lines)
from repro.powerflow.dc import build_dc_model, screen_contingencies
from repro.powerflow.grid import Grid
from repro.powerflow.hvdc import apply_hvdc, scale_genome_to_dispatch
from repro.powerflow.newton import line_flows, newton_powerflow


class HVDCDispatchFitness:
    """Callable (N, H) genomes in [-1, 1] -> (N, 1) objectives."""

    def __init__(self, grid: Grid, *, contingencies: int = 0,
                 newton_iters: int = 10, screen_top_k: int = 0,
                 ctx: Optional[ShardingCtx] = None, seed: int = 0):
        self.grid = grid
        self.gridj = grid.to_jax()
        self.ctx = ctx
        self.newton_iters = newton_iters
        self.num_contingencies = contingencies
        self.screen_top_k = screen_top_k
        if contingencies:
            self.outages = jnp.asarray(
                select_contingency_lines(grid, contingencies, seed))
        else:
            self.outages = None
        self.dc_model = build_dc_model(self.gridj) if screen_top_k else None

    @property
    def num_genes(self) -> int:
        return self.grid.n_hvdc

    def _one(self, genome: jax.Array):
        gridj = self.gridj
        dispatch = scale_genome_to_dispatch(gridj, genome)
        p_extra = apply_hvdc(gridj, dispatch)
        res = newton_powerflow(gridj, p_extra=p_extra,
                               num_iters=self.newton_iters)
        fl = line_flows(gridj, res.vm, res.va)
        base = jnp.sum(fl)                                    # eq. (2)
        base = jnp.where(res.converged, base, base * 100.0)

        if self.outages is not None:
            if self.dc_model is not None:
                p_inj = gridj["p_inj"] + p_extra
                cases = screen_contingencies(
                    self.dc_model, p_inj, gridj["rate"], self.screen_top_k)
            else:
                cases = self.outages
            loadings = contingency_loadings(
                gridj, cases, p_extra=p_extra,
                num_iters=self.newton_iters, ctx=self.ctx)
            base = penalized_objective(base, loadings)        # eq. (3)
        return base[None], res.converged, res.iters

    def _solve(self, genomes: jax.Array):
        # one genome at a time (``lax.map``): at German-grid size the TPU
        # compiler refuses a batched LU of the Jacobian (its panel
        # overflows the scoped VMEM), while one solve compiles, and
        # device memory stays that of one solve
        out, converged, iters = jax.lax.map(self._one, genomes)
        if self.ctx is not None and self.ctx.mesh is not None and self.ctx.dp:
            out = self.ctx.cs(out, self.ctx.dp_spec, None)
        return out, converged, iters

    def evaluate(self, genomes: jax.Array):
        """(N, H) genomes -> ((N, 1) objectives, (N,) base-case Newton
        converged)."""
        out, converged, _ = self._solve(genomes)
        return out, converged

    def evaluate_with_stats(self, genomes: jax.Array):
        """(N, H) genomes -> ((N, 1) objectives, batch sums of the
        base-case Newton solves: ``newton_iterations`` that updated the
        voltages (``PFResult.iters``), ``newton_solves`` and
        ``newton_unconverged``). The broker forwards the sums to the
        engine, which publishes them as ``chambga_<name>_total``."""
        out, converged, iters = self._solve(genomes)
        return out, {"newton_iterations": jnp.sum(iters),
                     "newton_solves": jnp.full((), genomes.shape[0],
                                               jnp.int32),
                     "newton_unconverged": jnp.sum(~converged,
                                                   dtype=jnp.int32)}

    def __call__(self, genomes: jax.Array) -> jax.Array:
        return self.evaluate(genomes)[0]

    def cost_model(self):
        """Predicted per-genome evaluation cost for the broker: Newton
        iteration count grows with dispatch magnitude (stress)."""
        pmax = self.gridj["hvdc_pmax"]

        def cost(genomes: jax.Array) -> jax.Array:
            stress = jnp.sum(jnp.abs(genomes) * pmax[None], axis=-1)
            return 4.0 + stress / jnp.maximum(jnp.sum(pmax), 1e-9) * 6.0

        return cost
