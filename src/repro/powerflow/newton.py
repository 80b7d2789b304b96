"""Batched full-Newton AC powerflow in polar form.

Dense complex linear algebra throughout (MATPOWER's dSbus_dV formulation) —
the Jacobian assembly is all matmuls/diagonal scalings, ideal for the MXU,
and the solve is one dense LU per iteration which XLA lowers to the
platform solver. The loop (``lax.while_loop``) stops once the solve has
converged, after at most ``num_iters`` iterations; the step that starts
from a mismatch under ``tol`` still updates the voltages and counts in
``iters``. Under ``jax.vmap`` the loop runs until the slowest lane has
converged and holds the finished lanes fixed; under ``lax.map`` each
solve stops on its own (the broker balances predicted iteration counts
upstream).

Profiler scopes: ``chambga.newton.mismatch`` (each mismatch, the final
one included), ``chambga.newton.jacobian`` (``_ds_dv`` and the block
and mask assembly) and ``chambga.newton.lu`` (the dense solve) name the
device ops of one iteration.

Hardware adaptation (DESIGN.md §5): pandapower uses sparse LU on CPU; at
2715 buses a dense factorization is ~2715³*2/3 = 13 GFLOP — 66 µs at v5e
peak — so dense-on-MXU beats sparse-scalar by orders of magnitude while
batching over contingencies.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class PFResult(NamedTuple):
    vm: jax.Array          # (n,) voltage magnitudes
    va: jax.Array          # (n,) voltage angles (rad)
    mismatch: jax.Array    # () final max |mismatch| p.u.
    converged: jax.Array   # () bool
    iters: jax.Array       # () int32 iterations to convergence


def _sbus(ybus, v):
    return v * jnp.conj(ybus @ v)


def _ds_dv(ybus, v):
    """MATPOWER dSbus_dV (polar). Returns (dS_dVa, dS_dVm) complex (n,n)."""
    i = ybus @ v
    diag_v = jnp.diag(v)
    diag_i = jnp.diag(i)
    diag_vnorm = jnp.diag(v / jnp.abs(v))
    ds_dvm = diag_v @ jnp.conj(ybus @ diag_vnorm) + jnp.conj(diag_i) @ diag_vnorm
    ds_dva = 1j * diag_v @ jnp.conj(diag_i - ybus @ diag_v)
    return ds_dva, ds_dvm


def newton_powerflow(gridj: dict, *, p_extra: jax.Array | None = None,
                     num_iters: int = 12, tol: float = 5e-4,
                     line_mask: jax.Array | None = None) -> PFResult:
    """Solve one AC powerflow.

    gridj: Grid.to_jax() pytree. p_extra: optional (n,) additional active
    injections (HVDC terms). line_mask: optional (L,) {0,1} line in-service
    mask (contingencies) — the Ybus is rebuilt from branch data so outages
    are expressible inside jit.

    Every matmul and the LU solve run at HIGHEST precision: the TPU's
    default f32 matmul rounds its inputs to bf16, which leaves the
    mismatch far above ``tol`` (even exact f32 floors at ~2e-4 p.u. on the
    German grid).
    """
    with jax.default_matmul_precision("highest"):
        return _newton_powerflow(gridj, p_extra, num_iters, tol, line_mask)


def _newton_powerflow(gridj, p_extra, num_iters, tol, line_mask) -> PFResult:
    bt = gridj["bus_type"]
    n = bt.shape[0]
    is_slack = bt == 2
    is_pv = bt == 1
    is_pq = bt == 0
    npv_mask = ~is_slack                         # P equations at PV+PQ
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

    p_spec = gridj["p_inj"] + (0.0 if p_extra is None else p_extra)
    q_spec = gridj["q_inj"]

    vm0 = jnp.where(is_slack | is_pv, gridj["v_set"], 1.0)
    va0 = jnp.zeros((n,), jnp.float32)

    # row/col masks for the reduced Newton system, kept at full size with
    # identity padding (static shapes; masked rows solve to zero updates).
    p_row = npv_mask                                  # P eqs
    q_row = is_pq                                     # Q eqs

    def mismatch(vm, va):
        with jax.named_scope("chambga.newton.mismatch"):
            v = (vm * jnp.exp(1j * va)).astype(cdtype)
            s = _sbus(ybus, v)
            dp = jnp.real(s) - p_spec
            dq = jnp.imag(s) - q_spec
            return jnp.where(p_row, dp, 0.0), jnp.where(q_row, dq, 0.0), v

    def jacobian(v):
        ds_dva, ds_dvm = _ds_dv(ybus, v)
        j11 = jnp.real(ds_dva)                       # dP/dVa
        j12 = jnp.real(ds_dvm)                       # dP/dVm
        j21 = jnp.imag(ds_dva)                       # dQ/dVa
        j22 = jnp.imag(ds_dvm)                       # dQ/dVm

        pr = p_row.astype(j11.dtype)
        qr = q_row.astype(j11.dtype)
        j11 = j11 * pr[:, None] * pr[None, :]
        j12 = j12 * pr[:, None] * qr[None, :]
        j21 = j21 * qr[:, None] * pr[None, :]
        j22 = j22 * qr[:, None] * qr[None, :]
        # identity on masked diagonals keeps the system nonsingular
        j11 = j11 + jnp.diag(1.0 - pr)
        j22 = j22 + jnp.diag(1.0 - qr)
        return jnp.block([[j11, j12], [j21, j22]])

    def cond(carry):
        _, _, done, it = carry
        return ~done & (it < num_iters)

    def body(carry):
        vm, va, _, it = carry
        dp, dq, v = mismatch(vm, va)
        with jax.named_scope("chambga.newton.jacobian"):
            jac = jacobian(v)
        rhs = -jnp.concatenate([dp, dq])
        with jax.named_scope("chambga.newton.lu"):
            dx = jnp.linalg.solve(jac, rhs)
        err = jnp.maximum(jnp.max(jnp.abs(dp)), jnp.max(jnp.abs(dq)))
        return vm + dx[n:] * q_row, va + dx[:n] * p_row, err < tol, it + 1

    vm, va, _, iters = jax.lax.while_loop(
        cond, body,
        (vm0, va0, jnp.zeros((), bool), jnp.zeros((), jnp.int32)))
    dp, dq, _ = mismatch(vm, va)
    final_err = jnp.maximum(jnp.max(jnp.abs(dp)), jnp.max(jnp.abs(dq)))
    return PFResult(vm=vm, va=va, mismatch=final_err,
                    converged=final_err < tol, iters=iters)


def line_flows(gridj: dict, vm: jax.Array, va: jax.Array,
               line_mask: jax.Array | None = None) -> jax.Array:
    """Active-power flow magnitude per line (max of both ends), p.u."""
    cdtype = gridj["ybus"].dtype
    v = (vm * jnp.exp(1j * va)).astype(cdtype)
    f, t = gridj["f_bus"], gridj["t_bus"]
    ys = gridj["y_series"]
    if line_mask is not None:
        ys = ys * line_mask.astype(ys.dtype)
    bc = (1j * gridj["b_sh"] / 2.0).astype(cdtype)
    if line_mask is not None:
        bc = bc * line_mask
    vf, vt = v[f], v[t]
    i_ft = (vf - vt) * ys + vf * bc
    i_tf = (vt - vf) * ys + vt * bc
    p_ft = jnp.real(vf * jnp.conj(i_ft))
    p_tf = jnp.real(vt * jnp.conj(i_tf))
    return jnp.maximum(jnp.abs(p_ft), jnp.abs(p_tf))
