"""Plain reference of the HVDC dispatch objective: one AC powerflow per genome.

Textbook polar Newton-Raphson (MATPOWER's ``dSbus_dV`` formulation), on the
reduced system: active-power equations at every bus but the slack, reactive
ones at load (PQ) buses; unknowns are the angles there and the magnitudes
at PQ buses. The Jacobian is assembled with row and column scalings and
factored once per genome and iteration; each genome's bus currents are a
matrix-vector product. A genome takes Newton steps, at most ``max_iter``,
until a step starts from a mismatch under ``tol``: that step is its last.
It has converged if the mismatch where it stopped is under ``tol``.

HVDC line ``h`` withdraws ``x_h = genome_h * pmax_h`` at its from-bus and
injects ``(1 - loss) x_h`` at its to-bus. The objective is the sum over AC
lines of the larger active-power flow magnitude of the two ends; a solve
that does not converge multiplies it by 100.

Everything is float32 at ``highest`` matmul precision, as the
configuration states. On a TPU that changes nothing here: a matrix-vector
product and the LU run in full float32 whatever the flag, and the
Jacobian is assembled elementwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


class Powerflow:
    """The grid in device arrays plus the jitted per-genome objective."""

    def __init__(self, grid: dict, *, loss: float, tol: float,
                 max_iter: int):
        self.grid = grid
        self.loss, self.tol, self.max_iter = loss, tol, max_iter
        self.unconverged = 0                    # solves that missed tol
        self._arrays = None

    @property
    def arrays(self) -> dict:
        """The grid on the device, made at first use: after the program's
        window, so that it neither counts in set-up nor in its memory."""
        if self._arrays is None:
            self._arrays = _device_arrays(self.grid)
        return self._arrays

    def objective(self, genomes) -> np.ndarray:
        """(N, H) genomes -> (N,) objectives."""
        total, ok = _solve(self.arrays, jnp.asarray(genomes, jnp.float32),
                           loss=self.loss, tol=self.tol,
                           max_iter=self.max_iter)
        self.unconverged += int(np.sum(~np.asarray(ok)))
        return np.asarray(total)


def _device_arrays(grid: dict) -> dict:
    n = grid["n_bus"]
    ys = 1.0 / (grid["r"] + 1j * grid["x"])
    bc = 0.5j * grid["b_sh"]
    f, t = grid["f_bus"], grid["t_bus"]
    y = np.zeros((n, n), np.complex128)
    np.add.at(y, (f, f), ys + bc)
    np.add.at(y, (t, t), ys + bc)
    np.add.at(y, (f, t), -ys)
    np.add.at(y, (t, f), -ys)
    y[np.diag_indices(n)] += 1e-6j              # the grid model's small shunt
    bt = grid["bus_type"]
    return dict(
        ybus=jnp.asarray(y.astype(np.complex64)),
        ys=jnp.asarray(ys.astype(np.complex64)),
        bc=jnp.asarray(bc.astype(np.complex64)),
        f=jnp.asarray(f), t=jnp.asarray(t),
        p0=jnp.asarray((grid["p_gen"] - grid["p_load"]).astype(np.float32)),
        q0=jnp.asarray((-grid["q_load"]).astype(np.float32)),
        vm0=jnp.asarray(np.where(bt != 0, grid["v_set"], 1.0)
                        .astype(np.float32)),
        hf=jnp.asarray(grid["hvdc_f"]), ht=jnp.asarray(grid["hvdc_t"]),
        pmax=jnp.asarray(grid["hvdc_pmax"].astype(np.float32)),
        ns=jnp.asarray(np.flatnonzero(bt != 2)),    # P equations, angles
        pq=jnp.asarray(np.flatnonzero(bt == 0)))    # Q equations, magnitudes


@functools.partial(jax.jit, static_argnames=("loss", "tol", "max_iter"))
def _solve(a, genomes, *, loss, tol, max_iter):
    with jax.default_matmul_precision("highest"):
        n = a["p0"].shape[0]
        x = genomes * a["pmax"]                              # (N, H)
        p_spec = jax.vmap(lambda xi: a["p0"].at[a["hf"]].add(-xi)
                          .at[a["ht"]].add((1.0 - loss) * xi))(x)
        ns, pq, y = a["ns"], a["pq"], a["ybus"]

        def mismatch(vm, va):
            v = (vm * jnp.exp(1j * va)).astype(y.dtype)      # (N, n)
            cur = jax.lax.map(lambda vi: y @ vi, v)          # bus currents
            s = v * jnp.conj(cur)
            f = jnp.concatenate([jnp.real(s)[:, ns] - p_spec[:, ns],
                                 jnp.imag(s)[:, pq] - a["q0"][pq]], axis=1)
            return f, v, cur

        def step(vfc):
            v, f, cur = vfc
            vn = v / jnp.abs(v)
            ds_dvm = (v[:, None] * jnp.conj(y * vn[None, :])
                      + jnp.diag(jnp.conj(cur) * vn))
            ds_dva = 1j * v[:, None] * jnp.conj(jnp.diag(cur)
                                                - y * v[None, :])
            jac = jnp.block([
                [jnp.real(ds_dva)[ns][:, ns], jnp.real(ds_dvm)[ns][:, pq]],
                [jnp.imag(ds_dva)[pq][:, ns], jnp.imag(ds_dvm)[pq][:, pq]]])
            return jnp.linalg.solve(jac, -f)

        def cond(c):
            return (c[3] < max_iter) & ~jnp.all(c[2])

        def body(c):
            vm, va, done, it = c
            f, v, cur = mismatch(vm, va)
            dx = jax.lax.map(step, (v, f, cur))              # one LU each
            keep = jnp.where(done, 0.0, 1.0)[:, None]        # stopped before
            return (vm.at[:, pq].add(keep * dx[:, ns.shape[0]:]),
                    va.at[:, ns].add(keep * dx[:, :ns.shape[0]]),
                    done | (jnp.max(jnp.abs(f), axis=1) < tol), it + 1)

        m = genomes.shape[0]
        vm0 = jnp.broadcast_to(a["vm0"], (m, n))
        vm, va, _, _ = jax.lax.while_loop(
            cond, body, (vm0, jnp.zeros((m, n), jnp.float32),
                         jnp.zeros((m,), bool), jnp.int32(0)))
        f, v, _ = mismatch(vm, va)
        vf, vt = v[:, a["f"]], v[:, a["t"]]
        p_ft = jnp.real(vf * jnp.conj((vf - vt) * a["ys"] + vf * a["bc"]))
        p_tf = jnp.real(vt * jnp.conj((vt - vf) * a["ys"] + vt * a["bc"]))
        total = jnp.sum(jnp.maximum(jnp.abs(p_ft), jnp.abs(p_tf)), axis=1)
        ok = jnp.max(jnp.abs(f), axis=1) < tol
        return jnp.where(ok, total, 100.0 * total), ok
