"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk dual form
[arXiv:2405.21060].

Per (batch-chunk, head) grid cell, computes in VMEM:
    da     = dt * a_h                       (Q,)
    cum    = tril_ones @ da                 (cumsum as an MXU matmul —
                                             avoids a sequential scan op)
    L      = exp(cum_i - cum_j) . tril      (Q, Q)
    y_diag = ((C B^T) . L . dt_j) @ X       (Q, P)   <- the FLOP hot spot
    state  = X^T @ (B . (dt . exp(cum_Q - cum)))     (P, N)
    in_dec = exp(cum)                       (Q,)

The O(L) inter-chunk recurrence and the rank-N off-diagonal correction
(y_off) stay in XLA (ops.py): they are 1/Q of the FLOPs and XLA already
fuses them; the kernel owns the Q^2-dense part. Block sizes: Q=chunk (256
default), P/N = 64..128 — everything 128-lane aligned.

VMEM per cell: x (Q,P) 128 KiB + b/c (Q,N) 256 KiB + L/cb (Q,Q) 512 KiB
+ outs ~160 KiB -> ~1 MiB « 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_vec, x_ref, dt_ref, b_ref, c_ref, y_ref, st_ref, dec_ref, *,
            chunk: int):
    h = pl.program_id(1)
    x = x_ref[0, 0].astype(jnp.float32)                  # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)             # (Q,)
    b = b_ref[0].astype(jnp.float32)                     # (Q, N)
    c = c_ref[0].astype(jnp.float32)                     # (Q, N)
    a_h = a_vec[h]

    q = chunk
    da = dt * a_h                                        # (Q,) <= 0
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril_strict = (rows > cols).astype(jnp.float32)      # j < i
    tril = rows >= cols
    # cum[i] = sum_{k<=i} da_k  via ones-tril matmul (incl diag)
    incl = (rows >= cols).astype(jnp.float32)
    cum = jax.lax.dot(incl, da[:, None])[:, 0]           # (Q,)

    lmat = jnp.where(tril, jnp.exp(cum[:, None] - cum[None, :]), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))   # (Q, Q)
    w = cb * lmat * dt[None, :]
    y = jax.lax.dot(w, x)                                # (Q, P)

    dec_end = jnp.exp(cum[-1] - cum) * dt                # (Q,)
    state = jax.lax.dot_general(x, b * dec_end[:, None],
                                (((0,), (0,)), ((), ())))       # (P, N)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = state.astype(st_ref.dtype)
    dec_ref[0, 0, 0] = jnp.exp(cum).astype(dec_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_intra_chunk(x, dt, a, b_mat, c_mat, *, chunk: int,
                    interpret: bool = True):
    """x: (B, L, H, P); dt: (B, L, H) (softplus'd); a: (H,);
    b/c: (B, L, N). L % chunk == 0.
    Returns (y_diag (B,L,H,P), states (B,NC,H,P,N), in_decay (B,NC,H,Q))."""
    bsz, l, h, p = x.shape
    n = b_mat.shape[-1]
    nc = l // chunk

    xr = x.reshape(bsz, nc, chunk, h, p).transpose(0, 1, 3, 2, 4) \
          .reshape(bsz * nc, h, chunk, p)
    dtr = dt.reshape(bsz, nc, chunk, h).transpose(0, 1, 3, 2) \
            .reshape(bsz * nc, h, 1, chunk)
    br = b_mat.reshape(bsz * nc, chunk, n)
    cr = c_mat.reshape(bsz * nc, chunk, n)

    kern = functools.partial(_kernel, chunk=chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz * nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j, s: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda i, j, s: (i, j, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, s: (i, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, s: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j, s: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j, s: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda i, j, s: (i, j, 0, 0)),
        ],
    )
    y, states, in_dec = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((bsz * nc, h, chunk, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz * nc, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((bsz * nc, h, 1, chunk), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(a.astype(jnp.float32), xr, dtr, br, cr)

    y = y.reshape(bsz, nc, h, chunk, p).transpose(0, 1, 3, 2, 4) \
         .reshape(bsz, l, h, p)
    states = states.reshape(bsz, nc, h, p, n)
    in_dec = in_dec.reshape(bsz, nc, h, chunk)
    return y, states, in_dec
