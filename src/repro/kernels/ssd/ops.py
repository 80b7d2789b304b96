"""Public wrapper: full SSD scan = Pallas intra-chunk kernel + XLA
inter-chunk recurrence + off-diagonal correction.

Matches models.ssm.ssd_chunked_ref exactly: (y, final_state)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd.chunk_kernel import ssd_intra_chunk


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ssd_chunked(x, dt, a, b_mat, c_mat, chunk: int,
                init_state: Optional[jax.Array] = None,
                interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    bsz, l0, h, p = x.shape
    n = b_mat.shape[-1]
    if l0 % chunk:
        pad = chunk - l0 % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    l = x.shape[1]
    nc = l // chunk
    interp = (not _is_tpu()) if interpret is None else interpret

    y_diag, states, in_dec = ssd_intra_chunk(
        x, dt, a, b_mat, c_mat, chunk=chunk, interpret=interp)

    # inter-chunk recurrence (sequential over nc, tiny)
    chunk_decay = in_dec[..., -1]                        # (B, NC, H)
    s0 = (jnp.zeros((bsz, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(carry, inp):
        dec, snew = inp
        prev = carry
        return prev * dec[..., None, None] + snew, prev

    final, prev_states = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(states, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)        # (B, NC, H, P, N)

    cc = c_mat.reshape(bsz, nc, chunk, n).astype(jnp.float32)
    y_off = jnp.einsum("bcin,bchpn,bchi->bcihp", cc, prev_states, in_dec)
    y = (y_diag.reshape(bsz, nc, chunk, h, p) + y_off).reshape(bsz, l, h, p)
    return y[:, :l0].astype(x.dtype), final
