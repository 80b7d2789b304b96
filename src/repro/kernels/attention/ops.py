"""Public wrapper: flash attention with custom VJP.

Forward: Pallas kernel (compiled on TPU; interpret elsewhere).
Backward: recompute via the XLA-flash formulation's VJP (flash-style
recompute — no O(S^2) residuals stored).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.attention.flash import flash_attention_fwd
from repro.models.attention import flash_attention_xla


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, window, attn_softcap, q_offset):
    return flash_attention_fwd(q, k, v, scale=scale, causal=causal,
                               window=window, attn_softcap=attn_softcap,
                               q_offset=q_offset, interpret=not _is_tpu())


def _fwd(q, k, v, scale, causal, window, attn_softcap, q_offset):
    out = _flash(q, k, v, scale, causal, window, attn_softcap, q_offset)
    return out, (q, k, v)


def _bwd(scale, causal, window, attn_softcap, q_offset, res, dout):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention_xla(
            q, k, v, scale=scale, causal=causal, window=window,
            attn_softcap=attn_softcap, q_offset=q_offset), q, k, v)
    return vjp(dout)


_flash.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, *, scale, causal=True, window=0,
                    attn_softcap=0.0, q_offset=0):
    return _flash(q, k, v, scale, causal, window, attn_softcap, q_offset)
