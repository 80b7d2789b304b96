"""jit'd public wrapper for the fused variation kernel.

``fused_variation(rng, parents, ...)`` matches operators.variation's
contract exactly (same distributions; the uniforms are drawn here and fed
to both kernel and oracle in tests).

On non-TPU backends the kernel runs in interpret mode (Python semantics on
CPU) — correct but not fast; the TPU lowering uses the compiled kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.genetic.fused_variation import fused_variation_pallas
from repro.kernels.genetic.ref import draw_uniforms, fused_variation_ref


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_variation(rng: jax.Array, parents: jax.Array, *, eta_cx, prob_cx,
                    eta_mut, prob_mut, indpb, lower, upper,
                    interpret: bool | None = None) -> jax.Array:
    """parents: (P, G) with P even -> offspring (P, G)."""
    p, g = parents.shape
    rnd = draw_uniforms(rng, p, g)
    scalars = jnp.stack([jnp.asarray(eta_cx, jnp.float32),
                         jnp.asarray(prob_cx, jnp.float32),
                         jnp.asarray(eta_mut, jnp.float32),
                         jnp.asarray(prob_mut, jnp.float32),
                         jnp.asarray(indpb, jnp.float32)])
    lo = jnp.broadcast_to(jnp.asarray(lower, jnp.float32), (g,))
    hi = jnp.broadcast_to(jnp.asarray(upper, jnp.float32), (g,))
    interp = (not _is_tpu()) if interpret is None else interpret
    o1, o2 = fused_variation_pallas(parents[0::2], parents[1::2], rnd,
                                    scalars, lo, hi, interpret=interp)
    return jnp.stack([o1, o2], axis=1).reshape(p, g)


def fused_variation_oracle(rng: jax.Array, parents: jax.Array, *, eta_cx,
                           prob_cx, eta_mut, prob_mut, indpb, lower, upper
                           ) -> jax.Array:
    """Same contract via the pure-jnp reference (for allclose tests)."""
    p, g = parents.shape
    rnd = draw_uniforms(rng, p, g)
    lo = jnp.broadcast_to(jnp.asarray(lower, jnp.float32), (g,))
    hi = jnp.broadcast_to(jnp.asarray(upper, jnp.float32), (g,))
    return fused_variation_ref(parents[0::2], parents[1::2], rnd,
                               eta_cx=eta_cx, prob_cx=prob_cx,
                               eta_mut=eta_mut, prob_mut=prob_mut,
                               indpb=indpb, lower=lo, upper=hi)
