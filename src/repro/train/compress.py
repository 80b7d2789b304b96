"""Gradient compression for cross-pod reduction (beyond-paper optimization).

At 2+ pods the gradient all-reduce over the `pod` axis crosses the slower
inter-pod links (DCI), while the intra-pod reduce stays on ICI. Quantizing
the pod-crossing traffic to int8 with stochastic rounding cuts those bytes
4x at <1e-2 relative error per element (unbiased).

Implementation: per-leaf symmetric quantization. The reduce is expressed as
all_gather(int8) + local sum so the wire format really is 8-bit (a psum of
int8 would still move int32 partials). Used inside shard_map over the pod
axis in train_step when ``compress_pod_reduce=True``.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def quantize(x: jax.Array, rng: jax.Array, bits: int = 8):
    """Unbiased stochastic-rounding quantization. Returns (q, scale)."""
    qmax = 2 ** (bits - 1) - 1
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32)) / qmax + 1e-30
    y = x32 / scale
    lo = jnp.floor(y)
    p_up = y - lo
    up = jax.random.uniform(rng, x.shape) < p_up
    q = jnp.clip(lo + up.astype(jnp.float32), -qmax - 1, qmax)
    return q.astype(jnp.int8), scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_tree(grads: Any, axis_name: str, rng: jax.Array) -> Any:
    """int8 all_gather + local-sum mean over `axis_name` (inside shard_map)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    rngs = jax.random.split(rng, len(leaves))
    n = jax.lax.psum(1, axis_name)

    def reduce_one(x, r):
        q, scale = quantize(x, r)
        qg = jax.lax.all_gather(q, axis_name)            # int8 on the wire
        sg = jax.lax.all_gather(scale, axis_name)        # tiny
        summed = jnp.sum(qg.astype(jnp.float32)
                         * sg.reshape((-1,) + (1,) * x.ndim), axis=0)
        return (summed / n).astype(x.dtype)

    out = [reduce_one(x, r) for x, r in zip(leaves, rngs)]
    return jax.tree_util.tree_unflatten(treedef, out)
