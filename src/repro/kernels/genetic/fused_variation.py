"""Pallas TPU kernel: fused tournament-output variation
(SBX crossover -> polynomial mutation -> bounds clip) in one VMEM pass.

Why a kernel: per generation the unfused pipeline reads/writes the (P, G)
genome matrix four times (crossover read, crossover write, mutation
read/write, clip). At GA scale (P ~ 10^4-10^5 individuals on-device) the
operators are strictly HBM-bandwidth-bound VPU work; fusing them keeps each
genome tile resident in VMEM for the whole variation — one HBM round-trip.

Layout: parents are pre-split into pair halves x1/x2 (P/2, G); grid tiles
the pair axis (rows, 8-aligned) with the full padded gene axis per tile
(G is small: 4-128 for GA problems; padded to 128 lanes). eta/prob scalars
arrive via scalar prefetch (SMEM) so they may be traced (meta-GA).

Randomness is supplied as pre-drawn uniforms (same HBM traffic the unfused
pipeline pays; keeps the kernel deterministic and oracle-comparable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EPS = 1e-14


def _kernel(scalars, x1, x2, u_cx, m_pair, m_gene, u_mut1, u_mut2,
            m_ind1, m_ind2, m_genem1, m_genem2, lower, upper, o1, o2):
    eta_cx = scalars[0]
    prob_cx = scalars[1]
    eta_mut = scalars[2]
    prob_mut = scalars[3]
    indpb = scalars[4]

    a = x1[...]
    b = x2[...]
    lo = lower[...]
    hi = upper[...]
    u = u_cx[...]

    y1 = jnp.minimum(a, b)
    y2 = jnp.maximum(a, b)
    span = jnp.maximum(y2 - y1, EPS)

    def betaq(beta):
        alpha = 2.0 - jnp.power(beta, -(eta_cx + 1.0))
        return jnp.where(
            u <= 1.0 / alpha,
            jnp.power(u * alpha, 1.0 / (eta_cx + 1.0)),
            jnp.power(1.0 / jnp.maximum(2.0 - u * alpha, EPS),
                      1.0 / (eta_cx + 1.0)))

    c1 = jnp.clip(0.5 * ((y1 + y2) - betaq(1.0 + 2.0 * (y1 - lo) / span)
                         * (y2 - y1)), lo, hi)
    c2 = jnp.clip(0.5 * ((y1 + y2) + betaq(1.0 + 2.0 * (hi - y2) / span)
                         * (y2 - y1)), lo, hi)

    apply_cx = (m_pair[...] < prob_cx) & (m_gene[...] < 0.5)
    off1 = jnp.where(apply_cx, c1, a)
    off2 = jnp.where(apply_cx, c2, b)

    def mutate(off, u2, m_ind, m_genem):
        span2 = hi - lo
        d1 = (off - lo) / span2
        d2 = (hi - off) / span2
        mp = 1.0 / (eta_mut + 1.0)
        lo_b = jnp.power(jnp.maximum(
            2.0 * u2 + (1.0 - 2.0 * u2) * jnp.power(1.0 - d1, eta_mut + 1.0),
            EPS), mp) - 1.0
        hi_b = 1.0 - jnp.power(jnp.maximum(
            2.0 * (1.0 - u2) + 2.0 * (u2 - 0.5)
            * jnp.power(1.0 - d2, eta_mut + 1.0), EPS), mp)
        deltaq = jnp.where(u2 < 0.5, lo_b, hi_b)
        mut = jnp.clip(off + deltaq * span2, lo, hi)
        return jnp.where((m_ind < prob_mut) & (m_genem < indpb), mut, off)

    o1[...] = mutate(off1, u_mut1[...], m_ind1[...], m_genem1[...])
    o2[...] = mutate(off2, u_mut2[...], m_ind2[...], m_genem2[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_variation_pallas(x1, x2, rnd, scalars, lower, upper, *,
                           interpret: bool, block_rows: int = 256):
    """x1/x2: (P2, G); rnd: dict from ref.draw_uniforms (split per child);
    scalars: (5,) [eta_cx, prob_cx, eta_mut, prob_mut, indpb].
    ``interpret``: True runs the kernel body in Python semantics (CPU);
    False compiles it for the TPU. Returns (o1, o2) each (P2, G)."""
    p2, g = x1.shape
    gp = max(128, -(-g // 128) * 128)                # lane-pad gene axis
    bp = min(block_rows, p2)
    grid = (-(-p2 // bp),)

    def pad(x):
        return jnp.pad(x, ((0, grid[0] * bp - x.shape[0]),
                           (0, gp - x.shape[1])))

    x1p, x2p = pad(x1), pad(x2)
    u_cx = pad(rnd["u_cx"])
    m_pair = jnp.pad(rnd["m_pair"], ((0, grid[0] * bp - p2), (0, 0)))
    m_pair = jnp.broadcast_to(m_pair, (grid[0] * bp, gp)) + 0.0
    m_gene = pad(rnd["m_gene"])
    u_mut = rnd["u_mut"]
    m_ind = jnp.broadcast_to(rnd["m_ind"], rnd["u_mut"].shape) + 0.0
    m_genem = rnd["m_genem"]
    u_mut1, u_mut2 = pad(u_mut[0::2]), pad(u_mut[1::2])
    m_ind1, m_ind2 = pad(m_ind[0::2]), pad(m_ind[1::2])
    m_genem1, m_genem2 = pad(m_genem[0::2]), pad(m_genem[1::2])
    # bounds broadcast to a full tile row
    lo = jnp.broadcast_to(jnp.pad(lower, (0, gp - g)), (bp, gp)) + 0.0
    hi = jnp.broadcast_to(jnp.pad(upper, (0, gp - g),
                                  constant_values=1.0), (bp, gp)) + 0.0

    from jax.experimental.pallas import tpu as pltpu

    # index maps receive (grid_idx, scalar_ref) under scalar prefetch
    row_spec = pl.BlockSpec((bp, gp), lambda i, s: (i, 0))
    fix_spec = pl.BlockSpec((bp, gp), lambda i, s: (0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[row_spec] * 11 + [fix_spec, fix_spec],
        out_specs=[row_spec, row_spec],
    )
    o1, o2 = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((grid[0] * bp, gp), x1.dtype),
                   jax.ShapeDtypeStruct((grid[0] * bp, gp), x1.dtype)),
        interpret=interpret,
    )(scalars, x1p, x2p, u_cx, m_pair, m_gene, u_mut1, u_mut2,
      m_ind1, m_ind2, m_genem1, m_genem2, lo, hi)
    return o1[:p2, :g], o2[:p2, :g]
