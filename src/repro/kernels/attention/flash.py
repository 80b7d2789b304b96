"""Pallas TPU flash attention (causal GQA + sliding window + softcap).

Grid: (B * KV_heads, q_blocks, kv_blocks) — kv innermost. Running
(max, denom, accumulator) live in VMEM scratch across the kv sweep; the
output block is written once on the last kv iteration. Q arrives
pre-grouped as (B, KV, G, Sq, hd) so one grid cell computes all G query
heads sharing a KV head: the score matmul is (G*BQ, hd) x (hd, BK) — MXU-
aligned when G*BQ is a multiple of 128 (BQ=128 default).

VMEM budget per cell (defaults BQ=BK=128, hd<=256, G<=8):
  q (G*BQ, hd) 1 MiB + k/v 2*(BK, hd) 256 KiB + scratch acc 1 MiB + m/l
  0.5 MiB + scores (G*BQ, BK) 0.5 MiB  ->  ~3.5 MiB « 16 MiB VMEM.

Numerics identical to models/attention.flash_attention_xla (the oracle):
f32 softmax, clamped-max so fully-masked rows yield zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIN = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, softcap, q_offset, t_actual, nk,
            block_q, block_k, g):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (G, BQ, hd)
    gq, bq, hd = q.shape
    q2 = q.reshape(gq * bq, hd) * scale
    k = k_ref[0].astype(jnp.float32)                   # (BK, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())))   # (G*BQ, BK)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    rows = jax.lax.broadcasted_iota(jnp.int32, (gq * bq, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (gq * bq, block_k), 1)
    qpos = q_offset + iq * block_q + rows % bq
    kpos = ik * block_k + cols
    mask = kpos < t_actual
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, -jnp.inf)

    m_prev = m_scr[...][:, :1]                         # (G*BQ, 1)
    l_prev = l_scr[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.maximum(m_new, _MIN)
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(jnp.maximum(m_prev, _MIN) - m_safe)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * corr + jax.lax.dot(p, v)

    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc_scr[...] / l).reshape(gq, bq, hd)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "attn_softcap", "q_offset",
    "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, scale, causal=True, window=0,
                        attn_softcap=0.0, q_offset=0, block_q=128,
                        block_k=128, interpret=True):
    """q: (B, Sq, H, hd); k/v: (B, T, KV, hd) -> (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    bq = min(block_q, sq)
    bk = min(block_k, t)
    nq = -(-sq // bq)
    nk = -(-t // bk)
    sqp, tp = nq * bq, nk * bk

    # (B, KV, G, Sq, hd) / (B, KV, T, hd), zero-padded to block multiples
    qg = q.reshape(b, sq, kv, g, hd).transpose(0, 2, 3, 1, 4)
    qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, sqp - sq), (0, 0)))
    kg = k.transpose(0, 2, 1, 3)
    kg = jnp.pad(kg, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    vg = v.transpose(0, 2, 1, 3)
    vg = jnp.pad(vg, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    qg = qg.reshape(b * kv, g, sqp, hd)
    kg = kg.reshape(b * kv, tp, hd)
    vg = vg.reshape(b * kv, tp, hd)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=attn_softcap, q_offset=q_offset, t_actual=t, nk=nk,
        block_q=bq, block_k=bk, g=g)

    out = pl.pallas_call(
        kernel,
        grid=(b * kv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, g, bq, hd), lambda ib, iq, ik: (ib, 0, iq, 0)),
            pl.BlockSpec((1, bk, hd), lambda ib, iq, ik: (ib, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda ib, iq, ik: (ib, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, bq, hd),
                               lambda ib, iq, ik: (ib, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kv, g, sqp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq, 128), jnp.float32),
            pltpu.VMEM((g * bq, 128), jnp.float32),
            pltpu.VMEM((g * bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, kg, vg)

    out = out.reshape(b, kv, g, sqp, hd)[:, :, :, :sq]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
