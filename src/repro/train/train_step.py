"""Training step: loss + grads (with microbatch accumulation), AdamW update.

The returned ``train_step(state, batch) -> (state, metrics)`` is pure and
jit-able; distribution comes entirely from the shardings of `state`/`batch`
plus the model's internal constraints (GSPMD). Microbatch accumulation runs
as a ``lax.scan`` so the activation peak is one microbatch.

Optional ``compress_pod_reduce``: the cross-pod gradient reduction is
executed as an int8 all-gather + local sum inside a ``shard_map``
(see train/compress.py). In that mode
the per-pod loss is averaged over the pod-local batch shard, and pods are
synchronized exclusively through the compressed reduce.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.model import Model
from repro.train.compress import compressed_psum_tree
from repro.train.loss import lm_loss
from repro.train.optimizer import OptimizerConfig, adamw_update, init_opt_state

_METRIC_KEYS = ("loss", "ppl_log", "tokens", "accuracy", "aux")


def frontend_len(cfg, batch=None) -> int:
    """Frontend prefix length inside the decoder stream (VLM patches)."""
    if cfg.frontend != "vision_patches":
        return 0
    if batch is not None and "frontend_embeds" in batch:
        return batch["frontend_embeds"].shape[1]
    return 576


def make_loss_fn(model: Model):
    cfg = model.cfg

    def loss_fn(params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        fl = frontend_len(cfg, batch)
        tokens = batch["tokens"]
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        fwd = {"tokens": inputs}
        if "frontend_embeds" in batch:
            fwd["frontend_embeds"] = batch["frontend_embeds"]
        logits, aux = model.forward(params, fwd)
        if fl:
            logits = logits[:, fl:]
        loss, metrics = lm_loss(cfg, logits, labels, batch.get("loss_mask"))
        total = loss + cfg.router_aux_weight * aux
        metrics = {**metrics, "aux": aux}
        return total, {k: metrics[k] for k in _METRIC_KEYS}

    return loss_fn


def make_compute_grads(model: Model, microbatches: int = 1,
                       unroll: bool = False):
    loss_fn = make_loss_fn(model)

    def compute_grads(params, batch):
        if microbatches == 1:
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, metrics

        def split_mb(x):
            return x.reshape((microbatches, x.shape[0] // microbatches)
                             + x.shape[1:])
        mbs = jax.tree_util.tree_map(split_mb, batch)

        def body(acc, mb):
            gacc, macc = acc
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            macc = {k: macc[k] + metrics[k] for k in _METRIC_KEYS}
            return (gacc, macc), None

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        m0 = {k: jnp.zeros((), jnp.float32) for k in _METRIC_KEYS}
        if unroll:                       # dry-run depth probe: exact counts
            acc = (g0, m0)
            for i in range(microbatches):
                acc, _ = body(acc, jax.tree_util.tree_map(
                    lambda x: x[i], mbs))
            grads, msum = acc
        else:
            (grads, msum), _ = jax.lax.scan(body, (g0, m0), mbs)
        grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        metrics = {k: msum[k] / microbatches for k in _METRIC_KEYS}
        return grads, metrics

    return compute_grads


def make_train_step(model: Model, opt_cfg: OptimizerConfig, *,
                    microbatches: int = 1,
                    compress_pod_reduce: bool = False,
                    shard_grads: bool = False,
                    unroll: bool = False):
    ctx = model.ctx
    compute_grads = make_compute_grads(model, microbatches, unroll)

    def train_step(state, batch):
        params = state["params"]
        if compress_pod_reduce and ctx.mesh is not None and "pod" in ctx.mesh.axis_names:
            grads, metrics = _pod_compressed_grads(
                model, microbatches, unroll, params, batch, state["rng"])
        else:
            grads, metrics = compute_grads(params, batch)
        if shard_grads and ctx.mesh is not None:
            # pin gradients to the parameter sharding BEFORE the optimizer:
            # GSPMD then lowers the batch-reduction as reduce-scatter into
            # the FSDP layout instead of all-reduce + later reshard
            from repro.models.sharding import param_shardings
            sh = param_shardings(grads, ctx)
            grads = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, grads, sh)
        new_params, new_opt, stats = adamw_update(
            opt_cfg, params, grads, state["opt"])
        metrics = {**metrics, **stats}
        new_rng = jax.random.fold_in(state["rng"], state["opt"]["step"][()]
                                     if hasattr(state["opt"]["step"], "shape")
                                     else 0)
        return {"params": new_params, "opt": new_opt, "rng": new_rng}, metrics

    return train_step


def _pod_compressed_grads(model, microbatches, unroll, params, batch, rng):
    """Per-pod grads + int8 compressed cross-pod reduce.

    Requires pure DP across pods: params/opt replicated over the pod axis
    (FSDP within a pod only) — the natural layout when inter-pod links are
    slow enough to warrant compression.

    Lowered as a ``jax.shard_map`` that is manual over every mesh axis:
    each device computes grads on its (pod, dp) batch shard, the dp mean
    is an exact f32 ``pmean``, and the cross-pod reduce is an explicit
    int8 ``all_gather`` (compress.compressed_psum_tree). Params enter
    replicated; the model axis computes redundantly. (A shard_map manual
    over 'pod' alone, with 'data'/'model' left to GSPMD inside, aborts in
    XLA's SPMD partitioner.)
    """
    import dataclasses

    ctx = model.ctx
    inner_dp = tuple(a for a in ctx.dp if a != "pod")
    inner_model = model.with_ctx(dataclasses.replace(ctx, mesh=None))
    compute_grads = make_compute_grads(inner_model, microbatches, unroll)

    def per_device(params, batch, rng):
        grads, metrics = compute_grads(params, batch)
        if inner_dp:
            grads = jax.lax.pmean(grads, inner_dp)
        grads = compressed_psum_tree(grads, "pod", rng)
        metrics = jax.lax.pmean(metrics, ("pod",) + inner_dp)
        return grads, metrics

    pspecs = jax.tree_util.tree_map(lambda _: P(), params)
    bspecs = jax.tree_util.tree_map(lambda _: P(("pod",) + inner_dp), batch)
    f = jax.shard_map(per_device, mesh=ctx.mesh,
                      in_specs=(pspecs, bspecs, P()),
                      out_specs=(pspecs, P()), check_vma=False)
    return f(params, batch, rng)


def init_train_state(model: Model, rng: jax.Array,
                     moment_dtype: str = "float32") -> dict:
    params = model.init_params(rng)
    return {"params": params, "opt": init_opt_state(params, moment_dtype),
            "rng": jax.random.fold_in(rng, 1)}


def train_state_shapes(model: Model, moment_dtype: str = "float32") -> dict:
    return jax.eval_shape(
        lambda r: init_train_state(model, r, moment_dtype),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
