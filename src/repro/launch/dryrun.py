import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and extract roofline inputs.

MUST be invoked as its own process (``python -m repro.launch.dryrun``) so
the XLA_FLAGS line above precedes jax initialization. Smoke tests and
benchmarks run in normal processes and see 1 device.

Per cell this emits:
  * compiled.memory_analysis()  — proves the cell fits per-device HBM
  * compiled.cost_analysis()    — per-device HLO FLOPs / bytes accessed
  * collective byte counts parsed from the partitioned HLO
results are appended to a JSON file (``--out``).
"""
import argparse
import json
import re
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, list_archs, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import batch_specs, decode_specs, sds, train_specs
from repro.models.model import Model
from repro.models.sharding import make_serve_ctx, make_train_ctx
from repro.train.optimizer import OptimizerConfig, optimizer_for_arch
from repro.train.train_step import make_train_step

# Per-arch gradient-accumulation defaults for train_4k (fit-memory knob;
# tuned from memory_analysis — see EXPERIMENTS.md §Dry-run).
MICROBATCHES = {
    "jamba-1.5-large-398b": 8,
    "llava-next-34b": 4,
    "granite-8b": 2,
}

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "c64": 8, "s64": 8, "u64": 8,
    "f64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_COLL_RE = re.compile(
    r"=\s*(.*?)\s+(" + "|".join(_COLL_OPS) + r")(-start|-done)?\(")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo: str) -> dict:
    """Per-device bytes moved by collectives in the partitioned module."""
    per_op = {op: 0 for op in _COLL_OPS}
    count = {op: 0 for op in _COLL_OPS}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:           # avoid double count of async pairs
            continue
        result_part, op = m.group(1), m.group(2)
        b = _shape_bytes(result_part)
        per_op[op] += b
        count[op] += 1
    per_op_named = {f"bytes_{k}": v for k, v in per_op.items()}
    per_op_named.update({f"count_{k}": v for k, v in count.items()})
    per_op_named["coll_bytes"] = sum(per_op.values())
    return per_op_named


def _compile_and_report(jitted, args, label: str, verbose: bool) -> dict:
    t0 = time.monotonic()
    lowered = jitted.lower(*args)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    rec = {"lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)}
    try:
        mem = compiled.memory_analysis()
        rec["mem"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:                                 # pragma: no cover
        rec["mem"] = {"error": str(e)[:200]}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rec["cost"] = {"flops": cost.get("flops"),
                       "bytes_accessed": cost.get("bytes accessed")}
    except Exception as e:                                 # pragma: no cover
        rec["cost"] = {"error": str(e)[:200]}
    try:
        rec.update(collective_stats(compiled.as_text()))
    except Exception as e:                                 # pragma: no cover
        rec["coll_error"] = str(e)[:200]

    if verbose:
        mem = rec.get("mem", {})
        cost = rec.get("cost", {})
        print(f"  [{label}] lower {rec['lower_s']}s compile "
              f"{rec['compile_s']}s | flops/dev {cost.get('flops')} | "
              f"bytes/dev {cost.get('bytes_accessed')} | "
              f"arg+tmp bytes {mem.get('argument_bytes')}+"
              f"{mem.get('temp_bytes')} | coll/dev "
              f"{rec.get('coll_bytes')}", flush=True)
    return rec


# Hillclimb variants (EXPERIMENTS.md §Perf): model/step kwargs per name.
VARIANTS = {
    "baseline":    {},
    "mb1":         {"microbatches": 1},
    "mb2":         {"microbatches": 2},
    "pad_experts": {"model": {"pad_experts": True}},
    "moe_dense":   {"model": {"moe_impl": "dense"}},
    "moe_dense_pad": {"model": {"moe_impl": "dense", "pad_experts": True}},
    "remat_dots":  {"model": {"remat_policy": "dots"}},
    "cap1":        {"model": {"moe_capacity_factor": 1.0}},
    "pad_cap1":    {"model": {"pad_experts": True,
                              "moe_capacity_factor": 1.0}},
    "no_seqpar":   {"ctx": {"seq_parallel": False}},
    "compress_pod": {"step": {"compress_pod_reduce": True}},
    "grad_rs":     {"step": {"shard_grads": True}},
    "grad_rs_mb2": {"step": {"shard_grads": True}, "microbatches": 2},
}


def _lower_one(cfg, shape, mesh, *, microbatches, label, verbose,
               unroll=False, variant="baseline"):
    """Lower + compile one cell for one config; returns the record."""
    big = cfg.total_params() > 20e9
    vkw = VARIANTS[variant]
    model_kw = dict(vkw.get("model", {}))
    step_kw = dict(vkw.get("step", {}))
    ctx_kw = dict(vkw.get("ctx", {}))
    if "microbatches" in vkw:
        microbatches = vkw["microbatches"]

    moment_dtype = "bfloat16" if big else "float32"

    if shape.kind == "train":
        ctx = make_train_ctx(mesh, **ctx_kw)
        model = Model(cfg, ctx, compute_dtype="bfloat16",
                      attn_impl="flash_xla", remat=True,
                      max_seq=shape.seq_len, unroll=unroll, **model_kw)
        mb = microbatches or MICROBATCHES.get(cfg.name, 1)
        opt_cfg = optimizer_for_arch(cfg.name, moment_dtype=moment_dtype)
        step = make_train_step(model, opt_cfg, microbatches=mb,
                               unroll=unroll, **step_kw)
        state_shapes, state_sh = train_specs(model, moment_dtype)
        batch, batch_sh = batch_specs(cfg, shape, ctx, train=True)
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         donate_argnums=(0,))
        rec = _compile_and_report(jitted, (state_shapes, batch),
                                  f"{label} train mb={mb}", verbose)
        rec["microbatches"] = mb

    elif shape.kind == "prefill":
        ctx = make_serve_ctx(mesh, global_batch=shape.global_batch,
                             big_model=big)
        model = Model(cfg, ctx, compute_dtype="bfloat16",
                      attn_impl="flash_xla", max_seq=shape.seq_len,
                      unroll=unroll, **model_kw)

        def prefill(params, batch):
            return model.prefill(params, batch, max_cache_len=shape.seq_len)

        p_shapes = model.param_shapes()
        from repro.models.sharding import cache_shardings, param_shardings
        p_sh = param_shardings(p_shapes, ctx)
        batch, batch_sh = batch_specs(cfg, shape, ctx, train=False)
        cache_shapes = model.cache_shapes(shape.global_batch, shape.seq_len,
                                          dtype=model.compute_dtype)
        c_sh = cache_shardings(cache_shapes, ctx)
        jitted = jax.jit(prefill, in_shardings=(p_sh, batch_sh),
                         out_shardings=(None, c_sh))
        rec = _compile_and_report(jitted, (p_shapes, batch),
                                  f"{label} prefill", verbose)

    else:  # decode
        ctx = make_serve_ctx(mesh, global_batch=shape.global_batch,
                             big_model=big)
        model = Model(cfg, ctx, compute_dtype="bfloat16",
                      max_seq=shape.seq_len + 8, unroll=unroll, **model_kw)

        def decode(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)

        p_shapes = model.param_shapes()
        from repro.models.sharding import param_shardings
        p_sh = param_shardings(p_shapes, ctx)
        cache, c_sh, tokens, tok_sh, pos = decode_specs(cfg, shape, model)
        jitted = jax.jit(decode,
                         in_shardings=(p_sh, c_sh, tok_sh, None),
                         donate_argnums=(1,))
        rec = _compile_and_report(jitted, (p_shapes, cache, tokens, pos),
                                  f"{label} decode", verbose)
    return rec


# keys that the depth probe corrects by linear extrapolation over periods
_DEPTH_KEYS = ("coll_bytes",) + tuple(
    f"bytes_{op}" for op in _COLL_OPS) + tuple(
    f"count_{op}" for op in _COLL_OPS)


def _shallow_cfg(cfg, periods: int):
    import dataclasses
    enc = 0
    if cfg.encoder_layers:
        enc = max(1, cfg.encoder_layers // cfg.num_periods) * periods
    return dataclasses.replace(cfg, name=cfg.name,
                               num_layers=cfg.scan_period * periods,
                               encoder_layers=enc)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             microbatches: Optional[int] = None, depth_probe: bool = True,
             variant: str = "baseline", verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    base = {"arch": arch, "shape": shape_name, "mesh": mesh_name}

    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        if verbose:
            print(f"  [SKIP] {arch} x {shape_name}: {reason}", flush=True)
        return {**base, "status": "skip", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    label = f"{arch} x {shape_name} x {mesh_name}"
    label += "" if variant == "baseline" else f" [{variant}]"
    rec = _lower_one(cfg, shape, mesh, microbatches=microbatches,
                     label=label, verbose=verbose, variant=variant)

    if depth_probe and cfg.num_periods > 2:
        # XLA cost analysis counts a while-loop (scan) body ONCE; recover
        # true totals by lowering 1- and 2-period variants UNROLLED and
        # extrapolating: total = d1 + (NP - 1) * (d2 - d1).
        # Train probes run one microbatch (batch/mb) and scale by mb — the
        # only mb-invariant part is the optimizer update, negligible next
        # to layer flops, and unrolling mb would explode compile time.
        np_ = cfg.num_periods
        mb = 1
        probe_shape = shape
        if shape.kind == "train":
            import dataclasses as _dc
            mb = (VARIANTS[variant].get("microbatches") or microbatches
                  or MICROBATCHES.get(cfg.name, 1))
            if mb > 1:
                probe_shape = _dc.replace(
                    shape, global_batch=max(shape.global_batch // mb, 16))
                mb = shape.global_batch / probe_shape.global_batch
        d1 = _lower_one(_shallow_cfg(cfg, 1), probe_shape, mesh,
                        microbatches=1, label=label + " d1",
                        verbose=False, unroll=True, variant=variant)
        d2 = _lower_one(_shallow_cfg(cfg, 2), probe_shape, mesh,
                        microbatches=1, label=label + " d2",
                        verbose=False, unroll=True, variant=variant)
        corr = {}
        for key in ("flops", "bytes_accessed"):
            a, b = d1.get("cost", {}).get(key), d2.get("cost", {}).get(key)
            if a is not None and b is not None:
                corr[f"{key}_corrected"] = (a + (np_ - 1) * (b - a)) * mb
        for key in _DEPTH_KEYS:
            a, b = d1.get(key), d2.get(key)
            if a is not None and b is not None:
                corr[f"{key}_corrected"] = (a + (np_ - 1) * (b - a)) * mb
        rec.update(corr)
        if verbose and "flops_corrected" in corr:
            print(f"  [{label}] depth-corrected flops/dev "
                  f"{corr['flops_corrected']:.3e} coll/dev "
                  f"{corr.get('coll_bytes_corrected', 0):.3e}", flush=True)

    rec.update(base)
    rec["variant"] = variant
    rec["status"] = "ok"
    rec["chips"] = 512 if multi_pod else 256
    rec["total_params"] = cfg.total_params()
    rec["active_params"] = cfg.active_params()
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--no-depth-probe", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=sorted(VARIANTS))
    ap.add_argument("--out", default="experiments/dryrun.jsonl")
    args = ap.parse_args(argv)

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    n_fail = 0
    with open(args.out, "a") as f:
        for arch in archs:
            for shape in shapes:
                for mp in meshes:
                    try:
                        rec = run_cell(arch, shape, multi_pod=mp,
                                       microbatches=args.microbatches,
                                       depth_probe=not args.no_depth_probe,
                                       variant=args.variant)
                    except Exception as e:                 # noqa: BLE001
                        n_fail += 1
                        rec = {"arch": arch, "shape": shape,
                               "mesh": "2x16x16" if mp else "16x16",
                               "status": "fail", "error": str(e)[:500]}
                        print(f"  [FAIL] {arch} x {shape}: "
                              f"{str(e)[:200]}", flush=True)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
    print(f"done; failures={n_fail}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
