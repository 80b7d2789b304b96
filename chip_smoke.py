#!/usr/bin/env python3
"""Smoke run of the GA's main path on TPU chips.

    python chip_smoke.py             # one chip: phases a, b, c below
    python chip_smoke.py --chips 4   # islands sharded over four chips

One chip, one process, three phases:

  a. the test-function GA through ``ga_run.main`` (rastrigin, 8 islands x
     1024, 18 genes, fused Pallas variation kernel), then the same epoch
     step compiled ahead of time: the Pallas kernel must be in it, and
     best fitness must be finite and never get worse;
  b. the fused variation kernel against its pure-jnp reference
     (P = 8192, G = 18);
  c. HVDC dispatch on the German-grid counts (2715 buses, 5351 lines,
     871 generators, 18 HVDC lines) through ``GAEngine``: every Newton
     solve must converge, and the objective must agree with the same
     genomes evaluated at ``highest`` matmul precision.

``--chips 4`` runs only the island GA on a 4-way ``data`` mesh next to
the same configuration on one device: the population must be sharded
over four devices, the trajectories must agree, and the compiled epoch
must hold the migration collective.

Every input is made from ``--seed``. Timings are informational. The
script exits non-zero, without the final line, when JAX finds no TPU or
any check fails. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

KERNEL_RTOL = KERNEL_ATOL = 1e-4     # phase b: kernel vs reference
HVDC_REL_TOL = 1e-3                  # phase c: default vs highest precision
TRAJ_TOL = 1e-5                      # --chips 4: sharded vs one device


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def phase_ga_run(seed: int, kind: str) -> None:
    import jax
    import numpy as np
    from repro.core.engine import GAEngine
    from repro.launch import ga_run

    argv = ["--fitness", "rastrigin", "--genes", "18", "--islands", "8",
            "--pop", "1024", "--gens-per-epoch", "5", "--epochs", "3",
            "--dispatch-backend", "inline", "--seed", str(seed)]
    t0 = time.perf_counter()
    _, hist = ga_run.main(argv)
    wall = time.perf_counter() - t0
    best = [h["best"] for h in hist]
    check(len(best) == 3 and all(np.isfinite(best)),
          f"phase a: finite best per epoch {best}")
    check(all(b <= a for a, b in zip(best, best[1:])),
          f"phase a: best never gets worse {best}")

    cfg, fitness, _ = ga_run.build("rastrigin", argparse.Namespace(
        genes=18, pop=1024, islands=8, gens_per_epoch=5, epochs=3,
        seed=seed))
    check(cfg.fused_operators, "phase a: fused kernel on")
    eng = GAEngine(cfg, fitness)
    pop = jax.block_until_ready(eng.init())
    t0 = time.perf_counter()
    step = eng._epoch_step.lower(pop).compile()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in step.as_text(),
          "phase a: Pallas kernel in the compiled epoch step")
    t0 = time.perf_counter()
    pop, _ = jax.block_until_ready(step(pop))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    pop, _ = jax.block_until_ready(step(pop))
    steady_s = time.perf_counter() - t0
    print(f"phase a: ga_run ok, best per epoch {best}, "
          f"tpu_custom_call in epoch step")
    print(f"phase a: ga_run wall {wall:.3f} s for 3 epochs incl. compile "
          f"(informational, {kind})")
    print(f"phase a: epoch compile {compile_s:.3f} s, first epoch "
          f"{first_s:.6f} s after compile (informational, {kind})")
    print(f"phase a: steady epoch {steady_s:.6f} s, 8x1024x18, 5 gens "
          f"(informational, {kind})")


def phase_kernel(seed: int) -> None:
    import functools
    import jax
    import numpy as np
    from repro.kernels.genetic import ops as gk

    kw = dict(eta_cx=15.0, prob_cx=0.9, eta_mut=20.0, prob_mut=0.7,
              indpb=1.0 / 18, lower=-5.12, upper=5.12)
    k_var, k_par = jax.random.split(jax.random.PRNGKey(seed))
    parents = jax.random.uniform(k_par, (8192, 18), minval=-5.12,
                                 maxval=5.12)
    kernel = jax.jit(functools.partial(gk.fused_variation, **kw))
    check("tpu_custom_call" in kernel.lower(k_var, parents).as_text(),
          "phase b: kernel compiled for the TPU, not interpreted")
    out = np.asarray(kernel(k_var, parents))
    ref = np.asarray(jax.jit(functools.partial(
        gk.fused_variation_oracle, **kw))(k_var, parents))
    err = float(np.max(np.abs(out - ref)))
    print(f"phase b: kernel vs reference max abs diff {err!r} "
          f"(rtol {KERNEL_RTOL}, atol {KERNEL_ATOL})")
    check(np.all(np.isfinite(out)), "phase b: finite kernel output")
    check(np.allclose(out, ref, rtol=KERNEL_RTOL, atol=KERNEL_ATOL),
          "phase b: kernel allclose to reference")


def phase_hvdc(seed: int, kind: str) -> None:
    import jax
    import numpy as np
    from repro.configs.base import GAConfig
    from repro.core.engine import GAEngine
    from repro.fitness.powerflow import HVDCDispatchFitness
    from repro.powerflow.grid import make_german_grid

    grid = make_german_grid(seed)
    counts = (grid.n_bus, grid.n_line, int(np.sum(grid.bus_type != 0)),
              grid.n_hvdc)
    print(f"phase c: grid buses/lines/generators/hvdc {counts}")
    check(counts == (2715, 5351, 871, 18), "phase c: German-grid counts")
    fit = HVDCDispatchFitness(grid)
    cfg = GAConfig(num_genes=grid.n_hvdc, pop_per_island=4, num_islands=2,
                   generations_per_epoch=1, num_epochs=2,
                   lower=-1.0, upper=1.0,
                   mutation_prob=0.7, mutation_eta=34.6,
                   crossover_prob=1.0, crossover_eta=97.5, seed=seed)
    eng = GAEngine(cfg, fit, cost_fn=fit.cost_model())
    t0 = time.perf_counter()
    pop, hist = eng.run()
    wall = time.perf_counter() - t0
    best = [h["best"] for h in hist]
    print(f"phase c: GAEngine best per epoch {best}, wall {wall:.3f} s "
          f"for 2 epochs incl. compile (informational, {kind})")
    check(len(best) == 2 and all(np.isfinite(best)),
          "phase c: finite best per epoch")

    genomes = pop.genomes.reshape(-1, grid.n_hvdc)
    obj, conv = jax.jit(fit.evaluate)(genomes)
    with jax.default_matmul_precision("highest"):
        obj_hi, conv_hi = jax.jit(fit.evaluate)(genomes)
    obj, obj_hi = np.asarray(obj[:, 0]), np.asarray(obj_hi[:, 0])
    share = float(np.mean(np.asarray(conv)))
    share_hi = float(np.mean(np.asarray(conv_hi)))
    rel = float(np.max(np.abs(obj - obj_hi) / np.abs(obj_hi)))
    print(f"phase c: converged share {share!r} "
          f"(highest precision: {share_hi!r}) over {obj.size} genomes")
    print(f"phase c: objective default  {obj.tolist()}")
    print(f"phase c: objective highest  {obj_hi.tolist()}")
    print(f"phase c: max relative difference {rel!r} (tol {HVDC_REL_TOL})")
    check(np.all(np.isfinite(obj)), "phase c: finite objectives")
    check(share == 1.0, "phase c: every Newton solve converged")
    check(rel <= HVDC_REL_TOL, "phase c: objective agrees with highest")


def phase_four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from repro.configs.base import GAConfig
    from repro.core.engine import GAEngine
    from repro.fitness import get_benchmark
    from repro.launch.mesh import make_local_mesh
    from repro.models.sharding import ShardingCtx

    cfg = GAConfig(num_genes=18, pop_per_island=1024, num_islands=8,
                   generations_per_epoch=5, num_epochs=3,
                   lower=-5.12, upper=5.12, mutation_prob=0.7,
                   mutation_eta=20.0, crossover_prob=0.9,
                   crossover_eta=15.0, seed=seed)
    fitness = get_benchmark("rastrigin")
    pop1, hist1 = GAEngine(cfg, fitness).run()
    ctx = ShardingCtx(mesh=make_local_mesh(data=4, model=1), dp=("data",),
                      tp="model", fsdp=())
    eng = GAEngine(cfg, fitness, ctx=ctx)
    pop4, hist4 = eng.run()
    shards = len(pop4.genomes.sharding.device_set)
    err = float(np.max(np.abs(np.asarray(pop1.genomes)
                              - np.asarray(pop4.genomes))))
    hlo = eng._epoch_step.lower(pop4).compile().as_text()
    collective = [c for c in ("collective-permute", "all-to-all",
                              "all-gather") if c in hlo]
    print(f"four chips: population sharded over {shards} devices")
    print(f"four chips: best per epoch one device "
          f"{[h['best'] for h in hist1]}, four {[h['best'] for h in hist4]}")
    print(f"four chips: trajectory max abs diff {err!r} (tol {TRAJ_TOL})")
    print(f"four chips: collectives in compiled epoch {collective}, "
          f"tpu_custom_call {'tpu_custom_call' in hlo}")
    check(shards == 4, "four chips: population sharded over 4 devices")
    check(err <= TRAJ_TOL, "four chips: trajectories agree")
    check(bool(collective), "four chips: migration collective present")
    check("tpu_custom_call" in hlo, "four chips: Pallas kernel present")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    print(f"devices: {len(devices)} x {platform} {kind}")
    if platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 1
    if args.chips == 4:
        phase_four_chips(args.seed)
    else:
        phase_ga_run(args.seed, kind)
        phase_kernel(args.seed)
        phase_hvdc(args.seed, kind)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
