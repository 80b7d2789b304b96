#!/usr/bin/env python3
"""CHAMB-GA chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload hvdc_horizontal --seed 7 \\
        --seconds 45 --trace 0

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``
and the builder ``configs/<name>.py``) and a traffic mix
(``mixes/<name>.json``). A run:

1. points JAX's persistent compilation cache at the checkout
   (``repro.launch.compile_cache``), and fails unless JAX finds a TPU with
   as many chips as the cell asks for;
2. builds the deployment from the cell's files and ``--seed``, makes and
   evaluates the initial population on the device (``GAEngine.init``), and
   runs one warm-up epoch, which compiles or loads the epoch step.
   ``setup_s`` runs from process start to here;
3. drives ``GAEngine.run`` for ``--seconds`` (the window): ``evals_per_s``
   is the evaluations of the epochs completed over the window's wall time.
   With ``--trace 1`` the same window is followed by
   ``trace_epochs`` epochs under the profiler, and the run reports the
   cell's per-layer metrics, each read by ``metrics/<name>.py``; the
   ops of the trace are named by the program scopes of the compiled
   epoch step, read from its HLO text after the traced epochs;
4. runs the window's compiled epoch step once more, reads the device's
   peak memory, frees the program, and compares both populations with the
   plain reference (``compare.py``), which decides ``correct``.

The last line of stdout is one JSON object; the numbers compared, each with
its limit, are the last lines of stderr and the last key of that object.
Without a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if __name__ == "__main__":
    # import the benchmark as the package ``chipbench``: its directory on
    # the path would let chipbench/trace.py shadow the standard library's
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import compare  # noqa: E402
from chipbench import trace as tracing  # noqa: E402


class NoChip(RuntimeError):
    pass


def load_cell(name: str) -> tuple:
    """(benchmark, cell, config file, mix file) for workload ``name``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "mixes", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return bench, cell, config, mix


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def chips_or_fail(jax, chips: int) -> dict:
    """The device record; raises NoChip without a TPU of ``chips`` chips."""
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {platform} device(s)")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    return {"platform": platform, "kind": kind, "count": len(devices),
            "peaks": peaks[kind]}


def host_pop(jax, pop) -> dict:
    g, f, r = jax.device_get((pop.genomes, pop.fitness, pop.rng))
    return {"genomes": g, "fitness": f, "rng": r}


def drive(jax, dep, seconds: float, traced: bool) -> dict:
    """Set-up, the window and the replay epoch. Returns what the result
    line and the check need; the program's device state is freed."""
    from repro.core.engine import GAEngine

    eng = GAEngine(dep.cfg, dep.fitness, cost_fn=dep.cost_fn, ctx=dep.ctx)
    pop = jax.block_until_ready(eng.init(dep.ga_seed))
    pop, _ = eng.run(pop, epochs=1)
    pop = jax.block_until_ready(pop)
    out = {"setup_s": time.monotonic() - T_START}

    start = pop                         # the window's first epoch input
    t0 = time.monotonic()
    pop, hist = eng.run(pop, epochs=10**9, wallclock_s=seconds)
    pop = jax.block_until_ready(pop)
    out["window_s"] = time.monotonic() - t0
    if traced:
        # a few epochs more, under the profiler, at the state the window
        # ended in (most of a window's epochs are past its first seconds)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                pop, traced_hist = eng.run(pop, epochs=dep.trace_epochs)
                pop = jax.block_until_ready(pop)
            jax.profiler.stop_trace()
            t_scopes = time.monotonic()
            out["traced_s"] = t_scopes - t0 - out["window_s"]
            scopes = tracing.scope_map(
                eng._epoch_step.lower(pop).compile().as_text())
            out["scopes_s"] = time.monotonic() - t_scopes
            out["trace"] = tracing.from_profile_dir(trace_dir, scopes)
            out["traced_epochs"] = len(traced_hist)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    out["epochs"] = len(hist)

    # the window's first epoch once more, from the same input: the same
    # compiled step on the same state gives what the window's first epoch
    # gave, which the reference follows epoch for epoch
    t1 = time.monotonic()
    replay, _ = eng.run(start, epochs=1)
    out["start_pop"] = host_pop(jax, start)
    out["window_pop"] = host_pop(jax, pop)
    out["replay_pop"] = host_pop(jax, replay)
    out["replay_s"] = time.monotonic() - t1
    used = (dep.ctx.mesh.devices.flat if dep.ctx is not None
            else jax.devices()[:1])
    out["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    del eng, pop, replay, start
    gc.collect()
    return out


def end_to_end(bench: dict, cell: str, dep, run: dict) -> dict:
    evals = run["epochs"] * dep.evals_per_epoch
    values = {"evals_per_s": evals / run["window_s"],
              "setup_s": run["setup_s"]}
    print(f"window: {run['epochs']} epochs, {evals} evaluations in "
          f"{run['window_s']!r} s", file=sys.stderr)
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if applies(m, cell) and m["name"] in values}


def per_layer(bench: dict, cell: str, dep, run: dict, device: dict) -> dict:
    trace = run["trace"]
    info = dict(dep.shapes, epochs=run["traced_epochs"],
                peaks=device["peaks"])
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, cell):
            continue
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        value = reader.read(trace, info)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def numbers(dep, run: dict) -> tuple:
    """``compare.numbers`` of the run's populations: (numbers, wrong
    evaluations)."""
    return compare.numbers(
        dep.fitness_name, dep.reference, dep.ga, run["start_pop"],
        run["window_pop"], run["replay_pop"], dep.match_tol,
        dep.limits[f"{dep.fitness_name}_gap"])


def check(dep, run: dict) -> tuple:
    """(correct, failed, numbers with limits) against the reference.
    ``failed`` counts the checked evaluations whose fitness is off by more
    than the limit, and reference solves that did not converge."""
    nums, wrong = numbers(dep, run)
    checks = {k: {"value": v, "limit": dep.limits[k]} for k, v in nums.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    return correct, wrong + dep.unconverged(), checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, config, mix = load_cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    try:
        device = chips_or_fail(jax, cell["chips"])
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(f"device: {device['count']} x {device['platform']} {device['kind']};"
          f" compile cache {cache}", file=sys.stderr)
    builder = importlib.import_module(f"chipbench.configs.{cell['config']}")
    dep = builder.build(config, mix, seed=args.seed, chips=cell["chips"])
    run = drive(jax, dep, args.seconds, bool(args.trace))
    t_check = time.monotonic()
    correct, failed, checks = check(dep, run)
    print("phases (s): " + ", ".join(
        f"{k} {run[k + '_s']:.1f}" for k in ("setup", "window", "traced",
                                             "scopes", "replay")
        if k + "_s" in run)
          + f", check {time.monotonic() - t_check:.1f}", file=sys.stderr)

    result = {"correct": correct,
              "attempted": run["epochs"] * dep.evals_per_epoch,
              "failed": failed}
    dev = {k: device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = run["memory_peak_bytes"]
    if args.trace:
        result["metrics"] = per_layer(bench, cell["name"], dep, run, device)
        busy = run["trace"].busy_s()
        dev["busy_s"] = sum(busy) / len(busy)
        dev["window_s"] = run["trace"].window_s
        result["device"] = dev
        result["breakdown"] = tracing.breakdown(run["trace"])
    else:
        result["metrics"] = end_to_end(bench, cell["name"], dep, run)
        result["device"] = dev
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
