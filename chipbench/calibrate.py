#!/usr/bin/env python3
"""Readings that set the limits of ``correct``; not part of a benchmark run.

    python3 chipbench/calibrate.py --workload hvdc_horizontal \\
        --seeds 101 102 103 --control-seeds 201 202 203 --seconds 1 \\
        [--save-trace out.json.gz]

In one process, for each of ``--seeds``: the cell's set-up and a window of
``--seconds`` through the harness's own ``drive``, then the numbers of
the cell's comparison, ``run.numbers`` (the lower readings). For each of
``--control-seeds`` the same with the control in the program's place,
the program in the precision below the configuration's (the upper
readings). One JSON line per seed. ``--control-precision`` hands the
control another precision; ``--save-trace`` also records a traced window
of the first seed.
Needs the cell's chips, like ``run.py``.
"""
import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import run  # noqa: E402


def readings(jax, dep, seconds: float) -> dict:
    t = time.monotonic()
    out = run.drive(jax, dep, seconds, False)
    drive_s = time.monotonic() - t
    t = time.monotonic()
    nums, wrong = run.numbers(dep, out)
    return {"epochs": out["epochs"], "window_s": out["window_s"],
            "setup_s": out["setup_s"], "drive_s": drive_s,
            "reference_s": time.monotonic() - t,
            "memory_peak_bytes": out["memory_peak_bytes"], "numbers": nums,
            "wrong": wrong, "reference_unconverged": dep.unconverged()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-precision", default="high")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--save-trace", default=None)
    args = ap.parse_args(argv)

    bench, cell, config, mix = run.load_cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    device = run.chips_or_fail(jax, cell["chips"])
    builder = importlib.import_module(f"chipbench.configs.{cell['config']}")

    def deployment(seed):
        return builder.build(config, mix, seed=seed, chips=cell["chips"])

    if args.save_trace and args.seeds:
        dep = deployment(args.seeds[0])
        out = run.drive(jax, dep, args.seconds, True)
        out["trace"].save(args.save_trace)
        print(json.dumps({"seed": args.seeds[0], "traced": args.save_trace,
                          "metrics": run.per_layer(bench, cell["name"], dep,
                                                   out, device)}),
              flush=True)
    for seed in args.seeds:
        print(json.dumps(dict(seed=seed, side="program",
                              **readings(jax, deployment(seed),
                                         args.seconds))), flush=True)
    for seed in args.control_seeds:
        dep = deployment(seed)
        with dep.control(args.control_precision):
            r = readings(jax, dep, args.seconds)
        print(json.dumps(dict(seed=seed, side="control",
                              precision=args.control_precision, **r)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
