# One function per paper table/figure. Prints ``name,...,derived`` CSV.
"""Benchmark entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

  fig4_efficiency  — parallel efficiency vs workers x eval time (Fig. 4)
  fig5_*           — horizontal vs vertical HVDC scaling (Fig. 5)
  fig6_metaga      — meta-GA hyperparameter evolution (Fig. 6)
  broker/operator  — framework overhead microbench (Tab. 1 / §3 claims)

Pass --quick for the fast subset (CI); --only NAME to run one section.
--json PATH dumps every section's rows machine-readably (the default
``BENCH_obs.json`` feeds dashboards and regression diffing — notably
the ``mq_dispatch_metrics_{off,on}`` observability-overhead pair and
the ``mq_autoscale_{depth,cost}_signal`` shoot-out).
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _jsonable(value):
    """Best-effort conversion of a benchmark row value (floats, numpy
    scalars, nested tuples) into plain JSON types."""
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    if isinstance(value, dict):
        return {str(k): _jsonable(v) for k, v in value.items()}
    if isinstance(value, (str, int, bool)) or value is None:
        return value
    try:
        return float(value)
    except (TypeError, ValueError):
        return str(value)


def write_bench_json(path: str, sections: dict) -> None:
    """Dump every section's rows as ``{section: [[name, value], ...]}``
    — the machine-readable mirror of the CSV lines printed above."""
    with open(path, "w") as f:
        json.dump({k: _jsonable(v) for k, v in sections.items()},
                  f, indent=2, sort_keys=True)
        f.write("\n")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default="BENCH_obs.json", metavar="PATH",
                    help="write all section rows machine-readably "
                         "(empty string disables)")
    args = ap.parse_args(argv)

    sections = {}

    def want(name):
        return args.only is None or args.only == name

    t_all = time.perf_counter()

    if want("broker_overhead"):
        from benchmarks import broker_overhead
        print("# --- framework overhead (paper §3 / Tab. 1) ---")
        sections["broker_overhead"] = broker_overhead.run()

    if want("efficiency"):
        from benchmarks import efficiency
        print("# --- Fig. 4: parallel efficiency ---")
        sections["efficiency"] = efficiency.run()

    if want("hvdc_scaling"):
        from benchmarks import hvdc_scaling
        print("# --- Fig. 5: horizontal vs vertical HVDC ---")
        sections["hvdc_scaling"] = hvdc_scaling.run(
            grid_buses=30 if args.quick else 40,
            epochs=2 if args.quick else 4)

    if want("meta_ga"):
        from benchmarks import meta_ga
        print("# --- Fig. 6: meta-GA hyperparameters ---")
        sections["meta_ga"] = meta_ga.run(
            epochs=1 if args.quick else 2,
            pop=6 if args.quick else 8,
            inner_generations=4 if args.quick else 6)

    if args.json:
        write_bench_json(args.json, sections)
        print(f"# wrote {args.json}")
    print(f"# total {time.perf_counter() - t_all:.1f}s")


if __name__ == "__main__":
    main()
