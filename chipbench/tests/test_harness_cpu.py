"""The harness end to end on the CPU at a tiny size, driven as functions
(the command itself refuses a CPU, which the first test checks)."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.configs import hvdc_german

ROOT = run.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
MIX = json.load(open(os.path.join(ROOT, "chipbench", "mixes", "inline.json")))
PEAKS = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))


def tiny_hvdc():
    conf = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "hvdc_german.json")))
    conf["grid"].update(n_bus=60, n_line=114, n_gen=15, n_hvdc=4,
                        hvdc_pmax_mw=[1300, 1300, 2000, 2000])
    conf["num_genes"] = 4
    return conf


def drive_and_check(dep, seconds=1.0, traced=False):
    out = run.drive(jax, dep, seconds, traced)
    return out, run.check(dep, out)


def test_command_refuses_a_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chipbench",
                                                     "run.py"),
                        "--workload", "hvdc_horizontal", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=str(tmp_path), env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_hvdc_run_is_correct_and_reports_its_metrics():
    dep = hvdc_german.build(tiny_hvdc(), MIX, seed=2**31 + 17, chips=1)
    out, (correct, failed, checks) = drive_and_check(dep)
    assert correct and failed == 0, checks
    assert list(checks) == ["objective_gap", "unmatched_share",
                            "migrants_missing"]
    e2e = run.end_to_end(BENCH, "hvdc_horizontal", dep, out)
    assert set(e2e) == {"evals_per_s", "setup_s"}
    assert e2e["evals_per_s"]["value"] == pytest.approx(
        out["epochs"] * 2 * 4 * 5 / out["window_s"])


def test_same_seed_same_inputs():
    conf = tiny_hvdc()
    a = hvdc_german.build(conf, MIX, seed=5, chips=1)
    b = hvdc_german.build(conf, MIX, seed=5, chips=1)
    c = hvdc_german.build(conf, MIX, seed=5 + 2**32, chips=1)
    assert a.ga_seed == b.ga_seed != c.ga_seed


def test_traced_run_on_cpu_has_no_device_metrics():
    dep = hvdc_german.build(tiny_hvdc(), MIX, seed=3, chips=1)
    out, (correct, _, _) = drive_and_check(dep, seconds=0.5, traced=True)
    assert correct
    assert out["traced_epochs"] == 1 and out["trace"].window_s > 0
    # the CPU has no TPU planes: every device reader finds nothing
    assert run.per_layer(BENCH, "hvdc_horizontal", dep, out,
                         {"peaks": PEAKS["TPU v5 lite"]}) == {}


def _dot_precisions(dep):
    genomes = jnp.zeros((2, dep.cfg.num_genes), jnp.float32)
    text = jax.jit(dep.fitness).lower(genomes).as_text()
    return set(re.findall(r"precision = \[(\w+)", text))


def test_control_runs_the_newton_solve_at_high():
    """On the CPU every float32 product is exact whatever the flag, so the
    control cannot fail here (calibrate.py reads it on the chip); what it
    plants is checked in the program as lowered."""
    dep = hvdc_german.build(tiny_hvdc(), MIX, seed=6, chips=1)
    assert _dot_precisions(dep) == {"HIGHEST"}
    with dep.control():
        dep = hvdc_german.build(tiny_hvdc(), MIX, seed=6, chips=1)
        assert _dot_precisions(dep) == {"HIGH"}
    dep = hvdc_german.build(tiny_hvdc(), MIX, seed=6, chips=1)
    assert _dot_precisions(dep) == {"HIGHEST"}


def test_four_chips_are_refused():
    with pytest.raises(ValueError, match="one chip"):
        hvdc_german.build(tiny_hvdc(), MIX, seed=1, chips=4)


def test_command_fails_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's paths
    has no system under test: the run exits non-zero with no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "hvdc_horizontal", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=str(tmp_path),
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
