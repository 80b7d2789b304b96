"""One process per chip: host-side fitness workers run JAX on the CPU, and
the compile cache sits where the deployment says (or at a fixed path)."""
import os
import subprocess
import sys

import pytest

import jax

from repro.core.broker import HostPoolBackend
from repro.fitness import hostsim
from repro.launch import compile_cache
from repro.runtime import batchq, mq, netbroker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


@pytest.fixture
def popen_envs(monkeypatch):
    """Record the env of every subprocess.Popen; start nothing. The
    parent asks for the TPU, so only the spawner can make it CPU."""
    envs = []

    class RecordingPopen:
        def __init__(self, cmd, env=None, **kwargs):
            envs.append(env)

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(subprocess, "Popen", RecordingPopen)
    return envs


def _assert_cpu_worker_env(env):
    assert env is not None and env["JAX_PLATFORMS"] == "cpu"
    assert env["PYTHONPATH"].split(os.pathsep)[0] == SRC


@pytest.mark.parametrize("spawn", ["mq", "batchq", "netbroker"])
def test_worker_subprocess_runs_jax_on_cpu(spawn, popen_envs, tmp_path):
    if spawn == "mq":
        pool = mq.LocalWorkerPool(num_workers=1, mode="subprocess",
                                  mq_dir=str(tmp_path))
        pool._spawn_member()
    elif spawn == "batchq":
        batchq._spawn_local_worker(str(tmp_path / "chunk_0000_try0.npz"),
                                   "subprocess", sys.executable, ())
    else:
        pool = netbroker.NetWorkerPool(num_workers=1, mode="subprocess",
                                       addr="127.0.0.1:1")
        pool._spawn_member()
    assert len(popen_envs) == 1
    _assert_cpu_worker_env(popen_envs[0])


def test_process_pool_worker_runs_jax_on_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with HostPoolBackend(hostsim.sphere, num_workers=1,
                         executor="process") as backend:
        child = backend._pool.submit(os.getenv, "JAX_PLATFORMS")
        assert child.result(timeout=120) == "cpu"


def test_compile_cache_dir_from_env_else_fixed(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv(compile_cache.ENV_VAR)
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_written_where_env_says(tmp_path):
    """In a fresh process, a compile lands in $JAX_COMPILATION_CACHE_DIR."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert os.listdir(tmp_path)
