"""Where JAX keeps its persistent compilation cache, and what compiling
costs.

A deployment places the cache with ``JAX_COMPILATION_CACHE_DIR``; without
it the cache lives at a fixed path inside the checkout (``.jax_cache``,
git-ignored). The path is part of the cache's key, so it is never built
from a temp name, a pid or the time. Call :func:`enable_compile_cache`
before the process's first compile.

:func:`enable_compile_cache` also registers one ``jax.monitoring``
listener that publishes, through the ``repro.runtime.metrics`` seam,
``chambga_compile_seconds_total{fun=<function>, phase=trace|lower|compile}``
and ``chambga_compile_cache_hits_total``. Each second is counted once: JAX
times the persistent cache's retrieval inside the backend compile, so a
cache hit's seconds fall under ``compile``; and a jitted function traced
inside another's trace (``jnp`` functions are jitted) is counted in its
caller's trace, not again on its own.
"""
from __future__ import annotations

import os
import re
import threading

from repro.runtime import metrics as _metrics

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

# jax.monitoring duration events -> the phase label they publish under
PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MODULE = re.compile(r"^jit\((.*)\)$")

_listening = False


class _Open(threading.local):
    """Per thread: how many of each phase have begun and not yet ended."""

    def __init__(self):
        self.depth = {}


_open = _Open()


def _on_start(event: str, value: float, **kwargs) -> None:
    # JAX records a phase's start time as a scalar when the phase begins
    if event in PHASES:
        _open.depth[event] = _open.depth.get(event, 0) + 1


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    phase = PHASES.get(event)
    if phase is None:
        return
    begun = _open.depth.get(event, 0)
    _open.depth[event] = max(0, begun - 1)
    if begun > 1:
        return                  # inside the same phase of its caller
    m = _metrics.get_registry()
    if m.enabled:
        # the lower and compile phases name the module (``jit(<fun>)``),
        # the trace phase the function: one label for all three
        fun = str(kwargs.get("fun_name", ""))
        mod = _MODULE.match(fun)
        m.inc("chambga_compile_seconds_total", float(seconds),
              fun=mod.group(1) if mod else fun, phase=phase)


def _on_event(event: str, **kwargs) -> None:
    if event == CACHE_HIT_EVENT:
        m = _metrics.get_registry()
        if m.enabled:
            m.inc("chambga_compile_cache_hits_total")


def register_compile_listener() -> None:
    """Publish compile seconds and cache hits to the metrics seam (once
    per process; a no-op while no registry is installed)."""
    global _listening
    if _listening:
        return
    import jax

    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _listening = True


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    if set, else at :data:`DEFAULT_DIR`, and register the compile
    listener. Returns the directory."""
    import jax

    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    register_compile_listener()
    return path
