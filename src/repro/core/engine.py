"""GAEngine: epoch orchestration, termination, checkpointing, logging.

The engine is the paper's "CHAMB-GA scripts" control hub (Fig. 1): it owns
the jitted epoch step (cluster side) and handles user-facing concerns —
run control, wall-clock/target termination, checkpoint/restart, history.

Async manager/worker note: JAX dispatch is asynchronous — the host enqueues
epoch e+1 while the devices still execute epoch e; the engine only blocks
when it *reads* metrics. The epoch loop is double-buffered: the population
buffers are donated to the jitted step (in-place update on accelerator
backends), each epoch's metrics start a non-blocking device->host copy
immediately, and the blocking ``device_get`` of epoch e is deferred until
epoch e+``pipeline_depth`` has been dispatched — the manager-side
counterpart of the paper's non-blocking queue submission. ``sync_every``
additionally batches how often the pending queue is drained.

Observability: the host steps are profiler spans, each with ``epoch=<n>``
(``chambga.init`` the initial evaluation, ``chambga.dispatch`` the epoch
step's call, any retrace or recompile included, ``chambga.drain`` the
blocking metric read, ``chambga.checkpoint`` a save). Each drained epoch
is published through the ``repro.runtime.metrics`` seam as
``chambga_epochs_total`` and ``chambga_evaluations_total``, and the
fitness's own batch sums (the HVDC fitness's Newton iterations, solves
and unconverged solves) as ``chambga_<name>_total``; the history record
carries the same sums.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GAConfig
from repro.core.broker import Broker, DispatchBackend
from repro.core.island import (evaluate_population, make_epoch_step,
                               constrain_pop)
from repro.core.population import (Population, best_of, evals_dtype,
                                   init_population)
from repro.models.sharding import ShardingCtx
from repro.runtime import metrics as _metrics


def _start_host_copy(tree) -> None:
    """Kick off non-blocking device->host transfers for every leaf, so the
    later device_get finds the bytes already on host."""
    for leaf in jax.tree_util.tree_leaves(tree):
        copy = getattr(leaf, "copy_to_host_async", None)
        if copy is not None:
            copy()


class GAEngine:
    def __init__(self, cfg: GAConfig, fitness_fn: Optional[Callable] = None, *,
                 cost_fn: Optional[Callable] = None,
                 backend: Optional[DispatchBackend] = None,
                 ctx: Optional[ShardingCtx] = None,
                 num_workers: Optional[int] = None,
                 checkpointer=None, checkpoint_every: int = 0,
                 log_fn: Optional[Callable] = None,
                 sync_every: int = 1,
                 pipeline_depth: int = 1):
        self.cfg = cfg
        self.ctx = ctx
        workers = num_workers if num_workers is not None else (
            ctx.dp_size if ctx and ctx.mesh else 1)
        self.broker = Broker(fitness_fn, cost_fn, num_workers=workers,
                             backend=backend)
        self.checkpointer = checkpointer
        self.checkpoint_every = checkpoint_every
        self.log_fn = log_fn
        self.sync_every = max(1, sync_every)
        self.pipeline_depth = max(0, pipeline_depth)
        # exact eval counting past 2^31: the device counter is i32 without
        # x64 (wraps after ~128 epochs at 3,500-core scale), so the engine
        # accumulates per-epoch increments into an unbounded host int,
        # checkpointed alongside the device counter as "evals_host"
        self.evals_host: int = 0
        # donation aliases the input population buffers to the output on
        # backends that support it (TPU/GPU); CPU ignores donation, so skip
        # it there to avoid per-compile warnings
        self._donate = jax.default_backend() != "cpu"
        self._build_steps()

    def _build_steps(self) -> None:
        """(Re)jit the epoch/init steps for the current cfg + broker —
        called at construction and after an elastic :meth:`resize`."""
        self._epoch_step = jax.jit(
            make_epoch_step(self.cfg, self.broker, self.ctx),
            donate_argnums=(0,) if self._donate else ())
        self._init_eval = jax.jit(
            lambda pop: evaluate_population(self.cfg, self.broker, pop))

    # ------------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> Population:
        rng = jax.random.PRNGKey(self.cfg.seed if seed is None else seed)
        pop = init_population(self.cfg, rng)
        pop = constrain_pop(pop, self.ctx)
        self.evals_host = self.cfg.global_pop
        with jax.profiler.TraceAnnotation("chambga.init", epoch=0):
            return self._init_eval(pop)

    def restore(self, step: Optional[int] = None) -> Optional[Population]:
        if self.checkpointer is None:
            return None
        state = self.checkpointer.restore(step)
        if state is None:
            return None
        # exact host-side counter rides along the device counter; older
        # checkpoints (no "evals_host") seed it from the stored value
        # BEFORE the i32 downcast, so a legacy count past 2^31 stays exact
        host = state.pop("evals_host", None)
        evals64 = np.asarray(state["evals"]).astype(np.int64)
        self.evals_host = (int(host) if host is not None
                           else max(0, int(evals64)))
        # pre-int checkpoints stored the eval counter as f32; normalize
        state["evals"] = jnp.asarray(evals64).astype(evals_dtype())
        return Population(**state)

    def _checkpoint_state(self, pop: Population) -> dict:
        state = dict(pop._asdict())
        state["evals_host"] = np.uint64(self.evals_host)
        return state

    # ------------------------------------------------------------------
    def resize(self, pop: Population, new_islands: int, *,
               rng: Optional[jax.Array] = None,
               num_workers: Optional[int] = None) -> Population:
        """Elastic lane re-balance: repartition ``pop`` onto
        ``new_islands`` islands (``runtime/elastic.repartition_islands``)
        and rebuild the broker's balanced assignment for the resized
        fleet — ``num_workers`` scales proportionally with the island
        count unless given explicitly, and the epoch step is re-jitted so
        the new lane count never collides with stale traces. Grown
        populations (clones marked +inf) are re-evaluated before the
        engine continues. Dispatch permutations never change fitness
        values, so a re-balanced run tracks a fixed-lane run exactly on
        deterministic fitness."""
        old_islands = pop.genomes.shape[0]
        if rng is None:
            rng = jax.random.fold_in(jax.random.PRNGKey(self.cfg.seed),
                                     1000 + new_islands)
        from repro.runtime.elastic import repartition_islands
        pop = repartition_islands(self.cfg, pop, new_islands, rng)
        self.cfg = dataclasses.replace(self.cfg, num_islands=new_islands)
        if num_workers is None:
            num_workers = max(
                1, self.broker.num_workers * new_islands // old_islands)
        self.broker = Broker(self.broker.fitness_fn, self.broker.cost_fn,
                             num_workers=num_workers,
                             backend=self.broker.backend)
        backend = self.broker.backend
        if hasattr(backend, "num_workers"):
            # decoupled backends chunk by their own num_workers; keep the
            # split aligned with the broker's lane boundaries (executor
            # pool sizes stay as constructed — extra chunks just queue)
            backend.num_workers = num_workers
        if hasattr(self.broker.cost_fn, "reset"):
            self.broker.cost_fn.reset()      # slot-keyed EMA: N changed
        self._build_steps()
        pop = constrain_pop(pop, self.ctx)
        if bool(jax.device_get(jnp.any(jnp.isinf(pop.fitness)))):
            pop = self._init_eval(pop)       # grow path: evaluate clones
            self.evals_host += self.cfg.global_pop
        return pop

    # ------------------------------------------------------------------
    def _drain(self, pending: list, history: list, keep: int = 0) -> None:
        """Blocking-read all but the newest `keep` pending epoch metrics
        into `history` (oldest first), and publish each epoch's counts."""
        m = _metrics.get_registry()
        while len(pending) > keep:
            ee, mm = pending.pop(0)
            with jax.profiler.TraceAnnotation("chambga.drain", epoch=ee):
                mm = jax.device_get(mm)
            sums = {k: int(np.sum(v))
                    for k, v in mm.get("fitness", {}).items()}
            rec = {"epoch": ee,
                   "best_per_island": np.asarray(mm["best"])[-1],
                   "best": float(np.min(mm["best"])),
                   "trace": np.asarray(mm["best"]),
                   "skew": float(np.mean(mm["skew"])),
                   "balanced": float(np.mean(mm.get("balanced", 0.0))),
                   **sums}
            history.append(rec)
            if m.enabled:
                m.inc("chambga_epochs_total")
                m.inc("chambga_evaluations_total", float(
                    self.cfg.generations_per_epoch * self.cfg.global_pop))
                for k, v in sums.items():
                    m.inc(f"chambga_{k}_total", float(v))
            if self.log_fn:
                self.log_fn(rec)

    def run(self, pop: Optional[Population] = None, *,
            epochs: Optional[int] = None,
            target: Optional[float] = None,
            wallclock_s: Optional[float] = None):
        """Run until an epoch/target/wall-clock limit. Returns
        (population, history) where history is a list of per-epoch dicts."""
        cfg = self.cfg
        if pop is None:
            pop = self.restore() or self.init()
        else:
            if self.evals_host == 0:
                # externally supplied population: seed the exact host
                # counter from the device value (exact until first wrap)
                self.evals_host = max(0, int(jax.device_get(pop.evals)))
            if self._donate:
                # first epoch_step donates its input; copy so the CALLER's
                # population survives (every later step donates
                # engine-internal buffers, so the aliasing win is kept for
                # the whole loop)
                pop = jax.tree_util.tree_map(jnp.copy, pop)
        epochs = epochs if epochs is not None else cfg.num_epochs
        history = []
        t0 = time.monotonic()
        pending = []                                   # in-flight metrics
        start_epoch = int(jax.device_get(pop.epoch))
        evals_per_epoch = (cfg.generations_per_epoch
                           * pop.genomes.shape[0] * pop.genomes.shape[1])

        for e in range(start_epoch, start_epoch + epochs):
            with jax.profiler.TraceAnnotation("chambga.dispatch", epoch=e):
                pop, metrics = self._epoch_step(pop)
            self.evals_host += evals_per_epoch         # exact, unbounded
            _start_host_copy(metrics)                  # non-blocking D2H
            pending.append((e, metrics))
            if (e + 1) % self.sync_every == 0:
                # keep `pipeline_depth` epochs in flight: the blocking read
                # of epoch e-depth overlaps device execution of epoch e.
                # With a target, drain fully so the check sees the newest
                # epoch and stops as early as the synchronous loop would.
                self._drain(pending, history,
                            keep=0 if target is not None
                            else self.pipeline_depth)
                if target is not None and history and \
                        history[-1]["best"] <= target:
                    break
            if self.checkpointer and self.checkpoint_every and \
                    (e + 1) % self.checkpoint_every == 0:
                self._save(pop, e + 1)
            if wallclock_s is not None and time.monotonic() - t0 > wallclock_s:
                break
        self._drain(pending, history, keep=0)
        if self.checkpointer and self.checkpoint_every:
            self._save(pop, int(jax.device_get(pop.epoch)))
        return pop, history

    def _save(self, pop: Population, epoch: int) -> None:
        with jax.profiler.TraceAnnotation("chambga.checkpoint", epoch=epoch):
            self.checkpointer.save(self._checkpoint_state(pop), step=epoch)

    def best(self, pop: Population):
        g, f = jax.device_get(best_of(pop))
        return np.asarray(g), np.asarray(f)
