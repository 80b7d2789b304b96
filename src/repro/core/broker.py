"""The TPU-native "message broker" (DESIGN.md §2).

The paper's RabbitMQ queue load-balances heterogeneous fitness evaluations
across a shared worker pool: any idle worker pulls the next individual.
TPU pods are SPMD, so dynamic pulling doesn't exist — instead the broker
computes a *static balanced assignment* from a per-individual cost model and
executes it as one permutation (a gather across the island/data sharding →
GSPMD lowers it to an all-to-all), evaluates, and routes results back with
the inverse permutation.

Dispatch is *total*: when ``N % num_workers != 0`` the broker pads the
batch up to the next multiple of W with sentinel-cost entries, so
cost-model balancing engages for every island/worker ratio. Padded lanes
evaluate a duplicate of genome 0 (at most W-1 wasted evaluations) and are
masked out of the load statistics and the result gather.

Balance guarantee: with costs sorted descending and snake (boustrophedon)
assignment over W equal-count bins, per-bin cost differs from optimal LPT
by at most one item per round — the same O(1/N) skew the shared queue
achieves dynamically. Sentinel pads sort last, so they fill the cheapest
slots of the final snake row.

For uniform costs (``cost_fn=None``) dispatch is the identity: zero
overhead, matching the paper's "minimal overhead" benchmark claim.

Evaluation itself is pluggable (the paper's decoupled "simulation backend"
microservice): a :class:`DispatchBackend` executes the shuffled batch.
:class:`InlineBackend` traces the fitness function into the caller's XLA
program (SPMD, zero copies); :class:`HostPoolBackend` bridges out of the
program with ``jax.pure_callback`` and fans chunks across a host executor
pool — for external / embedded simulators that cannot be traced.

Batch-scheduled dispatch (SLURM / Kubernetes)
---------------------------------------------
``repro.runtime.batchq`` adds the paper's K8s<->SLURM portability story:
:class:`~repro.runtime.batchq.SlurmArrayBackend` implements the same
:class:`DispatchBackend` protocol by *spooling* each evaluation batch to
disk and submitting it as array-job work items through a pluggable
``Scheduler`` — ``SlurmScheduler`` (``sbatch``/``squeue`` shell-outs),
``KubernetesScheduler`` (one indexed Job per batch via ``kubectl``), or a
``LocalMockScheduler``/``MockKubectl`` pair that runs chunks in
subprocesses/threads for CI. When the broker supplies a cost model, the
backend sizes chunks by predicted per-genome cost (largest-cost-first,
see ``hostbridge.cost_sized_chunk_sizes``) so array tasks finish
together instead of splitting the batch into equal counts.

Spool layout (one job directory per evaluate call)::

    <spool>/job_000042/
        payload.json               # num_objectives + fitness import spec
        fn.pkl                     # pickled fitness (when no import spec)
        chunk_0003_try0.npz        # input genomes for chunk 3, attempt 0
        chunk_0003_try0.result.npz # fitness + measured duration (atomic)
        chunk_0003_try0.fail       # traceback marker on worker failure

Both decoupled backends share :func:`run_chunks_retry`: every chunk is
submitted up front, waited on with a per-chunk timeout measured from
submission, and *re-queued* (a fresh attempt via the scheduler/pool) when
it straggles past the timeout or fails, up to ``max_retries`` times.

Cost-model learning: :class:`CostEMA` is a drop-in ``cost_fn`` that learns
an online EMA of measured per-lane wall times (reported by the decoupled
backends) and feeds them back into :func:`balanced_permutation` — the
ROADMAP's replacement for a static cost model.

``ga_run`` flags: ``--dispatch-backend slurm|slurm-mock|k8s|k8s-mock``
selects the batch-scheduled backend (real scheduler vs local mock),
``--spool-dir`` / ``--chunk-timeout-s`` / ``--keep-jobs`` tune the spool,
``--k8s-namespace`` / ``--k8s-image`` parameterize the Kubernetes Job
manifest, and ``--cost-ema`` enables the learned cost model (primed from
the fitness backend's static cost model when one exists).

Message-queue dispatch (persistent workers)
-------------------------------------------
``repro.runtime.mq`` goes beyond per-batch scheduling: a file-backed
broker directory holds a leased task queue with at-least-once delivery,
and a fleet of PERSISTENT workers — launched once per run (locally, or as
one long-lived SLURM array / K8s indexed Job through the same
``Scheduler`` protocol) — loops claim -> evaluate -> report, amortizing
startup across chunks and generations.
:class:`~repro.runtime.mq.QueueBackend` implements ``DispatchBackend`` on
top of it and *streams* results: each finished chunk's measured duration
is fed to :class:`CostEMA` mid-flight instead of at batch end, so the next
generation's dispatch sees sharpened estimates even under long tails
(``ga_run --dispatch-backend mq|mq-mock``, ``--mq-dir``, ``--lease-s``,
``--num-mq-workers``, ``--mq-fleet``).

The queue is MULTI-TENANT and ELASTIC: several concurrent GA runs (each
with its own ``Broker`` + ``QueueBackend``) can share one worker fleet —
task names are run-scoped, a ``runs/`` registry assigns claim priorities
(idle workers steal work from whichever run is loaded, highest priority
first), and per-run teardown/GC never touches another run's files
(``ga_run --mq-run-id``, ``--mq-priority``, a shared ``--mq-dir``).
``mq.FleetAutoscaler`` grows/shrinks the fleet from observed queue depth
(``ga_run --mq-autoscale MIN:MAX``). :meth:`Broker.backend_stats`
snapshots the backend's counters (jobs, retries, timeouts, lease
re-queues, streamed EMA updates) for benchmarks and run logs.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hostbridge import PureCallbackBridge, collect_chunk_results
from repro.runtime import metrics as _metrics


def padded_size(n: int, num_workers: int) -> int:
    """Smallest multiple of ``num_workers`` that is >= n."""
    return -(-n // num_workers) * num_workers


def balanced_permutation(cost: jax.Array, num_workers: int) -> jax.Array:
    """perm (Np,) with Np = padded_size(N, W), s.t. taking items in `perm`
    order and splitting into W contiguous equal chunks balances per-chunk
    total cost. Entries ``perm[j] >= N`` are padding (sentinel-cost slots
    that fill the partial final snake row); for N % W == 0 the result is an
    exact permutation of range(N), bit-identical to the historical
    behavior.
    """
    n = cost.shape[0]
    w = num_workers
    n_pad = padded_size(n, w)
    if n_pad != n:
        # sentinel pads: -inf cost sorts last under descending order, so
        # padding lands in the cheapest slots of the last snake row
        cost = jnp.concatenate(
            [cost, jnp.full((n_pad - n,), -jnp.inf, cost.dtype)])
    rows = n_pad // w
    order = jnp.argsort(-cost)                  # descending cost
    i = jnp.arange(n_pad)
    row, col = i // w, i % w
    worker = jnp.where(row % 2 == 0, col, w - 1 - col)     # snake
    dest = worker * rows + row
    perm = jnp.zeros((n_pad,), jnp.int32).at[dest].set(
        order.astype(jnp.int32))
    return perm


def padded_take(x: jax.Array, perm: jax.Array, n: int) -> jax.Array:
    """Gather rows of `x` (first n are real) in `perm` order; padded
    entries (perm[j] >= n) read row 0 — their results are dropped by the
    masked :func:`inverse_permutation` on the way back."""
    return jnp.take(x, jnp.where(perm < n, perm, 0), axis=0)


def inverse_permutation(perm: jax.Array, n: Optional[int] = None) -> jax.Array:
    """inv (n,) with inv[i] = slot of original item i in `perm`.

    `n` is the number of real items (defaults to len(perm)); padded
    entries ``perm[j] >= n`` are dropped from the scatter, so gathering
    results with `inv` never reads a padded lane.
    """
    n_pad = perm.shape[0]
    n = n_pad if n is None else n
    return jnp.zeros((n,), jnp.int32).at[perm].set(
        jnp.arange(n_pad, dtype=jnp.int32), mode="drop")


# ---------------------------------------------------------------------------
# Per-chunk timeout + retry (shared by every decoupled backend)
# ---------------------------------------------------------------------------

class ChunkFailure(RuntimeError):
    """A dispatched evaluation chunk failed (or straggled) beyond retry."""


def run_chunks_retry(chunks, submit: Callable, wait: Callable, *,
                     timeout_s: Optional[float] = None,
                     max_retries: int = 0,
                     on_retry: Optional[Callable] = None,
                     initial_tokens: Optional[list] = None) -> list:
    """Drive a set of evaluation chunks with per-chunk timeout + re-queue.

    All chunks are submitted up front (``submit(i, chunk, attempt) ->
    token``, or pass ``initial_tokens`` when attempt 0 was already
    batch-submitted — e.g. as one SLURM array job); each is then waited on
    (``wait(i, token, timeout_s) -> result``). How ``timeout_s`` is
    clocked is ``wait``'s choice — both backends count *execution* time
    only, so queue/PENDING time never reads as straggling. ``wait`` raises
    ``TimeoutError`` for stragglers or any other exception for failed
    chunks, and the chunk is re-queued via a fresh ``submit`` up to
    ``max_retries`` times. Shared by
    :class:`HostPoolBackend` (executor futures) and
    :class:`~repro.runtime.batchq.SlurmArrayBackend` (spool polling), so
    both get identical straggler semantics.
    """
    tokens = (list(initial_tokens) if initial_tokens is not None
              else [submit(i, c, 0) for i, c in enumerate(chunks)])
    attempts = [0] * len(chunks)
    results = [None] * len(chunks)
    for i, chunk in enumerate(chunks):
        while True:
            try:
                token = tokens[i]
                if isinstance(token, _FailedSubmit):
                    raise token.exc          # count against the budget
                results[i] = wait(i, token, timeout_s)
                break
            except Exception as exc:
                attempts[i] += 1
                if attempts[i] > max_retries:
                    raise ChunkFailure(
                        f"chunk {i}/{len(chunks)} failed after "
                        f"{attempts[i]} attempt(s): {exc!r}") from exc
                if on_retry is not None:
                    on_retry(i, attempts[i], exc)
                try:
                    tokens[i] = submit(i, chunk, attempts[i])
                except Exception as submit_exc:
                    # a failing re-queue (e.g. transient sbatch error) is
                    # just another failed attempt, not an abort
                    tokens[i] = _FailedSubmit(submit_exc)
    return results


class _FailedSubmit:
    """Token marking a re-queue whose submission itself failed."""

    def __init__(self, exc: Exception):
        self.exc = exc


# ---------------------------------------------------------------------------
# Online cost-model learning
# ---------------------------------------------------------------------------

class CostEMA:
    """Learned cost model: an online EMA of measured per-lane wall times.

    Drop-in ``cost_fn`` for :class:`Broker`. Estimates are keyed by batch
    slot: slot ``i`` of the flattened ``(I*P)`` batch belongs to island
    ``i // P``, so island- and slot-level cost structure (e.g. one
    island's HVDC region needing more contingency solves) persists across
    generations even as individual genomes change.

    The decoupled backends measure each chunk's wall time on the worker
    (``HostPoolBackend`` / ``SlurmArrayBackend``) and call
    :meth:`observe` with the dispatch permutation, attributing
    ``duration / chunk_size`` to every real slot in the chunk. The traced
    ``__call__`` reads the current table through ``jax.pure_callback``, so
    each generation's :func:`balanced_permutation` sees fresh estimates
    without retracing. Requires a decoupled backend — inline SPMD
    evaluation exposes no per-lane timings.

    Cold start: by default the table initializes to a uniform
    ``init_cost``, so the first dispatch of a skewed workload is maximally
    unbalanced. ``prime_fn`` (a static, traceable cost model ``(N, G) ->
    (N,)``) seeds the slot table from its prediction on the first batch
    instead (ROADMAP "CostEMA priming"); measured wall times then refine
    it online.
    """

    def __init__(self, alpha: float = 0.25, init_cost: float = 1.0,
                 prime_fn: Optional[Callable] = None):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1]: {alpha}")
        self.alpha = float(alpha)
        self.init_cost = float(init_cost)
        self.prime_fn = prime_fn
        self._est: Optional[np.ndarray] = None
        self._lock = threading.Lock()
        self.updates = 0

    def snapshot(self, n: int, prime: Optional[np.ndarray] = None) -> np.ndarray:
        """Current (n,) cost estimates. A cold (or re-keyed after resize)
        table initializes from ``prime`` when given, else to uniform
        ``init_cost``."""
        with self._lock:
            if self._est is None or self._est.shape[0] != int(n):
                if prime is not None:
                    # explicit copy: the prediction arrives as jax's
                    # read-only callback buffer, and observe() writes here
                    self._est = np.array(prime, np.float32,
                                         copy=True).reshape(int(n))
                else:
                    self._est = np.full((int(n),), self.init_cost,
                                        np.float32)
            return self._est.copy()

    def observe(self, perm, chunk_sizes, durations) -> None:
        """Fold measured per-chunk wall times back into the estimates.

        perm: the (padded) dispatch permutation the chunks were taken
        from; entries ``>= n`` (sentinel pads) are skipped. Every real
        slot in chunk ``w`` is charged ``durations[w] / chunk_sizes[w]``.
        """
        perm = np.asarray(perm)
        with self._lock:
            if self._est is None:
                return                      # no reader yet — nothing keyed
            n = self._est.shape[0]
            a = self.alpha
            off = 0
            for size, dur in zip(chunk_sizes, durations):
                idx = perm[off:off + size]
                off += size
                idx = idx[idx < n]
                if idx.size:
                    per_item = np.float32(dur / max(size, 1))
                    self._est[idx] = ((1.0 - a) * self._est[idx]
                                      + a * per_item)
            self.updates += 1
            est = self._est
        m = _metrics.get_registry()
        if m.enabled:
            # per-slot costs, summarized: full per-slot label
            # cardinality would blow the registry's series cap on any
            # real population, so exporters get the distribution shape
            m.inc("cost_ema_updates_total")
            m.set_gauge("cost_ema_mean_seconds", float(est.mean()))
            m.set_gauge("cost_ema_max_seconds", float(est.max()))
            m.set_gauge("cost_ema_min_seconds", float(est.min()))

    def reset(self) -> None:
        """Drop learned state (e.g. after an elastic resize re-keys
        slots)."""
        with self._lock:
            self._est = None

    def __call__(self, genomes: jax.Array) -> jax.Array:
        n = genomes.shape[0]
        shape = jax.ShapeDtypeStruct((n,), jnp.float32)
        # genomes as operand: orders the read after the previous
        # generation's evaluate (whose observe() updated the table)
        if self.prime_fn is not None:
            # the prediction is computed on-device every generation and
            # consumed only by cold reads — deliberate: evaluating a
            # (jax-traceable) cost model from INSIDE the host callback is
            # unsupported reentrancy, and the steady-state overhead is one
            # (N,) f32 transfer per generation
            pred = self.prime_fn(genomes)
            return jax.pure_callback(
                lambda g, p: self.snapshot(g.shape[0], p), shape,
                genomes, pred)
        return jax.pure_callback(
            lambda g: self.snapshot(g.shape[0]), shape, genomes)


# ---------------------------------------------------------------------------
# Dispatch backends — the paper's pluggable "simulation backend" container
# ---------------------------------------------------------------------------

@runtime_checkable
class DispatchBackend(Protocol):
    """Executes a (possibly shuffled/padded) genome batch: (N, G) -> (N, O)."""

    name: str

    def __call__(self, genomes: jax.Array) -> jax.Array: ...


class InlineBackend:
    """SPMD inline evaluation: the fitness function is traced into the
    caller's jitted program. Zero dispatch overhead; the fitness itself may
    be model-axis sharded (vertical scaling)."""

    name = "inline"

    def __init__(self, fitness_fn: Callable):
        self.fitness_fn = fitness_fn

    def __call__(self, genomes: jax.Array) -> jax.Array:
        return self.fitness_fn(genomes)

    def evaluate_with_stats(self, genomes: jax.Array) -> Tuple[jax.Array, dict]:
        """(fitness, the fitness's own batch sums): a fitness that counts
        its work (``evaluate_with_stats``, e.g. the HVDC fitness's Newton
        iterations) hands the counts on; any other gives none."""
        fn = getattr(self.fitness_fn, "evaluate_with_stats", None)
        if fn is None:
            return self.fitness_fn(genomes), {}
        return fn(genomes)


def _timed_eval(fn: Callable, chunk: np.ndarray):
    """Evaluate one chunk, returning (fitness, wall_seconds). Module-level
    so process pools can pickle it alongside a picklable ``fn``."""
    t0 = time.perf_counter()
    out = np.asarray(fn(chunk), np.float32).reshape(len(chunk), -1)
    return out, time.perf_counter() - t0


def _host_worker_init() -> None:
    """Process-pool initializer: hold the worker's JAX to the CPU before
    it evaluates a fitness — the accelerator belongs to the manager.
    Importing this module already imported jax, so the config is set as
    well as the environment (inherited by the worker's own children)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


class HostPoolBackend(PureCallbackBridge):
    """Decoupled evaluation on a host executor pool via ``pure_callback``.

    For external / embedded simulators (subprocess powerflow binaries,
    non-JAX models) that cannot be traced into XLA. The batch is split into
    ``num_workers`` chunks, each submitted to the pool; the callback blocks
    until all chunks return — the device program sees one opaque op.

    executor: "thread" (default; any callable) or "process" (true
    parallelism for GIL-bound python simulators; ``fitness_fn`` must be
    picklable, i.e. a module-level function or callable instance).
    Process pools use the *spawn* start method and are created eagerly at
    construction: forking lazily from inside a running XLA host callback
    deadlocks (the forked child inherits the runtime's held locks).

    Hardening: ``chunk_timeout_s`` bounds each chunk's *execution* wall
    time (time queued behind a full pool does not count); a straggling or
    failed chunk is re-submitted to the pool up to ``max_retries`` times
    (speculative re-queue — a hung worker thread keeps its slot, the
    retry races it). ``close()`` *drains*
    in-flight callbacks before shutting the pool down — the engine's
    pipelined epoch loop can still have a ``pure_callback`` executing when
    the caller tears the backend down — and the class is a context
    manager. ``cost_ema`` (a :class:`CostEMA`) receives measured per-chunk
    wall times when the broker dispatches with a permutation.
    """

    name = "host-pool"

    def __init__(self, fitness_fn: Callable, *, num_objectives: int = 1,
                 num_workers: int = 4, executor: str = "thread",
                 chunk_timeout_s: Optional[float] = None,
                 max_retries: int = 2,
                 cost_ema: Optional[CostEMA] = None):
        if executor not in ("thread", "process"):
            raise ValueError(f"executor must be thread|process: {executor}")
        self.fitness_fn = fitness_fn
        self.num_objectives = num_objectives
        self.num_workers = max(1, num_workers)
        self.executor = executor
        self.chunk_timeout_s = chunk_timeout_s
        self.max_retries = max_retries
        self.cost_ema = cost_ema
        self.stats = {"retries": 0}
        self._cond = threading.Condition()
        self._inflight = 0
        self._closing = False
        # eager pool creation — lazy init inside the host callback would
        # race under the engine's pipelined epoch loop (two in-flight
        # callbacks), and forking from a running XLA callback deadlocks
        import concurrent.futures as cf
        if executor == "thread":
            self._pool = cf.ThreadPoolExecutor(max_workers=self.num_workers)
        else:
            import multiprocessing as mp
            self._pool = cf.ProcessPoolExecutor(
                max_workers=self.num_workers,
                mp_context=mp.get_context("spawn"),
                initializer=_host_worker_init)

    def _host_eval(self, genomes: np.ndarray,
                   perm: Optional[np.ndarray] = None,
                   cost: Optional[np.ndarray] = None) -> np.ndarray:
        # `cost` (predicted per-slot cost) is accepted for protocol parity
        # with the batch-scheduled backend but unused here: this path keeps
        # equal splits (cost-sized chunking lives in SlurmArrayBackend,
        # where every chunk is a separately scheduled array task)
        with self._cond:
            if self._closing or self._pool is None:
                raise RuntimeError("HostPoolBackend used after close()")
            self._inflight += 1
            pool = self._pool
        try:
            n = genomes.shape[0]
            chunks = np.array_split(genomes,
                                    min(self.num_workers, max(1, n)))

            def submit(i, chunk, attempt):
                return pool.submit(_timed_eval, self.fitness_fn, chunk)

            def wait(i, fut, timeout_s):
                if timeout_s is None:
                    return fut.result()
                # the straggler clock starts when the chunk begins
                # executing — time spent queued behind a full pool (e.g.
                # after resize() raised num_workers past the pool size)
                # must not count as straggling
                while not (fut.running() or fut.done()):
                    time.sleep(0.005)
                return fut.result(timeout=timeout_s)

            def on_retry(i, attempt, exc):
                # two pipelined _host_eval threads can retry at once
                with self._cond:
                    self.stats["retries"] += 1

            outs = run_chunks_retry(chunks, submit, wait,
                                    timeout_s=self.chunk_timeout_s,
                                    max_retries=self.max_retries,
                                    on_retry=on_retry)
            return collect_chunk_results(outs, self.cost_ema, perm,
                                         [len(c) for c in chunks])
        finally:
            with self._cond:
                self._inflight -= 1
                self._cond.notify_all()

    def stats_snapshot(self) -> dict:
        """Consistent copy of the counters — increments run under
        ``self._cond``'s lock, so read under it too."""
        with self._cond:
            return dict(self.stats)

    def close(self):
        """Drain in-flight host callbacks, then shut the pool down. Safe
        to call more than once. The drain guarantees every result anyone
        is waiting on has been delivered; shutdown then does NOT join the
        worker threads — a truly hung simulator thread (abandoned by a
        timed-out chunk whose retry won the race) would block close()
        forever."""
        with self._cond:
            if self._pool is None:
                return
            self._closing = True
            while self._inflight:
                self._cond.wait()
            pool, self._pool = self._pool, None
        pool.shutdown(wait=False)


# ---------------------------------------------------------------------------
# Broker
# ---------------------------------------------------------------------------

class Broker:
    """Shared-pool evaluation dispatcher.

    fitness_fn: (N, G) -> (N, O)  (may itself be model-axis sharded =
                vertical scaling); ignored if `backend` is given
    cost_fn:    (N, G) -> (N,) predicted evaluation cost, or None (uniform)
    num_workers: number of horizontal lanes (defaults to dp shards)
    backend:    DispatchBackend executing the shuffled batch
                (default: InlineBackend(fitness_fn))
    """

    def __init__(self, fitness_fn: Optional[Callable] = None,
                 cost_fn: Optional[Callable] = None,
                 num_workers: int = 1,
                 backend: Optional[DispatchBackend] = None):
        if backend is None:
            if fitness_fn is None:
                raise ValueError("need fitness_fn or backend")
            backend = InlineBackend(fitness_fn)
        self.backend = backend
        self.fitness_fn = fitness_fn or getattr(backend, "fitness_fn", None)
        self.cost_fn = cost_fn
        self.num_workers = max(1, num_workers)
        # learned cost model: wire the EMA into a decoupled backend that
        # can report measured per-chunk wall times back to it
        if (isinstance(cost_fn, CostEMA)
                and hasattr(backend, "cost_ema")
                and getattr(backend, "cost_ema") is None):
            backend.cost_ema = cost_fn

    def backend_stats(self) -> dict:
        """Snapshot of the dispatch backend's host-side counters — jobs,
        retries, timeouts, lease re-queues, streamed EMA updates, pruned
        jobs, whatever the backend keeps (empty for backends that keep
        none, e.g. inline SPMD). Returns a copy: safe to mutate, and
        stable while in-flight evaluations keep counting. Every shipped
        backend (HostPool, slurm-array batch, mq) exposes a locked
        ``stats_snapshot`` and is read through it — a direct
        ``self.stats`` dict read from the manager thread is a latent
        race under concurrent increments; the raw fallback exists only
        for foreign backends without one. A fleet autoscaled by the mq
        backend contributes its own snapshot under ``autoscaler_*``
        keys (same locked-read contract)."""
        snap = getattr(self.backend, "stats_snapshot", None)
        stats = snap() if snap is not None \
            else dict(getattr(self.backend, "stats", None) or {})
        scaler = getattr(self.backend, "autoscaler", None)
        if scaler is not None:
            for k, v in scaler.stats_snapshot().items():
                stats[f"autoscaler_{k}"] = v
        return stats

    def _identity_stats(self) -> dict:
        one = jnp.ones(())
        return {"skew": one, "naive_skew": one, "balanced": jnp.zeros(()),
                "padded": jnp.zeros((), jnp.int32)}

    def _backend_eval(self, genomes: jax.Array) -> Tuple[jax.Array, dict]:
        with jax.named_scope("chambga.fitness"):
            fn = getattr(self.backend, "evaluate_with_stats", None)
            if fn is None:
                return self.backend(genomes), {}
            return fn(genomes)

    def evaluate(self, genomes: jax.Array) -> Tuple[jax.Array, dict]:
        """genomes: (N, G) -> (fitness (N, O), dispatch stats).

        Total: cost-balanced dispatch applies for EVERY N/num_workers
        combination when a cost model is given (no silent identity
        fallback); padding absorbs N % W != 0.

        ``stats["fitness"]`` holds the inline fitness's own batch sums
        (empty where the fitness or backend keeps none); padded lanes are
        evaluated, so they count. Profiler scopes: the backend call is
        ``chambga.fitness``; the cost model, permutation, gather and
        inverse are ``chambga.dispatch``.
        """
        n = genomes.shape[0]
        w = self.num_workers
        if self.cost_fn is None or w <= 1:
            fit, fstats = self._backend_eval(genomes)
            return fit, dict(self._identity_stats(), fitness=fstats)
        with jax.named_scope("chambga.dispatch"):
            cost = self.cost_fn(genomes)
            perm = balanced_permutation(cost, w)            # (Np,)
            n_pad = perm.shape[0]
            real = perm < n                                 # pad mask
            shuffled = padded_take(genomes, perm, n)        # the "all-to-all"
            # predicted per-slot cost in shuffled order (pads carry zero)
            lane_cost = jnp.where(real, padded_take(cost, perm, n), 0.0)
        fstats = {}
        if hasattr(self.backend, "eval_with_perm"):
            # decoupled backend: `perm` keys measured per-chunk wall times
            # back into the EMA cost model, and the cost operand drives
            # cost-sized chunking (array tasks finish together). Sentinel
            # pads are marked -inf — NOT their zero stats-cost: a pad slot
            # re-evaluates a duplicate of genome 0 at its true price, so a
            # cost-sizing backend must identify pads (it skips them — their
            # results are dropped by the masked inverse anyway), not
            # mistake them for free work
            pad_marked = jnp.where(real, lane_cost, -jnp.inf)
            with jax.named_scope("chambga.fitness"):
                fit_shuf = self.backend.eval_with_perm(shuffled, perm,
                                                       pad_marked)
        else:
            fit_shuf, fstats = self._backend_eval(shuffled)
        with jax.named_scope("chambga.dispatch"):
            inv = inverse_permutation(perm, n)
            fit = jnp.take(fit_shuf, inv, axis=0)
        # stats: per-worker predicted load skew (max/mean), before/after;
        # padded lanes contribute zero load
        loads = jnp.sum(lane_cost.reshape(w, n_pad // w), axis=1)
        cost_pad = (cost if n_pad == n else
                    jnp.concatenate([cost, jnp.zeros((n_pad - n,),
                                                     cost.dtype)]))
        naive = jnp.sum(cost_pad.reshape(w, n_pad // w), axis=1)
        stats = {
            "skew": jnp.max(loads) / jnp.maximum(jnp.mean(loads), 1e-9),
            "naive_skew": jnp.max(naive) / jnp.maximum(jnp.mean(naive), 1e-9),
            "balanced": jnp.ones(()),
            "padded": jnp.full((), n_pad - n, jnp.int32),
            "fitness": fstats,
        }
        return fit, stats
