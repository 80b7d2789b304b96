"""GA optimization driver — the paper's main entrypoint (CHAMB-GA Fig. 1).

Selects a fitness backend (benchmark function / HVDC powerflow / LM
hyperparameter search), builds the scaling plan, and runs the island-model
engine with checkpointing.

Usage:
  PYTHONPATH=src python -m repro.launch.ga_run --fitness rastrigin \
      --genes 8 --islands 4 --pop 48 --epochs 20
  PYTHONPATH=src python -m repro.launch.ga_run --fitness hvdc \
      --grid-size 60 --epochs 10
  PYTHONPATH=src python -m repro.launch.ga_run --fitness lm --epochs 3
  # batch-scheduled simulation backend (SLURM array jobs; use slurm-mock
  # to exercise the same spool path on local subprocesses)
  PYTHONPATH=src python -m repro.launch.ga_run --fitness sphere \
      --dispatch-backend slurm --slurm-partition compute --cost-ema
  # the same workload on Kubernetes indexed Jobs (k8s-mock runs the
  # identical spool path against an in-process kubectl, no cluster)
  PYTHONPATH=src python -m repro.launch.ga_run --fitness sphere \
      --dispatch-backend k8s --k8s-namespace ga --k8s-image my/worker:1
  # persistent-worker message queue: the fleet starts once and streams
  # results (mq-mock drives the same queue on in-process threads)
  PYTHONPATH=src python -m repro.launch.ga_run --fitness sphere \
      --dispatch-backend mq --mq-fleet slurm --num-mq-workers 16 \
      --cost-ema
"""
from __future__ import annotations

import argparse
import contextlib
import os

import jax
import numpy as np

SCHEDULERS_HELP = """\
Schedulers (--dispatch-backend slurm|slurm-mock|k8s|k8s-mock):
  Both batch backends spool each evaluation batch to --spool-dir and
  submit the chunks through the Scheduler protocol; only the scheduler
  object differs (the paper's K8s<->SLURM portability claim).
    slurm      one `sbatch --array` job per batch; task i resolves its
               chunk from a manifest by $SLURM_ARRAY_TASK_ID. scancel
               cancels a single timed-out array task.
    k8s        one indexed Job per batch (completionMode=Indexed); pod i
               resolves its chunk by $JOB_COMPLETION_INDEX. K8s cannot
               cancel one index, so a timed-out chunk's re-queued attempt
               races the original (speculative retry); Job objects are
               deleted once results are collected.
    slurm-mock / k8s-mock
               same spool/poll/retry path against local workers (no
               cluster needed) — CI and smoke runs.
  Scheduler states: pending (queued; the straggler clock does NOT run),
  running, done, failed, unknown. Results always travel via the spool's
  chunk_*.result.npz files, never the scheduler — the spool must be a
  filesystem shared with the workers (SLURM: cluster FS; K8s: a volume
  mounted at the same path in every worker pod). Completed job_* spool
  dirs are pruned down to --keep-jobs; chunks are sized by predicted
  per-genome cost whenever a cost model is active (equal counts
  otherwise); chunks predicted cheaper than --min-chunk-cost-s are
  folded into a neighbor instead of paying a full task startup.

Message queue (--dispatch-backend mq|mq-mock):
  The paper's central broker as a persistent subsystem: --mq-dir holds a
  file-backed task queue + result queue (same shared-volume contract as
  the batch spool), and a fleet of PERSISTENT workers — launched once,
  not per batch — loops claim -> evaluate -> report, amortizing
  interpreter startup and fitness resolution across every chunk of every
  generation. Delivery is at-least-once: a worker claims a task by
  atomic rename and heartbeats a lease while evaluating; the manager
  re-queues any task whose lease goes stale for --lease-s (dead-worker
  liveness, no retry budget consumed) and keeps --chunk-timeout-s as the
  backstop for live-but-stuck workers (same retry semantics as the batch
  backends). Results are consumed as a stream: each finished chunk's
  measured duration reaches the --cost-ema model mid-flight, before the
  batch's stragglers land.
    mq         persistent workers; the fleet is --mq-fleet local (numpy
               subprocesses on this host), slurm / k8s — ONE long-lived
               array job / indexed Job submitted through the same
               Scheduler protocol via *.worker.json tickets — or
               external: attach to a fleet another invocation owns (see
               Fleet sharing below).
    mq-mock    in-process thread workers — CI and smoke runs.
  --num-mq-workers sizes the fleet (default: the dispatch lane count).
  The broker directory stays bounded: completed jobs are reduced to
  their winning result files and swept beyond --keep-jobs, stale leases
  of killed workers included — and the sweep is run-aware: it never
  touches another run's files in a shared directory.

Network transport (--dispatch-backend mq-net):
  The SAME queue contract as mq — cross-run priority claims, leases
  with delivery-bump re-queue, at-least-once delivery, first-result-
  wins, run-scoped GC — but spoken to a TCP broker SERVICE instead of
  a shared directory: the paper's central message broker as a
  standalone microservice. No shared volume anywhere; workers hold one
  persistent connection each, task payloads arrive in the claim reply,
  and results stream back inline as length-prefixed frames.

    # broker service (prints its bound address)
    python -m repro.runtime.netbroker --serve --port 7077
    # workers, anywhere with a route to the broker
    python -m repro.runtime.netbroker --worker --broker-addr host:7077
    # managers, sharing the fleet exactly like Fleet sharing below
    ga_run --fitness sphere --dispatch-backend mq-net \\
        --broker-addr host:7077 --mq-priority 10

  Without --broker-addr the run is self-contained: an in-process
  server plus thread workers (CI / single box). Failure semantics: a
  connection dropped mid-frame never corrupts queue state — a torn
  RESULT frame is discarded whole by the server and the chunk is
  re-queued via lease expiry; workers reconnect and resume claiming
  with no duplicate winner; lease age is measured on the server's
  clock, so manager/worker clock skew cannot fake a stale lease. The
  broker's state is private to the server process: if the server dies,
  managers fail their chunks through the normal retry budget. Prefer
  the file broker (mq) when a durable shared volume exists and no
  extra service is wanted; prefer mq-net for cloud deployments without
  a shared filesystem and for large fleets, where every claim/
  heartbeat/result is one TCP round-trip instead of a shared-FS
  metadata op. --mq-autoscale is file-broker only (poison-ticket
  scale-down); --mq-dir does not apply. The conformance suite and the
  protocol replay corpus run against BOTH transports
  (tests/backend_conformance.py, tests/test_proto_replay.py).

Fleet sharing (multi-tenant message queue):
  Several GA runs — parameter sweeps, the meta-GA, multi-stage HVDC
  workflows — can share ONE persistent worker fleet. Every run registers
  itself (--mq-run-id, --mq-priority) in the broker directory's runs/
  registry, its task names are run-scoped, and idle workers steal work
  across runs: the highest-priority run's oldest task is always claimed
  first. Teardown is per-run — a finishing run deregisters and sweeps
  only its own files; the fleet-wide STOP sentinel is raised only by the
  invocation that owns the fleet. Two-terminal example:

    # terminal 1: launch the fleet AND run at high priority
    ga_run --fitness sphere --dispatch-backend mq \\
        --mq-dir /shared/broker --num-mq-workers 8 --mq-priority 10
    # terminal 2: attach to the same fleet at low priority
    ga_run --fitness rastrigin --dispatch-backend mq \\
        --mq-fleet external --mq-dir /shared/broker --mq-priority 1

  (the fleet-owning invocation should outlive attached ones; for a
  standalone fleet, start workers directly:
  python -m repro.runtime.mq --worker --mq-dir /shared/broker)

  --mq-autoscale MIN:MAX makes the owned fleet ELASTIC: a manager-side
  controller watches queue depth + lease counts, grows the fleet toward
  MAX while tasks queue (incremental Scheduler submit — one more sbatch
  --array / kubectl apply round-trip), and shrinks it back to MIN on
  drain by dropping poison STOP tickets that idle workers honor at
  chunk boundaries (never mid-evaluation, never ahead of queued work).
  --mq-autoscale-signal picks what the controller scales ON:
    depth      raw outstanding task count (ready + leased) against
               one-task-per-worker backlog — the default.
    cost       predicted outstanding COST: (ready + leased) x the
               streaming per-task cost EMA, provisioned to drain
               within a horizon, plus measured worker utilization —
               eight 10ms tasks and eight 10s tasks are the same
               depth but very different fleets. Decision inputs are
               read from the metrics bus (see Observability), so
               enabling --metrics-dir/--events-log also records every
               decision with its inputs.

Observability (--metrics-dir / --metrics-port / --events-log):
  Off by default and zero-cost when off (the runtime publishes through
  a no-op seam; nothing under runtime/ imports repro.obs). Any of the
  three flags installs the metrics bus (repro.obs.MetricsRegistry):
  queue depth and lease counts per run, claim latency, chunk-duration
  histograms, worker busy/idle utilization, per-task cost EMA, and
  autoscaler decisions, from every dispatch backend that emits them.
  The engine and the compiler add, for every run:
    chambga_epochs_total, chambga_evaluations_total
               epochs drained and the evaluations they ran
    chambga_newton_iterations_total, chambga_newton_solves_total,
    chambga_newton_unconverged_total
               (--fitness hvdc) base-case Newton solves, the iterations
               they ran (each solve stops at convergence), and the
               solves left above tolerance
    chambga_compile_seconds_total{fun=...,phase=trace|lower|compile}
               seconds spent compiling, per jitted function
    chambga_compile_cache_hits_total
               compiles served by the persistent compilation cache
    --metrics-dir DIR   publish DIR/chambga.prom atomically every ~2s
               (Prometheus textfile exposition — point a node-exporter
               textfile collector, or this repo's terminal dashboard,
               at it: python -m repro.obs --dashboard --metrics-dir DIR)
    --metrics-port P    serve http://127.0.0.1:P/metrics from a stdlib
               http.server thread (cloud runs; 0 picks a free port)
    --events-log FILE   append every structured event (enqueue/claim/
               publish/lease_requeue/retry/autoscale/...) as one JSON
               line; replay queue depth over time with
               python -m repro.obs --dashboard --events-log FILE
  python -m repro.obs --grafana-out FILE writes an import-ready
  Grafana dashboard JSON over the exported metric families.
"""

from repro.configs.base import GAConfig
from repro.core.engine import GAEngine
from repro.core.scaling import plan_scaling
from repro.checkpoint import Checkpointer
from repro.launch.compile_cache import enable_compile_cache


def build(fitness_name: str, args):
    """(GAConfig, fitness_fn, cost_fn) for a backend.

    fitness_fn is returned UNJITTED: the inline backend traces it into the
    jitted epoch step anyway, and the host-pool backends need the raw
    (picklable for --dispatch-backend host-process) callable."""
    cost_fn = None
    if fitness_name in ("rastrigin", "sphere", "rosenbrock", "ackley",
                        "griewank"):
        from repro.fitness import get_benchmark
        fn = get_benchmark(fitness_name)
        cfg = GAConfig(num_genes=args.genes, pop_per_island=args.pop,
                       num_islands=args.islands,
                       generations_per_epoch=args.gens_per_epoch,
                       num_epochs=args.epochs, lower=-5.12, upper=5.12,
                       mutation_prob=0.7, mutation_eta=20.0,
                       crossover_prob=0.9, crossover_eta=15.0,
                       seed=args.seed)
        return cfg, fn, cost_fn
    if fitness_name == "hvdc":
        from repro.fitness.powerflow import HVDCDispatchFitness
        from repro.powerflow.grid import make_synthetic_grid
        n = args.grid_size
        grid = make_synthetic_grid(
            n_bus=n, n_line=int(n * 1.97), n_gen=max(4, n // 4),
            n_hvdc=args.hvdc_lines, seed=args.seed)
        fit = HVDCDispatchFitness(grid, contingencies=args.contingencies,
                                  screen_top_k=args.screen_top_k)
        cfg = GAConfig(num_genes=grid.n_hvdc, pop_per_island=args.pop,
                       num_islands=args.islands,
                       generations_per_epoch=args.gens_per_epoch,
                       num_epochs=args.epochs, lower=-1.0, upper=1.0,
                       mutation_prob=0.7, mutation_eta=34.6,   # paper Tab. 3
                       crossover_prob=1.0, crossover_eta=97.5,
                       seed=args.seed)
        return cfg, fit, fit.cost_model()
    if fitness_name == "lm":
        from repro.fitness.lm import LMTrainFitness, NUM_LM_GENES
        fit = LMTrainFitness(args.lm_arch, steps=args.lm_steps)
        cfg = GAConfig(num_genes=NUM_LM_GENES, pop_per_island=args.pop,
                       num_islands=args.islands,
                       generations_per_epoch=args.gens_per_epoch,
                       num_epochs=args.epochs, lower=0.0, upper=1.0,
                       mutation_prob=0.5, mutation_eta=20.0,
                       crossover_prob=0.9, crossover_eta=15.0,
                       fused_operators=False, seed=args.seed)
        return cfg, fit, cost_fn
    raise ValueError(fitness_name)


def main(argv=None):
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=SCHEDULERS_HELP)
    ap.add_argument("--fitness", default="rastrigin")
    ap.add_argument("--genes", type=int, default=8)
    ap.add_argument("--islands", type=int, default=4)
    ap.add_argument("--pop", type=int, default=32)
    ap.add_argument("--gens-per-epoch", type=int, default=5)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grid-size", type=int, default=60)
    ap.add_argument("--hvdc-lines", type=int, default=4)
    ap.add_argument("--contingencies", type=int, default=0)
    ap.add_argument("--screen-top-k", type=int, default=0)
    ap.add_argument("--lm-arch", default="tinyllama-1.1b")
    ap.add_argument("--lm-steps", type=int, default=6)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--wallclock-s", type=float, default=None)
    ap.add_argument("--dispatch-backend", default="inline",
                    choices=("inline", "host-thread", "host-process",
                             "slurm", "slurm-mock", "k8s", "k8s-mock",
                             "mq", "mq-mock", "mq-net"),
                    help="inline: fitness traced into the XLA program; "
                         "host-*: decoupled simulation backend on a host "
                         "executor pool (external/embedded simulators); "
                         "slurm: batch-scheduled array jobs via sbatch; "
                         "k8s: Kubernetes indexed Jobs via kubectl; "
                         "mq: persistent-worker message queue (leased "
                         "tasks, streaming results; see Message queue "
                         "below); mq-net: the same queue contract over a "
                         "TCP broker service — no shared volume (see "
                         "Network transport below); *-mock: same path on "
                         "local workers (no cluster needed; see "
                         "Schedulers below)")
    ap.add_argument("--num-workers", type=int, default=None,
                    help="broker dispatch lanes (default: dp shards)")
    ap.add_argument("--spool-dir", default=None,
                    help="batch-dispatch spool directory (slurm backends; "
                         "default: a fresh temp dir)")
    ap.add_argument("--chunk-timeout-s", type=float, default=None,
                    help="per-chunk straggler timeout for decoupled "
                         "backends, clocked on execution time (re-queued "
                         "up to 2 times); 0 disables, default: none for "
                         "host-*, 300 for slurm*")
    ap.add_argument("--slurm-partition", default=None,
                    help="sbatch partition for --dispatch-backend slurm")
    ap.add_argument("--k8s-namespace", default="default",
                    help="namespace for --dispatch-backend k8s Jobs")
    ap.add_argument("--k8s-image", default="chambga-worker:latest",
                    help="worker container image for --dispatch-backend "
                         "k8s (must bundle repro + mount the spool)")
    ap.add_argument("--keep-jobs", type=int, default=4,
                    help="completed job_* spool directories kept per "
                         "batch backend (older ones are pruned; -1 "
                         "disables pruning); for mq backends, completed "
                         "queue jobs kept before their files are swept")
    ap.add_argument("--min-chunk-cost-s", type=float, default=0.0,
                    help="fold cost-sized chunks predicted cheaper than "
                         "this into a neighbor (a tiny chunk still pays "
                         "a full task startup); 0 disables")
    ap.add_argument("--mq-dir", default=None,
                    help="message-queue broker directory (mq backends; "
                         "default: a fresh temp dir). Must be a shared "
                         "volume reachable by every worker; point several "
                         "invocations at the same directory to share one "
                         "fleet (see Fleet sharing below)")
    ap.add_argument("--broker-addr", default=None, metavar="HOST:PORT",
                    help="socket broker server address (mq-net backend; "
                         "start one with `python -m "
                         "repro.runtime.netbroker --serve` and its "
                         "workers with `--worker --broker-addr`). "
                         "Default: a self-contained in-process server "
                         "plus thread workers (see Network transport "
                         "below)")
    ap.add_argument("--lease-s", type=float, default=15.0,
                    help="mq task lease: workers heartbeat at lease/4; "
                         "the manager re-queues tasks whose lease goes "
                         "stale this long (dead-worker liveness)")
    ap.add_argument("--num-mq-workers", type=int, default=None,
                    help="persistent mq fleet size (default: the "
                         "dispatch lane count)")
    ap.add_argument("--mq-fleet", default="local",
                    choices=("local", "slurm", "k8s", "external"),
                    help="how --dispatch-backend mq gets its persistent "
                         "fleet: local numpy subprocesses, ONE long-lived "
                         "SLURM array / K8s indexed Job through the "
                         "Scheduler protocol, or external — attach to a "
                         "shared fleet another invocation owns")
    ap.add_argument("--mq-run-id", default=None,
                    help="run id namespacing this run's tasks in a "
                         "(possibly shared) broker directory — lowercase "
                         "alphanumerics and dashes; default: a generated "
                         "unique id")
    ap.add_argument("--mq-priority", type=int, default=0,
                    help="claim priority among runs sharing a fleet: "
                         "higher-priority runs' tasks are claimed first "
                         "(default 0)")
    ap.add_argument("--mq-autoscale", default=None, metavar="MIN:MAX",
                    help="elastic fleet: start at MIN workers, grow "
                         "toward MAX on queue depth, shrink back to MIN "
                         "on drain via poison STOP tickets (owned fleets "
                         "only)")
    ap.add_argument("--mq-autoscale-signal", default="depth",
                    choices=("depth", "cost"),
                    help="what --mq-autoscale scales on: raw outstanding "
                         "task count (depth) or predicted outstanding "
                         "cost x measured utilization read from the "
                         "metrics bus (cost; see Observability below)")
    ap.add_argument("--metrics-dir", default=None,
                    help="publish a Prometheus textfile "
                         "(DIR/chambga.prom, atomic replace) for "
                         "node-exporter textfile collectors / the "
                         "terminal dashboard (see Observability below)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics on this port via stdlib "
                         "http.server (0 picks a free port)")
    ap.add_argument("--events-log", default=None,
                    help="append structured dispatch events (JSONL) "
                         "here: enqueue/claim/publish/lease_requeue/"
                         "retry/autoscale/... (see Observability below)")
    ap.add_argument("--cost-ema", action="store_true",
                    help="learn the dispatch cost model online from "
                         "measured per-lane wall times (needs a "
                         "decoupled backend)")
    ap.add_argument("--ema-alpha", type=float, default=0.25,
                    help="EMA smoothing factor for --cost-ema")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="drain metrics every N epochs")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="epochs kept in flight before blocking on metrics")
    args = ap.parse_args(argv)
    # odd --pop is fine: operators.variation carries the unpaired last
    # parent through mutation-only
    enable_compile_cache()

    cfg, fitness_fn, cost_fn = build(args.fitness, args)
    if args.cost_ema:
        if args.dispatch_backend == "inline":
            ap.error("--cost-ema needs measured per-lane wall times — "
                     "use a decoupled backend (host-*, slurm*, k8s* "
                     "or mq*)")
        from repro.core.broker import CostEMA
        # when the fitness backend ships a static cost model (HVDC), it
        # primes the EMA's slot table so even the FIRST dispatch of a
        # skewed workload is balanced; wall times refine it online
        cost_fn = CostEMA(alpha=args.ema_alpha, prime_fn=cost_fn)
    # observability plane: install the metrics bus BEFORE backend
    # construction so the very first job's enqueue/claim events land;
    # absent these flags the runtime keeps its no-op null registry
    obs_registry = obs_exporter = obs_http = obs_events = None
    if args.metrics_dir or args.metrics_port is not None \
            or args.events_log:
        from repro.obs import (PROM_FILENAME, EventLog, MetricsHTTPServer,
                               MetricsRegistry, TextfileExporter)
        from repro.runtime import metrics as runtime_metrics
        if args.events_log:
            parent = os.path.dirname(args.events_log)
            if parent:
                os.makedirs(parent, exist_ok=True)
            obs_events = EventLog(args.events_log)
        obs_registry = MetricsRegistry(events=obs_events)
        runtime_metrics.set_registry(obs_registry)
        if args.metrics_dir:
            os.makedirs(args.metrics_dir, exist_ok=True)
            obs_exporter = TextfileExporter(
                obs_registry,
                os.path.join(args.metrics_dir, PROM_FILENAME)).start()
        if args.metrics_port is not None:
            obs_http = MetricsHTTPServer(
                obs_registry, port=args.metrics_port).start()
            print(f"metrics: http://127.0.0.1:{obs_http.port}/metrics")
    backend = None
    # decoupled backends default to 4 workers; the broker's lane count
    # must match them (not the dp-shard default of 1, which would take
    # the identity path and never engage the cost model)
    workers = args.num_workers
    if args.dispatch_backend != "inline":
        workers = args.num_workers or 4
    # 0 disables the timeout (falsy-zero must not resurrect the default)
    timeout = args.chunk_timeout_s or None
    if args.dispatch_backend.startswith("host-"):
        from repro.core.broker import HostPoolBackend
        backend = HostPoolBackend(
            fitness_fn, num_objectives=cfg.num_objectives,
            num_workers=workers,
            executor=args.dispatch_backend.split("-")[1],
            chunk_timeout_s=timeout)
    elif args.dispatch_backend.startswith(("slurm", "k8s")):
        from repro.runtime.batchq import (KubernetesScheduler,
                                          LocalMockScheduler, MockKubectl,
                                          SlurmArrayBackend, SlurmScheduler)
        if args.dispatch_backend == "slurm":
            scheduler = SlurmScheduler(partition=args.slurm_partition)
        elif args.dispatch_backend == "slurm-mock":
            scheduler = LocalMockScheduler()
        else:
            # k8s: real kubectl; k8s-mock: in-process kubectl stand-in
            # (the scheduler enables its status cache only for the real
            # one — each live poll is a ~100ms shell-out)
            scheduler = KubernetesScheduler(
                namespace=args.k8s_namespace, image=args.k8s_image,
                runner=(MockKubectl()
                        if args.dispatch_backend == "k8s-mock" else None))
        # named benchmarks resolve to numpy-only host simulators so array
        # tasks skip the jax import; other fitness callables are pickled
        from repro.fitness import hostsim
        fn_spec = (f"repro.fitness.hostsim:{args.fitness}"
                   if hasattr(hostsim, args.fitness) else None)
        backend = SlurmArrayBackend(
            fitness_fn, fn_spec=fn_spec,
            num_objectives=cfg.num_objectives,
            num_workers=workers,
            scheduler=scheduler, spool_dir=args.spool_dir,
            chunk_timeout_s=(300.0 if args.chunk_timeout_s is None
                             else timeout),
            min_chunk_cost_s=args.min_chunk_cost_s,
            keep_jobs=None if args.keep_jobs < 0 else args.keep_jobs)
    elif args.dispatch_backend == "mq-net":
        from repro.runtime.netbroker import (NetWorkerPool,
                                             SocketQueueBackend)
        from repro.fitness import hostsim
        fn_spec = (f"repro.fitness.hostsim:{args.fitness}"
                   if hasattr(hostsim, args.fitness) else None)
        if args.mq_autoscale:
            ap.error("--mq-autoscale is not wired for mq-net (the "
                     "poison-ticket scale-down protocol is file-broker "
                     "only); size the fleet with --num-mq-workers")
        if args.mq_dir:
            ap.error("mq-net has no broker directory — the server owns "
                     "its state privately; use --broker-addr (or drop "
                     "--mq-dir for a self-contained in-process server)")
        if args.mq_fleet != "local":
            ap.error("--mq-fleet does not apply to mq-net: attach to a "
                     "shared fleet with --broker-addr, or launch workers "
                     "with `python -m repro.runtime.netbroker --worker`")
        pool = None
        if args.broker_addr is None:
            # self-contained: in-process server + thread workers (the
            # single-box / CI shape; SocketQueueBackend starts its own
            # server and binds the pool to it)
            pool = NetWorkerPool(
                num_workers=args.num_mq_workers or workers,
                mode="thread", lease_s=args.lease_s)
        backend = SocketQueueBackend(
            fitness_fn, fn_spec=fn_spec,
            num_objectives=cfg.num_objectives,
            num_workers=workers,
            broker_addr=args.broker_addr,
            run_id=args.mq_run_id, priority=args.mq_priority,
            lease_s=args.lease_s,
            chunk_timeout_s=(300.0 if args.chunk_timeout_s is None
                             else timeout),
            min_chunk_cost_s=args.min_chunk_cost_s,
            keep_jobs=None if args.keep_jobs < 0 else args.keep_jobs,
            worker_pool=pool)
    elif args.dispatch_backend.startswith("mq"):
        from repro.runtime.mq import (FleetAutoscaler, LocalWorkerPool,
                                      MQWorkerFleet, QueueBackend)
        from repro.fitness import hostsim
        fn_spec = (f"repro.fitness.hostsim:{args.fitness}"
                   if hasattr(hostsim, args.fitness) else None)
        n_mq = args.num_mq_workers or workers
        autoscale = None
        if args.mq_autoscale:
            lo, _, hi = args.mq_autoscale.partition(":")
            try:
                autoscale = (int(lo), int(hi))
            except ValueError:
                ap.error("--mq-autoscale wants MIN:MAX, e.g. 1:16")
            if autoscale[0] < 1 or autoscale[1] < autoscale[0]:
                ap.error("--mq-autoscale wants 1 <= MIN <= MAX")
            n_mq = autoscale[0]      # start at the floor, grow on depth
        pool = None
        if args.dispatch_backend == "mq-mock":
            # in-process thread workers: the CI / smoke-run fleet
            pool = LocalWorkerPool(num_workers=n_mq, mode="thread",
                                   lease_s=args.lease_s)
        elif args.mq_fleet == "external":
            # attach to a fleet another invocation owns (the two-terminal
            # shared-fleet pattern; see Fleet sharing in the epilog) —
            # close() then deregisters this run WITHOUT stopping workers
            if not args.mq_dir:
                ap.error("--mq-fleet external needs the shared --mq-dir "
                         "the fleet-owning invocation uses")
            if autoscale:
                ap.error("--mq-autoscale cannot resize an external fleet "
                         "— only the invocation that owns it can")
        elif args.mq_fleet == "local":
            # persistent numpy-only worker subprocesses on this host
            pool = LocalWorkerPool(num_workers=n_mq, mode="subprocess",
                                   lease_s=args.lease_s)
        else:
            # ONE long-lived array job / indexed Job carrying the whole
            # fleet, submitted through the batchq Scheduler protocol
            if not args.mq_dir:
                ap.error("--mq-fleet slurm|k8s needs an explicit --mq-dir "
                         "on a volume shared with the cluster workers — a "
                         "local temp dir would leave the fleet idling on "
                         "a path it cannot see")
            from repro.runtime.batchq import (KubernetesScheduler,
                                              SlurmScheduler)
            # the fleet must outlive the whole run, not SlurmScheduler's
            # 30-minute per-batch default
            sched = (SlurmScheduler(partition=args.slurm_partition,
                                    time_limit="7-00:00:00")
                     if args.mq_fleet == "slurm" else
                     KubernetesScheduler(namespace=args.k8s_namespace,
                                         image=args.k8s_image))
            pool = MQWorkerFleet(sched, n_mq, lease_s=args.lease_s)
        scaler = (FleetAutoscaler(pool, min_workers=autoscale[0],
                                  max_workers=autoscale[1],
                                  signal=args.mq_autoscale_signal,
                                  metrics=obs_registry)
                  if autoscale else None)
        backend = QueueBackend(
            fitness_fn, fn_spec=fn_spec,
            num_objectives=cfg.num_objectives,
            num_workers=workers,
            mq_dir=args.mq_dir, run_id=args.mq_run_id,
            priority=args.mq_priority, lease_s=args.lease_s,
            chunk_timeout_s=(300.0 if args.chunk_timeout_s is None
                             else timeout),
            min_chunk_cost_s=args.min_chunk_cost_s,
            keep_jobs=None if args.keep_jobs < 0 else args.keep_jobs,
            worker_pool=pool, autoscaler=scaler)
    # context-managed teardown: a crash anywhere past this point (engine
    # construction included) must still drain in-flight pure_callbacks
    # and free the pool / temp spool — a failed run must not strand them
    with contextlib.ExitStack() as stack:
        if obs_registry is not None:
            # LIFO: runs after the backend's close() below, so the
            # exporter's final write captures the end-of-run counters
            from repro.runtime import metrics as runtime_metrics
            stack.callback(runtime_metrics.set_registry, None)
            if obs_events is not None:
                stack.callback(obs_events.close)
            if obs_http is not None:
                stack.callback(obs_http.stop)
            if obs_exporter is not None:
                stack.callback(obs_exporter.stop)
        if backend is not None:
            stack.enter_context(backend)
        plan = plan_scaling(len(jax.devices()), pop_total=cfg.global_pop,
                            sim_parallelism=max(args.contingencies, 1))
        print(f"scaling plan: horizontal={plan.horizontal} "
              f"vertical={plan.vertical}")
        ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        eng = GAEngine(cfg, fitness_fn, cost_fn=cost_fn, backend=backend,
                       num_workers=workers, checkpointer=ckpt,
                       checkpoint_every=2 if ckpt else 0,
                       sync_every=args.sync_every,
                       pipeline_depth=args.pipeline_depth,
                       log_fn=lambda r: print(
                           f"epoch {r['epoch']:4d} best {r['best']:.5f} "
                           f"skew {r['skew']:.3f}"))
        pop, hist = eng.run(wallclock_s=args.wallclock_s)
        g, f = eng.best(pop)
        stats = eng.broker.backend_stats()
        if stats:
            print("dispatch stats: " + " ".join(
                f"{k}={v}" for k, v in sorted(stats.items())))
    print(f"best fitness: {f[0]:.6f}")
    print(f"best genome:  {np.round(g, 4)}")
    return pop, hist


if __name__ == "__main__":
    main()
