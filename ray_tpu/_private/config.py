"""Env-overridable framework configuration.

TPU-native equivalent of the reference's macro-generated config
(``src/ray/common/ray_config_def.h:46-66`` — 141 ``RAY_CONFIG(type, name,
default)`` entries, each overridable from env ``RAY_{name}``, plus a JSON
``_system_config`` propagated to all daemons via ``RayConfig::initialize``,
``src/ray/common/ray_config.cc:29``).

Here every dataclass field is overridable from env ``RAY_TPU_{NAME}`` and from
the ``_system_config`` dict passed to :func:`ray_tpu.init`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Optional

from ray_tpu._private.debug.lock_order import diag_lock


@dataclasses.dataclass
class Config:
    # ------ scheduler (reference: ray_config_def.h:138,463,533,342) ------
    #: Utilization below which the hybrid policy packs instead of spreads.
    scheduler_spread_threshold: float = 0.5
    #: Prefer non-TPU nodes for tasks that don't require TPU (reference:
    #: ``scheduler_avoid_gpu_nodes``, ray_config_def.h:533).
    scheduler_avoid_tpu_nodes: bool = True
    #: Which backend solves the task->node assignment each tick:
    #: "native" = greedy per-task python/numpy policy (reference parity),
    #: "jax"    = batched TPU bin-packing kernel (the north star) with
    #:            device-resident world state and validated native
    #:            fallback.  Default since round 3.  The device is the
    #:            one the process holds: children the runtime starts
    #:            are pinned to the CPU (_private/device_policy.py).
    scheduler_backend: str = "jax"

    #: Heterogeneity cost weight (Gavel-style effective-rate scaling):
    #: slower nodes (per the ray_tpu.throughput / accel_throughput node
    #: labels) cost this much extra utilization at full rate spread.
    #: 0 disables the term; 1/16 of weight = one fill bucket.
    scheduler_het_weight: float = 0.25
    #: Arg-locality cost weight: a node holding ALL of a class's queued
    #: argument bytes gets this much utilization bonus (negative cost).
    #: 0 disables the term.
    scheduler_locality_weight: float = 0.5
    #: Placement-group bundle packing backend: "auto" routes through the
    #: TPU bundle kernel when jax is importable and the cluster has at
    #: least pg_kernel_min_nodes nodes (greedy numpy fallback below
    #: that, and on any kernel failure), "force" always kernels,
    #: "off" always greedy.
    pg_kernel_backend: str = "auto"
    pg_kernel_min_nodes: int = 32
    #: Autoscaler demand-solve backend: "auto" routes
    #: get_bin_pack_residual / get_nodes_for through the batched kernel
    #: when nodes x demand-classes >= autoscaler_kernel_min_cells
    #: (exact numpy below, and on any kernel failure), "force" / "off"
    #: as above.
    autoscaler_kernel_backend: str = "auto"
    autoscaler_kernel_min_cells: int = 2048
    #: Pod-sharded solve: shard the (classes x nodes) waterfill /
    #: solve-tick / bundle-pack along the NODE axis across the local
    #: devices (shard_map over a 1-D mesh, cross-shard prefix/argmax
    #: reductions per bucket step).  "auto" shards when more than one
    #: device is visible AND the cluster has at least
    #: solver_shard_min_nodes nodes; "force" shards whenever >1 device
    #: exists (tests); "off" never shards.  The single-device path
    #: stays the default below the gate — sharding a small solve pays
    #: collective latency for nothing.
    solver_shard_backend: str = "auto"
    solver_shard_min_nodes: int = 4096
    #: Event-buffer lock striping: per-thread striped sub-buffers
    #: (round-robin thread->stripe binding) drained and merged by the
    #: flusher.  1 = the old single-lock buffer.
    task_event_stripes: int = 8
    #: Max lease requests in flight per scheduling class
    #: (ray_config_def.h:342).  Batched lease requests count each
    #: entry against this cap.
    max_pending_lease_requests_per_scheduling_category: int = 10
    #: Max lease entries coalesced into ONE request_worker_lease_batch
    #: round-trip (the dispatch fast path: a same-class burst leases up
    #: to this many workers per RPC instead of one).  1 disables
    #: batching (every lease rides the single-lease RPC).
    lease_batch_size: int = 10
    #: Retry delay for lease-batch entries the raylet returned as
    #: ``backlog`` (feasible but no capacity this tick) when nothing
    #: else — a completion, a new submit, a lease reply — re-pumps the
    #: class first.  Pure fallback; the common re-pump is event-driven.
    lease_backlog_retry_ms: int = 20
    #: How long an idle LEASED worker is parked submitter-side before
    #: its lease is returned to the raylet (lease pipelining: a
    #: same-class task submitted within the window is pushed directly,
    #: zero scheduling round-trips).  Trade-off: a parked lease HOLDS
    #: its resource reservation for up to the window, so other
    #: scheduling classes see less capacity; keep it at request-gap
    #: scale.  0 = off (return leases immediately, current behavior).
    worker_lease_keepalive_ms: int = 0
    #: Submit-side flow control: when a scheduling class's transport
    #: queue is deeper than this at submit time, the submitting thread
    #: yields the GIL (``time.sleep(0)``) so executing workers can
    #: drain — a tight submission loop otherwise starves the very
    #: pipeline it is filling and every queued task's latency grows by
    #: the imbalance.  A yield, not a block: semantics are unchanged,
    #: and shallow queues never hit it.  0 disables.
    submit_backpressure_depth: int = 64
    #: Event-driven scheduling wakeup debounce: a task arrival /
    #: resource release schedules the tick this many ms out, and
    #: further wakeups inside the window coalesce into that one tick —
    #: a submission burst becomes one batched solve instead of one tick
    #: per task.  0 = post the tick immediately (no coalescing).  The
    #: periodic event_loop_tick_ms tick remains as fallback.
    scheduler_wakeup_debounce_ms: float = 1.0
    #: GCS-side actor scheduling (ray_config_def.h:463).
    gcs_actor_scheduling_enabled: bool = False

    #: Reconnect-reconcile sweep exempts lease grants younger than this
    #: (their grant reply may legitimately still be in flight).
    lease_reconcile_grace_s: float = 5.0
    #: Per-attempt bound on head->node lease RPCs (request/return over
    #: the wire).  A blackholed request (asymmetric partition: the node
    #: heartbeats but cannot receive) would otherwise strand the
    #: submitter forever — the bounded attempts retry under one dedup
    #: token (a slow-but-delivered first attempt is replayed, never
    #: re-granted) and exhausted attempts surface as a lease rejection
    #: the submitter's transient re-lease machinery absorbs.  Keep WELL
    #: above legitimate dep-wait lease holds.
    lease_rpc_timeout_s: float = 30.0

    # ------ failure detection (ray_config_def.h:51-55) ------
    raylet_heartbeat_period_milliseconds: int = 100
    num_heartbeats_timeout: int = 30
    #: Missed beats before a node is marked SUSPECT (published; the
    #: scheduler masks suspect nodes for NEW placements while actors /
    #: objects / placement groups stay untouched).  A transient
    #: partition that heals between this and num_heartbeats_timeout
    #: costs a placement pause, not a node death.  Must be below
    #: num_heartbeats_timeout; the gap is the "suspect grace".
    num_heartbeats_suspect: int = 15

    # ------ object store ------
    #: Objects larger than this are promoted to the node (plasma-equivalent)
    #: store instead of the in-process memory store (reference: 100KB
    #: promotion threshold in CoreWorker::Put).
    max_direct_call_object_size: int = 100 * 1024
    #: Per-node object store capacity in bytes before spilling kicks in.
    object_store_memory: int = 2 * 1024 * 1024 * 1024
    #: Spill when store utilization exceeds this fraction.
    object_spilling_threshold: float = 0.8
    #: Min number of objects batched into one spill operation
    #: (reference: local_object_manager.h min_spilling_size).
    min_spilling_size: int = 100 * 1024 * 1024
    #: How long an over-capacity create/put/transfer reservation may
    #: queue for space (retried as seals/evictions/spills free room)
    #: before ObjectStoreFullError surfaces (reference:
    #: oom_grace_period_s over the plasma create_request_queue).
    object_store_full_grace_period_s: float = 10.0
    #: Delay between retries while a queued create waits for space.
    object_store_full_retry_ms: int = 20
    #: Use the native C++ shared-memory store when available.
    use_native_object_store: bool = True
    #: Chunk size for node-to-node object transfer (object_manager.cc).
    object_manager_chunk_size: int = 5 * 1024 * 1024
    #: In-flight chunk requests per pull transfer: the receiver keeps a
    #: window of this many pipelined chunk RPCs open to hide round-trip
    #: latency (push_manager.cc ack window / pull retry flow).
    object_transfer_pipeline_depth: int = 8
    #: Sender-side transfer admission: max concurrent OUTBOUND transfer
    #: sessions per store (chunk sessions + in-process store-to-store
    #: copies share the cap).  Excess pulls queue FIFO instead of
    #: thrashing every session's window (push_manager.cc bounded
    #: chunks-in-flight, made a per-store budget).
    object_transfer_max_outbound_sessions: int = 4
    #: How long a ``fetch_meta`` waits in the sender's FIFO admission
    #: queue before replying ``busy`` (the receiver then backs off or
    #: re-selects another source).
    object_transfer_admission_wait_s: float = 1.0
    #: Chunk-level relay: a node mid-pull registers a PARTIAL location
    #: row and serves the already-assembled prefix of its in-flight
    #: transfer to downstream pullers, so a 1->N broadcast completes as
    #: a pipelined chain/tree instead of N full copies out of the
    #: origin.  Off = every pull streams from a full copy only.
    object_transfer_relay_enabled: bool = True
    #: Source selection for pulls with multiple known locations:
    #: "load" weighs candidates by live per-source outbound load
    #: (sessions + queue + in-flight bytes), "first" keeps the naive
    #: first-directory-row choice (the pre-relay behavior; the bench's
    #: naive arm).
    object_transfer_source_selection: str = "load"
    #: Server-side wait for the assembly watermark to advance past a
    #: relay chunk request before replying ``pending`` (the receiver
    #: re-requests that chunk).
    object_transfer_relay_wait_s: float = 2.0

    # ------ core worker / task path ------
    #: Args at or below this size are inlined into the task spec
    #: (reference: task_rpc_inlined_bytes_limit / put threshold).
    task_args_inline_bytes_limit: int = 100 * 1024
    #: Default max retries for normal tasks (reference: default 3).
    task_max_retries: int = 3
    #: Lineage pinning for reconstruction (ray_config_def.h:97,110).
    lineage_pinning_enabled: bool = True
    #: Max lineage bytes kept per owner before disabling reconstruction.
    max_lineage_bytes: int = 1024 * 1024 * 1024
    #: Max recursion depth when reconstructing a lost object whose
    #: creating task's args are themselves lost (object_recovery_manager
    #: parity: recovery walks the lineage DAG, bounded).
    max_lineage_reconstruction_depth: int = 10
    #: Base of the per-task exponential backoff between repeated
    #: reconstruction attempts of the same creating task.
    lineage_reconstruction_backoff_s: float = 0.2

    # ------ worker pool ------
    #: "thread" = executor threads in the node process (default; one
    #: process per host owns the TPU chips); "process" = real OS worker
    #: processes spawned via worker_main and driven over the framed-RPC
    #: wire (reference StartWorkerProcess parity, worker_pool.h:428).
    #: Process-mode workers are pinned to the CPU (device_policy): work
    #: that needs the chip is not supported there yet (ROADMAP D7).
    worker_process_mode: str = "thread"
    #: Soft cap of idle workers kept alive per node (ray_config_def.h:129).
    num_workers_soft_limit: int = 64
    #: Warm-worker prestart target (reference ``PrestartWorkers``,
    #: worker_pool.h:350): when queued work outnumbers idle+starting
    #: workers, the dispatch loop starts workers AHEAD of pop_worker up
    #: to this many total, so a burst doesn't pay per-task worker
    #: startup inline.  Memory trade-off: every prestarted worker holds
    #: a thread stack (thread mode) or a whole Python interpreter
    #: (process mode, tens of MB each) even if the burst never
    #: materializes — size it to expected burst width, not max_workers.
    #: 0 = off (workers start lazily in pop_worker, current behavior).
    num_prestart_workers: int = 0
    #: Also prestart from the SUBMIT edge (cluster task manager queue
    #: arrival), not just the local dispatch loop — fires before
    #: scheduling, so workers warm while the solve runs.  No effect
    #: unless num_prestart_workers > 0.
    prestart_on_submit: bool = False
    #: Maximum workers starting up concurrently (reference semantics:
    #: a throttle on spawns, NOT a total cap).
    maximum_startup_concurrency: int = 64
    #: Process-wide (ALL pools in this OS process) cap on workers in
    #: startup concurrently — the cluster-envelope startup-storm
    #: throttle: per-node caps alone let N nodes × per-node cap spawns
    #: land at once on one shared box.  A pop over the cap returns None
    #: (the dispatch tick retries, same contract as the per-node cap).
    #: 0 disables the global gate.
    worker_global_startup_concurrency: int = 128
    #: Stagger between consecutive background prestart spawns
    #: (milliseconds) so a prestart storm ramps instead of spiking.
    #: Only the throwaway prestart thread sleeps; pop_worker never does.
    worker_startup_stagger_ms: float = 0.0
    #: Hard per-node worker cap (runaway backstop; the envelope needs
    #: thousands of dedicated actor workers, reference supports 10k+).
    max_workers_per_node: int = 20_000
    #: Mirror process-worker stdout/stderr lines onto the driver's
    #: terminal via the worker_logs pubsub channel (reference
    #: log_to_driver / log_monitor.py behavior).
    log_to_driver: bool = True

    # ------ rpc ------
    #: Dispatch threads per RpcServer; requests beyond BOTH the pool and
    #: its queue get dedicated threads so blocking handlers can never
    #: deadlock the pool (reference: grpc server completion-queue
    #: thread pool).
    rpc_dispatch_pool_size: int = 64
    #: Attempts for RpcClient.call on verbs classified retryable in
    #: rpc/verbs.py (timeout / connection loss only — a remote handler
    #: exception is deterministic and never retried).
    rpc_retry_attempts: int = 3
    #: Base of the exponential backoff between those retry attempts.
    rpc_retry_backoff_s: float = 0.2
    #: Server-side dedup window (entries) for requests carrying a
    #: client-minted dedup token: the handler of a non-idempotent verb
    #: runs once per token; duplicates — client retries AND duplicated
    #: wire deliveries — get the recorded reply.  Size it well above
    #: (concurrent in-flight mutating requests x retry attempts).
    rpc_dedup_window_size: int = 512

    # ------ GCS ------
    gcs_storage_backend: str = "memory"  # "memory" | "file"
    #: Period of the GCS resource usage poll/broadcast loop
    #: (reference: ray_syncer.h broadcast thread).
    gcs_resource_broadcast_period_milliseconds: int = 100
    #: Head-side registration admission: ``register_node`` handlers
    #: running concurrently beyond this get ``{"busy": True,
    #: "retry_after_ms"}`` instead of a proxy dial — fan-in
    #: backpressure for a 64-host registration storm (the node host
    #: retries with jittered backoff).  0 disables the gate.
    head_registration_concurrency: int = 8

    # ------ misc ------
    event_loop_tick_ms: int = 5
    metrics_report_interval_ms: int = 2_000
    temp_dir: str = "/tmp/ray_tpu"
    #: Enable OpenTelemetry-style span capture (tracing_helper.py parity).
    tracing_enabled: bool = False

    # ------ causal job profiler (gcs/job_graph.py) ------
    #: Arms provenance capture end-to-end: parent/arg-ids stamped onto
    #: submit-side task events, terminal records copied into the per-job
    #: graph store, and object-plane spans (transfer/spill/restore)
    #: force-recorded so `ray-tpu profile` can attribute edge time.
    #: Off = the pre-profiler pipeline, byte-for-byte (the bench's
    #: armed-vs-off overhead row toggles exactly this).
    job_profiler_enabled: bool = True
    #: Bounded graph store: jobs tracked (LRU-evicted beyond this)...
    job_graph_max_jobs: int = 16
    #: ...and terminal task records kept per job (oldest-first evicted;
    #: the profile reports the eviction count as a coverage caveat).
    job_graph_max_tasks: int = 20_000

    # ------ heartbeat-channel shipping budget ------
    #: Per-heartbeat-ship-window byte budget for the node-side timeline
    #: span shipper (unused budget carries over, capped at 4 windows):
    #: bounds observability's share of the heartbeat channel so a span
    #: storm cannot congest the control plane at 64-node scale.
    timeline_ship_budget_bytes: int = 262_144
    #: Shared per-beat byte budget for EVERYTHING observability ships
    #: on the heartbeat channel (metrics deltas + timeline spans).  The
    #: liveness beat itself is never charged: when a beat's payloads
    #: would exceed the budget, the metrics delta is shed (the shipper
    #: force-fulls so the next admitted report resyncs — deferral, not
    #: loss) and the timeline shipper gets only the leftover budget —
    #: congestion sheds telemetry, never liveness.  Shed bytes are
    #: observable as ``ray_tpu_heartbeat_shed_bytes``.  0 = unbounded.
    heartbeat_payload_budget_bytes: int = 1_048_576

    # ------ introspection plane (flight recorder / watchdog) ------
    #: Always-on per-process decision ring (debug.flight_recorder):
    #: scheduler tick summaries, lease-batch vectors, transfer source
    #: selections, spill/restore/reconstruction attempts, create-queue
    #: admits, fault firings.  Dumped by `ray-tpu doctor`, wedge
    #: reports and crash paths.
    flight_recorder_enabled: bool = True
    #: Ring capacity in fixed slots (overwrites oldest; O(slots) memory).
    flight_recorder_slots: int = 512
    #: Stall watchdog over event loops and pump threads: emits wedge
    #: reports (thread stacks + held locks + recorder tail) to a crash
    #: file and to the head.  Report-only — never kills anything.
    watchdog_enabled: bool = True
    #: A loop handler running longer than this (or queued work making
    #: no progress for this long) is a wedge.  0 disables detection
    #: while keeping the beat bookkeeping.
    loop_stall_budget_s: float = 10.0
    #: Watchdog poll cadence (clamped to budget/4).
    watchdog_poll_interval_s: float = 0.5
    #: Per-process cap on wedge/crash files kept in <temp_dir>/wedges:
    #: after each write the oldest files beyond this are pruned (64
    #: hosts under a chaos schedule otherwise grow the directory
    #: without bound).  Dropped files are counted into the
    #: introspection metrics; a clean shutdown removes this process's
    #: remaining files.  0 = unbounded.
    wedge_files_keep: int = 20

    # ------ serve (inference plane) ------
    #: Replica placement backend for serve deployments: "auto" routes
    #: replica starts through the pack-mode TPU kernel solve when the
    #: cluster has at least serve_kernel_min_nodes nodes (DEFAULT
    #: placement below that, and on any solve failure), "force" always
    #: solves, "off" always DEFAULT placement.
    serve_kernel_placement: str = "auto"
    serve_kernel_min_nodes: int = 2
    #: Pipeline ingress inputs at least this large are put ONCE into
    #: the object store and handed to every stage as an ObjectRef (the
    #: zero-copy object-id handoff) instead of being pickled into each
    #: stage's task args.  0 forces the handoff for every input;
    #: negative disables it.
    serve_zero_copy_threshold_bytes: int = 65_536
    #: How many times Router.call re-assigns a request whose replica
    #: died mid-flight before surfacing ReplicaDiedError.  User
    #: exceptions are NEVER retried.
    serve_request_retries: int = 3
    #: Cadence of the router's queue-depth reports to the controller
    #: (the autoscaler's queue signal).  Idle routers go silent after
    #: one zero report regardless of cadence.
    serve_router_report_interval_s: float = 0.25

    @classmethod
    def from_env(cls, system_config: Optional[dict] = None) -> "Config":
        cfg = cls()
        for f in dataclasses.fields(cls):
            env_key = "RAY_TPU_" + f.name.upper()
            # Also honor the reference's RAY_<name> convention.
            raw = os.environ.get(env_key, os.environ.get("RAY_" + f.name))
            if raw is not None:
                setattr(cfg, f.name, _parse(raw, f.type, getattr(cfg, f.name)))
        if system_config:
            for k, v in system_config.items():
                if not hasattr(cfg, k):
                    raise ValueError(f"Unknown system config key: {k}")
                setattr(cfg, k, v)
        return cfg

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def _parse(raw: str, ftype, default):
    t = type(default)
    if t is bool:
        return raw.lower() in ("1", "true", "yes")
    if t is int:
        return int(raw)
    if t is float:
        return float(raw)
    return raw


_global_config: Optional[Config] = None
_lock = diag_lock("config._lock")


def get_config() -> Config:
    """Process-wide config singleton (initialized lazily from env)."""
    global _global_config
    with _lock:
        if _global_config is None:
            _global_config = Config.from_env()
        return _global_config


def initialize_config(system_config: Optional[dict] = None) -> Config:
    global _global_config
    with _lock:
        _global_config = Config.from_env(system_config)
        return _global_config
