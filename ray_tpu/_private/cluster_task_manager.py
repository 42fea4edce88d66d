"""Cluster-level scheduling queues + the scheduling tick.

Parity: reference ``src/ray/raylet/scheduling/cluster_task_manager.cc`` —
per-``SchedulingClass`` FIFO queues (:44-123), the periodic
``ScheduleAndDispatchTasks`` tick (also run on every state change,
node_manager.cc:392-394), spillback via ``ScheduleOnNode`` (:285-323),
infeasible queues parked and retried on cluster change (:125-159).

This is the north-star surface (SURVEY.md §3.4): each tick the queues are a
``demand[C, R]`` matrix and the local view an ``avail[N, R]`` matrix.  With
``scheduler_backend=native`` each task is placed by the greedy policy; with
``scheduler_backend=jax`` whole queues are solved in one batched TPU call
(ray_tpu.scheduler.jax_backend) and the per-task grant/spill decisions are
validated against exact fixed-point vectors before commit — stale-view
tolerant, exactly like spillback.
"""

from __future__ import annotations

import logging
import threading
from collections import defaultdict, deque
from typing import Callable, Dict, Tuple

import time

from ray_tpu._private import fault_injection
from ray_tpu._private.config import get_config
from ray_tpu._private.debug import diag_rlock, flight_recorder, loop_only
from ray_tpu._private.task_spec import TaskSpec
from ray_tpu.scheduler import policy as policy_mod

logger = logging.getLogger(__name__)

# Consecutive pop->dispatch failures of one task before the lease is
# rejected back to the submitter (which charges the task's retry
# budget) — bounds the requeue loop under a deterministic fault.
_MAX_DISPATCH_REQUEUES = 20

# Tick-latency histogram bounds (seconds).  The north-star budget is
# 50 ms/tick at 1M tasks x 10k nodes (BASELINE.md); the sub-ms buckets
# resolve the common in-process case.
_TICK_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                 0.1, 0.25, 1.0)

# Lease-batch wait (queued -> swept by a tick) histogram bounds
# (seconds): from the wakeup debounce (1 ms) up to a batch that sat
# behind a whole multi-second tick.
_WAIT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                 1.0, 5.0)


class _LeaseBatch:
    """Collector for one batched lease request: N entries, ONE reply.

    Each entry resolves independently (grant when the local dispatch
    path binds a worker, spillback during the scheduling pass, backlog
    when the sweep withdraws it); the batch reply fires once, when the
    last entry lands, carrying the ordered result vector — the
    one-round-trip shape the wire protocol needs."""

    __slots__ = ("results", "queued_at", "_remaining", "_reply", "_lock")

    def __init__(self, n: int, reply: Callable):
        self.results: list = [None] * n
        # Stamped once; the tick that sweeps the batch reads it for
        # "how long did this request wait for the loop".
        self.queued_at = time.perf_counter()
        self._remaining = n
        self._reply = reply
        from ray_tpu._private.debug import diag_lock
        self._lock = diag_lock("_LeaseBatch._lock")

    def resolve(self, idx: int, result: dict) -> None:
        with self._lock:
            if self.results[idx] is not None:
                return          # duplicate resolution: first wins
            self.results[idx] = result
            self._remaining -= 1
            done = self._remaining == 0
        if done:
            # Flight recorder: the grant/backlog vector this batch
            # resolved to — the lease-protocol decision the metrics
            # plane only counts.
            flight_recorder.record(
                "lease.batch_reply", n=len(self.results),
                grants=sum(1 for r in self.results
                           if r and "worker" in r),
                spillbacks=sum(1 for r in self.results
                               if r and "retry_at" in r),
                backlog=sum(1 for r in self.results
                            if r and r.get("backlog")
                            and not r.get("infeasible")),
                infeasible=sum(1 for r in self.results
                               if r and r.get("infeasible")),
                rejected=sum(1 for r in self.results
                             if r and r.get("rejected")))
            self._reply({"results": self.results})


class _BatchEntry:
    """Per-entry reply callable of a :class:`_LeaseBatch` — the queues
    hold ``(spec, reply)`` pairs, and the backlog sweep recognizes batch
    entries by this type to withdraw them."""

    __slots__ = ("batch", "idx")

    def __init__(self, batch: _LeaseBatch, idx: int):
        self.batch = batch
        self.idx = idx

    def __call__(self, result: dict) -> None:
        self.batch.resolve(self.idx, result)


class ClusterTaskManager:
    def __init__(self, raylet):
        self._raylet = raylet
        self._lock = diag_rlock("ClusterTaskManager._lock")
        self._queues: Dict[int, deque] = defaultdict(deque)
        self._infeasible: Dict[int, deque] = defaultdict(deque)
        self._view_version = -1
        self._jax_solver = None
        # Event-driven wakeup coalescing: True while a tick is already
        # scheduled but not yet started — further wakeup requests
        # inside the debounce window fold into it (guarded by _lock).
        self._wakeup_pending = False
        # Lease batches whose unresolved entries the next tick's sweep
        # may withdraw as backlog (guarded by _lock); a batch is swept
        # only by a scheduling pass that STARTED after it was queued.
        self._pending_batches: list = []
        # Tick telemetry: the hot path bumps these plain counters; the
        # scrape-time collector renders them at /metrics (the repo-wide
        # stats pattern — no registry lock on the tick path).  Only the
        # tick-latency histogram observes into the registry directly
        # (bounded _Hist accumulator, one call per tick).
        self._node_label = self._raylet.node_id.hex()[:12]
        self.tick_stats = {"ticks": 0, "busy_ticks": 0,
                           "spillbacks": 0,
                           # Spillbacks decomposed by reason — the two
                           # placement-quality counters the cost-matrix
                           # terms are measured against: no_capacity =
                           # the local node could not run the task now;
                           # locality_override = the cost-aware solve
                           # moved a locally-runnable task to the node
                           # holding its argument bytes / the faster
                           # throughput class.
                           "spillbacks_no_capacity": 0,
                           "spillbacks_locality_override": 0,
                           "jnp_fallbacks": 0,
                           "last_batch_classes": 0, "last_batch_tasks": 0,
                           "dispatch_errors": 0,
                           # Entries answered, by kind.  Over one
                           # batched pass these and ``spillbacks`` sum
                           # to ``last_batch_tasks``: granted_local =
                           # handed to the local dispatch path;
                           # requeued_busy = no node this tick, some
                           # node's total fits; parked_infeasible = no
                           # node's total fits; spill_refused = the
                           # solve's node failed validation against the
                           # exact vectors (``is_feasible`` /
                           # ``view.subtract``) — solve attempts that
                           # were not useful outcomes;
                           # requeued_dispatch_failed = the local
                           # handoff raised.  (The greedy pass leaves a
                           # busy entry queued without touching it, so
                           # it counts only the kinds it answers.)
                           "granted_local": 0, "requeued_busy": 0,
                           "parked_infeasible": 0, "spill_refused": 0,
                           "requeued_dispatch_failed": 0,
                           "is_feasible_anywhere_calls": 0}
        # Consecutive failed dispatch handoffs per task (cleared on
        # success): past _MAX_DISPATCH_REQUEUES the lease is rejected.
        self._dispatch_failures: Dict = {}
        from ray_tpu._private.metrics_agent import (get_metrics_registry,
                                                    record_internal)
        label = {"node": self._node_label}

        def _collect(mgr):
            for k, v in mgr.tick_stats.items():
                record_internal(f"ray_tpu.scheduler.tick.{k}", v, **label)
            for k, v in mgr._raylet.lease_stats.items():
                record_internal(f"ray_tpu.scheduler.{k}", v, **label)
            record_internal("ray_tpu.scheduler.pending_queue_depth",
                            mgr.num_queued(), **label)
            # The latency histogram is observed on the tick path, not
            # here — claim its series so it dies with this manager
            # instead of leaking per-node cardinality under churn.
            get_metrics_registry().claim_series(
                "ray_tpu.scheduler.tick_latency", **label)
            get_metrics_registry().claim_series(
                "ray_tpu.scheduler.lease_batch_wait", **label)
        get_metrics_registry().register_collector(self, _collect)

    # ---- entry (HandleRequestWorkerLease -> QueueAndScheduleTask) -------
    def queue_and_schedule(self, spec: TaskSpec, reply: Callable):
        with self._lock:
            self._queues[spec.scheduling_class].append((spec, reply))
        self._maybe_prestart(1)
        self.request_tick()

    def queue_and_schedule_batch(self, specs, reply: Callable):
        """Batched lease entry (the dispatch fast path): N same-class
        lease requests in one call, ONE reply carrying the ordered
        grant/spillback/backlog vector.  Entries the first scheduling
        pass can serve resolve through the normal dispatch machinery;
        the pass's leftovers are withdrawn as ``backlog`` (or
        ``infeasible``) by the sweep so the reply is one tick prompt
        instead of deferred until the last worker frees — a deferred
        batch reply would hold granted workers hostage behind entries
        still waiting on the resources those workers occupy."""
        batch = _LeaseBatch(len(specs), reply)
        with self._lock:
            for i, spec in enumerate(specs):
                self._queues[spec.scheduling_class].append(
                    (spec, _BatchEntry(batch, i)))
            self._pending_batches.append(batch)
        self._maybe_prestart(len(specs))
        self.request_tick()

    def requeue_for_spill(self, spec: TaskSpec, reply: Callable):
        """A locally-queued task whose resources vanished (e.g. PG removed)
        goes back through cluster scheduling."""
        with self._lock:
            self._queues[spec.scheduling_class].appendleft((spec, reply))
        self.request_tick()

    def on_resources_freed(self):
        self.request_tick()

    def on_cluster_changed(self):
        """Retry infeasible queues when nodes/resources change (:125-159)."""
        with self._lock:
            for cls, q in self._infeasible.items():
                self._queues[cls].extend(q)
                q.clear()
        self.request_tick()

    def request_tick(self):
        """Event-driven scheduling wakeup, coalesced: the first request
        schedules the tick ``scheduler_wakeup_debounce_ms`` out and
        every further request before it runs folds into it — a
        submission burst becomes ONE batched solve instead of one tick
        per arrival flooding the loop with redundant passes.  The
        periodic ``event_loop_tick_ms`` tick stays as the fallback for
        anything a wakeup edge misses."""
        with self._lock:
            if self._wakeup_pending:
                return
            self._wakeup_pending = True
        debounce = get_config().scheduler_wakeup_debounce_ms / 1000.0
        if debounce > 0:
            self._raylet.loop.schedule_after(
                debounce, self.schedule_and_dispatch, "cluster.schedule")
        else:
            self._raylet.loop.post(self.schedule_and_dispatch,
                                   "cluster.schedule")

    def _maybe_prestart(self, queued_now: int):
        """Predictive warm-worker prestart from queue depth
        (PrestartWorkers parity): fire-and-forget, bounded by
        ``num_prestart_workers``; a no-op when the knob is 0 or the
        pool already has enough idle+starting workers."""
        cfg = get_config()
        if not cfg.num_prestart_workers or not cfg.prestart_on_submit:
            return
        self._raylet.worker_pool.prestart_for_backlog(
            self.num_queued() + queued_now, cfg.num_prestart_workers)

    # ---- the tick -------------------------------------------------------
    @loop_only("raylet")
    def schedule_and_dispatch(self):
        """The scheduling tick.  Loop-affine by design: every caller
        posts it to the raylet loop (queue_and_schedule, resource-freed
        and cluster-changed notifications, the periodic tick) so queue
        pops, the dirty cluster view and tick_stats are only touched
        from one thread — graftcheck R4 verifies the call sites
        statically, the decorator enforces it at runtime in tests."""
        from ray_tpu._private.metrics_agent import observe_internal
        from ray_tpu.util import tracing
        cfg = get_config()
        with self._lock:
            # Requests arriving from here on need a fresh tick.
            self._wakeup_pending = False
            # Sweep set: batches queued BEFORE this pass starts — the
            # pass below definitely considers their entries, so
            # whatever it leaves queued is genuine backlog.  Batches
            # queued mid-pass wait for the next tick.
            sweep, self._pending_batches = self._pending_batches, []
        depth = self._total_queued()
        t0 = time.perf_counter()
        # Lease wait: queued -> swept, one observation per batch.  It is
        # what neither the submitter's clock around a whole round nor
        # the tick's own latency can see, and it shows a burst that the
        # wakeup debounce split over two ticks.
        oldest_wait = 0.0
        for batch in sweep:
            wait = t0 - batch.queued_at
            oldest_wait = max(oldest_wait, wait)
            observe_internal("ray_tpu.scheduler.lease_batch_wait", wait,
                             buckets=_WAIT_BUCKETS, node=self._node_label)
        oldest_wait_ms = round(oldest_wait * 1000.0, 3)
        # One span per WORKING tick (idle ticks fire every
        # event_loop_tick_ms — tracing them would bury the timeline).
        span = tracing.span("scheduler.tick", category="sched",
                            node=self._node_label, queued=depth,
                            swept_batches=len(sweep),
                            oldest_lease_wait_ms=oldest_wait_ms) \
            if depth else None
        try:
            if span is not None:
                span.__enter__()
            if cfg.scheduler_backend == "jax" and depth > 1:
                if self._schedule_batched():
                    return
                # Device path unavailable/invalid this tick — the work
                # was requeued; fall through to the validated native
                # policy.
                self.tick_stats["jnp_fallbacks"] += 1
            self._schedule_greedy()
        finally:
            # Even when the pass raised: an unreplied batch entry left
            # queued would defer the whole batch reply indefinitely.
            if sweep:
                with tracing.span("scheduler.backlog", category="sched"):
                    self._resolve_batch_backlog(sweep)
            if span is not None:
                span.__exit__(None, None, None)
            dt = time.perf_counter() - t0
            self.tick_stats["ticks"] += 1
            if depth:
                # Flight recorder: one record per WORKING tick — the
                # solve summary (batch shape + spillback split) behind
                # every grant/spill decision this tick made.
                ts = self.tick_stats
                flight_recorder.record(
                    "sched.tick", node=self._node_label, queued=depth,
                    dur_ms=round(dt * 1000.0, 3),
                    batch_tasks=ts["last_batch_tasks"],
                    batch_classes=ts["last_batch_classes"],
                    spillbacks=ts["spillbacks"],
                    no_capacity=ts["spillbacks_no_capacity"],
                    locality_override=ts[
                        "spillbacks_locality_override"],
                    jnp_fallbacks=ts["jnp_fallbacks"],
                    dispatch_errors=ts["dispatch_errors"],
                    granted_local=ts["granted_local"],
                    requeued_busy=ts["requeued_busy"],
                    parked_infeasible=ts["parked_infeasible"],
                    spill_refused=ts["spill_refused"],
                    requeued_dispatch_failed=ts[
                        "requeued_dispatch_failed"],
                    is_feasible_anywhere_calls=ts[
                        "is_feasible_anywhere_calls"],
                    swept_batches=len(sweep),
                    oldest_lease_wait_ms=oldest_wait_ms)
                # Working ticks only (same gate as the span): idle
                # no-op ticks fire every event_loop_tick_ms and their
                # microsecond latencies would drown the signal the
                # 50 ms/tick budget is measured against.
                self.tick_stats["busy_ticks"] += 1
                observe_internal("ray_tpu.scheduler.tick_latency", dt,
                                 buckets=_TICK_BUCKETS,
                                 node=self._node_label)

    def _total_queued(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._queues.values())

    def _emit_scheduled(self, spec: TaskSpec):
        from ray_tpu.gcs import task_events
        task_events.emit(self._raylet.cluster, spec.task_id,
                         task_events.SCHEDULED,
                         node_id=self._raylet.node_id.hex())

    # A scheduled task is POPPED from its queue before its lease reply
    # fires, so any exception between the pop and the reply silently
    # loses the lease request: the submitter waits forever and the
    # caller's get() times out (the seed-era "lost dispatch" flake —
    # a rare exception on the tick thread, e.g. an import race or IO
    # error deep in a dispatch callback, was swallowed by the event
    # loop WITH the popped work).  Every pop->reply edge below
    # therefore runs through one of these guards, which requeue the
    # task on failure instead of unwinding the tick.

    def _dispatch_local(self, spec: TaskSpec, reply: Callable) -> bool:
        """Hand a locally-scheduled task to the dispatch path.  Returns
        False (never raises) when the handoff failed BEFORE the reply
        was registered — the caller requeues the task and returns its
        resource reservation."""
        try:
            fault_injection.hook("worker.dispatch")
            self._emit_scheduled(spec)
            self._raylet.local_task_manager.queue_and_schedule(spec, reply)
            self._dispatch_failures.pop(spec.task_id, None)
            return True
        except Exception:
            self.tick_stats["dispatch_errors"] += 1
            logger.exception("local dispatch of %s failed; requeueing",
                             spec.task_id)
            return False

    def _spillback_reason(self, spec: TaskSpec, cost_active: bool) -> str:
        """Classify a spillback: ``locality_override`` when a cost-aware
        solve moved a task the LOCAL node could run right now (the
        locality/heterogeneity terms chose a better-placed node);
        ``no_capacity`` otherwise (the local node simply can't take
        it).  Only HYBRID specs ride the cost-aware solve — a policy
        (SPREAD/affinity) spill in the same batch is never an
        override."""
        from ray_tpu.scheduler.policy import SchedulingType
        if not cost_active or spec.scheduling_options.scheduling_type \
                is not SchedulingType.HYBRID:
            return "no_capacity"
        node = self._raylet.cluster_view.node_resources(
            self._raylet.node_id)
        if node is not None and node.is_available(spec.resources):
            return "locality_override"
        return "no_capacity"

    def _reply_spillback(self, spec: TaskSpec, reply: Callable,
                         target, reason: str = "no_capacity") -> None:
        """Deliver a spillback reply; an exception inside the reply
        chain is counted but NOT requeued (the submitter may already
        have acted on it — task-level retries cover the remainder)."""
        try:
            self.tick_stats["spillbacks"] += 1
            self.tick_stats[f"spillbacks_{reason}"] += 1
            reply({"retry_at": target})
        except Exception:
            self.tick_stats["dispatch_errors"] += 1
            logger.exception("spillback reply for %s failed",
                             spec.task_id)

    def _requeue(self, spec: TaskSpec, reply: Callable) -> None:
        # Capped: a dispatch path that fails DETERMINISTICALLY (wedged
        # worker pool, persistent fault) must escalate to the submitter
        # as a rejection, not livelock the tick loop in an endless
        # pop -> fail -> requeue -> re-post cycle.
        n = self._dispatch_failures.get(spec.task_id, 0) + 1
        self._dispatch_failures[spec.task_id] = n
        if n > _MAX_DISPATCH_REQUEUES:
            self._dispatch_failures.pop(spec.task_id, None)
            try:
                reply({"rejected": True,
                       "reason": f"local dispatch failed {n} times"})
            except Exception:
                logger.exception("dispatch-failure reply for %s failed",
                                 spec.task_id)
            return
        with self._lock:
            self._queues[spec.scheduling_class].append((spec, reply))
        self.request_tick()

    def _resolve_batch_backlog(self, swept) -> None:
        """Withdraw swept batches' entries the scheduling pass left
        behind: still in ``_queues`` = feasible but no capacity this
        tick (``backlog`` — the submitter keeps the task client-side
        and re-pumps on its next progress edge); parked in
        ``_infeasible`` = no node's totals fit (``infeasible`` — the
        submitter re-leases it through the SINGLE-lease path, which
        parks at the raylet exactly like today so the autoscaler's
        ``resource_load`` demand stays visible until the cluster
        changes)."""
        if not swept:
            return
        swept_set = set(swept)
        withdrawn = []
        with self._lock:
            for queues, infeasible in ((self._queues, False),
                                       (self._infeasible, True)):
                for q in queues.values():
                    if not q:
                        continue
                    kept = [(spec, rep) for spec, rep in q
                            if not (isinstance(rep, _BatchEntry) and
                                    rep.batch in swept_set)]
                    if len(kept) != len(q):
                        withdrawn.extend(
                            (rep, infeasible) for _s, rep in q
                            if isinstance(rep, _BatchEntry) and
                            rep.batch in swept_set)
                        q.clear()
                        q.extend(kept)
        for rep, infeasible in withdrawn:
            result = {"backlog": True}
            if infeasible:
                result["infeasible"] = True
            try:
                rep(result)
            except Exception:
                self.tick_stats["dispatch_errors"] += 1
                logger.exception("batch backlog reply failed")

    def _schedule_greedy(self):
        """Reference-parity greedy loop: per class, per task, pick the best
        node, dispatch locally or spill back."""
        view = self._raylet.cluster_view
        local_id = self._raylet.node_id
        while True:
            progress = False
            with self._lock:
                classes = [c for c, q in self._queues.items() if q]
            for cls in classes:
                while True:
                    with self._lock:
                        q = self._queues[cls]
                        if not q:
                            break
                        spec, reply = q[0]
                    target = policy_mod.schedule(
                        view, spec.resources, spec.scheduling_options,
                        local_node_id=local_id)
                    if target is None:
                        with self._lock:
                            if self._queues[cls] and \
                                    self._queues[cls][0][0] is spec:
                                self._queues[cls].popleft()
                                self._infeasible[cls].append((spec, reply))
                                self.tick_stats["parked_infeasible"] += 1
                        progress = True
                        continue
                    if target == local_id:
                        # Reserve local resources at decision time (the
                        # view's local row IS the authoritative
                        # NodeResources), then hand to the local dispatch
                        # path; released when the worker lease returns.
                        if not view.subtract(local_id, spec.resources):
                            # Feasible but not currently available: leave
                            # queued; freed resources re-run the tick.
                            break
                        with self._lock:
                            if not (self._queues[cls] and
                                    self._queues[cls][0][0] is spec):
                                view.add_back(local_id, spec.resources)
                                continue
                            self._queues[cls].popleft()
                        if self._dispatch_local(spec, reply):
                            self.tick_stats["granted_local"] += 1
                        else:
                            view.add_back(local_id, spec.resources)
                            self._requeue(spec, reply)
                            self.tick_stats[
                                "requeued_dispatch_failed"] += 1
                        progress = True
                    else:
                        if not view.subtract(target, spec.resources):
                            # Stale view: couldn't commit; park and retry.
                            break
                        with self._lock:
                            if not (self._queues[cls] and
                                    self._queues[cls][0][0] is spec):
                                view.add_back(target, spec.resources)
                                continue
                            self._queues[cls].popleft()
                        # Spillback (ScheduleOnNode :285): tell the lessee
                        # to retry at the chosen raylet.  The dirty
                        # subtract above stops this tick from spilling
                        # everything to the same node; the broadcast
                        # corrects it.
                        self._reply_spillback(spec, reply, target)
                        progress = True
            if not progress:
                return

    def _arg_locality_bytes(self, specs) -> Dict:
        """Per-node argument bytes for a class's queued specs — the
        arg-locality cost signal.  Sizes and locations come from the
        object directory (the owner registers both when a big object
        lands in a node store); small inlined args have no directory
        row and correctly contribute nothing — they copy anywhere for
        free.  Called by the device solver only for classes whose specs
        actually carry object-ref args."""
        directory = getattr(self._raylet.cluster, "object_directory", None)
        if directory is None or not hasattr(directory, "size_hint"):
            return {}
        out: Dict = {}
        for spec in specs:
            for oid in spec.arg_object_ids():
                size = directory.size_hint(oid)
                if not size:
                    continue
                for nid in directory.get_locations(oid):
                    out[nid] = out.get(nid, 0) + size
        return out

    def _schedule_batched(self) -> bool:
        """Solve all queues in one device call (scheduler_backend=jax).

        The solver session keeps avail/total device-resident between
        ticks (dirty-row deltas only, ``DeviceRuntimeSolver``); per tick
        only the per-class counts go down and a validated sparse
        assignment comes back.  NOTE the within-bucket fill order
        diverges from the reference's strict min-utilization pick (see
        jax_backend module docstring) — every grant below is still
        re-validated against the exact fixed-point vectors.
        """
        from ray_tpu.scheduler import jax_backend
        from ray_tpu.util import tracing
        if self._jax_solver is None:
            self._jax_solver = jax_backend.DeviceRuntimeSolver(
                node_label=self._raylet.node_id.hex()[:12],
                locality_provider=self._arg_locality_bytes)
        view = self._raylet.cluster_view
        with tracing.span("scheduler.collect", category="sched"), \
                self._lock:
            work: list = []
            for cls, q in self._queues.items():
                work.extend(q)
                q.clear()
        if not work:
            return True
        self.tick_stats["last_batch_tasks"] = len(work)
        self.tick_stats["last_batch_classes"] = len(
            {spec.scheduling_class for spec, _ in work})
        try:
            with tracing.span("scheduler.solve", category="sched"):
                assignments = self._jax_solver.solve(
                    view, [spec for spec, _ in work])
        except Exception:
            # The solver guards its device path internally, but the
            # whole batch was already POPPED — any escaped exception
            # (e.g. the non-hybrid fallback leg) must not take the
            # popped lease requests down with it.
            logger.exception("batched solve failed; requeueing batch")
            assignments = None
        if assignments is None:
            # Device solve failed — put everything back for greedy.
            with self._lock:
                for spec, reply in work:
                    self._queues[spec.scheduling_class].append((spec, reply))
            return False
        with tracing.span("scheduler.reply", category="sched"):
            self._reply_batch(view, work, assignments)
        return True

    def _reply_batch(self, view, work, assignments) -> None:
        """Answer every entry of a solved batch: validate against the
        exact vectors, then dispatch, spill or requeue.  100,000 entries
        a tick at the north-star scale, so there is no span and no
        flight record per entry: the kinds are counted in locals and
        folded into ``tick_stats`` once."""
        granted = busy = infeasible = refused = failed = anywhere = 0
        local_id = self._raylet.node_id
        # LOCAL grants commit first (view.subtract), remote spills after:
        # _spillback_reason checks "could the local node still run this
        # task" and must see THIS tick's local reservations, or a batch
        # where cost terms are live would mislabel ordinary
        # capacity-competition spillbacks as locality_override.
        ordered = sorted(
            zip(work, assignments),
            key=lambda wa: 0 if wa[1] == local_id else 1)
        for (spec, reply), target in ordered:
            if target is None:
                # The device solve yields None for can't-place-THIS-TICK,
                # which conflates busy (no availability right now) with
                # structurally infeasible (no node's TOTAL fits).  Only
                # the latter may park in _infeasible — that queue is
                # retried solely on cluster-membership changes, so a
                # merely-busy task parked there stalls until an
                # unrelated broadcast rescues it (or forever).
                feasible_somewhere = view.is_feasible_anywhere(
                    spec.resources)
                anywhere += 1
                with self._lock:
                    if feasible_somewhere:
                        self._queues[spec.scheduling_class].append(
                            (spec, reply))
                        busy += 1
                    else:
                        self._infeasible[spec.scheduling_class].append(
                            (spec, reply))
                        infeasible += 1
            elif target == local_id:
                if not view.subtract(local_id, spec.resources):
                    with self._lock:
                        self._queues[spec.scheduling_class].append(
                            (spec, reply))
                    refused += 1
                    continue
                if self._dispatch_local(spec, reply):
                    granted += 1
                else:
                    view.add_back(local_id, spec.resources)
                    self._requeue(spec, reply)
                    failed += 1
            else:
                # Validate against the exact vectors before committing the
                # spill (kernel output validated by IsSchedulable,
                # SURVEY.md §7.4).
                node = view.node_resources(target)
                if node is not None and node.is_feasible(spec.resources):
                    self._reply_spillback(
                        spec, reply, target,
                        self._spillback_reason(
                            spec, self._jax_solver.last_cost_active))
                else:
                    with self._lock:
                        self._queues[spec.scheduling_class].append(
                            (spec, reply))
                    refused += 1
        ts = self.tick_stats
        ts["granted_local"] += granted
        ts["requeued_busy"] += busy
        ts["parked_infeasible"] += infeasible
        ts["spill_refused"] += refused
        ts["requeued_dispatch_failed"] += failed
        ts["is_feasible_anywhere_calls"] += anywhere

    # ---- introspection --------------------------------------------------
    def num_queued(self) -> int:
        with self._lock:
            return (sum(len(q) for q in self._queues.values()) +
                    sum(len(q) for q in self._infeasible.values()))

    def resource_load(self) -> list:
        """Pending per-task resource demands (queued + infeasible), the
        raylet's contribution to the autoscaler's demand vector
        (reference: ResourcesData.resource_load_by_shape)."""
        with self._lock:
            out = []
            for q in list(self._queues.values()) + \
                    list(self._infeasible.values()):
                out.extend(spec.resources.to_dict() for spec, _ in q)
            return out

    def debug_state(self) -> dict:
        with self._lock:
            return {
                "queued": {c: len(q) for c, q in self._queues.items() if q},
                "infeasible": {c: len(q) for c, q in self._infeasible.items()
                               if q},
            }
