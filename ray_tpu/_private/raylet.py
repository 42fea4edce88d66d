"""The per-node daemon: scheduler + worker pool + object store.

Parity: reference ``src/ray/raylet/node_manager.cc`` (NodeManager implements
the NodeManagerService: RequestWorkerLease/ReturnWorker (:1629), PG bundle
2PC, periodic ``ScheduleAndDispatchTasks`` tick (:392-394), debug dump) and
``src/ray/raylet/main.cc`` (raylet process = plasma store in-process +
NodeManager).  Here a Raylet is an in-process object with its own event loop
and worker threads; the lease/return/2PC surface is identical so a gRPC
transport can be slotted in front of it for multi-host deployments.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

from ray_tpu._private import fault_injection
from ray_tpu._private.config import get_config
from ray_tpu._private.cluster_task_manager import ClusterTaskManager
from ray_tpu._private.event_loop import EventLoop
from ray_tpu._private.ids import NodeID, PlacementGroupID
from ray_tpu._private.local_object_manager import LocalObjectManager
from ray_tpu._private.local_task_manager import LocalTaskManager
from ray_tpu._private.object_manager import NodeObjectManager
from ray_tpu._private.object_store import NodeObjectStore
from ray_tpu._private.task_spec import TaskSpec
from ray_tpu._private.worker_pool import WorkerPool
from ray_tpu.scheduler.bundle_packing import bundle_resource_names
from ray_tpu.scheduler.resources import (
    ClusterResourceView, NodeResources, ResourceRequest, _quantize)


class Raylet:
    def __init__(self, cluster, resources: Dict[str, float],
                 node_name: str = "", labels: Optional[Dict] = None,
                 object_store_memory: Optional[int] = None):
        cfg = get_config()
        self.cluster = cluster
        self.node_id = NodeID.from_random()
        self.node_name = node_name or f"node-{self.node_id.hex()[:8]}"
        #: Monotonic registration incarnation, minted by the GCS node
        #: manager at register time (incarnation fencing).  None until
        #: registered; preserved across GCS restarts via reconcile.
        self.incarnation: Optional[int] = None
        self.local_resources = NodeResources(resources, labels=labels)
        self.cluster_view = ClusterResourceView()   # local (dirty) view
        self.loop = EventLoop(f"raylet-{self.node_id.hex()[:6]}")
        store_capacity = object_store_memory or cfg.object_store_memory
        spill_dir = f"{cfg.temp_dir}/spill/{self.node_id.hex()[:8]}"
        self.object_store = NodeObjectStore(
            self.node_id,
            store_capacity,
            spill_dir=spill_dir,
            spill_threshold=cfg.object_spilling_threshold,
            native_backend=_maybe_native_store(cfg, store_capacity),
            on_spilled=self._record_spilled_url)
        # Async spill IO thread (local_object_manager parity): moves
        # over-threshold spilling off the put path and feeds the
        # create-request queue.
        self.local_object_manager = LocalObjectManager(
            self.object_store, spill_dir,
            node_label=self.node_id.hex()[:12])
        self.object_store.attach_spill_manager(self.local_object_manager)
        self.worker_pool = WorkerPool(self)
        self.local_task_manager = LocalTaskManager(self)
        self.cluster_task_manager = ClusterTaskManager(self)
        self.object_manager = NodeObjectManager(self, cluster.object_directory)
        self.core_worker = None      # wired by the cluster/driver
        # Lease-protocol round-trip counters (plain bumps on the hot
        # path, rendered by the tick collector): the dispatch fast
        # path's "a 500-task burst costs dozens of RPCs, not 500" claim
        # is asserted against lease_requests + lease_batch_requests.
        self.lease_stats = {"lease_requests": 0,
                            "lease_batch_requests": 0,
                            "lease_batch_entries": 0}
        self._dead = False
        self._host_stats = None
        self._host_stats_ts = 0.0
        # Bundles: (pg_id, idx) -> ResourceRequest, prepared or committed.
        self._prepared_bundles: Dict = {}
        self._committed_bundles: Dict = {}
        # Periodic scheduling tick (node_manager.cc:392-394).
        self.loop.schedule_every(cfg.event_loop_tick_ms / 1000.0,
                                 self.cluster_task_manager.schedule_and_dispatch,
                                 "raylet.schedule_tick")
        # Heartbeats to GCS on a DEDICATED thread: the event loop runs
        # callbacks serially, so one long callback (a big serialization,
        # a compile) would delay beats behind it and a loaded box could
        # miss num_heartbeats_timeout in a row — a false node death.
        # The reference raylet also heartbeats off its main dispatch
        # path (gcs_heartbeat_manager.h).
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop,
            args=(cfg.raylet_heartbeat_period_milliseconds / 1000.0,),
            daemon=True,
            name=f"ray_tpu::hb::{self.node_id.hex()[:6]}")
        self._hb_thread.start()
        # Seed own view.
        self.cluster_view.add_node(self.node_id, self.local_resources)

    # ---- GCS-facing -----------------------------------------------------
    def node_info(self) -> dict:
        return {
            "node_id": self.node_id.hex(),
            "node_name": self.node_name,
            "alive": True,
            "resources": self.local_resources.to_float_dict("total"),
            "labels": dict(self.local_resources.labels),
        }

    def get_resource_report(self) -> dict:
        report = {
            "available": self.local_resources.to_float_dict("available"),
            "total": self.local_resources.to_float_dict("total"),
            "load": {"queued": self.cluster_task_manager.num_queued(),
                     "dispatch": self.local_task_manager.num_queued()},
            # Outbound-transfer load (sessions/queue/in-flight bytes):
            # the head folds this into directory answers so pullers can
            # spread across the least-loaded sources (load-aware source
            # selection for collective broadcasts).
            "transfer_load":
                self.object_store.transfer_ledger.load_snapshot(),
        }
        # Physical stats ride the report the node already sends
        # (reference: reporter agent -> GCS), throttled to ~1 Hz.
        import time as time_mod
        now = time_mod.monotonic()
        if now - self._host_stats_ts >= 1.0:
            try:
                from ray_tpu.dashboard.reporter import collect_host_stats
                self._host_stats = collect_host_stats()
                self._host_stats_ts = now
            except Exception:
                pass
        if self._host_stats is not None:
            report["host_stats"] = self._host_stats
        return report

    def update_resource_usage(self, batch: dict):
        """Apply the GCS broadcast to the local (dirty) view
        (grpc_based_resource_broadcaster parity).

        Batch format: ``{"rows": {node_id: usage}, "full": bool,
        "removed": [node_id]}`` — a DELTA upserts its rows only; a FULL
        snapshot additionally prunes nodes absent from it; explicit
        removals (node death/dereg) arrive in ``removed`` so deltas
        never have to enumerate the whole membership
        (ray_syncer.h:37-66)."""
        if self._dead:
            return
        rows = batch.get("rows", batch)     # legacy plain-dict = full
        is_full = batch.get("full", "rows" not in batch)
        removed = batch.get("removed", ())
        known = set(self.cluster_view.node_ids())
        for node_id, usage in rows.items():
            if node_id == self.node_id:
                continue
            if node_id not in known:
                nr = NodeResources(usage["total"])
                nr.available = {k: _quantize(v)
                                for k, v in usage["available"].items()}
                self.cluster_view.add_node(node_id, nr)
                self.cluster_task_manager.on_cluster_changed()
            else:
                self.cluster_view.update_available(node_id,
                                                   usage["available"])
        gone = set(removed) & known
        if is_full:
            gone |= known - set(rows.keys()) - {self.node_id}
        for node_id in gone:
            self.cluster_view.remove_node(node_id)
        # Suspect membership (suspect-before-dead): mask those nodes in
        # the local scheduling view — no NEW placements there until
        # their beats resume.  Includes self: a node the GCS suspects
        # (e.g. its outbound link is cut) stops self-placing too.
        suspect = batch.get("suspect")
        if suspect is not None:
            self.cluster_view.set_masked(set(suspect))
        self.cluster_task_manager.on_cluster_changed()

    def _record_spilled_url(self, object_id, url: str):
        """Spill callback: record the spilled_url with the owner's
        reference counter (the reconstruction/debug surface the
        reference keeps in the ObjectDirectory/owner table).

        Posted to the event loop, never taken inline: the store invokes
        this callback while HOLDING its lock, and the reference
        counter's delete path runs its subscribers (which take the
        store lock) while holding the refcount lock — recording
        inline would be an ABBA deadlock between a spill publish and a
        concurrent last-ref drop."""
        core = self.core_worker or self.cluster.core_worker
        if core is None:
            return

        def record():
            try:
                core.reference_counter.set_spilled_url(object_id, url)
            except Exception as e:
                # A lost spilled_url silently breaks restore-from-disk
                # for this object later — count it (graftcheck R7).
                from ray_tpu._private.debug import swallow
                swallow.noted("raylet.record_spilled_url", e)
        self.loop.post(record, "raylet.record_spilled_url")

    def _heartbeat(self):
        if not self._dead:
            # Chaos point: an injected error/delay here simulates a
            # partitioned or wedged node (missed beats -> declared
            # dead) without killing the process.  ctx carries the node
            # so in-process multi-node tests can cut ONE node's beats.
            fault_injection.hook("node.heartbeat",
                                 node=self.node_id.hex()[:12])
            self.cluster.gcs.heartbeat_manager.heartbeat(self.node_id)

    def _heartbeat_loop(self, period_s: float):
        import time as time_mod

        from ray_tpu._private.debug import swallow
        while not self._dead:
            try:
                self._heartbeat()
            except Exception as e:
                # The sender must survive a flapping GCS link, but a
                # silently-failing heartbeat loop looks exactly like a
                # healthy one until the node is declared dead —
                # count/log it (graftcheck R7).
                swallow.noted("raylet.heartbeat", e)
            time_mod.sleep(period_s)

    # ---- lease protocol (NodeManagerService) ----------------------------
    def request_worker_lease(self, spec: TaskSpec, reply: Callable):
        """HandleRequestWorkerLease (node_manager.cc:1629)."""
        if self._dead:
            reply({"rejected": True, "reason": "node dead"})
            return
        self.lease_stats["lease_requests"] += 1
        self.cluster_task_manager.queue_and_schedule(spec, reply)

    def request_worker_lease_batch(self, specs, reply: Callable):
        """Batched HandleRequestWorkerLease: lease up to len(specs)
        workers of one scheduling class in ONE round-trip.  ``reply``
        fires once with ``{"results": [...]}`` ordered like ``specs``;
        each result is a grant (``worker``/``raylet``), a spillback
        (``retry_at``), a rejection, or ``backlog`` (feasible but no
        capacity this tick — the submitter keeps the task and re-pumps;
        with ``infeasible: True`` it re-leases through the single-lease
        path, which parks raylet-side until the cluster changes)."""
        if self._dead:
            reply({"results": [{"rejected": True, "reason": "node dead"}
                               for _ in specs]})
            return
        self.lease_stats["lease_batch_requests"] += 1
        self.lease_stats["lease_batch_entries"] += len(specs)
        try:
            # Chaos point: bounce a WHOLE batch (the submitter must
            # fall back to single leases without burning task retries).
            fault_injection.hook("worker.lease_batch")
        except Exception as e:
            reply({"results": [{"rejected": True, "batch_fault": True,
                                "reason": f"lease batch fault: {e}"}
                               for _ in specs]})
            return
        self.cluster_task_manager.queue_and_schedule_batch(specs, reply)

    def return_worker(self, worker, disconnect: bool = False):
        """HandleReturnWorker: release lease + resources."""
        self.local_task_manager.release_worker_resources(worker)
        if disconnect:
            worker.stop()
        else:
            self.worker_pool.push_worker(worker)
        # A freed worker slot may unblock the dispatch queue.
        self.loop.post(self.local_task_manager.dispatch, "local.dispatch")

    def on_actor_worker_exit(self, actor_id, worker_id):
        self.local_task_manager.release_worker_resources(
            _WorkerIdHolder(worker_id))
        self.cluster.gcs.actor_manager.on_actor_worker_died(
            actor_id, "worker exited")

    # ---- placement group 2PC (node_manager.proto:319-330) ---------------
    def prepare_bundle_resources(self, pg_id: PlacementGroupID, idx: int,
                                 req: ResourceRequest) -> bool:
        if self._dead:
            return False
        if (pg_id, idx) in self._prepared_bundles or \
                (pg_id, idx) in self._committed_bundles:
            return True
        if not self.local_resources.allocate(req):
            return False
        self._prepared_bundles[(pg_id, idx)] = req
        return True

    def commit_bundle_resources(self, pg_id: PlacementGroupID, idx: int,
                                req: ResourceRequest):
        self._prepared_bundles.pop((pg_id, idx), None)
        self._committed_bundles[(pg_id, idx)] = req
        # Add the formatted PG resources to this node (bundle_spec.h).
        formatted = bundle_resource_names(pg_id, idx, req)
        for name, amount in formatted.items():
            q = _quantize(amount)
            self.local_resources.total[name] = \
                self.local_resources.total.get(name, 0) + q
            self.local_resources.available[name] = \
                self.local_resources.available.get(name, 0) + q
        self.cluster_view.update_node(self.node_id, self.local_resources)
        self.cluster_task_manager.on_cluster_changed()

    def cancel_resource_reserve(self, pg_id: PlacementGroupID, idx: int):
        req = self._prepared_bundles.pop((pg_id, idx), None)
        if req is not None:
            self.local_resources.release(req)
            return
        req = self._committed_bundles.pop((pg_id, idx), None)
        if req is None:
            return
        formatted = bundle_resource_names(pg_id, idx, req)
        for name, amount in formatted.items():
            q = _quantize(amount)
            self.local_resources.total[name] = max(
                0, self.local_resources.total.get(name, 0) - q)
            self.local_resources.available[name] = max(
                0, self.local_resources.available.get(name, 0) - q)
            if self.local_resources.total.get(name) == 0:
                self.local_resources.total.pop(name, None)
                self.local_resources.available.pop(name, None)
        self.local_resources.release(req)
        self.cluster_view.update_node(self.node_id, self.local_resources)

    # ---- lifecycle ------------------------------------------------------
    def kill(self):
        """Simulated hard node death (chaos testing: NodeKillerActor
        parity) — stops heartbeating and drops all state."""
        self._dead = True
        self.worker_pool.shutdown()
        self.object_manager.stop()
        self.local_object_manager.stop()
        self.loop.stop()

    def shutdown(self):
        if self._dead:
            return
        self._dead = True
        self.cluster.gcs.unregister_raylet(self.node_id)
        self.worker_pool.shutdown()
        self.object_manager.stop()
        self.local_object_manager.stop()
        self.loop.stop()

    def debug_string(self) -> str:
        return (f"Raylet {self.node_name} ({self.node_id.hex()[:8]}): "
                f"res={self.local_resources.to_float_dict('available')} "
                f"queues={self.cluster_task_manager.debug_state()} "
                f"workers={self.worker_pool.num_total()} "
                f"objects={self.object_store.num_objects()}")


class _WorkerIdHolder:
    __slots__ = ("worker_id",)

    def __init__(self, worker_id):
        self.worker_id = worker_id


_native_store_failed = False


def _maybe_native_store(cfg, capacity_bytes: int = 0):
    """Load the native C++ shm store if built (ray_tpu/native).

    The segment is sized to the node store's capacity (clamped to the
    free space actually available on /dev/shm): a segment smaller than
    the store forced every large put onto the python-held fallback path
    — and through its extra flatten copy (a 1.44 GB/s put on the
    50-host soak's 1-core CPU box).
    tmpfs pages are allocated on first touch, so an over-provisioned
    segment costs nothing until objects actually land in it."""
    global _native_store_failed
    if not cfg.use_native_object_store or _native_store_failed:
        return None
    capacity = capacity_bytes or cfg.object_store_memory
    try:
        from ray_tpu.native import shm_store
    except Exception:
        _native_store_failed = True
        return None
    try:
        import shutil
        # tmpfs pages are first-touch, so df-free does not reflect other
        # open sparse segments; subtract this process's outstanding
        # reservations and keep a 4x headroom for sibling processes —
        # over-committed segments die with SIGBUS when filled, not with
        # a catchable error.
        shm_free = shutil.disk_usage("/dev/shm").free \
            - shm_store.reserved_bytes()
        capacity = max(64 * 1024 * 1024, min(capacity, shm_free // 4))
    except Exception:
        pass
    try:
        return shm_store.open_store(capacity=capacity)
    except Exception:
        _native_store_failed = True
        return None
