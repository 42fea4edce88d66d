"""The in-process cluster: GCS + raylets + object directory.

Parity: reference ``python/ray/cluster_utils.py:100`` (``Cluster`` — multi
node without real machines: extra raylets/GCS as local entities with
distinct node ids; ``add_node`` :166, ``remove_node`` :235) — the backbone
of the reference's multi-node test strategy (SURVEY.md §4a) and of this
framework's simulated deployments.  A real multi-host deployment replaces
the direct method calls with the gRPC transport in front of the same
Raylet/GcsServer surfaces.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from ray_tpu._private.config import get_config
from ray_tpu._private.ids import NodeID
from ray_tpu._private.object_manager import ObjectDirectory
from ray_tpu._private.raylet import Raylet
from ray_tpu.gcs.server import GcsServer
from ray_tpu._private.debug import diag_lock


class Cluster:
    def __init__(self, initialize_head: bool = True,
                 head_node_args: Optional[dict] = None,
                 gcs_storage_path: Optional[str] = None):
        self._gcs_storage_path = gcs_storage_path
        self.gcs = GcsServer(storage_path=gcs_storage_path)
        self.object_directory = ObjectDirectory()
        self._lock = diag_lock("Cluster._lock")
        self._raylets: List[Raylet] = []
        # EVERY in-process raylet ever created, including ones later
        # declared dead (heartbeat timeout) and dropped from
        # membership: shutdown must still stop their worker pools /
        # monitors, or process workers and log-monitor refs leak.
        self._ever_raylets: List[Raylet] = []
        self.head_node: Optional[Raylet] = None
        self.core_worker = None
        self.head_service = None          # wire front, started on demand
        self._remote_procs: List = []     # spawned NodeHost OS processes
        self.gcs.subscribe_node_death(self._on_node_death)
        if initialize_head:
            self.head_node = self.add_node(**(head_node_args or {}))

    # ---- membership -----------------------------------------------------
    @staticmethod
    def _assemble_totals(num_cpus=None, num_tpus=0.0, num_gpus=0.0,
                         memory=None, object_store_memory=None,
                         resources=None) -> Dict[str, float]:
        """One resource-dict builder for both in-process and remote
        nodes, so their defaults can never drift apart."""
        import os
        total: Dict[str, float] = {}
        total["CPU"] = float(num_cpus) if num_cpus is not None \
            else float(os.cpu_count() or 1)
        if num_tpus:
            total["TPU"] = float(num_tpus)
        if num_gpus:
            total["GPU"] = float(num_gpus)
        total["memory"] = memory if memory is not None else 4 * 1024**3
        total["object_store_memory"] = float(
            object_store_memory or get_config().object_store_memory)
        total.update(resources or {})
        return total

    def add_node(self, num_cpus: Optional[float] = None,
                 num_tpus: float = 0, num_gpus: float = 0,
                 memory: Optional[float] = None,
                 object_store_memory: Optional[int] = None,
                 resources: Optional[Dict[str, float]] = None,
                 node_name: str = "", labels: Optional[Dict] = None) -> Raylet:
        total = self._assemble_totals(num_cpus, num_tpus, num_gpus, memory,
                                      object_store_memory, resources)
        raylet = Raylet(self, total, node_name=node_name, labels=labels,
                        object_store_memory=object_store_memory)
        raylet.core_worker = self.core_worker
        with self._lock:
            self._raylets.append(raylet)
            self._ever_raylets.append(raylet)
        self.gcs.register_raylet(raylet)
        return raylet

    def adopt_raylet(self, raylet):
        """Register an externally-constructed raylet (a RemoteNodeProxy
        mirroring a NodeHost OS process) into the membership — the
        head-side half of NodeInfoGcsService.RegisterNode.  A
        re-registration of the same node id (a fenced node coming back
        as a fresh incarnation) REPLACES the stale mirror."""
        with self._lock:
            self._raylets = [r for r in self._raylets
                             if r.node_id != raylet.node_id]
            self._raylets.append(raylet)
            self._ever_raylets.append(raylet)
        self.gcs.register_raylet(raylet)

    def start_head_service(self, port: int = 0):
        """Start (once) the wire front that NodeHost processes join."""
        if self.head_service is None:
            from ray_tpu._private.head_service import HeadService
            self.head_service = HeadService(self, port=port)
        return self.head_service.address

    def add_remote_node(self, num_cpus: float = 1, num_tpus: float = 0,
                        num_gpus: float = 0,
                        memory: Optional[float] = None,
                        object_store_memory: Optional[int] = None,
                        resources: Optional[Dict[str, float]] = None,
                        node_name: str = "",
                        timeout: float = 30.0) -> "RemoteNodeHandle":
        """Spawn a worker-host OS process (``python -m
        ray_tpu._private.node_host``) and wait for it to register over
        TCP.  Reference: ``Cluster.add_node`` backed by a real raylet
        process instead of an in-process one.  The spawned process is
        matched by a one-shot registration token, so duplicate
        node_names cannot bind the handle to the wrong node."""
        return self.add_remote_nodes(
            [dict(num_cpus=num_cpus, num_tpus=num_tpus, num_gpus=num_gpus,
                  memory=memory, object_store_memory=object_store_memory,
                  resources=resources, node_name=node_name)],
            timeout=timeout)[0]

    def _spawn_node_host(self, spec: dict):
        """Spawn one NodeHost OS process; returns ``(proc, reg_token,
        name)`` without waiting for registration."""
        import json
        import subprocess
        import sys
        import uuid

        from ray_tpu._private.device_policy import child_env
        host, port = self.start_head_service()
        total = self._assemble_totals(
            spec.get("num_cpus", 1), spec.get("num_tpus", 0),
            spec.get("num_gpus", 0), spec.get("memory"),
            spec.get("object_store_memory"), spec.get("resources"))
        name = spec.get("node_name") or f"remote-{uuid.uuid4().hex[:8]}"
        reg_token = uuid.uuid4().hex
        # This process holds the chip (if any): the node host solves on
        # the CPU (device_policy — one process per chip).
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_host",
             "--head", f"{host}:{port}",
             "--resources", json.dumps(total),
             "--name", name,
             "--reg-token", reg_token,
             "--system-config", get_config().to_json()],
            env=child_env())
        return proc, reg_token, name

    def add_remote_nodes(self, specs, timeout: float = 60.0,
                         spawn_interval_s: float = 0.0
                         ) -> List["RemoteNodeHandle"]:
        """Spawn MANY NodeHost processes concurrently, then wait for
        them all to register.  Spawning everything before waiting is
        what makes a 50–64-host fleet stand up in seconds instead of
        serial spawn×poll round trips — and it deliberately produces
        the registration storm the head's admission gate
        (``head_registration_concurrency``) has to absorb.  On
        timeout/early-exit, already-spawned unregistered processes are
        killed and the error names the failing node."""
        import time

        entries = []           # (proc, reg_token, name, node_id|None)
        try:
            for spec in specs:
                proc, reg_token, name = self._spawn_node_host(spec)
                entries.append([proc, reg_token, name, None])
                if spawn_interval_s > 0:
                    time.sleep(spawn_interval_s)
            deadline = time.monotonic() + timeout
            pending = list(entries)
            while pending and time.monotonic() < deadline:
                still = []
                for e in pending:
                    node_id = self.head_service.node_id_for_token(e[1])
                    if node_id is not None:
                        e[3] = node_id
                        continue
                    if e[0].poll() is not None:
                        raise RuntimeError(
                            f"node_host {e[2]!r} exited with "
                            f"{e[0].returncode} before registering")
                    still.append(e)
                pending = still
                if pending:
                    time.sleep(0.02)
            if pending:
                raise TimeoutError(
                    f"{len(pending)}/{len(entries)} remote nodes failed "
                    f"to register within {timeout}s (first: "
                    f"{pending[0][2]!r})")
        except Exception:
            from ray_tpu._private.debug import swallow
            for proc, _tok, _name, node_id in entries:
                if node_id is None:
                    try:
                        proc.kill()
                    except Exception as kill_err:
                        swallow.noted("cluster.add_remote_nodes.kill",
                                      kill_err)
            raise
        handles = [RemoteNodeHandle(self, proc, node_id, name)
                   for proc, _tok, name, node_id in entries]
        with self._lock:
            self._remote_procs.extend(handles)
        return handles

    def remove_node(self, raylet: Raylet, graceful: bool = True):
        with self._lock:
            if raylet in self._raylets:
                self._raylets.remove(raylet)
        if graceful:
            raylet.shutdown()
        else:
            self.kill_node(raylet)

    def kill_node(self, raylet: Raylet):
        """Hard kill (no heartbeats, no dereg) — the GCS heartbeat manager
        declares it dead after num_heartbeats_timeout misses."""
        with self._lock:
            if raylet in self._raylets:
                self._raylets.remove(raylet)
        raylet.kill()

    def raylets(self) -> List[Raylet]:
        with self._lock:
            return list(self._raylets)

    # ---- driver wiring --------------------------------------------------
    def attach_core_worker(self, core_worker):
        self.core_worker = core_worker
        with self._lock:
            for r in self._raylets:
                r.core_worker = core_worker

    def _on_node_death(self, node_id: NodeID):
        with self._lock:
            self._raylets = [r for r in self._raylets
                             if r.node_id != node_id]
        lost = self.object_directory.on_node_death(node_id)
        if self.core_worker is not None:
            self.core_worker.on_node_death(node_id, lost)

    def shutdown(self):
        with self._lock:
            everyone = list(self._ever_raylets)
        from ray_tpu._private.debug import swallow
        for r in everyone:          # Raylet.shutdown is idempotent
            try:
                r.shutdown()
            except Exception as e:
                swallow.noted("cluster.shutdown_raylet", e)
        with self._lock:
            handles, self._remote_procs = self._remote_procs, []
        for h in handles:
            h.terminate()
        if self.head_service is not None:
            self.head_service.stop()
            self.head_service = None
        self.gcs.shutdown()
        try:
            # Clean shutdown: drop this (driver/head) process's crash
            # files — evidence already surfaced; the disk copy exists
            # for SIGKILL forensics, which this is not.
            from ray_tpu._private.debug import watchdog
            watchdog.prune_own_crash_files()
        except Exception as e:
            swallow.noted("cluster.wedge_prune", e)

    def restart_gcs(self):
        """Kill and restart the control plane over the same persistent
        storage, then reconcile it against the still-running raylets —
        the test surface of ``test_gcs_fault_tolerance.py``.  Requires a
        file-backed GCS (``gcs_storage_path``)."""
        if self._gcs_storage_path is None:
            raise ValueError("restart_gcs requires gcs_storage_path "
                             "(the in-memory store dies with the GCS)")
        self.gcs.shutdown()
        self.gcs = GcsServer(storage_path=self._gcs_storage_path)
        self.gcs.subscribe_node_death(self._on_node_death)
        self.gcs.reconcile(self.raylets())
        if self.core_worker is not None:
            self.core_worker.actor_submitter.on_gcs_restart()
        return self.gcs

    def proxy_for(self, node_id: NodeID):
        """The RemoteNodeProxy currently mirroring ``node_id`` (None for
        in-process raylets)."""
        raylet = self.gcs.raylet(node_id)
        return raylet if getattr(raylet, "is_remote_proxy", False) else None

    def wait_for_nodes(self, count: int, timeout: float = 10.0) -> bool:
        import time
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if len(self.gcs.node_manager.alive_nodes) >= count:
                return True
            time.sleep(0.01)
        return False


class RemoteNodeHandle:
    """Driver-side handle on a spawned NodeHost OS process."""

    def __init__(self, cluster: Cluster, proc, node_id: NodeID, name: str):
        self.cluster = cluster
        self.proc = proc
        self.node_id = node_id
        self.node_name = name

    @property
    def proxy(self):
        return self.cluster.proxy_for(self.node_id)

    def kill(self):
        """Hard kill the OS process: no dereg, no more heartbeats — the
        GCS declares the node dead after num_heartbeats_timeout misses
        (NodeKillerActor chaos parity, but with a real process)."""
        try:
            self.proc.kill()
            self.proc.wait(timeout=10.0)
        except Exception:
            pass

    def terminate(self):
        """Graceful stop: ask the node to shut down, then reap it."""
        proxy = self.proxy
        if proxy is not None:
            proxy.shutdown()
        try:
            self.proc.wait(timeout=5.0)
        except Exception:
            self.kill()
