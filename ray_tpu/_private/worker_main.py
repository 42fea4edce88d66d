"""Standalone worker process: ``python -m ray_tpu._private.worker_main``.

Parity: reference worker processes started by the raylet's pool
(``src/ray/raylet/worker_pool.h:428`` StartWorkerProcess spawns
``python/ray/_private/workers/default_worker.py``, which registers back
over the raylet socket and then serves ``CoreWorkerService.PushTask``,
``core_worker.proto:353``).

Protocol here (framed RPC, ray_tpu.rpc):
  1. start an RpcServer on an ephemeral port serving push/stop;
  2. connect to the raylet host service and ``register_worker`` with
     (worker_id, port) — the handshake the pool's ProcessWorker waits on;
  3. each ``push`` request executes one task: args arrive inline
     (serialized blobs) or as object ids fetched from the raylet host via
     ``get_object``; function blobs are fetched from the GCS KV via
     ``kv_get`` and cached; serialized return values ride back in the
     reply (the host stores them with owner semantics).

Task bodies get the FULL public API: after registration the process's
global worker is wired to the host via ``client_runtime`` (the
reference's in-worker CoreWorker role), so nested ``.remote`` calls,
``put/get/wait``, actor creation/lookup/kill all work from inside a
process-mode task.
"""

from __future__ import annotations

import argparse
import os
import pickle
import sys
import threading
import traceback
from typing import Dict, Optional

from ray_tpu import exceptions
from ray_tpu._private.debug.lock_order import diag_lock
from ray_tpu._private.serialization import (
    SerializedObject, deserialize, loads_function, serialize,
    serialize_into)
from ray_tpu.rpc import RpcClient, RpcServer

_SHM_MISS = object()
# Returns below this ride the reply socket (the owner memory-store
# inline path wants them anyway); above it they go through the segment.
_SHM_RETURN_MIN = 100 * 1024


class _ShmReturnWriter:
    """serialize_into writer for the write-through-shm return path
    (plasma Create/Seal): ``reserve`` asks the host for a segment
    block, the serializer fills it through this process's mapping (the
    single data copy — no intermediate flattened bytes), ``commit``
    seals it host-side.  Declines small values (they ride the reply
    socket, which the owner memory-store inline path wants anyway) and
    cleans up its own reservation on any failure, so a False outcome
    simply means "use the socket fallback"."""

    __slots__ = ("_runtime", "_oid_bin", "_off")

    def __init__(self, runtime: "_WorkerRuntime", oid_bin: bytes):
        self._runtime = runtime
        self._oid_bin = oid_bin
        self._off = None

    def reserve(self, nbytes: int):
        shm = self._runtime._shm
        if shm is None or nbytes <= _SHM_RETURN_MIN:
            return None
        try:
            off = self._runtime.node_client.call(
                "shm_create", {"object_id": self._oid_bin,
                               "size": nbytes}, timeout=30.0)
        except Exception:
            return None
        if off is None:
            return None
        self._off = int(off)
        return shm.view(self._off, nbytes)

    def commit(self, _serialized, nbytes: int) -> bool:
        try:
            if self._runtime.node_client.call(
                    "shm_seal", {"object_id": self._oid_bin,
                                 "size": nbytes}, timeout=30.0):
                return True
        except Exception:
            pass
        self._abort_reservation()
        return False

    def abort(self, _exc) -> None:
        self._abort_reservation()

    def _abort_reservation(self) -> None:
        # The write/seal failed mid-way: the reservation is invisible
        # to eviction — abort it host-side or it leaks.
        if self._off is None:
            return
        try:
            self._runtime.node_client.call_async(
                "shm_abort", {"object_id": self._oid_bin},
                lambda _r, _e: None)
        except Exception:
            pass


class _CtxSpec:
    """Task-context slice for runtime_context inside the child (the host
    ships the relevant spec fields in the push payload)."""

    def __init__(self, payload):
        from ray_tpu.scheduler.resources import ResourceRequest
        self.task_id = payload.get("task_id")
        self.actor_id = payload.get("actor_id")
        self.task_type = payload.get("task_type", "NORMAL_TASK")
        self.resources = ResourceRequest(payload.get("resources") or {})
        lr = payload.get("lifetime_resources")
        self.lifetime_resources = \
            ResourceRequest(lr) if lr is not None else None
        self.depth = 0
        self.function_name = payload.get("function_name", "")
        self.placement_group_id = payload.get("placement_group_id")
        self.placement_group_bundle_index = \
            payload.get("placement_group_bundle_index", -1)

    def is_actor_creation(self) -> bool:
        return self.task_type == "ACTOR_CREATION_TASK"

    def is_actor_task(self) -> bool:
        return self.task_type == "ACTOR_TASK"


class _WorkerRuntime:
    def __init__(self, host: str, port: int, worker_id: str):
        self.worker_id = worker_id
        self.node_client = RpcClient((host, port))
        self.server = RpcServer(name=f"worker-{worker_id[:8]}")
        self.server.register_async("push", self._handle_push)
        self.server.register("ping", lambda _p: "pong")
        self.server.register("stop", self._handle_stop)
        self._fn_cache: Dict[bytes, object] = {}
        self.actor_instance = None
        self._sema: Optional[threading.Semaphore] = None
        # Per-concurrency-group bounds (concurrency_group_manager.cc).
        self._group_semas: Dict[str, threading.Semaphore] = {}
        self._order_lock = diag_lock("WorkerServer._order_lock")
        self._stop_event = threading.Event()
        # Plasma-client mapping of the node's shm segment (metadata via
        # node_client RPC, bytes through this mmap — zero-copy).
        self._shm = None

    def _attach_shm(self):
        try:
            info = self.node_client.call("shm_info", None, timeout=10.0)
            if info:
                from ray_tpu.native.shm_store import AttachedSegment
                self._shm = AttachedSegment(info["name"],
                                            info["capacity"])
        except Exception:
            self._shm = None

    def run(self):
        # Nested-.remote support: wire this process's global worker to
        # the host BEFORE registering — a task can be pushed the moment
        # registration lands (client_runtime — the reference's in-worker
        # CoreWorker role).
        from ray_tpu._private import client_runtime
        client_runtime.install(self.node_client,
                               client_worker_id=self.worker_id)
        # Attach the segment BEFORE registering: a task can be pushed
        # the moment registration lands, and it must find the mapping.
        self._attach_shm()
        self.node_client.call("register_worker", {
            "worker_id": self.worker_id,
            "port": self.server.address[1],
            "pid": os.getpid(),
        })

        # Orphan watchdog: if the host process dies without a clean
        # "stop", exit rather than linger (reference: workers die with
        # their raylet).
        def watchdog():
            while not self._stop_event.is_set():
                try:
                    self.node_client.call("ping", None, timeout=10.0)
                except Exception:
                    self._stop_event.set()
                    return
                self._stop_event.wait(timeout=5.0)

        threading.Thread(target=watchdog, daemon=True,
                         name="ray_tpu::worker::watchdog").start()
        self._stop_event.wait()
        self.server.stop()

    # ---- execution -----------------------------------------------------
    def _handle_stop(self, _payload):
        self._stop_event.set()
        return True

    def _handle_push(self, payload, reply):
        kind = payload["kind"]
        sema = None
        if kind == "actor_task":
            group = payload.get("concurrency_group") or ""
            sema = self._group_semas.get(group, self._sema)
        if sema is not None:
            sema.acquire()
            try:
                reply(self._execute(payload))
            finally:
                sema.release()
        else:
            reply(self._execute(payload))

    def _execute(self, payload) -> dict:
        from ray_tpu._private import worker_context
        from ray_tpu.util import tracing
        prev_ctx = worker_context.get_context()
        worker_context.set_context(worker_context.ExecutionContext(
            task_spec=_CtxSpec(payload), node=None, worker=None))
        trace_ctx = payload.get("trace_ctx")
        pinned: list = []
        out: dict
        try:
            with tracing.span(
                    f"execute:{payload.get('function_name', '?')}",
                    category="execute", parent=trace_ctx,
                    force=bool(trace_ctx)):
                kind = payload["kind"]
                # Actor calls (and creation) copy shm args out of the
                # mapping so their pins can be released at frame end —
                # an arg kept as actor state must not reference a
                # region the host could evict once unpinned.  Normal
                # tasks stay zero-copy (args die with the frame).
                args, kwargs = self._resolve_args(
                    payload["args"], pinned, copy_shm=(kind != "task"))
                if kind == "create_actor":
                    cls = self._load_function(payload["function_key"])
                    self.actor_instance = cls(*args, **kwargs)
                    n = max(1, int(payload.get("max_concurrency", 1)))
                    self._sema = threading.Semaphore(n)
                    for gname, gsize in (
                            payload.get("concurrency_groups")
                            or {}).items():
                        self._group_semas[gname] = threading.Semaphore(
                            max(1, int(gsize)))
                    out = {"error": None, "returns": []}
                elif kind == "actor_task":
                    if self.actor_instance is None:
                        raise exceptions.RayTpuError(
                            "actor not initialized")
                    method = getattr(self.actor_instance,
                                     payload["actor_method_name"])
                    result = method(*args, **kwargs)
                    out = {"error": None,
                           "returns": self._pack_returns(payload, result)}
                else:
                    fn = self._load_function(payload["function_key"])
                    result = fn(*args, **kwargs)
                    out = {"error": None,
                           "returns": self._pack_returns(payload, result)}
        except Exception as e:  # noqa: BLE001 — user errors cross the wire
            err = exceptions.TaskError(
                e, task_desc=f"{payload.get('function_name', '?')}"
                             f"[process-worker]")
            try:
                blob = pickle.dumps(err)
            except Exception:
                blob = pickle.dumps(exceptions.RayTpuError(
                    "".join(traceback.format_exception(e))))
            out = {"error": blob, "returns": []}
        finally:
            worker_context.set_context(prev_ctx)
            # Every kind releases its pins at frame end: normal-task
            # args died with the frame (zero-copy views included), and
            # actor creation/call args were copied out of the mapping
            # above.  Holding pins for an actor's lifetime permanently
            # pinned every large shm arg a long-lived actor ever took.
            if pinned:
                self._release_pins(pinned)
        if trace_ctx:
            # Ship locally-recorded spans back on the reply (ProfileEvent
            # batching parity) — the driver's pool ingests them.
            out["trace"] = tracing.drain()
        return out

    def _resolve_args(self, packed, pinned, copy_shm: bool = False):
        from ray_tpu._private.executor import _split_args
        flat = []
        for kind, data in packed:
            if kind == "inline":
                flat.append(deserialize(SerializedObject.from_bytes(data)))
                continue
            value = self._shm_get(data, pinned, copy=copy_shm)
            if value is not _SHM_MISS:
                flat.append(value)
                continue
            blob = self.node_client.call("get_object", data, timeout=30.0)
            if blob is None:
                raise exceptions.ObjectLostError(
                    data.hex(), "arg not available on host node")
            flat.append(deserialize(SerializedObject.from_bytes(blob)))
        return _split_args(flat)

    def _shm_get(self, oid_bin: bytes, pinned: list, copy: bool = False):
        """Arg read through the segment (plasma client Get): locate
        pins the object host-side, bytes come from the read-only
        mapping.  ``copy=False`` (normal tasks) keeps zero-copy — the
        deserialized arrays reference the mapping and the pin holds
        until task end.  ``copy=True`` (actor creation/calls) snapshots
        the bytes first so the value survives the pin release at frame
        end.  Every pin key lands in ``pinned``."""
        if self._shm is None:
            return _SHM_MISS
        try:
            loc = self.node_client.call(
                "shm_locate", {"object_id": oid_bin,
                               "worker_id": self.worker_id},
                timeout=30.0)
        except Exception:
            return _SHM_MISS
        if loc is None:
            return _SHM_MISS
        pinned.append(oid_bin)
        view = self._shm.read(int(loc[0]), int(loc[1]))
        if copy:
            view = bytes(view)
        return deserialize(SerializedObject.from_bytes(view))

    def _release_pins(self, pinned: list):
        for oid_bin in pinned:
            try:
                self.node_client.call_async(
                    "shm_release", {"object_id": oid_bin,
                                    "worker_id": self.worker_id},
                    lambda _r, _e: None)
            except Exception:
                pass

    def _pack_returns(self, payload, result):
        num = payload["num_returns"]
        if num == 0:
            return []
        values = [result] if num == 1 else list(result)
        if len(values) != num:
            raise ValueError(
                f"task returned {len(values)} values, expected {num}")
        out = []
        for oid_bin, value in zip(payload["return_ids"], values):
            # Single-copy return: serialize straight into the mapped
            # segment when the host grants a reservation (sealed
            # host-side, nothing crosses the socket); otherwise the
            # SAME SerializedObject rides the reply socket flattened.
            serialized, in_shm = serialize_into(
                value, _ShmReturnWriter(self, oid_bin))
            out.append((oid_bin, None if in_shm
                        else serialized.to_bytes()))
        return out

    def _load_function(self, key: bytes):
        fn = self._fn_cache.get(key)
        if fn is None:
            blob = self.node_client.call("kv_get", key, timeout=30.0)
            if blob is None:
                raise KeyError(f"function blob missing for {key!r}")
            fn = loads_function(blob)
            self._fn_cache[key] = fn
        return fn


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--worker-id", required=True)
    args = parser.parse_args(argv)
    # Runtime-env working_dir: the spawner materialized it and points us
    # at it (reference: worker started inside its env's directory).
    cwd = os.environ.get("RAY_TPU_WORKER_CWD")
    if cwd:
        os.chdir(cwd)
        sys.path.insert(0, cwd)
    if os.environ.get("RAY_TPU_TRACING") == "1":
        from ray_tpu.util import tracing
        tracing.enable()
    runtime = _WorkerRuntime(args.host, args.port, args.worker_id)
    runtime.run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
