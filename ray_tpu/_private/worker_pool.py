"""Per-node worker pool.

Parity: reference ``src/ray/raylet/worker_pool.{h,cc}`` — pool of
pre-startable workers, ``PopWorker`` (worker_pool.h:338) /
``PushWorker`` return, ``PrestartWorkers`` (:350), idle soft-cap with
eviction (ray_config_def.h:129), dedicated workers for actors.

Two worker modes behind one lease lifecycle
(``worker_process_mode`` config):

* ``thread`` (default) — workers are threads in the node's process.
  One process per host owns the TPU chips (XLA requires single
  ownership), so Python-level parallelism comes from threads — jax
  compiled computations release the GIL, and framework logic is
  IO-bound.
* ``process`` — workers are real OS processes
  (``python -m ray_tpu._private.worker_main``), spawned like the
  reference's ``StartWorkerProcess`` (worker_pool.h:428): the child
  registers back over a framed-RPC socket (``WorkerHostService``) and
  tasks are pushed to its own RPC server (``CoreWorkerService.PushTask``
  parity, core_worker.proto:353) — every task and object crosses a real
  process boundary.

The scheduler and transport layers are identical in both modes.
"""

from __future__ import annotations

import queue
import subprocess
import sys
import threading
import traceback
from typing import Callable, Dict, List, Optional

from ray_tpu import exceptions
from ray_tpu._private import worker_context
from ray_tpu._private.config import get_config
from ray_tpu._private.ids import ObjectID, WorkerID
from ray_tpu._private.debug import diag_lock, diag_rlock


class WorkerState:
    IDLE = "IDLE"
    LEASED = "LEASED"
    ACTOR = "ACTOR"
    DEAD = "DEAD"


class Worker:
    """One executor thread; may become dedicated to an actor."""

    runtime_env_hash = ""   # thread workers are universal: the env is
                            # applied per task in the executor

    def __init__(self, pool: "WorkerPool", node):
        self.worker_id = WorkerID.from_random()
        self.node = node
        self.node_id = node.node_id
        self._pool = pool
        self.state = WorkerState.IDLE
        self._queue: "queue.Queue" = queue.Queue()
        self.actor_id = None
        self.actor_instance = None
        self._actor_threads: List[threading.Thread] = []
        self._group_queues: Dict[str, "queue.Queue"] = {}
        self._max_concurrency = 1
        self._killed = threading.Event()
        self._thread = threading.Thread(
            target=self._main_loop, daemon=True,
            name=f"ray_tpu::worker::{self.worker_id.hex()[:8]}")
        self._thread.start()

    # ---- normal task path ----------------------------------------------
    def push_task(self, spec, on_done: Callable):
        """Execute a normal (or actor-creation) task on this worker
        (CoreWorkerService.PushTask parity)."""
        self._queue.put(("task", spec, on_done))

    def assign_actor(self, creation_spec, on_done: Callable):
        """Run the actor creation task; on success this worker is dedicated
        to the actor until death."""
        self._queue.put(("create_actor", creation_spec, on_done))

    def submit_actor_task(self, spec, on_done: Callable):
        """Ordered actor method execution (sequential_actor_submit_queue
        parity; max_concurrency>1 uses the out-of-order queue).  A
        method tagged with a concurrency group routes to that group's
        own pool (concurrency_group_manager.cc)."""
        group = getattr(spec, "concurrency_group", "")
        gq = self._group_queues.get(group) if group else None
        if gq is not None:
            gq.put(("actor_task", spec, on_done))
        else:
            self._queue.put(("actor_task", spec, on_done))

    def kill_actor(self):
        self._killed.set()
        self._queue.put(("exit", None, None))

    def stop(self):
        self._killed.set()
        self._queue.put(("exit", None, None))

    # ---- main loop ------------------------------------------------------
    def _main_loop(self):
        worker_context.set_context(
            worker_context.ExecutionContext(worker=self, node=self.node))
        from ray_tpu._private import executor as executor_mod
        while not self._killed.is_set():
            try:
                kind, spec, on_done = self._queue.get(timeout=1.0)
            except queue.Empty:
                continue
            if kind == "exit":
                break
            try:
                if kind == "create_actor":
                    self._handle_create_actor(spec, on_done, executor_mod)
                elif kind == "actor_task":
                    self._run_actor_task(spec, on_done, executor_mod)
                else:
                    ok, err = executor_mod.execute_task(
                        spec, self.node, self.node.core_worker)
                    on_done(None if ok else err)
            except Exception as e:  # framework error, not user error
                traceback.print_exc()
                if on_done is not None:
                    on_done(exceptions.RayTpuError(str(e)))
            # Drop the frame's bindings: an idle worker must not pin the
            # last spec (its inline args hold live ObjectRefs — keeping
            # them would defer the owner's release indefinitely).
            kind = spec = on_done = None
        self._on_exit()

    def _handle_create_actor(self, spec, on_done, executor_mod):
        ok, result = executor_mod.execute_task(
            spec, self.node, self.node.core_worker)
        if not ok:
            on_done(result)
            return
        self.state = WorkerState.ACTOR
        self.actor_id = spec.actor_id
        self.actor_instance = result
        self._max_concurrency = max(1, spec.max_concurrency)
        if self._max_concurrency > 1:
            for i in range(self._max_concurrency - 1):
                t = threading.Thread(target=self._actor_concurrent_loop,
                                     daemon=True,
                                     name=f"{self._thread.name}::cc{i}")
                t.start()
                self._actor_threads.append(t)
        # Named concurrency groups: each gets its own queue + thread
        # pool, concurrent with the default group and each other
        # (concurrency_group_manager.cc parity).
        for gname, gsize in (spec.concurrency_groups or {}).items():
            gq: "queue.Queue" = queue.Queue()
            self._group_queues[gname] = gq
            for i in range(max(1, int(gsize))):
                t = threading.Thread(
                    target=self._actor_concurrent_loop, args=(gq,),
                    daemon=True,
                    name=f"{self._thread.name}::cg-{gname}-{i}")
                t.start()
                self._actor_threads.append(t)
        on_done(None)

    def _run_actor_task(self, spec, on_done, executor_mod):
        ok, err = executor_mod.execute_task(
            spec, self.node, self.node.core_worker,
            actor_instance=self.actor_instance)
        on_done(None if ok else err)

    def _actor_concurrent_loop(self, source: "queue.Queue" = None):
        worker_context.set_context(
            worker_context.ExecutionContext(worker=self, node=self.node))
        from ray_tpu._private import executor as executor_mod
        src = source if source is not None else self._queue
        while not self._killed.is_set():
            try:
                kind, spec, on_done = src.get(timeout=1.0)
            except queue.Empty:
                continue
            if kind == "exit":
                src.put(("exit", None, None))  # propagate to siblings
                break
            self._run_actor_task(spec, on_done, executor_mod)
            kind, spec, on_done = None, None, None  # no idle-frame pinning

    def _on_exit(self):
        was_actor = self.state == WorkerState.ACTOR
        self.state = WorkerState.DEAD
        self._pool.on_worker_exit(self)
        if was_actor and self.actor_id is not None:
            self.node.on_actor_worker_exit(self.actor_id, self.worker_id)


class WorkerHostService:
    """Raylet-side RPC service that process-mode workers talk to:
    registration handshake, object reads for task args, and function-blob
    fetches from the GCS KV (reference: the raylet socket workers register
    on + plasma UDS + GCS function table, collapsed into one surface)."""

    def __init__(self, node):
        from ray_tpu.rpc import RpcServer
        self._node = node
        self._lock = diag_lock("WorkerHostService._lock")
        self._ports: Dict[str, int] = {}
        self._events: Dict[str, threading.Event] = {}
        self._worker_pins: Dict[str, list] = {}
        self._shm_pins: Dict[str, list] = {}
        # Orders seal against abort: each RPC runs on its own dispatch
        # thread, and abort's locate-then-delete must not interleave
        # with a concurrent seal of the same key (the sealed-object
        # guard would read stale state and delete a live object).
        self._shm_seal_lock = diag_lock("WorkerHostService._shm_seal_lock")
        self.shm_locate_count = 0    # observability/tests
        self.server = RpcServer(
            name=f"workerhost-{node.node_id.hex()[:6]}")
        self.server.register("register_worker", self._register_worker)
        self.server.register("ping", lambda _p: "pong")
        self.server.register("get_object", self._get_object)
        self.server.register("kv_get", self._kv_get)
        # Plasma-client surface (plasma/client.cc parity): metadata over
        # RPC, bytes through the worker's own mmap of the segment.
        self.server.register("shm_info", self._shm_info)
        self.server.register("shm_locate", self._shm_locate)
        self.server.register("shm_release", self._shm_release)
        self.server.register("shm_create", self._shm_create)
        self.server.register("shm_seal", self._shm_seal)
        self.server.register("shm_abort", self._shm_abort)
        # Client-runtime surface: process-mode workers drive the full
        # public API (nested .remote, put/get/wait, actors) through the
        # SAME handlers remote drivers use (client_service.py), with
        # ownership kept by the host's core worker.  Big get_value
        # replies ride chunk sessions.
        from ray_tpu._private.client_service import register_client_surface
        from ray_tpu._private.worker import global_worker_or_none
        from ray_tpu._private.object_store import segment_chunk_source
        from ray_tpu.rpc.chunked import serve_chunks
        self._chunk_server = serve_chunks(
            self.server,
            lambda oid_bin: self._get_object(oid_bin),
            get_source=segment_chunk_source(node.object_store))

        def _namespace():
            w = global_worker_or_none()
            return getattr(w, "namespace", "") if w else ""

        register_client_surface(
            self.server,
            core=self._core,
            kv=node.cluster.gcs.kv,
            actor_manager=lambda: self._node.cluster.gcs.actor_manager,
            node_id_fn=lambda: self._node.node_id,
            namespace_fn=_namespace,
            chunk_server=self._chunk_server,
            pin_cb=self._record_pin)

    @property
    def port(self) -> int:
        return self.server.address[1]

    def wait_for_worker(self, worker_id_hex: str,
                        timeout: float) -> Optional[int]:
        with self._lock:
            ev = self._events.setdefault(worker_id_hex, threading.Event())
        if not ev.wait(timeout=timeout):
            return None
        with self._lock:
            return self._ports.get(worker_id_hex)

    def _register_worker(self, payload) -> bool:
        wid = payload["worker_id"]
        with self._lock:
            self._ports[wid] = payload["port"]
            ev = self._events.setdefault(wid, threading.Event())
        ev.set()
        return True

    def _get_object(self, oid_bin: bytes) -> Optional[bytes]:
        from ray_tpu._private.serialization import SerializedObject
        oid = ObjectID(oid_bin)
        serialized = self._node.object_store.get_serialized(oid)
        if serialized is not None:
            return serialized.to_bytes()
        core = self._node.core_worker
        if core is not None:
            e = core.memory_store.get_entry(oid)
            if e is not None and e.sealed and e.error is None and \
                    isinstance(e.data, SerializedObject):
                return e.data.to_bytes()
        return None

    def _kv_get(self, key: bytes) -> Optional[bytes]:
        return self._node.cluster.gcs.kv.get(key)

    # ---- shm client surface (plasma/client.cc parity) ------------------
    def _native_store(self):
        store = self._node.object_store
        native = getattr(store, "_native", None)
        return store, native

    def _shm_info(self, _payload):
        _store, native = self._native_store()
        if native is None:
            return None
        return {"name": native.name, "capacity": native.capacity}

    def _shm_locate(self, payload):
        """(offset, size) of a sealed object; pins it (store-level AND
        native) against eviction/spill while the worker reads through
        its mapping.  Pin BEFORE reading the offset: native.pin fails
        if the object was just freed, and once it succeeds the block
        cannot move — so the returned (offset, size) can never be
        stale.  The worker releases its pins at the end of every task
        frame (actor calls copy the bytes out first); worker death
        releases whatever a crashed worker still held."""
        store, native = self._native_store()
        if native is None:
            return None
        oid = ObjectID(payload["object_id"])
        entry = store.get(oid)
        from ray_tpu._private.object_store import _NativeHandle
        if entry is None or not isinstance(entry.data, _NativeHandle):
            return None
        store.pin(oid)                       # blocks python-side spill
        if not native.pin(payload["object_id"]):
            store.unpin(oid)                 # freed in the window
            return None
        loc = native.locate(payload["object_id"])
        if loc is None:
            native.unpin(payload["object_id"])
            store.unpin(oid)
            return None
        with self._lock:
            self._shm_pins.setdefault(payload["worker_id"], []).append(oid)
            self.shm_locate_count += 1
        return list(loc)

    def _shm_release(self, payload):
        store, native = self._native_store()
        oid = ObjectID(payload["object_id"])
        with self._lock:
            pins = self._shm_pins.get(payload["worker_id"])
            if not pins or oid not in pins:
                return False      # not pinned by this worker: no-op
            pins.remove(oid)
        store.unpin(oid)
        if native is not None:
            native.unpin(payload["object_id"])
        return True

    def _shm_abort(self, payload):
        """Drop a create-reservation whose write/seal failed — unsealed
        entries are invisible to eviction and would leak forever.

        Reclaims ONLY unsealed reservations: the worker fires abort on
        any mid-write exception, including a timeout on a seal reply
        that actually LANDED host-side — by then the object is sealed,
        registered in the node store and locatable by other readers, so
        deleting it here would corrupt a live object."""
        _store, native = self._native_store()
        if native is None:
            return False
        key = payload["object_id"]
        with self._shm_seal_lock:
            if native.locate(key) is not None:
                return False  # sealed: the seal won the race, keep it
            native.delete(key)
        return True

    def _shm_create(self, payload):
        """Reserve space for a worker-written return value; the worker
        fills the bytes through its own mapping, then shm_seal.  Runs
        the store's eviction-retry reservation (create_request_queue.h
        flow), so a full segment spills LRU victims instead of kicking
        the return onto the socket path."""
        store, native = self._native_store()
        if native is None:
            return None
        return store.reserve_native(ObjectID(payload["object_id"]),
                                    int(payload["size"]))

    def _shm_seal(self, payload):
        """Seal a worker-written object and register it in the node
        store with owner semantics (the big-return path of
        _store_returns, minus the socket copy)."""
        from ray_tpu._private.object_store import InPlasmaMarker
        store, native = self._native_store()
        if native is None:
            return False
        key = payload["object_id"]
        with self._shm_seal_lock:
            if not native.seal(key):
                return False
        oid = ObjectID(key)
        size = int(payload["size"])
        store.register_native_entry(oid, size)
        self._node.cluster.object_directory.add_location(
            oid, self._node.node_id, size=size)
        core = self._node.core_worker
        if core is not None:
            core.memory_store.put(oid, InPlasmaMarker(self._node.node_id))
        return True

    def release_worker_shm_pins(self, worker_id_hex: str):
        store, native = self._native_store()
        with self._lock:
            oids = self._shm_pins.pop(worker_id_hex, [])
        from ray_tpu._private.debug import swallow
        for oid in oids:
            try:
                store.unpin(oid)
                if native is not None:
                    native.unpin(oid.binary())
            except Exception as e:
                # A lost unpin wedges eviction of that object forever.
                swallow.noted("worker_pool.release_shm_pin", e)

    def _core(self):
        core = self._node.core_worker
        if core is None:
            raise RuntimeError("host node has no core worker attached")
        return core

    def _record_pin(self, worker_id_hex: str, object_id):
        with self._lock:
            self._worker_pins.setdefault(worker_id_hex, []).append(
                object_id)

    def release_worker_pins(self, worker_id_hex: str):
        """Drop the put-object pins a (cleanly exited) worker
        accumulated."""
        with self._lock:
            oids = self._worker_pins.pop(worker_id_hex, [])
        core = self._node.core_worker
        if core is None:
            return
        for oid in oids:
            try:
                core.reference_counter.remove_local_ref(oid)
            except Exception:
                pass

    def fail_worker_owned_objects(self, worker_id_hex: str):
        """Owner-death semantics for a CRASHED worker process: objects
        it put are invalidated with OwnerDiedError so borrowers holding
        the refs observe the death instead of ObjectLost-after-timeout
        (reference: reference_count.cc OWNER_DIED; clean exits release
        pins normally via :meth:`release_worker_pins`)."""
        from ray_tpu import exceptions as exc
        with self._lock:
            oids = self._worker_pins.pop(worker_id_hex, [])
        core = self._node.core_worker
        if core is None:
            return
        for oid in oids:
            try:
                core.fail_owned_object(oid, exc.OwnerDiedError(oid))
            except Exception:
                pass

    def stop(self):
        self.server.stop()


class ProcessWorker:
    """A worker living in its own OS process; same interface as Worker.

    Host side of the lease lifecycle: spawns the child (StartWorkerProcess
    parity), waits for its registration on the WorkerHostService, then
    pushes tasks over the child's RPC server and stores the returned
    serialized values with owner semantics."""

    def __init__(self, pool: "WorkerPool", node, runtime_env=None):
        self.worker_id = WorkerID.from_random()
        self.node = node
        self.node_id = node.node_id
        self._pool = pool
        self.state = WorkerState.IDLE
        self.actor_id = None
        self.actor_instance = None      # lives in the child process
        self._max_concurrency = 1
        self._killed = threading.Event()
        self._died_abnormally = False   # crash vs clean stop/cull
        self._queue: "queue.Queue" = queue.Queue()
        self._client = None
        host = pool.host_service()
        from ray_tpu._private import runtime_env as runtime_env_mod
        self.runtime_env_hash = (runtime_env or {}).get("_hash", "")
        # Materialize working_dir/py_modules host-side, inject env
        # vars + import paths + cwd at spawn (worker_pool.h:428:
        # workers are started FOR an env and keyed by its hash).  The
        # worker is pinned to the CPU unless its runtime_env says
        # otherwise (device_policy — one process per chip).
        from ray_tpu._private.device_policy import child_env
        env = child_env(runtime_env_mod.materialize(
            runtime_env, node.cluster.gcs.kv) if runtime_env else None)
        # Unbuffered child stdio: prints must reach the tailed log file
        # as they happen, not on 8KB block-buffer flushes at exit.
        env["PYTHONUNBUFFERED"] = "1"
        from ray_tpu._private.config import get_config
        if get_config().tracing_enabled:
            # A traced run traces its process workers too: beyond the
            # forced per-task execute span, spans recorded around it
            # (puts, gets, nested calls) ride the task-reply drain.
            env["RAY_TPU_TRACING"] = "1"
        # Child stdout/stderr land in per-worker session log files; the
        # pool's LogMonitor tails them and streams lines to the driver
        # (reference log_monitor.py + worker stdout redirection).
        from ray_tpu._private import log_monitor as log_monitor_mod
        out_f, err_f = log_monitor_mod.open_worker_log_files(
            self.worker_id.hex())
        pool.ensure_log_monitor()
        try:
            self._proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.worker_main",
                 "--host", "127.0.0.1", "--port", str(host.port),
                 "--worker-id", self.worker_id.hex()],
                env=env, stdout=out_f, stderr=err_f)
        finally:
            # The child owns its copies of the fds now.
            out_f.close()
            err_f.close()
        self._pump = threading.Thread(
            target=self._pump_loop, daemon=True,
            name=f"ray_tpu::pworker::{self.worker_id.hex()[:8]}")
        self._pump.start()

    # ---- Worker interface ----------------------------------------------
    def push_task(self, spec, on_done: Callable):
        self._queue.put(("task", spec, on_done))

    def assign_actor(self, creation_spec, on_done: Callable):
        self._queue.put(("create_actor", creation_spec, on_done))

    def submit_actor_task(self, spec, on_done: Callable):
        self._queue.put(("actor_task", spec, on_done))

    def kill_actor(self):
        self.stop()

    def stop(self):
        self._killed.set()
        self._queue.put(("exit", None, None))

        # The pump may be blocked inside a roundtrip for a long-running
        # task; don't leave the OS process orphaned behind it.
        def reap():
            try:
                self._proc.wait(timeout=5.0)
            except Exception:
                try:
                    self._proc.terminate()
                    self._proc.wait(timeout=5.0)
                except Exception:
                    try:
                        self._proc.kill()
                    except Exception:
                        pass

        threading.Thread(target=reap, daemon=True,
                         name="ray_tpu::reap::"
                              + self.worker_id.hex()[:8]).start()

    # ---- pump ----------------------------------------------------------
    def _pump_loop(self):
        from ray_tpu.rpc import RpcClient
        # Generous: on a loaded small box, N concurrently spawned
        # children serialize their interpreter+numpy imports.
        port = self._pool.host_service().wait_for_worker(
            self.worker_id.hex(), timeout=120.0)
        if port is None:
            self._died_abnormally = True
            self._fail_until_exit("worker process failed to register")
            return
        self._client = RpcClient(("127.0.0.1", port))
        while not self._killed.is_set():
            try:
                kind, spec, on_done = self._queue.get(timeout=1.0)
            except queue.Empty:
                # Liveness sweep between pushes: a child that died while
                # idle (crash, OOM-kill) must trigger owner-death
                # handling promptly, not on the next task push.  Drain
                # anything enqueued in the detection window first — an
                # abandoned spec's on_done would otherwise never fire
                # and the submitter's get would hang.
                if self._proc.poll() is not None:
                    self._died_abnormally = True
                    self._killed.set()
                    self._drain_queue_failing(
                        "worker process died while idle")
                    break
                continue
            if kind == "exit":
                break
            if kind == "actor_task" and (
                    self._max_concurrency > 1 or
                    getattr(spec, "concurrency_group", "")):
                # Out-of-order queue parity: up to max_concurrency calls
                # in flight (group-tagged calls bound by their group's
                # semaphore in the child); replies on the client reader.
                self._emit_running(spec)
                fut = self._client.call_future(
                    "push", self._build_payload(kind, spec))
                fut.add_done_callback(
                    lambda f, s=spec, cb=on_done, k=kind:
                    self._on_reply_future(f, s, cb, k))
                continue
            self._roundtrip(kind, spec, on_done)
        self._on_exit()

    def _emit_running(self, spec):
        """Host-side RUNNING transition: the push to the child's RPC
        server is the moment the task starts executing in the worker OS
        process (the child has no path to the GCS event buffer)."""
        from ray_tpu.gcs import task_events
        task_events.emit(self.node.cluster, spec.task_id,
                         task_events.RUNNING,
                         node_id=self.node_id.hex(),
                         worker_id=self.worker_id.hex())

    def _roundtrip(self, kind, spec, on_done):
        self._emit_running(spec)
        try:
            reply = self._client.call("push",
                                      self._build_payload(kind, spec),
                                      timeout=None)
        except Exception as e:
            on_done(exceptions.RayTpuError(
                f"worker process died: {e}"))
            self._died_abnormally = True
            self._killed.set()
            return
        self._handle_reply(reply, spec, on_done, kind)

    def _on_reply_future(self, fut, spec, on_done, kind):
        err = fut.exception()
        if err is not None:
            on_done(exceptions.RayTpuError(f"worker process died: {err}"))
            self._died_abnormally = True
            self._killed.set()
            return
        self._handle_reply(fut.result(), spec, on_done, kind)

    def _handle_reply(self, reply, spec, on_done, kind):
        import pickle
        if reply.get("trace"):
            from ray_tpu.util import tracing
            tracing.ingest(reply["trace"])
        err_blob = reply.get("error")
        if err_blob is not None:
            try:
                err = pickle.loads(err_blob)
            except Exception:
                err = exceptions.RayTpuError("undecodable worker error")
            on_done(err)
            return
        self._store_returns(reply["returns"])
        if kind == "create_actor":
            self.state = WorkerState.ACTOR
            self.actor_id = spec.actor_id
            self._max_concurrency = max(1, spec.max_concurrency)
        on_done(None)

    def _build_payload(self, kind, spec) -> dict:
        from ray_tpu._private.function_manager import _KV_PREFIX
        args = []
        for a in spec.args:
            if a.is_inline:
                args.append(("inline", a.value.to_bytes()))
            else:
                args.append(("ref", a.object_id.binary()))
        fn_key = None
        if spec.function_id is not None:
            fn_key = _KV_PREFIX + spec.function_id.binary()
        return {
            "kind": kind,
            "trace_ctx": getattr(spec, "trace_ctx", None),
            "concurrency_group": getattr(spec, "concurrency_group", ""),
            "concurrency_groups": getattr(spec, "concurrency_groups",
                                          None),
            "function_key": fn_key,
            "function_name": spec.function_name,
            "actor_method_name": spec.actor_method_name,
            "num_returns": spec.num_returns,
            "return_ids": [oid.binary() for oid in spec.return_ids],
            "max_concurrency": spec.max_concurrency,
            "args": args,
            # Context for runtime_context inside the child.
            "task_id": spec.task_id,
            "actor_id": spec.actor_id,
            "resources": spec.resources.to_dict(),
            "placement_group_id": spec.placement_group_id,
            "placement_group_bundle_index":
                spec.placement_group_bundle_index,
            "lifetime_resources":
                spec.lifetime_resources.to_dict()
                if spec.lifetime_resources is not None else None,
            "task_type": spec.task_type,
        }

    def _store_returns(self, returns):
        from ray_tpu._private.serialization import SerializedObject
        core = self.node.core_worker
        for oid_bin, blob in returns:
            oid = ObjectID(oid_bin)
            if blob is None:
                # Worker wrote the value through the shm segment; the
                # host's shm_seal handler already registered the store
                # entry, directory location and memory-store marker.
                continue
            # Owner-correct return storage for BOTH node flavors: the
            # head's CoreWorker seals its own memory store; a spoke's
            # core shim ships small returns to the owner over the wire
            # (put_inline) and directory-registers big ones.
            core.put_serialized_return(
                oid, SerializedObject.from_bytes(blob), self.node)

    def _drain_queue_failing(self, reason: str):
        """Fail every spec currently queued (non-blocking drain)."""
        while True:
            try:
                kind, _spec, on_done = self._queue.get_nowait()
            except queue.Empty:
                return
            if kind != "exit" and on_done is not None:
                on_done(exceptions.RayTpuError(reason))

    def _fail_until_exit(self, reason: str):
        while not self._killed.is_set():
            try:
                kind, _spec, on_done = self._queue.get(timeout=1.0)
            except queue.Empty:
                continue
            if kind == "exit":
                break
            if on_done is not None:
                on_done(exceptions.RayTpuError(reason))
        self._on_exit()

    def _on_exit(self):
        was_actor = self.state == WorkerState.ACTOR
        self.state = WorkerState.DEAD
        host = self._pool._host_service
        if host is not None:
            try:
                if self._died_abnormally:
                    # Crash: the worker OWNED its put objects — seal
                    # OwnerDiedError for borrowers (reference:
                    # OWNER_DIED), then drop whatever it still pinned.
                    host.fail_worker_owned_objects(self.worker_id.hex())
                else:
                    host.release_worker_pins(self.worker_id.hex())
                host.release_worker_shm_pins(self.worker_id.hex())
            except Exception:
                pass
        if self._client is not None:
            try:
                self._client.call("stop", None, timeout=2.0)
            except Exception:
                pass
            self._client.close()
        try:
            self._proc.terminate()
            self._proc.wait(timeout=5.0)
        except Exception:
            try:
                self._proc.kill()
            except Exception:
                pass
        self._pool.on_worker_exit(self)
        if was_actor and self.actor_id is not None:
            self.node.on_actor_worker_exit(self.actor_id, self.worker_id)


# ---- process-wide startup gate (startup-storm throttle) -----------------
# Per-node caps (`maximum_startup_concurrency`) bound ONE pool; a 64-node
# envelope on a shared box is 64 pools spawning at once.  This gate caps
# workers in startup across every pool in the OS process
# (`worker_global_startup_concurrency`); a pop over the cap returns None
# and the dispatch tick retries — exactly the per-node cap's contract,
# applied fleet-wide.  Lock order: WorkerPool._lock may be held when the
# gate is taken, never the reverse.
_global_start_lock = diag_lock("worker_pool._global_start_lock")
_global_starting = 0
_global_throttled = 0


def _acquire_global_start_slots(n: int) -> int:
    """Claim up to ``n`` startup slots; returns how many were granted
    (0 when the gate is saturated).  Shortfall counts as throttling.
    The in-flight counter moves even with the gate disabled, so an
    acquire/release pair stays symmetric across a config flip."""
    global _global_starting, _global_throttled
    if n <= 0:
        return 0
    cap = get_config().worker_global_startup_concurrency
    with _global_start_lock:
        granted = n if cap <= 0 else \
            max(0, min(n, cap - _global_starting))
        _global_starting += granted
        if granted < n:
            _global_throttled += n - granted
    return granted


def _release_global_start_slots(n: int):
    global _global_starting
    if n <= 0:
        return
    with _global_start_lock:
        _global_starting = max(0, _global_starting - n)


def global_startup_in_flight() -> int:
    with _global_start_lock:
        return _global_starting


def global_startup_throttled() -> int:
    """Cumulative pops/prestarts deferred by the process-wide gate."""
    with _global_start_lock:
        return _global_throttled


class WorkerPool:
    def __init__(self, node):
        self._node = node
        # RLock: pop_worker holds it while constructing a ProcessWorker,
        # whose __init__ re-enters via host_service().
        self._lock = diag_rlock("WorkerPool._lock")
        self._idle: List[Worker] = []
        self._leased: Dict[WorkerID, Worker] = {}
        self._actors: Dict[WorkerID, Worker] = {}
        self._all: Dict[WorkerID, Worker] = {}
        cfg = get_config()
        # Total cap is the runaway backstop; maximum_startup_concurrency
        # throttles concurrent SPAWNS, it is not a total cap
        # (worker_pool.h:428 semantics — 10k dedicated actor workers
        # must be reachable).
        self._max_workers = cfg.max_workers_per_node
        self._max_starting = cfg.maximum_startup_concurrency
        self._starting = 0
        self._soft_limit = cfg.num_workers_soft_limit
        self._process_mode = cfg.worker_process_mode == "process"
        self._host_service: Optional[WorkerHostService] = None
        self._log_monitor = None

    def ensure_log_monitor(self):
        """Hold a reference on this process's (singleton) log-file
        tailer, which streams worker log lines into the ``worker_logs``
        pubsub channel.  No-op when no publisher is reachable."""
        with self._lock:
            if self._log_monitor:
                return
            gcs = getattr(getattr(self._node, "cluster", None), "gcs",
                          None)
            publisher = getattr(gcs, "publisher", None)
            if publisher is None:
                return
            from ray_tpu._private import log_monitor as log_monitor_mod
            log_monitor_mod.acquire_local_monitor(publisher)
            self._log_monitor = True

    def host_service(self) -> WorkerHostService:
        with self._lock:
            if self._host_service is None:
                self._host_service = WorkerHostService(self._node)
            return self._host_service

    def _new_worker(self, runtime_env=None):
        if self._process_mode:
            return ProcessWorker(self, self._node, runtime_env=runtime_env)
        return Worker(self, self._node)

    def prestart_workers(self, n: int):
        """Construct outside the lock (same rule as pop_worker: a
        process-mode spawn must not stall concurrent lease traffic)."""
        with self._lock:
            capacity = self._max_workers - len(self._all) - self._starting
            count = max(0, min(n, capacity,
                               self._max_starting - self._starting))
            count = _acquire_global_start_slots(count)
            self._starting += count
        stagger = get_config().worker_startup_stagger_ms / 1000.0
        created = []
        try:
            for i in range(count):
                if i and stagger > 0:
                    # Ramp, don't spike: only this background path
                    # sleeps (prestart runs on a throwaway thread).
                    import time
                    time.sleep(stagger)
                created.append(self._new_worker())
        finally:
            _release_global_start_slots(count)
            with self._lock:
                self._starting -= count
                for w in created:
                    self._all[w.worker_id] = w
                    self._idle.append(w)

    def prestart_for_backlog(self, depth: int, bound: int) -> int:
        """Predictive warm-worker prestart (``PrestartWorkers``,
        worker_pool.h:350): bring idle+starting up to
        ``min(depth, bound)`` workers ahead of ``pop_worker`` so a
        queued burst doesn't pay worker startup one task at a time on
        the dispatch path.  The construction runs on a throwaway daemon
        thread — a process-mode spawn storm must block neither the
        raylet loop nor the submitting thread.  Returns the shortfall
        this call saw (0 = pool already warm enough).  Leased workers
        count as serving the backlog (they cycle back through reuse),
        and the hard worker cap bounds the target — otherwise a
        saturated pool would spawn a futile no-op thread on EVERY
        submit/dispatch edge of a burst."""

        def shortfall() -> int:
            # Callers hold self._lock.
            warm = len(self._idle) + self._starting + len(self._leased)
            room = self._max_workers - len(self._all) - self._starting
            return min(min(depth, bound) - warm, room)

        with self._lock:
            want = shortfall()
        if want <= 0:
            return 0

        def _prestart():
            # Re-check under the pool lock at spawn time: concurrent
            # prestart calls and pop_worker starts shrink the shortfall
            # between the caller's check and this thread running.
            with self._lock:
                n = shortfall()
            if n > 0:
                self.prestart_workers(n)

        threading.Thread(target=_prestart, daemon=True,
                         name="ray_tpu::prestart").start()
        return want

    def pop_worker(self, runtime_env=None) -> Optional[Worker]:
        """Lease an idle worker, starting one if under the cap
        (WorkerPool::PopWorker, worker_pool.h:338).  In process mode
        workers are keyed by runtime-env hash (worker_pool.h:428);
        thread workers are universal (env applied per task)."""
        want_hash = (runtime_env or {}).get("_hash", "") \
            if self._process_mode else ""
        with self._lock:
            kept = []
            found = None
            while self._idle:
                w = self._idle.pop()
                if w.state != WorkerState.IDLE:
                    continue
                if w.runtime_env_hash != want_hash:
                    kept.append(w)
                    continue
                found = w
                break
            self._idle.extend(kept)
            if found is not None:
                found.state = WorkerState.LEASED
                self._leased[found.worker_id] = found
                return found
            total = len(self._all) + self._starting
            if total >= self._max_workers and kept:
                # At the cap with only mismatched-env idle workers:
                # evict one to make room (the reference kills an idle
                # worker rather than starving the new env forever).
                victim = kept[0]
                self._idle.remove(victim)
                self._all.pop(victim.worker_id, None)
                victim.stop()
                total -= 1
            if total >= self._max_workers or \
                    self._starting >= self._max_starting:
                return None      # caller retries on the dispatch tick
            if _acquire_global_start_slots(1) < 1:
                return None      # process-wide storm throttle; retried
            self._starting += 1
        # Construct OUTSIDE the lock: a process-mode spawn materializes
        # the runtime env (KV fetch + unzip) — holding the pool lock for
        # that would stall every concurrent lease/return.
        try:
            w = self._new_worker(runtime_env=runtime_env)
        except BaseException:
            with self._lock:
                self._starting -= 1
            _release_global_start_slots(1)
            raise
        _release_global_start_slots(1)
        with self._lock:
            self._starting -= 1
            self._all[w.worker_id] = w
            w.state = WorkerState.LEASED
            self._leased[w.worker_id] = w
            return w

    def push_worker(self, worker: Worker):
        """Return a leased worker to the idle pool."""
        with self._lock:
            self._leased.pop(worker.worker_id, None)
            if worker.state == WorkerState.DEAD:
                return
            if worker.state == WorkerState.ACTOR:
                self._actors[worker.worker_id] = worker
                return
            worker.state = WorkerState.IDLE
            if len(self._idle) >= self._soft_limit:
                if not self._idle:
                    # soft_limit == 0: keep no idle workers at all.
                    self._all.pop(worker.worker_id, None)
                    worker.stop()
                    return
                # Evict the OLDEST idle worker, not the returning one —
                # the most recently used worker (with its runtime env and
                # warm caches) is the one worth keeping (reference: idle
                # worker killing is LRU, ray_config_def.h:129).
                victim = self._idle.pop(0)
                self._all.pop(victim.worker_id, None)
                victim.stop()
            self._idle.append(worker)

    def promote_to_actor(self, worker: Worker):
        with self._lock:
            self._leased.pop(worker.worker_id, None)
            self._actors[worker.worker_id] = worker

    def on_worker_exit(self, worker: Worker):
        with self._lock:
            self._all.pop(worker.worker_id, None)
            self._leased.pop(worker.worker_id, None)
            self._actors.pop(worker.worker_id, None)
            if worker in self._idle:
                self._idle.remove(worker)

    def worker_for_actor(self, actor_id):
        """The dedicated worker currently running ``actor_id`` (GCS
        restart reconciliation scans surviving raylets with this)."""
        with self._lock:
            # Scan every tracked worker: a dedicated actor worker may sit
            # in _leased (the lease is held by the GCS actor manager and
            # never returned) as well as in _actors.
            for w in self._all.values():
                if w.actor_id == actor_id and w.state == WorkerState.ACTOR:
                    return w
            return None

    def num_idle(self) -> int:
        with self._lock:
            return len(self._idle)

    def num_total(self) -> int:
        with self._lock:
            return len(self._all)

    def shutdown(self):
        with self._lock:
            workers = list(self._all.values())
            host, self._host_service = self._host_service, None
            monitor, self._log_monitor = self._log_monitor, None
        for w in workers:
            w.stop()
        if host is not None:
            host.stop()
        if monitor:
            from ray_tpu._private import log_monitor as log_monitor_mod
            log_monitor_mod.release_local_monitor()
