"""Driver bootstrap: the global worker + init/shutdown.

Parity: reference ``python/ray/worker.py`` — ``init`` (:683) starts/connects
the cluster (head path: Redis -> GCS -> raylet -> monitor -> dashboard,
node.py:1064; here: GcsServer + head Raylet + driver CoreWorker),
``shutdown``, the global-worker singleton, and the public
``get/put/wait/kill/cancel/get_actor`` entry points re-exported from
``ray_tpu/__init__.py``.
"""

from __future__ import annotations

import atexit
import threading
from typing import Any, List, Optional, Sequence, Tuple

from ray_tpu import exceptions
from ray_tpu._private import worker_context
from ray_tpu._private.config import get_config, initialize_config
from ray_tpu._private.core_worker import CoreWorker
from ray_tpu._private.ids import JobID
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.debug import diag_rlock


class Worker:
    """The per-process global worker (driver side)."""

    def __init__(self):
        self.connected = False
        self.cluster = None
        self.core_worker: Optional[CoreWorker] = None
        self.job_id: Optional[JobID] = None
        self.namespace: str = ""
        self.mode: Optional[str] = None
        self.client_connection = None    # set in remote-driver mode


_global_worker: Optional[Worker] = None
_init_lock = diag_rlock("worker._init_lock")


def global_worker() -> Worker:
    global _global_worker
    with _init_lock:
        if _global_worker is None:
            _global_worker = Worker()
        return _global_worker


def global_worker_or_none() -> Optional[Worker]:
    return _global_worker


def init(address: Optional[str] = None, *, num_cpus: Optional[float] = None,
         num_tpus: Optional[float] = None, num_gpus: Optional[float] = None,
         resources: Optional[dict] = None, object_store_memory: Optional[int] = None,
         namespace: str = "", job_config: Optional[dict] = None,
         ignore_reinit_error: bool = False, _system_config: Optional[dict] = None,
         _cluster=None, **kwargs):
    """Start (or connect to) a cluster and attach this driver.

    ``address=None`` starts a new in-process cluster with one head node
    (reference head path, worker.py:683 + node.py:1064).  ``_cluster``
    attaches to an existing :class:`ray_tpu._private.cluster.Cluster`
    (cluster_utils test path).
    """
    w = global_worker()
    with _init_lock:
        if w.connected:
            if ignore_reinit_error:
                return RuntimeContextInfo(w)
            raise RuntimeError("ray_tpu.init() called twice; pass "
                              "ignore_reinit_error=True to ignore.")
        initialize_config(_system_config)
        if get_config().tracing_enabled:
            from ray_tpu.util import tracing
            tracing.enable()
        if address and str(address).startswith("ray-tpu://"):
            # Remote-driver path (Ray Client parity): connect to a
            # running head's wire service and drive it from here.
            from ray_tpu._private import client_runtime
            from ray_tpu.rpc import RpcClient
            host, _, port = address[len("ray-tpu://"):].rpartition(":")
            w.client_connection = RpcClient((host, int(port)))
            client_runtime.install(w.client_connection)
            w.namespace = namespace or w.namespace
            if get_config().log_to_driver:
                # Worker log lines reach the remote driver over the
                # long-poll batched pubsub (one outstanding poll).
                from ray_tpu._private.log_monitor import LOG_CHANNEL
                from ray_tpu.gcs.wire_pubsub import SubscriberClient
                from ray_tpu._private import log_monitor as lm
                sub = SubscriberClient(w.client_connection)
                sub.subscribe(LOG_CHANNEL, None,
                              lm.make_log_mirror_callback())
                w.client_log_sub = sub
            atexit.register(_atexit_shutdown)
            return RuntimeContextInfo(w)
        from ray_tpu._private.cluster import Cluster
        if _cluster is not None:
            cluster = _cluster
        else:
            if num_tpus is None:
                num_tpus = _detect_tpu_chips()
            head_args = dict(num_cpus=num_cpus, num_tpus=num_tpus or 0,
                             num_gpus=num_gpus or 0,
                             object_store_memory=object_store_memory,
                             resources=resources, node_name="head")
            cluster = Cluster(initialize_head=True, head_node_args=head_args)
        w.cluster = cluster
        w.job_id = JobID.next()
        w.namespace = namespace or f"anon_ns_{w.job_id.hex()}"
        w.core_worker = CoreWorker(cluster, w.job_id, is_driver=True)
        cluster.attach_core_worker(w.core_worker)
        cluster.gcs.job_manager.add_job(w.job_id, job_config)
        w.connected = True
        w.mode = "local" if _cluster is None else "cluster"
        if get_config().log_to_driver:
            # print()s inside process-mode workers (local or on remote
            # NodeHosts) surface on this terminal, reference
            # log_to_driver behavior.
            from ray_tpu._private.log_monitor import mirror_worker_logs
            w.log_mirror_sub = mirror_worker_logs(cluster.gcs.publisher)
        if get_config().worker_process_mode == "process" and \
                cluster.head_node is not None:
            # Hide OS-process spawn latency behind init (reference:
            # PrestartWorkers on driver start, worker_pool.h:350).
            total = cluster.head_node.local_resources.to_float_dict("total")
            cluster.head_node.worker_pool.prestart_workers(
                min(int(total.get("CPU", 1)), 8))
        atexit.register(_atexit_shutdown)
        return RuntimeContextInfo(w)


def shutdown():
    w = global_worker_or_none()
    if w is None or not w.connected:
        return
    with _init_lock:
        if w.mode == "client":
            sub = getattr(w, "client_log_sub", None)
            if sub is not None:
                try:
                    sub.close()
                except Exception:
                    pass
                w.client_log_sub = None
            try:
                w.client_connection.close()
            except Exception:
                pass
            w.connected = False
            w.cluster = None
            w.core_worker = None
            w.client_connection = None
            worker_context.clear_context()
            return
        if w.job_id is not None:
            try:
                w.cluster.gcs.job_manager.mark_job_finished(w.job_id)
            except Exception:
                pass
        try:
            w.core_worker.reference_counter.close()
        except Exception:
            pass
        try:
            w.cluster.shutdown()
        except Exception:
            pass
        w.connected = False
        w.cluster = None
        w.core_worker = None
        worker_context.clear_context()
        # Reset scheduling-class interning between clusters to keep ids
        # stable in long test sessions.


def _atexit_shutdown():
    try:
        shutdown()
    except Exception:
        pass


def is_initialized() -> bool:
    w = global_worker_or_none()
    return bool(w and w.connected)


def _require_connected() -> Worker:
    """get/put/wait/kill require an initialized cluster (reference:
    "ray.init has not been called yet" RayConnectionError). No auto-init
    here: a background thread (e.g. an actor-pool reaper) touching the
    API after shutdown() must not silently boot a fresh cluster — that
    leaves connected=True and breaks the next init()."""
    w = global_worker()
    if not w.connected:
        raise RuntimeError(
            "ray_tpu.init() has not been called yet (or the cluster was "
            "shut down); call ray_tpu.init() first.")
    return w


def _detect_tpu_chips() -> float:
    """TPU chips on this host.

    Never *initializes* a jax backend here — first backend init on a real
    TPU can take tens of seconds and must not sit on the ``init()`` path.
    Counted only from env (``RAY_TPU_CHIPS``) or from an
    already-initialized jax backend; a driver that has not touched JAX
    yet passes ``init(num_tpus=...)``.
    """
    import os
    import sys
    if "RAY_TPU_CHIPS" in os.environ:
        return float(os.environ["RAY_TPU_CHIPS"])
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            return float(sum(d.platform != "cpu" for d in jax.devices()))
    return 0.0


class RuntimeContextInfo:
    """Return value of init(): address info (client context parity)."""

    def __init__(self, worker: Worker):
        head = getattr(worker.cluster, "head_node", None) \
            if worker.cluster else None
        node_id = getattr(head, "node_id", None)
        self.address_info = {
            "node_id": node_id.hex() if node_id is not None else None,
            "namespace": worker.namespace,
        }

    def __getitem__(self, k):
        return self.address_info[k]

    def __enter__(self):
        return self

    def __exit__(self, *a):
        shutdown()


# ---------------------------------------------------------------------------
# Public API bodies (re-exported by ray_tpu/__init__.py).
# ---------------------------------------------------------------------------

def get(refs, timeout: Optional[float] = None):
    w = _require_connected()
    if isinstance(refs, ObjectRef):
        return w.core_worker.get([refs], timeout)[0]
    if not isinstance(refs, (list, tuple)):
        raise TypeError(f"get() expects an ObjectRef or list, got {type(refs)}")
    return w.core_worker.get(list(refs), timeout)


def put(value) -> ObjectRef:
    w = _require_connected()
    if isinstance(value, ObjectRef):
        raise TypeError("Calling put() on an ObjectRef is not allowed.")
    return w.core_worker.put(value)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None,
         fetch_local: bool = True) -> Tuple[List, List]:
    w = _require_connected()
    refs = list(refs)
    if any(not isinstance(r, ObjectRef) for r in refs):
        raise TypeError("wait() expects a list of ObjectRefs")
    if len(set(refs)) != len(refs):
        raise ValueError("wait() expects unique ObjectRefs")
    if num_returns > len(refs):
        raise ValueError("num_returns > number of refs")
    return w.core_worker.wait(refs, num_returns, timeout, fetch_local)


def kill(actor, *, no_restart: bool = True):
    from ray_tpu.actor import ActorHandle
    w = _require_connected()
    if not isinstance(actor, ActorHandle):
        raise TypeError("kill() expects an ActorHandle")
    w.cluster.gcs.actor_manager.destroy_actor(actor._actor_id,
                                              no_restart=no_restart)


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True):
    """Best-effort task cancellation (core_worker.cc Cancel parity).

    Queued tasks are dequeued and failed with TaskCancelledError; a task
    already running on a worker thread cannot be preempted (threads, not
    processes) — it is marked so its result is discarded.
    """
    w = _require_connected()
    task_id = ref.task_id()
    tm = w.core_worker.task_manager
    spec = tm.get_spec(task_id)
    if spec is None or not tm.is_pending(task_id):
        return
    tm.fail_task(spec, exceptions.TaskCancelledError(task_id))


def get_actor(name: str, namespace: Optional[str] = None):
    from ray_tpu.actor import ActorHandle
    w = _require_connected()
    ns = namespace if namespace is not None else w.namespace
    actor = w.cluster.gcs.actor_manager.get_named_actor(name, ns)
    if actor is None:
        raise ValueError(f"Failed to look up actor {name!r} in namespace "
                         f"{ns!r}")
    return ActorHandle._from_gcs_actor(actor)


def get_gpu_ids():
    return []


def get_tpu_ids():
    ctx = worker_context.current_task_spec()
    if ctx is None:
        return []
    n = int(ctx.resources.get("TPU"))
    return list(range(n))


def nodes() -> List[dict]:
    w = _require_connected()
    out = []
    for node_id, info in w.cluster.gcs.node_manager.get_all_node_info().items():
        entry = dict(info)
        entry["NodeID"] = node_id.hex()
        entry["Alive"] = info.get("state") == "ALIVE"
        entry["Resources"] = info.get("info", info).get("resources", {}) \
            if "info" in info else info.get("resources", {})
        out.append(entry)
    return out


def cluster_resources() -> dict:
    w = _require_connected()
    return w.cluster.gcs.resource_manager.view.total_cluster_resources()


def available_resources() -> dict:
    w = _require_connected()
    return w.cluster.gcs.resource_manager.live_available_resources()


def timeline(job=None, critical_path: bool = False) -> list:
    """Merged chrome://tracing dump for the whole cluster: this
    process's spans plus clock-normalized span batches every remote
    daemon shipped to the GCS timeline store.  ``job`` filters to one
    job's spans; ``critical_path`` overlays that job's critical path
    as flow events (``ray-tpu profile`` in trace form)."""
    w = _require_connected()
    from ray_tpu.gcs.timeline import merged_timeline
    return merged_timeline(w.cluster, job=job, critical_path=critical_path)
