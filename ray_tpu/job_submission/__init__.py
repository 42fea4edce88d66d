"""Job submission: run driver scripts against the cluster with a
tracked lifecycle.

Parity: reference ``dashboard/modules/job/job_manager.py`` (``JobManager``
:274 — ``submit_job`` :390 runs the entrypoint as a supervised child with
its runtime env materialized, status tracked through
PENDING/RUNNING/SUCCEEDED/FAILED/STOPPED, logs captured per job) and
``python/ray/job_submission/`` (the client API + ``JobStatus``/
``JobInfo`` types).

The entrypoint runs as a real OS process on the node hosting the
JobManager (the head), with its runtime env's working_dir as cwd,
env_vars injected, and logs teed to ``<temp>/jobs/<id>/driver.log``.
Job records live in the GCS KV (namespace ``job``) so any process with
cluster access can query them.
"""

from __future__ import annotations

import json
import os
import shlex
import signal
import subprocess
import threading
import time
import uuid
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional

from ray_tpu._private.config import get_config
from ray_tpu._private.debug.lock_order import diag_lock

_JOB_NS = b"job"


class JobStatus:
    PENDING = "PENDING"
    RUNNING = "RUNNING"
    STOPPED = "STOPPED"
    SUCCEEDED = "SUCCEEDED"
    FAILED = "FAILED"

    TERMINAL = (STOPPED, SUCCEEDED, FAILED)


@dataclass
class JobInfo:
    submission_id: str
    entrypoint: str
    status: str = JobStatus.PENDING
    message: str = ""
    start_time: float = 0.0
    end_time: float = 0.0
    metadata: Dict[str, str] = field(default_factory=dict)
    driver_pid: int = 0

    def to_json(self) -> bytes:
        return json.dumps(asdict(self)).encode()

    @classmethod
    def from_json(cls, blob: bytes) -> "JobInfo":
        return cls(**json.loads(blob))


class JobManager:
    """Supervises driver subprocesses (job_manager.py:274 parity)."""

    def __init__(self, cluster):
        self._cluster = cluster
        self._kv = cluster.gcs.kv
        self._lock = diag_lock("JobManager._lock")
        self._procs: Dict[str, subprocess.Popen] = {}
        self._stopping: set = set()
        self._log_root = os.path.join(get_config().temp_dir, "jobs")

    # ---- records --------------------------------------------------------
    def _save(self, info: JobInfo):
        self._kv.put(info.submission_id.encode(), info.to_json(),
                     namespace=_JOB_NS)

    def get_job_info(self, submission_id: str) -> Optional[JobInfo]:
        blob = self._kv.get(submission_id.encode(), namespace=_JOB_NS)
        return None if blob is None else JobInfo.from_json(blob)

    def get_job_status(self, submission_id: str) -> Optional[str]:
        info = self.get_job_info(submission_id)
        return None if info is None else info.status

    def list_jobs(self) -> List[JobInfo]:
        out = []
        for key in self._kv.keys(namespace=_JOB_NS):
            blob = self._kv.get(key, namespace=_JOB_NS)
            if blob is not None:
                out.append(JobInfo.from_json(blob))
        return sorted(out, key=lambda j: j.start_time)

    def log_path(self, submission_id: str) -> str:
        return os.path.join(self._log_root, submission_id, "driver.log")

    def get_job_logs(self, submission_id: str) -> str:
        try:
            with open(self.log_path(submission_id), "r",
                      errors="replace") as f:
                return f.read()
        except OSError:
            return ""

    # ---- lifecycle ------------------------------------------------------
    def submit_job(self, entrypoint: str,
                   runtime_env: Optional[dict] = None,
                   submission_id: Optional[str] = None,
                   metadata: Optional[Dict[str, str]] = None) -> str:
        """Start the entrypoint as a supervised child
        (``_exec_entrypoint``, job_manager.py:123 parity)."""
        from ray_tpu._private import runtime_env as runtime_env_mod

        submission_id = submission_id or f"raytpu-job-{uuid.uuid4().hex[:10]}"
        if self.get_job_info(submission_id) is not None:
            raise ValueError(f"job {submission_id!r} already exists")
        info = JobInfo(submission_id=submission_id, entrypoint=entrypoint,
                       metadata=metadata or {}, start_time=time.time())
        self._save(info)

        normalized = runtime_env_mod.normalize(runtime_env, self._kv) \
            if runtime_env else None
        from ray_tpu._private.device_policy import child_env
        ctx = runtime_env_mod.materialize(normalized, self._kv)
        env = child_env(ctx)
        env["RAY_TPU_JOB_ID"] = submission_id

        log_path = self.log_path(submission_id)
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        log_f = open(log_path, "wb")
        try:
            proc = subprocess.Popen(
                shlex.split(entrypoint), env=env,
                cwd=ctx.cwd or None,
                stdout=log_f, stderr=subprocess.STDOUT,
                start_new_session=True)
        except OSError as e:
            log_f.close()
            info.status = JobStatus.FAILED
            info.message = f"failed to start entrypoint: {e}"
            info.end_time = time.time()
            self._save(info)
            return submission_id
        with self._lock:
            self._procs[submission_id] = proc
        info.status = JobStatus.RUNNING
        info.driver_pid = proc.pid
        self._save(info)
        threading.Thread(
            target=self._supervise, args=(submission_id, proc, log_f),
            daemon=True, name=f"ray_tpu::job::{submission_id}").start()
        return submission_id

    def _supervise(self, submission_id: str, proc: subprocess.Popen, log_f):
        rc = proc.wait()
        log_f.close()
        with self._lock:
            self._procs.pop(submission_id, None)
            stopped = submission_id in self._stopping
            self._stopping.discard(submission_id)
        info = self.get_job_info(submission_id)
        if info is None:
            return
        info.end_time = time.time()
        if stopped:
            info.status = JobStatus.STOPPED
            info.message = "stopped by user"
        elif rc == 0:
            info.status = JobStatus.SUCCEEDED
        else:
            info.status = JobStatus.FAILED
            info.message = f"entrypoint exited with code {rc}"
        self._save(info)

    def stop_job(self, submission_id: str, grace_s: float = 3.0) -> bool:
        """SIGTERM, then SIGKILL after the grace period."""
        with self._lock:
            proc = self._procs.get(submission_id)
            if proc is None:
                return False
            self._stopping.add(submission_id)
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except (OSError, ProcessLookupError):
            proc.terminate()
        deadline = time.monotonic() + grace_s
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                return True
            time.sleep(0.05)
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (OSError, ProcessLookupError):
            proc.kill()
        return True

    def wait_job(self, submission_id: str,
                 timeout: Optional[float] = None) -> Optional[str]:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            status = self.get_job_status(submission_id)
            if status in JobStatus.TERMINAL:
                return status
            if deadline is not None and time.monotonic() >= deadline:
                return status
            time.sleep(0.1)

    def shutdown(self):
        with self._lock:
            ids = list(self._procs)
        for sid in ids:
            self.stop_job(sid)


class JobSubmissionClient:
    """Client against a running head's wire service (the reference's
    REST ``JobSubmissionClient``, over the framed RPC instead of HTTP).

    ``working_dir`` is packaged CLIENT-side and shipped in the submit
    payload, so `submit --working-dir .` works from any machine that can
    reach the head."""

    def __init__(self, address):
        from ray_tpu.rpc import RpcClient
        self._client = RpcClient(tuple(address))

    def submit_job(self, entrypoint: str,
                   runtime_env: Optional[dict] = None,
                   submission_id: Optional[str] = None,
                   metadata: Optional[Dict[str, str]] = None) -> str:
        payload = {"entrypoint": entrypoint, "submission_id": submission_id,
                   "metadata": metadata, "runtime_env": None,
                   "working_dir_zip": None}
        if runtime_env:
            from ray_tpu._private import runtime_env as runtime_env_mod
            spec = runtime_env_mod.validate(runtime_env)
            wd = spec.get("working_dir")
            if wd and not str(wd).startswith("pkg://"):
                payload["working_dir_zip"] = runtime_env_mod._zip_dir(wd)
                spec = dict(spec)
                spec.pop("working_dir")
            payload["runtime_env"] = spec
        return self._client.call("submit_job", payload, timeout=120.0)

    def get_job_status(self, submission_id: str) -> Optional[str]:
        return self._client.call("job_status", submission_id, timeout=30.0)

    def get_job_info(self, submission_id: str) -> Optional[dict]:
        return self._client.call("job_info", submission_id, timeout=30.0)

    def get_job_logs(self, submission_id: str) -> str:
        return self._client.call("job_logs", submission_id, timeout=60.0)

    def list_jobs(self) -> List[dict]:
        return self._client.call("list_jobs", None, timeout=30.0)

    def stop_job(self, submission_id: str) -> bool:
        return self._client.call("stop_job", submission_id, timeout=30.0)

    def cluster_status(self) -> dict:
        return self._client.call("cluster_status", None, timeout=30.0)

    def memory_summary(self) -> list:
        return self._client.call("memory_summary", None, timeout=30.0)

    def timeline(self, job: Optional[str] = None,
                 critical_path: bool = False) -> list:
        """Merged chrome://tracing dump; ``job`` restricts it to one
        job's spans, ``critical_path`` overlays that job's critical
        path as flow events."""
        payload = None
        if job or critical_path:
            payload = {"job": job, "critical_path": critical_path}
        return self._client.call("timeline_dump", payload, timeout=60.0)

    def profile_job(self, job: Optional[str] = None,
                    top_k: int = 3) -> dict:
        """Critical-path profile of one job (`ray-tpu profile`):
        stage/node/edge wall-clock attribution along the dependency
        chain, from the head's job-graph store."""
        return self._client.call(
            "profile_job", {"job": job, "top_k": top_k}, timeout=60.0)

    def list_state(self, resource: str, filters: Optional[list] = None,
                   limit: Optional[int] = 100, offset: int = 0) -> list:
        """State API rows (`ray-tpu list tasks/actors/objects/nodes`)."""
        return self._client.call(
            "state_list", {"resource": resource, "filters": filters,
                           "limit": limit, "offset": offset},
            timeout=30.0)

    def summarize_tasks(self) -> dict:
        return self._client.call("state_summary", None, timeout=30.0)

    def latency_summary(self) -> dict:
        """Per-stage task-dispatch latency rollup (p50/p99)."""
        return self._client.call("latency_summary", None, timeout=30.0)

    def debug_dump(self, stacks: bool = True, tail: int = 50,
                   timeout: float = 10.0) -> dict:
        """Cluster-wide introspection dump (`ray-tpu doctor`): the
        head's per-process report plus one per node host, with
        internal-loop liveness."""
        return self._client.call(
            "debug_dump",
            {"stacks": stacks, "tail": tail, "timeout": timeout},
            timeout=timeout * 2 + 10.0)

    def close(self):
        self._client.close()
