"""Failure / chaos tests (reference: test_chaos.py NodeKillerActor,
test_component_failures*.py, test_reconstruction.py)."""

import time

import numpy as np
import pytest

import ray_tpu


def test_node_death_by_heartbeat_timeout(ray_start_cluster):
    cluster = ray_start_cluster(num_cpus=1)
    victim = cluster.add_node(num_cpus=1)
    assert cluster.wait_for_nodes(2)
    cluster.kill_node(victim)  # hard kill: no dereg, heartbeats stop
    deadline = time.monotonic() + 15
    gcs = cluster.gcs
    while time.monotonic() < deadline:
        if victim.node_id not in gcs.node_manager.alive_nodes:
            break
        time.sleep(0.05)
    assert victim.node_id not in gcs.node_manager.alive_nodes
    assert victim.node_id in gcs.node_manager.dead_nodes


def test_actor_restart_on_node_death(ray_start_cluster):
    cluster = ray_start_cluster(num_cpus=1)
    victim = cluster.add_node(num_cpus=2, resources={"spot": 1})
    assert cluster.wait_for_nodes(2)

    @ray_tpu.remote(resources={"spot": 0.1}, num_cpus=1, max_restarts=1)
    class A:
        def ping(self):
            return ray_tpu.get_runtime_context().get_node_id()

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=10) == victim.node_id.hex()
    # Replacement node also offers "spot" so the restart can place.
    cluster.add_node(num_cpus=2, resources={"spot": 1})
    cluster.remove_node(victim)  # graceful: immediate death notification
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            node = ray_tpu.get(a.ping.remote(), timeout=2)
            if node != victim.node_id.hex():
                return
        except ray_tpu.exceptions.RayTpuError:
            time.sleep(0.1)
    pytest.fail("actor did not restart on the replacement node")


def test_actor_no_restart_becomes_dead(ray_start_cluster):
    cluster = ray_start_cluster(num_cpus=1)
    victim = cluster.add_node(num_cpus=1, resources={"spot": 1})
    assert cluster.wait_for_nodes(2)

    @ray_tpu.remote(resources={"spot": 0.1}, num_cpus=0, max_restarts=0)
    class A:
        def ping(self):
            return 1

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=10) == 1
    cluster.remove_node(victim)
    time.sleep(0.3)
    with pytest.raises(ray_tpu.exceptions.ActorError):
        ray_tpu.get(a.ping.remote(), timeout=5)


def test_object_reconstruction_on_node_loss(ray_start_cluster):
    cluster = ray_start_cluster(num_cpus=1)
    producer_node = cluster.add_node(num_cpus=1, resources={"prod": 1})
    assert cluster.wait_for_nodes(2)

    @ray_tpu.remote(resources={"prod": 0.1}, num_cpus=0, max_retries=2)
    def produce():
        return np.ones(2_000_000, dtype=np.float32)  # 8MB -> node store

    ref = produce.remote()
    first = ray_tpu.get(ref)
    assert first.sum() == 2_000_000
    # Add a replacement node that can re-run the task, then lose the
    # original copy with the producer node.
    cluster.add_node(num_cpus=1, resources={"prod": 1})
    cluster.remove_node(producer_node)
    time.sleep(0.3)
    # Lineage reconstruction: the creating task is resubmitted.
    again = ray_tpu.get(ref, timeout=15)
    assert again.sum() == 2_000_000


def test_task_failure_exhausts_retries(ray_start_regular):
    attempts = []

    @ray_tpu.remote(max_retries=2, retry_exceptions=True)
    def flaky():
        raise RuntimeError("always fails")

    with pytest.raises(RuntimeError, match="always fails"):
        ray_tpu.get(flaky.remote())


def test_unrecoverable_loss_raises_object_lost(ray_start_cluster):
    """A get() on an object whose every copy is gone and whose lineage
    cannot reproduce it must raise ObjectLostError promptly — not spin
    until the timeout (r3 verdict: silent abandonment on the pull path)."""
    cluster = ray_start_cluster(num_cpus=1)
    producer_node = cluster.add_node(num_cpus=1, resources={"prod": 1})
    assert cluster.wait_for_nodes(2)

    @ray_tpu.remote(resources={"prod": 0.1}, num_cpus=0, max_retries=0)
    class Holder:
        def make(self):
            return np.ones(2_000_000, dtype=np.float32)  # node store

    h = Holder.remote()
    # Actor-task returns are NOT lineage-reconstructable, so losing the
    # only copy is unrecoverable by design.  Wait for readiness WITHOUT
    # fetching (a driver-side get would pull a surviving copy to the
    # head), then drop the node holding the only copy.
    ref = h.make.remote()
    ready, _ = ray_tpu.wait([ref], timeout=10)
    assert ready
    cluster.remove_node(producer_node)
    time.sleep(0.3)

    t0 = time.monotonic()
    with pytest.raises(ray_tpu.exceptions.ObjectLostError):
        ray_tpu.get(ref, timeout=20)
    assert time.monotonic() - t0 < 10, \
        "loss should surface promptly, not burn the whole timeout"


def test_owner_death_borrower_observes_owner_died():
    """Kill the OS process that owns an object (put from inside a
    process-mode worker) and assert the borrower's get raises
    OwnerDiedError — not a hang, not a bare timeout (reference:
    reference_count.cc OWNER_DIED propagation: this
    semantics existed in exceptions.py but was never exercised)."""
    import os
    import signal

    ray_tpu.init(num_cpus=1, _system_config={
        "worker_process_mode": "process",
        "scheduler_backend": "native",
    })
    try:
        from ray_tpu._private.worker import global_worker

        @ray_tpu.remote
        def make_owned():
            inner = ray_tpu.put(np.ones(500_000, dtype=np.float64))
            return [inner]

        [inner_ref] = ray_tpu.get(make_owned.remote(), timeout=120)
        # Readable while the owner lives.
        assert ray_tpu.get(inner_ref, timeout=60)[0] == 1.0

        pool = global_worker().cluster.head_node.worker_pool
        killed = 0
        for w in list(pool._all.values()):
            proc = getattr(w, "_proc", None)
            if proc is not None and proc.poll() is None:
                os.kill(proc.pid, signal.SIGKILL)
                killed += 1
        assert killed, "no process-mode worker found to kill"

        deadline = time.monotonic() + 30
        while True:
            try:
                ray_tpu.get(inner_ref, timeout=2.0)
            except ray_tpu.exceptions.OwnerDiedError:
                break                      # expected
            except ray_tpu.exceptions.GetTimeoutError:
                pass                       # death not yet detected
            assert time.monotonic() < deadline, \
                "borrower never observed OwnerDiedError"
    finally:
        ray_tpu.shutdown()
