"""Dispatch fast path: batched leases, warm-worker prestart, lease
keepalive, and the queue_wait stage-coverage guarantee.

The protocol surface under test (PR: event-driven scheduling + batched
leases + prestart): ``Raylet.request_worker_lease_batch`` resolves N
same-class lease entries in one round-trip (grant / spillback / backlog
vector), the submitter coalesces bursts into those batches (a 500-task
burst costs dozens of lease RPCs, not 500), grants for a worker that
died in the grant->push window re-lease without charging the task's
retry budget, and the ``worker.lease_batch`` fault point can bounce a
whole batch (chaos fallback: single leases, no retries burned).
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import fault_injection
from ray_tpu._private.config import get_config
from ray_tpu._private.worker import global_worker


def _head():
    return global_worker().cluster.head_node


def _noop_spec(remote_fn, resources=None):
    """A real, runnable TaskSpec for ``remote_fn`` (registered as
    pending so lease grants can dispatch it like any submitted task)."""
    from ray_tpu._private.task_spec import make_spec
    core = global_worker().core_worker
    fid = core.function_manager.export(remote_fn._function)
    spec = make_spec(
        job_id=global_worker().job_id, owner_id=core.worker_id,
        function_id=fid, function_name="noop", args=[], num_returns=1,
        resources=resources or {"CPU": 1})
    core.task_manager.add_pending_task(spec)
    return spec


def _lease_rpcs(raylet):
    return (raylet.lease_stats["lease_requests"]
            + raylet.lease_stats["lease_batch_requests"])


class TestBatchedLeaseProtocol:
    def test_500_task_burst_costs_dozens_of_lease_rpcs(self):
        """Acceptance: batched-lease RPC count for a 500-task
        single-class burst is <= 50 (it was one lease per scheduled
        task before the batch protocol)."""
        ray_tpu.init(num_cpus=8)
        try:
            @ray_tpu.remote
            def noop():
                return None

            ray_tpu.get([noop.remote() for _ in range(100)])  # warm
            before = _lease_rpcs(_head())
            ray_tpu.get([noop.remote() for _ in range(500)])
            spent = _lease_rpcs(_head()) - before
            assert spent <= 50, f"500-task burst cost {spent} lease RPCs"
            assert _head().lease_stats["lease_batch_entries"] >= 2, \
                "batching never engaged"
        finally:
            ray_tpu.shutdown()

    def test_batch_reply_mixes_grant_and_spillback(self, ray_start_cluster):
        """One batch against a nearly-full local node: the reply vector
        carries grants for what fits locally and spillbacks pointing at
        the free remote node — per entry, exactly like single leases."""
        cluster = ray_start_cluster(num_cpus=1)
        remote = cluster.add_node(num_cpus=8)
        assert cluster.wait_for_nodes(2)
        head = cluster.head_node
        # The scheduler spills against the head's LOCAL view; wait for
        # the resource broadcast to deliver the new node's row.
        deadline = time.monotonic() + 30
        while len(head.cluster_view.node_ids()) < 2:
            assert time.monotonic() < deadline, "view never saw node 2"
            time.sleep(0.02)

        @ray_tpu.remote
        def noop():
            return None

        specs = [_noop_spec(noop) for _ in range(4)]
        done = threading.Event()
        got = {}

        def reply(result):
            got["results"] = result["results"]
            done.set()

        head.request_worker_lease_batch(specs, reply)
        assert done.wait(timeout=30)
        results = got["results"]
        assert len(results) == 4
        grants = [r for r in results if "worker" in r]
        spills = [r for r in results if "retry_at" in r]
        assert len(grants) == 1, results
        assert spills, f"no spillback in mixed batch: {results}"
        assert all(r["retry_at"] == remote.node_id for r in spills)
        for r in grants:
            r["raylet"].return_worker(r["worker"])

    def test_batch_backlog_entries_stay_client_side_and_complete(self):
        """A burst far deeper than capacity: backlog entries are
        withdrawn from the raylet (no parked lease per queued task) and
        the whole burst still completes through reuse + re-pump."""
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def tick():
                time.sleep(0.001)
                return 1

            assert sum(ray_tpu.get(
                [tick.remote() for _ in range(120)], timeout=120)) == 120
            # Far fewer workers than tasks: leases stayed bounded.
            assert _head().worker_pool.num_total() <= 12
        finally:
            ray_tpu.shutdown()


class TestDependentBurst:
    def test_same_class_producer_consumer_burst_completes(self):
        """Consumers share their producers' scheduling class (class =
        resources+options).  A consumer coalesced into the same lease
        batch as its producers would dep-wait at the raylet and
        withhold the whole batch reply — including the producers'
        granted workers — behind outputs only those producers can
        create.  Ref-arg specs therefore ride the single-lease path;
        this pins the end-to-end shape (many dependent pairs, one
        class, bursty submission)."""
        ray_tpu.init(num_cpus=4)
        try:
            @ray_tpu.remote
            def produce(i):
                return i

            @ray_tpu.remote
            def consume(x):
                return x + 1

            producers = [produce.remote(i) for i in range(40)]
            consumers = [consume.remote(p) for p in producers]
            assert ray_tpu.get(consumers, timeout=90) == \
                list(range(1, 41))
        finally:
            ray_tpu.shutdown()


class TestGrantPushDeathWindow:
    def test_dead_worker_grant_releases_lease_and_burns_no_retry(self):
        """A grant whose worker died before the push falls back to
        re-lease: the lease returns (resources freed), the spec stays
        queued, and fail_or_retry is never called."""
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def noop():
                return None

            ray_tpu.get(noop.remote())
            core = global_worker().core_worker
            sub = core.task_submitter
            spec = _noop_spec(noop)
            key = spec.scheduling_class

            retries = []
            orig = core.task_manager.fail_or_retry
            core.task_manager.fail_or_retry = \
                lambda *a, **k: retries.append(a) or orig(*a, **k)

            class DeadWorker:
                state = "DEAD"
                worker_id = spec.task_id      # any id-shaped object
                node_id = _head().node_id

            returned = []
            head = _head()
            orig_return = head.return_worker
            head.return_worker = \
                lambda w, disconnect=False: returned.append(w)
            try:
                with sub._lock:
                    st = sub._keys[key]
                    st.queue.append(spec)
                    st.pending_leases += 1
                    st.leased_task_ids.add(spec.task_id)
                sub._handle_grant(spec, key,
                                  {"worker": DeadWorker(), "raylet": head})
                assert returned, "dead-worker lease was not returned"
                assert not retries, "grant-window death burned a retry"
            finally:
                head.return_worker = orig_return
            # The dead-grant handler re-pumped: a FRESH lease runs the
            # task to completion (the task never failed, never retried).
            deadline = time.monotonic() + 30
            while core.task_manager.is_pending(spec.task_id):
                assert time.monotonic() < deadline, \
                    "task never re-leased after dead-worker grant"
                time.sleep(0.02)
            assert not retries
            core.task_manager.fail_or_retry = orig
        finally:
            ray_tpu.shutdown()

    def test_lease_batch_fault_bounces_whole_batch_without_retries(self):
        """Chaos point ``worker.lease_batch``: a bounced batch falls
        back to single leases; every task completes and no task retry
        budget is spent.  Gate-blocked workers force the class queue
        deep so the pump MUST form a batch (a fast machine can
        otherwise drain a free-running burst on reused leases without
        ever needing a second lease round-trip)."""
        import os
        import tempfile
        ray_tpu.init(num_cpus=4, _system_config={
            "scheduler_backend": "native"})
        gate = os.path.join(tempfile.mkdtemp(), "release")
        try:
            @ray_tpu.remote(max_retries=0)
            def wait_for(gate_path):
                deadline = time.monotonic() + 120
                while not os.path.exists(gate_path) and \
                        time.monotonic() < deadline:
                    time.sleep(0.01)
                return 1

            fault_injection.arm("worker.lease_batch", "error", count=1)
            try:
                # max_retries=0: if the bounce charged the task budget,
                # tasks would fail instead of re-leasing.
                refs = [wait_for.remote(gate) for _ in range(20)]
                deadline = time.monotonic() + 60
                while fault_injection.fired("worker.lease_batch") < 1:
                    assert time.monotonic() < deadline, \
                        "batch lease RPC never issued for a deep queue"
                    time.sleep(0.02)
                open(gate, "w").close()
                assert sum(ray_tpu.get(refs, timeout=120)) == 20
            finally:
                fault_injection.disarm("worker.lease_batch")
        finally:
            ray_tpu.shutdown()


class TestPrestartAndKeepalive:
    def test_prestart_bounded_by_knob(self):
        ray_tpu.init(num_cpus=8)
        try:
            pool = _head().worker_pool
            base = pool.num_total()
            pool.prestart_for_backlog(depth=50, bound=base + 3)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and \
                    pool.num_total() < base + 3:
                time.sleep(0.02)
            assert pool.num_total() == base + 3
            # Already warm enough: a second call is a no-op.
            assert pool.prestart_for_backlog(depth=50, bound=base + 3) == 0
        finally:
            ray_tpu.shutdown()

    def test_prestart_off_by_default(self):
        assert get_config().num_prestart_workers == 0
        assert get_config().worker_lease_keepalive_ms == 0

    def test_keepalive_reuses_lease_across_bursts(self):
        ray_tpu.init(num_cpus=4, _system_config={
            "worker_lease_keepalive_ms": 2_000})
        try:
            @ray_tpu.remote
            def noop():
                return None

            ray_tpu.get([noop.remote() for _ in range(50)])
            before = _lease_rpcs(_head())
            # Sequential calls inside the keepalive window ride the
            # parked lease: ~zero fresh lease round-trips (tolerate a
            # couple — a full-suite box stall can outlast any window;
            # without keepalive this costs one lease per call).
            for _ in range(20):
                ray_tpu.get(noop.remote())
            assert _lease_rpcs(_head()) - before <= 2
        finally:
            ray_tpu.shutdown()

    def test_keepalive_returns_lease_after_window(self):
        ray_tpu.init(num_cpus=2, _system_config={
            "worker_lease_keepalive_ms": 50})
        try:
            @ray_tpu.remote
            def noop():
                return None

            ray_tpu.get([noop.remote() for _ in range(10)])
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                avail = ray_tpu.available_resources().get("CPU", 0)
                if avail == 2:
                    break
                time.sleep(0.05)
            assert ray_tpu.available_resources().get("CPU", 0) == 2, \
                "parked leases never expired back to the raylet"
        finally:
            ray_tpu.shutdown()


class TestQueueWaitCoverage:
    def test_every_task_gets_a_queue_wait_sample(self):
        """The queue_wait coverage gap: lease-reuse pushes skipped the
        scheduler and produced NO queue_wait sample, so the histogram
        covered only the slow path.  The transport now emits SCHEDULED
        at push time: every stage's sample count must match."""
        ray_tpu.init(num_cpus=4)
        try:
            from ray_tpu.experimental.state.api import summarize_tasks

            @ray_tpu.remote
            def noop():
                return None

            ray_tpu.get([noop.remote() for _ in range(20)])
            summarize_tasks()     # flush stragglers
            mgr = global_worker().cluster.gcs.task_event_manager
            mgr.reset_stage_samples()
            ray_tpu.get([noop.remote() for _ in range(60)])
            stages = summarize_tasks()["dispatch_latency"]
            counts = {s: row["count"] for s, row in stages.items()}
            assert set(counts) >= {"queue_wait", "dispatch", "startup",
                                   "execution", "total"}
            assert len(set(counts.values())) == 1, \
                f"stage-coverage gap: {counts}"
        finally:
            ray_tpu.shutdown()


class TestBatchedLeaseWire:
    def test_lease_batch_round_trip_over_wire(self):
        """The batched lease RPC against a REAL NodeHost OS process:
        one wire round-trip, grants wrapped into remote worker handles
        (tokens held for reconcile), excess entries resolved — same
        vector semantics as the in-process surface."""
        from ray_tpu._private.ids import (FunctionID, JobID, TaskID,
                                          WorkerID)
        from ray_tpu._private.task_spec import TaskSpec
        from ray_tpu.scheduler.policy import SchedulingOptions
        from ray_tpu.scheduler.resources import ResourceRequest

        ray_tpu.init(num_cpus=1)
        try:
            cluster = global_worker().cluster
            cluster.add_remote_node(num_cpus=2,
                                    resources={"spoke": 4.0})
            proxy = None
            for raylet in cluster.gcs.resource_manager._raylets.values():
                if getattr(raylet, "is_remote_proxy", False):
                    proxy = raylet
            assert proxy is not None

            def spec():
                return TaskSpec(
                    task_id=TaskID.from_random(), job_id=JobID.next(),
                    task_type="NORMAL_TASK",
                    function_id=FunctionID.from_random(),
                    function_name="wire_batch_probe", args=[],
                    num_returns=1,
                    resources=ResourceRequest({"CPU": 1.0,
                                               "spoke": 1.0}),
                    scheduling_options=SchedulingOptions.hybrid(),
                    scheduling_class=434343,
                    owner_id=WorkerID.from_random())

            specs = [spec() for _ in range(4)]
            done = threading.Event()
            got = {}

            def reply(result):
                got["results"] = result["results"]
                done.set()

            proxy.request_worker_lease_batch(specs, reply)
            assert done.wait(timeout=60)
            results = got["results"]
            assert len(results) == 4
            grants = [r for r in results if "worker" in r]
            assert len(grants) == 2, results      # node has 2 CPUs
            assert all(r.get("backlog") for r in results
                       if "worker" not in r), results
            for r in grants:
                # The handle duck-types the worker surface and the head
                # holds its token (reconcile safety).
                token = r["worker"].worker_id.binary()
                with proxy._tokens_lock:
                    assert token in proxy._held_tokens
                r["raylet"].return_worker(r["worker"])
        finally:
            ray_tpu.shutdown()


class TestEventDrivenTick:
    def test_wakeup_coalesces_burst_into_few_ticks(self):
        """A burst queued inside one debounce window runs one batched
        scheduling pass, not one tick per arrival."""
        ray_tpu.init(num_cpus=4, _system_config={
            "scheduler_wakeup_debounce_ms": 5.0})
        try:
            @ray_tpu.remote
            def noop():
                return None

            ray_tpu.get(noop.remote())        # warm one worker
            ctm = _head().cluster_task_manager
            busy_before = ctm.tick_stats["busy_ticks"]
            ray_tpu.get([noop.remote() for _ in range(100)], timeout=60)
            busy = ctm.tick_stats["busy_ticks"] - busy_before
            assert busy <= 30, \
                f"{busy} busy ticks for one burst: wakeups not coalesced"
        finally:
            ray_tpu.shutdown()

    def test_zero_debounce_still_schedules(self):
        ray_tpu.init(num_cpus=2, _system_config={
            "scheduler_wakeup_debounce_ms": 0.0})
        try:
            @ray_tpu.remote
            def noop():
                return 7

            assert ray_tpu.get(noop.remote(), timeout=30) == 7
        finally:
            ray_tpu.shutdown()
