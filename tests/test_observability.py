"""Observability pipeline: worker log capture/streaming to the driver,
and runtime metrics aggregation through the Prometheus endpoint.

Reference models: ``python/ray/_private/log_monitor.py`` (worker
stdout/stderr files tailed and published; driver mirrors lines) and the
stats pipeline (``src/ray/stats/metric_defs.h`` exported via each
node's metrics agent to ``/metrics``).
"""

import os
import time
import urllib.request

import pytest

import ray_tpu


@pytest.fixture
def process_cluster():
    ray_tpu.init(num_cpus=4, _system_config={
        "worker_process_mode": "process",
        "scheduler_backend": "native",
    })
    yield
    ray_tpu.shutdown()


@pytest.fixture
def thread_cluster():
    ray_tpu.init(num_cpus=8)
    yield
    ray_tpu.shutdown()


class TestWorkerLogs:
    def test_worker_stdout_lands_in_session_files(self, process_cluster):
        @ray_tpu.remote
        def shout():
            print("LOGLINE_FILE_MARKER_77")
            return os.getpid()

        pid = ray_tpu.get(shout.remote())
        assert pid != os.getpid()
        from ray_tpu._private.log_monitor import worker_log_dir
        d = worker_log_dir(create=False)
        deadline = time.monotonic() + 10
        found = False
        while time.monotonic() < deadline and not found:
            for name in os.listdir(d):
                if not name.endswith(".out"):
                    continue
                with open(os.path.join(d, name), "rb") as f:
                    if b"LOGLINE_FILE_MARKER_77" in f.read():
                        found = True
                        break
            time.sleep(0.1)
        assert found, "worker stdout never reached its session log file"

    def test_worker_print_mirrored_to_driver(self, process_cluster):
        """print() inside a process worker surfaces on the driver via
        the worker_logs pubsub channel (log_to_driver behavior)."""
        from ray_tpu._private import log_monitor
        from ray_tpu._private.worker import global_worker

        seen = []
        pub = global_worker().cluster.gcs.publisher
        sub = pub.subscribe(log_monitor.LOG_CHANNEL, None,
                            lambda _k, msg: seen.extend(msg["lines"]))

        @ray_tpu.remote
        def shout():
            print("LOGLINE_MIRROR_MARKER_88")
            return True

        assert ray_tpu.get(shout.remote())
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if any("LOGLINE_MIRROR_MARKER_88" in ln for ln in seen):
                break
            time.sleep(0.1)
        pub.unsubscribe(log_monitor.LOG_CHANNEL, None, sub)
        assert any("LOGLINE_MIRROR_MARKER_88" in ln for ln in seen), \
            "worker print never published on the worker_logs channel"

    def test_stderr_flagged(self, process_cluster):
        import sys
        from ray_tpu._private import log_monitor
        from ray_tpu._private.worker import global_worker

        msgs = []
        pub = global_worker().cluster.gcs.publisher
        sub = pub.subscribe(log_monitor.LOG_CHANNEL, None,
                            lambda _k, m: msgs.append(m))

        @ray_tpu.remote
        def complain():
            print("ERRLINE_MARKER_99", file=sys.stderr)
            return True

        assert ray_tpu.get(complain.remote())
        deadline = time.monotonic() + 10
        hit = None
        while time.monotonic() < deadline and hit is None:
            for m in list(msgs):
                if any("ERRLINE_MARKER_99" in ln for ln in m["lines"]):
                    hit = m
                    break
            time.sleep(0.1)
        pub.unsubscribe(log_monitor.LOG_CHANNEL, None, sub)
        assert hit is not None and hit["is_err"] is True


class TestMetricsPipeline:
    def _scrape(self):
        from ray_tpu._private.metrics_agent import get_metrics_registry
        return get_metrics_registry().render_prometheus()

    def test_runtime_metrics_populated(self, thread_cluster):
        @ray_tpu.remote
        def f(x):
            return x + 1

        ray_tpu.get([f.remote(i) for i in range(20)])
        text = self._scrape()
        assert "ray_tpu_core_worker_tasks_submitted" in text
        assert "ray_tpu_cluster_alive_nodes" in text
        assert "ray_tpu_object_store_used_bytes" in text
        # The counters carry real values, not just registrations.  The
        # registry is process-global, so earlier tests' (dead) workers
        # may still expose series — judge the max across workers.
        vals = [float(line.rsplit(" ", 1)[1])
                for line in text.splitlines()
                if line.startswith("ray_tpu_core_worker_tasks_submitted")]
        assert vals and max(vals) >= 20

    def test_scheduler_metrics_under_jax_backend(self):
        ray_tpu.init(num_cpus=8)   # default backend = jax
        try:
            @ray_tpu.remote
            def f():
                return 1

            ray_tpu.get([f.remote() for _ in range(8)])
            text = self._scrape()
            assert "ray_tpu_scheduler_ticks" in text
        finally:
            ray_tpu.shutdown()

    def test_dashboard_metrics_route_serves_runtime_series(
            self, thread_cluster):
        from ray_tpu._private.worker import global_worker
        from ray_tpu.dashboard.head import start_dashboard

        @ray_tpu.remote
        def f():
            return 1

        ray_tpu.get(f.remote())
        dash = start_dashboard(global_worker().cluster)
        try:
            with urllib.request.urlopen(dash.url + "/metrics",
                                        timeout=10) as resp:
                body = resp.read().decode()
            assert "ray_tpu_cluster_alive_nodes" in body
            assert "ray_tpu_core_worker_tasks_submitted" in body
        finally:
            dash.stop()


class TestCollectorSeriesPruning:
    def test_dead_collector_series_removed(self):
        """Series written by a scrape collector vanish when its owner is
        collected — per-worker label cardinality must not grow without
        bound under worker churn (ADVICE r4: metrics_agent series never
        pruned)."""
        import gc

        from ray_tpu._private.metrics_agent import MetricsRegistry

        reg = MetricsRegistry()
        reg.register("churn.gauge", "gauge", "per-worker gauge")

        class Owner:
            def __init__(self, wid):
                self.wid = wid

        def collect(owner):
            reg.set("churn.gauge", 1.0, (("worker_id", owner.wid),))

        owner = Owner("w1")
        reg.register_collector(owner, collect)
        reg.run_collectors()
        assert reg.get_value("churn.gauge", (("worker_id", "w1"),)) == 1.0

        # Survivor keeps its series while the dead owner's are pruned.
        keeper = Owner("w2")
        reg.register_collector(keeper, collect)
        reg.run_collectors()
        del owner
        gc.collect()
        reg.run_collectors()
        assert reg.get_value("churn.gauge", (("worker_id", "w1"),)) is None
        assert reg.get_value("churn.gauge", (("worker_id", "w2"),)) == 1.0


class TestTracing:
    """Spans around submit/execute with context propagation
    (tracing_helper.py:157,314 parity; trace ctx rides TaskSpec)."""

    def test_remote_call_produces_linked_spans(self):
        from ray_tpu.util import tracing
        ray_tpu.init(num_cpus=2, _system_config={"tracing_enabled": True})
        try:
            tracing.clear()

            @ray_tpu.remote
            def traced(x):
                return x + 1

            assert ray_tpu.get(traced.remote(1), timeout=30) == 2
            events = ray_tpu.timeline()
            submits = [e for e in events if e["cat"] == "submit"]
            executes = [e for e in events if e["cat"] == "execute"]
            assert submits and executes
            sub, ex = submits[0], executes[0]
            # Same trace; execute's parent is the submit span.
            assert ex["args"]["trace_id"] == sub["args"]["trace_id"]
            assert ex["args"]["parent_id"] == sub["args"]["span_id"]
            # get/put spans exist too.
            assert any(e["cat"] == "object" and e["name"] == "get"
                       for e in events)
            # Renders as chrome://tracing JSON (required keys).
            for e in events:
                assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        finally:
            ray_tpu.shutdown()
            tracing.enable(False)
            tracing.clear()

    def test_trace_id_unbroken_driver_actor_nested_task(self):
        """Trace-context coverage (ISSUE 15 satellite): driver ->
        actor method -> nested task must share ONE trace_id, in thread
        mode.  Actor-method submits inject TaskSpec.trace_ctx exactly
        like plain tasks."""
        from ray_tpu.util import tracing
        ray_tpu.init(num_cpus=2, _system_config={"tracing_enabled": True})
        try:
            tracing.clear()

            @ray_tpu.remote
            def nested_tr(x):
                return x + 1

            @ray_tpu.remote
            class ChainTr:
                def go(self, x):
                    return ray_tpu.get(nested_tr.remote(x)) + 1

            actor = ChainTr.remote()
            assert ray_tpu.get(actor.go.remote(1), timeout=30) == 3
            events = ray_tpu.timeline()
            executes = [e for e in events if e.get("cat") == "execute"]
            method = next(e for e in executes if "go" in e["name"])
            nested = next(e for e in executes
                          if "nested_tr" in e["name"])
            sub = next(e for e in events if e.get("cat") == "submit"
                       and "go" in e["name"])
            assert method["args"]["trace_id"] == sub["args"]["trace_id"]
            assert nested["args"]["trace_id"] == \
                sub["args"]["trace_id"], \
                "trace broke between the actor method and its nested task"
        finally:
            ray_tpu.shutdown()
            tracing.enable(False)
            tracing.clear()

    def test_trace_id_unbroken_across_client_submission(self):
        """Trace-context coverage, process mode: a nested task
        submitted from INSIDE a process-mode worker goes through the
        ray-client submit path (client_runtime), which must inject
        TaskSpec.trace_ctx like core_worker.py does for plain tasks —
        the pre-fix behavior started a fresh trace at the process
        boundary."""
        from ray_tpu.util import tracing
        ray_tpu.init(num_cpus=2, _system_config={
            "worker_process_mode": "process",
            "scheduler_backend": "native",
            "tracing_enabled": True,
        })
        try:
            tracing.clear()

            @ray_tpu.remote
            def inner_tr(x):
                return x * 2

            @ray_tpu.remote
            def outer_tr(x):
                return ray_tpu.get(inner_tr.remote(x)) + 1

            assert ray_tpu.get(outer_tr.remote(3), timeout=60) == 7
            events = ray_tpu.timeline()
            executes = [e for e in events if e.get("cat") == "execute"]
            outer = next(e for e in executes if "outer_tr" in e["name"])
            inner = next((e for e in executes
                          if "inner_tr" in e["name"]), None)
            assert inner is not None, \
                "nested execute span never reached the driver"
            assert inner["args"]["trace_id"] == \
                outer["args"]["trace_id"], \
                "trace broke across the client submission boundary"
        finally:
            ray_tpu.shutdown()
            tracing.enable(False)
            tracing.clear()

    def test_spans_cross_the_process_boundary(self):
        """Execute spans recorded in a worker OS process must appear in
        the driver's timeline with the worker's pid (ProfileEvent
        batching parity)."""
        from ray_tpu.util import tracing
        ray_tpu.init(num_cpus=2, _system_config={
            "worker_process_mode": "process",
            "scheduler_backend": "native",
            "tracing_enabled": True,
        })
        try:
            tracing.clear()

            @ray_tpu.remote
            def where():
                return os.getpid()

            worker_pid = ray_tpu.get(where.remote(), timeout=60)
            assert worker_pid != os.getpid()
            events = ray_tpu.timeline()
            executes = [e for e in events if e["cat"] == "execute"]
            submits = [e for e in events if e["cat"] == "submit"]
            assert submits and executes
            assert any(e["pid"] == worker_pid for e in executes), \
                "execute span from the worker process missing"
            assert any(e["pid"] == os.getpid() for e in submits)
            ex = next(e for e in executes if e["pid"] == worker_pid)
            sub = submits[0]
            assert ex["args"]["trace_id"] == sub["args"]["trace_id"]
        finally:
            ray_tpu.shutdown()
            tracing.enable(False)
            tracing.clear()


class TestNodeStatsReporter:
    def test_node_stats_route_serves_host_stats(self, thread_cluster):
        """reporter-module parity: /api/node_stats carries psutil
        samples riding the resource reports."""
        import json as json_mod

        from ray_tpu._private.worker import global_worker
        from ray_tpu.dashboard.head import start_dashboard
        cluster = global_worker().cluster
        dash = start_dashboard(cluster)
        try:
            body = urllib.request.urlopen(
                dash.url + "/api/node_stats", timeout=10).read()
            rows = json_mod.loads(body)
            assert rows, "no node stats rows"
            hs = rows[0]["host_stats"]
            assert hs["cpu_count"] >= 1
            assert hs["mem"]["total"] > 0
            assert "load" in rows[0]
        finally:
            dash.stop()


class TestTaskEvents:
    """Task-event pipeline (reference State API / task-events backend):
    lifecycle transitions emitted by core worker + raylet + executor,
    batched over pubsub into the GCS TaskEventManager, queried through
    ``ray_tpu.experimental.state``."""

    ORDER = ["PENDING_ARGS_AVAIL", "SCHEDULED", "SUBMITTED_TO_WORKER",
             "RUNNING", "FINISHED", "FAILED"]

    def _rows_named(self, fragment, terminal_within=None):
        """Rows whose name contains ``fragment``.  With
        ``terminal_within``, poll up to that many seconds for the last
        row to reach a terminal state first — events flush on the
        node-host heartbeat loop, so a just-finished task's FINISHED
        record can trail the driver's get() by a beat (flaky under
        full-suite load)."""
        from ray_tpu.experimental.state import list_tasks

        def rows():
            return [r for r in list_tasks(limit=None)
                    if fragment in r["name"]]
        if terminal_within:
            deadline = time.monotonic() + terminal_within
            while time.monotonic() < deadline:
                out = rows()
                if out and out[-1]["state"] in ("FINISHED", "FAILED"):
                    return out
                time.sleep(0.05)
        return rows()

    def _assert_lifecycle(self, rec):
        # All five states observed, in canonical order, each stamped.
        states = [s for s, _ts in rec["events"]]
        expected = ["PENDING_ARGS_AVAIL", "SCHEDULED",
                    "SUBMITTED_TO_WORKER", "RUNNING", "FINISHED"]
        for s in expected:
            assert s in states, f"missing state {s} in {states}"
            assert s in rec["state_ts"], f"no timestamp for {s}"
        indices = [self.ORDER.index(s) for s in states]
        assert indices == sorted(indices), \
            f"states out of lifecycle order: {states}"
        ts = [rec["state_ts"][s] for s in expected]
        assert ts == sorted(ts), "per-state timestamps not monotone"
        assert rec["state"] == "FINISHED"
        assert rec["node_id"] and rec["worker_id"]
        assert rec["duration_s"] is not None and rec["duration_s"] >= 0

    def test_lifecycle_thread_mode(self, thread_cluster):
        @ray_tpu.remote
        def add_one_te(x):
            return x + 1

        assert ray_tpu.get(add_one_te.remote(1), timeout=30) == 2
        rows = self._rows_named("add_one_te", terminal_within=10.0)
        assert rows, "task never reached the event manager"
        self._assert_lifecycle(rows[-1])

    def test_lifecycle_process_mode(self, process_cluster):
        @ray_tpu.remote
        def add_two_te(x):
            return x + 2

        assert ray_tpu.get(add_two_te.remote(1), timeout=60) == 3
        rows = self._rows_named("add_two_te", terminal_within=10.0)
        assert rows
        self._assert_lifecycle(rows[-1])

    def test_attempt_counter_on_retry(self, thread_cluster, tmp_path):
        marker = str(tmp_path / "flaky_marker")

        @ray_tpu.remote(max_retries=2, retry_exceptions=True)
        def flaky_te(path):
            if not os.path.exists(path):
                open(path, "w").close()
                raise ValueError("first attempt fails")
            return "ok"

        assert ray_tpu.get(flaky_te.remote(marker), timeout=30) == "ok"
        rows = self._rows_named("flaky_te")
        assert rows
        rec = rows[-1]
        assert rec["attempt"] >= 1, \
            "retry did not bump the attempt counter"
        assert rec["state"] == "FINISHED"

    def test_failed_task_records_error(self, thread_cluster):
        @ray_tpu.remote(max_retries=0)
        def boom_te():
            raise RuntimeError("deliberate")

        with pytest.raises(Exception):
            ray_tpu.get(boom_te.remote(), timeout=30)
        rows = self._rows_named("boom_te")
        assert rows
        rec = rows[-1]
        assert rec["state"] == "FAILED"
        assert "FAILED" in rec["state_ts"]
        assert rec["error"] and "deliberate" in rec["error"]

    def test_burst_500_tasks_zero_drops(self, thread_cluster):
        from ray_tpu._private.worker import global_worker
        from ray_tpu.experimental.state import summarize_tasks

        @ray_tpu.remote
        def unit_te(i):
            return i

        out = ray_tpu.get([unit_te.remote(i) for i in range(500)],
                          timeout=120)
        assert sorted(out) == list(range(500))
        gcs = global_worker().cluster.gcs
        gcs.task_events.flush()
        assert gcs.task_event_manager.num_dropped_at_source() == 0, \
            "bounded buffer dropped events under a 500-task burst"
        rows = self._rows_named("unit_te")
        finished = [r for r in rows if r["state"] == "FINISHED"]
        assert len(finished) == 500
        summary = summarize_tasks()
        assert summary["dropped_at_source"] == 0
        name = next(k for k in summary["summary"] if "unit_te" in k)
        assert summary["summary"][name]["count"] == 500

    def test_filters_and_pagination(self, thread_cluster):
        from ray_tpu.experimental.state import list_tasks

        @ray_tpu.remote
        def page_te(i):
            return i

        ray_tpu.get([page_te.remote(i) for i in range(10)], timeout=60)
        finished = list_tasks(filters=[("state", "=", "FINISHED")],
                              limit=None)
        assert all(r["state"] == "FINISHED" for r in finished)
        page1 = list_tasks(limit=4)
        page2 = list_tasks(limit=4, offset=4)
        assert len(page1) == 4 and len(page2) == 4
        assert {r["task_id"] for r in page1}.isdisjoint(
            {r["task_id"] for r in page2})
        not_finished = list_tasks(filters=[("state", "!=", "FINISHED")],
                                  limit=None)
        assert all(r["state"] != "FINISHED" for r in not_finished)

    def test_task_table_global_state(self, thread_cluster):
        from ray_tpu.state import state as global_state

        @ray_tpu.remote
        def table_te():
            return 1

        ref = table_te.remote()
        assert ray_tpu.get(ref, timeout=30) == 1
        table = global_state.task_table()
        tid = ref.task_id().hex()
        assert tid in table
        assert table[tid]["state"] == "FINISHED"

    def test_actor_task_lifecycle(self, thread_cluster):
        @ray_tpu.remote
        class CounterTE:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        c = CounterTE.remote()
        assert ray_tpu.get(c.bump.remote(), timeout=30) == 1
        rows = self._rows_named("CounterTE.bump")
        assert rows
        rec = rows[-1]
        states = [s for s, _ts in rec["events"]]
        assert "PENDING_ARGS_AVAIL" in states
        assert "SUBMITTED_TO_WORKER" in states
        assert rec["state"] == "FINISHED"

    def test_dashboard_tasks_route(self, thread_cluster):
        import json as json_mod

        from ray_tpu._private.worker import global_worker
        from ray_tpu.dashboard.head import start_dashboard

        @ray_tpu.remote
        def dash_te():
            return 1

        ray_tpu.get(dash_te.remote(), timeout=30)
        dash = start_dashboard(global_worker().cluster)
        try:
            body = urllib.request.urlopen(
                dash.url + "/api/tasks?state=FINISHED&limit=1000",
                timeout=10).read()
            rows = json_mod.loads(body)
            assert rows and all(r["state"] == "FINISHED" for r in rows)
            assert any("dash_te" in r["name"] for r in rows)
            body = urllib.request.urlopen(
                dash.url + "/api/tasks/summary", timeout=10).read()
            summary = json_mod.loads(body)
            assert summary["dropped_at_source"] == 0
            assert any("dash_te" in k for k in summary["summary"])
        finally:
            dash.stop()


class TestSchedulerTickMetrics:
    """Scheduler tick instrumentation: latency histogram, queue depth
    gauge, spillback/fallback counters at /metrics, and a tracing span
    per working tick."""

    def _scrape(self):
        from ray_tpu._private.metrics_agent import get_metrics_registry
        return get_metrics_registry().render_prometheus()

    def test_tick_series_exposed_and_populated(self, thread_cluster):
        @ray_tpu.remote
        def tick_te(i):
            return i

        ray_tpu.get([tick_te.remote(i) for i in range(16)], timeout=60)
        text = self._scrape()
        assert "ray_tpu_scheduler_tick_latency_bucket" in text
        assert "ray_tpu_scheduler_pending_queue_depth" in text
        assert "ray_tpu_scheduler_tick_ticks" in text
        assert "ray_tpu_scheduler_tick_spillbacks" in text
        assert "ray_tpu_scheduler_tick_jnp_fallbacks" in text
        # The histogram carries at least one observation after a tick.
        counts = [float(line.rsplit(" ", 1)[1])
                  for line in text.splitlines()
                  if line.startswith("ray_tpu_scheduler_tick_latency_count")]
        assert counts and max(counts) >= 1
        # The scheduler actually ticked with work queued.
        busy = [float(line.rsplit(" ", 1)[1])
                for line in text.splitlines()
                if line.startswith("ray_tpu_scheduler_tick_busy_ticks")]
        assert busy and max(busy) >= 1

    def test_tick_emits_tracing_span(self):
        from ray_tpu.util import tracing
        ray_tpu.init(num_cpus=2, _system_config={"tracing_enabled": True})
        try:
            tracing.clear()

            @ray_tpu.remote
            def span_te():
                return 1

            assert ray_tpu.get(span_te.remote(), timeout=30) == 1
            events = ray_tpu.timeline()
            ticks = [e for e in events if e["cat"] == "sched"]
            assert ticks, "no scheduler.tick span in the timeline"
            assert any(e["name"] == "scheduler.tick" for e in ticks)
        finally:
            ray_tpu.shutdown()
            tracing.enable(False)
            tracing.clear()


class TestLatencyEnvelope:
    def test_task_roundtrip_tail_latency(self, thread_cluster):
        """Pins the magic-timeout hazards (an earlier review: wait()'s 200 ms
        coarse-poll fallback, get's fixed pull wait): if a READY
        object's get ever falls into a polling fallback, p99 blows past
        the bound.  The bound is generous for a loaded CI box; the
        assertion is about fallback regressions, not peak speed."""
        import time as time_mod

        @ray_tpu.remote
        def echo(i):
            return i

        # Warm the worker pool / code paths.
        ray_tpu.get([echo.remote(i) for i in range(20)], timeout=60)
        lat = []
        for i in range(200):
            t0 = time_mod.perf_counter()
            assert ray_tpu.get(echo.remote(i), timeout=30) == i
            lat.append(time_mod.perf_counter() - t0)
        lat.sort()
        p50 = lat[len(lat) // 2]
        p99 = lat[int(len(lat) * 0.99)]
        assert p50 < 0.05, f"median task round-trip {p50*1e3:.1f} ms"
        assert p99 < 0.25, \
            f"p99 {p99*1e3:.1f} ms — a ready-object get hit a polling " \
            "fallback"

    def test_wait_ready_object_is_fast(self, thread_cluster):
        import time as time_mod

        @ray_tpu.remote
        def one():
            return 1

        refs = [one.remote() for _ in range(8)]
        ray_tpu.get(refs, timeout=30)          # all sealed
        t0 = time_mod.perf_counter()
        for _ in range(50):
            ready, rest = ray_tpu.wait(refs, num_returns=8, timeout=5.0)
            assert len(ready) == 8 and not rest
        dt = (time_mod.perf_counter() - t0) / 50
        assert dt < 0.05, \
            f"wait() on sealed objects took {dt*1e3:.1f} ms — the " \
            "coarse-poll fallback is on the ready path"


class TestDispatchLatencyDecomposition:
    """Per-stage task-dispatch latency derived from the task-event
    lifecycle (queue_wait -> dispatch -> startup; total = submit ->
    running, the BASELINE.json north-star p99)."""

    def _manager(self):
        from ray_tpu.gcs.pubsub import Publisher
        from ray_tpu.gcs.task_events import TaskEventManager
        pub = Publisher()
        return pub, TaskEventManager(pub)

    def _feed(self, pub, events):
        from ray_tpu.gcs.pubsub import TASK_EVENT_CHANNEL
        pub.publish(TASK_EVENT_CHANNEL, b"",
                    {"buffer_id": "test", "events": events, "dropped": 0})

    def test_injected_stage_delays_attributed_to_right_stage(self):
        """ACCEPTANCE: a known per-stage delay shows up in that stage's
        rollup and nowhere else."""
        from ray_tpu.gcs import task_events as te
        pub, mgr = self._manager()
        t0 = 1_000_000.0
        delays = {"queue_wait": 0.5, "dispatch": 0.2, "startup": 0.3,
                  "execution": 0.25}
        self._feed(pub, [
            {"task_id": "t1", "state": te.PENDING_ARGS_AVAIL, "ts": t0},
            {"task_id": "t1", "state": te.SCHEDULED,
             "ts": t0 + 0.5},
            {"task_id": "t1", "state": te.SUBMITTED_TO_WORKER,
             "ts": t0 + 0.7},
            {"task_id": "t1", "state": te.RUNNING, "ts": t0 + 1.0},
            {"task_id": "t1", "state": te.FINISHED, "ts": t0 + 1.25},
        ])
        summary = mgr.latency_summary()
        for stage, expect in delays.items():
            assert stage in summary, (stage, summary)
            assert abs(summary[stage]["p50_s"] - expect) < 1e-6, \
                (stage, summary[stage])
            assert summary[stage]["count"] == 1
        # total = submit -> running (excludes execution).
        assert abs(summary["total"]["p50_s"] - 1.0) < 1e-6

    def test_duplicate_and_straggler_events_do_not_double_count(self):
        from ray_tpu.gcs import task_events as te
        pub, mgr = self._manager()
        t0 = 1_000_000.0
        self._feed(pub, [
            {"task_id": "t1", "state": te.PENDING_ARGS_AVAIL, "ts": t0},
            {"task_id": "t1", "state": te.SCHEDULED, "ts": t0 + 0.1},
            # Straggling duplicate of SCHEDULED from another buffer.
            {"task_id": "t1", "state": te.SCHEDULED, "ts": t0 + 0.4},
            # The straggler must NOT have overwritten the anchor:
            # dispatch measures against the FIRST SCHEDULED (t0+0.1).
            {"task_id": "t1", "state": te.SUBMITTED_TO_WORKER,
             "ts": t0 + 0.15},
        ])
        summary = mgr.latency_summary()
        assert summary["queue_wait"]["count"] == 1
        assert abs(summary["dispatch"]["p50_s"] - 0.05) < 1e-6, summary

    def test_out_of_order_cross_buffer_arrival_still_measures(self):
        """The dependent state routinely lands before its anchor (owner
        and node buffers interleave): the stage must be measured when
        the anchor arrives, not dropped."""
        from ray_tpu.gcs import task_events as te
        pub, mgr = self._manager()
        t0 = 1_000_000.0
        self._feed(pub, [
            # Node-side SCHEDULED reaches the manager FIRST...
            {"task_id": "t1", "state": te.SCHEDULED, "ts": t0 + 0.5},
            # ...then the owner's PENDING batch flushes.
            {"task_id": "t1", "state": te.PENDING_ARGS_AVAIL, "ts": t0},
        ])
        summary = mgr.latency_summary()
        assert summary["queue_wait"]["count"] == 1
        assert abs(summary["queue_wait"]["p50_s"] - 0.5) < 1e-6

    def test_retry_measures_stages_again(self):
        from ray_tpu.gcs import task_events as te
        pub, mgr = self._manager()
        t0 = 1_000_000.0
        self._feed(pub, [
            {"task_id": "t1", "state": te.PENDING_ARGS_AVAIL, "ts": t0},
            {"task_id": "t1", "state": te.SCHEDULED, "ts": t0 + 0.1},
            # Retry: attempt bumps, lifecycle reruns.
            {"task_id": "t1", "state": te.PENDING_ARGS_AVAIL,
             "ts": t0 + 1.0, "attempt": 1},
            {"task_id": "t1", "state": te.SCHEDULED,
             "ts": t0 + 1.3, "attempt": 1},
        ])
        assert mgr.latency_summary()["queue_wait"]["count"] == 2

    def test_e2e_rollup_and_metrics_surface(self, thread_cluster):
        from ray_tpu.experimental.state.api import summarize_tasks

        @ray_tpu.remote
        def f(x):
            return x

        assert ray_tpu.get([f.remote(i) for i in range(30)],
                           timeout=60) == list(range(30))
        stages = summarize_tasks()["dispatch_latency"]
        # Every task has dispatch/startup/total/execution; queue_wait
        # only exists for tasks that traversed the raylet scheduler
        # (lease-reuse pushes legitimately skip SCHEDULED).
        for stage in ("dispatch", "startup", "total", "execution"):
            assert stage in stages, stages
            assert stages[stage]["count"] >= 30
        assert stages.get("queue_wait", {}).get("count", 0) >= 1
        for row in stages.values():
            assert 0.0 <= row["p50_s"] <= row["p99_s"] <= row["max_s"]
        from ray_tpu._private.metrics_agent import get_metrics_registry
        text = get_metrics_registry().render_prometheus()
        assert 'ray_tpu_task_dispatch_stage_seconds_bucket' in text
        assert 'stage="total"' in text


class TestMetricsRegistryBounds:
    """Regression: a bucketless histogram must never accumulate a raw
    observation list (unbounded memory on a hot path)."""

    def test_bucketless_histogram_forced_onto_default_buckets(self):
        from ray_tpu._private.metrics_agent import (MetricsRegistry,
                                                    _Hist)
        reg = MetricsRegistry()
        reg.register("h.nobuckets", "histogram")     # no buckets given
        for i in range(10_000):
            reg.observe("h.nobuckets", i / 10_000.0, ())
        val = reg.get_value("h.nobuckets", ())
        assert isinstance(val, _Hist), type(val)     # not a list
        assert val.count == 10_000
        # Renders as a real histogram.
        text = reg.render_prometheus()
        assert "h_nobuckets_bucket" in text
        assert "h_nobuckets_count 10000" in text


class TestTracingRing:
    """The tracing buffer is a fixed ring: overflow drops the OLDEST
    events, counted and surfaced (instant event + /metrics)."""

    def test_ring_bounds_and_drop_accounting(self):
        from ray_tpu.util import tracing
        tracing.clear()
        tracing.enable(True)
        old_cap = tracing._max_events
        try:
            tracing.set_capacity(10)
            for i in range(50):
                tracing.record_instant(f"ev{i}")
            assert tracing.num_buffered() <= 10
            assert tracing.dropped_count() == 40
            events = tracing.drain()
            # Ring keeps the newest events; a drop marker rides the
            # drain so loss is visible in the trace itself.
            names = [e["name"] for e in events]
            assert "ev49" in names and "ev0" not in names
            markers = [e for e in events if e["name"] == "tracing.dropped"]
            assert markers and \
                markers[0]["args"]["dropped_total"] == 40
            # /metrics surface.
            from ray_tpu._private.metrics_agent import \
                get_metrics_registry
            text = get_metrics_registry().render_prometheus()
            assert "ray_tpu_tracing_dropped_events" in text
        finally:
            tracing.set_capacity(old_cap)
            tracing.enable(False)
            tracing.clear()


class TestTimelineStoreClockSkew:
    """GCS-side timeline store: bounded ingest + clock normalization
    (a skewed node's spans land in head-clock microseconds)."""

    def _store(self, **kw):
        from ray_tpu.gcs.pubsub import Publisher
        from ray_tpu.gcs.timeline import TimelineStore
        pub = Publisher()
        return pub, TimelineStore(pub, **kw)

    def _publish(self, pub, events, offset_us=0.0, source="n1",
                 node_id="n1", dropped=0):
        from ray_tpu.gcs.pubsub import TIMELINE_CHANNEL
        pub.publish(TIMELINE_CHANNEL, b"",
                    {"source": source, "node_id": node_id,
                     "clock_offset_us": offset_us, "dropped": dropped,
                     "events": events})

    def test_injected_skew_normalized_and_parent_child_monotone(self):
        pub, store = self._store()
        # Head-side parent span at t=1000s; the child ran 10ms later on
        # a node whose clock is 2s BEHIND: its raw ts precedes the
        # parent until the node's estimated +2s offset is applied.
        parent_ts = 1_000.0 * 1e6
        child_raw_ts = (1_000.0 + 0.010 - 2.0) * 1e6
        self._publish(pub, [{"name": "child", "ph": "X",
                             "ts": child_raw_ts, "dur": 5.0,
                             "pid": 2, "tid": 1}],
                      offset_us=2.0 * 1e6)
        (child,) = store.events()
        assert child["ts"] >= parent_ts
        assert abs(child["ts"] - (parent_ts + 10_000)) < 1.0
        assert child["args"]["node_id"] == "n1"

    def test_bounded_ring_with_drop_counters(self):
        pub, store = self._store(max_events=5)
        self._publish(pub, [{"name": f"e{i}", "ph": "i", "ts": float(i),
                             "pid": 1, "tid": 1} for i in range(12)],
                      dropped=3)
        assert store.num_buffered() == 5
        assert store.dropped == 7
        assert store.num_dropped_at_source() == 3
        events = store.events()
        names = [e["name"] for e in events]
        assert "e11" in names and "e0" not in names    # oldest dropped
        marker = [e for e in events if e["name"] == "timeline.dropped"]
        assert marker and marker[0]["args"]["store_dropped"] == 7


class TestMetricsFederationUnit:
    """Delta shipper + head-side federation (same-process unit test;
    the cross-process path is covered in test_cross_process_cluster)."""

    def test_delta_upsert_and_prune(self):
        from ray_tpu._private.metrics_agent import (
            MetricsDeltaShipper, MetricsFederation, MetricsRegistry)
        node_reg = MetricsRegistry()
        head_reg = MetricsRegistry()
        node_reg.register("n.counter", "counter")
        node_reg.inc("n.counter", 3.0, (("k", "v"),))
        shipper = MetricsDeltaShipper(node_reg)
        fed = MetricsFederation(head_reg)
        snap, full = shipper.collect_delta()
        assert full            # first report is a full snapshot
        fed.ingest("nodeA", snap, full=full)
        text = head_reg.render_prometheus()
        assert 'n_counter{k="v",node_id="nodeA"} 3.0' in text
        # Steady state: nothing changed, nothing ships.
        assert shipper.collect_delta() == (None, False)
        # A change ships only the changed series, upserted at the head.
        node_reg.inc("n.counter", 2.0, (("k", "v"),))
        delta, full = shipper.collect_delta()
        assert not full and list(delta) == ["n.counter"]
        fed.ingest("nodeA", delta, full=full)
        assert 'n_counter{k="v",node_id="nodeA"} 5.0' in \
            head_reg.render_prometheus()
        # Prune: every series the node ever shipped vanishes.
        fed.drop("nodeA")
        assert "nodeA" not in head_reg.render_prometheus()

    def test_full_resync_prunes_locally_dropped_series(self):
        """Worker churn prunes series in the node registry; a FULL
        report must stop the head from rendering the stale copies."""
        from ray_tpu._private.metrics_agent import (
            MetricsDeltaShipper, MetricsFederation, MetricsRegistry)
        node_reg = MetricsRegistry()
        head_reg = MetricsRegistry()
        node_reg.register("w.gauge", "gauge")
        node_reg.set("w.gauge", 1.0, (("worker", "w1"),))
        node_reg.set("w.gauge", 2.0, (("worker", "w2"),))
        shipper = MetricsDeltaShipper(node_reg, full_every=2)
        fed = MetricsFederation(head_reg)
        snap, full = shipper.collect_delta()
        fed.ingest("nodeA", snap, full=full)
        assert 'worker="w1"' in head_reg.render_prometheus()
        # w1's worker dies: the node prunes its series locally.
        with node_reg._lock:
            node_reg._metrics["w.gauge"].series.pop((("worker", "w1"),))
        # Delta report in between (reports: 1 -> 2)...
        node_reg.set("w.gauge", 2.5, (("worker", "w2"),))
        snap, full = shipper.collect_delta()
        assert not full
        fed.ingest("nodeA", snap, full=full)
        assert 'worker="w1"' in head_reg.render_prometheus()  # still stale
        # ...then full_every=2 makes this report FULL -> head replaces.
        node_reg.set("w.gauge", 3.0, (("worker", "w2"),))
        snap, full = shipper.collect_delta()
        assert full
        fed.ingest("nodeA", snap, full=full)
        text = head_reg.render_prometheus()
        assert 'worker="w1"' not in text, text
        assert 'w_gauge{node_id="nodeA",worker="w2"} 3.0' in text

    def test_repeat_dump_keeps_drop_marker(self):
        from ray_tpu.util import tracing
        tracing.clear()
        tracing.enable(True)
        old_cap = tracing._max_events
        try:
            tracing.set_capacity(5)
            for i in range(9):
                tracing.record_instant(f"x{i}")
            for _ in range(2):       # read-only dump never consumes it
                dump = tracing.chrome_tracing_dump()
                assert any(e["name"] == "tracing.dropped" for e in dump)
        finally:
            tracing.set_capacity(old_cap)
            tracing.enable(False)
            tracing.clear()
