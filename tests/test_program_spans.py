"""The program's spans on the device trace's clock (PR 26).

``tracing.span`` has two sinks: the ring (``enable()``; ``time.time()``
stamps, parent ids — what ``ray_tpu.timeline()`` reads) and, where
``jax`` is already imported, the XLA profiler's host plane.  These tests
pin both, the spans and counters that split the raylet tick and the
train worker from inside, and the two benchmark readers that read them.
"""

import contextlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private.worker import global_worker
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TICK_CHILDREN = ("scheduler.collect", "scheduler.solve", "scheduler.reply",
                 "scheduler.backlog")
SOLVE_CHILDREN = ("scheduler.solve.sync", "scheduler.solve.classes",
                  "scheduler.solve.dispatch", "scheduler.solve.fetch",
                  "scheduler.solve.expand")
ANSWER_KINDS = ("granted_local", "spillbacks", "requeued_busy",
                "parked_infeasible", "spill_refused",
                "requeued_dispatch_failed")


@contextlib.contextmanager
def _alarm(seconds: int):
    """This test's own timeout (no pytest-timeout here): a profiler
    session that hangs must fail this test, not the run."""
    def _raise(signum, frame):
        raise TimeoutError(f"no end after {seconds} s")
    old = signal.signal(signal.SIGALRM, _raise)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def ring():
    """The ring on and empty; off and empty afterwards."""
    tracing.clear()
    tracing.enable(True)
    yield tracing
    tracing.enable(False)
    tracing.clear()


def _spec(remote_fn, **resources):
    from ray_tpu._private.task_spec import make_spec
    core = global_worker().core_worker
    spec = make_spec(
        job_id=global_worker().job_id, owner_id=core.worker_id,
        function_id=core.function_manager.export(remote_fn._function),
        function_name="noop", args=[], num_returns=1, resources=resources)
    core.task_manager.add_pending_task(spec)
    return spec


def _two_node_cluster(ray_start_cluster):
    """Head with 2 CPUs (jax backend, the default) and a remote node
    with 8, the remote's row already in the head's view."""
    cluster = ray_start_cluster(num_cpus=2)
    cluster.add_node(num_cpus=8)
    assert cluster.wait_for_nodes(2)
    head = cluster.head_node
    deadline = time.monotonic() + 30
    while len(head.cluster_view.node_ids()) < 2:
        assert time.monotonic() < deadline, "view never saw node 2"
        time.sleep(0.02)
    return head


def _mixed_batch(head):
    """ONE lease batch, so one tick: 14 x CPU:1 against 10 CPUs (local
    grants, spills, and four that stay busy) and 2 x CPU:64 (no node's
    total fits).  Returns (results, tick_stats before, after)."""
    @ray_tpu.remote
    def noop():
        return None

    specs = [_spec(noop, CPU=1) for _ in range(14)] + \
        [_spec(noop, CPU=64) for _ in range(2)]
    mgr = head.cluster_task_manager
    before = dict(mgr.tick_stats)
    done, got = threading.Event(), {}

    def reply(result):
        got["results"] = result["results"]
        done.set()

    head.request_worker_lease_batch(specs, reply)
    assert done.wait(timeout=60), "the batch was never answered"
    # the reply fires inside the tick; its stats fold at the tick's end
    deadline = time.monotonic() + 10
    while mgr.tick_stats["busy_ticks"] == before["busy_ticks"]:
        assert time.monotonic() < deadline, "the tick never ended"
        time.sleep(0.005)
    for r in got["results"]:
        if "worker" in r:
            r["raylet"].return_worker(r["worker"])
    return got["results"], before, dict(mgr.tick_stats)


def test_span_reaches_the_profiler_with_the_ring_off(tmp_path):
    """(a) One profiler session on the CPU backend; a span opened with
    the ring DISABLED is found by name on a /host: plane, through the
    benchmark's own loader.  The only test that starts a session."""
    import jax  # noqa: F401  (the sink exists only once jax is imported)

    from benchmarks.harness import trace_reduce
    assert not tracing.is_enabled()
    tracing.clear()
    with _alarm(120):
        trace_reduce.start(str(tmp_path))
        try:
            with tracing.span("scheduler.probe", queued=3):
                time.sleep(0.002)
        finally:
            trace_reduce.stop()
        reduced = trace_reduce.load(str(tmp_path))
    found = [s for s in reduced["host_spans"] if s[0] == "scheduler.probe"]
    # the constant name alone: metadata stays in the ring
    assert len(found) == 1, reduced["host_spans"]
    assert found[0][2] >= 2e6                  # ns: the sleep is inside
    assert tracing.num_buffered() == 0         # and the ring stayed off


def test_a_working_tick_yields_the_spans_chained_to_the_tick(
        ray_start_cluster, ring):
    """(b) scheduler.collect / solve (+ its five children) / reply /
    backlog, each chaining by parent_id to scheduler.tick and lying
    inside its parent's interval."""
    head = _two_node_cluster(ray_start_cluster)
    ring.clear()
    _mixed_batch(head)
    deadline = time.monotonic() + 10
    while True:
        events = [e for e in ring.chrome_tracing_dump()
                  if e.get("cat") == "sched"]
        ticks = [e for e in events if e["name"] == "scheduler.tick"
                 and e["args"].get("queued") == 16]
        if ticks:
            break
        assert time.monotonic() < deadline, [e["name"] for e in events]
        time.sleep(0.01)
    (tick,) = ticks
    assert tick["args"]["swept_batches"] == 1
    assert tick["args"]["oldest_lease_wait_ms"] > 0
    by_id = {e["args"]["span_id"]: e for e in events}
    trace = [e for e in events
             if e["args"]["trace_id"] == tick["args"]["trace_id"]]
    by_name = {}
    for e in trace:
        by_name.setdefault(e["name"], []).append(e)
    for name in TICK_CHILDREN + SOLVE_CHILDREN:
        assert len(by_name.get(name, ())) == 1, (name, sorted(by_name))
    for name in TICK_CHILDREN:
        assert by_name[name][0]["args"]["parent_id"] == \
            tick["args"]["span_id"], name
    for name in SOLVE_CHILDREN:
        assert by_name[name][0]["args"]["parent_id"] == \
            by_name["scheduler.solve"][0]["args"]["span_id"], name
    slack = 50.0            # us: two time.time() reads per edge
    for name in TICK_CHILDREN + SOLVE_CHILDREN:
        child = by_name[name][0]
        parent = by_id[child["args"]["parent_id"]]
        assert child["ts"] >= parent["ts"] - slack, name
        assert child["ts"] + child["dur"] <= \
            parent["ts"] + parent["dur"] + slack, name


def test_answers_by_kind_sum_to_the_batch_and_waits_are_observed(
        ray_start_cluster):
    """(c) local grants + spillbacks + busy + infeasible (+ the refused
    and failed kinds) sum to last_batch_tasks on a batch that mixes
    them; (d) lease_batch_wait gets one observation per swept batch."""
    from ray_tpu._private.metrics_agent import get_metrics_registry
    head = _two_node_cluster(ray_start_cluster)
    mgr = head.cluster_task_manager
    label = (("node", mgr._node_label),)

    def waits():
        hist = get_metrics_registry().get_value(
            "ray_tpu.scheduler.lease_batch_wait", label)
        return hist.count if hist is not None else 0

    waits_before = waits()
    results, before, after = _mixed_batch(head)
    delta = {k: after[k] - before[k] for k in ANSWER_KINDS}
    assert after["last_batch_tasks"] == 16
    assert sum(delta.values()) == 16, delta
    assert delta["granted_local"] >= 1, delta
    assert delta["spillbacks"] >= 1, delta
    assert delta["requeued_busy"] >= 1, delta
    assert delta["parked_infeasible"] == 2, delta
    assert after["is_feasible_anywhere_calls"] - \
        before["is_feasible_anywhere_calls"] == \
        delta["requeued_busy"] + delta["parked_infeasible"]
    # what the submitter saw agrees with what the raylet counted
    assert sum(1 for r in results if "worker" in r) == \
        delta["granted_local"]
    assert sum(1 for r in results if "retry_at" in r) == \
        delta["spillbacks"]
    assert sum(1 for r in results if r.get("infeasible")) == 2
    assert waits() - waits_before == 1
    assert mgr._jax_solver.stats["solve_programs"] >= 1
    # the per-entry record is gone; the tick's record carries the counts
    from ray_tpu._private.debug import flight_recorder
    records = flight_recorder.tail()
    assert not [r for r in records if r["cat"] == "sched.spillback"]
    tick = [r for r in records if r["cat"] == "sched.tick"
            and r.get("batch_tasks") == 16][-1]
    assert tick["swept_batches"] == 1 and tick["oldest_lease_wait_ms"] > 0
    assert tick["parked_infeasible"] == after["parked_infeasible"]


def test_off_means_off(ray_start_cluster):
    """(e) Ring disabled, no profiler session: a working tick and a
    train.report append nothing to the ring."""
    from ray_tpu.train.session import Session
    assert not tracing.is_enabled()
    tracing.clear()
    head = _two_node_cluster(ray_start_cluster)
    _mixed_batch(head)
    session = Session(lambda: None, 0, 0, 1)
    session.report(loss=1.0)
    session.save_checkpoint(step=1)
    assert session.get_next(timeout=1).type == "report"
    assert tracing.num_buffered() == 0
    assert tracing.chrome_tracing_dump() == []


def test_train_worker_spans(ring):
    """train.report from the session, train.model_step around each call
    of what make_train_step returns -- which still reaches the jitted
    function's own attributes (chip_smoke.py calls ``.lower``)."""
    import jax

    from ray_tpu.models.transformer import _TracedStep
    from ray_tpu.train.session import Session
    step = _TracedStep(jax.jit(lambda state, batch: (state + batch, batch)))
    assert int(step(1, 2)[0]) == 3
    assert "add" in step.lower(1, 2).as_text()
    session = Session(lambda: None, 0, 0, 1)
    session.report(loss=1.0)
    session.save_checkpoint(step=1)
    names = [e["name"] for e in ring.chrome_tracing_dump()]
    assert names.count("train.model_step") == 1
    assert names.count("train.report") == 2


def _reader(name):
    from benchmarks import run as bench_run
    return bench_run._reader(name)


def _ctx(spans):
    return {"trace": {"device_ops": {}, "host_spans": spans}}


@pytest.mark.parametrize("spans,want", [
    # medians of each span, summed, in ms
    ([["train.model_step", 0, 300e3], ["train.model_step", 2e9, 500e3],
      ["train.model_step", 4e9, 400e3], ["train.report", 1e9, 20e3],
      ["train.report", 3e9, 40e3], ["train.step", 0, 9e9]], 0.43),
    # a worker that never reports: the step's dispatch alone
    ([["train.model_step", 0, 250e3]], 0.25),
    # the parent commit's trace: the benchmark's own annotations only
    ([["train.step", 0, 1e6], ["train.wait", 1e6, 1e9]], None),
    ([], None),
])
def test_train_worker_self_ms_reader(spans, want):
    got = _reader("train_worker_self_ms")(_ctx(spans))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("spans,want", [
    ([["scheduler.tick", 0, 4e9], ["scheduler.tick", 5e9, 2e9],
      ["scheduler.tick", 8e9, 3e9], ["scheduler.reply", 1e9, 1e9],
      ["round.wait_replies", 0, 9e9]], 3000.0),
    ([["round.submit", 0, 1e9], ["round.wait_replies", 1e9, 5e9]], None),
    ([], None),
])
def test_raylet_tick_ms_reader(spans, want):
    got = _reader("raylet_tick_ms")(_ctx(spans))
    assert got == (None if want is None else pytest.approx(want))


def test_recorded_raylet_round_splits_by_the_programs_spans():
    """The recorded chip trace of two raylet-64.backlog rounds: the tick
    reader finds the ticks, and the existing idle-gap reduction -- not a
    second one -- names the tick's children."""
    from benchmarks.harness import trace_reduce
    with open(os.path.join(ROOT, "benchmarks", "recorded",
                           "raylet_round.json")) as f:
        recorded = json.load(f)
    names = {s[0] for s in recorded["host_spans"]}
    assert {"scheduler.tick", "round.wait_replies"} <= names
    assert set(TICK_CHILDREN + SOLVE_CHILDREN) <= names
    tick_ms = _reader("raylet_tick_ms")({"trace": recorded})
    ticks = sorted(s[2] for s in recorded["host_spans"]
                   if s[0] == "scheduler.tick")
    assert ticks[0] / 1e6 <= tick_ms <= ticks[-1] / 1e6
    gaps = dict(trace_reduce.idle_gaps(recorded, k=50))
    # the device idles under the tick's children: not under the tick
    # itself, the harness's round.* or no span at all
    largest = max(gaps, key=gaps.get)
    assert largest.startswith("scheduler.") and largest != "scheduler.tick"
    assert "round.wait_replies" not in gaps, gaps


def test_tracing_does_not_import_jax():
    """(g) Worker children, the GCS and CPU-only drivers open spans
    without paying for jax."""
    code = ("import sys\n"
            "from ray_tpu.util import tracing\n"
            "with tracing.span('scheduler.probe'):\n"
            "    pass\n"
            "tracing.enable(True)\n"
            "with tracing.span('scheduler.probe'):\n"
            "    pass\n"
            "assert tracing.num_buffered() == 1\n"
            "assert 'jax' not in sys.modules, 'tracing imported jax'\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
