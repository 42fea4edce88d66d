"""The program's spans on the device trace's clock (PR 26).

``tracing.span`` has two sinks: the ring (``enable()``; ``time.time()``
stamps, parent ids — what ``ray_tpu.timeline()`` reads) and, where
``jax`` is already imported, the XLA profiler's host plane.  These tests
pin both, the spans and counters that split the raylet tick and the
train worker from inside, and the two benchmark readers that read them.

Since PR 37 the module also holds the registry of compiled programs: the
manifest of the train step that ran (which ``named_scope`` and which pass
owns each instruction), ``device_time_by_scope`` and the eight benchmark
readers that sum a traced window's device time by it.
"""

import ast
import contextlib
import gc
import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time
import weakref

import pytest

import ray_tpu
from ray_tpu._private.worker import global_worker
from ray_tpu.util import tracing
from tiny_steps import _tiny_step

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
            "assert tracing.programs() == {}\n"
            "assert tracing.manifest_of_text('')['scopes'] == {}\n"
            "assert 'jax' not in sys.modules, 'tracing imported jax'\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


# ---- the registry of compiled programs (PR 37) -------------------------

def _train_path_calls():
    """(file, called name, call node) for every call in the train path's
    source, ``ray_tpu/models`` and ``ray_tpu/ops``."""
    for folder in ("models", "ops"):
        for path in sorted(glob.glob(os.path.join(
                ROOT, "ray_tpu", folder, "*.py"))):
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call):
                    called = getattr(node.func, "attr",
                                     getattr(node.func, "id", None))
                    yield os.path.relpath(path, ROOT), called, node


# Kernels that are NOT in ``KERNEL_EVENTS``, on purpose: their custom
# calls keep the scope of their ``op_name`` (``gdn_conv``), so their time
# stays in that scope's group metric (``delta_layers_ms``); listed, they
# would fall out of every group (PR 41).
KERNELS_UNDER_THEIR_SCOPE = {"causal_conv_fwd": "gdn_conv",
                             "causal_conv_bwd": "gdn_conv"}


def test_every_scope_literal_is_declared_and_every_declared_scope_is_used():
    """A ``named_scope`` cannot ship on the train path without being in
    ``STEP_SCOPES`` (so without a reader, below), nor a ``pallas_call``
    under a name that is neither in ``KERNEL_EVENTS`` nor counted under
    the scope it is called in; and the lists hold no name the source has
    lost.  Read from the syntax tree."""
    scopes, kernels = set(), set()
    for path, called, node in _train_path_calls():
        if called == "named_scope":
            (arg,) = node.args
            assert isinstance(arg, ast.Constant), (path, node.lineno)
            assert arg.value in tracing.STEP_SCOPES, (path, arg.value)
            scopes.add(arg.value)
        elif called == "pallas_call":
            names = [k.value for k in node.keywords if k.arg == "name"]
            assert names and isinstance(names[0], ast.Constant), \
                (path, node.lineno)
            assert names[0].value in (*tracing.KERNEL_EVENTS,
                                      *KERNELS_UNDER_THEIR_SCOPE), \
                (path, names[0].value)
            kernels.add(names[0].value)
    assert scopes == set(tracing.STEP_SCOPES)
    assert kernels == {*tracing.KERNEL_EVENTS, *KERNELS_UNDER_THEIR_SCOPE}
    assert not set(tracing.KERNEL_EVENTS) & set(KERNELS_UNDER_THEIR_SCOPE)
    assert set(KERNELS_UNDER_THEIR_SCOPE.values()) <= set(tracing.STEP_SCOPES)
    assert len(set(tracing.STEP_SCOPES)) == len(tracing.STEP_SCOPES) == 40
    assert set(tracing.RUN_SCOPES) == {"mha_window"} < set(
        tracing.STEP_SCOPES)
    assert tracing.KERNEL_EVENTS == (
        "flash_attention_fwd", "flash_attention_bwd", "gated_delta_fwd",
        "gated_delta_bwd", "selective_scan_fwd", "selective_scan_bwd",
        "ssd_fwd", "ssd_bwd", "kda_fwd", "kda_bwd")


def test_every_declared_scope_feeds_one_metric():
    """Each scope and kernel event is summed by exactly one of the
    benchmark's group metrics -- ``step_scopes.GROUPS``, or the list in
    the file of a metric that came after it (``ssm_layers_ms``,
    ``mamba2_layers_ms``, ``kda_layers_ms``);
    ``optimizer`` is left to the rest (``train_step_device_ms`` less the
    groups), and ``diff_attn`` to ``step_attributed_pct`` alone.  A
    run's scope owns no instruction and is no group's: it is what one
    reader keeps rows by (``window_layers_ms``)."""
    from benchmarks import run as bench_run
    from benchmarks.harness import step_scopes
    assert (bench_run._reader("window_layers_ms").__globals__["RUN"],) == \
        tracing.RUN_SCOPES
    ssm = bench_run._reader("ssm_layers_ms").__globals__["SCOPES"]
    assert {"ssm_proj", "ssm_conv", "ssm_scan", "ssm_out", "gmu",
            "selective_scan_fwd", "selective_scan_bwd"} == set(ssm)
    mamba2 = bench_run._reader("mamba2_layers_ms").__globals__["SCOPES"]
    assert {"ssd_proj", "ssd_conv", "ssd_rule", "ssd_norm", "ssd_out",
            "ssd_fwd", "ssd_bwd"} == set(mamba2)
    kda = bench_run._reader("kda_layers_ms").__globals__["SCOPES"]
    assert {"kda_proj", "kda_conv", "kda_core", "kda_out", "kda_fwd",
            "kda_bwd"} == set(kda)
    grouped = [s for group in step_scopes.GROUPS.values() for s in group]
    grouped += list(ssm) + list(mamba2) + list(kda)
    assert len(grouped) == len(set(grouped))
    assert set(grouped) | {"optimizer", "diff_attn"} == \
        (set(tracing.STEP_SCOPES) - set(tracing.RUN_SCOPES)) \
        | set(tracing.KERNEL_EVENTS)
    for metric in step_scopes.GROUPS:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", metric + ".py")), metric


ALL, ONCE, OUTSIDE = {"fwd", "bwd", "recompute"}, {"fwd", "bwd"}, {"fwd"}
# A layer's scopes run forward, backward and again under remat; the
# experts' products and the rows' return have a backward by hand that
# keeps what it needs (nothing of them is rematted); heads and losses
# lie outside the rematted scan; the optimizer and the router bias'
# update have no backward.  Off the TPU no flash or delta-rule kernel
# runs, so their two scopes are the chip's to show (PERF.md section 5).
MOE = {"moe_router": ALL, "moe_dispatch": ALL, "moe_experts": ONCE,
       "moe_combine": ONCE}
MANIFESTS = {
    "dense": {"attention": ALL, "ffn": ALL, "head_loss": ONCE,
              "optimizer": OUTSIDE},
    "block_diffusion": dict(MOE, attention=ALL, ffn=ALL, optimizer=OUTSIDE,
                            block_diffusion_loss=ONCE),
    "latent": dict(MOE, attention=ALL, ffn=ALL, mla_q=ALL, mla_kv=ALL,
                   mla_out=ALL, moe_shared=ALL, moe_bias=OUTSIDE,
                   head_loss=ONCE, mtp_loss=ONCE, mtp_module=ONCE,
                   optimizer=OUTSIDE),
    "hybrid": dict(MOE, attention=ALL, ffn=ALL, attn_gate=ALL, gdn_proj=ALL,
                   gdn_conv=ALL, gdn_core=ALL, gdn_out=ALL, moe_shared=ALL,
                   head_loss=ONCE, optimizer=OUTSIDE),
    "sambay": dict(attention=ALL, ffn=ALL, ssm_proj=ALL, ssm_conv=ALL,
                   ssm_scan=ALL, ssm_out=ALL, gmu=ALL, diff_attn=ALL,
                   head_loss=ONCE, optimizer=OUTSIDE),
    "windowed": dict(MOE, attention=ALL, ffn=ALL, attn_gate=ALL,
                     moe_shared=ALL, head_loss=ONCE, optimizer=OUTSIDE),
    # (the experts' latent sum is read by ``w_up``'s gradient: without a
    # plan the experts' forward runs again in the backward pass)
    "nemotron": dict(MOE, moe_experts=ALL, moe_combine=ALL, attention=ALL,
                     ffn=ALL, ssd_proj=ALL,
                     ssd_conv=ALL, ssd_rule=ALL, ssd_norm=ALL, ssd_out=ALL,
                     moe_shared=ALL, moe_bias=OUTSIDE, head_loss=ONCE,
                     optimizer=OUTSIDE),
    "ling": dict(MOE, attention=ALL, ffn=ALL, kda_proj=ALL, kda_conv=ALL,
                 kda_core=ALL, kda_out=ALL, mla_q=ALL, mla_kv=ALL,
                 mla_out=ALL, moe_shared=ALL, moe_bias=OUTSIDE,
                 head_loss=ONCE, optimizer=OUTSIDE),
}


@pytest.mark.parametrize("kind", sorted(MANIFESTS))
def test_the_manifest_has_each_scope_of_the_configuration(kind):
    """One call of the tiny step registers it; the manifest, resolved
    when read, has every scope the configuration uses in the passes it
    runs in, every ``while`` among the enclosing instructions, and the
    executable's memory."""
    tracing.clear()
    step, state, batch = _tiny_step(kind)
    assert tracing.programs() == {}              # registered by the call
    step(state, batch)
    entry = tracing.programs()["train_step"]
    phases = {}
    for scope, phase in entry["scopes"].values():
        phases.setdefault(scope, set()).add(phase)
    assert {k: v for k, v in phases.items() if k is not None} == \
        MANIFESTS[kind]
    assert phases[None] == ONCE
    # the runs under a window, and they alone, say so: their attention
    # halves (the gate's scope among them) in all three passes, nothing
    # of an FFN, and no scope of their own
    under = {}
    for name, run in entry["runs"].items():
        assert run == "mha_window"
        scope, phase = entry["scopes"][name]
        under.setdefault(scope, set()).add(phase)
    assert under == ({"attention": ALL, "attn_gate": ALL}
                     if kind == "windowed" else {})
    assert "mha_window" not in phases
    # (XLA:CPU outlines a small ``while`` as a ``call``: the hybrid
    # step's scan over a period's counters since PR 41)
    assert entry["enclosing"] and all(
        name.startswith(("while", "call")) for name in entry["enclosing"])
    assert set(entry["memory"]) == {
        "temp_size_in_bytes", "argument_size_in_bytes",
        "output_size_in_bytes", "peak_memory_in_bytes", "remat"}
    assert entry["memory"]["argument_size_in_bytes"] > 0
    # no device to ask here: the layer scans keep what they always kept
    assert entry["memory"]["remat"] == {
        "budget_bytes": None, "kept_bytes": 0, "runs": []}
    assert entry["text_bytes"] > 0 and entry["resolve_s"] > 0
    tracing.clear()


@pytest.mark.parametrize("kind", sorted(MANIFESTS))
def test_the_registry_entrys_memory_carries_the_plan(kind, monkeypatch):
    """Where the device reports room (stood in for here) the tiny step's
    layer scans keep more than the flash kernel's two names, and what
    they keep -- names a run, bytes a layer and in all, the budget, the
    names refused for room, and ``plan_seconds``, what making the plan
    cost the trace -- is in the registry entry's ``memory`` (the
    ``step_scopes`` line of a traced run prints it whole) and on
    /metrics; the step compiles once, the manifest read afterwards
    neither traces nor compiles, and the step's numbers are the
    unplanned step's."""
    import jax
    import numpy as np
    from jax import monitoring

    from ray_tpu._private.metrics_agent import get_metrics_registry
    from ray_tpu.models import remat
    tracing.clear()
    step, state, batch = _tiny_step(kind)
    plain = jax.device_get(step(jax.tree.map(jax.numpy.copy, state),
                                batch)[1])
    assert tracing.programs()["train_step"]["memory"]["remat"]["runs"] == []
    tracing.clear()
    step, _, _ = _tiny_step(kind)
    monkeypatch.setattr(remat, "device_memory",
                        lambda mesh=None: (10 ** 9, 0))     # room for all
    # (at a tiny width no product is dearer to make again than to keep)
    monkeypatch.setattr(remat, "_KEPT_BYTE_MOVES", 0.0)
    fired = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: fired.append(name.rsplit("/", 1)[-1]))
    planned = jax.device_get(step(state, batch)[1])
    assert fired.count("backend_compile_duration") == 1
    del fired[:]
    plan = tracing.programs()["train_step"]["memory"]["remat"]
    # jit's own caches answered the manifest: nothing lowered or compiled
    assert set(fired) <= {"jaxpr_trace_duration"}
    tracing.clear()
    assert plan["bytes_limit"] == 10 ** 9 and plan["bytes_in_use"] == 0
    assert plan["budget_bytes"] == int(remat.SAFETY * (
        10 ** 9 - plan["step_bytes"]))
    assert plan["runs"] and all(
        ("mid_residual" in run["names"]) != run["kind"].endswith("+none")
        and run["refused"] == [] and run["bytes_a_layer"] > 0
        for run in plan["runs"])
    assert plan["kept_bytes"] == sum(
        run["layers"] * run["bytes_a_layer"] for run in plan["runs"])
    assert sorted(run["kind"] for run in plan["runs"]) == {
        "dense": ["mha+dense"], "block_diffusion": ["mha+moe"],
        "latent": ["mla+dense", "mla+moe", "mla+moe"],
        "hybrid": ["gdn+moe", "mha+moe"],
        "sambay": ["diff:reads=kv+dense", "diff:window=8+dense",
                   "diff:writes=kv+dense", "gmu+dense", "mamba+dense",
                   "mamba:writes=memory+dense"],
        "windowed": ["mha:heads=6,rope=global+dense",
                     "mha:heads=6,rope=global+moe",
                     "mha:heads=9,window=8,rope=local+moe"],
        "nemotron": ["mamba2+moe", "mamba2+moe", "mamba2+none",
                     "mha+moe"],
        "ling": ["kda+dense", "kda+moe", "mla+moe"]}[kind]
    assert 0 < plan["plan_seconds"] < 5 and plan["trace_seconds"] > 0
    exposed = get_metrics_registry().render_prometheus().splitlines()
    for name in ("kept_bytes", "budget_bytes", "plan_seconds"):
        assert f"ray_tpu_train_remat_{name} {float(plan[name])}" in exposed
    for name, value in plain.items():
        np.testing.assert_allclose(planned[name], value, rtol=2e-5,
                                   atol=1e-6, err_msg=name)


def test_the_registry_costs_no_compile_unread_and_no_step_compiles_after():
    """Five calls of the step and nobody reading: one lowering and one
    backend compile, the step's own.  Reading the manifest afterwards
    compiles at most once more (on this JAX not at all: jit's own caches
    answer), and a later call of the step fires nothing."""
    from jax import monitoring
    fired = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: fired.append(name.rsplit("/", 1)[-1]))
    counted = ("jaxpr_to_mlir_module_duration", "backend_compile_duration")

    def count():
        got = [fired.count(name) for name in counted]
        del fired[:]
        return got

    import jax
    tracing.clear()
    step, state, batch = _tiny_step("dense")
    # a call under a trace has nothing to offer, and breaks nothing
    assert jax.eval_shape(step, state, batch)[1]["loss"].shape == ()
    assert tracing.programs() == {}
    count()
    for _ in range(5):
        state, metrics = step(state, batch)
    assert count() == [1, 1]
    entry = tracing.programs()["train_step"]
    assert entry["scopes"] and entry["scopes"] is entry["scopes"]
    lowerings, compiles = count()
    assert lowerings <= 1 and compiles <= 1
    state, metrics = step(state, batch)
    assert float(metrics["loss"]) > 0
    assert count() == [0, 0]
    tracing.clear()


class _Executable:
    """What ``jitted.lower(*args).compile()`` gives, by hand."""

    def __init__(self, text):
        self.text, self.lowered_with = text, None

    def lower(self, *args):
        self.lowered_with = args
        return self

    def compile(self):
        return self

    def as_text(self):
        return self.text

    def memory_analysis(self):
        return None


# What the chip's compiler writes, cut to a line an instruction: tiled
# layouts, names with and without "%", a fused computation's inner
# instruction, a kernel's custom call inside ``attention``, XLA's
# ``ragged-dot`` without metadata and as the TPU's custom call, a
# conditional named ``cond.<n>``.
STEP_TEXT = """HloModule jit_train_step, is_scheduled=true

%fused_computation (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  ROOT %add.1 = f32[2]{0:T(256)} add(%p, %p), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/add"}
}

ENTRY %main.9 (a: f32[2]) -> f32[2] {
  %fusion.1 = bf16[4,8]{1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/bsd,dhk->bshk/dot_general" stack_frame_id=3}
  fusion.2 = f32[2]{0} fusion(a), kind=kLoop, calls=fc, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/ffn/jit(silu)/mul"}
  %flash_attention_fwd.3 = (bf16[2]{0}, f32[2]{0}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/pallas_call"}
  %flash_attention_bwd.4 = (bf16[2]{0}, /*index=1*/bf16[2]{0}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/attention/flash_attention_bwd/pallas_call"}
  %fusion.5 = f32[2]{0} fusion(%a), kind=kInput, calls=%f5, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/attention/flash_attention_bwd/reduce_sum"}
  %ragged-dot.6 = bf16[8,4]{1,0} ragged-dot(%x, %w, %g), lhs_contracting_dims={1}
  %ragged-dot-none.19 = bf16[8,4]{1,0:T(8,128)(2,1)} custom-call(%m, %x, %w), custom_call_target="tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point="true",ragged_dot_tiling="512,256,512"}, metadata={op_name="ragged-dot-none"}
  %cond.7 = (f32[2]{0}) conditional(%pred, %a, %b), true_computation=%t, false_computation=%f, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ffn/moe_dispatch/cond"}
  %while.8 = (s32[], f32[2]{0}) while(%init), condition=%c, body=%b
  %fusion.10 = f32[2]{0} fusion(%a), kind=kLoop, calls=%f10, metadata={op_name="jit(train_step)/transpose(jvp(head_loss))/mul"}
  %fusion.11 = f32[2]{0} fusion(%a), kind=kLoop, calls=%f11, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ffn/moe_router/jit(_where)/select_n"}
  %fusion.12 = f32[2]{0} fusion(%a), kind=kLoop, calls=%f12, metadata={op_name="jit(train_step)/mtp_module/jvp()/while/body/closed_call/attention/mla_q/dot_general"}
  %fusion.13 = f32[2]{0} fusion(%a), kind=kLoop, calls=%f13, metadata={op_name="jit(train_step)/jvp(mtp_module)/concatenate"}
  %custom-call.14 = f32[2]{0} custom-call(%a), custom_call_target="Sharding", metadata={op_name="jit(train_step)/optimizer/mul"}
  %gated_delta_fwd.15 = bf16[2]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attention/gdn_core/pallas_call"}
  %fusion.16 = f32[2]{0} fusion(%a), kind=kLoop, calls=%f16, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/gdn_conv/mul"}
  %fusion.17 = f32[2]{0} fusion(%a), kind=kLoop, calls=%f17, metadata={op_name="jit(train_step)/jvp(block_diffusion_loss)/mul"}
  ROOT %copy.18 = f32[2]{0} copy(%a)
}
"""
STEP_SCOPES_WANT = {
    "p": (None, "fwd"), "add.1": ("attention", "fwd"),
    "fusion.1": ("attention", "fwd"), "fusion.2": ("ffn", "recompute"),
    "flash_attention_fwd.3": ("flash_attention_fwd", "fwd"),
    "flash_attention_bwd.4": ("flash_attention_bwd", "bwd"),
    "fusion.5": ("flash_attention_bwd", "bwd"),
    "ragged-dot.6": ("moe_experts", "fwd"),
    "ragged-dot-none.19": ("moe_experts", "fwd"),
    "cond.7": ("moe_dispatch", "fwd"), "while.8": (None, "fwd"),
    "fusion.10": ("head_loss", "bwd"), "fusion.11": ("moe_router", "fwd"),
    "fusion.12": ("mla_q", "fwd"), "fusion.13": ("mtp_module", "fwd"),
    "custom-call.14": ("optimizer", "fwd"),
    "gated_delta_fwd.15": ("gated_delta_fwd", "recompute"),
    "fusion.16": ("gdn_conv", "fwd"),
    "fusion.17": ("block_diffusion_loss", "fwd"),
    "copy.18": (None, "fwd"),
}


@pytest.fixture
def hand_made_step():
    """``"train_step"`` registered over the text above."""
    tracing.clear()
    executable = _Executable(STEP_TEXT)
    tracing.register_program("train_step", executable, "state", "batch")
    yield executable
    tracing.clear()


_IN_THE_STEP = "jit(train_step)/{}/while/body/closed_call/while/body/" \
    "closed_call/{}attention/gdn_conv/{}/pallas_call"


@pytest.mark.parametrize("name,op_name,want", [
    ("causal_conv_fwd.3",
     _IN_THE_STEP.format("jvp()", "", "causal_conv_fwd"),
     ("gdn_conv", "fwd")),
    ("causal_conv_bwd.12",
     _IN_THE_STEP.format("transpose(jvp())", "checkpoint/",
                         "causal_conv_bwd"),
     ("gdn_conv", "bwd")),
    ("causal_conv_fwd.24",
     _IN_THE_STEP.format("transpose(jvp())",
                         "checkpoint/rematted_computation/",
                         "causal_conv_fwd"),
     ("gdn_conv", "recompute")),
    # (a listed kernel is its own row whatever encloses it)
    ("gated_delta_fwd.7",
     _IN_THE_STEP.format("jvp()", "", "gated_delta_fwd"),
     ("gated_delta_fwd", "fwd")),
])
def test_a_kernel_that_is_not_listed_keeps_the_scope_it_is_called_in(
        name, op_name, want):
    """The convolution's two kernels as the hybrid step's text names
    them (compiled for a described v5e at PR 41): custom calls whose
    ``op_name`` ends in ``gdn_conv/<kernel>/pallas_call`` in the forward
    scan, in the backward scan and in remat's second forward."""
    line = (f'  %{name} = f32[2,8192,8192]{{2,1,0:T(8,128)}} custom-call('
            f'%bitcast.1, %bitcast.1, %bitcast.2), custom_call_target='
            f'"tpu_custom_call", metadata={{op_name="{op_name}" '
            f'stack_frame_id=72}}')
    assert tracing.manifest_of_text(line)["scopes"] == {name: want}


_WINDOW_RUN = "jit(train_step)/{}/while/body/closed_call/while/body/" \
    "closed_call/{}attention/mha_window/{}"
# A window run's instructions as the step's text names them (compiled
# for a described v5e at PR 42), and one of a run that sees everything.
RUNS_TEXT = "\n".join(
    f'  %{name} = f32[2]{{0}} {opcode}(%a), metadata={{op_name="{path}"}}'
    for name, opcode, path in (
        ("fusion.1", "fusion",
         _WINDOW_RUN.format("jvp()", "", "bsd,dhk->bshk/dot_general")),
        ("flash_attention_fwd.24", "custom-call", _WINDOW_RUN.format(
            "jvp()", "", "jit(flash_attention)/flash_attention_fwd/"
            "pallas_call")),
        ("flash_attention_bwd.20", "custom-call", _WINDOW_RUN.format(
            "transpose(jvp())", "checkpoint/", "jit(flash_attention)/"
            "flash_attention_bwd/pallas_call")),
        ("fusion.2", "fusion", _WINDOW_RUN.format(
            "transpose(jvp())", "checkpoint/rematted_computation/",
            "attn_gate/logistic")),
        ("fusion.3", "fusion",
         "jit(train_step)/jvp()/while/body/closed_call/attention/attn_gate/"
         "logistic"),
        ("flash_attention_fwd.22", "custom-call",
         "jit(train_step)/jvp()/while/body/closed_call/attention/"
         "jit(flash_attention)/flash_attention_fwd/pallas_call"),
        ("fusion.4", "fusion", "jit(train_step)/transpose(jvp(mha_window))/"
         "mul")))


def test_a_runs_scope_owns_nothing_and_keeps_rows_apart():
    """``mha_window`` is never an instruction's scope -- the part's is
    (``attention``, ``attn_gate``, the kernels' events) -- and the
    manifest's ``runs`` says which instructions lie under it, the
    kernels' calls among them; ``device_time_by_scope(within=)`` sums
    those alone."""
    tracing.clear()
    manifest = tracing.manifest_of_text(RUNS_TEXT)
    assert manifest["scopes"] == {
        "fusion.1": ("attention", "fwd"),
        "flash_attention_fwd.24": ("flash_attention_fwd", "fwd"),
        "flash_attention_bwd.20": ("flash_attention_bwd", "bwd"),
        "fusion.2": ("attn_gate", "recompute"),
        "fusion.3": ("attn_gate", "fwd"),
        "flash_attention_fwd.22": ("flash_attention_fwd", "fwd"),
        "fusion.4": (None, "bwd")}
    assert manifest["runs"] == dict.fromkeys(
        ("fusion.1", "flash_attention_fwd.24", "flash_attention_bwd.20",
         "fusion.2", "fusion.4"), "mha_window")
    assert tracing.manifest_of_text(STEP_TEXT)["runs"] == {}
    tracing.register_program("train_step", _Executable(RUNS_TEXT))
    rows = [("fusion.1", 1.0), ("flash_attention_fwd.24", 2.0),
            ("%flash_attention_bwd.20", 4.0), ("fusion.2", 8.0),
            ("fusion.3", 16.0), ("flash_attention_fwd.22", 32.0),
            ("fusion.4", 64.0), ("fusion.999", 128.0)]
    everything = tracing.device_time_by_scope(rows)
    assert everything["flash_attention_fwd"]["fwd"] == 34.0
    assert everything["attn_gate"] == {"fwd": 16.0, "bwd": 0.0,
                                       "recompute": 8.0}
    window = tracing.device_time_by_scope(rows, within="mha_window")
    assert window == {
        "unknown": 128.0,
        "attention": {"fwd": 1.0, "bwd": 0.0, "recompute": 0.0},
        "flash_attention_fwd": {"fwd": 2.0, "bwd": 0.0, "recompute": 0.0},
        "flash_attention_bwd": {"fwd": 0.0, "bwd": 4.0, "recompute": 0.0},
        "attn_gate": {"fwd": 0.0, "bwd": 0.0, "recompute": 8.0},
        None: {"fwd": 0.0, "bwd": 64.0, "recompute": 0.0}}
    # the benchmark's reader: milliseconds a step, both steps' rows
    trace = {"device_ops": {"/device:TPU:0": [
        [name.lstrip("%"), 0.0, seconds * 1e6] for name, seconds in rows]},
        "host_spans": []}
    read = _reader("window_layers_ms")
    assert read({"trace": trace, "facts": {"steps": 2}}) == pytest.approx(
        (1 + 2 + 4 + 8 + 64) / 2)
    # a step without such a run, and no step at all: nothing
    tracing.register_program("train_step", _Executable(STEP_TEXT))
    assert read({"trace": STEP_TRACE, "facts": {"steps": 2}}) is None
    tracing.clear()
    assert read({"trace": STEP_TRACE, "facts": {"steps": 2}}) is None


def test_the_rules_of_the_manifest_on_a_hand_made_text(hand_made_step):
    entry = tracing.programs()["train_step"]
    assert hand_made_step.lowered_with is None    # not before it is read
    assert entry["scopes"] == STEP_SCOPES_WANT
    assert hand_made_step.lowered_with == ("state", "batch")
    assert entry["enclosing"] == {"cond.7", "while.8"}
    assert entry["memory"] == {}                  # a backend without one
    assert entry["text_bytes"] == len(STEP_TEXT)


@pytest.mark.parametrize("rows,want", [
    # a kernel's event inside ``attention`` is the kernel's, not the
    # projections'; the scope ``flash_attention_bwd`` joins its kernel
    ([("fusion.1", 2.0), ("flash_attention_fwd.3", 4.0),
      ("flash_attention_bwd.4", 8.0), ("fusion.5", 0.5)],
     {"attention": {"fwd": 2.0}, "flash_attention_fwd": {"fwd": 4.0},
      "flash_attention_bwd": {"bwd": 8.5}}),
    # XLA's grouped product, which lost its scope: the instruction
    # without metadata, and the custom call the TPU's compiler makes of it
    ([("ragged-dot.6", 3.0), ("ragged-dot-none.19", 0.5)],
     {"moe_experts": {"fwd": 3.5}}),
    # a trace that kept the HLO's "%", and one that did not; remat
    ([("%fusion.2", 1.0), ("fusion.2", 0.25)],
     {"ffn": {"recompute": 1.25}}),
    # instructions that only enclose others, by opcode: ``cond.<n>`` too
    ([("cond.7", 100.0), ("while.8", 100.0), ("fusion.11", 0.5)],
     {"moe_router": {"fwd": 0.5}}),
    # another program's events in the window, and an unscoped copy
    ([("fusion.999", 7.0), ("copy.18", 0.25), ("fusion.10", 0.5)],
     {"unknown": 7.0, None: {"fwd": 0.25}, "head_loss": {"bwd": 0.5}}),
    ([], {}),
])
def test_device_time_by_scope_on_hand_made_rows(hand_made_step, rows, want):
    got = tracing.device_time_by_scope(iter(rows))
    zero = dict.fromkeys(tracing.PHASES, 0.0)
    full = {scope: (value if scope == "unknown" else dict(zero, **value))
            for scope, value in want.items()}
    full.setdefault("unknown", 0.0)
    assert got == full


def test_the_registry_keeps_one_entry_a_name_and_clear_empties_it():
    tracing.clear()
    with pytest.raises(KeyError):
        tracing.device_time_by_scope([("fusion.1", 1.0)])
    first, second = _Executable(STEP_TEXT), _Executable("")
    gone = weakref.ref(first)
    tracing.register_program("train_step", first)
    tracing.register_program("train_step", second)
    del first
    gc.collect()
    assert gone() is None                         # replaced and freed
    assert list(tracing.programs()) == ["train_step"]
    assert tracing.programs()["train_step"]["scopes"] == {}
    gone = weakref.ref(second)
    del second
    gc.collect()
    assert gone() is None                         # resolved: let go of
    tracing.clear()
    assert tracing.programs() == {}


# Two steps of one chip's trace over the hand-made step: [name, start
# ns, duration ns]; the conditional's row would count its branch twice.
STEP_TRACE = {"device_ops": {"/device:TPU:0": [
    ["fusion.1", 0.0, 4e6], ["fusion.2", 0.0, 2e6],
    ["flash_attention_fwd.3", 0.0, 6e6], ["flash_attention_bwd.4", 0.0, 8e6],
    ["fusion.5", 0.0, 1e6], ["ragged-dot.6", 0.0, 10e6],
    ["cond.7", 0.0, 50e6], ["fusion.10", 0.0, 3e6], ["fusion.11", 0.0, 1e6],
    ["fusion.12", 0.0, 5e6], ["fusion.13", 0.0, 0.5e6],
    ["custom-call.14", 0.0, 2e6], ["gated_delta_fwd.15", 0.0, 7e6],
    ["fusion.16", 0.0, 1e6], ["fusion.17", 0.0, 0.5e6],
    ["copy.18", 0.0, 1.5e6], ["fusion.999", 0.0, 0.5e6]]},
    "host_spans": []}
READERS = {
    # of 53 ms: all but the copy's 1.5 and the stranger's 0.5
    "step_attributed_pct": 100.0 * 51.0 / 53.0,
    "remat_recompute_ms": (2.0 + 7.0) / 2,
    "attn_proj_ms": (4.0 + 5.0) / 2,
    "attn_kernels_ms": (6.0 + 8.0 + 1.0) / 2,
    "delta_layers_ms": (7.0 + 1.0) / 2,
    "ffn_ms": 2.0 / 2,
    "experts_ms": (10.0 + 1.0) / 2,
    "head_loss_ms": (3.0 + 0.5 + 0.5) / 2,
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_scope_reader_on_a_hand_made_trace(hand_made_step, metric, capfd):
    ctx = {"trace": STEP_TRACE, "facts": {"steps": 2}}
    assert _reader(metric)(ctx) == pytest.approx(READERS[metric])
    # the table is made once a run, and written out whole
    assert _reader(metric)(ctx) == pytest.approx(READERS[metric])
    (line,) = [l for l in capfd.readouterr().err.splitlines()
               if l.startswith('{"step_scopes"')]
    table = json.loads(line)["step_scopes"]
    assert table["ms_a_step"]["optimizer"]["fwd"] == pytest.approx(1.0)
    assert table["ms_a_step"]["unknown"] == pytest.approx(0.25)
    assert table["instructions"] == len(STEP_SCOPES_WANT)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_scope_reader_without_a_manifest_returns_nothing(metric,
                                                         monkeypatch):
    """A raylet run, or a window none of whose events is the step's;
    and the parent commit's program, which has no registry at all."""
    tracing.clear()
    ctx = {"trace": STEP_TRACE, "facts": {"steps": 2}}
    assert _reader(metric)(ctx) is None
    tracing.register_program("train_step", _Executable(""))
    assert _reader(metric)({"trace": STEP_TRACE,
                            "facts": {"steps": 2}}) is None
    tracing.clear()
    monkeypatch.delattr(tracing, "programs")
    assert _reader(metric)({"trace": STEP_TRACE,
                            "facts": {"steps": 2}}) is None
