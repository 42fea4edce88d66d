"""Runtime scale-envelope benchmark — the BASELINE.md envelope driven
through the real ``ray_tpu`` API.

Reference: ``benchmarks/single_node/test_single_node.py`` (MAX_ARGS
10k / MAX_RETURNS 3k / MAX_QUEUED_TASKS 1M / many-get 10k),
``benchmarks/distributed/test_many_{tasks,actors,pgs}.py``, and
``python/ray/_private/ray_perf.py`` (task/actor throughput).

Each row prints one JSON line; the final line is the whole envelope.
``--quick`` shrinks the counts ~10x for smoke runs.  This file measures
the RUNTIME's host-side envelope on the CPU (``main()`` pins its own
process there); what runs on the chip is measured by
``benchmarks/run.py`` through the chip tool.
"""

import argparse
import json
import os
import sys
import time


def emit(metric, value, unit, **extra):
    row = {"metric": metric, "value": round(value, 2), "unit": unit}
    row.update(extra)
    print(json.dumps(row), flush=True)
    return row


def bench_tasks(n):
    import ray_tpu

    @ray_tpu.remote
    def noop():
        return None

    ray_tpu.get([noop.remote() for _ in range(200)])      # warm
    t0 = time.monotonic()
    ray_tpu.get([noop.remote() for _ in range(n)])
    dt = time.monotonic() - t0
    return emit("tasks_per_second", n / dt, "tasks/s", n=n)


def bench_queued(n, num_blockers):
    """Queue depth: block every worker slot, pour n tasks into the
    scheduler queues, measure submission rate, then release and drain."""
    import tempfile

    import ray_tpu

    gate = os.path.join(tempfile.mkdtemp(), "release")

    @ray_tpu.remote
    def blocker(gate_path):
        deadline = time.monotonic() + 600
        while not os.path.exists(gate_path) and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        return None

    @ray_tpu.remote
    def noop():
        return None

    blockers = [blocker.remote(gate) for _ in range(num_blockers)]
    time.sleep(0.2)
    t0 = time.monotonic()
    refs = [noop.remote() for _ in range(n)]
    submit_dt = time.monotonic() - t0
    emit("queued_tasks_submit_rate", n / submit_dt, "tasks/s", queued=n)
    open(gate, "w").close()
    t0 = time.monotonic()
    ray_tpu.get(refs)
    ray_tpu.get(blockers)
    drain_dt = time.monotonic() - t0
    return emit("queued_tasks_drained", n, "tasks",
                drain_rate=round(n / drain_dt, 2))


def bench_dispatch_latency(n, warm=True, reset_window=True):
    """Task-dispatch latency decomposed by lifecycle stage — the
    BASELINE.json north-star metric (p99 task-dispatch latency),
    derived from the task-event pipeline: queue_wait (submit ->
    scheduled/bound), dispatch (scheduled -> handed to worker), startup
    (handoff -> running), total (submit -> running).  Every task gets a
    queue_wait sample (lease-reuse pushes emit SCHEDULED transport-side
    since the fast-path PR), so the per-stage counts must agree —
    asserted here so a coverage regression fails the bench, not just a
    test."""
    import ray_tpu
    from ray_tpu.experimental.state.api import summarize_tasks

    @ray_tpu.remote
    def noop():
        return None

    from ray_tpu._private.worker import global_worker
    cluster = global_worker().cluster
    if warm:
        ray_tpu.get([noop.remote() for _ in range(200)])
    def settled_stages():
        # A task's FINISHED event can trail the get() that returned its
        # result by a flush: wait (bounded) until every stage has the
        # same count, so a straggler neither leaks into the next window
        # nor reads as a coverage gap in this one.
        deadline = time.monotonic() + 5.0
        while True:
            stages = summarize_tasks().get("dispatch_latency", {})
            if len({row["count"] for row in stages.values()}) <= 1 or \
                    time.monotonic() > deadline:
                return stages
            time.sleep(0.01)

    if reset_window:
        # One concurrency level per sample window: without the reset a
        # sweep's later rows would blend the earlier levels' samples.
        # Flush first so straggling pre-reset events can't leak into
        # the fresh window and skew the per-stage counts.
        settled_stages()
        cluster.gcs.task_event_manager.reset_stage_samples()
    lease_before = dict(cluster.head_node.lease_stats)
    ray_tpu.get([noop.remote() for _ in range(n)])
    stages = settled_stages()
    total = stages.get("total", {})
    ticks = cluster.head_node.cluster_task_manager.tick_stats
    lease = cluster.head_node.lease_stats
    counts = {s: row["count"] for s, row in stages.items()}
    assert len(set(counts.values())) <= 1, \
        f"stage-coverage gap: {counts}"
    cfg = __import__("ray_tpu._private.config",
                     fromlist=["get_config"]).get_config()
    return emit("task_dispatch_latency_p99",
                total.get("p99_s", 0.0) * 1000.0, "ms", n=n,
                spillbacks_no_capacity=ticks["spillbacks_no_capacity"],
                spillbacks_locality_override=ticks[
                    "spillbacks_locality_override"],
                lease_rpcs=(lease["lease_requests"]
                            - lease_before["lease_requests"]
                            + lease["lease_batch_requests"]
                            - lease_before["lease_batch_requests"]),
                fastpath={
                    "lease_batch_size": cfg.lease_batch_size,
                    "worker_lease_keepalive_ms":
                        cfg.worker_lease_keepalive_ms,
                    "num_prestart_workers": cfg.num_prestart_workers,
                    "scheduler_wakeup_debounce_ms":
                        cfg.scheduler_wakeup_debounce_ms,
                },
                p50_ms=round(total.get("p50_s", 0.0) * 1000.0, 4),
                stages={
                    stage: {"p50_ms": round(row["p50_s"] * 1000.0, 4),
                            "p99_ms": round(row["p99_s"] * 1000.0, 4),
                            "count": row["count"]}
                    for stage, row in stages.items()})


def introspection_summary():
    """Contention rollup from THIS process's debug plane: top-5 locks
    by total sampled acquire-wait, max event-loop post-to-run lag, and
    the flight-recorder counters — folded into bench JSON so BENCH
    rows carry the attribution data alongside the latency numbers."""
    from ray_tpu._private.debug import flight_recorder, watchdog
    from ray_tpu._private.debug.report import (striped_lock_rollup,
                                               top_locks)
    loops = watchdog.loops_snapshot()
    return {
        "top_locks": top_locks(5),
        # Striped locks (ISSUE 17: TaskEventBuffer/ReferenceCounter)
        # rolled back up to their base names so the row compares
        # 1:1 against the pre-striping PR 13 waits.
        "striped_locks": striped_lock_rollup(),
        "max_loop_lag_ms": round(
            max((lp.get("lag_max_s", 0.0) for lp in loops),
                default=0.0) * 1000.0, 3),
        "recorder": flight_recorder.stats(),
    }


def bench_introspection_overhead(n=500):
    """Overhead bound for the introspection plane (ISSUE 13): the
    dispatch-latency row with flight recorder + lock-contention
    profiling armed, to hold against an unarmed --dispatch-only row
    from the same machine; the acceptance target is p99 within 10% of
    it (``--introspection-gate`` is the enforced form)."""
    row = bench_dispatch_latency(n, warm=True, reset_window=True)
    return emit("dispatch_latency_introspection_armed",
                row["value"], "ms", n=n, p50_ms=row.get("p50_ms"),
                stages=row.get("stages"),
                lease_rpcs=row.get("lease_rpcs"),
                introspection=introspection_summary())


def bench_introspection_gate(n=500, max_ratio=1.10, retries=1,
                             samples=3, p99_target_ms=8.0):
    """CI regression gate (ISSUE 17): the introspection-armed dispatch
    row must stay within ``max_ratio`` of an UNARMED run of the same
    burst, and every stage's sample count must agree (stage-coverage
    parity).  BOTH arms run as fresh subprocesses — contention arming
    is read at lock-creation time and cannot be toggled in-process,
    and an in-process arm would carry accumulated cluster state the
    subprocess arm doesn't (a 4x phantom "regression" in early runs of
    this gate).  The p99 of one burst on a 1-core CI runner bounces
    3-27 ms run to run, so each arm is the MIN over ``samples`` fresh
    runs (scheduler noise is strictly additive; the minimum estimates
    the true cost) and a failing ratio still gets ``retries`` fresh
    measurement rounds before the gate trips; the JSON row records
    every attempt.

    The absolute n=500 ``total p99 <= p99_target_ms`` target (ISSUE 17
    tentpole 2) is ENFORCED only on a multi-core box: on 1 core every
    burst serializes workers, flusher, raylet loop and bench harness
    onto the same CPU, so the absolute number measures the runner, not
    the runtime (the r07 9.34 ms and a same-box 24.6 ms were recorded
    days apart with zero code delta in between).  The row always
    records the target and whether it was enforced/met, so a
    multi-core CI lane trips on it for free."""
    import subprocess

    def run_arm(armed):
        env = dict(os.environ)
        env.pop("RAY_TPU_LOCK_CONTENTION", None)
        env.pop("RAY_TPU_LOCK_DIAG", None)
        flag = "--introspection-bench" if armed else "--dispatch-one"
        want = ("dispatch_latency_introspection_armed" if armed
                else "task_dispatch_latency_p99")
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag,
             "--n", str(n)],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if out.returncode != 0:
            raise RuntimeError(
                f"gate arm {flag} failed rc={out.returncode}: "
                f"{(out.stderr or out.stdout)[-1000:]}")
        for line in reversed(out.stdout.strip().splitlines()):
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if row.get("metric") == want:
                return row
        raise RuntimeError(f"gate arm {flag} printed no {want} row")

    def stage_parity(row):
        # Stage-coverage parity, recomputed here so the gate does not
        # depend on the assertion inside bench_dispatch_latency
        # surviving future edits: every lifecycle stage must have seen
        # every task of the burst.
        stage_counts = {s: r["count"] for s, r in
                        (row.get("stages") or {}).items()}
        return (len(stage_counts) >= 2 and
                len(set(stage_counts.values())) == 1)

    attempts = []
    ok = False
    armed = None
    target_enforced = (os.cpu_count() or 1) > 1
    for _ in range(1 + retries):
        armed_runs = [run_arm(True) for _ in range(samples)]
        off_runs = [run_arm(False) for _ in range(samples)]
        armed = min(armed_runs, key=lambda r: r["value"])
        off = min(off_runs, key=lambda r: r["value"])
        parity = all(stage_parity(r) for r in armed_runs + off_runs)
        ratio = (round(armed["value"] / off["value"], 3)
                 if off["value"] else None)
        target_met = off["value"] <= p99_target_ms
        attempts.append({
            "armed_p99_ms": armed["value"],
            "unarmed_p99_ms": off["value"],
            "armed_runs_ms": [r["value"] for r in armed_runs],
            "unarmed_runs_ms": [r["value"] for r in off_runs],
            "ratio": ratio, "stage_parity": parity,
            "p99_target_met": target_met})
        ok = (parity and ratio is not None and ratio <= max_ratio and
              (target_met or not target_enforced))
        if ok:
            break
    return emit("introspection_gate", attempts[-1]["ratio"] or -1.0,
                "ratio", n=n, max_ratio=max_ratio, passed=ok,
                attempts=attempts, cores=os.cpu_count(),
                p99_target_ms=p99_target_ms,
                p99_target_enforced=target_enforced,
                striped_locks=armed.get(
                    "introspection", {}).get("striped_locks"))


def bench_profile_overhead(n=500):
    """Overhead bound for the causal job profiler (ISSUE 15): the
    dispatch-latency row with provenance capture armed (parent/arg ids
    on every submit event, terminal records copied into the job-graph
    store, object spans force-recorded) vs the same burst with
    ``job_profiler_enabled`` off.  Acceptance target: armed within 10%
    of off, like the PR-13 introspection row.  The armed arm also runs
    ``profile_job`` over its own burst — the end-to-end proof that the
    captured graph answers the question the layer exists for."""
    from ray_tpu._private.config import get_config
    from ray_tpu.experimental.state.api import profile_job

    cfg = get_config()
    armed = bench_dispatch_latency(n, warm=True, reset_window=True)
    prof = profile_job()        # the driver job's own burst
    cfg.job_profiler_enabled = False
    try:
        off = bench_dispatch_latency(n, warm=False, reset_window=True)
    finally:
        cfg.job_profiler_enabled = True
    ratio = (round(armed["value"] / off["value"], 3)
             if off["value"] else None)
    profile_summary = None
    if not prof.get("error"):
        profile_summary = {
            "headline": prof.get("headline"),
            "path_len": prof.get("coverage", {}).get("path_len"),
            "path_s": prof.get("path_s"),
            "wall_clock_s": prof.get("wall_clock_s"),
            "sink": prof.get("sink_task", {}).get("name"),
        }
    return emit("dispatch_latency_provenance_armed",
                armed["value"], "ms", n=n,
                off_p99_ms=off["value"],
                ratio=ratio,
                # 1-core runners' p99 is noisy run-to-run:
                # the honest record is both numbers, not just the bit.
                within_10pct=(ratio is not None and ratio <= 1.10),
                p50_ms=armed.get("p50_ms"),
                off_p50_ms=off.get("p50_ms"),
                profile=profile_summary)


def bench_dispatch_sweep(levels=(500, 2_000, 5_000)):
    """Concurrency sweep of the dispatch-latency row: one row per burst
    size, same warm worker pool, fresh sample window per level — the
    trajectory captures how the stage breakdown scales with queue
    depth."""
    rows = []
    for i, n in enumerate(levels):
        rows.append(bench_dispatch_latency(
            n, warm=(i == 0), reset_window=True))
    return rows


def bench_actors(n):
    import ray_tpu

    @ray_tpu.remote
    class Echo:
        def ping(self, v):
            return v

    t0 = time.monotonic()
    actors = [Echo.remote() for _ in range(n)]
    assert ray_tpu.get([a.ping.remote(i) for i, a in enumerate(actors)],
                       timeout=600) == list(range(n))
    dt = time.monotonic() - t0
    row = emit("actors_created_and_called", n / dt, "actors/s", n=n)
    for a in actors:
        ray_tpu.kill(a)
    return row


def bench_pgs(n):
    import ray_tpu
    from ray_tpu.util.placement_group import (
        placement_group, remove_placement_group)

    t0 = time.monotonic()
    pgs = [placement_group([{"CPU": 0.01}]) for _ in range(n)]
    for pg in pgs:
        assert ray_tpu.get(pg.ready(), timeout=120)
    dt = time.monotonic() - t0
    row = emit("placement_groups_per_second", n / dt, "pgs/s", n=n)
    for pg in pgs:
        remove_placement_group(pg)
    return row


def bench_args(n):
    import ray_tpu

    @ray_tpu.remote
    def count(*args):
        return len(args)

    refs = [ray_tpu.put(i) for i in range(n)]
    t0 = time.monotonic()
    got = ray_tpu.get(count.remote(*refs), timeout=600)
    dt = time.monotonic() - t0
    assert got == n, got
    return emit("max_args_single_task", n, "args", seconds=round(dt, 2))


def bench_returns(n):
    import ray_tpu

    @ray_tpu.remote(num_returns=n)
    def spread():
        return list(range(n))

    t0 = time.monotonic()
    refs = spread.remote()
    values = ray_tpu.get(refs, timeout=600)
    dt = time.monotonic() - t0
    assert values == list(range(n))
    return emit("max_returns_single_task", n, "returns",
                seconds=round(dt, 2))


def bench_get_many(n):
    import ray_tpu
    refs = [ray_tpu.put(i) for i in range(n)]
    t0 = time.monotonic()
    values = ray_tpu.get(refs, timeout=600)
    dt = time.monotonic() - t0
    assert values == list(range(n))
    return emit("objects_in_one_get", n, "objects", seconds=round(dt, 2))


def bench_object_gb(gib):
    """Large-object roundtrip, measured honestly on BOTH axes.

    put_gbps is steady-state single-copy throughput (warmup round first:
    the cold number is dominated by kernel page-zeroing of fresh tmpfs
    pages, reported separately as cold_put_gbps).  get_gbps streams the
    returned array once (a full reduction) — the store's zero-copy get
    returns a view in ~constant time, and timing only the view creation
    once produced an absurd 6805 "GB/s"; the view-latency signal is
    kept as get_view_ms."""
    import gc

    import numpy as np

    import ray_tpu
    data = np.ones(int(gib * 1024**3), dtype=np.uint8)

    def one_put():
        t0 = time.monotonic()
        ref = ray_tpu.put(data)
        return ref, time.monotonic() - t0

    ref, cold_dt = one_put()

    t0 = time.monotonic()
    out = ray_tpu.get(ref)
    view_dt = time.monotonic() - t0
    # Materialized read: stream the bytes out of the store once (memcpy
    # into a PRE-FAULTED scratch buffer, so destination page faults
    # don't masquerade as store read cost) — symmetric with put.
    scratch = np.empty_like(data)
    scratch.fill(0)
    t0 = time.monotonic()
    np.copyto(scratch, out)
    read_dt = time.monotonic() - t0
    assert out.nbytes == data.nbytes and scratch[0] == 1 \
        and scratch[-1] == 1
    del out, scratch
    del ref
    gc.collect()          # frees the store copy; the block is reused warm
    put_dts = []
    for _ in range(3):
        ref2, dt = one_put()
        put_dts.append(dt)
        del ref2
        gc.collect()
    del data
    put_dt = min(put_dts)
    get_dt = view_dt + read_dt
    return emit("large_object_roundtrip", gib, "GiB",
                put_gbps=round(gib / put_dt, 2),
                cold_put_gbps=round(gib / cold_dt, 2),
                get_gbps=round(gib / get_dt, 2),
                get_view_ms=round(view_dt * 1000.0, 3),
                asymmetry=round(max(gib / get_dt, gib / put_dt) /
                                max(1e-9, min(gib / get_dt,
                                              gib / put_dt)), 2))


def bench_broadcast(mb, n_nodes):
    """Broadcast row (BASELINE.md cluster table analogue): ONE object
    fanned out to N simulated node stores over the object plane — each
    node's pull assembles directly into its own shm segment (the
    single-copy fetch path).  Reports put/get/fetch throughput so the
    read/write asymmetry stays visible in every envelope."""
    import numpy as np

    import ray_tpu
    from ray_tpu._private.worker import global_worker

    cluster = global_worker().cluster
    per_node_store = max(4 * mb, 64) * 1024 * 1024
    nodes = [cluster.add_node(num_cpus=0,
                              object_store_memory=per_node_store)
             for _ in range(n_nodes)]
    try:
        import gc
        data = np.ones(mb * 1024 * 1024, dtype=np.uint8)
        gib = data.nbytes / 1024**3
        warm = ray_tpu.put(data)      # fault the segment pages once
        del warm
        gc.collect()
        t0 = time.monotonic()
        ref = ray_tpu.put(data)
        put_dt = time.monotonic() - t0

        scratch = np.empty_like(data)
        scratch.fill(0)
        t0 = time.monotonic()
        out = ray_tpu.get(ref)
        np.copyto(scratch, out)
        get_dt = time.monotonic() - t0
        assert scratch[0] == 1 and scratch[-1] == 1
        del out, scratch

        oid = ref.object_id()
        import threading

        def broadcast_once():
            done = threading.Event()
            pending = [len(nodes)]
            failures = [0]

            def cb(ok):
                if not ok:
                    failures[0] += 1
                pending[0] -= 1
                if pending[0] == 0:
                    done.set()

            t0 = time.monotonic()
            for node in nodes:
                node.object_manager.pull_async(oid, cb)
            assert done.wait(timeout=600), "broadcast pulls timed out"
            dt = time.monotonic() - t0
            assert failures[0] == 0, f"{failures[0]} pulls failed"
            for node in nodes:
                assert node.object_store.contains(oid)
            return dt

        cross_before = sum(n.object_manager.stats["cross_node_fetch_bytes"]
                           for n in nodes)
        cold_fetch_dt = broadcast_once()
        # Steady state: drop the replicas (head keeps the primary) and
        # broadcast again — the nodes' segment blocks are reused warm.
        head_id = global_worker().cluster.head_node.node_id
        for node in nodes:
            node.object_store.delete(oid)
            cluster.object_directory.remove_location(oid, node.node_id)
        assert head_id in cluster.object_directory.get_locations(oid)
        fetch_dt = broadcast_once()
        window = max(n.object_manager.stats["inflight_window_peak"]
                     for n in nodes)
        cross_delta = sum(n.object_manager.stats["cross_node_fetch_bytes"]
                          for n in nodes) - cross_before
        return emit("broadcast_object", mb, "MiB",
                    n_nodes=n_nodes,
                    put_gbps=round(gib / put_dt, 2),
                    get_gbps=round(gib / get_dt, 2),
                    fetch_gbps=round(gib * n_nodes / fetch_dt, 2),
                    fetch_gbps_per_node=round(gib / fetch_dt, 2),
                    cold_fetch_gbps=round(gib * n_nodes / cold_fetch_dt,
                                          2),
                    # Placement-quality counter: bytes the object plane
                    # moved between nodes for these broadcasts (the
                    # metric the arg-locality cost term shrinks on the
                    # dispatch path).
                    cross_node_fetch_bytes=cross_delta,
                    inflight_window_peak=window)
    finally:
        for node in nodes:
            try:
                cluster.remove_node(node)
            except Exception:
                pass


def _run_broadcast_arm(cluster, nodes, mb, relay, link_delay_s):
    """One broadcast of a fresh ``mb``-MiB object to every node in
    ``nodes``, with a modeled per-chunk link delay (the
    ``transfer.chunk`` fault point in delay mode — receiver-side, one
    sleep per chunk, overlapping across concurrent transfers exactly
    like link time does).  Returns (seconds, served-bytes per source
    [head first], relay-served delta)."""
    import gc
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu._private import fault_injection
    from ray_tpu._private.config import get_config

    cfg = get_config()
    cfg.object_transfer_relay_enabled = relay
    cfg.object_transfer_source_selection = "load" if relay else "first"
    head = cluster.head_node
    data = np.ones(mb * 1024 * 1024, dtype=np.uint8)
    ref = ray_tpu.put(data)
    oid = ref.object_id()
    del data
    stores = [head.object_store] + [n.object_store for n in nodes]
    served_before = [s.stats["outbound_served_bytes"] for s in stores]
    relayed_before = sum(s.stats["relay_served_bytes"] for s in stores)
    fault_injection.arm("transfer.chunk", "delay", count=-1,
                        delay_s=link_delay_s)
    try:
        t0 = time.monotonic()
        events, results = [], []
        for node in nodes:
            ev = threading.Event()
            res = {}

            def cb(ok, ev=ev, res=res):
                res["ok"] = ok
                ev.set()

            node.object_manager.pull_async(oid, cb)
            events.append(ev)
            results.append(res)
            if relay:
                # Stagger only until the pull's transfer writer exists:
                # a chain link can only attach to an OBSERVABLE
                # in-flight transfer.  The stagger is inside the timed
                # region — it is part of the relay arm's real cost.
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline and \
                        node.object_store.num_partials() == 0 and \
                        not ev.is_set():
                    time.sleep(0.002)
        for ev in events:
            assert ev.wait(timeout=900), "broadcast pull timed out"
        dt = time.monotonic() - t0
    finally:
        fault_injection.disarm("transfer.chunk")
    assert all(r.get("ok") for r in results), \
        f"{sum(not r.get('ok') for r in results)} pulls failed"
    served = [s.stats["outbound_served_bytes"] - b
              for s, b in zip(stores, served_before)]
    relayed = sum(s.stats["relay_served_bytes"]
                  for s in stores) - relayed_before
    for node in nodes:
        node.object_store.delete(oid)
        cluster.object_directory.remove_location(oid, node.node_id)
    del ref
    gc.collect()
    # The release cascade is deferred (drain thread): wait for the
    # origin copy to actually leave the head store before the next arm
    # charges its budget.
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and \
            head.object_store.contains(oid):
        time.sleep(0.01)
    return dt, served, relayed


def bench_broadcast_relay(sweep=((64, 8), (64, 16), (64, 32),
                                 (256, 8), (256, 16), (256, 32)),
                          link_time_s=0.8):
    """broadcast_relay row: relay-vs-naive broadcast sweep.

    Same-box model of the cluster envelope's GiB broadcast: per-chunk
    link time is injected (``transfer.chunk`` delay, scaled so every
    hop costs ``link_time_s`` of modeled link regardless of size) and
    the sender admission cap is 1 per store — a shared source NIC
    serves N full-object streams in N x link-time no matter the
    concurrency, which is exactly what the cap models.  Both arms run
    under the SAME cap and delay; the only difference is relay +
    load-aware selection vs first-row selection (the pre-relay code
    path).  Memcpy cost is NOT modeled — it is real, identical in both
    arms, and serialized by the host's actual core count (recorded:
    a 1-core runner understates the speedup; see cpu_throttled).

    Asserts the collective property: in the relay arm the origin
    serves <= 2x its fair share of the bytes moved."""
    import shutil

    import ray_tpu
    from ray_tpu._private.config import get_config
    from ray_tpu._private.worker import global_worker

    cluster = global_worker().cluster
    cfg = get_config()
    saved = {k: getattr(cfg, k) for k in
             ("object_manager_chunk_size",
              "object_transfer_max_outbound_sessions",
              "object_transfer_relay_enabled",
              "object_transfer_source_selection")}
    chunk = 1024 * 1024
    cfg.object_manager_chunk_size = chunk
    cfg.object_transfer_max_outbound_sessions = 1
    results = []
    try:
        for mb, n_nodes in sweep:
            need = (n_nodes + 2) * mb * 1024 * 1024
            try:
                free = shutil.disk_usage("/dev/shm").free
            except OSError:
                free = need
            if need > free // 2:
                results.append({"mb": mb, "n_nodes": n_nodes,
                                "skipped": True,
                                "reason": f"needs {need} bytes of shm, "
                                          f"{free} free"})
                continue
            per_node_store = max(2 * mb, 64) * 1024 * 1024
            nodes = [cluster.add_node(num_cpus=0,
                                      object_store_memory=per_node_store)
                     for _ in range(n_nodes)]
            try:
                delay = link_time_s / mb      # 1 MiB chunks: mb chunks
                naive_s, naive_served, _ = _run_broadcast_arm(
                    cluster, nodes, mb, relay=False, link_delay_s=delay)
                relay_s, relay_served, relayed = _run_broadcast_arm(
                    cluster, nodes, mb, relay=True, link_delay_s=delay)
            finally:
                for node in nodes:
                    try:
                        cluster.remove_node(node)
                    except Exception:
                        pass
            total = max(sum(relay_served), 1)
            fair = total / (n_nodes + 1)
            origin_ratio = relay_served[0] / fair
            results.append({
                "mb": mb, "n_nodes": n_nodes,
                # The collective claim (origin <= 2x fair share in the
                # relay arm, one chunk of rounding slack), RECORDED per
                # config — a violation must not abort the envelope's
                # remaining rows; --broadcast-only turns it into rc=1.
                "origin_fair_ok":
                    bool(relay_served[0] <= 2 * fair + chunk),
                "naive_s": round(naive_s, 2),
                "relay_s": round(relay_s, 2),
                "speedup": round(naive_s / relay_s, 2),
                "origin_served_mb": round(relay_served[0] / 2**20, 1),
                "origin_fair_ratio": round(origin_ratio, 2),
                "naive_origin_served_mb":
                    round(naive_served[0] / 2**20, 1),
                "relayed_mb": round(relayed / 2**20, 1),
                "served_balance_mb": [round(s / 2**20, 1)
                                      for s in relay_served],
            })
    finally:
        for k, v in saved.items():
            setattr(cfg, k, v)
    cores = os.cpu_count() or 1
    best = {}
    for r in results:
        if not r.get("skipped"):
            best.setdefault("speedup_min", r["speedup"])
            best["speedup_min"] = min(best["speedup_min"], r["speedup"])
    acceptance = next((r for r in results
                       if r.get("mb") == 256 and r.get("n_nodes") == 16
                       and not r.get("skipped")), None)
    return emit("broadcast_relay", len(results), "configs",
                modeled_link_time_s_per_hop=link_time_s,
                admission_cap=1, chunk_mb=1,
                cores=cores,
                # Real memcpy on few cores dilutes the modeled-link
                # speedup: mark it so the trajectory reads honestly.
                cpu_throttled=cores < 4,
                fair_share_ok=all(r.get("origin_fair_ok", True)
                                  for r in results),
                acceptance_256x16=(
                    None if acceptance is None else
                    {"speedup": acceptance["speedup"],
                     "origin_fair_ratio":
                         acceptance["origin_fair_ratio"]}),
                sweep=results, **best)


def bench_process_mode_objects(mb, rounds):
    """Process-mode worker object path: big args down + big returns
    back.  With the shm client surface both directions go through the
    mapped segment (zero-copy reads, create/seal writes) instead of
    pickle-over-socket — this row tracks that throughput."""
    import subprocess

    import numpy as np
    script = f"""
import os, time, json
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import ray_tpu
ray_tpu.init(num_cpus=2, _system_config={{
    "worker_process_mode": "process",
    "scheduler_backend": "native",
}})

@ray_tpu.remote
def bounce(a):
    return a * 2.0

arr = np.ones({mb} * 1024 * 128, dtype=np.float64)   # {mb} MB
ref = ray_tpu.put(arr)
ray_tpu.get(bounce.remote(ref), timeout=120)          # warm worker
t0 = time.monotonic()
for _ in range({rounds}):
    out = ray_tpu.get(bounce.remote(ref), timeout=120)
dt = time.monotonic() - t0
assert float(out[0]) == 2.0
print(json.dumps({{"mb_per_s": {mb} * 2 * {rounds} / dt,
                   "seconds": dt}}))
ray_tpu.shutdown()
"""
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0 or not out.stdout.strip():
        raise RuntimeError(
            f"process-mode bench child failed (rc={out.returncode}):\n"
            f"{out.stderr[-2000:]}")
    import json as json_mod
    line = out.stdout.strip().splitlines()[-1]
    res = json_mod.loads(line)
    return emit("process_mode_object_throughput",
                res["mb_per_s"], "MB/s",
                payload_mb=mb, rounds=rounds,
                seconds=round(res["seconds"], 2))


def bench_partition_recovery():
    """Partition-tolerance row (ISSUE 14): a sub-grace network flap
    around a live node-host OS process must cost a PLACEMENT PAUSE and
    nothing else — zero actor restarts, zero lineage reconstructions,
    no fencing — and the row records how fast scheduling converges
    after the heal (first spoke-targeted task completion).  Runs in a
    subprocess: failure detection needs its own (fast) heartbeat
    config, and a wedged run must not take the envelope down."""
    import subprocess
    script = """
import os, time, json
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import ray_tpu
from ray_tpu._private import fault_injection
from ray_tpu._private.worker import global_worker

ray_tpu.init(num_cpus=2, _system_config={
    "scheduler_backend": "native",
    "raylet_heartbeat_period_milliseconds": 50,
    "num_heartbeats_suspect": 6,
    "num_heartbeats_timeout": 200,
    "gcs_resource_broadcast_period_milliseconds": 50,
})
cluster = global_worker().cluster
handle = cluster.add_remote_node(num_cpus=1, resources={"spoke": 2.0})
nid = handle.node_id

@ray_tpu.remote(resources={"spoke": 1}, num_cpus=0, max_restarts=2)
class Probe:
    def __init__(self):
        self.n = 0
    def incr(self):
        self.n += 1
        return self.n

@ray_tpu.remote(resources={"spoke": 1}, num_cpus=0)
def ping():
    return "up"

probe = Probe.remote()
assert ray_tpu.get(probe.incr.remote(), timeout=30) == 1
assert ray_tpu.get(ping.remote(), timeout=30) == "up"

part = fault_injection.partition(handle.proxy.address,
                                 outbound=True, inbound=False)
part.arm()
deadline = time.monotonic() + 10
while time.monotonic() < deadline and not \
        cluster.gcs.heartbeat_manager.is_suspect(nid):
    time.sleep(0.01)
assert cluster.gcs.heartbeat_manager.is_suspect(nid), "never SUSPECT"
part.heal(); part.close()
heal_t = time.monotonic()
assert ray_tpu.get(ping.remote(), timeout=60) == "up"
converged_ms = (time.monotonic() - heal_t) * 1000.0
# Zero-restart assertion: the actor kept its in-memory state.
assert ray_tpu.get(probe.incr.remote(), timeout=30) == 2, "actor restarted"
assert cluster.gcs.node_manager.fenced_count(nid) == 0, "fenced in-grace"
from ray_tpu._private.metrics_agent import get_metrics_registry
text = get_metrics_registry().render_prometheus()
for line in text.splitlines():
    if line.startswith("ray_tpu_lineage_reconstructions"):
        assert float(line.rsplit(" ", 1)[1]) == 0.0, line
print(json.dumps({"heal_to_converged_ms": round(converged_ms, 1),
                  "restarts": 0, "reconstructions": 0}))
ray_tpu.shutdown()
"""
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=300)
    if out.returncode != 0 or not out.stdout.strip():
        return emit("partition_recovery", -1.0, "ms", error=(
            f"child failed rc={out.returncode}: "
            f"{(out.stderr or out.stdout)[-500:]}"))
    res = json.loads(out.stdout.strip().splitlines()[-1])
    return emit("partition_recovery", res["heal_to_converged_ms"], "ms",
                restarts=res["restarts"],
                reconstructions=res["reconstructions"],
                zero_restart_ok=res["restarts"] == 0)


def bench_envelope_smoke(hosts=4, timeout_s=420):
    """envelope_smoke row: the cluster envelope driver (tools/envelope.py
    / ``ray-tpu envelope``) at smoke scale — ``hosts`` real node-host OS
    processes, a small actor/PG/broadcast workload, 2 scheduled chaos
    faults — in a fresh subprocess, timeout-bounded.  Parses the single
    summary JSON line the driver prints on stdout; the driver exits
    non-zero on ANY silent loss, so the row carries the zero-silent-loss
    contract, not just throughput."""
    import subprocess
    cmd = [sys.executable, "-m", "ray_tpu._private.envelope",
           "--hosts", str(hosts), "--cpus-per-host", "1",
           "--actors", "40", "--actor-wave", "20",
           "--pgs", "8", "--pg-wave", "4",
           "--broadcast", "8:2",
           "--chaos-events", "2", "--chaos-window-s", "6",
           "--chaos-seed", "1234",
           "--get-timeout-s", "60", "--stand-up-timeout", "120",
           "--out", "", "--quiet"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        out = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return emit("envelope_smoke", -1.0, "s", hosts=hosts,
                    error=f"timed out after {timeout_s}s")
    summary = None
    for line in reversed((out.stdout or "").strip().splitlines()):
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "envelope" in row:
            summary = row["envelope"]
            break
    if summary is None:
        return emit("envelope_smoke", -1.0, "s", hosts=hosts,
                    error=f"no summary line (rc={out.returncode}): "
                          f"{(out.stderr or '')[-400:]}")
    # rc=1 means the driver saw silent loss — keep the data, mark it.
    return emit("envelope_smoke", summary["wall_s"], "s",
                passed=(out.returncode == 0 and
                        summary["silent_loss"] == 0), **summary)


def _pctl(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


def _serve_level(handle, clients, n_per_client):
    """One closed-loop concurrency level: ``clients`` threads, each
    issuing ``n_per_client`` requests back-to-back (next request only
    after the previous response) — offered load rises with the client
    count, not with an open-loop arrival rate."""
    import threading

    import ray_tpu
    lats, errors, lock = [], [0], threading.Lock()

    def client(cid):
        local = []
        for i in range(n_per_client):
            want = cid * 100_000 + i
            t0 = time.monotonic()
            try:
                ok = ray_tpu.get(handle.remote(want), timeout=60) == want
            except Exception:   # noqa: BLE001 — counted, not hidden
                ok = False
            dt = time.monotonic() - t0
            with lock:
                if ok:
                    local.append(dt)
                else:
                    errors[0] += 1
        with lock:
            lats.extend(local)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    wall = time.monotonic() - t0
    lats.sort()
    return {"clients": clients,
            "requests": clients * n_per_client,
            "errors": errors[0],
            "throughput_rps": round(len(lats) / wall, 1),
            "p50_ms": round(_pctl(lats, 0.50) * 1000.0, 2),
            "p99_ms": round(_pctl(lats, 0.99) * 1000.0, 2),
            "wall_s": round(wall, 3)}


def _serve_trace_stages(handle, n=40):
    """Per-request critical-path split that sums to wall-clock by
    construction: assign (handle.remote returns — router queue wait +
    replica pick + dispatch) and execute_fetch (ray_tpu.get — batch
    wait + user fn + result hop).  One single-threaded client so the
    split is the request's own path, not queueing noise."""
    import ray_tpu
    assign, fetch = [], []
    for i in range(n):
        t0 = time.monotonic()
        ref = handle.remote(i)
        t1 = time.monotonic()
        ray_tpu.get(ref, timeout=60)
        t2 = time.monotonic()
        assign.append(t1 - t0)
        fetch.append(t2 - t1)
    total = sorted(a + b for a, b in zip(assign, fetch))
    assign.sort()
    fetch.sort()
    return {
        "assign_ms": {"p50": round(_pctl(assign, 0.5) * 1000, 3),
                      "p99": round(_pctl(assign, 0.99) * 1000, 3)},
        "execute_fetch_ms": {"p50": round(_pctl(fetch, 0.5) * 1000, 3),
                             "p99": round(_pctl(fetch, 0.99) * 1000, 3)},
        "total_ms": {"p50": round(_pctl(total, 0.5) * 1000, 3),
                     "p99": round(_pctl(total, 0.99) * 1000, 3)},
        # assign + execute_fetch == total per request by construction;
        # recorded so the row is self-checking, not trust-me.
        "sums_to_wall_clock": True,
        "count": n}


def _serve_cold_start_arm(relay_enabled, mb=4):
    """One cold-start arm: 3-node cluster, 3 replicas whose __init__
    takes a ``mb``-MiB weights ObjectRef, chunk transfers slowed so the
    concurrent pulls overlap.  Returns deploy->first-response wall and
    the origin/relay served-bytes split (relay arm: origin serves ~one
    copy; naive arm: origin serves all N)."""
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import fault_injection
    from ray_tpu._private.cluster import Cluster
    from ray_tpu._private.config import get_config

    _mb = 1024 * 1024
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 0})
    ray_tpu.init(_cluster=cluster)
    # AFTER init: init re-derives the config singleton, so knobs set
    # before it are silently reset (the chunk size is read per
    # transfer, so post-init is early enough).
    cfg = get_config()
    cfg.object_transfer_relay_enabled = relay_enabled
    cfg.object_transfer_max_outbound_sessions = 1
    cfg.object_manager_chunk_size = 256 * 1024
    try:
        workers = [cluster.add_node(num_cpus=2,
                                    object_store_memory=64 * _mb)
                   for _ in range(3)]
        serve.start(http_options={"location": "NoServer"})
        weights = (np.arange(mb * _mb, dtype=np.uint8) % 251)
        ref = ray_tpu.put(weights)
        head = cluster.head_node
        size = head.object_store.get(ref.object_id()).size
        origin_before = head.object_store.stats["outbound_served_bytes"]

        @serve.deployment(name="model", num_replicas=3,
                          ray_actor_options={"num_cpus": 2})
        class Model:
            def __init__(self, w):
                self.checksum = int(w[:1024].sum())

            def __call__(self, req):
                return self.checksum

        fault_injection.arm("transfer.chunk", "delay", count=-1,
                            delay_s=0.02)
        t0 = time.monotonic()
        try:
            Model.deploy(ref)
        finally:
            fault_injection.disarm("transfer.chunk")
        h = Model.get_handle()
        ok = ray_tpu.get(h.remote(None), timeout=120) == \
            int(weights[:1024].sum())
        wall = time.monotonic() - t0
        origin_served = head.object_store.stats[
            "outbound_served_bytes"] - origin_before
        return {"arm": "relay" if relay_enabled else "naive",
                "ok": bool(ok),
                "deploy_to_first_response_s": round(wall, 3),
                "weights_bytes": size,
                "origin_served_bytes": origin_served,
                "origin_amplification": round(origin_served / size, 2),
                "relay_served_bytes": sum(
                    n.object_store.stats["relay_served_bytes"]
                    for n in workers),
                "relay_pulls": sum(
                    n.object_manager.stats["relay_pulls"]
                    for n in workers)}
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        ray_tpu.shutdown()


def bench_serve(quick=False):
    """serve_closed_loop row (ISSUE 20): closed-loop concurrent-client
    sweep against an autoscaled, adaptively-batched deployment —
    p50/p99 + throughput per offered-load level, the saturation knee
    identified (first level whose throughput gain over the previous
    level drops under 10%), a single-client stage trace that sums to
    wall-clock, the autoscaler's decision counters, the batch queue's
    flush/fill stats, and a relay-vs-naive cold-start arm pair.

    Service time is MODELED (a sleep per batch): on a chipless box the
    row measures the serving plane — routing, batching, autoscaling,
    data plane — not matmul throughput, and says so (cpu_throttled)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.config import get_config

    cores = os.cpu_count() or 1
    service_s = 0.004
    levels = (1, 4, 8) if quick else (1, 2, 4, 8, 16)
    n_per_client = 10 if quick else 25

    cfg = get_config()
    ray_tpu.init(num_cpus=8)
    serve.start(http_options={"location": "NoServer"})
    try:
        @serve.deployment(
            name="bench", max_concurrent_queries=8,
            autoscaling_config={
                "min_replicas": 1, "max_replicas": 3,
                "target_num_ongoing_requests_per_replica": 4,
                "upscale_delay_s": 0.2, "downscale_delay_s": 30.0,
            })
        @serve.batch(max_batch_size=8, latency_budget_s=0.05)
        def bench_fn(requests):
            time.sleep(service_s)      # modeled per-batch service time
            return list(requests)

        bench_fn.deploy()
        h = bench_fn.get_handle()
        ray_tpu.get(h.remote(-1), timeout=60)          # warm
        rows = [_serve_level(h, c, n_per_client) for c in levels]

        knee = rows[-1]
        for prev, cur in zip(rows, rows[1:]):
            if cur["throughput_rps"] < prev["throughput_rps"] * 1.10:
                knee = prev
                break

        stages = _serve_trace_stages(h, 20 if quick else 40)
        profile = None
        try:
            from ray_tpu.experimental.state.api import profile_job
            prof = profile_job()
            if not prof.get("error"):
                profile = {"headline": prof.get("headline"),
                           "path_s": prof.get("path_s"),
                           "wall_clock_s": prof.get("wall_clock_s")}
            else:
                profile = {"error": prof["error"]}
        except Exception as err:  # noqa: BLE001
            profile = {"error": repr(err)}

        controller = ray_tpu.get_actor(serve.controller.CONTROLLER_NAME)
        autoscaler = ray_tpu.get(
            controller.get_autoscaler_stats.remote())
        info = ray_tpu.get(
            controller.get_deployment_info.remote("bench"))
        from ray_tpu.serve import batching
        batch_stats = None
        for (mod, qual), q in batching._FN_QUEUES.items():
            if qual.endswith("bench_fn"):
                s = dict(q.stats)
                s["avg_batch"] = round(
                    s["requests"] / max(1, s["flushes"]), 2)
                batch_stats = s
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    cold = {"relay": _serve_cold_start_arm(True),
            "naive": _serve_cold_start_arm(False)}
    errors = sum(r["errors"] for r in rows)
    passed = (errors == 0 and cold["relay"]["ok"] and
              cold["naive"]["ok"] and
              cold["relay"]["origin_amplification"] <
              cold["naive"]["origin_amplification"])
    return emit("serve_closed_loop", knee["throughput_rps"], "req/s",
                knee_clients=knee["clients"],
                p50_ms_at_knee=knee["p50_ms"],
                p99_ms_at_knee=knee["p99_ms"],
                sweep=rows, errors=errors,
                stages=stages, profile=profile,
                autoscaler=autoscaler,
                replicas_final=info["num_running_replicas"],
                batch=batch_stats,
                cold_start=cold,
                passed=passed,
                batch_max=8, latency_budget_s=0.05,
                modeled_service_time_s=service_s,
                # The serving plane is what's measured; the "model" is
                # a sleep.  A 1-core runner also serializes the client
                # threads — the knee is a floor, not the machine's.
                cpu_throttled=cores < 4, cores=cores)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true",
                        help="~10x smaller counts")
    parser.add_argument("--queued", type=int, default=None,
                        help="queued-task count (default 1M; quick 20k)")
    parser.add_argument("--dispatch-only", action="store_true",
                        help="run only the dispatch-latency row")
    parser.add_argument("--broadcast-only", action="store_true",
                        help="run only the relay-vs-naive broadcast "
                             "sweep")
    parser.add_argument("--introspection-bench", action="store_true",
                        help="run the dispatch-latency row with the "
                             "flight recorder + lock-contention "
                             "profiling armed (the ISSUE-13 overhead "
                             "bound)")
    parser.add_argument("--profile-bench", action="store_true",
                        help="run the dispatch-latency row with "
                             "provenance capture armed vs off (the "
                             "ISSUE-15 job-profiler overhead bound)")
    parser.add_argument("--introspection-gate", action="store_true",
                        help="CI regression gate (ISSUE 17): armed vs "
                             "unarmed dispatch p99 ratio must be "
                             "<= 1.10 and stage counts must agree; "
                             "exits non-zero on violation")
    parser.add_argument("--dispatch-one", action="store_true",
                        help="run exactly one dispatch-latency row at "
                             "--n tasks (subprocess arm of the gate)")
    parser.add_argument("--n", type=int, default=500,
                        help="burst size for --dispatch-one / "
                             "--introspection-gate")
    parser.add_argument("--gate-samples", type=int, default=3,
                        help="fresh runs per gate arm (min taken)")
    parser.add_argument("--gate-retries", type=int, default=1,
                        help="extra measurement rounds before the "
                             "gate trips")
    parser.add_argument("--envelope-smoke", action="store_true",
                        help="run the cluster envelope driver at smoke "
                             "scale (4 node-host OS processes, chaos "
                             "armed) in a fresh subprocess; exits "
                             "non-zero on silent loss")
    parser.add_argument("--envelope-hosts", type=int, default=4,
                        help="fleet size for --envelope-smoke")
    parser.add_argument("--serve-bench", action="store_true",
                        help="closed-loop serve sweep: autoscaled + "
                             "adaptively-batched deployment, p50/p99 "
                             "vs offered load with the knee, stage "
                             "trace, relay-vs-naive cold start")
    args = parser.parse_args()
    # Host-side envelope: this process and its children stay off the
    # chip unless the caller names another platform explicitly.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.introspection_bench:
        # Must land before ray_tpu import: contention arming is read
        # at lock CREATION time (module-level locks are created at
        # import).  The flight recorder is on by default.
        os.environ["RAY_TPU_LOCK_CONTENTION"] = "1"
    if args.envelope_smoke:
        # The driver owns its own cluster in a fresh subprocess — no
        # ray_tpu.init in THIS process.  rc mirrors the zero-silent-
        # loss contract so a CI lane trips on loss, not just on crash.
        row = bench_envelope_smoke(hosts=args.envelope_hosts)
        return 0 if row.get("passed") else 1
    if args.serve_bench:
        # Owns its own init/shutdown cycles (the cold-start arms stand
        # up multi-node Clusters) — no cluster in THIS frame.  The row
        # prints either way; a loss or a non-chaining relay arm
        # surfaces as rc=1 WITHOUT losing the data.
        row = bench_serve(quick=args.quick)
        return 0 if row.get("passed") else 1
    if args.introspection_gate:
        # Both arms are fresh subprocesses — no cluster in THIS
        # process.  The row is printed either way; a gate violation
        # surfaces as rc=1 WITHOUT losing the data.
        row = bench_introspection_gate(args.n,
                                       retries=args.gate_retries,
                                       samples=args.gate_samples)
        return 0 if row.get("passed") else 1

    import ray_tpu
    cpus = 8
    ray_tpu.init(num_cpus=cpus, _system_config={
        "scheduler_backend": "native",   # runtime envelope, not kernel
        "object_store_memory": 4 * 1024**3,
        # Dispatch fast path: park idle leases briefly for direct push
        # across bursts, prestart the burst's workers off the dispatch
        # path.  Batching + wakeup debounce are on by default.
        "worker_lease_keepalive_ms": 50,
        "num_prestart_workers": cpus,
        "prestart_on_submit": True,
    })

    quick = args.quick
    if args.introspection_bench:
        bench_introspection_overhead(args.n)
        ray_tpu.shutdown()
        return 0
    if args.profile_bench:
        bench_profile_overhead(500)
        ray_tpu.shutdown()
        return 0
    if args.dispatch_one:
        bench_dispatch_latency(args.n)
        ray_tpu.shutdown()
        return 0
    if args.dispatch_only:
        bench_dispatch_sweep((500, 2_000, 5_000))
        ray_tpu.shutdown()
        return 0
    if args.broadcast_only:
        row = bench_broadcast_relay()
        ray_tpu.shutdown()
        # The fair-share property is the acceptance gate here: the row
        # is already printed, so a violation surfaces as rc=1 WITHOUT
        # losing the data.
        return 0 if row.get("fair_share_ok", True) else 1
    rows = []
    rows.append(bench_tasks(1_000 if quick else 10_000))
    rows.append(bench_dispatch_latency(500 if quick else 2_000))
    rows.append(bench_actors(100 if quick else 1_000))
    rows.append(bench_pgs(20 if quick else 100))
    rows.append(bench_args(1_000 if quick else 10_000))
    rows.append(bench_returns(300 if quick else 3_000))
    rows.append(bench_get_many(1_000 if quick else 10_000))
    rows.append(bench_object_gb(0.25 if quick else 1.0))
    rows.append(bench_broadcast(64 if quick else 256,
                                4 if quick else 8))
    rows.append(bench_broadcast_relay(
        sweep=((64, 4),) if quick else ((64, 8), (256, 16)),
        link_time_s=0.4 if quick else 0.8))
    rows.append(bench_process_mode_objects(8 if quick else 32,
                                           3 if quick else 10))
    rows.append(bench_partition_recovery())
    queued = args.queued if args.queued is not None else \
        (20_000 if quick else 1_000_000)
    rows.append(bench_queued(queued, num_blockers=cpus))

    print(json.dumps({"metric": "runtime_envelope", "value": len(rows),
                      "unit": "rows",
                      "rows": {r["metric"]: {k: v for k, v in r.items()
                                             if k != "metric"}
                               for r in rows}}), flush=True)
    ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
