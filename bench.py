"""Headline benchmark: scheduler ticks at 1M pending tasks x 10k nodes.

North star (BASELINE.md / BASELINE.json): snapshot the pending-task queue
(deduped into scheduling classes, task_spec.h:297) and per-node resource
vectors, solve the batched task->node assignment on TPU in <50 ms/tick on
a single host.  The reference's greedy loop
(``HybridSchedulingPolicy::Schedule`` per task over per-node hash maps)
is replaced by ``ray_tpu.scheduler.jax_backend``'s dense [C,R]x[N,R]
bucketized waterfill.

TPU-resident design measured here (how a raylet colocated with the chip
would run):
  * world state (avail/total [N,R], class demand shapes [C,R]), the
    per-class pending queue AND the inflight-work matrix live on device —
    world uploaded once by ``prepare_device``, queue + availability +
    inflight carried as scan state;
  * the loop is CLOSED on device in STATE, not just queue: tick k's
    placements subtract capacity that stays subtracted, a geometric
    completion process (per-class rate rho) releases it back, and the
    unplaced remainder carries into tick k+1 — only the exogenous
    arrival stream is staged ahead (a real raylet streams it in), never
    future queue or availability snapshots;
  * each tick ships a fixed-size sparse assignment (idx,val pairs) +
    validation bits back; ticks stream through one device program
    (``solve_stream``) so dispatch latency amortizes.
The same kernel family also runs the live dispatch path: a raylet's
ClusterTaskManager holds the world device-resident via
``jax_backend.DeviceRuntimeSolver`` (scheduler_backend=jax, the default),
shipping dirty-row deltas per tick — bench_runtime.py measures that
end-to-end path through ``ray_tpu.remote``.

Prints ONE JSON line:
  {"metric": ..., "value": <ms per tick>, "unit": "ms", "vs_baseline": x}
vs_baseline > 1.0 means faster than the 50 ms target.  The metric is a
device metric: without a TPU this script exits non-zero and prints no
row (``chip_smoke.py`` is the quicker proof that the chip path starts).

Problem shape (config 5 of BASELINE.json, Google-cluster-trace shaped):
1,000,000 tasks in 256 scheduling classes, 10,000 heterogeneous nodes,
8 resource columns.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np


def build_problem(rng, num_tasks=1_000_000, C=256, N=10_000, R=8):
    # Heterogeneous fleet: small CPU nodes, big CPU nodes, TPU hosts.
    total = np.zeros((N, R), dtype=np.float32)
    kinds = rng.choice(3, size=N, p=[0.6, 0.3, 0.1])
    total[:, 0] = np.where(kinds == 0, 4, np.where(kinds == 1, 64, 8))  # CPU
    total[:, 1] = np.where(kinds == 0, 16, np.where(kinds == 1, 256, 64))  # mem GB
    total[:, 2] = np.where(kinds == 2, 4, 0)   # TPU chips
    total[:, 3] = rng.integers(0, 2, N)        # GPU-ish custom accel
    for r in range(4, R):
        total[:, r] = rng.integers(0, 8, N)    # custom resources
    used = rng.uniform(0.0, 0.6, size=(N, R)).astype(np.float32)
    avail = np.floor(total * (1.0 - used))

    # Trace-shaped demand: most classes small CPU tasks, a tail of
    # memory-heavy and accelerator classes; counts follow a power law.
    demand = np.zeros((C, R), dtype=np.float32)
    demand[:, 0] = rng.choice([0.5, 1, 2, 4], size=C, p=[0.4, 0.4, 0.15, 0.05])
    demand[:, 1] = rng.choice([1, 2, 4, 16], size=C, p=[0.5, 0.3, 0.15, 0.05])
    accel_classes = rng.random(C) < 0.08
    demand[accel_classes, 2] = rng.choice([1, 4], size=accel_classes.sum())
    raw = rng.pareto(1.5, size=C) + 1.0
    counts = np.floor(raw / raw.sum() * num_tasks).astype(np.int64)
    counts[-1] += num_tasks - counts.sum()
    accel_node = total[:, 2] > 0
    return avail, total, demand, counts, accel_node, accel_classes


def arrival_stream(rng, counts, ticks, per_tick=130_000):
    """Exogenous per-tick task arrivals: tick 0 delivers the full 1M
    backlog; later ticks deliver ~placement-rate volume (so the pending
    queue hovers around 1M) with a rotating per-class mix."""
    C = counts.shape[0]
    stream = np.empty((ticks, C), dtype=np.int64)
    stream[0] = counts
    frac = counts / counts.sum()
    for k in range(1, ticks):
        mix = np.roll(frac, k)
        row = np.floor(mix * per_tick).astype(np.int64)
        row += rng.integers(0, 3, size=C)
        stream[k] = row
    return stream


def _model_bench_row():
    """Run bench_model.py (transformer train-step MFU) in a subprocess
    and return its parsed JSON row, or a structured skip dict.  The
    child inherits this process's platform and runs BEFORE this process
    touches JAX: a chip belongs to one process at a time."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_model.py")
    try:
        proc = subprocess.run([sys.executable, path],
                              capture_output=True, text=True,
                              timeout=1200)
    except subprocess.TimeoutExpired:
        return {"skipped": True, "reason": "bench_model timed out"}
    if proc.returncode != 0 or not proc.stdout.strip():
        return {"skipped": True,
                "reason": f"bench_model rc={proc.returncode}: "
                          f"{(proc.stderr or '')[-400:]}"}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except ValueError:
        return {"skipped": True, "reason": "unparseable bench_model output"}


def _dispatch_latency_rows():
    """Run bench_runtime.py --dispatch-only in a subprocess (its own
    CPU-side runtime, never touches the chip) and return the parsed
    task_dispatch_latency_p99 sweep rows (n=500/2000/5000), or a
    structured skip dict — the bench trajectory records the north-star
    p99 from every bench.py invocation."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_runtime.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, path, "--dispatch-only"],
            env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return {"skipped": True, "reason": "dispatch bench timed out"}
    if proc.returncode != 0:
        return {"skipped": True,
                "reason": f"dispatch bench rc={proc.returncode}: "
                          f"{(proc.stderr or '')[-400:]}"}
    rows = []
    for line in proc.stdout.strip().splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if row.get("metric") == "task_dispatch_latency_p99":
            rows.append(row)
    if not rows:
        return {"skipped": True, "reason": "no dispatch-latency row in output"}
    return {"rows": rows}


def _introspection_overhead_row():
    """Run bench_runtime.py --introspection-bench in a subprocess (the
    contention arming must exist before any lock is created, hence a
    fresh process) and return the armed dispatch-latency row with its
    contention summary, or a structured skip dict."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_runtime.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, path, "--introspection-bench"],
            env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return {"skipped": True,
                "reason": "introspection bench timed out"}
    if proc.returncode != 0:
        return {"skipped": True,
                "reason": f"introspection bench rc={proc.returncode}: "
                          f"{(proc.stderr or '')[-400:]}"}
    for line in proc.stdout.strip().splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if row.get("metric") == "dispatch_latency_introspection_armed":
            return row
    return {"skipped": True,
            "reason": "no introspection row in output"}


def _profile_overhead_row():
    """Run bench_runtime.py --profile-bench in a subprocess and return
    the provenance-armed dispatch-latency row (the ISSUE-15 job
    profiler's overhead bound + its end-to-end profile of the burst),
    or a structured skip dict."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_runtime.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, path, "--profile-bench"],
            env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return {"skipped": True, "reason": "profile bench timed out"}
    if proc.returncode != 0:
        return {"skipped": True,
                "reason": f"profile bench rc={proc.returncode}: "
                          f"{(proc.stderr or '')[-400:]}"}
    for line in proc.stdout.strip().splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if row.get("metric") == "dispatch_latency_provenance_armed":
            return row
    return {"skipped": True, "reason": "no profile row in output"}


def _broadcast_relay_row():
    """Run bench_runtime.py --broadcast-only in a subprocess (CPU-side
    runtime, never touches the chip) and return the parsed
    broadcast_relay sweep row, or a structured skip dict — the data
    plane's collective-transfer claim (relay-arm >= 3x naive, origin
    <= 2x fair share) rides every bench.py invocation."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_runtime.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, path, "--broadcast-only"],
            env=env, capture_output=True, text=True, timeout=1200)
    except subprocess.TimeoutExpired:
        return {"skipped": True, "reason": "broadcast bench timed out"}
    # Parse the row even on rc!=0: the sweep prints its data BEFORE
    # exiting 1 on a fair-share violation — the honest failure must
    # reach the JSON, not collapse into a skip.
    for line in proc.stdout.strip().splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if row.get("metric") == "broadcast_relay":
            if proc.returncode != 0:
                row["failed"] = True
                row["failed_rc"] = proc.returncode
            return row
    return {"skipped": True,
            "reason": f"no broadcast_relay row in output "
                      f"(rc={proc.returncode}): "
                      f"{(proc.stderr or '')[-400:]}"}


def _envelope_row():
    """Run bench_runtime.py --envelope-smoke in a subprocess (the
    envelope driver stands up its own fleet of node-host OS processes;
    this process's backend/cluster state must not leak into it) and
    return the parsed envelope_smoke row, or a structured skip dict.
    The full 50-host soak is recorded separately (ENVELOPE_r06.json);
    this row keeps the stand-up + zero-silent-loss contract riding
    every bench.py invocation at smoke cost."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_runtime.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, path, "--envelope-smoke"],
            env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return {"skipped": True, "reason": "envelope smoke timed out"}
    # Parse the row even on rc!=0: silent loss prints its data before
    # exiting 1 — the honest failure must reach the JSON.
    for line in proc.stdout.strip().splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if row.get("metric") == "envelope_smoke":
            if proc.returncode != 0:
                row["failed"] = True
                row["failed_rc"] = proc.returncode
            return row
    return {"skipped": True,
            "reason": f"no envelope_smoke row in output "
                      f"(rc={proc.returncode}): "
                      f"{(proc.stderr or '')[-400:]}"}


def _serve_bench_row():
    """Run bench_runtime.py --serve-bench in a subprocess (the serving
    plane on CPU: closed-loop client sweep against an autoscaled,
    adaptively-batched deployment, plus the relay-vs-naive cold-start
    arm pair) and return the parsed serve_closed_loop row, or a
    structured skip dict.  --quick keeps the riding cost down; a full
    sweep is recorded per-round (BENCH_r09.json onward)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_runtime.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, path, "--serve-bench", "--quick"],
            env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return {"skipped": True, "reason": "serve bench timed out"}
    # Parse the row even on rc!=0: a lost request or a non-chaining
    # relay arm prints its data before exiting 1 — the honest failure
    # must reach the JSON, not collapse into a skip.
    for line in proc.stdout.strip().splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if row.get("metric") == "serve_closed_loop":
            if proc.returncode != 0:
                row["failed"] = True
                row["failed_rc"] = proc.returncode
            return row
    return {"skipped": True,
            "reason": f"no serve_closed_loop row in output "
                      f"(rc={proc.returncode}): "
                      f"{(proc.stderr or '')[-400:]}"}


def main():
    # MFU child runs BEFORE this process initializes any backend: the
    # TPU is per-process exclusive, so a parent already holding the
    # chip would starve (or wedge) the very measurement this exists
    # for.  The child gets the chip to itself, then releases it.
    model = _model_bench_row()

    import jax

    from ray_tpu._private.device_policy import enable_compile_cache
    enable_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"bench.py: no TPU (jax backend is "
              f"{jax.default_backend()!r}); "
              f"scheduler_tick_1M_tasks_x_10k_nodes is a device metric "
              f"and is not measured on other backends", file=sys.stderr)
        return 1

    rng = np.random.default_rng(42)
    avail, total, demand, counts, accel_node, accel_class = \
        build_problem(rng)

    from ray_tpu.scheduler.jax_backend import BatchSolver
    solver = BatchSolver(mode="waterfill")

    # One-time world-state upload (the raylet keeps this device-resident,
    # updating deltas as nodes join/leave).
    solver.prepare_device(avail, total, demand, accel_node=accel_node,
                          accel_class=accel_class, spread_threshold=0.5)

    ticks = 40
    stream = arrival_stream(rng, counts, ticks)
    # Per-class geometric completion rates (mean service 2-8 ticks) —
    # the closed loop evolves availability: placements occupy capacity
    # until their completions release it.
    rho = rng.integers(2, 9, size=demand.shape[0]) / 16.0

    # Warmup (compile) + correctness: decode tick 0's sparse assignment
    # (queue = the full 1M backlog) and check capacity/count bounds on
    # the host.
    out = solver.solve_stream(stream, rho=rho)
    assert out["ok"].all(), "on-device validation failed"
    alloc0 = solver.expand_sparse(out["idx"][0], out["vals"][0])
    usage = alloc0.T.astype(np.float64) @ demand.astype(np.float64)
    assert (usage <= avail.astype(np.float64) + 1e-2).all(), \
        "capacity violated"
    assert (alloc0.sum(axis=1) <= stream[0]).all()
    placed = int(out["placed"][0])

    # Timed: K closed-loop ticks per device program.  Everything a tick
    # needs crosses the boundary inside the timed region: arrivals down,
    # sparse assignment + validation bits back; queue, availability and
    # inflight state stay device-resident between ticks.
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        out = solver.solve_stream(stream, rho=rho)
    elapsed = time.perf_counter() - t0
    assert out["ok"].all()
    ms_per_tick = elapsed / (reps * ticks) * 1000.0

    baseline_ms = 50.0  # BASELINE.json target: <50 ms/tick
    device = jax.devices()[0]
    res = {
        "metric": "scheduler_tick_1M_tasks_x_10k_nodes",
        "value": round(ms_per_tick, 3),
        "unit": "ms",
        # Which program the timed region ran ("single/pallas" = the
        # fused Mosaic fill): a jnp-path number must never be recorded
        # as a Pallas number.
        "solve_path": solver.last_path,
        "vs_baseline": round(baseline_ms / ms_per_tick, 2),
        "placed_tasks": placed,
        "ticks_per_program": ticks,
        "nnz_max_per_tick": int(out["nnz"].max()),
        "classes": int(demand.shape[0]),
        "nodes": int(avail.shape[0]),
        "backend": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }

    # Model-compute axis: transformer train-step MFU rode the same
    # bench.py invocation (measured above, before this process touched
    # the chip — the driver runs nothing else).  Its own JSON line is
    # printed for the record AND folded into the headline row as an
    # ``mfu`` field (structured null + reason on skip).
    if model.get("skipped"):
        res["mfu"] = None
        res["mfu_skip_reason"] = model.get("reason")
    else:
        print(json.dumps(model))
        res["mfu"] = model.get("value")
        res["mfu_backend"] = model.get("backend")
    # The two kernelized host solves (PG bundle packing + autoscaler
    # demand solve) get their own rows at full scale on the chip this
    # process holds; structured skip on failure.
    try:
        import bench_runtime
        pg_row = bench_runtime.bench_pg_packing(1_000, 10_000)
        auto_row = bench_runtime.bench_autoscaler_solve(10_000, 1_000)
        res["pg_bundle_packing"] = {k: v for k, v in pg_row.items()
                                    if k != "metric"}
        res["autoscaler_solve"] = {k: v for k, v in auto_row.items()
                                   if k != "metric"}
    except Exception as err:
        res["pg_bundle_packing"] = {"skipped": True, "reason": repr(err)}
        res["autoscaler_solve"] = {"skipped": True, "reason": repr(err)}

    # North-star runtime axis: p99 task-dispatch latency, decomposed by
    # stage and swept across burst sizes (n=500/2000/5000) — measured
    # end-to-end through ray_tpu.remote by a CPU-side subprocess (the
    # chip is untouched), folded into the headline row.  The headline
    # dispatch_p99_ms stays the n=500 row for cross-round continuity.
    # Data-plane collective axis: relay-vs-naive broadcast sweep
    # (64/256 MiB x 8/16/32 in-process stores, modeled link time,
    # per-source served-bytes balance), folded as broadcast_relay.
    res["broadcast_relay"] = {
        k: v for k, v in _broadcast_relay_row().items()
        if k not in ("metric", "value", "unit")}

    # Cluster-envelope axis: the chaos-soak driver at smoke scale
    # (4 node-host OS processes, seeded faults, zero-silent-loss
    # contract), folded as envelope — the summary already carries the
    # driver's own honest cpu_throttled marking for this box.
    res["envelope"] = {
        k: v for k, v in _envelope_row().items()
        if k not in ("metric", "value", "unit")}

    # Serving axis (ISSUE 20): closed-loop p50/p99 vs offered load
    # with the saturation knee, the autoscaler's decisions, adaptive
    # batch fill, and the relay-vs-naive cold-start pair — folded as
    # serve.  The knee throughput rides as serve["knee_rps"].
    serve_row = _serve_bench_row()
    res["serve"] = {
        k: v for k, v in serve_row.items()
        if k not in ("metric", "value", "unit")}
    if not serve_row.get("skipped"):
        res["serve"]["knee_rps"] = serve_row.get("value")

    dispatch = _dispatch_latency_rows()
    if dispatch.get("skipped"):
        res["dispatch_p99_ms"] = None
        res["dispatch_skip_reason"] = dispatch.get("reason")
    else:
        rows = dispatch["rows"]
        head_row = next((r for r in rows if r.get("n") == 500), rows[0])
        for row in rows:
            print(json.dumps(row))
        res["dispatch_p99_ms"] = head_row.get("value")
        res["dispatch_p50_ms"] = head_row.get("p50_ms")
        res["dispatch_stages"] = head_row.get("stages")
        res["dispatch_lease_rpcs"] = head_row.get("lease_rpcs")
        res["dispatch_sweep"] = [
            {k: row.get(k) for k in ("n", "value", "p50_ms",
                                     "lease_rpcs", "stages")}
            for row in rows]

    # Introspection-plane overhead bound (ISSUE 13): the same n=500
    # dispatch row with flight recorder + lock-contention profiling
    # armed, compared against the unarmed headline row above; the
    # armed run's contention summary (top-5 lock wait, max loop lag)
    # rides the JSON so BENCH rows carry attribution data.
    armed = _introspection_overhead_row()
    if armed.get("skipped"):
        res["introspection_overhead"] = armed
    else:
        print(json.dumps(armed))
        baseline_p99 = res.get("dispatch_p99_ms")
        ratio = (round(armed["value"] / baseline_p99, 3)
                 if baseline_p99 else None)
        res["introspection_overhead"] = {
            "armed_p99_ms": armed["value"],
            "baseline_p99_ms": baseline_p99,
            "ratio": ratio,
            # Target: within 10% (note this 1-core runner's p99
            # varies run-to-run on identical code — see BENCH_r07 —
            # so the honest record is both numbers, not just a bit).
            "within_10pct": (ratio is not None and ratio <= 1.10),
        }
        res["contention_summary"] = armed.get("introspection")

    # Causal-profiler overhead bound (ISSUE 15): provenance capture
    # armed vs off on the same dispatch burst, plus the armed arm's
    # critical-path profile of its own burst (the end-to-end proof).
    prov = _profile_overhead_row()
    if prov.get("skipped"):
        res["provenance_overhead"] = prov
    else:
        print(json.dumps(prov))
        res["provenance_overhead"] = {
            "armed_p99_ms": prov["value"],
            "off_p99_ms": prov.get("off_p99_ms"),
            "ratio": prov.get("ratio"),
            "within_10pct": prov.get("within_10pct"),
        }
        res["job_profile_summary"] = prov.get("profile")
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
