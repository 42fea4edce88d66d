"""Driver ``raylet_rounds``: the lease path of one live raylet, read from
the client's side.

The window drives ``Raylet.request_worker_lease_batch(specs, reply)`` on
the head raylet of an in-process ``Cluster`` (default configuration:
``scheduler_backend="jax"``, the raylet's own event loop and debounce).
The harness plays the two parties a raylet talks to:

* the submitters: each round every pending entry goes out as one lease
  batch per scheduling class, and the harness waits for every reply
  vector;
* the cluster's other raylets: stub nodes registered at the GCS's
  resource manager, whose ``get_resource_report()`` the real GCS polls
  and broadcasts.  A stub admits a spilled lease only while its own
  exact fixed-point ledger has room, as a remote raylet would; an entry
  it turns away stays pending and is asked for again.

Admitted placements run for a geometric number of rounds (the class's
rate) and then give their resources back.

What decides ``correct`` is the configuration's guarantees, held on the
answers the window itself got: every lease entry answered exactly once
per request with a known kind of answer, none lost, no node's ledger
ever over its capacity, and no tick of the window off the device path
(``jnp_fallbacks``, ``fallbacks``, ``device_errors`` all 0).

NOT YET A CELL: see PERF.md section 7.  The plain scheduler reference
(an oracle's placement count per tick) is not written yet.
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from benchmarks.harness import trace_reduce, traffic as traffic_mod
from benchmarks.harness.compile_clock import clock as compile_clock

FP = 10_000            # the program's fixed point (resources.FP_SCALE)


class StubNode:
    """What a remote raylet is to the GCS and to a submitter: a node id,
    a resource report, and a ledger that admits or turns away."""

    is_remote_proxy = True

    def __init__(self, node_id, names, total_row):
        self.node_id = node_id
        self.node_name = "stub"
        self.total = (np.asarray(total_row) * FP).round().astype(np.int64)
        self.avail = self.total.copy()
        self._held = [(n, j) for j, n in enumerate(names) if self.total[j] > 0]
        self._report = None

    def node_info(self) -> dict:
        return {"node_id": self.node_id.hex(), "node_name": self.node_name,
                "alive": True, "remote": True,
                "resources": self._floats(self.total), "labels": {}}

    def _floats(self, row) -> dict:
        return {n: int(row[j]) / FP for n, j in self._held}

    def get_resource_report(self) -> dict:
        if self._report is None:
            self._report = {"available": self._floats(self.avail),
                            "total": self._floats(self.total),
                            "load": {"queued": 0, "dispatch": 0}}
        return self._report

    def update_resource_usage(self, batch: dict) -> None:
        pass

    def admit(self, demand) -> bool:
        if (self.avail < demand).any():
            return False
        self.avail -= demand
        self._report = None
        return True

    def release(self, demand) -> None:
        self.avail += demand
        self._report = None


def build_fleet(config: dict, seed: int):
    """-> (names, total [N, R], used fraction [N, R]) from the
    configuration's node mix (``bench.build_problem``'s draw, copied)."""
    rng = traffic_mod.rng_for(seed, "fleet")
    names = config["resources"]
    n = config["nodes"]
    kinds = rng.choice(len(config["node_mix"]), size=n,
                       p=[k["share"] for k in config["node_mix"]])
    total = np.zeros((n, len(names)))
    for j, name in enumerate(names):
        per_kind = np.array([k["total"].get(name, 0)
                             for k in config["node_mix"]], float)
        total[:, j] = per_kind[kinds]
    for name, hi in config.get("custom_uniform", {}).items():
        total[:, names.index(name)] = rng.integers(0, hi, n)
    lo, hi = config["initial_use"]
    used = rng.uniform(lo, hi, size=total.shape)
    return names, total, used


def build_classes(config: dict, seed: int):
    """-> (demand [C, R] in fixed point, popularity [C], completion rate
    [C]) from the configuration's class table."""
    rng = traffic_mod.rng_for(seed, "classes")
    names, spec = config["resources"], config["classes"]
    c = spec["count"]
    demand = np.zeros((c, len(names)))
    for name, draw in spec["demand"].items():
        demand[:, names.index(name)] = rng.choice(
            draw["values"], size=c, p=draw["p"])
    accel = spec.get("accelerator")
    if accel:
        is_accel = rng.random(c) < accel["share"]
        demand[is_accel, names.index(accel["resource"])] = rng.choice(
            accel["values"], size=int(is_accel.sum()))
    raw = rng.pareto(spec["popularity_pareto"], size=c) + 1.0
    lo, hi, den = spec["completion_rate_sixteenths"] + [16]
    rate = rng.integers(lo, hi + 1, c) / den
    # classes that share one demand vector are one scheduling class to
    # the raylet: merge them so a class here is a class there
    keys = {}
    for i, row in enumerate(demand):
        keys.setdefault(tuple(row), []).append(i)
    keep = [v[0] for v in keys.values()]
    pop = np.array([raw[v].sum() for v in keys.values()])
    return ((demand[keep] * FP).round().astype(np.int64), pop / pop.sum(),
            rate[keep])


class Rounds:
    """The closed loop.  ``step()`` is one round; everything it touches
    is the harness's own but ``raylet.request_worker_lease_batch``."""

    def __init__(self, raylet, stubs, names, demand, popularity, rate,
                 traffic: dict, seed: int):
        from ray_tpu._private.ids import FunctionID, JobID, WorkerID
        from ray_tpu._private.task_spec import make_spec
        self.raylet = raylet
        self.by_id = {s.node_id: s for s in stubs}
        self.demand = demand
        self.popularity, self.rate = popularity, rate
        self.pending_target = traffic["pending"]
        self.rng = traffic_mod.rng_for(seed, "rounds")
        ids = dict(job_id=JobID.from_int(1), owner_id=WorkerID.from_random(),
                   function_id=FunctionID.from_random())

        def new_spec(cls):
            res = {n: v / FP for n, v in zip(names, demand[cls]) if v}
            return make_spec(function_name="bench.noop", args=[],
                             num_returns=1, resources=res, **ids)

        self._new_spec = new_spec
        self.free = [[] for _ in demand]           # specs by class
        self.specs_made = 0
        self.pending = []                          # [class, spec, first_t]
        self.finishing = {}                        # round -> [(stub, cls, spec)]
        self.round = 0
        self.latencies = []
        self.counts = dict(requested=0, answered=0, placed=0, turned_away=0,
                           backlog=0, rejected=0, unknown=0, granted=0,
                           lost=0, over_capacity=0)

    def _spec(self, cls):
        if self.free[cls]:
            return self.free[cls].pop()
        self.specs_made += 1
        return self._new_spec(cls)

    def step(self, annotate=None):
        span = annotate or (lambda name: contextlib.nullcontext())
        self.round += 1
        for stub, cls, spec in self.finishing.pop(self.round, ()):
            stub.release(self.demand[cls])
            self.free[cls].append(spec)
        need = self.pending_target - len(self.pending)
        now = time.perf_counter()
        for cls in self.rng.choice(len(self.demand), size=need,
                                   p=self.popularity):
            self.pending.append([int(cls), self._spec(int(cls)), now])

        by_class = {}
        for entry in self.pending:
            by_class.setdefault(entry[0], []).append(entry)
        replies, lock, done = {}, threading.Lock(), threading.Event()

        def on_reply(cls, result):
            with lock:
                replies.setdefault(cls, []).append(result)
                if len(replies) == len(by_class):
                    done.set()

        with span("round.submit"):
            for cls, entries in by_class.items():
                self.raylet.request_worker_lease_batch(
                    [e[1] for e in entries],
                    lambda result, cls=cls: on_reply(cls, result))
        self.counts["requested"] += len(self.pending)
        with span("round.wait_replies"):
            if not done.wait(timeout=120.0):
                self.counts["lost"] += sum(
                    len(v) for k, v in by_class.items() if k not in replies)

        still = []
        with span("round.admit"):
            now = time.perf_counter()
            for cls, entries in by_class.items():
                got = replies.get(cls, [])
                if len(got) != 1 or len(got[0].get("results", ())) != \
                        len(entries):
                    # answered twice, or a vector of the wrong length
                    self.counts["lost"] += len(entries) if not got else 0
                    self.counts["unknown"] += len(entries) if got else 0
                    still.extend(entries)
                    continue
                for entry, res in zip(entries, got[0]["results"]):
                    self.counts["answered"] += 1
                    kind = self._apply(entry, res, now)
                    self.counts[kind] += 1
                    if kind != "placed":
                        still.append(entry)
        self.pending = still

    def _apply(self, entry, res, now) -> str:
        cls, spec, first = entry
        target = res.get("retry_at")
        if target is not None:
            stub = self.by_id.get(target)
            if stub is None:
                return "unknown"
            if not stub.admit(self.demand[cls]):
                return "turned_away"
            if (stub.avail < 0).any():
                self.counts["over_capacity"] += 1
            self.latencies.append(now - first)
            run_for = int(self.rng.geometric(self.rate[cls]))
            self.finishing.setdefault(self.round + run_for, []).append(
                (stub, cls, spec))
            return "placed"
        if res.get("backlog"):
            return "backlog"
        if res.get("rejected"):
            return "rejected"
        if "worker" in res or "raylet" in res:
            return "granted"
        return "unknown"


def _solver_stats(raylet) -> dict:
    mgr = raylet.cluster_task_manager
    solver = getattr(mgr, "_jax_solver", None)
    out = {k: mgr.tick_stats.get(k, 0)
           for k in ("ticks", "busy_ticks", "jnp_fallbacks", "spillbacks")}
    for k in ("ticks", "full_syncs", "row_deltas", "fallbacks",
              "device_errors", "sharded_ticks"):
        out["solver_" + k] = solver.stats.get(k, 0) if solver else 0
    out["last_path"] = getattr(solver, "last_path", None)
    return out


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir) -> dict:
    import jax

    import ray_tpu
    from ray_tpu._private.cluster import Cluster
    from ray_tpu._private.ids import NodeID
    from ray_tpu.scheduler.resources import NodeResources

    names, total, used = build_fleet(config, seed)
    demand, popularity, rate = build_classes(config, seed)

    cluster = Cluster(initialize_head=True,
                      head_node_args=dict(num_cpus=0))
    ray_tpu.init(_cluster=cluster)
    try:
        raylet = cluster.head_node
        stubs = []
        for row, use in zip(total, used):
            stub = StubNode(NodeID.from_random(), names, row)
            stub.avail = np.floor(row * (1.0 - use)).astype(np.int64) * FP
            cluster.gcs.resource_manager.register_raylet(
                stub.node_id, stub, NodeResources(stub._floats(stub.total)))
            stubs.append(stub)
        deadline = time.perf_counter() + 60.0
        while raylet.cluster_view.num_nodes() < len(stubs) + 1:
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    f"the raylet saw {raylet.cluster_view.num_nodes()} of "
                    f"{len(stubs) + 1} nodes after 60 s")
            time.sleep(0.05)

        rounds = Rounds(raylet, stubs, names, demand, popularity, rate,
                        traffic, seed)
        for _ in range(traffic["warmup_rounds"]):
            rounds.step()

        clock = compile_clock()
        before, stats0 = clock.snapshot(), _solver_stats(raylet)
        counts0 = dict(rounds.counts)
        made0, rounds.latencies = rounds.specs_made, []
        annotate = None
        if trace_dir:
            trace_reduce.start(trace_dir)
            annotate = jax.profiler.TraceAnnotation
        round_s = []
        t_start = time.perf_counter()
        while True:
            t = time.perf_counter()
            rounds.step(annotate)
            round_s.append(time.perf_counter() - t)
            if time.perf_counter() - t_start >= seconds:
                break
        t_end = time.perf_counter()
        if trace_dir:
            trace_reduce.stop()
        after, stats1 = clock.snapshot(), _solver_stats(raylet)
        mem = jax.local_devices()[0].memory_stats() or {}
    finally:
        ray_tpu.shutdown()

    window = {k: rounds.counts[k] - counts0[k] for k in rounds.counts}
    delta = {k: stats1[k] - stats0[k] for k in stats1
             if isinstance(stats1[k], (int, float))}
    window_s = t_end - t_start
    lat = np.array(rounds.latencies)
    end_to_end = {"sched_placed_per_s": window["placed"] / window_s}
    if len(lat):
        end_to_end["sched_place_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
    return {
        "attempted": window["requested"],
        "failed": window["rejected"] + window["lost"] + window["unknown"]
        + window["granted"],
        "t_window_start": t_start, "window_s": window_s,
        "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
        "end_to_end": end_to_end,
        "facts": {
            "rounds": len(round_s), "round_seconds": round_s,
            "window_s": window_s, "placed": window["placed"],
            "turned_away": window["turned_away"],
            "backlog": window["backlog"], "requested": window["requested"],
            "classes": int(len(demand)), "nodes": len(stubs),
            "specs_made_in_window": rounds.specs_made - made0,
            "solver": delta, "last_path": stats1["last_path"],
            "compile_before_window": before,
        },
        "counts": {
            "unanswered_or_twice": window["lost"] + window["unknown"],
            "answers_off_requests": abs(
                window["answered"] + window["lost"] - window["requested"]),
            "rejected": window["rejected"],
            "local_grants": window["granted"],
            "over_capacity": window["over_capacity"],
            "jnp_fallbacks": delta["jnp_fallbacks"],
            "fallbacks": delta["solver_fallbacks"],
            "device_errors": delta["solver_device_errors"],
            "device_ticks_missing": int(delta["solver_ticks"] <= 0),
            "compiles_in_window": after["lowerings"] - before["lowerings"],
        },
    }


def check(cell: dict, config: dict, seed: int, result: dict) -> dict:
    return {name: (count, "count")
            for name, count in result["counts"].items()}
