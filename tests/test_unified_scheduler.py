"""One TPU scheduling kernel for all three schedulers.

Covers ISSUE 10: the cost-matrix extension of the batched waterfill
(heterogeneity rates, arg-locality, pack mode), the PG bundle kernel
vs the numpy greedy (feasibility parity across all four strategies),
the autoscaler's kernel-routed bin-pack, and the placement-quality
counters (spillback reasons, cross_node_fetch_bytes)."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.config import get_config
from ray_tpu.scheduler.jax_backend import (BatchSolver, DeviceRuntimeSolver,
                                           waterfill_oracle)


def _random_problem(rng, C=10, N=40, R=4):
    total = rng.integers(1, 32, size=(N, R)).astype(np.float32)
    used_frac = rng.uniform(0, 0.5, size=(N, R)).astype(np.float32)
    avail = np.floor(total * (1 - used_frac))
    demand = np.zeros((C, R), dtype=np.float32)
    for c in range(C):
        k = rng.integers(1, R + 1)
        cols = rng.choice(R, size=k, replace=False)
        demand[c, cols] = rng.integers(1, 4, size=k)
    counts = rng.integers(0, 40, size=C)
    accel_node = rng.random(N) < 0.25
    accel_class = rng.random(C) < 0.2
    return avail, total, demand, counts, accel_node, accel_class


class TestCostMatrixKernel:
    """The per-(class, node) cost term + pack mode in the waterfill."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_cost_matches_numpy_oracle(self, seed):
        rng = np.random.default_rng(seed)
        solver = BatchSolver()
        avail, total, demand, counts, an, ac = _random_problem(rng)
        cost = np.where(rng.random((demand.shape[0], avail.shape[0])) < 0.15,
                        rng.uniform(-0.7, 0.5,
                                    (demand.shape[0], avail.shape[0])),
                        0.0).astype(np.float32)
        got = solver.solve_matrices(avail, total, demand, counts, an, ac,
                                    spread_threshold=0.5, cost=cost)
        want = waterfill_oracle(avail, total, demand, counts, an, ac,
                                spread_threshold=0.5, cost=cost)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_pack_mode_matches_numpy_oracle(self, seed):
        rng = np.random.default_rng(seed)
        solver = BatchSolver()
        avail, total, demand, counts, an, ac = _random_problem(rng)
        got = solver.solve_matrices(avail, total, demand, counts, an, ac,
                                    spread_threshold=0.0, invert_util=True,
                                    zero_shifts=True)
        want = waterfill_oracle(avail, total, demand, counts, an, ac,
                                spread_threshold=0.0, invert_util=True,
                                zero_shifts=True)
        np.testing.assert_array_equal(got, want)

    def test_locality_cost_steers_placement(self):
        """A strong negative cost on one node pulls the whole class
        there (capacity permitting) — the arg-locality shape."""
        solver = BatchSolver()
        N = 8
        avail = total = np.full((N, 1), 10.0, dtype=np.float32)
        demand = np.ones((1, 1), dtype=np.float32)
        counts = np.array([6])
        cost = np.zeros((1, N), dtype=np.float32)
        cost[0, 5] = -0.9                    # node 5 holds the arg bytes
        alloc = solver.solve_matrices(avail, total, demand, counts,
                                      spread_threshold=0.5, cost=cost)
        assert alloc[0, 5] == 6
        assert alloc.sum() == 6

    def test_pack_mode_minimizes_nodes_used(self):
        """Inverted-utilization + zero shifts = bin-packing order: the
        solve fills one node before touching the next."""
        solver = BatchSolver()
        N = 8
        avail = total = np.full((N, 1), 10.0, dtype=np.float32)
        demand = np.ones((2, 1), dtype=np.float32)
        counts = np.array([4, 5])
        alloc = solver.solve_matrices(avail, total, demand, counts,
                                      spread_threshold=0.0,
                                      invert_util=True, zero_shifts=True)
        assert alloc.sum() == 9
        assert int((alloc.sum(axis=0) > 0).sum()) == 1   # one node packed

    def test_accel_class_lands_on_accel_nodes_cpu_avoids(self):
        """Heterogeneity baseline: accelerator demand can only land on
        accelerator nodes; CPU-only classes avoid them (bucket 17)."""
        solver = BatchSolver()
        N = 8
        total = np.zeros((N, 3), dtype=np.float32)
        total[:, 0] = 8.0                     # CPU everywhere
        total[4:, 2] = 4.0                    # TPU on nodes 4..7
        avail = total.copy()
        demand = np.array([[1.0, 0.0, 1.0],   # accel class
                           [1.0, 0.0, 0.0]],  # cpu class
                          dtype=np.float32)
        counts = np.array([8, 16])
        accel_node = total[:, 2] > 0
        accel_class = np.array([True, False])
        alloc = solver.solve_matrices(avail, total, demand, counts,
                                      accel_node, accel_class,
                                      spread_threshold=0.5)
        assert alloc[0, :4].sum() == 0        # accel demand on accel nodes
        assert alloc[0].sum() == 8
        assert alloc[1, 4:].sum() == 0        # cpu work avoids accel nodes
        assert alloc[1].sum() == 16


class _Spec:
    def __init__(self, cpu, cls, args=()):
        from ray_tpu.scheduler.policy import SchedulingOptions
        from ray_tpu.scheduler.resources import ResourceRequest
        self.resources = ResourceRequest({"CPU": cpu})
        self.scheduling_options = SchedulingOptions.hybrid()
        self.scheduling_class = cls
        self.args = list(args)

    def arg_object_ids(self):
        return list(self.args)


def _view(nodes):
    from ray_tpu.scheduler.resources import (ClusterResourceView,
                                             NodeResources)
    view = ClusterResourceView()
    for name, total, labels in nodes:
        view.add_node(name, NodeResources(total, labels=labels))
    return view


class TestDeviceSolverCostTerms:
    """Locality + heterogeneity terms on the runtime dispatch path."""

    def test_locality_provider_steers_targets(self):
        view = _view([(f"n{i}", {"CPU": 8.0}, None) for i in range(4)])

        def locality(specs):
            return {"n2": 1 << 20}            # n2 holds the arg bytes

        solver = DeviceRuntimeSolver(locality_provider=locality)
        specs = [_Spec(1.0, 7001, args=["oid"]) for _ in range(4)]
        targets = solver.solve(view, specs)
        assert targets == ["n2"] * 4
        assert solver.last_cost_active
        assert solver.stats["cost_ticks"] == 1

    def test_no_cost_ships_nothing(self):
        view = _view([(f"n{i}", {"CPU": 8.0}, None) for i in range(4)])
        solver = DeviceRuntimeSolver()
        targets = solver.solve(view, [_Spec(1.0, 7002) for _ in range(4)])
        assert targets is not None and all(t is not None for t in targets)
        assert not solver.last_cost_active
        assert solver.stats["cost_ticks"] == 0

    def test_throughput_labels_prefer_fast_nodes(self):
        """Gavel-style effective rates: with equal utilization the
        faster throughput class fills first."""
        from ray_tpu.scheduler.jax_backend import NODE_THROUGHPUT_LABEL
        view = _view([
            ("slow0", {"CPU": 8.0}, {NODE_THROUGHPUT_LABEL: "1.0"}),
            ("slow1", {"CPU": 8.0}, {NODE_THROUGHPUT_LABEL: "1.0"}),
            ("fast", {"CPU": 8.0}, {NODE_THROUGHPUT_LABEL: "4.0"}),
        ])
        solver = DeviceRuntimeSolver()
        targets = solver.solve(view, [_Spec(1.0, 7003) for _ in range(6)])
        assert targets is not None
        assert all(t == "fast" for t in targets), targets
        assert solver.last_cost_active

    def test_homogeneous_rates_cost_inactive(self):
        from ray_tpu.scheduler.jax_backend import NODE_THROUGHPUT_LABEL
        view = _view([
            ("a", {"CPU": 8.0}, {NODE_THROUGHPUT_LABEL: "2.0"}),
            ("b", {"CPU": 8.0}, {NODE_THROUGHPUT_LABEL: "2.0"}),
        ])
        solver = DeviceRuntimeSolver()
        targets = solver.solve(view, [_Spec(1.0, 7004) for _ in range(3)])
        assert targets is not None
        assert not solver.last_cost_active


class TestBundleKernelParity:
    """Kernel vs greedy PG packing: same feasibility, never silently
    divergent (the satellite's property tests)."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_feasibility_parity_all_strategies(self, seed):
        from ray_tpu.scheduler.bundle_packing import (
            _pack_bundles_greedy, pack_bundles_kernel, validate_assignment)
        from ray_tpu.scheduler.resources import ResourceRequest
        rng = np.random.default_rng(seed)
        for trial in range(12):
            n = int(rng.integers(2, 9))
            view = _view([(f"n{i}",
                           {"CPU": float(rng.integers(1, 8)),
                            "memory": float(rng.integers(1, 16))}, None)
                          for i in range(n)])
            nb = int(rng.integers(1, 6))
            bundles = [ResourceRequest(
                {"CPU": float(rng.integers(1, 4)),
                 "memory": float(rng.integers(0, 4))}) for _ in range(nb)]
            for strategy in ("PACK", "SPREAD", "STRICT_PACK",
                             "STRICT_SPREAD"):
                greedy = _pack_bundles_greedy(view, bundles, strategy)
                kernel = pack_bundles_kernel(view, bundles, strategy)
                assert (greedy is None) == (kernel is None), (
                    f"seed={seed} trial={trial} {strategy}: greedy="
                    f"{greedy} kernel={kernel}")
                if kernel is not None:
                    assert validate_assignment(view, bundles, kernel,
                                               strategy, set())

    def test_exclude_nodes_respected(self):
        from ray_tpu.scheduler.bundle_packing import pack_bundles_kernel
        from ray_tpu.scheduler.resources import ResourceRequest
        view = _view([("a", {"CPU": 4.0}, None), ("b", {"CPU": 4.0}, None)])
        bundles = [ResourceRequest({"CPU": 2.0})]
        got = pack_bundles_kernel(view, bundles, "PACK",
                                  exclude_nodes={"a"})
        assert got == ["b"]

    def test_strict_spread_needs_distinct_nodes(self):
        from ray_tpu.scheduler.bundle_packing import pack_bundles_kernel
        from ray_tpu.scheduler.resources import ResourceRequest
        view = _view([("a", {"CPU": 8.0}, None), ("b", {"CPU": 8.0}, None)])
        two = [ResourceRequest({"CPU": 1.0}) for _ in range(2)]
        got = pack_bundles_kernel(view, two, "STRICT_SPREAD")
        assert got is not None and len(set(got)) == 2
        three = [ResourceRequest({"CPU": 1.0}) for _ in range(3)]
        assert pack_bundles_kernel(view, three, "STRICT_SPREAD") is None

    def test_strict_pack_single_node(self):
        from ray_tpu.scheduler.bundle_packing import pack_bundles_kernel
        from ray_tpu.scheduler.resources import ResourceRequest
        view = _view([("a", {"CPU": 2.0}, None), ("b", {"CPU": 8.0}, None)])
        bundles = [ResourceRequest({"CPU": 2.0}) for _ in range(3)]
        got = pack_bundles_kernel(view, bundles, "STRICT_PACK")
        assert got == ["b"] * 3

    def test_pg_end_to_end_rides_kernel(self, ray_start_cluster):
        """With pg_kernel_backend=force a real placement group solves
        through the kernel (kernel_placements counter moves) and still
        reserves/commits correctly."""
        from ray_tpu.scheduler import bundle_packing
        from ray_tpu.util.placement_group import (placement_group,
                                                  remove_placement_group)
        ray_start_cluster(num_cpus=2)
        get_config().pg_kernel_backend = "force"
        before = bundle_packing.kernel_stats["kernel_placements"]
        pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
        assert ray_tpu.get(pg.ready(), timeout=30)
        assert bundle_packing.kernel_stats["kernel_placements"] > before
        remove_placement_group(pg)


class TestAutoscalerKernel:
    """The demand solve routed through the kernel."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_bin_pack_residual_parity(self, seed):
        from ray_tpu.autoscaler import resource_demand_scheduler as rds
        rng = np.random.default_rng(seed)
        for _ in range(15):
            n = int(rng.integers(1, 10))
            nodes = [{"CPU": float(rng.integers(1, 9)),
                      "memory": float(rng.integers(1, 17))}
                     for _ in range(n)]
            nd = int(rng.integers(1, 15))
            demands = [{"CPU": float(rng.integers(1, 5))}
                       for _ in range(nd)]
            unf_np, _ = rds.get_bin_pack_residual(nodes, list(demands))
            unf_k, _, _ = rds._kernel_bin_pack(nodes, list(demands))
            # The kernel's best-fit ordering may only ever fit MORE.
            assert len(unf_k) <= len(unf_np)

    def test_get_nodes_for_never_over_launches(self):
        from ray_tpu.autoscaler import resource_demand_scheduler as rds
        types = {"small": {"resources": {"CPU": 4, "memory": 8},
                           "max_workers": 50},
                 "big": {"resources": {"CPU": 32, "memory": 128},
                         "max_workers": 10}}
        rng = np.random.default_rng(5)
        for _ in range(10):
            nd = int(rng.integers(1, 30))
            demands = [{"CPU": float(rng.choice([1, 2, 4])),
                        "memory": float(rng.choice([1, 2, 8]))}
                       for _ in range(nd)]
            to_np, unf_np = rds.get_nodes_for(types, {}, 16, list(demands))
            to_k, unf_k = rds._kernel_get_nodes_for(types, {}, 16,
                                                    list(demands))
            assert len(unf_k) <= len(unf_np)
            assert sum(to_k.values()) <= max(sum(to_np.values()), 1)

    def test_get_nodes_to_launch_kernel_forced(self):
        """The full orchestration under autoscaler_kernel_backend=force
        (every bin-pack call rides the kernel) matches the numpy path's
        launch decision on a representative demand mix."""
        from ray_tpu.autoscaler import resource_demand_scheduler as rds
        types = {"head": {"resources": {"CPU": 4}, "max_workers": 1},
                 "worker": {"resources": {"CPU": 8, "memory": 32},
                            "min_workers": 1, "max_workers": 8},
                 "tpu_worker": {"resources": {"CPU": 8, "TPU": 4},
                                "max_workers": 4}}
        sched = rds.ResourceDemandScheduler(types, max_workers=12,
                                            head_node_type="head")
        demands = [{"CPU": 2}] * 10 + [{"TPU": 2}] * 3
        pgs = [{"strategy": "STRICT_SPREAD",
                "bundles": [{"CPU": 4}, {"CPU": 4}]}]
        args = dict(node_type_counts={"head": 1},
                    launching_nodes={},
                    resource_demands=[dict(d) for d in demands],
                    unused_resources_by_node={"h": {"CPU": 4}},
                    pending_placement_groups=pgs)
        get_config().autoscaler_kernel_backend = "off"
        base, base_unf = sched.get_nodes_to_launch(**args)
        get_config().autoscaler_kernel_backend = "force"
        before = rds.kernel_stats["kernel_solves"]
        got, got_unf = sched.get_nodes_to_launch(**args)
        assert rds.kernel_stats["kernel_solves"] > before
        assert len(got_unf) <= len(base_unf)
        assert sum(got.values()) <= sum(base.values())
        # TPU demand must still force TPU workers on both paths.
        assert got.get("tpu_worker", 0) >= 1
        assert base.get("tpu_worker", 0) >= 1


class TestPlacementQualityCounters:
    """The two /metrics counters the cost terms are measured against."""

    def test_spillback_reason_counters_exist_and_label(
            self, ray_start_cluster, tmp_path):
        import os
        cluster = ray_start_cluster(num_cpus=1)
        cluster.add_node(num_cpus=1)
        assert cluster.wait_for_nodes(2)
        ctm = cluster.head_node.cluster_task_manager
        assert "spillbacks_no_capacity" in ctm.tick_stats
        assert "spillbacks_locality_override" in ctm.tick_stats
        barrier = str(tmp_path / "barrier")
        os.makedirs(barrier, exist_ok=True)

        @ray_tpu.remote(num_cpus=1)
        def busy(i, n):
            # Both tasks must run CONCURRENTLY -> one must spill.
            open(os.path.join(barrier, str(i)), "w").close()
            deadline = time.monotonic() + 30
            while len(os.listdir(barrier)) < n:
                if time.monotonic() > deadline:
                    raise TimeoutError("barrier never filled")
                time.sleep(0.01)
            return ray_tpu.get_runtime_context().get_node_id()

        nodes = set(ray_tpu.get([busy.remote(i, 2) for i in range(2)],
                                timeout=60))
        assert len(nodes) == 2                       # someone spilled
        total = ctm.tick_stats["spillbacks"]
        assert total >= 1
        assert (ctm.tick_stats["spillbacks_no_capacity"] +
                ctm.tick_stats["spillbacks_locality_override"]) == total
        # The reason-labeled counters are real /metrics series.
        from ray_tpu._private.metrics_agent import get_metrics_registry
        text = get_metrics_registry().render_prometheus()
        assert "ray_tpu_scheduler_tick_spillbacks_no_capacity" in text
        assert "ray_tpu_scheduler_tick_spillbacks_locality_override" \
            in text

    def test_locality_zeroes_cross_node_fetch(self, ray_start_cluster):
        """ACCEPTANCE: with the arg-locality cost live, a burst of
        tasks consuming a B-resident object runs ON B — the
        cross_node_fetch_bytes counters do not move.  Retried with a
        fresh object per attempt (a single greedy-degraded tick could
        legitimately place one task locally)."""
        cluster = ray_start_cluster(num_cpus=4)
        node_b = cluster.add_node(num_cpus=4, resources={"b": 1})
        assert cluster.wait_for_nodes(2)
        time.sleep(0.3)

        @ray_tpu.remote(resources={"b": 0.01}, num_cpus=0)
        def produce():
            return np.ones(600_000, dtype=np.float64)   # ~4.8MB -> store

        @ray_tpu.remote(num_cpus=1)
        def consume(x):
            return (float(x[0]), ray_tpu.get_runtime_context().get_node_id())

        def fetch_bytes():
            return sum(
                n.object_manager.stats["cross_node_fetch_bytes"]
                for n in (cluster.head_node, node_b))

        b_hex = node_b.node_id.hex()
        for attempt in range(3):
            ref = produce.remote()
            ray_tpu.wait([ref], timeout=30)
            before = fetch_bytes()
            out = ray_tpu.get([consume.remote(ref) for _ in range(4)],
                              timeout=60)
            assert [v for v, _ in out] == [1.0] * 4
            where = {n for _, n in out}
            if where == {b_hex} and fetch_bytes() == before:
                break
        else:
            pytest.fail(f"locality never converged: ran on {where}, "
                        f"fetched {fetch_bytes() - before} bytes")
        # And the counter is a real /metrics series.
        from ray_tpu._private.metrics_agent import get_metrics_registry
        assert "ray_tpu_object_manager_cross_node_fetch_bytes" in \
            get_metrics_registry().render_prometheus()


class TestTransferWriterDedupe:
    """Source-level fix for the double-writer native-delete race."""

    def test_single_writer_per_object(self, ray_start_regular):
        """Concurrent create_transfer_writer calls for one object: the
        loser blocks until the winner seals, then adopts its copy
        (returns None) instead of opening a second writer."""
        import threading

        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.worker import global_worker
        store = global_worker().cluster.head_node.object_store
        oid = ObjectID(b"x" * 24)
        payload = np.arange(250_000, dtype=np.float64).tobytes()
        from ray_tpu._private.serialization import serialize
        blob = serialize(np.frombuffer(payload,
                                       dtype=np.float64)).to_bytes()

        w1 = store.create_transfer_writer(oid, len(blob))
        assert w1 is not None
        results = []

        def second():
            w2 = store.create_transfer_writer(oid, len(blob))
            results.append(w2)

        t = threading.Thread(target=second, daemon=True)
        t.start()
        time.sleep(0.2)
        assert not results                  # blocked behind the winner
        w1.write(0, blob)
        w1.seal()
        t.join(timeout=10)
        assert results == [None]            # adopted, no second writer
        assert store.contains(oid)
        assert store.stats.get("vanished_objects", 0) == 0
        store.delete(oid)

    def test_concurrent_pull_stress_no_vanished_objects(
            self, ray_start_cluster):
        """The cross-node transfer stress shape that produced the
        upstream race: many concurrent pulls of the same objects into
        one store.  With the single-writer dedupe, vanished_objects
        stays 0 everywhere and every copy reads back intact."""
        import threading

        cluster = ray_start_cluster(num_cpus=1)
        src = cluster.add_node(num_cpus=0, resources={"src": 1},
                               object_store_memory=256 * 1024 * 1024)
        dst = cluster.add_node(num_cpus=0, resources={"dst": 1},
                               object_store_memory=256 * 1024 * 1024)
        assert cluster.wait_for_nodes(3)

        @ray_tpu.remote(resources={"src": 0.01}, num_cpus=0)
        def produce(i):
            return np.full(300_000, i, dtype=np.float64)  # ~2.4MB

        refs = [produce.remote(i) for i in range(4)]
        ray_tpu.wait(refs, num_returns=len(refs), timeout=60)
        oids = [r.object_id() for r in refs]

        for _round in range(3):
            done = []
            errors = []

            def pull(oid):
                ev = threading.Event()

                def cb(ok):
                    if not ok:
                        errors.append(oid)
                    ev.set()

                dst.object_manager.pull_async(oid, cb)
                assert ev.wait(timeout=60)
                done.append(oid)

            threads = [threading.Thread(target=pull, args=(oid,),
                                        daemon=True)
                       for oid in oids for _ in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=90)
            assert not errors
            assert len(done) == len(threads)
            for i, oid in enumerate(oids):
                entry = dst.object_store.get(oid)
                assert entry is not None
                # Drop the replica so the next round re-pulls.
                dst.object_store.delete(oid)
                cluster.object_directory.remove_location(oid, dst.node_id)
        for node in [cluster.head_node, src, dst]:
            assert node.object_store.stats.get("vanished_objects", 0) == 0


class TestShardedSolveParity:
    """ISSUE 17 satellite: the pod-sharded solve vs the single-device
    kernel.  The suite-wide ``XLA_FLAGS=--xla_force_host_platform_
    device_count=8`` (conftest) gives these tests an 8-device CPU
    "pod" in-process.

    Parity contract (sharded_solve module docstring): the sharded ring
    pads N to ``_GROUP * n_shards``, so against the numpy oracle ON
    THAT RING the waterfill is bit-exact for ANY N; against the
    single-device kernel it is bit-exact when both rings coincide and
    feasibility-equal otherwise (same placed totals per class is NOT
    guaranteed node-for-node — only oracle-pinned determinism is)."""

    def _force(self, n_shards=None):
        import jax
        cfg = get_config()
        cfg.solver_shard_backend = "force"
        return n_shards or len(jax.devices())

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    @pytest.mark.parametrize("mode", ["plain", "cost", "pack",
                                      "pack_cost"])
    def test_waterfill_matches_oracle_on_sharded_ring(self, seed, mode):
        from ray_tpu.scheduler import sharded_solve
        rng = np.random.default_rng(seed)
        n_shards = self._force()
        C, N, R = 8, int(rng.integers(20, 90)), 4
        avail, total, demand, counts, an, ac = _random_problem(
            rng, C=C, N=N, R=R)
        cost = None
        if "cost" in mode:
            cost = np.where(rng.random((C, N)) < 0.2,
                            rng.uniform(-0.7, 0.5, (C, N)),
                            0.0).astype(np.float32)
        pack = "pack" in mode
        got = sharded_solve.solve_matrices_sharded(
            avail, total, demand, counts, an, ac, 0.5, cost,
            pack, pack, n_shards)
        _, n_pad, _ = sharded_solve.pads_sharded(C, N, R, n_shards)
        want = waterfill_oracle(avail, total, demand, counts, an, ac,
                                0.5, cost=cost, invert_util=pack,
                                zero_shifts=pack, n_pad=n_pad)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_waterfill_bit_parity_on_aligned_n(self, seed):
        """When N is a multiple of _GROUP * n_shards both rings
        coincide: sharded == single-device bit-for-bit."""
        from ray_tpu.scheduler import sharded_solve
        from ray_tpu.scheduler.jax_backend import _GROUP
        rng = np.random.default_rng(seed)
        n_shards = self._force()
        N = _GROUP * n_shards
        avail, total, demand, counts, an, ac = _random_problem(
            rng, C=6, N=N, R=3)
        get_config().solver_shard_backend = "off"
        single = BatchSolver().solve_matrices(
            avail, total, demand, counts, an, ac, spread_threshold=0.5)
        sharded = sharded_solve.solve_matrices_sharded(
            avail, total, demand, counts, an, ac, 0.5, None,
            False, False, n_shards)
        np.testing.assert_array_equal(single, sharded)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("strategy", ["PACK", "SPREAD",
                                          "STRICT_PACK",
                                          "STRICT_SPREAD"])
    def test_bundle_bit_parity_all_strategies(self, seed, strategy):
        """Bundles are argmax-per-step: the cross-shard first-max
        reduction reproduces the single-device tie-break exactly, so
        bit parity holds for ANY N."""
        from ray_tpu.scheduler import sharded_solve
        rng = np.random.default_rng(seed)
        n_shards = self._force()
        N, R = int(rng.integers(3, 40)), 3
        total = rng.integers(2, 32, size=(N, R)).astype(np.float64)
        avail = np.floor(total * rng.uniform(0.3, 1.0, size=(N, R)))
        B = int(rng.integers(1, 6))
        demand = rng.integers(0, 5, size=(B, R)).astype(np.float64)
        excluded = rng.random(N) < 0.1
        get_config().solver_shard_backend = "off"
        i1, o1 = BatchSolver().solve_bundles(avail, total, demand,
                                             strategy, excluded)
        i2, o2 = sharded_solve.solve_bundles_sharded(
            avail, total, demand, strategy, excluded, n_shards)
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_array_equal(o1, o2)

    def test_pg_strategies_through_pack_bundles_surface(self):
        """End-to-end through the pack_bundles routing with the shard
        gate forced: every strategy still validates."""
        from ray_tpu.scheduler.bundle_packing import (
            pack_bundles_kernel, validate_assignment)
        from ray_tpu.scheduler.resources import ResourceRequest
        rng = np.random.default_rng(5)
        self._force()
        cfg = get_config()
        cfg.pg_kernel_backend = "force"
        view = _view([(f"n{i}",
                       {"CPU": float(rng.integers(2, 8)),
                        "memory": float(rng.integers(2, 16))}, None)
                      for i in range(6)])
        bundles = [ResourceRequest({"CPU": 1.0, "memory": 1.0})
                   for _ in range(3)]
        for strategy in ("PACK", "SPREAD", "STRICT_PACK",
                         "STRICT_SPREAD"):
            got = pack_bundles_kernel(view, bundles, strategy)
            assert got is not None, strategy
            assert validate_assignment(view, bundles, got, strategy,
                                       set())

    def test_min_nodes_gate(self):
        """Below solver_shard_min_nodes (mode=auto) the solve stays
        single-device; force overrides; off disables."""
        import jax
        from ray_tpu.scheduler import sharded_solve
        cfg = get_config()
        cfg.solver_shard_backend = "auto"
        cfg.solver_shard_min_nodes = 4096
        assert sharded_solve.plan_shards(100) == 1
        assert sharded_solve.plan_shards(4096) == len(jax.devices())
        cfg.solver_shard_backend = "force"
        assert sharded_solve.plan_shards(100) == len(jax.devices())
        cfg.solver_shard_backend = "off"
        assert sharded_solve.plan_shards(100_000) == 1

    def test_shard_failure_is_loud(self, monkeypatch):
        """No run-time switch hides a sharded failure: it propagates
        from BatchSolver, and on the live tick it is counted under
        ``device_errors`` (apart from the validated ``fallbacks``)
        while the sharded path stays planned."""
        from ray_tpu.scheduler import sharded_solve
        rng = np.random.default_rng(9)
        self._force()
        avail, total, demand, counts, an, ac = _random_problem(rng)

        def boom(*a, **k):
            raise RuntimeError("injected shard failure")

        monkeypatch.setattr(sharded_solve, "solve_matrices_sharded",
                            boom)
        with pytest.raises(RuntimeError, match="injected shard"):
            BatchSolver().solve_matrices(
                avail, total, demand, counts, an, ac,
                spread_threshold=0.5)

        monkeypatch.setattr(sharded_solve, "solve_tick_sharded", boom)
        view = _view([(f"n{i}", {"CPU": 8.0}, None) for i in range(4)])
        solver = DeviceRuntimeSolver()
        specs = [_Spec(1.0, 7101) for _ in range(4)]
        assert solver.solve(view, specs) is None
        assert solver.stats["device_errors"] == 1
        assert solver.stats["fallbacks"] == 0
        assert sharded_solve.plan_shards(10_000) > 1   # not switched off
        monkeypatch.undo()
        targets = solver.solve(view, specs)
        assert targets is not None and all(t is not None for t in targets)
        assert solver.stats["sharded_ticks"] == 1
        assert solver.stats["device_errors"] == 1
