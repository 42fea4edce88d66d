"""TPU scheduling kernel tests: golden vs numpy oracle, feasibility
invariants, end-to-end scheduler_backend=jax (runs on the virtual CPU
mesh in CI; the same code path runs on the real chip in
chip_smoke.py)."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.scheduler.jax_backend import (BatchSolver, DeviceRuntimeSolver,
                                           waterfill_oracle)


@pytest.fixture(autouse=True)
def _no_device_errors(monkeypatch):
    """Every DeviceRuntimeSolver this module creates — directly or
    inside a raylet — must finish its test with zero device errors: a
    compile or device failure returns None like a stale view does, so
    without this gate the greedy fallback would keep the suite green."""
    solvers = []
    init = DeviceRuntimeSolver.__init__

    def tracking_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        solvers.append(self)

    monkeypatch.setattr(DeviceRuntimeSolver, "__init__", tracking_init)
    yield solvers
    assert [s.stats["device_errors"] for s in solvers] == \
        [0] * len(solvers)


def random_problem(rng, C=12, N=40, R=4):
    total = rng.integers(1, 32, size=(N, R)).astype(np.float32)
    # Some nodes partially used already.
    used_frac = rng.uniform(0, 0.5, size=(N, R)).astype(np.float32)
    avail = np.floor(total * (1 - used_frac))
    demand = np.zeros((C, R), dtype=np.float32)
    for c in range(C):
        k = rng.integers(1, R + 1)
        cols = rng.choice(R, size=k, replace=False)
        demand[c, cols] = rng.integers(1, 4, size=k)
    counts = rng.integers(0, 50, size=C)
    accel_node = rng.random(N) < 0.25
    accel_class = rng.random(C) < 0.2
    return avail, total, demand, counts, accel_node, accel_class


class TestWaterfillKernel:
    def test_matches_numpy_oracle(self):
        rng = np.random.default_rng(0)
        solver = BatchSolver()
        for trial in range(5):
            avail, total, demand, counts, an, ac = random_problem(rng)
            got = solver.solve_matrices(avail, total, demand, counts, an, ac,
                                        spread_threshold=0.5)
            want = waterfill_oracle(avail, total, demand, counts, an, ac,
                                    spread_threshold=0.5)
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"trial {trial}")

    def test_capacity_never_violated(self):
        rng = np.random.default_rng(1)
        solver = BatchSolver()
        for _ in range(5):
            avail, total, demand, counts, an, ac = random_problem(
                rng, C=20, N=64, R=5)
            alloc = solver.solve_matrices(avail, total, demand, counts,
                                          an, ac)
            usage = alloc.T.astype(np.float64) @ demand.astype(np.float64)
            assert (usage <= avail + 1e-3).all()
            assert (alloc.sum(axis=1) <= counts).all()

    def test_all_assigned_when_plenty(self):
        solver = BatchSolver()
        avail = total = np.full((8, 2), 100.0, dtype=np.float32)
        demand = np.array([[1.0, 0.0], [0.0, 2.0]], dtype=np.float32)
        counts = np.array([100, 50])
        alloc = solver.solve_matrices(avail, total, demand, counts)
        assert alloc.sum(axis=1).tolist() == [100, 50]

    def test_infeasible_left_unassigned(self):
        solver = BatchSolver()
        avail = total = np.full((4, 1), 2.0, dtype=np.float32)
        demand = np.array([[5.0]], dtype=np.float32)  # never fits
        alloc = solver.solve_matrices(avail, total, demand, np.array([10]))
        assert alloc.sum() == 0


class TestSolveTickProgram:
    """``_jit_solve_tick`` — the program a raylet runs each tick — called
    as ``DeviceRuntimeSolver._solve_groups`` calls it: resident [R, N]
    matrices in, one packed sparse assignment with ``_pack_tick``'s
    validation bits out."""

    @staticmethod
    def _tick(avail, total, demand, counts, accel_node, accel_class,
              nnz_max, cost=None, fused=False):
        """Returns (dense alloc[C, N], placed, ok, nnz) of one tick;
        ``fused`` takes the Pallas fill (interpret mode off the chip)."""
        from ray_tpu.scheduler import jax_backend as jb
        C, R = demand.shape
        N = avail.shape[0]
        c_pad, n_pad, r_pad = BatchSolver._pads(C, N, R)
        cost_p = np.zeros((c_pad, n_pad), np.float32) if cost is None \
            else jb._pad_to(cost, (c_pad, n_pad))
        packed = np.asarray(jb._jit_solve_tick(
            c_pad, n_pad, r_pad, nnz_max, fused)(
                jb._pad_to(avail, (n_pad, r_pad)).T.copy(),
                jb._pad_to(total, (n_pad, r_pad)).T.copy(),
                jb._pad_to(demand, (c_pad, r_pad)),
                jb._pad_to(counts.astype(np.float32), (c_pad,)),
                jb._pad_to(accel_node, (n_pad,)),
                jb._pad_to(accel_class, (c_pad,)),
                np.float32(0.5), cost_p))
        assert packed.shape == (2 * nnz_max + 3,)
        idx, vals, placed, ok, nnz = jb._unpack_tick(packed, nnz_max)
        return (jb._dense_alloc(idx, vals, c_pad, n_pad)[:C, :N],
                placed, ok, nnz)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    @pytest.mark.parametrize("fused", [False, True])
    def test_packed_tick_decodes_to_the_oracle(self, seed, fused):
        rng = np.random.default_rng(seed)
        avail, total, demand, counts, an, ac = random_problem(rng)
        cost = None
        if seed % 2:
            shape = (demand.shape[0], avail.shape[0])
            cost = np.where(rng.random(shape) < 0.15,
                            rng.uniform(-0.7, 0.5, shape),
                            0.0).astype(np.float32)
        want = waterfill_oracle(avail, total, demand, counts, an, ac,
                                spread_threshold=0.5, cost=cost)
        assert (want > 0).sum() > 16          # several cells per class
        alloc, placed, ok, nnz = self._tick(avail, total, demand, counts,
                                            an, ac, nnz_max=512, cost=cost,
                                            fused=fused)
        assert ok
        np.testing.assert_array_equal(alloc, want)
        assert nnz == int((want > 0).sum())
        assert placed == int(want.sum())

    def test_overflow_clears_the_ok_bit(self):
        """``nnz_max`` below the true number of nonzeros must come back
        ``ok`` false with the true count — the bit ``_solve_groups``
        falls back to the greedy path on — and the next bucket up holds
        the same tick whole."""
        avail = total = np.full((16, 2), 100.0, dtype=np.float32)
        demand = np.ones((8, 2), dtype=np.float32)
        counts = np.full(8, 16)                # a cell per class: 8 > 4
        none = np.zeros(16, dtype=bool), np.zeros(8, dtype=bool)
        _, placed, ok, nnz = self._tick(avail, total, demand, counts,
                                        *none, nnz_max=4)
        assert not ok and nnz > 4 and placed == 8 * 16
        alloc, placed, ok, nnz_fit = self._tick(avail, total, demand,
                                                counts, *none, nnz_max=256)
        assert ok and nnz_fit == nnz == int((alloc > 0).sum())
        assert alloc.sum(axis=1).tolist() == [16] * 8


class TestDeviceRuntimeSolver:
    """The device-resident session the runtime dispatch path runs on."""

    class _Spec:
        def __init__(self, cpu, cls):
            from ray_tpu.scheduler.policy import SchedulingOptions
            from ray_tpu.scheduler.resources import ResourceRequest
            self.resources = ResourceRequest({"CPU": cpu})
            self.scheduling_options = SchedulingOptions.hybrid()
            self.scheduling_class = cls

    def _view(self, n=4, cpu=4.0):
        from ray_tpu.scheduler.resources import (ClusterResourceView,
                                                 NodeResources)
        view = ClusterResourceView()
        for i in range(n):
            view.add_node(f"node{i}",
                          NodeResources({"CPU": cpu, "memory": 8.0}))
        return view

    def test_solve_then_delta_sync(self):
        view = self._view()
        solver = DeviceRuntimeSolver()
        specs = [self._Spec(1.0, 9101) for _ in range(8)]
        targets = solver.solve(view, specs)
        assert targets is not None and all(t is not None for t in targets)
        assert solver.stats["full_syncs"] == 1
        # Commit grants on the host view -> dirty rows -> the next tick
        # ships row deltas instead of re-uploading the world.
        for t, s in zip(targets, specs):
            assert view.subtract(t, s.resources)
        targets2 = solver.solve(
            view, [self._Spec(1.0, 9101) for _ in range(4)])
        assert targets2 is not None and all(t is not None for t in targets2)
        assert solver.stats["full_syncs"] == 1   # no structural change
        assert solver.stats["row_deltas"] >= 1
        assert solver.stats["fallbacks"] == 0

    def test_structural_change_forces_full_sync(self):
        from ray_tpu.scheduler.resources import NodeResources
        view = self._view(n=2)
        solver = DeviceRuntimeSolver()
        assert solver.solve(view, [self._Spec(1.0, 9102)]) is not None
        view.add_node("late", NodeResources({"CPU": 4.0}))
        t2 = solver.solve(view, [self._Spec(1.0, 9102) for _ in range(9)])
        assert t2 is not None and all(t is not None for t in t2)
        assert solver.stats["full_syncs"] == 2
        assert "late" in t2  # the new node is schedulable

    def test_respects_capacity_and_reports_infeasible(self):
        view = self._view(n=2, cpu=2.0)
        solver = DeviceRuntimeSolver()
        specs = [self._Spec(1.0, 9103) for _ in range(10)]
        targets = solver.solve(view, specs)
        assert targets is not None
        placed = [t for t in targets if t is not None]
        assert len(placed) == 4          # 2 nodes x 2 CPU
        from collections import Counter
        assert max(Counter(placed).values()) <= 2


    def test_resource_no_node_advertises_yet(self):
        """A class demanding a resource the view has no column for (its
        node's first report has not arrived) makes the column and solves:
        nothing fits, nothing raises.  This used to die with an
        IndexError inside demand_matrix that the solver's catch-all
        counted as an ordinary fallback."""
        from ray_tpu.scheduler.resources import ResourceRequest
        view = self._view()
        solver = DeviceRuntimeSolver()
        late = self._Spec(1.0, 9105)
        late.resources = ResourceRequest({"CPU": 1.0, "late_resource": 1.0})
        targets = solver.solve(view, [late, self._Spec(1.0, 9106)])
        assert targets is not None
        assert targets[0] is None and targets[1] is not None
        assert solver.stats["fallbacks"] == 0
        assert "late_resource" in view.columns

    def test_class_eviction_bounds_demand_matrix(self):
        """Churning through many distinct scheduling classes must not
        grow the demand matrix forever: idle classes are evicted when
        growth would widen c_cap, and the solver still solves correctly
        afterwards."""
        view = self._view(n=4, cpu=64.0)
        solver = DeviceRuntimeSolver()
        solver._CLASS_IDLE_TICKS = 4   # make staleness cheap to reach
        for wave in range(40):
            specs = [self._Spec(1.0, 20000 + wave)]
            targets = solver.solve(view, specs)
            assert targets is not None and targets[0] is not None
        assert solver.stats["class_evictions"] > 0
        # Bounded: far fewer live rows than the 40 classes ever seen.
        assert len(solver._class_reqs) < 24
        assert solver._demand_host.shape[0] <= 24
        # Still correct after compaction, including for a re-appearing
        # evicted class.
        specs = [self._Spec(1.0, 20000), self._Spec(1.0, 20039)]
        targets = solver.solve(view, specs)
        assert targets is not None and all(t is not None for t in targets)

    def test_class_hard_cap_falls_back(self):
        """A tick needing more than _MAX_CLASS_ROWS live classes returns
        None (native greedy fallback) instead of growing unboundedly."""
        view = self._view(n=2, cpu=8.0)
        solver = DeviceRuntimeSolver()
        solver._MAX_CLASS_ROWS = 8
        specs = [self._Spec(1.0, 30000 + i) for i in range(12)]
        assert solver.solve(view, specs) is None
        assert solver.stats["fallbacks"] == 1

    def test_device_error_is_counted_apart_and_logged_once(
            self, monkeypatch, caplog, _no_device_errors):
        """A device/compile error returns None like an invalid
        assignment does, but under its own counter and with one
        traceback in the log — and the next tick retries the device
        path (no run-time switch to another path)."""
        from ray_tpu.scheduler import jax_backend
        view = self._view()
        solver = DeviceRuntimeSolver()
        specs = [self._Spec(1.0, 9104) for _ in range(4)]
        real = jax_backend._jit_solve_tick

        def boom(*a, **k):
            raise RuntimeError("injected mosaic failure")

        monkeypatch.setattr(jax_backend, "_jit_solve_tick", boom)
        with caplog.at_level("ERROR", logger=jax_backend.__name__):
            assert solver.solve(view, specs) is None
            assert solver.solve(view, specs) is None
        assert solver.stats["device_errors"] == 2
        assert solver.stats["fallbacks"] == 0
        logged = [r for r in caplog.records
                  if "device solve failed" in r.getMessage()]
        assert len(logged) == 1 and logged[0].exc_info is not None
        monkeypatch.setattr(jax_backend, "_jit_solve_tick", real)
        targets = solver.solve(view, specs)
        assert targets is not None and all(t is not None for t in targets)
        assert solver.last_path == "single/jnp"
        _no_device_errors.remove(solver)   # the injected errors


class TestJaxBackendEndToEnd:
    def test_jax_is_the_default_backend_and_on_dispatch_path(self):
        """scheduler_backend defaults to jax since round 3; burst
        submissions run the device-resident session, not the dense
        per-call path, and never fall back."""
        from ray_tpu._private.cluster import Cluster
        cluster = Cluster(initialize_head=True,
                          head_node_args=dict(num_cpus=4))
        ray_tpu.init(_cluster=cluster)
        try:
            from ray_tpu._private.config import get_config
            assert get_config().scheduler_backend == "jax"

            @ray_tpu.remote
            def f(i):
                # Hold the worker a moment: with a no-op body the first
                # leased workers can drain the whole burst by reuse, the
                # submitter then never asks for a lease BATCH, no tick
                # sees two queued entries, and the session legitimately
                # never engages (a third of the runs on a quiet box).
                time.sleep(0.02)
                return i + 1

            for _ in range(3):
                refs = [f.remote(i) for i in range(40)]
                assert ray_tpu.get(refs) == list(range(1, 41))
            solver = cluster.head_node.cluster_task_manager._jax_solver
            assert solver is not None, "device session never engaged"
            assert solver.stats["ticks"] >= 1
            assert solver.stats["fallbacks"] == 0
        finally:
            ray_tpu.shutdown()

    def test_tasks_run_under_jax_backend(self):
        ray_tpu.init(num_cpus=4,
                     _system_config={"scheduler_backend": "jax"})
        try:
            @ray_tpu.remote
            def f(i):
                return i * 2

            refs = [f.remote(i) for i in range(100)]
            assert ray_tpu.get(refs) == [i * 2 for i in range(100)]
        finally:
            ray_tpu.shutdown()

    def test_batch_spreads_across_cluster(self):
        import time
        from ray_tpu._private.cluster import Cluster
        cluster = Cluster(initialize_head=True,
                          head_node_args=dict(num_cpus=2))
        ray_tpu.init(_cluster=cluster,
                     _system_config={"scheduler_backend": "jax"})
        try:
            for _ in range(3):
                cluster.add_node(num_cpus=2)
            assert cluster.wait_for_nodes(4)
            time.sleep(0.3)

            @ray_tpu.remote
            def where():
                time.sleep(0.05)
                return ray_tpu.get_runtime_context().get_node_id()

            nodes = set(ray_tpu.get([where.remote() for _ in range(24)]))
            assert len(nodes) >= 3
        finally:
            ray_tpu.shutdown()


class TestPallasClassFill:
    """The fused Mosaic kernel must compute EXACTLY what the jnp scan
    path computes (it is an independent reimplementation of the
    bucket/prefix math).  Runs in Pallas interpret mode so the CPU test
    suite covers the kernel's semantics; chip_smoke.py asks the chip
    the same question at full width."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("with_cost", [False, True])
    def test_interpret_mode_matches_jnp_scan(self, seed, with_cost):
        import jax.numpy as jnp

        from ray_tpu.scheduler import jax_backend as jb

        rng = np.random.default_rng(seed)
        C, N, R = 16, 64, 4
        c_pad, n_pad, r_pad = 16, 128, 8
        avail = np.floor(rng.uniform(0, 8, (N, R))).astype(np.float32)
        total = avail + np.floor(rng.uniform(0, 4, (N, R))).astype(
            np.float32)
        demand = np.floor(rng.uniform(0, 2.2, (C, R))).astype(np.float32)
        counts = rng.integers(0, 50, C).astype(np.float32)
        accel_node = rng.random(N) < 0.2
        accel_class = rng.random(C) < 0.3

        av_t = jnp.asarray(jb._pad_to(avail, (n_pad, r_pad)).T)
        total_t = jnp.asarray(jb._pad_to(total, (n_pad, r_pad)).T)
        dm = jnp.asarray(jb._pad_to(demand, (c_pad, r_pad)))
        cn = jnp.asarray(jb._pad_to(counts, (c_pad,)))
        an = jnp.asarray(jb._pad_to(accel_node.astype(np.float32),
                                    (n_pad,)) > 0)
        ac = jnp.asarray(jb._pad_to(accel_class.astype(np.float32),
                                    (c_pad,)) > 0)
        thr = np.float32(0.5)
        if with_cost:
            # Locality/heterogeneity-shaped offsets: a few strong node
            # preferences per class, the rest zero.
            cost_np = np.where(rng.random((c_pad, n_pad)) < 0.1,
                               rng.uniform(-0.6, 0.4,
                                           (c_pad, n_pad)), 0.0)
            cost = jnp.asarray(cost_np.astype(np.float32))
            invert = jnp.float32(1.0 if seed % 2 else 0.0)
        else:
            cost = jnp.zeros((c_pad, n_pad), jnp.float32)
            invert = jnp.float32(0.0)
        shifts = jb._class_shifts(c_pad, n_pad)

        av_jnp, alloc_jnp = jb._class_fill(
            av_t, total_t, dm, cn, ac, an, thr,
            c_pad=c_pad, n_pad=n_pad, r_pad=r_pad, use_pallas=False,
            cost=cost, invert=invert, shifts=shifts)
        fill = jb._pallas_class_fill(c_pad, n_pad, r_pad, interpret=True)
        av_pl, alloc_pl = fill(av_t, total_t, dm, cn, ac, an, thr,
                               cost, invert, shifts)

        np.testing.assert_array_equal(np.asarray(alloc_jnp),
                                      np.asarray(alloc_pl))
        np.testing.assert_allclose(np.asarray(av_jnp), np.asarray(av_pl),
                                   atol=1e-4)
