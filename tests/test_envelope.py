"""Cluster envelope / chaos soak: the driver, its schedule, and the
degradation fixes that rode in with it.

Three layers:

* pure-unit — chaos schedule determinism (same seed, same timeline:
  the property that makes a failing soak replayable), broadcast-merge
  algebra, the process-wide worker-startup gate, the wedge-file cap;
* gate-unit — the head's registration admission valve exercised with
  threads against a stubbed admit (deterministic overlap, no process
  races);
* mini-envelope — the REAL driver end-to-end at tier-1 scale (6 hosts,
  200 actors, 20 PGs, 16 MiB broadcast, 2 scheduled faults) asserting
  the zero-silent-loss contract the 50-host soak holds to, plus a
  ``slow``-marked 32-host variant.
"""

import dataclasses
import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import config as config_mod
from ray_tpu._private import worker_pool
from ray_tpu._private.chaos_schedule import (ChaosEvent, KINDS,
                                             generate_schedule)
from ray_tpu._private.envelope import (_parse_broadcasts, chaos_bands,
                                       envelope_system_config,
                                       run_envelope)
from ray_tpu._private.head_service import _merge_broadcast
from ray_tpu._private.worker import global_worker


def _wait_until(pred, timeout=15.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# Chaos schedule: pure-function determinism.


class TestChaosSchedule:
    def test_same_seed_same_timeline(self):
        a = generate_schedule(6, 60.0, 40, 32)
        b = generate_schedule(6, 60.0, 40, 32)
        assert [dataclasses.asdict(e) for e in a] == \
            [dataclasses.asdict(e) for e in b], \
            "schedule must be a pure function of its arguments"

    def test_different_seed_different_timeline(self):
        a = generate_schedule(6, 60.0, 40, 32)
        b = generate_schedule(7, 60.0, 40, 32)
        assert [dataclasses.asdict(e) for e in a] != \
            [dataclasses.asdict(e) for e in b]

    def test_sorted_and_inside_window(self):
        sched = generate_schedule(1, 100.0, 50, 16)
        times = [e.at_s for e in sched]
        assert times == sorted(times)
        assert all(5.0 <= t <= 95.0 for t in times)

    def test_kill_budget_and_origin_protection(self):
        n_targets = 64
        sched = generate_schedule(2, 60.0, 200, n_targets)
        kills = [e for e in sched if e.kind == "sigkill"]
        assert len(kills) <= max(1, n_targets // 16), \
            "SIGKILLs must stay inside the budget or the fleet " \
            "cannot survive its own soak"
        assert all(e.target >= 1 for e in sched), \
            "target 0 (relay origin) is never selected"
        assert {e.kind for e in sched} <= set(KINDS)

    def test_partition_durations_draw_from_bands(self):
        flap, hold = (0.2, 0.5), (2.0, 4.0)
        sched = generate_schedule(3, 60.0, 120, 16,
                                  flap_band=flap, hold_band=hold)
        parts = [e for e in sched if e.kind == "partition"]
        assert parts
        for e in parts:
            in_flap = flap[0] <= e.duration_s <= flap[1]
            in_hold = hold[0] <= e.duration_s <= hold[1]
            assert in_flap or in_hold
            assert e.params["direction"] in ("inbound", "outbound",
                                             "both")

    def test_timed_partition_actually_disarms(self, monkeypatch):
        # Soak-found: the runner closed the partition helper's control
        # client without disarming the drop faults in the daemon, so
        # every "healed" partition stayed armed forever — sub-grace
        # flaps escalated to node deaths and zero nodes ever came back
        # to be fenced.  Pin heal-before-close on both paths.
        import types

        from ray_tpu._private import chaos_schedule, fault_injection

        made = []

        class FakePartition:
            def __init__(self, target, outbound=True, inbound=True,
                         peer="*"):
                self.healed = False
                self.closed = False
                self.heal_before_close = None
                made.append(self)

            def arm(self):
                return self

            def heal(self):
                self.healed = True
                if self.heal_before_close is None:
                    self.heal_before_close = not self.closed

            def close(self):
                self.closed = True

        monkeypatch.setattr(fault_injection, "partition", FakePartition)

        class FakeProc:
            def poll(self):
                return None

        handle = types.SimpleNamespace(
            proc=FakeProc(), node_name="n0",
            proxy=types.SimpleNamespace(address=("127.0.0.1", 1)))
        sched = [ChaosEvent(0.0, "partition", 0, 0.05,
                            {"direction": "both"}),
                 ChaosEvent(0.0, "partition", 0, 3600.0,
                            {"direction": "inbound"})]
        runner = chaos_schedule.ChaosRunner([handle], sched).start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not (
                made and made[0].healed):
            time.sleep(0.01)
        runner.stop()           # heals the still-armed 3600s hold too
        assert len(made) == 2
        assert all(p.healed for p in made), \
            "every partition must be DISARMED, timed heal and on-stop"
        assert all(p.heal_before_close for p in made)
        assert all(p.closed for p in made)
        timed = [r for r in runner.event_log
                 if r.get("healed_s") not in (None, "on_stop")]
        assert timed, "the 0.05s partition must heal on its timer"


class TestEnvelopeCalibration:
    def test_heartbeat_relaxes_with_fleet_size(self):
        small = envelope_system_config(8)
        big = envelope_system_config(50)
        assert small["raylet_heartbeat_period_milliseconds"] == 100
        assert big["raylet_heartbeat_period_milliseconds"] == 500
        assert envelope_system_config(
            50, {"raylet_heartbeat_period_milliseconds": 250}
        )["raylet_heartbeat_period_milliseconds"] == 250

    def test_chaos_bands_track_grace_config(self):
        cfg = envelope_system_config(50)
        period_s = cfg["raylet_heartbeat_period_milliseconds"] / 1e3
        suspect_s = period_s * cfg["num_heartbeats_suspect"]
        dead_s = period_s * cfg["num_heartbeats_timeout"]
        flap, hold = chaos_bands(cfg)
        assert flap[1] < suspect_s, \
            "flaps must end inside the suspect grace (zero restarts)"
        assert hold[0] > suspect_s and hold[1] > dead_s, \
            "holds must straddle the dead grace (fence evidence)"

    def test_parse_broadcasts(self):
        assert _parse_broadcasts(["128:12", "1024"]) == \
            ((128, 12), (1024, 4))

    def test_oversubscription_tier(self):
        # 50 hosts on 1 core: cadences stretch, per-host thread
        # budgets shrink, watchdog grace grows.
        cfg = envelope_system_config(50, cpu_count=1)
        assert cfg["raylet_heartbeat_period_milliseconds"] == 2000
        assert cfg["rpc_dispatch_pool_size"] == 8
        assert cfg["event_loop_tick_ms"] == 50
        assert cfg["loop_stall_budget_s"] == 60.0
        # Plenty of cores: fleet-size tier only.
        roomy = envelope_system_config(50, cpu_count=64)
        assert roomy["raylet_heartbeat_period_milliseconds"] == 500
        assert "rpc_dispatch_pool_size" not in roomy
        # Small fleets never get the tier even on a starved box.
        mini = envelope_system_config(6, cpu_count=1)
        assert mini["raylet_heartbeat_period_milliseconds"] == 100
        assert "rpc_dispatch_pool_size" not in mini
        # Explicit overrides still win over the tier.
        assert envelope_system_config(
            50, {"rpc_dispatch_pool_size": 16}, cpu_count=1
        )["rpc_dispatch_pool_size"] == 16
        # Default (no cpu_count) stays deterministic for tests.
        assert envelope_system_config(50) == \
            envelope_system_config(50, cpu_count=64)


# ---------------------------------------------------------------------------
# Degradation fix 1: GCS broadcast coalescing (merge algebra + valve).


class TestBroadcastCoalescing:
    def test_merge_none_pending(self):
        batch = {"rows": {"a": 1}, "full": False, "removed": [],
                 "suspect": []}
        assert _merge_broadcast(None, batch) is batch

    def test_merge_delta_over_delta(self):
        pending = {"rows": {"a": 1, "b": 1}, "full": False,
                   "removed": ["x"], "suspect": ["a"]}
        batch = {"rows": {"b": 2, "c": 3}, "full": False,
                 "removed": ["y", "x"], "suspect": ["b"]}
        m = _merge_broadcast(pending, batch)
        assert m["rows"] == {"a": 1, "b": 2, "c": 3}
        assert m["full"] is False
        assert m["removed"] == ["x", "y"]        # union, stable, deduped
        assert m["suspect"] == ["b"]             # pure state: latest wins

    def test_merge_full_supersedes(self):
        pending = {"rows": {"a": 1}, "full": False, "removed": ["x"],
                   "suspect": []}
        batch = {"rows": {"b": 2}, "full": True, "removed": [],
                 "suspect": []}
        m = _merge_broadcast(pending, batch)
        assert m["rows"] == {"b": 2} and m["full"] is True
        assert m["removed"] == ["x"]

    def test_merge_full_pending_stays_full(self):
        pending = {"rows": {"a": 1}, "full": True, "removed": [],
                   "suspect": []}
        batch = {"rows": {"b": 2}, "full": False, "removed": [],
                 "suspect": []}
        m = _merge_broadcast(pending, batch)
        assert m["full"] is True and m["rows"] == {"a": 1, "b": 2}

    def test_at_most_one_rpc_in_flight(self):
        """Three broadcasts against a never-completing send: exactly one
        RPC leaves, the rest merge into one pending batch that flushes
        as a single send on completion."""
        from ray_tpu._private.head_service import RemoteNodeProxy
        from ray_tpu._private.debug.lock_order import diag_lock

        class FakeClient:
            def __init__(self):
                self.sent = []

            def call_async(self, verb, payload, on_done):
                self.sent.append((verb, payload, on_done))

        proxy = object.__new__(RemoteNodeProxy)
        proxy._bcast_lock = diag_lock("test._bcast_lock")
        proxy._bcast_inflight = False
        proxy._bcast_pending = None
        proxy.broadcasts_coalesced = 0
        proxy.broadcasts_sent = 0
        proxy.client = FakeClient()

        def batch(rows, full=False):
            return {"rows": rows, "full": full, "removed": [],
                    "suspect": []}

        proxy.update_resource_usage(batch({"a": 1}))
        proxy.update_resource_usage(batch({"b": 2}))
        proxy.update_resource_usage(batch({"a": 9}))
        assert len(proxy.client.sent) == 1, \
            "broadcasts behind an in-flight send must coalesce"
        assert proxy.broadcasts_coalesced == 2
        assert proxy.broadcasts_sent == 1

        # Complete the in-flight send: the merged pending flushes once.
        _verb, _payload, on_done = proxy.client.sent[0]
        on_done(None, None)
        assert len(proxy.client.sent) == 2
        assert proxy.client.sent[1][1]["rows"] == {"a": 9, "b": 2}
        # Drain: completing the flush with nothing pending goes idle.
        proxy.client.sent[1][2](None, None)
        assert proxy._bcast_inflight is False
        proxy.update_resource_usage(batch({"c": 3}))
        assert len(proxy.client.sent) == 3


# ---------------------------------------------------------------------------
# Degradation fix 2: head-side registration admission (fan-in valve).


class TestRegistrationAdmission:
    @pytest.fixture
    def head(self):
        ray_tpu.init(num_cpus=1)
        cluster = global_worker().cluster
        cluster.start_head_service()
        yield cluster.head_service
        ray_tpu.shutdown()

    def test_storm_defers_past_cap(self, head):
        config_mod.get_config().head_registration_concurrency = 1
        entered = threading.Event()
        release = threading.Event()
        admitted = []

        def slow_admit(payload):
            admitted.append(payload)
            entered.set()
            release.wait(10.0)
            return {"ok": True}

        head._admit_register_node = slow_admit
        replies = []

        def register(i):
            replies.append(head._handle_register_node({"who": i}))

        t0 = threading.Thread(target=register, args=(0,))
        t0.start()
        assert entered.wait(10.0)
        # Two more arrive while the slot is held: both bounce with a
        # busy reply carrying a backoff hint — never queued, never lost.
        register(1)
        register(2)
        release.set()
        t0.join(10.0)

        busy = [r for r in replies if r.get("busy")]
        assert len(busy) == 2 and len(admitted) == 1
        assert all(r["retry_after_ms"] >= 50 for r in busy)
        assert head.registrations_deferred == 2

    def test_deferred_backoff_spreads(self, head):
        """Successive deferrals get increasing retry hints (up to the
        cap) so a 64-node storm doesn't re-collide in lockstep."""
        config_mod.get_config().head_registration_concurrency = 1
        head._admit_register_node = lambda payload: {"ok": True}
        head._registrations_active = 1          # slot pinned busy
        hints = [head._handle_register_node({})["retry_after_ms"]
                 for _ in range(8)]
        assert hints == sorted(hints) and hints[0] < hints[-1]

    def test_gate_disabled_at_zero(self, head):
        config_mod.get_config().head_registration_concurrency = 0
        head._admit_register_node = lambda payload: {"ok": True}
        head._registrations_active = 5
        assert head._handle_register_node({}) == {"ok": True}


# ---------------------------------------------------------------------------
# Degradation fix 3: process-wide worker-startup gate.


class TestStartupThrottle:
    def _drain(self):
        worker_pool._release_global_start_slots(
            worker_pool.global_startup_in_flight())

    def test_cap_grants_and_throttles(self):
        self._drain()
        base_throttled = worker_pool.global_startup_throttled()
        config_mod.get_config().worker_global_startup_concurrency = 2
        try:
            assert worker_pool._acquire_global_start_slots(1) == 1
            assert worker_pool._acquire_global_start_slots(3) == 1
            assert worker_pool._acquire_global_start_slots(1) == 0
            assert worker_pool.global_startup_in_flight() == 2
            assert worker_pool.global_startup_throttled() - \
                base_throttled == 3
        finally:
            self._drain()
        assert worker_pool.global_startup_in_flight() == 0

    def test_disabled_gate_still_counts_in_flight(self):
        """cap<=0 disables throttling but the in-flight counter still
        moves — an acquire/release pair stays symmetric even if the
        config flips between the two calls."""
        self._drain()
        config_mod.get_config().worker_global_startup_concurrency = 0
        try:
            assert worker_pool._acquire_global_start_slots(4) == 4
            assert worker_pool.global_startup_in_flight() == 4
            config_mod.get_config().worker_global_startup_concurrency = 2
            worker_pool._release_global_start_slots(4)
            assert worker_pool.global_startup_in_flight() == 0
        finally:
            self._drain()

    def test_release_clamps_at_zero(self):
        self._drain()
        worker_pool._release_global_start_slots(100)
        assert worker_pool.global_startup_in_flight() == 0


# ---------------------------------------------------------------------------
# Soak-found race: the cluster view iterating a LIVE NodeResources
# ledger while a raylet's PG bundle commit adds keys to it.


class TestClusterViewLiveLedger:
    def test_update_node_survives_concurrent_key_churn(self):
        import threading

        from ray_tpu.scheduler.resources import (ClusterResourceView,
                                                 NodeResources)

        view = ClusterResourceView()
        res = NodeResources({"CPU": 4})
        view.add_node(b"n1", res)
        stop = threading.Event()
        errors = []

        def churn():
            # Bundle commit/cancel churn: formatted PG resource keys
            # appear and vanish on the live dicts.
            i = 0
            while not stop.is_set():
                key = f"CPU_group_{i % 7}_deadbeef"
                res.total[key] = 1000
                res.available[key] = 1000
                res.total.pop(key, None)
                res.available.pop(key, None)
                i += 1

        def update():
            try:
                for _ in range(300):
                    view.update_node(b"n1", res)
            except RuntimeError as e:
                errors.append(e)

        t1 = threading.Thread(target=churn, daemon=True)
        t2 = threading.Thread(target=update, daemon=True)
        t1.start(); t2.start()
        t2.join(30.0)
        stop.set()
        t1.join(5.0)
        assert not errors, f"update_node raced the live ledger: {errors}"


# ---------------------------------------------------------------------------
# Satellite: wedge/crash-file growth cap.


class TestWedgeFileCap:
    def _mk(self, d, pid, n, start=0):
        paths = []
        for i in range(n):
            p = os.path.join(d, f"wedge-{pid}-loop{start + i}-1.json")
            with open(p, "w") as f:
                f.write("{}")
            t = 1_000_000 + (start + i) * 10
            os.utime(p, (t, t))
            paths.append(p)
        return paths

    def test_prune_keeps_newest(self, tmp_path):
        from ray_tpu._private.debug import watchdog
        config_mod.get_config().wedge_files_keep = 3
        d = str(tmp_path)
        self._mk(d, 123, 6)
        other = self._mk(d, 999, 2)             # other pid: untouched
        before = watchdog.crash_files_dropped()
        watchdog._prune_crash_files(d, 123)
        kept = sorted(p for p in os.listdir(d)
                      if p.startswith("wedge-123-"))
        assert kept == ["wedge-123-loop3-1.json",
                        "wedge-123-loop4-1.json",
                        "wedge-123-loop5-1.json"]
        assert all(os.path.exists(p) for p in other)
        assert watchdog.crash_files_dropped() - before == 3

    def test_prune_disabled_at_zero(self, tmp_path):
        from ray_tpu._private.debug import watchdog
        config_mod.get_config().wedge_files_keep = 0
        d = str(tmp_path)
        self._mk(d, 123, 5)
        watchdog._prune_crash_files(d, 123)
        assert len(os.listdir(d)) == 5

    def test_prune_own_on_clean_shutdown(self, tmp_path):
        from ray_tpu._private.debug import watchdog
        config_mod.get_config().temp_dir = str(tmp_path)
        d = os.path.join(str(tmp_path), "wedges")
        os.makedirs(d)
        mine = self._mk(d, os.getpid(), 3)
        other = self._mk(d, 999999, 2)
        assert watchdog.prune_own_crash_files() == 3
        assert not any(os.path.exists(p) for p in mine)
        assert all(os.path.exists(p) for p in other), \
            "clean shutdown must not eat another process's evidence"


# ---------------------------------------------------------------------------
# Degradation fix 4: heartbeat payload budget (end-to-end, one node).


class TestHeartbeatShedding:
    def test_tiny_budget_sheds_telemetry_not_liveness(self):
        ray_tpu.init(num_cpus=1, _system_config={
            "raylet_heartbeat_period_milliseconds": 50,
            "num_heartbeats_timeout": 40,
            "metrics_report_interval_ms": 50,
            # One byte: every metrics payload exceeds it; liveness
            # beats don't consume the budget at all.
            "heartbeat_payload_budget_bytes": 1,
        })
        try:
            cluster = global_worker().cluster
            handle = cluster.add_remote_node(num_cpus=1, timeout=60.0)

            def sheds():
                try:
                    stats = handle.proxy.client.call(
                        "observability_stats", None, timeout=5.0)
                except Exception:
                    return 0
                return int(stats.get("metrics_sheds", 0))

            assert _wait_until(lambda: sheds() >= 2, timeout=30.0), \
                "a 1-byte budget must shed every metrics window"
            # The node must still be ALIVE: shedding is telemetry
            # deferral, never a liveness gap.
            nm = cluster.gcs.node_manager
            assert handle.node_id in nm.alive_nodes
        finally:
            ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# CLI routing.


class TestEnvelopeCli:
    def test_envelope_forwards_argv(self, monkeypatch):
        import ray_tpu._private.envelope as env_mod
        from ray_tpu.scripts import cli
        got = {}

        def fake_main(argv):
            got["argv"] = list(argv)
            return 7

        monkeypatch.setattr(env_mod, "main", fake_main)
        rc = cli.main(["envelope", "--hosts", "4", "--no-chaos"])
        assert rc == 7
        assert got["argv"] == ["--hosts", "4", "--no-chaos"]

    def test_summary_flags_parse(self):
        from ray_tpu.scripts.cli import build_parser
        p = build_parser()
        a = p.parse_args(["doctor", "--summary", "--max-nodes", "8"])
        assert a.summary and a.max_nodes == 8
        a = p.parse_args(["list", "nodes", "--summary"])
        assert a.summary


class TestEnvelopeSmokeBench:
    def test_bench_envelope_smoke_row(self):
        """The CI wiring: ``bench_runtime.py --envelope-smoke`` must
        produce a passing row (subprocess-isolated, timeout-bounded) —
        the envelope's stand-up + zero-silent-loss contract rides
        tier-1 at 4-host cost."""
        import json
        import subprocess
        import sys as _sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        out = subprocess.run(
            [_sys.executable, os.path.join(root, "bench_runtime.py"),
             "--envelope-smoke"],
            capture_output=True, text=True, timeout=420)
        assert out.returncode == 0, (out.stderr or out.stdout)[-800:]
        row = json.loads(out.stdout.strip().splitlines()[-1])
        assert row["metric"] == "envelope_smoke"
        assert row["passed"] and row["silent_loss"] == 0
        assert row["chaos_fired"] >= 1
        assert isinstance(row["cpu_throttled"], bool)


# ---------------------------------------------------------------------------
# The mini-envelope: the real driver, tier-1 scale, contract asserted.


def _assert_zero_silent_loss(result, actors, pgs):
    ledger = result["ledger"]
    assert result["silent_loss"] == 0, result["failures"][:10]
    assert ledger["actor_mismatches"] == 0
    assert ledger["bcast_mismatches"] == 0
    # Exactly-once accounting: every scheduled call is OK, attributed
    # failed, or its actor's create failed — nothing unaccounted.
    calls = actors * 1
    assert (ledger["actor_calls_ok"] + ledger["actor_calls_failed"] +
            ledger["actor_create_failed"]) == calls
    assert ledger["pg_created"] + \
        len([f for f in result["failures"]
             if f["op"] == "pg_create"]) == pgs
    assert ledger["pg_ready"] > 0


class TestMiniEnvelope:
    def test_mini_soak_zero_silent_loss(self):
        hosts, actors, pgs = 6, 200, 20
        try:
            result = run_envelope(
                hosts=hosts, cpus_per_host=1,
                actors=actors, actor_wave=50, calls_per_actor=1,
                pgs=pgs, pg_wave=10,
                broadcasts=((16, 4),),
                chaos=True, chaos_seed=1234,
                chaos_events=2, chaos_window_s=6.0,
                get_timeout_s=90.0, stand_up_timeout=120.0,
                log=lambda *a: None)
        finally:
            ray_tpu.shutdown()
        _assert_zero_silent_loss(result, actors, pgs)
        assert result["chaos"]["scheduled"] == 2
        assert result["chaos"]["fired"] + \
            result["chaos"]["skipped"] == 2
        assert result["chaos"]["fired"] >= 1
        # Every latency number has a per-stage breakdown.
        assert "dispatch" in result["latency"]
        assert "p99_s" in result["latency"]["dispatch"]
        # Degradation evidence is present (counters may be zero at
        # this scale — the keys must exist for the 50-host run).
        deg = result["degradation"]
        assert set(deg) == {"registration_admission",
                            "broadcast_coalescing",
                            "heartbeat_shedding",
                            "wedge_files_dropped"}
        assert deg["heartbeat_shedding"]["nodes_polled"] > 0

    @pytest.mark.slow
    def test_32_host_soak(self):
        hosts, actors, pgs = 32, 2000, 200
        try:
            result = run_envelope(
                hosts=hosts, cpus_per_host=2,
                actors=actors, actor_wave=200, calls_per_actor=1,
                pgs=pgs, pg_wave=25,
                broadcasts=((64, 8), (256, 4)),
                chaos=True, chaos_seed=6,
                chaos_events=16, chaos_window_s=45.0,
                get_timeout_s=120.0, stand_up_timeout=240.0,
                log=lambda *a: None)
        finally:
            ray_tpu.shutdown()
        _assert_zero_silent_loss(result, actors, pgs)
        assert result["chaos"]["fired"] >= 8
        assert result["membership"]["alive"] >= 1
