"""Cluster-scale envelope driver (ROADMAP open item 1).

Stands up a 50–64-host fleet of REAL node_host OS processes via
``LocalProcessProvider``, then drives the full envelope — actors
created/called/destroyed in waves, placement groups across all four
strategies, 100 MiB–1 GiB objects broadcast 1→N through the PR 12
relay chains — while a seeded :mod:`chaos_schedule` keeps asymmetric
partitions, SIGKILLs, RPC delays/duplicates and spill faults firing
underneath it.

The contract is ZERO SILENT LOSS, and the driver is its own auditor:

* every actor call carries a token the reply must echo — a wrong value
  is a ``silent_loss`` row, an exception/timeout is an ATTRIBUTED
  failure row (the difference is the whole point);
* every broadcast consumer returns the sha256 of the payload it saw —
  any digest differing from the origin's is silent loss;
* every latency number comes from the PR 15 critical-path plane
  (``task_event_manager.latency_summary()``), so a cliff has a
  per-stage breakdown, not a guess.

Entry points: :func:`run_envelope` (importable — tests and
``bench_runtime.py --envelope-smoke`` call it in-process),
:func:`main` (``python -m ray_tpu._private.envelope`` /
``tools/envelope.py`` / ``ray-tpu envelope``).  Results land as a JSON
document (``--out``).
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Workload atoms (module level so they pickle into remote workers).


class _EnvelopeActor:
    """Echo actor with a tamper-evident call counter: the reply must
    carry the creation token AND the per-actor monotone sequence — a
    duplicated execution (retry that was not provably a retry) or a
    cross-wired reply shows up as a mismatch, not a pass."""

    def __init__(self, token: int):
        self.token = token
        self.calls = 0

    def echo(self, i: int):
        self.calls += 1
        return (self.token, i, self.calls)

    def total(self) -> int:
        return self.calls


def _digest_blob(blob) -> str:
    data = blob if isinstance(blob, (bytes, bytearray)) else bytes(blob)
    return hashlib.sha256(data).hexdigest()


# ---------------------------------------------------------------------------
# Calibration.


def envelope_system_config(hosts: int,
                           overrides: Optional[dict] = None,
                           cpu_count: Optional[int] = None) -> dict:
    """System config for a many-process fleet sharing few cores: the
    heartbeat cadence relaxes with fleet size so liveness stays honest
    when 50+ daemons timeshare one box (a 100 ms beat across 64
    processes on 1 core is scheduler noise, not a liveness signal).

    When ``cpu_count`` is given and the fleet oversubscribes it ≥4×,
    a second tier kicks in: per-host thread counts and control-plane
    cadences shrink so the run-queue stays bounded.  Without it a
    50-host fleet on one core carries ~3200 dispatch threads, 10k
    event-loop wakeups/s and 100 control RPCs/s — load average in the
    four digits, and the head never gets the quantum it needs to
    ANSWER a registration (measured: stand-up dead at 420 s, load
    1191).  ``cpu_count=None`` (the default) applies only the
    fleet-size tier, so calibration stays deterministic for tests."""
    hb = 500 if hosts > 16 else 100
    cfg = {
        "raylet_heartbeat_period_milliseconds": hb,
        "num_heartbeats_suspect": 6,
        "num_heartbeats_timeout": 12,
        "gcs_resource_broadcast_period_milliseconds": max(200, hb),
        "lease_reconcile_grace_s": 2.0,
        "metrics_report_interval_ms": 1000,
    }
    oversub = hosts / max(1, cpu_count or hosts)
    if hosts > 16 and oversub >= 4:
        cfg.update({
            # 2 s beats: liveness grace (6/12 beats -> 12 s/24 s)
            # must dwarf worst-case scheduling delay, not sit inside
            # it — otherwise every GIL stall reads as a death.
            "raylet_heartbeat_period_milliseconds": 2000,
            "gcs_resource_broadcast_period_milliseconds": 2000,
            "metrics_report_interval_ms": 5000,
            # Thread-count hygiene: 8 dispatch threads/host instead
            # of 64, 50 ms ticks instead of 5 ms.
            "rpc_dispatch_pool_size": 8,
            "event_loop_tick_ms": 50,
            # The watchdog must not mistake CPU famine for a wedge.
            "loop_stall_budget_s": 60.0,
            "watchdog_poll_interval_s": 2.0,
        })
    cfg.update(overrides or {})
    return cfg


def chaos_bands(system_config: dict) -> Tuple[tuple, tuple]:
    """Partition duration bands derived from the run's OWN grace
    config: flaps land inside the suspect grace (must cause zero
    restarts — placement pause only), holds straddle the dead grace so
    some nodes get declared dead, come back talking, and are provably
    FENCED (the acceptance criterion's nonzero fence-rejection
    counters)."""
    period_s = system_config["raylet_heartbeat_period_milliseconds"] / 1e3
    suspect_s = period_s * system_config["num_heartbeats_suspect"]
    dead_s = period_s * system_config["num_heartbeats_timeout"]
    flap = (0.25 * suspect_s, 0.8 * suspect_s)
    hold = (1.05 * suspect_s, 1.5 * dead_s)
    return flap, hold


# ---------------------------------------------------------------------------
# The drive.


def run_envelope(hosts: int = 50, cpus_per_host: int = 4,
                 actors: int = 10_000, actor_wave: int = 500,
                 calls_per_actor: int = 1,
                 pgs: int = 1_000, pg_wave: int = 50,
                 broadcasts: Tuple[Tuple[int, int], ...] = ((128, 12),
                                                            (1024, 2)),
                 chaos: bool = True, chaos_seed: int = 6,
                 chaos_events: Optional[int] = None,
                 chaos_window_s: Optional[float] = None,
                 system_config: Optional[dict] = None,
                 stand_up_timeout: float = 240.0,
                 spawn_stagger_s: Optional[float] = None,
                 get_timeout_s: float = 120.0,
                 log=print) -> dict:
    """Run the envelope; returns the result document (also the JSON
    written by :func:`main`).  ``broadcasts`` is ``((size_mib,
    n_consumers), ...)``."""
    import ray_tpu
    from ray_tpu._private.worker import global_worker
    from ray_tpu._private import chaos_schedule
    from ray_tpu.autoscaler.node_provider import (
        LocalProcessProvider, TAG_NODE_KIND, TAG_NODE_TYPE,
        NODE_KIND_WORKER)

    sys_cfg = envelope_system_config(hosts, system_config,
                                     cpu_count=os.cpu_count())
    result: Dict[str, object] = {
        "round": "r06",
        "hosts": hosts,
        "cpus_per_host": cpus_per_host,
        "config": dict(sys_cfg),
        "cpu_count": os.cpu_count() or 1,
        # Honest marking: a fleet of OS processes timesharing fewer
        # cores than hosts measures the CONTROL PLANE's correctness
        # under contention, not per-host throughput.
        "cpu_throttled": (os.cpu_count() or 1) < hosts,
        "phases": {},
        "failures": [],
        "silent_loss": 0,
    }
    phases: Dict[str, dict] = result["phases"]  # type: ignore[assignment]

    t_init = time.monotonic()
    ray_tpu.init(num_cpus=cpus_per_host, _system_config=sys_cfg)
    w = global_worker()
    cluster = w.cluster

    # ---- fleet stand-up (one registration storm) -----------------------
    # On an oversubscribed box (fewer cores than hosts), pace the
    # Popen calls: 50 interpreters booting at the same instant starve
    # the head of the CPU it needs to answer registrations at all.
    # The admission gate still gets its storm — boots complete in
    # overlapping waves — but the head keeps scheduling quanta.
    if spawn_stagger_s is None:
        spawn_stagger_s = 0.25 if (os.cpu_count() or 1) < hosts else 0.0
    log(f"[envelope] standing up {hosts} node hosts "
        f"(spawn stagger {spawn_stagger_s:.2f}s) ...")
    provider = LocalProcessProvider(
        cluster, {"worker": {"resources": {"CPU": float(cpus_per_host)}}})
    handles = provider.create_node(
        {"resources": {"CPU": float(cpus_per_host)}},
        {TAG_NODE_KIND: NODE_KIND_WORKER, TAG_NODE_TYPE: "worker"},
        hosts, timeout=stand_up_timeout,
        spawn_interval_s=spawn_stagger_s)
    cluster.wait_for_nodes(hosts + 1, timeout=stand_up_timeout)
    stand_up_s = time.monotonic() - t_init
    phases["stand_up"] = {
        "wall_s": round(stand_up_s, 3),
        "hosts": hosts,
        "spawn_stagger_s": spawn_stagger_s,
        "registrations_deferred":
            cluster.head_service.registrations_deferred,
    }
    log(f"[envelope] fleet up in {stand_up_s:.1f}s "
        f"(registrations deferred: "
        f"{cluster.head_service.registrations_deferred})")

    # ---- chaos ---------------------------------------------------------
    runner = None
    schedule = []
    if chaos:
        if chaos_events is None:
            chaos_events = max(8, hosts // 2)
        if chaos_window_s is None:
            chaos_window_s = 30.0 + hosts * 0.8
        flap, hold = chaos_bands(sys_cfg)
        schedule = chaos_schedule.generate_schedule(
            chaos_seed, chaos_window_s, chaos_events, len(handles),
            flap_band=flap, hold_band=hold)
        runner = chaos_schedule.ChaosRunner(handles, schedule).start()
        log(f"[envelope] chaos armed: {len(schedule)} events over "
            f"{chaos_window_s:.0f}s (seed {chaos_seed})")

    ledger = {"actor_create_ok": 0, "actor_create_failed": 0,
              "actor_calls_ok": 0, "actor_calls_failed": 0,
              "actor_mismatches": 0, "pg_created": 0, "pg_ready": 0,
              "pg_failed": 0, "bcast_ok": 0, "bcast_failed": 0,
              "bcast_mismatches": 0}

    try:
        _drive_actor_waves(ray_tpu, actors, actor_wave, calls_per_actor,
                           get_timeout_s, ledger, result, phases, log)
        _drive_placement_groups(pgs, pg_wave, get_timeout_s, ledger,
                                result, phases, log)
        _drive_broadcasts(ray_tpu, cluster, broadcasts, get_timeout_s,
                          ledger, result, phases, log)
        if runner is not None:
            # Let the schedule finish firing (bounded): the soak's
            # evidence is events that FIRED, not events scheduled.
            deadline = time.monotonic() + (chaos_window_s or 0) + 10.0
            while runner._thread.is_alive() and \
                    time.monotonic() < deadline:
                time.sleep(0.25)
    finally:
        if runner is not None:
            runner.stop()

    # ---- evidence ------------------------------------------------------
    result["ledger"] = ledger
    result["silent_loss"] = (ledger["actor_mismatches"] +
                            ledger["bcast_mismatches"])
    result["latency"] = \
        cluster.gcs.task_event_manager.latency_summary()
    if runner is not None:
        result["chaos"] = {
            "seed": chaos_seed,
            "scheduled": len(schedule),
            "fired": runner.events_fired,
            "skipped": runner.events_skipped,
            "event_log": runner.event_log,
        }
    result["degradation"] = _collect_degradation(cluster, handles)
    result["membership"] = _membership_rollup(cluster)
    phases["total"] = {"wall_s": round(time.monotonic() - t_init, 3)}
    return result


def _drive_actor_waves(ray_tpu, actors, wave, calls_per_actor,
                       get_timeout_s, ledger, result, phases, log):
    Act = ray_tpu.remote(_EnvelopeActor)
    t0 = time.monotonic()
    created_total = 0
    while created_total < actors:
        n = min(wave, actors - created_total)
        base = created_total
        created_total += n
        live = []
        for k in range(n):
            token = base + k
            try:
                live.append((token, Act.remote(token)))
            except Exception as e:
                ledger["actor_create_failed"] += 1
                result["failures"].append(
                    {"op": "actor_create", "token": token,
                     "error": f"{type(e).__name__}: {e}"})
        refs = []
        for token, a in live:
            per = []
            for c in range(calls_per_actor):
                try:
                    per.append((c + 1, a.echo.remote(token + c)))
                except Exception as e:
                    ledger["actor_calls_failed"] += 1
                    result["failures"].append(
                        {"op": "actor_call", "token": token,
                         "error": f"{type(e).__name__}: {e}"})
            refs.append((token, a, per))
        for token, a, per in refs:
            ok = True
            for seq, ref in per:
                try:
                    got = ray_tpu.get(ref, timeout=get_timeout_s)
                except Exception as e:
                    ok = False
                    ledger["actor_calls_failed"] += 1
                    result["failures"].append(
                        {"op": "actor_call", "token": token,
                         "error": f"{type(e).__name__}: {e}"})
                    continue
                if got != (token, token + seq - 1, seq):
                    ledger["actor_mismatches"] += 1
                    result["failures"].append(
                        {"op": "actor_call", "token": token,
                         "error": "SILENT LOSS: value mismatch",
                         "got": repr(got)})
                else:
                    ledger["actor_calls_ok"] += 1
            if ok:
                ledger["actor_create_ok"] += 1
            try:
                ray_tpu.kill(a)
            except Exception as e:
                # Killing an actor whose node chaos already took is
                # expected; the count still lands in the swallow ledger.
                from ray_tpu._private.debug import swallow
                swallow.noted("envelope.actor_kill", e)
        if (created_total // wave) % 5 == 0:
            log(f"[envelope] actors {created_total}/{actors} "
                f"({time.monotonic() - t0:.0f}s)")
    phases["actors"] = {
        "wall_s": round(time.monotonic() - t0, 3),
        "actors": actors, "wave": wave,
        "calls_per_actor": calls_per_actor,
        "actors_per_s": round(actors / max(1e-9,
                                           time.monotonic() - t0), 1),
    }


def _drive_placement_groups(pgs, wave, get_timeout_s, ledger, result,
                            phases, log):
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)
    strategies = ("PACK", "SPREAD", "STRICT_PACK", "STRICT_SPREAD")
    t0 = time.monotonic()
    created = 0
    while created < pgs:
        n = min(wave, pgs - created)
        batch = []
        for k in range(n):
            strategy = strategies[(created + k) % len(strategies)]
            bundles = [{"CPU": 1}] if "PACK" in strategy \
                else [{"CPU": 1}, {"CPU": 1}]
            try:
                pg = placement_group(bundles, strategy=strategy)
                batch.append((strategy, pg))
                ledger["pg_created"] += 1
            except Exception as e:
                ledger["pg_failed"] += 1
                result["failures"].append(
                    {"op": "pg_create", "strategy": strategy,
                     "error": f"{type(e).__name__}: {e}"})
        for strategy, pg in batch:
            try:
                if pg.wait(timeout_seconds=get_timeout_s):
                    ledger["pg_ready"] += 1
                else:
                    ledger["pg_failed"] += 1
                    result["failures"].append(
                        {"op": "pg_ready", "strategy": strategy,
                         "error": "timeout waiting for placement"})
            except Exception as e:
                ledger["pg_failed"] += 1
                result["failures"].append(
                    {"op": "pg_ready", "strategy": strategy,
                     "error": f"{type(e).__name__}: {e}"})
            try:
                remove_placement_group(pg)
            except Exception as e:
                result["failures"].append(
                    {"op": "pg_remove", "strategy": strategy,
                     "error": f"{type(e).__name__}: {e}"})
        created += n
        if (created // wave) % 5 == 0:
            log(f"[envelope] PGs {created}/{pgs} "
                f"({time.monotonic() - t0:.0f}s)")
    phases["placement_groups"] = {
        "wall_s": round(time.monotonic() - t0, 3),
        "pgs": pgs, "strategies": list(strategies),
        "pgs_per_s": round(pgs / max(1e-9, time.monotonic() - t0), 1),
    }


def _drive_broadcasts(ray_tpu, cluster, broadcasts, get_timeout_s,
                      ledger, result, phases, log):
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)
    consume = ray_tpu.remote(_digest_blob)
    t0 = time.monotonic()
    rows = []
    total_bytes = 0
    for size_mib, consumers in broadcasts:
        block = os.urandom(1024 * 1024)
        data = block * size_mib
        want = hashlib.sha256(data).hexdigest()
        t1 = time.monotonic()
        ref = ray_tpu.put(data)
        del data
        # Spread consumers across ALIVE remote nodes: relay chains form
        # between them (PR 12), the origin serves O(size).
        nodes = [n for n in cluster.raylets()
                 if getattr(n, "is_remote_proxy", False)]
        refs = []
        for i in range(consumers):
            node = nodes[i % len(nodes)] if nodes else None
            opts = {}
            if node is not None:
                opts["scheduling_strategy"] = \
                    NodeAffinitySchedulingStrategy(node.node_id.hex(),
                                                   soft=True)
            refs.append(consume.options(**opts).remote(ref))
        ok = failed = mism = 0
        for r in refs:
            try:
                got = ray_tpu.get(r, timeout=get_timeout_s)
            except Exception as e:
                failed += 1
                result["failures"].append(
                    {"op": "broadcast", "size_mib": size_mib,
                     "error": f"{type(e).__name__}: {e}"})
                continue
            if got != want:
                mism += 1
                result["failures"].append(
                    {"op": "broadcast", "size_mib": size_mib,
                     "error": "SILENT LOSS: digest mismatch",
                     "got": got, "want": want})
            else:
                ok += 1
        wall = time.monotonic() - t1
        moved = size_mib * 1024 * 1024 * ok
        total_bytes += moved
        ledger["bcast_ok"] += ok
        ledger["bcast_failed"] += failed
        ledger["bcast_mismatches"] += mism
        rows.append({"size_mib": size_mib, "consumers": consumers,
                     "ok": ok, "failed": failed, "mismatches": mism,
                     "wall_s": round(wall, 3),
                     "gib_per_s": round(moved / max(1e-9, wall) / 1024**3,
                                        3)})
        log(f"[envelope] broadcast {size_mib} MiB -> {consumers}: "
            f"{ok} ok, {failed} failed in {wall:.1f}s")
        try:
            del ref
        except Exception:
            pass
    phases["broadcast"] = {
        "wall_s": round(time.monotonic() - t0, 3),
        "rows": rows,
        "total_gib": round(total_bytes / 1024**3, 3),
    }


def _collect_degradation(cluster, handles) -> dict:
    """Per-fix counters — the degradation fixes' before/after evidence
    read straight from the structures, not from the (sheddable)
    metrics plane."""
    from ray_tpu._private.debug import watchdog
    head = cluster.head_service
    coalesced = sent = 0
    for r in cluster.raylets():
        if getattr(r, "is_remote_proxy", False):
            coalesced += getattr(r, "broadcasts_coalesced", 0)
            sent += getattr(r, "broadcasts_sent", 0)
    obs = {"metrics_sheds": 0, "timeline_windows_shed": 0,
           "worker_startup_throttled": 0, "nodes_polled": 0}
    for h in handles:
        proxy = h.proxy
        if proxy is None or h.proc.poll() is not None:
            continue
        try:
            stats = proxy.client.call("observability_stats", None,
                                      timeout=5.0)
        except Exception:
            continue
        obs["nodes_polled"] += 1
        for k in ("metrics_sheds", "timeline_windows_shed",
                  "worker_startup_throttled"):
            obs[k] += int(stats.get(k, 0))
    return {
        "registration_admission": {
            "deferred": head.registrations_deferred,
        },
        "broadcast_coalescing": {
            "sent": sent, "coalesced": coalesced,
        },
        "heartbeat_shedding": obs,
        "wedge_files_dropped": watchdog.crash_files_dropped(),
    }


def _membership_rollup(cluster) -> dict:
    nm = cluster.gcs.node_manager
    fenced = {nid.hex()[:12]: dict(v)
              for nid, v in nm.fence_rejections.items() if v}
    return {
        "alive": len(nm.alive_nodes),
        "dead": len(nm.dead_nodes),
        "fence_rejections_total": sum(
            sum(v.values()) for v in nm.fence_rejections.values()),
        "fence_rejections": fenced,
    }


# ---------------------------------------------------------------------------
# CLI.


def _parse_broadcasts(specs: List[str]) -> Tuple[Tuple[int, int], ...]:
    out = []
    for s in specs:
        size, _, cons = s.partition(":")
        out.append((int(size), int(cons) if cons else 4))
    return tuple(out)


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog="ray-tpu envelope",
        description="Cluster-scale envelope / chaos soak driver.")
    p.add_argument("--hosts", type=int, default=50)
    p.add_argument("--cpus-per-host", type=int, default=4)
    p.add_argument("--actors", type=int, default=10_000)
    p.add_argument("--actor-wave", type=int, default=500)
    p.add_argument("--calls-per-actor", type=int, default=1)
    p.add_argument("--pgs", type=int, default=1_000)
    p.add_argument("--pg-wave", type=int, default=50)
    p.add_argument("--broadcast", action="append", default=None,
                   metavar="MIB[:CONSUMERS]",
                   help="repeatable; default 128:12 and 1024:2")
    p.add_argument("--no-chaos", action="store_true")
    p.add_argument("--chaos-seed", type=int, default=6)
    p.add_argument("--chaos-events", type=int, default=None)
    p.add_argument("--chaos-window-s", type=float, default=None)
    p.add_argument("--get-timeout-s", type=float, default=120.0)
    p.add_argument("--stand-up-timeout", type=float, default=240.0)
    p.add_argument("--spawn-stagger-s", type=float, default=None,
                   help="seconds between node-host spawns during "
                        "stand-up (default: auto — 0.25 when the box "
                        "has fewer cores than hosts, else 0)")
    p.add_argument("--config", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="system-config override on top of the "
                        "fleet-size calibration (repeatable; values "
                        "parsed as JSON, falling back to string)")
    p.add_argument("--out", default="envelope.json")
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args(argv)

    broadcasts = _parse_broadcasts(args.broadcast) \
        if args.broadcast else ((128, 12), (1024, 2))
    overrides = {}
    for kv in args.config:
        key, _, raw = kv.partition("=")
        try:
            overrides[key] = json.loads(raw)
        except ValueError:
            overrides[key] = raw
    log = (lambda *_a, **_k: None) if args.quiet \
        else (lambda *a: print(*a, file=sys.stderr, flush=True))
    import ray_tpu
    try:
        result = run_envelope(
            hosts=args.hosts, cpus_per_host=args.cpus_per_host,
            actors=args.actors, actor_wave=args.actor_wave,
            calls_per_actor=args.calls_per_actor,
            pgs=args.pgs, pg_wave=args.pg_wave,
            broadcasts=broadcasts,
            chaos=not args.no_chaos, chaos_seed=args.chaos_seed,
            chaos_events=args.chaos_events,
            chaos_window_s=args.chaos_window_s,
            system_config=overrides or None,
            get_timeout_s=args.get_timeout_s,
            stand_up_timeout=args.stand_up_timeout,
            spawn_stagger_s=args.spawn_stagger_s,
            log=log)
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1, default=str)
        log(f"[envelope] wrote {args.out}")
    # One summary JSON line on stdout — the contract bench_runtime's
    # subprocess harness parses.
    summary = {
        "envelope": {
            "hosts": result["hosts"],
            "actors": result["ledger"]["actor_create_ok"],
            "pgs_ready": result["ledger"]["pg_ready"],
            "broadcast_gib":
                result["phases"]["broadcast"]["total_gib"],
            "chaos_fired": result.get("chaos", {}).get("fired", 0),
            "failures": len(result["failures"]),
            "silent_loss": result["silent_loss"],
            "cpu_throttled": result["cpu_throttled"],
            "wall_s": result["phases"]["total"]["wall_s"],
        }
    }
    print(json.dumps(summary), flush=True)
    return 0 if result["silent_loss"] == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
