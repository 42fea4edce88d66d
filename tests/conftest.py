"""Shared fixtures (reference: python/ray/tests/conftest.py —
ray_start_regular / ray_start_cluster).

JAX-dependent tests run on a virtual 8-device CPU mesh: the env vars must
be set before jax is first imported, hence at conftest import time.
Multi-chip sharding is validated this way; the real chip is reached only
through the chip tool, starting with ``python chip_smoke.py`` (one
process per chip).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Arm the concurrency witnesses for the WHOLE suite (before any ray_tpu
# import creates a lock): every test doubles as a lock-order probe
# (debug.lock_order raises on cycle formation) and as an event-loop
# affinity probe (@loop_only raises off-loop).  Disable locally with
# RAY_TPU_LOCK_DIAG=0 when bisecting timing-sensitive failures.
os.environ.setdefault("RAY_TPU_LOCK_DIAG", "1")
os.environ.setdefault("RAY_TPU_LOOP_AFFINITY", "1")
# Contention profiling armed suite-wide too: the whole suite proves the
# "always-cheap" claim, and doctor/bench tests read the histograms.
os.environ.setdefault("RAY_TPU_LOCK_CONTENTION", "1")
# Stall watchdog armed suite-wide (watchdog_enabled defaults on): a
# tier-1 run that wedges any event loop / pump thread past the budget
# fails at sessionfinish WITH the wedge report attached, instead of
# timing out opaquely.  60s is far past any legitimate handler; tests
# that wedge deliberately lower the budget via config and
# reset_reports() in teardown.
os.environ.setdefault("RAY_TPU_LOOP_STALL_BUDGET_S", "60")

# graftcheck (tools/graftcheck) is imported by tests/test_graftcheck.py.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


def pytest_sessionfinish(session, exitstatus):
    """A lock-order cycle that formed under a broad except (EventLoop
    handlers print-and-continue, pump loops route through
    swallow.noted) would otherwise pass the suite green — the witness
    keeps every report, so fail the session if any survived.  Tests
    that form cycles deliberately snapshot/restore the graph."""
    try:
        from ray_tpu._private.debug import lock_order
    except Exception:
        return
    reports = lock_order.violations()
    if reports:
        print("\nlock-order witness reports (tier-1 must be cycle-free):",
              flush=True)
        for r in reports:
            print(r, flush=True)
        session.exitstatus = 1
    if os.environ.get("RAY_TPU_LOCK_DIAG_DUMP") == "1":
        print("\nlock acquisition graph (RAY_TPU_LOCK_DIAG_DUMP=1):",
              flush=True)
        for (a, b), prov in sorted(lock_order.graph_edges().items()):
            print(f"  {a} -> {b}\n      {prov}", flush=True)
    # Stall-watchdog gate: a loop wedged past the suite budget during
    # the run is a real finding even if every test passed — surface the
    # wedge report (stalled loop, handler, stacks) instead of letting
    # the next run time out opaquely.  Tests that wedge deliberately
    # call watchdog.reset_reports() in their teardown.
    try:
        from ray_tpu._private.debug import watchdog
    except Exception:
        return
    wedges = watchdog.wedge_reports()
    if wedges:
        print("\nstall-watchdog wedge reports (tier-1 must be "
              "wedge-free):", flush=True)
        for w in wedges:
            print(f"  loop {w.get('loop')} handler {w.get('handler')} "
                  f"stalled {w.get('stalled_for_s')}s "
                  f"(crash file: {w.get('crash_file', '-')})",
                  flush=True)
            for tname, frames in (w.get("stacks") or {}).items():
                if w.get("loop", "") and w["loop"] in tname:
                    for ln in frames[-6:]:
                        print(f"    {ln}", flush=True)
        session.exitstatus = 1
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _config_isolation():
    """Roll back process-global Config mutations after every test.

    Tests tune fields on the singleton (spill thresholds, chunk sizes,
    worker modes); a leaked value silently changes the behavior of every
    later test in the alphabetical run — the classic source of
    order-dependent flakes."""
    import dataclasses

    import ray_tpu._private.config as config_mod
    prev = config_mod._global_config
    snapshot = dataclasses.asdict(prev) if prev is not None else None
    yield
    with config_mod._lock:
        if snapshot is None:
            config_mod._global_config = None
        else:
            for k, v in snapshot.items():
                setattr(prev, k, v)
            config_mod._global_config = prev


@pytest.fixture
def ray_start_regular():
    import ray_tpu
    ctx = ray_tpu.init(num_cpus=4)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2_cpus():
    import ray_tpu
    ctx = ray_tpu.init(num_cpus=2)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """A Cluster the test can add/remove nodes on (cluster_utils parity)."""
    import ray_tpu
    from ray_tpu._private.cluster import Cluster
    created = []

    def factory(**head_args):
        cluster = Cluster(initialize_head=True, head_node_args=head_args)
        created.append(cluster)
        ray_tpu.init(_cluster=cluster)
        return cluster

    yield factory
    ray_tpu.shutdown()
