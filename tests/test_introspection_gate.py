"""Tier-1 wiring of the introspection-overhead regression gate
(ISSUE 17 satellite): every future hot-path change is GATED on the
armed/unarmed dispatch-p99 ratio staying <= 1.10 with stage-count
parity, not just benched after the fact.

The gate itself (``bench_runtime.py --introspection-gate``) runs both
arms as fresh subprocesses, min-of-k per arm (1-core CI runners bounce
3-27 ms at this percentile).  Tier-1 holds what is exact: the row of one
run at a small burst, and the gate's logic on stubbed arms; the measured
ratio is asserted by the test marked ``slow``.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "bench_runtime.py")


def _bench_module():
    sys.path.insert(0, _REPO)
    try:
        import bench_runtime
    finally:
        sys.path.remove(_REPO)
    return bench_runtime


def _clean_env():
    # The suite-wide conftest arms lock diagnostics in THIS process;
    # the gate's subprocess arms control their own arming and must not
    # inherit it.
    env = dict(os.environ)
    for k in ("RAY_TPU_LOCK_DIAG", "RAY_TPU_LOCK_CONTENTION",
              "RAY_TPU_LOOP_AFFINITY", "RAY_TPU_LOOP_STALL_BUDGET_S"):
        env.pop(k, None)
    return env


def _run_gate(samples, retries):
    """``bench_runtime.py --introspection-gate`` at its smallest burst
    -> (the completed process, its ``introspection_gate`` row or None)."""
    out = subprocess.run(
        [sys.executable, _BENCH, "--introspection-gate", "--n", "150",
         "--gate-samples", str(samples), "--gate-retries", str(retries)],
        capture_output=True, text=True, timeout=540,
        env=_clean_env(), cwd=_REPO)
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if cand.get("metric") == "introspection_gate":
            return out, cand
    return out, None


def test_introspection_gate_row_is_exact_whatever_the_clock_said():
    """The gate run once, one fresh process an arm: what holds whatever
    the host's clock read -- a well-formed row (the exit code is the
    row's ``passed``), stage parity in the attempt, the striped hot-path
    locks visible to the contention profiler (the ISSUE 17 reduction is
    measured on exactly these rollups).  The measured half, the ratio
    itself, is ``test_introspection_gate_passes`` (``slow``); its logic
    is held by the two stubbed tests below."""
    out, row = _run_gate(samples=1, retries=0)
    assert row is not None, (out.stdout[-2000:], out.stderr[-2000:])
    assert out.returncode == (0 if row["passed"] else 1)
    assert (row["n"], row["max_ratio"], row["unit"]) == (150, 1.10, "ratio")
    (attempt,) = row["attempts"]
    assert len(attempt["armed_runs_ms"]) == len(attempt["unarmed_runs_ms"]) == 1
    assert attempt["armed_p99_ms"] > 0 and attempt["unarmed_p99_ms"] > 0
    assert attempt["stage_parity"] is True
    striped = row.get("striped_locks") or {}
    assert "TaskEventBuffer._lock" in striped
    assert "ReferenceCounter._lock" in striped


@pytest.mark.slow
def test_introspection_gate_passes():
    """rc=0 and ratio <= 1.10 with parity: a p99 ratio of host latencies,
    which a shared box fails about one whole run in two (ROADMAP D17), so
    not tier-1's to assert.  One extra whole-gate retry on top of the
    gate's internal rounds -- compounded, a flake needs ~6 consecutive
    unlucky min-of-3 draws."""
    for _ in range(2):
        out, row = _run_gate(samples=3, retries=2)
        if out.returncode == 0:
            break
    assert out.returncode == 0, (
        f"introspection gate failed:\n{out.stdout[-3000:]}\n"
        f"{out.stderr[-2000:]}")
    assert row["passed"] is True
    assert row["attempts"][-1]["ratio"] <= row["max_ratio"]
    assert row["attempts"][-1]["stage_parity"] is True


def test_gate_trips_on_broken_stage_parity(monkeypatch):
    """The parity half of the gate: an arm whose stages disagree on
    sample counts fails the attempt even at a perfect ratio."""
    bench_runtime = _bench_module()
    armed_row = json.dumps({
        "metric": "dispatch_latency_introspection_armed", "value": 5.0,
        "stages": {"queue_wait": {"count": 150},
                   "total": {"count": 149}}})     # <-- coverage gap
    off_row = json.dumps({
        "metric": "task_dispatch_latency_p99", "value": 5.0,
        "stages": {"queue_wait": {"count": 150},
                   "total": {"count": 150}}})

    class FakeCompleted:
        returncode = 0
        stderr = ""

        def __init__(self, stdout):
            self.stdout = stdout

    def fake_run(cmd, **kw):
        armed = "--introspection-bench" in cmd
        return FakeCompleted((armed_row if armed else off_row) + "\n")

    # The gate imports the stdlib subprocess module inside the
    # function, so patching the module attribute reaches it.
    monkeypatch.setattr(subprocess, "run", fake_run)
    row = bench_runtime.bench_introspection_gate(
        n=150, retries=0, samples=1)
    assert row["passed"] is False
    assert row["attempts"][-1]["stage_parity"] is False


def test_gate_trips_on_ratio(monkeypatch):
    """The ratio half: armed/unarmed above max_ratio fails even with
    clean parity."""
    bench_runtime = _bench_module()

    def row(metric, value):
        return json.dumps({
            "metric": metric, "value": value,
            "stages": {"queue_wait": {"count": 150},
                       "total": {"count": 150}}}) + "\n"

    class FakeCompleted:
        returncode = 0
        stderr = ""

        def __init__(self, stdout):
            self.stdout = stdout

    def fake_run(cmd, **kw):
        if "--introspection-bench" in cmd:
            return FakeCompleted(
                row("dispatch_latency_introspection_armed", 12.0))
        return FakeCompleted(row("task_dispatch_latency_p99", 5.0))

    monkeypatch.setattr(subprocess, "run", fake_run)
    gate = bench_runtime.bench_introspection_gate(
        n=150, retries=1, samples=2)
    assert gate["passed"] is False
    assert gate["attempts"][-1]["ratio"] == 2.4
    assert len(gate["attempts"]) == 2           # retries exhausted
