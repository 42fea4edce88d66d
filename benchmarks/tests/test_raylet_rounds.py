"""The raylet driver at a tiny size on the CPU (8 nodes x 200 entries):
a whole run ends in a well-formed result whose guarantees hold; a reply
vector altered where it is produced comes out not correct."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

BENCHMARK = {"end_to_end": [
    {"name": "sched_placed_per_s", "unit": "tasks/s"},
    {"name": "sched_place_p95_ms", "unit": "ms"},
    {"name": "setup_s", "unit": "s"}], "per_layer": []}


def _tiny():
    cell, config, traffic = bench_run.load_cell("raylet-64.backlog")
    return (cell, dict(config, nodes=8),
            dict(traffic, pending=200, warmup_rounds=2))


def _run(tmp_path):
    cell, config, traffic = _tiny()
    return bench_run.run_cell(cell, config, traffic, BENCHMARK, seed=2**31 + 3,
                              seconds=0.5, trace=False,
                              work_dir=str(tmp_path), t0=time.perf_counter())


def test_a_run_ends_in_a_wellformed_correct_result(tmp_path):
    out = json.loads(json.dumps(_run(tmp_path)))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"sched_placed_per_s",
                                   "sched_place_p95_ms", "setup_s"}
    assert out["metrics"]["sched_placed_per_s"]["value"] > 0
    assert all(c["value"] == 0 for c in out["compared"].values())


def test_an_answer_dropped_where_it_is_produced_is_not_correct(
        tmp_path, monkeypatch):
    from ray_tpu._private.raylet import Raylet
    real = Raylet.request_worker_lease_batch

    def short(self, specs, reply):
        real(self, specs, lambda r: reply(
            {"results": r["results"][:-1]} if len(specs) > 1 else r))

    monkeypatch.setattr(Raylet, "request_worker_lease_batch", short)
    out = _run(tmp_path)
    assert out["correct"] is False
    assert out["compared"]["unanswered_or_twice"]["value"] > 0
