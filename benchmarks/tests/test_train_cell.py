"""The train driver at a tiny size on the CPU: a whole run ends in a
well-formed result that is correct; with the timed path broken
underneath, ``correct`` comes out false; the lower-precision control
comes out not correct.

Run from the repository's root: ``python -m pytest benchmarks/tests -q``.
"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.drivers import trainer_steps  # noqa: E402
from benchmarks.harness import compare  # noqa: E402

CONFIG = {
    "name": "tiny", "architecture": "dense_decoder",
    "reference": "dense_decoder", "costs": "dense_decoder",
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 128, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "initializer_range": 0.02, "dtype": "float32", "remat": True,
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
TRAFFIC = {"kind": "packed_documents", "rows": 4, "seq_len": 32,
           "pool_batches": 4, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 1}
# float32 program against the float32 reference: only summation order
# differs, so the limits here are tight; the cell's own limits (bfloat16
# program) are read on the chip and live in its workload file.
CELL = {"name": "tiny.pack", "config": "tiny", "traffic": "pack",
        "driver": "trainer_steps", "chips": 1,
        "check": {"steps": 3, "limits": {
            "grad1_norm_gap": 1e-3,
            "change_norm_gap": 1e-3, "compiles_in_window": 0,
            "nonfinite_losses": 0}}}
BENCHMARK = {
    "end_to_end": [
        {"name": "train_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "train_step_p50_ms", "unit": "ms",
         "moves": "train_tokens_per_s"},
        {"name": "step_mfu", "unit": "%", "moves": "train_tokens_per_s"},
        {"name": "flash_fwd_roofline", "unit": "%",
         "moves": "train_tokens_per_s", "workloads": ["tiny.pack"]}],
}


def _run(tmp_path, seed=2**31 + 11, trace=False):
    import time
    return bench_run.run_cell(CELL, CONFIG, TRAFFIC, BENCHMARK, seed=seed,
                              seconds=0.2, trace=trace,
                              work_dir=str(tmp_path), t0=time.perf_counter())


def test_a_run_ends_in_a_wellformed_correct_result(tmp_path, capfd):
    out = _run(tmp_path)
    line = json.loads(json.dumps(out))
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % (4 * 32) == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    assert set(line["compared"]) == {
        "grad1_norm_gap", "change_norm_gap", "compiles_in_window",
        "nonfinite_losses"}
    for name in line["compared"]:
        assert line["compared"][name]["value"] <= \
            line["compared"][name]["limit"]
    err = capfd.readouterr().err.splitlines()
    assert [l.split(":")[0] for l in err[-4:]] == [
        "compared grad1_norm_gap", "compared change_norm_gap",
        "compared compiles_in_window", "compared nonfinite_losses"]
    assert any("not_compared_loss_gaps" in l for l in err)


def _broken_step(kind):
    from ray_tpu.models import transformer
    real = transformer.make_train_step

    def make(cfg, tx, mesh=None, loss_override=None):
        step = real(cfg, tx, mesh, loss_override)
        import jax

        def unchanged(state, batch):
            copy_ = jax.tree.map(lambda a: a + 0, state)
            _, metrics = step(state, batch)
            return copy_, metrics

        def half_batch(state, batch):
            rows = batch["tokens"].shape[0] // 2
            return step(state, {"tokens": batch["tokens"][:rows]})

        return {"unchanged": unchanged, "half_batch": half_batch}[kind]

    return make


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    from ray_tpu.models import transformer
    monkeypatch.setattr(transformer, "make_train_step", _broken_step(fault))
    out = _run(tmp_path)
    assert out["correct"] is False
    failed = {k for k, c in out["compared"].items()
              if c["value"] > c["limit"]}
    want = {"unchanged": "change_norm_gap", "half_batch": "grad1_norm_gap"}
    assert want[fault] in failed
    if fault == "unchanged":
        # a state that is returned unchanged reads 1 by this measure
        assert out["compared"]["change_norm_gap"]["value"] == \
            pytest.approx(1.0)


@pytest.mark.parametrize("how", [dict(precision="fp8"),
                                 dict(batch_rows=[0, 1])])
def test_the_control_in_the_programs_place_is_not_correct(how):
    """The reference, put in the program's place and computed in the
    nearest precision below the configuration's (or on half the batch),
    fails at least one number."""
    from benchmarks.harness import traffic
    seed = 12345
    batches = traffic.generate(TRAFFIC, seed, vocab_size=128)[:3]
    ref = trainer_steps.follow_reference(CELL, CONFIG, seed, batches)
    control = trainer_steps.follow_reference(CELL, CONFIG, seed, batches,
                                             **how)
    numbers = compare.train_numbers(control, ref)
    correct, compared = compare.judge(
        numbers, dict(CELL["check"]["limits"]))
    assert correct is False, compared


def test_the_reference_in_its_own_place_is_correct():
    from benchmarks.harness import traffic
    batches = traffic.generate(TRAFFIC, 7, vocab_size=128)[:3]
    ref = trainer_steps.follow_reference(CELL, CONFIG, 7, batches)
    again = copy.deepcopy(ref)
    correct, _ = compare.judge(compare.train_numbers(again, ref),
                               CELL["check"]["limits"])
    assert correct is True


def test_a_new_cell_is_new_files_and_entries_only(tmp_path):
    """A later PR adds a cell by adding a workload, a configuration and
    a traffic file (and entries in BENCHMARK.json): the harness finds
    them by name and no existing file is edited."""
    for folder, name, body in (("workloads", "tiny.pack", CELL),
                               ("configs", "tiny", CONFIG),
                               ("traffic", "pack", TRAFFIC)):
        (tmp_path / folder).mkdir()
        (tmp_path / folder / (name + ".json")).write_text(json.dumps(body))
    cell, config, traffic = bench_run.load_cell("tiny.pack", str(tmp_path))
    assert (cell, config, traffic) == (CELL, CONFIG, TRAFFIC)
    names = [m["name"] for m in bench_run.metrics_of(
        BENCHMARK, "tiny.pack", "per_layer")]
    assert names == ["train_step_p50_ms", "step_mfu", "flash_fwd_roofline"]
    assert [m["name"] for m in bench_run.metrics_of(
        BENCHMARK, "another.cell", "per_layer")] == [
            "train_step_p50_ms", "step_mfu"]


def test_the_committed_benchmark_file_names_files_that_exist():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    here = os.path.join(ROOT, "benchmarks")
    for w in bench["workloads"]:
        cell, config, traffic = bench_run.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert os.path.exists(os.path.join(
            here, "drivers", cell["driver"] + ".py"))
        assert os.path.exists(os.path.join(
            here, "reference", config["reference"] + ".py"))
        for m in bench_run.metrics_of(bench, w["name"], "per_layer"):
            assert os.path.exists(os.path.join(
                here, "layer_metrics", m["name"] + ".py")), m["name"]
        e2e = [m["name"] for m in bench_run.metrics_of(
            bench, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
    for c in bench["configs"]:
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
