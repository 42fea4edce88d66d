"""The decoder-hybrid-decoder driver at a tiny size on the CPU: a whole
run ends in a well-formed result that is correct; two controls, put in
the program's place, come out not correct; the committed cell's files
say what ISSUE 40 fixed; the five new readers read a hand-made trace.

Run from the repository's root: ``python -m pytest benchmarks/tests -q``.
"""

import json
import math
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.costs import sambay_decoder as costs  # noqa: E402
from benchmarks.drivers import trainer_sambay_steps as driver  # noqa: E402
from benchmarks.harness import compare  # noqa: E402

NAME = "train-phi4-mini-flash.pack16k"
# Layers 14-19 of 32 at a tiny width: 8 query heads of 8 on 4 K/V heads,
# a window of 8, 128 channels of 4 states in chunks of 8.
CONFIG = {
    "name": "tiny", "architecture": "sambay_decoder",
    "reference": "sambay_decoder", "costs": "sambay_decoder",
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 8,
    "num_key_value_heads": 4, "layer_norm_eps": 1e-5, "mb_per_layer": 2,
    "num_hidden_layers": 6, "sliding_window": 8,
    "tie_word_embeddings": True, "vocab_size": 128,
    "layer_indices": [14, 15, 16, 17, 18, 19],
    "mamba_d_inner": 128, "mamba_d_state": 4, "mamba_d_conv": 4,
    "mamba_dt_rank": 4, "mamba_dt_min": 0.001, "mamba_dt_max": 0.1,
    "lambda_std": 0.1, "ssm_chunk": 8, "initializer_range": 0.02,
    "dtype": "float32", "remat": True,
    "published": {"num_hidden_layers": 32, "vocab_size": 1024},
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 3, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
# float32 program against the float32 reference: summation order (3e-6
# read); the cell's own limits (bfloat16 program) are read on the chip
# and live in its workload file.
CELL = {"name": "tiny.pack", "config": "tiny", "traffic": "pack",
        "driver": "trainer_sambay_steps", "chips": 1,
        "check": {"steps": 2, "limits": {
            "grad1_norm_gap": 1e-3, "change_norm_gap": 2e-3,
            "ssm_rule_gap": 1e-4, "ssm_rule_grad_gap": 1e-4,
            "compiles_in_window": 0, "nonfinite_losses": 0,
            # on the CPU the scan is the jnp one, and says so; the
            # committed cell allows none
            "ssm_scan_fallback_passes": 1}}}
BENCHMARK = {
    "end_to_end": [
        {"name": "train_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "train_step_p50_ms", "unit": "ms",
         "moves": "train_tokens_per_s"},
        {"name": "step_mfu", "unit": "%", "moves": "train_tokens_per_s"}],
}


def test_a_run_ends_in_a_wellformed_correct_result(tmp_path, capfd):
    out = bench_run.run_cell(CELL, CONFIG, TRAFFIC, BENCHMARK,
                             seed=2**31 + 17, seconds=0.2, trace=False,
                             work_dir=str(tmp_path), t0=time.perf_counter())
    line = json.loads(json.dumps(out))
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] > 0 and line["attempted"] % (2 * 32) == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["compared"]) == set(CELL["check"]["limits"])
    assert line["compared"]["ssm_scan_fallback_passes"]["value"] == 1
    err = capfd.readouterr().err
    facts = json.loads([l for l in err.splitlines()
                        if l.startswith('{"setup_s"')][0])["facts"]
    assert 0.001 < facts["ssm_delta_mean"] < 0.1
    # lambda of the three differential layers, their mean: it moves
    assert 0.7 < facts["diff_lambda_first"] < 0.9
    assert facts["diff_lambda_first"] != facts["diff_lambda_last"]


def _in_the_programs_place(seed, batches, **how):
    """A run's result as ``check`` takes it, with the reference under
    ``how`` where the program's numbers would be."""
    control = driver.follow_reference(CELL, CONFIG, seed, batches, **how)
    return {"program": control, "first_batches": batches,
            "counts": {"compiles_in_window": 0, "nonfinite_losses": 0,
                       "ssm_scan_fallback_passes": 0.0}}


@pytest.mark.parametrize("how", [dict(state="bfloat16"),
                                 dict(memory_from=14)])
def test_the_control_in_the_programs_place_is_not_correct(how):
    """Through ``check`` and ``judge``, as a run goes: a scan state kept
    in bfloat16 (told by the rule alone) and the unit gating layer 14's
    output for layer 16's (told by the gradients' norms)."""
    seed = 12345
    batches = list(driver.traffic_mod.generate(TRAFFIC, seed,
                                               vocab_size=128)[:2])
    limits = CELL["check"]["limits"]
    honest = _in_the_programs_place(seed, batches)
    correct, compared = compare.judge(
        driver.check(CELL, CONFIG, seed, honest), limits)
    assert correct is True, compared
    result = _in_the_programs_place(seed, batches, **how)
    correct, compared = compare.judge(
        driver.check(CELL, CONFIG, seed, result), limits)
    assert correct is False, compared
    told_by = ("ssm_rule_gap", "ssm_rule_grad_gap") if "state" in how \
        else ("grad1_norm_gap",)
    for name in told_by:
        assert compared[name]["value"] > limits[name], compared


def test_the_committed_cell_is_what_the_issue_fixed():
    cell, config, traffic = bench_run.load_cell(NAME)
    assert cell["traffic"] == "pack16k" and cell["check"]["steps"] == 2
    assert cell["driver"] == "trainer_sambay_steps" and cell["chips"] == 1
    assert traffic == dict(traffic, kind="packed_documents", rows=1,
                           seq_len=16384, pool_batches=16, bos_id=0,
                           doc_len={"alpha": 1.2, "min": 64, "max": 32768})
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["layer_indices"]) == (6, 25008, [14, 15, 16, 17, 18, 19])
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 200064}
    # every number of the catalog row's config, but the two reduced
    published = {"embd_pdrop": 0, "hidden_size": 2560,
                 "intermediate_size": 10240, "layer_norm_eps": 1e-05,
                 "max_position_embeddings": 262144, "mb_per_layer": 2,
                 "num_attention_heads": 40, "num_key_value_heads": 20,
                 "resid_pdrop": 0, "sliding_window": 512}
    assert {k: config[k] for k in published} == published
    assert config["tie_word_embeddings"] is True
    assert len(config["assumed"]) >= 10 and config["deployment"]
    # ISSUE 40's table, from the shapes the weight maker hands the
    # program
    import jax
    from benchmarks.harness import sambay_weights
    shapes = sambay_weights.sambay_shapes(config)

    def count(tree):
        return sum(math.prod(shape) for shape, _ in jax.tree.leaves(
            tree, is_leaf=sambay_weights._is_leaf))

    assert [count(layer) for layer in shapes["layers"]] == [
        119_895_040, 98_322_304, 119_895_040, 98_322_304, 104_867_840,
        91_766_144]
    assert count(shapes["layers"]) == 633_068_672
    assert count(shapes) == 697_094_272
    plan = sambay_weights.layer_plan(config)
    assert [(e["kind"], e["window"], e["writes"], e["reads"])
            for e in plan] == [
        ("mamba", None, None, None), ("diff", 512, None, None),
        ("mamba", None, "memory", None), ("diff", None, "kv", None),
        ("gmu", None, None, "memory"), ("diff", None, None, "kv")]
    assert [costs.mixer_matmul_params(config, e) for e in plan] == [
        41_241_600 - 20_480 - 5_120 * 3 - 81_920, 19_668_864 - 7_680 - 384,
        41_241_600 - 20_480 - 5_120 * 3 - 81_920, 19_668_864 - 7_680 - 384,
        26_214_400, 13_112_704 - 5_120 - 384]
    kwargs = driver._model_kwargs(config, traffic["seq_len"])
    assert kwargs["layer_pattern"] == (
        ("mamba", "dense", 1), ("diff:window=512", "dense", 1),
        ("mamba:writes=memory", "dense", 1), ("diff:writes=kv", "dense", 1),
        ("gmu", "dense", 1), ("diff:reads=kv", "dense", 1))
    assert (kwargs["first_layer_index"], kwargs["norm"], kwargs["rope"],
            kwargs["tie_embeddings"]) == (14, "layernorm", "none", True)
    # the required operations: 4.18 GFLOP a token in matrices, 0.78 in
    # the attention kernels (the window 0.024), some 81 TFLOP a step
    per_token = costs.train_flops_per_token(config, 16384)
    pairs = 3.0 * costs.pair_flops(config)
    window = pairs * costs.attention_pairs(16384, 512) / 16384
    full = pairs * costs.attention_pairs(16384, None) / 16384
    assert window == pytest.approx(0.0233e9, rel=1e-2)
    assert 2 * full == pytest.approx(0.755e9, rel=1e-2)
    scans = 2 * 3.0 * costs.scan_flops_per_token(config)
    assert per_token - window - 2 * full - scans == pytest.approx(
        4.18e9, rel=5e-3)
    assert per_token * 16384 == pytest.approx(81e12, rel=2e-2)
    # the SwiGLUs: 57% of it
    assert 6 * 6 * 3 * 2560 * 10240 / per_token == pytest.approx(0.57,
                                                                 abs=0.01)
    # 2.7 G state updates a step forward in the two scans
    assert 2 * 16384 * 5120 * 16 == pytest.approx(2.7e9, rel=1e-2)
    # six attention calls a step each way: the band's two, the
    # triangles' four; the backward's operations twice the forward's
    fwd = costs.flash_step_cost(config, 1, 16384, backward=False)
    bwd = costs.flash_step_cost(config, 1, 16384, backward=True)
    assert len(fwd) == len(bwd) == 6
    assert fwd[0]["flops"] == 20 * 384.0 * costs.attention_pairs(16384, 512)
    assert fwd[2]["flops"] == 20 * 384.0 * (16384 * 16385 // 2)
    assert [b["flops"] for b in bwd] == [2 * f["flops"] for f in fwd]
    # the scan: 0.5 GB forward against the table's peaks, memory-bound
    one = costs.scan_call_cost(config, 1, 16384, backward=False)
    assert one["bytes"] == pytest.approx(3 * 16384 * 5120 * 2, rel=1e-2)
    assert one["flops"] / 197e12 < one["bytes"] / 819e9
    # BENCHMARK.json names the cell, and the five readers name only it:
    # a subset check, so that a later cell's entries do not fail it
    benchmark = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert NAME in [w["name"] for w in benchmark["workloads"]]
    mine = {m["name"] for m in benchmark["per_layer"]
            if m.get("workloads") == [NAME]}
    assert mine >= {"ssm_scan_fwd_roofline", "ssm_scan_bwd_roofline",
                    "diff_flash_fwd_roofline", "diff_flash_bwd_roofline",
                    "ssm_layers_ms"}
    reported = {m["name"] for m in bench_run.metrics_of(
        benchmark, NAME, "per_layer")}
    assert reported >= mine | {
        "step_mfu", "train_step_p50_ms", "step_attributed_pct",
        "remat_recompute_ms", "attn_proj_ms", "attn_kernels_ms", "ffn_ms",
        "head_loss_ms"}
    assert not reported & {"delta_layers_ms", "experts_ms",
                           "gdn_fwd_roofline", "flash_fwd_roofline"}


def test_the_new_readers_read_a_hand_made_trace_and_nothing_elsewhere():
    """Events of the four kernels at round times: the scan's share is
    one call's least time over an event's, the flash share the step's
    six calls' over the events' time a step; a trace without them, or
    another configuration's file: nothing, and no raise."""
    _, config, _ = bench_run.load_cell(NAME)
    _, other, _ = bench_run.load_cell("train-qwen3-next.pack8k")
    flash = [["flash_attention_fwd", i * 1e7, 9e6] for i in range(6)] + \
        [["flash_attention_bwd", 1e8 + i * 2e7, 18e6] for i in range(6)]
    scans = [["selective_scan_fwd", 3e8, 8e6], ["selective_scan_fwd", 4e8,
                                                8e6],
             ["selective_scan_bwd", 5e8, 17e6]]
    names = ("ssm_scan_fwd_roofline", "ssm_scan_bwd_roofline",
             "diff_flash_fwd_roofline", "diff_flash_bwd_roofline")

    def read(ops, cfg):
        ctx = {"trace": {"device_ops": {"/device:TPU:0": ops},
                         "host_spans": []},
               "config": cfg, "device_kind": "TPU v5 lite",
               "facts": {"rows": 1, "seq_len": 16384, "steps": 1}}
        return [bench_run._reader(name)(ctx) for name in names]

    assert read(flash + scans, other) == [None] * 4
    assert read([], config) == [None] * 4
    s_fwd, s_bwd, f_fwd, f_bwd = read(flash, config)
    assert s_fwd is None and s_bwd is None and 0 < f_fwd <= 100 \
        and 0 < f_bwd <= 100
    got = read(flash + scans, config)
    assert all(0 < x <= 100 for x in got), got
    # 0.5 GB at 819 GB/s over 8 ms: 7.7% of the (memory) roofline
    one = costs.scan_call_cost(config, 1, 16384, False)
    assert got[0] == pytest.approx(100 * one["bytes"] / 819e9 / 8e-3,
                                   rel=1e-6)
    # the six forward calls' FLOPs at the peak over 54 ms
    flops = sum(c["flops"] for c in costs.flash_step_cost(config, 1, 16384,
                                                          False))
    assert got[2] == pytest.approx(100 * flops / 197e12 / 54e-3, rel=1e-2)
    # ssm_layers_ms: nothing without the program's manifest
    ctx = {"trace": {"device_ops": {"/device:TPU:0": scans},
                     "host_spans": []}, "config": config,
           "device_kind": "TPU v5 lite", "facts": {}}
    assert bench_run._reader("ssm_layers_ms")(ctx) is None
