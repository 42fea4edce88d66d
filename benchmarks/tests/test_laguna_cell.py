"""The driver of the decoder whose attention layers are of two kinds, at
a tiny size on the CPU: a whole run ends in a well-formed result that is
correct; two controls, put in the program's place, come out not correct;
the committed cell's files say what ISSUE 42 fixed; the costs module's
pair counts against a brute-force mask; the three new readers read a
hand-made trace.

Run from the repository's root: ``python -m pytest benchmarks/tests -q``.
"""

import json
import math
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.costs import swa_gqa_moe as costs  # noqa: E402
from benchmarks.drivers import trainer_swa_moe_steps as driver  # noqa: E402
from benchmarks.harness import compare, swa_moe_weights  # noqa: E402

NAME = "train-laguna-s.pack16k"
# Layer 0 and one period at a tiny width: 9 | 6 query heads of 16 over 3
# K/V heads, a window of 8, 8 experts of which 4 are held, 3 a token.
CONFIG = {
    "name": "tiny", "architecture": "swa_gqa_moe",
    "reference": "swa_gqa_moe", "costs": "swa_gqa_moe",
    "hidden_size": 48, "intermediate_size": 64,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_attention_heads": 6, "num_key_value_heads": 3, "head_dim": 16,
    "num_hidden_layers": 5, "vocab_size": 128, "sliding_window": 8,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"] * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 5,
    "num_attention_heads_per_layer": [6, 9, 9, 9, 6, 9],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 5000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 100,
                              "partial_rotary_factor": 1}},
    "gating": "per-head", "attention_bias": False,
    "moe_router_logit_softcapping": 0,
    "moe_apply_router_weight_on_input": False, "tie_word_embeddings": False,
    "num_experts": 8, "num_experts_per_tok": 3, "num_experts_held": 4,
    "experts_held_first": 0, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "router_aux_loss_coef": 0.0,
    "dispatch_alike_tail": 0.01, "rms_norm_eps": 1e-6,
    "initializer_range": 0.02, "dtype": "float32", "remat": True,
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
CONFIG["parameters"] = swa_moe_weights.parameter_count(CONFIG)
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 3, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
# float32 program against the float32 reference: summation order (1e-6
# read); the cell's own limits (bfloat16 program) are read on the chip
# and live in its workload file.
CELL = {"name": "tiny.pack", "config": "tiny", "traffic": "pack",
        "driver": "trainer_swa_moe_steps", "chips": 1,
        "check": {"steps": 2, "limits": {
            "grad1_norm_gap": 1e-4, "change_norm_gap": 1e-3,
            "routing_gap": 1e-5, "compiles_in_window": 0,
            "nonfinite_losses": 0, "moe_dropped_choices": 0}}}
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
                             seed=2**31 + 19, seconds=0.2, trace=False,
                             work_dir=str(tmp_path), t0=time.perf_counter())
    line = json.loads(json.dumps(out))
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] > 0 and line["attempted"] % (2 * 32) == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["compared"]) == set(CELL["check"]["limits"])
    err = capfd.readouterr().err
    facts = json.loads([l for l in err.splitlines()
                        if l.startswith('{"setup_s"')][0])["facts"]
    assert facts["parameters"] == CONFIG["parameters"]
    assert 0.4 < facts["attn_gate_mean"] < 0.6
    assert 0.4 < facts["attn_window_gate_mean"] < 0.6
    assert 0 < facts["moe_held_choices"] < 2 * 32 * 3
    assert 0 < facts["moe_load_cv"] < 3 and facts["moe_balance_loss"] >= 1
    assert facts["moe_layer_held_max"] >= facts["moe_held_choices"]


def test_a_count_that_is_not_the_trees_stops_the_run():
    with pytest.raises(ValueError, match="the configuration file says"):
        driver.run(CELL, dict(CONFIG, parameters=1), TRAFFIC, 3, 0.1, None)


def _in_the_programs_place(seed, batches, **how):
    """A run's result as ``check`` takes it, with the reference under
    ``how`` where the program's numbers would be."""
    control = driver.follow_reference(CELL, CONFIG, seed, batches, **how)
    return {"program": control, "first_batches": batches,
            "first_choices": control["choices"],
            "counts": {"compiles_in_window": 0, "nonfinite_losses": 0,
                       "moe_dropped_choices": 0.0}}


@pytest.mark.parametrize("how", [dict(yarn=False), dict(route_scale=1.0)])
def test_the_control_in_the_programs_place_is_not_correct(how):
    """Through ``check`` and ``judge``, as a run goes: the scaled rotary
    table left plain, and the router's gates left unscaled."""
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
    assert compared["grad1_norm_gap"]["value"] > limits["grad1_norm_gap"]


@pytest.mark.parametrize("seq_len,window", [(32, 8), (32, 1), (16, 16),
                                            (16, 40), (24, None)])
def test_the_pair_counts_are_the_masks(seq_len, window):
    """Against a brute-force table of ``i - window < j <= i``."""
    i, j = np.arange(seq_len)[:, None], np.arange(seq_len)[None, :]
    allowed = j <= i
    if window is not None:
        allowed &= i - j < window
    assert costs.attention_pairs(seq_len, window) == int(allowed.sum())


def test_the_committed_cell_is_what_the_issue_fixed():
    cell, config, traffic = bench_run.load_cell(NAME)
    assert cell["traffic"] == "pack16k" and cell["check"]["steps"] == 2
    assert cell["driver"] == "trainer_swa_moe_steps" and cell["chips"] == 1
    assert {"grad1_norm_gap", "change_norm_gap", "compiles_in_window",
            "nonfinite_losses"} <= set(cell["check"]["limits"])
    assert cell["check"]["limits"]["compiles_in_window"] == 0
    assert cell["check"]["limits"]["nonfinite_losses"] == 0
    assert traffic == dict(traffic, kind="packed_documents", rows=1,
                           seq_len=16384, pool_batches=16, bos_id=0,
                           doc_len={"alpha": 1.2, "min": 64, "max": 32768})
    assert config["name"] == "laguna-s-2.1-train"
    assert config["source"] == ("https://huggingface.co/poolside/"
                                "Laguna-S-2.1/blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers", "num_experts_held",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 256, "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["num_experts_held"],
            config["experts_held_first"], config["vocab_size"]) == \
        (5, 8, 0, 12544)
    # every number of the catalog row's config but the reduced ones
    published = {
        "hidden_size": 3072, "intermediate_size": 12288,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 1048576,
        "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "decoder_sparse_step": 1,
        "sliding_window": 512, "moe_routed_scaling_factor": 2.5,
        "moe_router_logit_softcapping": 0, "model_type": "laguna",
        "gating": "per-head", "mlp_only_layers": [0]}
    assert {k: config[k] for k in published} == published
    assert config["norm_topk_prob"] is True
    assert config["attention_bias"] is False
    assert config["tie_word_embeddings"] is False
    assert config["moe_apply_router_weight_on_input"] is False
    # the per-layer lists whole, as published: the first five are run
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) \
        == len(config["num_attention_heads_per_layer"]) \
        == len(config["gating_types"]) == 48
    assert config["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72,
                                                           48]
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    assert len(config["assumed"]) >= 12 and "32 chips" in config["deployment"]
    # ISSUE 42's table, from the shapes the weight maker hands the program
    import jax
    shapes = swa_moe_weights.decoder_shapes(config)

    def count(tree):
        return sum(math.prod(shape) for shape, _ in jax.tree.leaves(
            tree, is_leaf=swa_moe_weights._is_leaf))

    dense, (window, full) = shapes["layers"]
    assert count(dense) == 157_440_000
    assert count(window) == 3 * 148_862_976
    assert count(full) == 129_914_880
    assert count({k: window[k] for k in ("wq", "wk", "wv", "wo", "wg")}) \
        == 3 * 63_135_744
    assert count({k: full[k] for k in ("wq", "wk", "wv", "wo", "wg")}) \
        == 44_187_648
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 77_070_336
    assert count(shapes) == swa_moe_weights.parameter_count(config) \
        == config["parameters"] == 811_017_216
    kwargs = driver._model_kwargs(config, traffic["seq_len"])
    assert kwargs["layer_pattern"] == (
        ("mha:heads=48,rope=global", "dense", 1),
        ((("mha:heads=72,window=512,rope=local", "moe", 3),
          ("mha:heads=48,rope=global", "moe", 1)), 1))
    assert kwargs["rope_tables"] == {
        "global": {"theta": 500000.0, "rotary_dim": 64, "factor": 128.0,
                   "original_max_position": 8192, "beta_fast": 32.0,
                   "beta_slow": 1.0,
                   "attention_factor": 1.4852030263919618},
        "local": {"theta": 10000.0, "rotary_dim": 128}}
    assert (kwargs["attn_out_gate"], kwargs["moe_route_scale"],
            kwargs["moe_experts_held"], kwargs["moe_aux_coeff"],
            kwargs["moe_shared_width"], kwargs["d_ff"],
            kwargs["moe_d_ff"]) == ("head", 2.5, (0, 8), 0.0, 1024, 12288,
                                    1024)
    assert "rope_theta" not in kwargs and "rotary_dim" not in kwargs
    # the required operations: 1.42 GFLOP a token forward, some 70 TFLOP
    # a step; attention 71% (projections 39, triangles 28, bands 4), the
    # dense SwiGLU 16, shared and held experts 7, the head 5
    per_token = costs.train_flops_per_token(config, 16384)
    assert per_token / 3 == pytest.approx(1.42e9, rel=1e-2)
    assert per_token * 16384 == pytest.approx(70e12, rel=1e-2)
    plan = swa_moe_weights.layer_plan(config)
    proj = 6.0 * sum(costs.attention_matmul_params(config, e) for e in plan)
    pairs = [3.0 * 4 * 128 * e["heads"] * costs.attention_pairs(
        16384, e["window"]) / 16384 for e in plan]
    assert pairs[0] / 3 == pytest.approx(201e6, rel=1e-2)
    assert pairs[1] / 3 == pytest.approx(18.9e6, rel=2e-2)
    assert proj / per_token == pytest.approx(0.39, abs=0.01)
    assert (pairs[0] + pairs[4]) / per_token == pytest.approx(0.28, abs=0.01)
    assert sum(pairs[1:4]) / per_token == pytest.approx(0.04, abs=0.005)
    assert 6.0 * 3 * 3072 * 12288 / per_token == pytest.approx(0.16,
                                                               abs=0.01)
    assert 6.0 * 3072 * 12544 / per_token == pytest.approx(0.05, abs=0.005)
    # five attention calls a step each way: a triangle, three bands, a
    # triangle; the backward's operations twice the forward's; K and V
    # once a K/V head
    fwd = costs.flash_step_cost(config, 1, 16384, backward=False)
    bwd = costs.flash_step_cost(config, 1, 16384, backward=True)
    assert len(fwd) == len(bwd) == 5
    assert fwd[0]["flops"] == 48 * 512.0 * (16384 * 16385 // 2)
    assert fwd[1]["flops"] == 72 * 512.0 * costs.attention_pairs(16384, 512)
    assert [b["flops"] for b in bwd] == [2 * f["flops"] for f in fwd]
    per_tensor = 16384 * 128 * 2
    assert fwd[1]["bytes"] == 72 * (2 * per_tensor + 4 * 16384) \
        + 8 * 2 * per_tensor
    # BENCHMARK.json names the cell, and the three readers name only it:
    # a subset check, so that a later cell's entries do not fail it
    benchmark = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert NAME in [w["name"] for w in benchmark["workloads"]]
    (entry,) = [w for w in benchmark["workloads"] if w["name"] == NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("laguna-s-2.1-train", "pack16k", 1)
    (listed,) = [c for c in benchmark["configs"]
                 if c["name"] == "laguna-s-2.1-train"]
    assert listed["reduced"] == config["reduced"]
    assert listed["source"] == config["source"]
    mine = {m["name"] for m in benchmark["per_layer"]
            if m.get("workloads") == [NAME]}
    assert mine >= {"swa_flash_fwd_roofline", "swa_flash_bwd_roofline",
                    "window_layers_ms"}
    reported = {m["name"] for m in bench_run.metrics_of(
        benchmark, NAME, "per_layer")}
    assert reported >= mine | {
        "step_mfu", "train_step_p50_ms", "step_attributed_pct",
        "remat_recompute_ms", "attn_proj_ms", "attn_kernels_ms", "ffn_ms",
        "experts_ms", "head_loss_ms"}
    assert not reported & {"delta_layers_ms", "ssm_layers_ms",
                           "diff_flash_fwd_roofline", "flash_fwd_roofline"}


def test_the_new_readers_read_a_hand_made_trace_and_nothing_elsewhere():
    """Events of the two kernels at round times: the share is the step's
    five calls' least times over the events' time a step; a trace
    without them, or another configuration's file: nothing, and no
    raise."""
    _, config, _ = bench_run.load_cell(NAME)
    _, other, _ = bench_run.load_cell("train-qwen3-next.pack8k")
    flash = [["flash_attention_fwd", i * 1e8, 40e6] for i in range(5)] + \
        [["flash_attention_bwd", 1e9 + i * 2e8, 90e6] for i in range(5)]
    names = ("swa_flash_fwd_roofline", "swa_flash_bwd_roofline")

    def read(ops, cfg):
        ctx = {"trace": {"device_ops": {"/device:TPU:0": ops},
                         "host_spans": []},
               "config": cfg, "device_kind": "TPU v5 lite",
               "facts": {"rows": 1, "seq_len": 16384, "steps": 1}}
        return [bench_run._reader(name)(ctx) for name in names]

    assert read(flash, other) == [None] * 2
    assert read([], config) == [None] * 2
    fwd, bwd = read(flash, config)
    assert 0 < fwd <= 100 and 0 < bwd <= 100
    # the five forward calls' operations at the peak over 200 ms: every
    # call is compute-bound by the table's two peaks
    flops = sum(c["flops"] for c in costs.flash_step_cost(config, 1, 16384,
                                                          False))
    assert fwd == pytest.approx(100 * flops / 197e12 / 200e-3, rel=1e-6)
    assert bwd == pytest.approx(100 * 2 * flops / 197e12 / 450e-3, rel=1e-6)
    # window_layers_ms: nothing without the program's manifest
    from ray_tpu.util import tracing
    tracing.clear()
    ctx = {"trace": {"device_ops": {"/device:TPU:0": flash},
                     "host_spans": []}, "config": config,
           "device_kind": "TPU v5 lite", "facts": {"steps": 1}}
    assert bench_run._reader("window_layers_ms")(ctx) is None
