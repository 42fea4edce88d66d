"""The latent-attention sparse-expert driver at a tiny size on the CPU:
a whole run ends in a well-formed result that is correct; each control,
put in the program's place, comes out not correct; the committed cell's
files say what ISSUE 33 fixed.

Run from the repository's root: ``python -m pytest benchmarks/tests -q``.
"""

import copy
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.costs import mla_moe_mtp as costs  # noqa: E402
from benchmarks.drivers import trainer_mla_mtp_steps as driver  # noqa: E402
from benchmarks.harness import compare  # noqa: E402

# 1 dense-FFN layer + 2 expert layers + the module; 16 experts of which
# this rank holds 4 (experts 4-7), 4 a token; 4 heads of 16 + 8 score
# columns and 16 value columns.
CONFIG = {
    "name": "tiny", "architecture": "mla_moe_mtp",
    "reference": "mla_moe_mtp", "costs": "mla_moe_mtp",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_interleave": True, "rope_theta": 32000000,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1, "vocab_size": 128,
    "n_routed_experts": 16, "num_experts_per_tok": 4,
    "n_routed_experts_held": 4, "experts_held_first": 4,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "bias_update_rate": 0.001, "mtp_loss_coef": 0.3,
    "dispatch_alike_tail": 0.001,
    "rms_norm_eps": 1e-6, "initializer_range": 0.02, "dtype": "float32",
    "remat": True,
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 3, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
# float32 program against the float32 reference, which follows the
# program's experts: summation order alone; the cell's own limits
# (bfloat16 program) are read on the chip and live in its workload file.
CELL = {"name": "tiny.pack", "config": "tiny", "traffic": "pack",
        "driver": "trainer_mla_mtp_steps", "chips": 1,
        "check": {"steps": 2, "limits": {
            "grad1_norm_gap": 1e-3, "change_norm_gap": 2e-3,
            "routing_gap": 1e-4, "moe_bias_gap": 0,
            "compiles_in_window": 0, "nonfinite_losses": 0,
            "moe_dropped_choices": 0}}}
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
                             seed=2**31 + 11, seconds=0.2, trace=False,
                             work_dir=str(tmp_path), t0=time.perf_counter())
    line = json.loads(json.dumps(out))
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] > 0 and line["attempted"] % (2 * 32) == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["compared"]) == set(CELL["check"]["limits"])
    assert line["compared"]["moe_dropped_choices"]["value"] == 0
    assert line["compared"]["moe_bias_gap"]["value"] == 0
    err = capfd.readouterr().err
    facts = json.loads([l for l in err.splitlines()
                        if l.startswith('{"setup_s"')][0])["facts"]
    # 4 of 16 experts held, 4 choices a position, 2 x 32 positions
    assert 0 < facts["moe_held_choices"] < 2 * 32 * 4
    assert facts["moe_load_cv"] > 0 and facts["mtp_loss"] > 0
    # some expert's bias has moved the same way on every step so far:
    # the window's and the two checked ones
    assert facts["moe_bias_abs_max"] == pytest.approx(
        0.001 * (facts["steps"] + 2), rel=1e-3)


def _in_the_programs_place(seed, batches, **how):
    """A run's result as ``check`` takes it, with the reference under
    ``how`` where the program's numbers, experts and bias would be."""
    control = driver.follow_reference(CELL, CONFIG, seed, batches, **how)
    return {"program": control, "first_choices": control["choices"],
            "first_batches": batches,
            "counts": {"compiles_in_window": 0, "nonfinite_losses": 0,
                       "moe_dropped_choices": 0.0}}


@pytest.mark.parametrize("how", [
    dict(precision="fp8"), dict(rotary=False), dict(scoring="softmax"),
    dict(mtp_coeff=0.0), dict(shared=False), dict(learning_rate=0.0),
    "router", "bias"])
def test_the_control_in_the_programs_place_is_not_correct(how):
    """Through ``check`` and ``judge``, as a run goes: lower precision,
    the rotary columns left out of the score, softmax for sigmoid
    scoring, the module's loss left out, the shared expert left out, a
    step that changes nothing, a router that takes its fifth expert for
    its fourth (caught by ``routing_gap`` alone), and a bias that moved
    the wrong way on one expert (caught by ``moe_bias_gap`` alone)."""
    seed = 12345
    batches = list(driver.traffic_mod.generate(TRAFFIC, seed,
                                               vocab_size=128)[:2])
    limits = CELL["check"]["limits"]
    honest = _in_the_programs_place(seed, batches)
    correct, compared = compare.judge(
        driver.check(CELL, CONFIG, seed, honest), limits)
    assert correct is True, compared
    assert compared["routing_gap"]["value"] == 0.0
    if how == "router":
        result = copy.deepcopy(honest)
        k = CONFIG["num_experts_per_tok"]
        for chosen in result["first_choices"]:
            chosen[..., k - 1] = (chosen[..., k - 1] + 1) % 16
        # the loads are counted from what was used: the bias is not what
        # tells this one
        limits = dict(limits, moe_bias_gap=1.0)
    elif how == "bias":
        result = copy.deepcopy(honest)
        result["program"]["moe_bias"][1, 3] *= -1.0
    else:
        result = _in_the_programs_place(seed, batches, **how)
    correct, compared = compare.judge(
        driver.check(CELL, CONFIG, seed, result), limits)
    assert correct is False, compared
    if how == "router":
        assert compared["routing_gap"]["value"] > limits["routing_gap"]
    if how == "bias":
        assert [n for n, c in compared.items()
                if c["value"] > c["limit"]] == ["moe_bias_gap"]


def test_the_committed_cell_is_what_the_issue_fixed():
    cell, config, traffic = bench_run.load_cell("train-joyai-flash.pack8k")
    assert (traffic["rows"], traffic["seq_len"], traffic["pool_batches"],
            traffic["bos_id"]) == (2, 8192, 16, 0)
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["n_routed_experts_held"],
            config["vocab_size"]) == (5, 1, 256, 16, 16160)
    # ISSUE 33: 26,345,472 matrix parameters of latent attention a layer
    assert costs.attention_matmul_params(config) == 26_345_472
    d, v = 2048, config["vocab_size"]
    attention = 26_345_472 + 1536 + 512 + 2 * d
    dense = attention + 3 * d * 7168
    sparse = attention + d * 256 + (16 + 1) * 3 * d * 768
    module = sparse + 2 * d * d + 3 * d
    assert (dense, sparse, module) == (70_391_808, 107_091_968, 115_486_720)
    assert dense + 4 * sparse + module + 2 * v * d + d == 680_439_808
    # about 3.4 GFLOP a token, some 56 TFLOP a step
    per_token = costs.train_flops_per_token(config, 8192)
    assert per_token == pytest.approx(3.40e9, rel=5e-3)
    assert per_token * 2 * 8192 == pytest.approx(55.7e12, rel=5e-3)
    # the causal kernel forward: 2 x (192 + 128) x 32 x 4,096 a token
    fwd = costs.flash_call_cost(config, 2, 8192, backward=False)
    assert fwd["flops"] == 2.0 * 320 * 32 * 4096 * 2 * 8192
    assert costs.flash_call_cost(config, 2, 8192, backward=True)["flops"] \
        == 2 * fwd["flops"]
    # the rotary key's bytes once a row, not once a head
    assert fwd["bytes"] == 2 * (32 * (8192 * 2 * (192 + 128 + 128 + 128)
                                      + 4 * 8192) + 8192 * 2 * 64)
    kwargs = driver._model_kwargs(config, traffic["seq_len"])
    assert kwargs["layer_pattern"] == (("mla", "dense", 1), ("mla", "moe", 4))
    assert kwargs["moe_experts_held"] == (0, 16)
    assert kwargs["moe_shared_width"] == 768
    # the first chunk: 3 choices of each of the 16,384 tokens a layer
    from ray_tpu.models.moe import chunk_rows
    assert chunk_rows(16384, 256, 16, 8, kwargs["moe_alike_tail"]) == (
        49152, 16384)
    assert chunk_rows(16384, 256, 16, 8) == (32768, 16384)
