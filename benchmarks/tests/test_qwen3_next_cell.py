"""The hybrid delta-rule / gated-attention driver at a tiny size on the
CPU: a whole run ends in a well-formed result that is correct; each
control, put in the program's place, comes out not correct; the
committed cell's files say what ISSUE 35 fixed.

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
from benchmarks.costs import gdn_gated_moe as costs  # noqa: E402
from benchmarks.drivers import trainer_gdn_steps as driver  # noqa: E402
from benchmarks.harness import compare  # noqa: E402

# Two periods of 2 delta layers + 1 attention layer; 2 key heads of 8
# serving 4 value heads of 8; 4 query heads of 16 on 2 K/V heads, rotary
# on 4 columns; 16 experts of which this rank holds 4 (experts 4-7), 4 a
# token; a gated shared expert.
CONFIG = {
    "name": "tiny", "architecture": "gdn_gated_moe",
    "reference": "gdn_gated_moe", "costs": "gdn_gated_moe",
    "hidden_size": 64, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "gdn_chunk": 16,
    "full_attention_interval": 3, "num_hidden_layers": 6,
    "vocab_size": 128, "num_experts": 16, "num_experts_per_tok": 4,
    "num_experts_held": 4, "experts_held_first": 4, "norm_topk_prob": True,
    "router_aux_loss_coef": 0.001, "dispatch_alike_tail": 0.01,
    "rms_norm_eps": 1e-6, "initializer_range": 0.02, "dtype": "float32",
    "remat": True,
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 3, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
# float32 program against the float32 reference, which follows the
# program's experts: summation order and the chunked form's inverse; the
# cell's own limits (bfloat16 program) are read on the chip and live in
# its workload file.
CELL = {"name": "tiny.pack", "config": "tiny", "traffic": "pack",
        "driver": "trainer_gdn_steps", "chips": 1,
        "check": {"steps": 2, "limits": {
            "grad1_norm_gap": 1e-3, "change_norm_gap": 2e-3,
            "routing_gap": 1e-4, "gdn_rule_gap": 1e-4,
            "gdn_rule_grad_gap": 1e-4, "compiles_in_window": 0,
            "nonfinite_losses": 0, "moe_dropped_choices": 0,
            # on the CPU the state pass is the scan, and says so; the
            # committed cell allows none
            "gdn_scan_state_passes": 1}}}
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
    assert line["compared"]["moe_dropped_choices"]["value"] == 0
    assert line["compared"]["gdn_scan_state_passes"]["value"] == 1
    err = capfd.readouterr().err
    facts = json.loads([l for l in err.splitlines()
                        if l.startswith('{"setup_s"')][0])["facts"]
    # 4 of 16 experts held, 4 choices a position, 2 x 32 positions
    assert 0 < facts["moe_held_choices"] < 2 * 32 * 4
    # a single layer's most, counted on the device from the step's
    # experts, against the first chunk's rows (2 of 4 choices a token)
    assert facts["moe_held_choices"] <= facts["moe_layer_held_max"] \
        <= 2 * 32 * 4
    assert facts["moe_first_chunk_rows"] >= 2 * 32
    assert facts["moe_steps_past_first_chunk"] \
        <= facts["moe_steps_past_default_chunk"] <= line["attempted"] // 64
    # the new counters are on the run's facts line
    assert 0.4 < facts["attn_gate_mean"] < 0.6
    assert 0.4 < facts["moe_shared_gate_mean"] < 0.6
    assert 0.4 < facts["gdn_beta_mean"] < 0.6
    assert 0 < facts["gdn_decay_mean"] < 1 and facts["gdn_state_norm"] > 0
    assert facts["moe_balance_loss"] > 0.9


def _in_the_programs_place(seed, batches, **how):
    """A run's result as ``check`` takes it, with the reference under
    ``how`` where the program's numbers and experts would be."""
    control = driver.follow_reference(CELL, CONFIG, seed, batches, **how)
    return {"program": control, "first_choices": control["choices"],
            "first_batches": batches,
            "counts": {"compiles_in_window": 0, "nonfinite_losses": 0,
                       "moe_dropped_choices": 0.0}}


@pytest.mark.parametrize("how", [
    dict(precision="fp8"), dict(decay=False), dict(state="bfloat16"),
    dict(dstate="bfloat16"), dict(attn_gate=False), dict(rotary="all"), dict(shared_gate=False),
    dict(learning_rate=0.0), "router"])
def test_the_control_in_the_programs_place_is_not_correct(how):
    """Through ``check`` and ``judge``, as a run goes: lower precision,
    a state that never forgets, a state kept in bfloat16, its cotangent
    kept in bfloat16 in the backward pass, attention's
    output gate left out, rotary on every column, the shared expert's
    gate left out, a step that changes nothing, and a router that takes
    its fifth expert for its fourth (caught by ``routing_gap`` alone)."""
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
    else:
        result = _in_the_programs_place(seed, batches, **how)
    correct, compared = compare.judge(
        driver.check(CELL, CONFIG, seed, result), limits)
    assert correct is False, compared
    if how == "router":
        assert compared["routing_gap"]["value"] > limits["routing_gap"]


def test_the_committed_cell_is_what_the_issue_fixed():
    cell, config, traffic = bench_run.load_cell("train-qwen3-next.pack8k")
    assert cell["traffic"] == "pack8k" and cell["check"]["steps"] == 2
    assert (traffic["rows"], traffic["seq_len"], traffic["pool_batches"],
            traffic["bos_id"]) == (2, 8192, 16, 0)
    assert (config["num_hidden_layers"], config["full_attention_interval"],
            config["num_experts"], config["num_experts_held"],
            config["vocab_size"]) == (4, 4, 512, 32, 18992)
    assert config["reduced"] == ["num_hidden_layers", "num_experts_held",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    # ISSUE 35's arithmetic, a layer at a time (the norms, the taps,
    # A_log and dt_bias meet no matrix product)
    assert costs.delta_matmul_params(config) == 33_718_464 - 32_768 - 64 - 128
    assert costs.attention_matmul_params(config) == 27_263_488 - 512
    d, v = 2048, config["vocab_size"]
    experts = d * 512 + 32 * 3 * d * 512 + 3 * d * 512 + d
    assert experts == 104_859_648
    delta, attention = 33_718_464 + experts + 2 * d, \
        27_263_488 + experts + 2 * d
    assert (delta, attention) == (138_582_208, 132_127_232)
    assert 3 * delta + attention + 2 * v * d + d == 625_667_136
    # ... and from the shapes the weight maker hands the program
    from benchmarks.harness import gdn_weights
    import jax
    import math
    shapes = jax.tree.leaves(gdn_weights.hybrid_shapes(config),
                             is_leaf=gdn_weights._is_leaf)
    assert sum(math.prod(shape) for shape, _ in shapes) == 625_667_136
    # about 463 MFLOP a token forward, some 22.8 TFLOP a step
    per_token = costs.train_flops_per_token(config, 8192)
    assert per_token / 3 == pytest.approx(463e6, rel=5e-3)
    assert per_token * 2 * 8192 == pytest.approx(22.8e12, rel=5e-3)
    assert costs.delta_rule_flops_per_token(config) == pytest.approx(
        4.2e6, rel=5e-3)
    # the causal kernel forward: 2 x (256 + 256) x 16 x 4,096 a token
    fwd = costs.flash_call_cost(config, 2, 8192, backward=False)
    assert fwd["flops"] == 2.0 * 512 * 16 * 4096 * 2 * 8192
    assert costs.flash_call_cost(config, 2, 8192, backward=True)["flops"] \
        == 2 * fwd["flops"]
    # K and V once a group of 8 query heads
    assert fwd["bytes"] == 2 * (16 * (2 * 8192 * 256 * 2 + 4 * 8192)
                                + 2 * 2 * 8192 * 256 * 2)
    # the state pass: 2 products a chunk, about 1 GB a call, memory-bound
    one = costs.state_pass_cost(config, 2, 8192, backward=False)
    assert one["flops"] == 2 * 2.0 * 64 * 128 * 128 * 2 * 32 * 128
    assert one["bytes"] == pytest.approx(1.07e9, rel=5e-3)
    assert one["flops"] / 197e12 < one["bytes"] / 819e9
    back = costs.state_pass_cost(config, 2, 8192, backward=True)
    assert back["flops"] == 2 * one["flops"]
    assert back["bytes"] == pytest.approx(2.01e9, rel=5e-3)
    kwargs = driver._model_kwargs(config, traffic["seq_len"])
    assert kwargs["layer_pattern"] == (
        ((("gdn", "moe", 3), ("mha", "moe", 1)), 1),)
    assert kwargs["moe_experts_held"] == (0, 32)
    assert (kwargs["rotary_dim"], kwargs["moe_aux_coeff"]) == (64, 0.001)
    # the first chunk: 4 choices of each of the 16,384 tokens a layer
    from ray_tpu.models.moe import chunk_rows
    assert chunk_rows(16384, 512, 32, 10, kwargs["moe_alike_tail"]) == (
        65536, 16384)
    assert chunk_rows(16384, 512, 32, 10) == (49152, 16384)
    # BENCHMARK.json names the cell, and the four readers name only it
    benchmark = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = [m["name"] for m in benchmark["per_layer"]
            if m.get("workloads") == ["train-qwen3-next.pack8k"]]
    assert mine == ["gdn_fwd_roofline", "gdn_bwd_roofline",
                    "gated_flash_fwd_roofline", "gated_flash_bwd_roofline"]


def test_the_new_readers_find_nothing_in_another_cells_trace():
    """A trace without the delta kernels, or another configuration's
    file: the readers return nothing and do not raise."""
    _, config, _ = bench_run.load_cell("train-qwen3-next.pack8k")
    _, other, _ = bench_run.load_cell("train-joyai-flash.pack8k")
    flash_only = {"device_ops": {"/device:TPU:0": [
        ["flash_attention_fwd", 0.0, 12e6],
        ["flash_attention_bwd", 12e6, 30e6]]},
        "host_spans": []}
    both = {"device_ops": {"/device:TPU:0": flash_only["device_ops"][
        "/device:TPU:0"] + [["gated_delta_fwd", 5e7, 4e6],
                            ["gated_delta_bwd", 6e7, 8e6]]},
            "host_spans": []}
    names = ("gdn_fwd_roofline", "gdn_bwd_roofline",
             "gated_flash_fwd_roofline", "gated_flash_bwd_roofline")

    def read(trace, cfg):
        ctx = {"trace": trace, "config": cfg, "device_kind": "TPU v5 lite",
               "facts": {"rows": 2, "seq_len": 8192}}
        return [bench_run._reader(name)(ctx) for name in names]

    assert read(flash_only, other) == [None] * 4
    assert read(both, other) == [None] * 4
    fwd, bwd, f_fwd, f_bwd = read(flash_only, config)
    assert fwd is None and bwd is None and f_fwd > 0 and f_bwd > 0
    got = read(both, config)
    assert all(0 < x <= 100 for x in got), got
    # 1.07 GB at 819 GB/s over 4 ms: a third of the roofline
    assert got[0] == pytest.approx(100 * 1.0737e9 / 819e9 / 4e-3, rel=1e-3)
