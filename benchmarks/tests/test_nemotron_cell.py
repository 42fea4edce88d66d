"""The driver of the decoder whose layers are each one sublayer (Mamba-2
mixers, attention without positional encoding, squared-ReLU experts in a
latent), at a tiny size on the CPU: a whole run ends in a well-formed
result that is correct; controls, put in the program's place, come out
not correct; the committed cell's files say what ISSUE 46 fixed; the
three new readers read a hand-made trace and nothing elsewhere.

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
from benchmarks.costs import mamba2_latent_moe as costs  # noqa: E402
from benchmarks.drivers import trainer_mamba2_moe_steps as driver  # noqa
from benchmarks.harness import compare, mamba2_moe_weights  # noqa: E402

NAME = "train-nemotron-3-super.row8k"
_, COMMITTED, _ = bench_run.load_cell(NAME)
# The published layers 0-10 at a tiny width: 4 Mamba-2 heads of 8 over 2
# groups of 16 states in chunks of 16, 4 query heads of 8 over 2 K/V
# heads, 8 experts of 24 in a latent of 16, 4 held, 3 a token.
CONFIG = dict(
    COMMITTED, name="tiny", hidden_size=48, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, mamba_num_heads=4, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, chunk_size=16, n_routed_experts=8,
    n_routed_experts_held=4, num_experts_per_tok=3,
    moe_intermediate_size=24, moe_latent_size=16,
    moe_shared_expert_intermediate_size=32, vocab_size=128, dtype="float32")
CONFIG["parameters"] = mamba2_moe_weights.parameter_count(CONFIG)
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 3, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
# float32 program against the float32 reference: summation order and the
# chunked rule against the recurrence (1e-6 read); off the TPU the rule
# runs as ``jnp`` and says so (the chip's limit is 0); the cell's own
# limits (bfloat16 program) are read on the chip and live in its file.
CELL = {"name": "tiny.pack", "config": "tiny", "traffic": "pack",
        "driver": "trainer_mamba2_moe_steps", "chips": 1,
        "check": {"steps": 2, "limits": {
            "grad1_norm_gap": 1e-4, "change_norm_gap": 1e-3,
            "routing_gap": 1e-5, "ssd_rule_gap": 1e-5,
            "ssd_rule_grad_gap": 1e-4, "compiles_in_window": 0,
            "nonfinite_losses": 0, "moe_dropped_choices": 0,
            "ssd_fallback_passes": 1e9}}}
BENCHMARK = {
    "end_to_end": [
        {"name": "train_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": []}


def test_a_run_ends_in_a_wellformed_correct_result(tmp_path, capfd):
    out = bench_run.run_cell(CELL, CONFIG, TRAFFIC, BENCHMARK,
                             seed=2**31 + 29, seconds=0.2, trace=False,
                             work_dir=str(tmp_path), t0=time.perf_counter())
    line = json.loads(json.dumps(out))
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] > 0 and line["attempted"] % (2 * 32) == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["compared"]) == set(CELL["check"]["limits"])
    # off the TPU: every step's mixers and the probe ran as ``jnp``
    assert line["compared"]["ssd_fallback_passes"]["value"] >= 3
    err = capfd.readouterr().err
    facts = json.loads([l for l in err.splitlines()
                        if l.startswith('{"setup_s"')][0])["facts"]
    assert facts["parameters"] == CONFIG["parameters"]
    assert 0 < facts["moe_held_choices"] < 2 * 32 * 3
    assert facts["moe_layer_held_max"] >= facts["moe_held_choices"]
    assert facts["moe_bias_abs_max"] > 0
    assert 0.001 <= facts["ssd_dt_mean"] <= 0.1
    assert '"moe_bias_equal": true' in err


def test_a_count_that_is_not_the_trees_stops_the_run():
    with pytest.raises(ValueError, match="the configuration file says"):
        driver.run(CELL, dict(CONFIG, parameters=1), TRAFFIC, 3, 0.1, None)


def _in_the_programs_place(seed, batches, **how):
    """A run's result as ``check`` takes it, with the reference under
    ``how`` where the program's numbers would be (the probe is the
    program's own: the rule's controls are read against it below)."""
    control = driver.follow_reference(CELL, CONFIG, seed, batches, **how)
    probe, _ = driver.rule_probe(CONFIG, seed, 2, 32)
    return {"program": dict(control, rule_probe=probe),
            "first_batches": batches, "first_choices": control["choices"],
            "counts": {"compiles_in_window": 0, "nonfinite_losses": 0,
                       "moe_dropped_choices": 0.0, "ssd_fallback_passes": 0}}


@pytest.mark.parametrize("how", [dict(norm_groups=1), dict(skip=False),
                                 dict(latent=False)])
def test_the_control_in_the_programs_place_is_not_correct(how):
    """Through ``check`` and ``judge``, as a run goes: one norm group for
    eight, the ``D`` skip left out, the latent pair left out."""
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


@pytest.mark.parametrize("how", [dict(state="bfloat16"), dict(decay=False)])
def test_the_rules_controls_read_far_from_the_programs_rule(how):
    """The recurrence with its state rounded to bfloat16 at every
    position, or without its decay, against the program's rule on the
    probe: over the tiny limits by far; the sound recurrence under
    them."""
    seed = 4321
    probe, _ = driver.rule_probe(CONFIG, seed, 2, 32)
    limits = CELL["check"]["limits"]
    sound = driver.rule_numbers(CONFIG, seed, probe)
    assert sound["ssd_rule_gap"][0] <= limits["ssd_rule_gap"]
    assert sound["ssd_rule_grad_gap"][0] <= limits["ssd_rule_grad_gap"]
    control = driver.rule_numbers(CONFIG, seed, probe, **how)
    assert control["ssd_rule_gap"][0] > 10 * limits["ssd_rule_gap"]
    assert control["ssd_rule_grad_gap"][0] > 10 * limits["ssd_rule_grad_gap"]


def test_the_committed_cell_is_what_the_issue_fixed():
    cell, config, traffic = bench_run.load_cell(NAME)
    assert cell["traffic"] == "row8k" and cell["check"]["steps"] == 2
    assert cell["driver"] == "trainer_mamba2_moe_steps" and cell["chips"] == 1
    assert set(cell["check"]["limits"]) == {
        "grad1_norm_gap", "change_norm_gap", "routing_gap", "ssd_rule_gap",
        "ssd_rule_grad_gap", "compiles_in_window", "nonfinite_losses",
        "moe_dropped_choices", "ssd_fallback_passes"}
    for exact in ("compiles_in_window", "nonfinite_losses",
                  "moe_dropped_choices", "ssd_fallback_passes"):
        assert cell["check"]["limits"][exact] == 0
    assert traffic == dict(traffic, kind="packed_documents", rows=1,
                           seq_len=8192, pool_batches=16, bos_id=0,
                           doc_len={"alpha": 1.2, "min": 64, "max": 16384})
    assert config["name"] == "nemotron-3-super-120b-a12b-train"
    assert config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        "/blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts_held",
                                 "vocab_size", "num_nextn_predict_layers"]
    assert config["published"] == {
        "num_hidden_layers": 88, "n_routed_experts_held": 512,
        "vocab_size": 131072, "num_nextn_predict_layers": 1}
    assert (config["num_hidden_layers"], config["n_routed_experts_held"],
            config["experts_held_first"], config["vocab_size"],
            config["num_nextn_predict_layers"]) == (11, 8, 0, 16384, 0)
    # every number of the catalog row's config but the reduced ones
    published = {
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 4096, "intermediate_size": 2688,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_num_heads": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 512, "n_shared_experts": 1,
        "norm_eps": 1e-05, "num_attention_heads": 32,
        "num_experts_per_tok": 22, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rope_theta": 10000, "routed_scaling_factor": 5,
        "ssm_state_size": 128, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
        "model_type": "nemotron_h", "mtp_hybrid_override_pattern": "*E"}
    assert {k: config[k] for k in published} == published
    assert config["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert len(config["hybrid_override_pattern"]) == 88
    assert config["use_conv_bias"] is True and config["norm_topk_prob"]
    assert not (config["mamba_proj_bias"] or config["use_bias"]
                or config["mlp_bias"] or config["attention_bias"]
                or config["tie_word_embeddings"])
    assert len(config["assumed"]) >= 12 and "64 chips" in config["deployment"]
    # ISSUE 46's table, from the shapes the weight maker hands the program
    import jax
    shapes = mamba2_moe_weights.decoder_shapes(config)

    def count(tree):
        return sum(math.prod(shape) for shape, _ in jax.tree.leaves(
            tree, is_leaf=mamba2_moe_weights._is_leaf))

    runs = shapes["layers"]
    mixer = {k: runs[1][k] for k in ("ln1", "mamba2")}
    assert count(mixer) == 109_640_064
    assert count(runs[1]) == 109_640_064            # the M alone
    attention = {k: runs[2][k] for k in ("ln1", "wq", "wk", "wv", "wo")}
    assert count(attention) == 35_655_680
    # an expert layer with its norm, the routers' 512-wide bias being
    # state beside the tree (ISSUE 46 counts it: 98,570,752)
    expert = {"ln2": runs[2]["ln2"], "moe": runs[2]["moe"]}
    assert count(expert) + 512 == 98_570_752
    assert count(runs[2]["moe"]["w1"]) // 8 * 2 == 5_505_024
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == \
        2 * 16_384 * 4096
    assert count(shapes) == mamba2_moe_weights.parameter_count(config) \
        == config["parameters"] == 1_210_929_024
    assert config["parameters"] + 5 * 512 == 1_210_931_584
    kwargs = driver._model_kwargs(config, traffic["seq_len"])
    assert kwargs["layer_pattern"] == (
        ("mamba2", "moe", 3), ("mamba2", "none", 1), ("mha", "moe", 1),
        ("mamba2", "moe", 1))
    assert (kwargs["rope"], kwargs["moe_act"], kwargs["moe_latent"],
            kwargs["moe_scoring"], kwargs["moe_route_scale"],
            kwargs["moe_top_k"], kwargs["moe_experts_held"],
            kwargs["moe_shared_width"], kwargs["moe_aux_coeff"]) == (
        "none", "relu2", 1024, "sigmoid", 5, 22, (0, 8), 5376, 0.0)
    assert kwargs["mamba2"] == dict(
        num_heads=128, head_dim=64, n_groups=8, state_size=128,
        conv_kernel=4, chunk=128, norm_groups=8, dt_min=0.001, dt_max=0.1,
        dt_floor=0.0001)
    # the required operations: 48.3 TFLOP a step, 5.90 GFLOP a token of
    # which 1.97 forward; the mixers 57%, the expert layers 29% (the
    # shared expert 22), attention 7, the head 7
    per_token = costs.train_flops_per_token(config, 8192)
    assert per_token * 8192 == pytest.approx(48.30e12, rel=1e-3)
    assert per_token / 3 == pytest.approx(1.965e9, rel=1e-3)
    plan = mamba2_moe_weights.layer_plan(config)
    mixers = sum(6 * costs.mixer_matmul_params(config, e) for e in plan
                 if e["mixer"] == "mamba2") \
        + 5 * 3 * costs.rule_flops_per_token(config)
    assert mixers / per_token == pytest.approx(0.574, abs=0.001)
    assert 5 * 6 * costs.expert_matmul_params(config) / per_token == \
        pytest.approx(0.287, abs=0.001)
    assert 5 * 6 * 2 * 4096 * 5376 / per_token == pytest.approx(0.224,
                                                                abs=0.001)
    assert costs.rule_flops_per_token(config) == pytest.approx(6.55e6,
                                                               rel=1e-3)
    # one call of the rule, forward and backward
    fwd = costs.rule_call_cost(config, 1, 8192, backward=False)
    bwd = costs.rule_call_cost(config, 1, 8192, backward=True)
    assert fwd["flops"] == 8192 * costs.rule_flops_per_token(config)
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] == \
        2 * fwd["bytes"]
    assert fwd["bytes"] == 8192 * (2 * 8192 * 2 + 2 * 1024 * 2 + 128 * 4)
    # BENCHMARK.json names the cell, and the three readers name only it
    benchmark = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [w for w in benchmark["workloads"] if w["name"] == NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("nemotron-3-super-120b-a12b-train", "row8k", 1)
    (listed,) = [c for c in benchmark["configs"]
                 if c["name"] == "nemotron-3-super-120b-a12b-train"]
    assert listed["reduced"] == config["reduced"]
    assert listed["source"] == config["source"]
    mine = {m["name"] for m in benchmark["per_layer"]
            if m.get("workloads") == [NAME]}
    assert mine >= {"ssd_fwd_roofline", "ssd_bwd_roofline",
                    "mamba2_layers_ms"}
    reported = {m["name"] for m in bench_run.metrics_of(
        benchmark, NAME, "per_layer")}
    assert reported >= mine | {
        "step_mfu", "train_step_p50_ms", "step_attributed_pct",
        "remat_recompute_ms", "attn_proj_ms", "attn_kernels_ms", "ffn_ms",
        "experts_ms", "head_loss_ms"}
    assert not reported & {"delta_layers_ms", "ssm_layers_ms",
                           "window_layers_ms", "flash_fwd_roofline"}


def test_the_new_readers_read_a_hand_made_trace_and_nothing_elsewhere():
    """Events of the two kernels at round times: the share is one call's
    least time times the calls over their time; a trace without them,
    or another configuration's file: nothing, and no raise."""
    _, config, _ = bench_run.load_cell(NAME)
    _, other, _ = bench_run.load_cell("train-phi4-mini-flash.pack16k")
    ops = [["ssd_fwd.3", i * 1e8, 4e6] for i in range(5)] + \
        [["ssd_bwd.7", 1e9 + i * 1e8, 10e6] for i in range(5)]
    names = ("ssd_fwd_roofline", "ssd_bwd_roofline")

    def read(ops, cfg):
        ctx = {"trace": {"device_ops": {"/device:TPU:0": ops},
                         "host_spans": []},
               "config": cfg, "device_kind": "TPU v5 lite",
               "facts": {"rows": 1, "seq_len": 8192, "steps": 1}}
        return [bench_run._reader(name)(ctx) for name in names]

    assert read(ops, other) == [None] * 2
    assert read([], config) == [None] * 2
    fwd, bwd = read(ops, config)
    # both calls are memory-bound by the table's peaks
    one = costs.rule_call_cost(config, 1, 8192, False)
    assert one["bytes"] / 819e9 > one["flops"] / 197e12
    assert fwd == pytest.approx(100 * one["bytes"] / 819e9 / 4e-3, rel=1e-6)
    assert bwd == pytest.approx(100 * 2 * one["bytes"] / 819e9 / 10e-3,
                                rel=1e-6)
    assert 0 < fwd <= 100 and 0 < bwd <= 100
    # mamba2_layers_ms: nothing without the program's manifest
    from ray_tpu.util import tracing
    tracing.clear()
    ctx = {"trace": {"device_ops": {"/device:TPU:0": ops},
                     "host_spans": []}, "config": config,
           "device_kind": "TPU v5 lite", "facts": {"steps": 1}}
    assert bench_run._reader("mamba2_layers_ms")(ctx) is None
