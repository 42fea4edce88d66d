"""The driver of the decoder of Kimi Delta Attention and latent attention
layers over dense and expert FFNs (a sigmoid router limited to the best
groups of experts), at a tiny size on the CPU: a whole run ends in a
well-formed result that is correct; controls, put in the program's
place, come out not correct; the committed cell's files hold what the
configuration publishes and the cut's arithmetic; the three new readers
read a hand-made trace and nothing elsewhere.

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
from benchmarks.costs import kda_mla_moe as costs  # noqa: E402
from benchmarks.drivers import trainer_kda_mla_steps as driver  # noqa
from benchmarks.harness import compare, kda_weights  # noqa: E402
from benchmarks.harness import traffic as traffic_mod  # noqa: E402

NAME = "train-ling-3-flash.pack8k"
_, COMMITTED, _ = bench_run.load_cell(NAME)
# The published layers 1, 10 and 11 at a tiny width: 2 heads of 16, KDA
# in chunks of 16, 16 experts in 4 groups of which a token keeps 2, 4
# held (experts 4-7), 4 a token.
CONFIG = dict(
    COMMITTED, name="tiny", hidden_size=48, num_attention_heads=2,
    head_dim=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    kv_lora_rank=16, chunk_size=16, num_experts=16, n_group=4, topk_group=2,
    num_experts_held=4, experts_held_first=4, num_experts_per_tok=4,
    moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
    intermediate_size=32, vocab_size=128, dtype="float32",
    num_hidden_layers=3, kept_layers=[1, 10, 11])
CONFIG["parameters"] = kda_weights.parameter_count(CONFIG)
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 3, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
# float32 program against the float32 reference: summation order and the
# chunked rule against the recurrence; off the TPU the rule runs as
# ``jnp`` and says so (the chip's limit is 0); the cell's own limits
# (bfloat16 program) are read on the chip and live in its file.
CELL = {"name": "tiny.pack", "config": "tiny", "traffic": "pack",
        "driver": "trainer_kda_mla_steps", "chips": 1,
        "check": {"steps": 2, "limits": {
            "grad1_norm_gap": 1e-4, "change_norm_gap": 1e-3,
            "routing_gap": 1e-5, "kda_rule_gap": 1e-5,
            "kda_rule_grad_gap": 1e-4, "compiles_in_window": 0,
            "nonfinite_losses": 0, "moe_dropped_choices": 0,
            "kda_fallback_passes": 1e9}}}
BENCHMARK = {
    "end_to_end": [
        {"name": "train_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": []}


def test_a_run_ends_in_a_wellformed_correct_result(tmp_path, capfd):
    out = bench_run.run_cell(CELL, CONFIG, TRAFFIC, BENCHMARK,
                             seed=2**31 + 51, seconds=0.2, trace=False,
                             work_dir=str(tmp_path), t0=time.perf_counter())
    line = json.loads(json.dumps(out))
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] > 0 and line["attempted"] % (2 * 32) == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["compared"]) == set(CELL["check"]["limits"])
    # off the TPU: every step's KDA layers and the probe ran as ``jnp``
    assert line["compared"]["kda_fallback_passes"]["value"] >= 3
    err = capfd.readouterr().err
    facts = json.loads([l for l in err.splitlines()
                        if l.startswith('{"setup_s"')][0])["facts"]
    assert facts["parameters"] == CONFIG["parameters"]
    assert 0 < facts["moe_held_choices"] < 2 * 32 * 4
    assert facts["moe_bias_abs_max"] > 0
    assert 0 < facts["kda_decay_mean"] < 1
    assert facts["kda_fallback_passes"] >= 3
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
                       "moe_dropped_choices": 0.0, "kda_fallback_passes": 0}}


@pytest.mark.parametrize("how", [dict(decay="head"), dict(groups=False),
                                 dict(gate="softplus")])
def test_the_control_in_the_programs_place_is_not_correct(how):
    """Through ``check`` and ``judge``, as a run goes: one decay a head,
    the group step left out, the unbounded gate."""
    seed = 12345
    batches = list(traffic_mod.generate(TRAFFIC, seed,
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


@pytest.mark.parametrize("how", [dict(state="bfloat16"), dict(decay="head")])
def test_the_rules_controls_read_far_from_the_programs_rule(how):
    """The recurrence with its state rounded to bfloat16 at every
    position, or with one decay a head, against the program's rule on
    the probe: over the tiny limits by far; the sound recurrence under
    them."""
    seed = 4321
    probe, _ = driver.rule_probe(CONFIG, seed, 2, 32)
    limits = CELL["check"]["limits"]
    sound = driver.rule_numbers(CONFIG, seed, probe)
    assert sound["kda_rule_gap"][0] <= limits["kda_rule_gap"]
    assert sound["kda_rule_grad_gap"][0] <= limits["kda_rule_grad_gap"]
    control = driver.rule_numbers(CONFIG, seed, probe, **how)
    assert control["kda_rule_gap"][0] > 10 * limits["kda_rule_gap"]
    assert control["kda_rule_grad_gap"][0] > 10 * limits["kda_rule_grad_gap"]


def test_the_committed_cell_holds_the_published_configuration_and_cut():
    cell, config, traffic = bench_run.load_cell(NAME)
    assert cell["traffic"] == "pack8k" and cell["check"]["steps"] == 2
    assert cell["driver"] == "trainer_kda_mla_steps" and cell["chips"] == 1
    assert set(cell["check"]["limits"]) == {
        "grad1_norm_gap", "change_norm_gap", "routing_gap", "kda_rule_gap",
        "kda_rule_grad_gap", "compiles_in_window", "nonfinite_losses",
        "moe_dropped_choices", "kda_fallback_passes"}
    for exact in ("compiles_in_window", "nonfinite_losses",
                  "moe_dropped_choices", "kda_fallback_passes"):
        assert cell["check"]["limits"][exact] == 0
    assert traffic == dict(traffic, kind="packed_documents", rows=2,
                           seq_len=8192, pool_batches=16, bos_id=0,
                           doc_len={"alpha": 1.2, "min": 64, "max": 16384})
    assert config["name"] == "ling-3.0-flash-train"
    assert config["source"] == ("https://huggingface.co/inclusionAI/"
                                "Ling-3.0-flash-VL/blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers", "num_experts_held",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 42,
                                   "num_experts_held": 512,
                                   "vocab_size": 157184}
    assert (config["num_hidden_layers"], config["num_experts_held"],
            config["experts_held_first"], config["vocab_size"]) == \
        (7, 8, 0, 19648)
    assert config["vocab_size"] * 8 == 157184
    assert config["kept_layers"] == [1, 6, 7, 8, 9, 10, 11]
    # every number of the published config but the reduced ones
    published = {
        "hidden_size": 2560, "intermediate_size": 6144,
        "first_k_dense_replace": 2, "max_position_embeddings": 131072,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "num_attention_heads": 32, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
        "rms_norm_eps": 1e-06, "head_dim": 128, "partial_rotary_factor": 0.5,
        "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
        "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
        "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
        "rotary_dim": 64, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "image_patch_token": 157157,
        "video_patch_token": 156909, "image_start_token": 157158,
        "video_start_token": 157160}
    assert {k: config[k] for k in published} == published
    assert config["q_lora_rank"] is None and config["use_qk_norm"]
    assert config["kda_safe_gate"] and config["no_kda_lora"]
    assert config["score_function"] == "sigmoid"
    assert "rope_interleave" not in config
    assert len(config["expert_swiglu_limit_list"]) == 42
    assert not any(config["expert_swiglu_limit_list"][i]
                   or config["share_expert_swiglu_limit_list"][i]
                   for i in config["kept_layers"])
    assert len(config["assumed"]) >= 12 and "64 chips" in config["deployment"]
    # the cut's arithmetic, from the shapes the weight maker hands the
    # program
    import jax
    shapes = kda_weights.decoder_shapes(config)

    def count(tree):
        return sum(math.prod(shape) for shape, _ in jax.tree.leaves(
            tree, is_leaf=kda_weights._is_leaf))

    dense, kda_moe, mla_moe = shapes["layers"]
    assert count(dense["kda"]) == 52_646_048
    assert count(kda_moe["kda"]) == 5 * 52_646_048
    # 31,883,776 in the projections and the latent's norm, and the two
    # head norms' 2 x 192
    assert count(mla_moe["mla"]) == 31_883_776 + 2 * 192
    assert count({k: dense[k] for k in ("w1", "w3", "w2")}) == 47_185_920
    routed = {k: kda_moe["moe"][k] for k in ("w1", "w3", "w2")}
    assert count(routed) == 5 * 8 * 5_898_240
    # the expert layer outside its routed experts, the router's 512-wide
    # bias being state beside the tree (7,209,472 with it)
    outside = count(mla_moe["moe"]) - count(
        {k: mla_moe["moe"][k] for k in ("w1", "w3", "w2")})
    assert outside + 512 == 7_209_472
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == \
        2 * 19_648 * 2560
    assert count(shapes) == kda_weights.parameter_count(config) \
        == config["parameters"] == 821_951_808
    # at 8 bytes a parameter as trained, beside the harness's copy
    assert 8 * config["parameters"] / 1e9 == pytest.approx(6.58, abs=0.01)
    kwargs = driver.model_kwargs(config, traffic["seq_len"])
    assert kwargs["layer_pattern"] == (
        ("kda", "dense", 1), ("kda", "moe", 5), ("mla", "moe", 1))
    assert (kwargs["moe_scoring"], kwargs["moe_route_scale"],
            kwargs["moe_top_k"], kwargs["moe_n_group"],
            kwargs["moe_topk_group"], kwargs["moe_experts_held"],
            kwargs["moe_shared_width"], kwargs["moe_aux_coeff"]) == (
        "sigmoid", 2.5, 8, 8, 4, (0, 8), 768, 0.0)
    assert kwargs["kda"] == dict(num_heads=32, head_dim=128, conv_kernel=4,
                                 chunk=64, lower=-5.0)
    assert kwargs["mla"] == dict(
        q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_interleave=False,
        qk_norm=True)
    # the six KDA mixers' projections take most of a token's forward
    per_token = costs.train_flops_per_token(config, 8192)
    plan = kda_weights.layer_plan(config)
    kda_share = sum(6 * costs.mixer_matmul_params(config, e) for e in plan
                    if e["mixer"] == "kda") \
        + 6 * 3 * costs.rule_flops_per_token(config)
    assert 0.5 < kda_share / per_token < 0.7
    # the rule as its equations require: a head's chunk of 64 is 182,955
    # operations a token forward, whatever precision computes them
    assert costs.rule_flops_per_token(config) == pytest.approx(
        32 * (2 * 64 * 5 * 128 + 2 * 64 * 64 / 3 + 6 * 128 * 128))
    fwd = costs.rule_call_cost(config, 2, 8192, backward=False)
    bwd = costs.rule_call_cost(config, 2, 8192, backward=True)
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] > fwd["bytes"]
    # BENCHMARK.json names the cell, and the three readers name only it
    benchmark = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [w for w in benchmark["workloads"] if w["name"] == NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("ling-3.0-flash-train", "pack8k", 1)
    (listed,) = [c for c in benchmark["configs"]
                 if c["name"] == "ling-3.0-flash-train"]
    assert listed["reduced"] == config["reduced"]
    assert listed["source"] == config["source"]
    mine = {m["name"] for m in benchmark["per_layer"]
            if m.get("workloads") == [NAME]}
    assert mine >= {"kda_fwd_roofline", "kda_bwd_roofline", "kda_layers_ms"}
    reported = {m["name"] for m in bench_run.metrics_of(
        benchmark, NAME, "per_layer")}
    assert reported >= mine | {
        "mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
        "step_mfu", "train_step_p50_ms", "step_attributed_pct",
        "remat_recompute_ms", "attn_proj_ms", "attn_kernels_ms", "ffn_ms",
        "experts_ms", "head_loss_ms"}
    assert not reported & {"delta_layers_ms", "ssm_layers_ms",
                           "mamba2_layers_ms", "flash_fwd_roofline"}


def test_the_new_readers_read_a_hand_made_trace_and_nothing_elsewhere():
    """Events of the two kernels at round times: the share is one call's
    least time times the calls over their time; a trace without them,
    or another configuration's file: nothing, and no raise."""
    _, config, _ = bench_run.load_cell(NAME)
    _, other, _ = bench_run.load_cell("train-qwen3-next.pack8k")
    ops = [["kda_fwd.3", i * 1e8, 20e6] for i in range(5)] + \
        [["kda_bwd.7", 1e9 + i * 1e8, 60e6] for i in range(5)]
    names = ("kda_fwd_roofline", "kda_bwd_roofline")

    def read(ops, cfg):
        ctx = {"trace": {"device_ops": {"/device:TPU:0": ops},
                         "host_spans": []},
               "config": cfg, "device_kind": "TPU v5 lite",
               "facts": {"rows": 2, "seq_len": 8192, "steps": 1}}
        return [bench_run._reader(name)(ctx) for name in names]

    assert read(ops, other) == [None] * 2
    assert read([], config) == [None] * 2
    fwd, bwd = read(ops, config)
    one = costs.rule_call_cost(config, 2, 8192, False)
    least = max(one["bytes"] / 819e9, one["flops"] / 197e12)
    assert fwd == pytest.approx(100 * least / 20e-3, rel=1e-6)
    assert 0 < fwd <= 100 and 0 < bwd <= 100
    from ray_tpu.util import tracing
    tracing.clear()
    ctx = {"trace": {"device_ops": {"/device:TPU:0": ops},
                     "host_spans": []}, "config": config,
           "device_kind": "TPU v5 lite", "facts": {"steps": 1}}
    assert bench_run._reader("kda_layers_ms")(ctx) is None
