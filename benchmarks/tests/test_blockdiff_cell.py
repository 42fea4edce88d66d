"""The block-diffusion sparse-expert driver at a tiny size on the CPU: a
whole run ends in a well-formed result that is correct; the
lower-precision control and the causal-mask control, put in the
program's place, come out not correct; the committed cell's files say
what ISSUE 28 fixed.

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
from benchmarks.costs import block_diffusion_moe as costs  # noqa: E402
from benchmarks.drivers import trainer_blockdiff_steps as driver  # noqa: E402
from benchmarks.harness import compare  # noqa: E402

CONFIG = {
    "name": "tiny", "architecture": "block_diffusion_moe",
    "reference": "block_diffusion_moe", "costs": "block_diffusion_moe",
    "hidden_size": 64, "moe_intermediate_size": 32,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 128, "num_experts": 16,
    "num_experts_per_tok": 4, "num_experts_held": 4,
    "experts_held_first": 4, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "initializer_range": 0.02, "dtype": "float32", "remat": True,
    "router_aux_loss_coef": 0.0,
    "block_diffusion": {"block_length": 4, "t_min": 0.001,
                        "mask_token_id": 127},
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 3, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
# float32 program against the float32 reference, which follows the
# program's experts: summation order alone; the cell's own limits
# (bfloat16 program) are read on the chip and live in its workload file.
CELL = {"name": "tiny.blockdiff", "config": "tiny", "traffic": "blockdiff",
        "driver": "trainer_blockdiff_steps", "chips": 1,
        "check": {"steps": 2, "limits": {
            "grad1_norm_gap": 1e-3, "change_norm_gap": 2e-3,
            "routing_gap": 1e-3,
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
    assert line["correct"] is True and line["failed"] == 0
    # data tokens: 2 rows x 32 a step, each row run as 64 positions
    assert line["attempted"] > 0 and line["attempted"] % (2 * 32) == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["compared"]) == set(CELL["check"]["limits"])
    assert line["compared"]["moe_dropped_choices"]["value"] == 0
    err = capfd.readouterr().err
    facts = json.loads([l for l in err.splitlines()
                        if l.startswith('{"setup_s"')][0])["facts"]
    # 4 of 16 experts held, 4 choices a position, 2 x 64 positions
    assert 0 < facts["moe_held_choices"] < 2 * 64 * 4
    assert 0 < facts["masked_tokens"] <= 2 * 32
    assert facts["positions_per_row"] == 64


def _in_the_programs_place(seed, batches, **how):
    """A run's result as ``check`` takes it, with the reference under
    ``how`` where the program's numbers and experts would be."""
    control = driver.follow_reference(CELL, CONFIG, seed, batches, **how)
    return {"program": control, "first_choices": control["choices"],
            "first_batches": batches,
            "counts": {"compiles_in_window": 0, "nonfinite_losses": 0,
                       "moe_dropped_choices": 0.0}}


@pytest.mark.parametrize("how", [dict(precision="fp8"), dict(mask="causal"),
                                 dict(learning_rate=0.0), "half", "router"])
def test_the_control_in_the_programs_place_is_not_correct(how):
    """Through ``check`` and ``judge``, as a run goes: lower precision,
    another mask, a step that changes nothing, half the batch left out
    of the loss, and a router that takes its ninth expert for its
    eighth (caught by ``routing_gap`` alone)."""
    import numpy as np
    seed = 12345
    batches = driver.make_batches(CONFIG, TRAFFIC, seed)[:2]
    limits = CELL["check"]["limits"]
    honest = _in_the_programs_place(seed, batches)
    correct, compared = compare.judge(
        driver.check(CELL, CONFIG, seed, honest), limits)
    assert correct is True, compared
    assert compared["routing_gap"]["value"] == 0.0
    if how == "half":
        halved = [dict(b, weight=b["weight"] * np.array([[1.0], [0.0]],
                                                        np.float32))
                  for b in batches]
        result = dict(_in_the_programs_place(seed, halved),
                      first_batches=batches)
    elif how == "router":
        result = copy.deepcopy(honest)
        k = CONFIG["num_experts_per_tok"]
        for chosen in result["first_choices"]:
            # the expert after the last chosen one, wherever that is free
            chosen[..., k - 1] = (chosen[..., k - 1] + 1) % 16
    else:
        result = _in_the_programs_place(seed, batches, **how)
    correct, compared = compare.judge(
        driver.check(CELL, CONFIG, seed, result), limits)
    assert correct is False, compared
    if how == "router":
        assert compared["routing_gap"]["value"] > limits["routing_gap"]


def test_the_noise_is_block_constant_and_inside_the_slice():
    import numpy as np
    batches = driver.make_batches(CONFIG, TRAFFIC, 7)
    assert len(batches) == 3
    for b in batches:
        assert b["tokens"].shape == b["noisy"].shape == (2, 32)
        masked = b["noisy"] == 127
        assert (b["tokens"] < 127).all() and masked.any()
        assert ((b["weight"] > 0) == masked).all()
        t = 1.0 / b["weight"][masked]
        assert (t >= 0.001).all() and (t <= 1.0).all()
        # one t a block of 4: the weights of a block's masked tokens agree
        w = b["weight"].reshape(2, 8, 4)
        for row in w.reshape(-1, 4):
            assert len(set(np.round(row[row > 0], 4))) <= 1


def test_the_committed_cell_is_what_the_issue_fixed():
    cell, config, traffic = bench_run.load_cell(
        "train-sdar-30b-a3b.blockdiff4k")
    assert (traffic["rows"], traffic["seq_len"], traffic["pool_batches"],
            traffic["bos_id"]) == (4, 4096, 16, 0)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_experts_held"], config["vocab_size"]) == (
                6, 128, 16, 18992)
    # ISSUE 28: 645,623,296 parameters; 3.16 GFLOP a data token, of which
    # attention 1.21; 51.8 TFLOP a step
    d, v = config["hidden_size"], config["vocab_size"]
    layer = (2 * d * 32 * 128 + 2 * d * 4 * 128 + d * 128
             + 16 * 3 * d * 768 + 2 * d + 2 * 128)
    assert 6 * layer + 2 * v * d + d == 645_623_296
    per_token = costs.train_flops_per_token(config, 4096)
    assert per_token == pytest.approx(3.160e9, rel=1e-3)
    assert per_token * 4 * 4096 == pytest.approx(51.8e12, rel=2e-3)
    fwd = costs.flash_call_cost(config, 4, 4096, backward=False)
    assert fwd["flops"] == 4.0 * 128 * (4096 ** 2 + 4096 * 4) * 4 * 32
    assert costs.flash_call_cost(config, 4, 4096, backward=True)["flops"] \
        == 2 * fwd["flops"]
    kwargs = driver._model_kwargs(config, traffic["seq_len"])
    assert kwargs["moe_experts_held"] == (0, 16) and kwargs["n_kv_heads"] == 4
