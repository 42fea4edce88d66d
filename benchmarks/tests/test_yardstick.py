"""The yardstick's arithmetic against numbers worked by hand, and the
generators' determinism."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.costs import dense_decoder as costs  # noqa: E402
from benchmarks.harness import compare, peaks, traffic  # noqa: E402

SMALL = {"hidden_size": 8, "num_attention_heads": 2, "head_dim": 4,
         "num_key_value_heads": 2, "intermediate_size": 12,
         "num_hidden_layers": 3, "vocab_size": 10}


def test_dense_decoder_counts_by_hand():
    # a layer: q, k, v, o = 4 * 8 * 8 = 256; SwiGLU 3 * 8 * 12 = 288;
    # head 8 * 10 = 80
    assert costs.matmul_params(SMALL) == 3 * (256 + 288) + 80 == 1712
    # + embedding 80 + norms (2 * 3 + 1) * 8 = 56
    assert costs.total_params(SMALL) == 1712 + 80 + 56
    # 6 a matmul parameter + causal attention: 3 layers * 3 (fwd + bwd)
    # * 4 * S * d_attn / 2 with S = 16, d_attn = 8
    assert costs.train_flops_per_token(SMALL, 16) == \
        6 * 1712 + 3 * 3 * 4 * 16 * 8 / 2


def test_the_published_config_counts():
    import json
    cfg = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "dscoder-1b3-train.json")))
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5504
    assert costs.matmul_params(cfg) == 12 * per_layer + 2048 * 32256
    assert per_layer == 50_593_792          # "50.6 M parameters a layer"
    assert costs.total_params(cfg) == 739_297_280


def test_attention_cost_and_roofline_by_hand():
    c = peaks.attention_fwd_cost(batch_heads=64, seq_len=4096, head_dim=128,
                                 causal=True, itemsize=2)
    assert c["flops"] == 2 * 2 * 64 * 4096 * 4096 * 128 / 2
    assert c["bytes"] == 4 * 64 * 4096 * 128 * 2 + 4 * 64 * 4096
    r = peaks.roofline(c["flops"], c["bytes"], "TPU v5 lite")
    assert r["bound"] == "compute"
    assert r["min_s"] == pytest.approx(c["flops"] / 197e12)
    m = peaks.roofline(1e9, 819e9, "TPU v5 lite")
    assert m["bound"] == "memory" and m["min_s"] == pytest.approx(1.0)
    with pytest.raises(ValueError):
        peaks.chip_peaks("cpu")


def test_worst_leaf_gap_is_a_gap_of_norms_against_the_larger_norm():
    ref = {"a": np.array([1.0, 2.0, 4.0]), "b": np.array([0.001])}
    prog = {"a": np.array([1.1, 2.0, 4.0]), "b": np.array([0.101])}
    # median leaf norm is 1.5: b's gap 0.1 is measured against 1.5, a[0]'s
    # 0.1 against 1.5 too (its own norm 1.0 is smaller)
    gap, where = compare.worst_leaf_gap(prog, ref)
    assert gap == pytest.approx(0.1 / 1.5)
    prog["a"][2] = 2.0                       # a leaf that moved half
    gap, where = compare.worst_leaf_gap(prog, ref)
    assert where == "a[2]" and gap == pytest.approx(0.5)
    keep = {"a": np.array([True, True, False]), "b": np.array([True])}
    assert compare.worst_leaf_gap(prog, ref, keep)[0] == pytest.approx(
        0.1 / 1.5)


def test_leaves_with_no_gradient_are_left_out_by_rule_not_by_name():
    grad = {"w": np.array([1.0, 2.0, 3.0]), "bias": np.array([1e-9])}
    keep = compare.moving_leaves(grad)
    assert keep["w"].all() and not keep["bias"].any()


def test_judge_needs_a_limit_for_every_number():
    numbers = {"change_norm_gap": (0.5, ""), "grad1_norm_gap": (0.1, "w[0]")}
    ok, compared = compare.judge(numbers, {"change_norm_gap": 1.0,
                                           "grad1_norm_gap": 0.05})
    assert ok is False and compared["change_norm_gap"]["limit"] == 1.0
    assert compare.judge(numbers, {"change_norm_gap": 1.0,
                                   "grad1_norm_gap": 0.1})[0] is True
    with pytest.raises(KeyError):
        compare.judge(numbers, {"change_norm_gap": 1.0})


def test_packed_documents_from_the_seed():
    spec = {"kind": "packed_documents", "rows": 4, "seq_len": 64,
            "pool_batches": 3, "doc_len": {"alpha": 1.2, "min": 8, "max": 40},
            "bos_id": 7}
    big = 2**31 + 12345
    a = traffic.generate(spec, big, vocab_size=100)
    b = traffic.generate(spec, big, vocab_size=100)
    c = traffic.generate(spec, big + 1, vocab_size=100)
    assert a.shape == (3, 4, 65) and a.dtype == np.int32
    assert (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 100
    rows = a.reshape(-1, 65)
    assert len({r.tobytes() for r in rows}) == len(rows)   # all differ
    with pytest.raises(ValueError):
        traffic.generate({"kind": "nope"}, 1)
