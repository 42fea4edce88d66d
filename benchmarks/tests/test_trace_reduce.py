"""The trace -> metrics reduction, on a hand-made trace and on the small
recorded one (two steps of the train cell on a v5e chip)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import peaks, trace_reduce as tr  # noqa: E402

MS = 1e6
HAND = {
    "device_ops": {"/device:TPU:0": [
        ["fusion.1", 0 * MS, 10 * MS],
        ["fusion.2", 5 * MS, 10 * MS],        # overlaps fusion.1: union 15
        ["kernel_fwd.3", 20 * MS, 5 * MS],    # gap 15..20 under step
        ["fusion.1", 40 * MS, 10 * MS],       # gap 25..40 under wait
    ]},
    "host_spans": [["train.step", 0, 22 * MS], ["train.wait", 22 * MS, 30 * MS],
                   ["outer", 0, 60 * MS]],
}


def test_names_are_the_instructions_own():
    assert tr.op_name("%fusion.12 = bf16[4,8]{1,0} fusion(bf16[4] %p)") == \
        "fusion.12"
    assert tr.op_name("flash_attention_fwd.17") == "flash_attention_fwd.17"
    assert tr._ENCLOSING.match("while.84") and tr._ENCLOSING.match("call")
    assert not tr._ENCLOSING.match("while_fusion_thing")


def test_busy_is_a_union_and_gaps_are_named_by_the_innermost_span():
    assert tr.busy_seconds(HAND) == pytest.approx(0.030)
    assert tr.top_ops(HAND, 2) == [["fusion.1", pytest.approx(0.020)],
                                   ["fusion.2", pytest.approx(0.010)]]
    assert tr.idle_gaps(HAND) == [["train.wait", pytest.approx(0.015)],
                                  ["train.step", pytest.approx(0.005)]]
    assert tr.op_seconds(HAND, "kernel_fwd") == {
        "kernel_fwd.3": [1, pytest.approx(0.005)]}
    assert tr.busy_seconds({"device_ops": {}, "host_spans": []}) == 0.0


def test_the_recorded_trace():
    trace = json.load(open(os.path.join(
        ROOT, "benchmarks", "recorded", "train_two_steps.json")))
    # two 1.4335 s steps; operations under 0.2 ms were left out of the
    # recording, so busy is a little under the window
    assert tr.busy_seconds(trace) == pytest.approx(2.7623, abs=1e-3)
    flash = tr.op_seconds(trace, "flash_attention_fwd")
    calls = sum(n for n, _ in flash.values())
    seconds = sum(s for _, s in flash.values())
    # 12 layers x (forward + remat's recomputation) x 2 steps
    assert calls == 48 and seconds == pytest.approx(0.6242, abs=1e-3)
    cost = peaks.attention_fwd_cost(64, 4096, 128, True, 2)
    least = peaks.roofline(cost["flops"], cost["bytes"], "TPU v5 lite")
    assert 100 * least["min_s"] * calls / seconds == pytest.approx(
        10.73, abs=0.05)
    assert tr.top_ops(trace, 1)[0][0] == "convolution_add_fusion.6"
    assert tr.idle_gaps(trace)[0][0] == "train.wait"
    assert not any(tr._ENCLOSING.match(n)
                   for n, _, _ in trace["device_ops"]["/device:TPU:0"])
