"""The forward flash-attention kernel's share of its roofline (layer:
attention): the least time the chip could take for the call's required
operations and bytes (benchmarks/harness/peaks.py, from B, L, H, D --
the same count whatever implements the kernel) over the device time of
the kernel's events in the trace.  Compute-bound at these shapes.  No
such event in the trace: nothing is returned."""

import re

from benchmarks.harness import peaks, trace_reduce

KERNEL = re.compile(r"flash_attention_fwd")


def read(ctx):
    found = trace_reduce.op_seconds(ctx["trace"], KERNEL)
    calls = sum(n for n, _ in found.values())
    seconds = sum(s for _, s in found.values())
    if not calls or not seconds:
        return None
    cfg = ctx["config"]
    cost = peaks.attention_fwd_cost(
        batch_heads=ctx["facts"]["rows"] * cfg["num_attention_heads"],
        seq_len=ctx["facts"]["seq_len"], head_dim=cfg["head_dim"],
        causal=True, itemsize=2)
    least = peaks.roofline(cost["flops"], cost["bytes"], ctx["device_kind"])
    return 100.0 * least["min_s"] * calls / seconds
