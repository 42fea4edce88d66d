"""The forward flash-attention kernel's share of its roofline at the
gated-attention layer's shape (layer: attention): 256-wide heads, 8
query heads a K/V head.  The least time the chip could take for the
call's required operations and bytes (benchmarks/costs/gdn_gated_moe.py:
the causal half of the pairs over 256 + 256 columns, K and V once a K/V
head) over the device time of the kernel's events
``flash_attention_fwd`` in the trace.  Compute-bound."""

from benchmarks.costs import gdn_gated_moe as costs


def read(ctx):
    return costs.roofline_share(
        ctx, "flash_attention_fwd",
        lambda cfg, rows, seq: costs.flash_call_cost(cfg, rows, seq, False))
