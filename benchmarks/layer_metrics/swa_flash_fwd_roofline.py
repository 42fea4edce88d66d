"""The forward flash-attention kernel's share of its roofline over a
step's calls where the layers are of two kinds (layer: attention):
window layers at one head count (the band of ``sliding_window`` keys a
query), full layers at another (the causal triangle), both over grouped
K/V heads read once a K/V head.  The least times of the step's calls,
summed (benchmarks/costs/swa_gqa_moe.py), over the device time a step of
the kernel's events ``flash_attention_fwd``.  No such event: nothing is
returned."""

from benchmarks.costs import swa_gqa_moe as costs


def read(ctx):
    return costs.flash_roofline_share(ctx, "flash_attention_fwd", False)
