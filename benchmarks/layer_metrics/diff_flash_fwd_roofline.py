"""The forward flash-attention kernel's share of its roofline over the
differential layers' calls of a step (layer: attention): two maps a
layer, 20 query heads of 64 over 10 key heads of 64 and value heads of
128, the pairs each layer's mask allows (the band of 512 in the window
layer, the causal triangle in the full and the cross layer).  The least
times of the step's calls, summed (benchmarks/costs/sambay_decoder.py),
over the device time a step of the kernel's events
``flash_attention_fwd``.  No such event: nothing is returned."""

from benchmarks.costs import sambay_decoder as costs


def read(ctx):
    return costs.flash_roofline_share(ctx, "flash_attention_fwd", False)
