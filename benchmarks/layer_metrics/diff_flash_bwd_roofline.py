"""The backward flash-attention kernel's share of its roofline over the
differential layers' calls of a step (layer: attention): as
``diff_flash_fwd_roofline`` with the backward's count (four products a
pair, dk and dV once a K/V head) over the events
``flash_attention_bwd``."""

from benchmarks.costs import sambay_decoder as costs


def read(ctx):
    return costs.flash_roofline_share(ctx, "flash_attention_bwd", True)
