"""The backward flash-attention kernel's share of its roofline over a
step's calls where the layers are of two kinds (layer: attention): as
``swa_flash_fwd_roofline`` with the backward's count (four products a
pair, dk and dv once a K/V head) over the events
``flash_attention_bwd``."""

from benchmarks.costs import swa_gqa_moe as costs


def read(ctx):
    return costs.flash_roofline_share(ctx, "flash_attention_bwd", True)
