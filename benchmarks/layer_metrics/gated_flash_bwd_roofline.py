"""The backward flash-attention kernel's share of its roofline at the
gated-attention layer's shape (layer: attention): as
``gated_flash_fwd_roofline`` with the backward's count (four products a
pair, dk and dv once a K/V head) over the events
``flash_attention_bwd``."""

from benchmarks.costs import gdn_gated_moe as costs


def read(ctx):
    return costs.roofline_share(
        ctx, "flash_attention_bwd",
        lambda cfg, rows, seq: costs.flash_call_cost(cfg, rows, seq, True))
