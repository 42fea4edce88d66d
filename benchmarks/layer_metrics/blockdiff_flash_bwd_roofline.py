"""The backward flash-attention kernel's share of its roofline under the
block-diffusion mask with grouped K/V heads (layer: attention): as
``blockdiff_flash_fwd_roofline`` with the backward's count (four
products a pair, dk and dv once per K/V head) over the events
``flash_attention_bwd``."""

from benchmarks.costs.block_diffusion_moe import flash_roofline_share


def read(ctx):
    return flash_roofline_share(ctx, "flash_attention_bwd", backward=True)
