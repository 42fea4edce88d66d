"""The backward flash-attention kernel's share of its roofline under
latent attention (layer: attention): as ``mla_flash_fwd_roofline`` with
the backward's count (four products a pair; dq over 192 columns, dk and
dv per head, the rotary key's gradient once a row) over the events
``flash_attention_bwd``."""

from benchmarks.costs.mla_moe_mtp import flash_roofline_share


def read(ctx):
    return flash_roofline_share(ctx, "flash_attention_bwd", backward=True)
