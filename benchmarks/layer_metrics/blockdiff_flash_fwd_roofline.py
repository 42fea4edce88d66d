"""The forward flash-attention kernel's share of its roofline under the
block-diffusion mask with grouped K/V heads (layer: attention): the
least time the chip could take for the call's required operations and
bytes (benchmarks/costs/block_diffusion_moe.py: only the pairs the mask
admits, K and V once per K/V head) over the device time of the kernel's
events ``flash_attention_fwd`` in the trace.  Compute-bound."""

from benchmarks.costs.block_diffusion_moe import flash_roofline_share


def read(ctx):
    return flash_roofline_share(ctx, "flash_attention_fwd", backward=False)
