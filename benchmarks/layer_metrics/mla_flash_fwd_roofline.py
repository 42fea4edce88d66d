"""The forward flash-attention kernel's share of its roofline under
latent attention (layer: attention): scores over 128 + 64 columns, the
64 rotary ones against one key a position shared by the heads, values
over 128.  The least time the chip could take for the call's required
operations and bytes (benchmarks/costs/mla_moe_mtp.py: the causal half
of the pairs, the rotary key once a row) over the device time of the
kernel's events ``flash_attention_fwd`` in the trace.  Compute-bound."""

from benchmarks.costs.mla_moe_mtp import flash_roofline_share


def read(ctx):
    return flash_roofline_share(ctx, "flash_attention_fwd", backward=False)
