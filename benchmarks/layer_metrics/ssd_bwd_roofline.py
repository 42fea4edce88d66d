"""The backward state-space kernel's share of its roofline (layer:
attention): as ``ssd_fwd_roofline`` with the backward's count (twice the
forward's products; the forward's operands and ``y``'s cotangent read,
five cotangents written) over the events ``ssd_bwd``."""

from benchmarks.costs import mamba2_latent_moe as costs


def read(ctx):
    return costs.rule_roofline_share(ctx, "ssd_bwd", True)
