"""The backward KDA kernel's share of its roofline (layer: attention): as
``kda_fwd_roofline`` with the backward's count (twice the forward's
operations, nothing recomputed counted; the forward's operands and
``o``'s cotangent read, five cotangents written) over the events
``kda_bwd``."""

from benchmarks.costs import kda_mla_moe as costs


def read(ctx):
    return costs.rule_roofline_share(ctx, "kda_bwd", True)
