"""The delta layers' backward state pass's share of its roofline (layer:
attention): as ``gdn_fwd_roofline`` with the transpose's count (four
products a chunk; W, Kd, V', the states and both cotangents read, three
cotangents written) over the events ``gated_delta_bwd``."""

from benchmarks.costs import gdn_gated_moe as costs


def read(ctx):
    return costs.roofline_share(
        ctx, "gated_delta_bwd",
        lambda cfg, rows, seq: costs.state_pass_cost(cfg, rows, seq, True))
