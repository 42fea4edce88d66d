"""The backward selective-scan kernel's share of its roofline (layer:
attention): as ``ssm_scan_fwd_roofline`` with the backward's count (its
five cotangents written, the chunks' entering states read, twice the
operations) over the events ``selective_scan_bwd``."""

from benchmarks.costs import sambay_decoder as costs


def read(ctx):
    return costs.scan_roofline_share(ctx, "selective_scan_bwd", True)
