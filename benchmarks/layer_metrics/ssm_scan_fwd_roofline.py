"""The forward selective-scan kernel's share of its roofline (layer:
attention): the least time the chip could take for a call's required
bytes (c, delta, B, C read, y written, in the activations' type) and
operations (benchmarks/costs/sambay_decoder.py -- the same count
whatever implements the scan) over the device time of the kernel's
events ``selective_scan_fwd`` in the trace.  The table of peaks has no
vector rate, so this reads against HBM and is low: the scan is bound by
the vector unit.  No such event: nothing is returned."""

from benchmarks.costs import sambay_decoder as costs


def read(ctx):
    return costs.scan_roofline_share(ctx, "selective_scan_fwd", False)
