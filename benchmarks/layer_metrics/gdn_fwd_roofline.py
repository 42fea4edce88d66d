"""The delta layers' forward state pass's share of its roofline (layer:
attention): the least time the chip could take for the pass's two
products a chunk and the bytes of W, U, Kd, V' and the float32 states it
writes (benchmarks/costs/gdn_gated_moe.py, from rows, positions, heads
and head sizes -- the same count whatever implements the pass) over the
device time of the kernel's events ``gated_delta_fwd`` in the trace.
Memory-bound.  No such event: nothing is returned."""

from benchmarks.costs import gdn_gated_moe as costs


def read(ctx):
    return costs.roofline_share(
        ctx, "gated_delta_fwd",
        lambda cfg, rows, seq: costs.state_pass_cost(cfg, rows, seq, False))
