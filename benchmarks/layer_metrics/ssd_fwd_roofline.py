"""The forward state-space kernel's share of its roofline (layer:
attention): the least time the chip could take for a call's required
operations and bytes (``X``, ``B``, ``C`` and the decays read, ``y``
written; benchmarks/costs/mamba2_latent_moe.py -- the same count
whatever implements the rule) over the device time of the kernel's
events ``ssd_fwd`` in the trace.  No such event: nothing is returned."""

from benchmarks.costs import mamba2_latent_moe as costs


def read(ctx):
    return costs.rule_roofline_share(ctx, "ssd_fwd", False)
