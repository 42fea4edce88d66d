"""The forward KDA kernel's share of its roofline (layer: attention): the
least time the chip could take for a call's required operations and
bytes (each product of the chunked rule's forward once, at its
mathematical size; q, k, v, ``g`` and beta read, ``o`` and the step
states written: benchmarks/costs/kda_mla_moe.py) over the device
time of the kernel's events ``kda_fwd`` in the trace.  No such event:
nothing is returned."""

from benchmarks.costs import kda_mla_moe as costs


def read(ctx):
    return costs.rule_roofline_share(ctx, "kda_fwd", False)
