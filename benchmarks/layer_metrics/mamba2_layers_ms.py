"""Device milliseconds a step in the Mamba-2 mixers (layer: attention):
the scopes ``ssd_proj``, ``ssd_conv`` (the convolution's two kernels
among its instructions), ``ssd_rule``, ``ssd_norm`` and ``ssd_out`` and
the rule's kernels' events ``ssd_fwd`` / ``ssd_bwd``, by the program's
manifest of its step.  It depends on no count of operations.  No
manifest, or a step without such a mixer: nothing is returned."""

from benchmarks.harness import step_scopes

SCOPES = ("ssd_proj", "ssd_conv", "ssd_rule", "ssd_norm", "ssd_out",
          "ssd_fwd", "ssd_bwd")


def read(ctx):
    table = step_scopes.by_scope_ms(ctx)
    if table is None:
        return None
    found = [table[s] for s in SCOPES if s in table]
    return sum(sum(phases.values()) for phases in found) if found else None
