"""Device milliseconds a step in the KDA layers (layer: attention): the
scopes ``kda_proj``, ``kda_conv`` (the convolution's two kernels among
its instructions), ``kda_core`` and ``kda_out`` and the rule's kernels'
events ``kda_fwd`` / ``kda_bwd``, by the program's manifest of its step.
It depends on no count of operations.  No manifest, or a step without
such a layer: nothing is returned."""

from benchmarks.harness import step_scopes

SCOPES = ("kda_proj", "kda_conv", "kda_core", "kda_out", "kda_fwd",
          "kda_bwd")


def read(ctx):
    table = step_scopes.by_scope_ms(ctx)
    if table is None:
        return None
    found = [table[s] for s in SCOPES if s in table]
    return sum(sum(phases.values()) for phases in found) if found else None
