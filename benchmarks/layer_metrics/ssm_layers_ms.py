"""Device milliseconds a step in the state-space layers (layer:
attention): the scopes ``ssm_proj``, ``ssm_conv``, ``ssm_scan``,
``ssm_out``, ``gmu`` and ``selective_scan_bwd`` and the kernels' events
``selective_scan_fwd`` / ``selective_scan_bwd``, by the program's
manifest of its step.  It depends on no count of operations.  No
manifest, or a step without such a layer: nothing is returned."""

from benchmarks.harness import step_scopes

SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_out", "gmu",
          "selective_scan_fwd", "selective_scan_bwd")


def read(ctx):
    table = step_scopes.by_scope_ms(ctx)
    if table is None:
        return None
    found = [table[s] for s in SCOPES if s in table]
    return sum(sum(phases.values()) for phases in found) if found else None
