"""Device milliseconds a step in the Gated DeltaNet layers (layer:
attention): the scopes ``gdn_proj``, ``gdn_conv``, ``gdn_core``,
``gdn_out`` and ``gated_delta_bwd`` and the kernels' events
``gated_delta_fwd`` / ``gated_delta_bwd``, by the program's manifest of
its step.  It depends on no count of operations.  No manifest, or a step
without such a layer: nothing is returned."""

from benchmarks.harness import step_scopes


def read(ctx):
    return step_scopes.group_ms(ctx, "delta_layers_ms")
