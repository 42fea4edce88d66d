"""Device milliseconds a step in the scope ``ffn`` outside the expert
layer's ``moe_*`` scopes (layer: ffn): the dense SwiGLU, and every
layer's second norm and residual, by the program's manifest of its step.
No manifest: nothing is returned."""

from benchmarks.harness import step_scopes


def read(ctx):
    return step_scopes.group_ms(ctx, "ffn_ms")
