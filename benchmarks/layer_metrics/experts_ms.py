"""Device milliseconds a step in the expert layers (layer: experts): the
scopes ``moe_router``, ``moe_dispatch``, ``moe_experts`` (with XLA's
``ragged-dot``, which carries no scope), ``moe_combine``, ``moe_shared``
and ``moe_bias``, by the program's manifest of its step.  No manifest,
or a step without an expert layer: nothing is returned."""

from benchmarks.harness import step_scopes


def read(ctx):
    return step_scopes.group_ms(ctx, "experts_ms")
