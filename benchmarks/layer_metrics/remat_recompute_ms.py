"""Device milliseconds a step spent in the forward that ``jax.checkpoint``
runs a second time inside the backward pass, every scope (layer: model
step): instructions whose ``op_name`` has a ``rematted_computation``
component in the program's manifest of its step.  No manifest: nothing
is returned."""

from benchmarks.harness import step_scopes


def read(ctx):
    return step_scopes.recompute_ms(ctx)
