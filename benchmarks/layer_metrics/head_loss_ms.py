"""Device milliseconds a step in the heads and losses (layer: head): the
scopes ``head_loss``, ``mtp_loss``, ``mtp_module`` outside its layer's
scopes, and ``block_diffusion_loss``, by the program's manifest of its
step.  No manifest: nothing is returned."""

from benchmarks.harness import step_scopes


def read(ctx):
    return step_scopes.group_ms(ctx, "head_loss_ms")
