"""Device milliseconds a step in the two flash-attention kernels (layer:
attention): the events ``flash_attention_fwd`` and ``flash_attention_bwd``
and what the scope ``flash_attention_bwd`` holds beside its kernel (the
``delta`` reduction), by the program's manifest of its step.  No
manifest: nothing is returned."""

from benchmarks.harness import step_scopes


def read(ctx):
    return step_scopes.group_ms(ctx, "attn_kernels_ms")
