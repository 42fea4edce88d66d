"""Device milliseconds a step in attention outside its kernels (layer:
attention): the projections, norms, RoPE and gate -- the scopes
``attention`` (outside the delta layers' scopes), ``mla_q``, ``mla_kv``,
``mla_out`` and ``attn_gate`` of the program's manifest of its step.  No
manifest: nothing is returned."""

from benchmarks.harness import step_scopes


def read(ctx):
    return step_scopes.group_ms(ctx, "attn_proj_ms")
