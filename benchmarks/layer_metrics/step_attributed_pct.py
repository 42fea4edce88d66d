"""The share of the traced window's device time that the program's
manifest of its step gives to one of its named scopes or kernels (layer:
model step).  What the other scope metrics cover; a fall says the map
has gone stale (a scope dropped by the compiler, a new layer without a
scope).  No manifest: nothing is returned."""

from benchmarks.harness import step_scopes


def read(ctx):
    return step_scopes.attributed_pct(ctx)
