"""Device-busy milliseconds per step in the traced window (layer: model
step): steadier than the host's clock, and what is left when the host
is taken away."""


def read(ctx):
    steps = ctx["facts"].get("steps")
    if not steps or not ctx["busy_s"]:
        return None
    return ctx["busy_s"] / steps * 1e3
