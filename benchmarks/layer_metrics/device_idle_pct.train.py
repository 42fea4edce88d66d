"""1 - device-busy seconds over the traced window (layer: device)."""


def read(ctx):
    if not ctx["busy_s"] or not ctx["window_s"]:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
