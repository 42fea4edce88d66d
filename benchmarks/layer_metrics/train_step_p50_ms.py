"""Median, over the window's steps, of the benchmark's own clock between
one step's loss reaching the host and the next one's (layer: train
worker -- actor thread, dispatch, report loop)."""

import statistics


def read(ctx):
    steps = ctx["facts"].get("step_seconds")
    if not steps or len(steps) < 3:
        return None
    # the first interval holds the pipeline's fill, the last its drain
    return statistics.median(steps[1:-1]) * 1e3
