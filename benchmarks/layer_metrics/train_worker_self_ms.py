"""The train worker's own host time per step of the traced window
(layer: train worker), measured where the work happens: the median
duration of the program's ``train.model_step`` span (host dispatch of
the jitted step: it returns before the device finishes) plus the median
of its ``train.report`` span (the hand-off to the Trainer; nothing for a
worker that never reports).  Both come from the profiler's trace.  A
program without the spans: nothing is returned."""

import statistics


def _median_ms(spans, name):
    durations = [d for n, _, d in spans if n == name]
    return statistics.median(durations) / 1e6 if durations else None


def read(ctx):
    spans = ctx["trace"]["host_spans"]
    step = _median_ms(spans, "train.model_step")
    if step is None:
        return None
    return step + (_median_ms(spans, "train.report") or 0.0)
