"""Median duration of the raylet's working tick in the traced window
(layer: raylet tick): the program's ``scheduler.tick`` span.  How the
tick divides among its child spans is ``breakdown.idle_gaps``.  No entry
in BENCHMARK.json until a raylet cell reports ``sched_placed_per_s``.  A
trace without the span: nothing is returned."""

import statistics


def read(ctx):
    ticks = [d for n, _, d in ctx["trace"]["host_spans"]
             if n == "scheduler.tick"]
    return statistics.median(ticks) / 1e6 if ticks else None
