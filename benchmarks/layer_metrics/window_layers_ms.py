"""Device milliseconds a step in the attention halves of the layers
that attend under a window (layer: attention): every instruction of the
step -- the norm, the projections, the rotary, both flash kernels'
calls, the gate, the residual's sum -- that the program's manifest puts
under its run scope ``mha_window``
(``ray_tpu.util.tracing.device_time_by_scope(..., within=)``), forward,
backward and remat's second forward together.  It depends on no count of
operations.  No manifest, a program whose manifest knows no runs, or a
step without such a layer: nothing is returned."""

from benchmarks.harness import trace_reduce

RUN = "mha_window"


def read(ctx):
    from ray_tpu.util import tracing
    steps = ctx["facts"].get("steps")
    registry = getattr(tracing, "programs", None)
    entry = registry().get("train_step") if registry else None
    if not steps or entry is None \
            or RUN not in getattr(tracing, "RUN_SCOPES", ()):
        return None
    rows = ((name, seconds) for name, (_, seconds)
            in trace_reduce.op_seconds(ctx["trace"]).items())
    table = tracing.device_time_by_scope(rows, within=RUN)
    seconds = sum(sum(phases.values()) for scope, phases in table.items()
                  if scope != "unknown")
    if not seconds:
        return None
    return seconds * 1e3 / steps / max(1, len(ctx["trace"]["device_ops"]))
