"""From a profiler trace to numbers: device busy seconds, time by
operation, idle gaps named by what the host was doing.  One module, so
every PR computes the same number the same way; checked on the small
recorded trace under ``benchmarks/recorded/``.

A trace is reduced first to a plain dict (``load``), which is also the
recorded format:

    {"device_ops": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "host_spans": [[name, start_ns, dur_ns], ...]}

Device operations are the events of each device plane's ``XLA Ops``
line.  The profiler names an event by the whole HLO instruction
(``%fusion.12 = bf16[...] fusion(...)``): only the instruction's own
name is kept (``fusion.12``; a Pallas kernel keeps the name its
``pallas_call`` was given).  Control-flow operations that only enclose
others (``while``, ``conditional``, ``call``) are dropped, so time is
counted once.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
_ENCLOSING = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def start(trace_dir: str) -> None:
    """Start the profiler the way every driver does: device and host
    tracers on, the Python call tracer off (it is most of a trace's
    bytes and slows a host-bound window)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


#: Host spans kept: the drivers' own annotations and the program's tick.
SPAN_PREFIXES = ("train.", "round.", "scheduler.")


def load(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    device_ops, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        [op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)]
                        for e in line.events
                        if not _ENCLOSING.match(op_name(e.name))]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIXES))
    return {"device_ops": device_ops, "host_spans": host_spans}


def _merge(intervals):
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def busy_intervals(ops) -> list:
    return _merge([s, s + d] for _, s, d in ops)


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran on the device: the union of the
    operations' intervals, averaged over the chips in the trace."""
    planes = trace["device_ops"]
    if not planes:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(ops))
               for ops in planes.values()) / len(planes) / 1e9


def op_seconds(trace: dict, match=None) -> dict:
    """name -> [calls, seconds], summed over chips; ``match`` is a
    substring or compiled pattern on the name."""
    out = {}
    for ops in trace["device_ops"].values():
        for name, _, dur in ops:
            if match is not None and not (
                    match.search(name) if hasattr(match, "search")
                    else match in name):
                continue
            t = out.setdefault(name, [0, 0.0])
            t[0] += 1
            t[1] += dur / 1e9
    return out


def top_ops(trace: dict, k: int = 10) -> list:
    """[[name, seconds], ...]: the operations that took most device
    time, numbered variants of one operation (``fusion.12``) apart."""
    rows = sorted(((n, s) for n, (_, s) in op_seconds(trace).items()),
                  key=lambda r: -r[1])
    return [[n, s] for n, s in rows[:k]]


def idle_gaps(trace: dict, k: int = 10, other: str = "no_span") -> list:
    """[[host span, seconds], ...]: the device's idle time between its
    first and last operation, by the host span open at the middle of
    each gap (the shortest such span: the innermost), longest first."""
    by_span = {}
    spans = trace["host_spans"]
    for ops in trace["device_ops"].values():
        busy = busy_intervals(ops)
        for (_, end), (start, _) in zip(busy, busy[1:]):
            mid = (end + start) / 2
            open_ = [(d, n) for n, s, d in spans if s <= mid <= s + d]
            name = min(open_)[1] if open_ else other
            by_span[name] = by_span.get(name, 0.0) + (start - end) / 1e9
    n_chips = max(1, len(trace["device_ops"]))
    rows = sorted(by_span.items(), key=lambda r: -r[1])[:k]
    return [[n, s / n_chips] for n, s in rows]
