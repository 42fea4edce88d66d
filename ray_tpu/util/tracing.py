"""Task-level tracing / timeline profiling.

Parity: reference OpenTelemetry tracing (``tracing_helper.py:157,314`` —
spans around submit/execute, context propagated by injecting a
``_ray_trace_ctx`` into every traced remote call; here the context rides
a ``TaskSpec.trace_ctx`` field) and the C++ ``ProfileEvent`` timeline
(``src/ray/core_worker/profiling.h:64``) batched back to the driver and
dumped as chrome://tracing JSON via ``ray.timeline()``
(``python/ray/state.py:843``).

Workers in other OS processes record spans locally and piggyback them on
task replies (``drain``/``ingest``), the in-process analogue of the
reference's ProfileEvent batching to GCS.

The module holds three things.  One span API with two sinks and two
clocks:

* the ring (``enable()`` / ``force``): ``time.time()`` stamps, trace and
  parent ids, metadata — what ``ray_tpu.timeline()`` and the
  cross-process ``drain``/``ingest`` read;
* the XLA profiler's host plane: in a process where ``jax`` is already
  imported every span also opens a ``jax.profiler.TraceAnnotation``
  under its constant name, so a ``jax.profiler.start_trace`` anywhere in
  the process finds the program's spans on the clock of the device
  events.  The profiler drops the annotation unless a session is live,
  so this sink has no switch and is independent of ``enable()``.

And, third, the registry of compiled programs (``register_program`` /
``programs`` / ``device_time_by_scope``): for each program the process
registers (the train step, by ``models/transformer.py::_TracedStep``)
a manifest of the executable that runs -- which ``jax.named_scope`` of
``STEP_SCOPES`` and which pass (forward, backward, remat's second
forward) owns each instruction, which instructions only enclose others,
what memory the executable asks for -- so that a device trace's rows,
named ``fusion.586`` by the compiler, can be summed by the program's own
layers.  A manifest is made when it is first read, never when it is
registered: a run that reads none pays for none.

This module never imports ``jax`` itself: worker children, the GCS and
CPU-only drivers do not pay for it.
"""

from __future__ import annotations

import os
import re
import sys
import threading
import time
import uuid
from typing import Dict, Iterable, List, Optional, Tuple

from ray_tpu._private.debug.lock_order import diag_lock

_lock = diag_lock("tracing._lock")
_events: List[dict] = []
# Fixed-capacity ring: a long traced run must not grow memory forever
# (task-event buffer semantics — loss is bounded, counted, and visible).
# Oldest events are dropped first; the cumulative counter is surfaced as
# an instant event on every drain and at /metrics.
_MAX_EVENTS = 100_000
_max_events = _MAX_EVENTS
_dropped = 0
_dropped_reported = 0       # drop count already emitted on a drain
_enabled = False
_tls = threading.local()


def enable(flag: bool = True):
    global _enabled
    _enabled = flag


def is_enabled() -> bool:
    return _enabled


def set_capacity(n: int) -> None:
    """Resize the ring (tests); existing overflow is dropped+counted."""
    global _max_events, _dropped
    with _lock:
        _max_events = max(1, int(n))
        overflow = len(_events) - _max_events
        if overflow > 0:
            del _events[:overflow]
            _dropped += overflow


def dropped_count() -> int:
    with _lock:
        return _dropped


def num_buffered() -> int:
    with _lock:
        return len(_events)


def _append_locked(event: dict) -> None:
    """Ring append (callers hold ``_lock``): over capacity, the OLDEST
    events go — the tail of a long run is the part worth keeping.  The
    trim drops a BATCH (1/16th of capacity), not one slot: a per-append
    single-slot `del _events[:1]` on a full ring would memmove the
    whole list under the lock on every span, serializing all tracing
    threads on the hot path."""
    global _dropped
    if len(_events) >= _max_events:
        overflow = len(_events) - _max_events + 1
        trim = max(overflow, _max_events // 16)
        del _events[:trim]
        _dropped += trim
    _events.append(event)


_trace_annotation = None    # jax.profiler.TraceAnnotation, once found


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` if some other module has imported
    ``jax`` by now, else None (a half-imported ``jax`` reads as None
    too and is looked up again by the next span)."""
    global _trace_annotation
    if _trace_annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _trace_annotation = getattr(profiler, "TraceAnnotation", None)
    return _trace_annotation


def current_context() -> Optional[Dict]:
    """The innermost active span's propagatable context, if any."""
    stack = getattr(_tls, "stack", None)
    return dict(stack[-1]) if stack else None


class span:
    """RAII profile span (ProfileEvent parity).

    ``parent`` is an explicit trace context dict (e.g. a TaskSpec's
    ``trace_ctx`` on the executor side); without one, the thread's
    innermost active span is the parent.  ``force`` records the span
    even when process-wide capture is off — executors use it so a
    traced task from a remote driver is captured in a worker process
    that never called :func:`enable`.  Neither gates the profiler sink
    (module docstring), which gets the name alone: the trace's
    reduction keys on it, so ``meta`` goes to the ring only.
    """

    def __init__(self, name: str, category: str = "task",
                 parent: Optional[Dict] = None, force: bool = False,
                 **meta):
        self.name = name
        self.category = category
        self.meta = meta
        self.t0 = 0.0
        self._force = force
        self._parent = parent
        self._ctx: Optional[Dict] = None
        self._annotation = None

    @property
    def active(self) -> bool:
        return _enabled or self._force

    def context(self) -> Optional[Dict]:
        """Propagatable context (inject into TaskSpec.trace_ctx)."""
        return dict(self._ctx) if self._ctx else None

    def __enter__(self):
        annotation = _profiler_annotation()
        if annotation is not None:
            self._annotation = annotation(self.name)
            self._annotation.__enter__()
        if not self.active:
            return self
        self.t0 = time.time()
        parent = self._parent or current_context()
        self._ctx = {
            "trace_id": (parent or {}).get("trace_id") or uuid.uuid4().hex,
            "span_id": uuid.uuid4().hex[:16],
            "parent_id": (parent or {}).get("span_id"),
        }
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self._ctx)
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._ctx is None:
            return
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self._ctx:
            stack.pop()
        args = dict(self.meta)
        args.update(self._ctx)
        with _lock:
            _append_locked({
                "name": self.name,
                "cat": self.category,
                "ph": "X",
                "ts": self.t0 * 1e6,
                "dur": (time.time() - self.t0) * 1e6,
                "pid": os.getpid(),
                "tid": threading.get_ident() % 2**31,
                "args": args,
            })


def record_instant(name: str, **meta):
    if not _enabled:
        return
    with _lock:
        _append_locked({"name": name, "ph": "i", "ts": time.time() * 1e6,
                        "pid": os.getpid(),
                        "tid": threading.get_ident() % 2**31,
                        "s": "g", "args": meta})


def _drop_marker_locked(consume: bool) -> Optional[dict]:
    """Instant event accounting for ring overflow (loss must be visible
    in the trace itself, not only in a counter).  Only ``drain`` — the
    transfer-of-ownership path — advances the reported watermark; a
    read-only dump must keep showing the marker on every call (a second
    ``timeline()`` of a truncated run must not look complete)."""
    global _dropped_reported
    if consume:
        if _dropped <= _dropped_reported:
            return None
        since = _dropped - _dropped_reported
        _dropped_reported = _dropped
    else:
        if _dropped <= 0:
            return None
        since = _dropped - _dropped_reported
    return {"name": "tracing.dropped", "ph": "i",
            "ts": time.time() * 1e6, "pid": os.getpid(),
            "tid": threading.get_ident() % 2**31, "s": "g",
            "args": {"dropped_total": _dropped,
                     "dropped_since_last": since}}


def chrome_tracing_dump() -> List[dict]:
    with _lock:
        out = list(_events)
        marker = _drop_marker_locked(consume=False)
    if marker is not None:
        out.append(marker)
    return out


def drain() -> List[dict]:
    """Atomically remove and return buffered events (worker side: ship
    them back on the task reply)."""
    with _lock:
        out = list(_events)
        _events.clear()
        marker = _drop_marker_locked(consume=True)
    if marker is not None:
        out.append(marker)
    return out


def ingest(events: Optional[List[dict]]):
    """Merge events recorded in another process into this timeline."""
    if not events:
        return
    with _lock:
        for ev in events:
            _append_locked(ev)


def clear():
    global _dropped, _dropped_reported
    with _lock:
        _events.clear()
        _dropped = 0
        _dropped_reported = 0
        _programs.clear()


# ---- the registry of compiled programs ---------------------------------

#: Every name the train path passes to ``jax.named_scope``
#: (``ray_tpu/models``, ``ray_tpu/ops``; the raylet's solve program has
#: its own two and is not a step).  The models keep their literals;
#: ``tests/test_program_spans.py`` holds the literals to this list.
STEP_SCOPES = (
    "attention", "ffn", "head_loss", "optimizer",
    "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
    "moe_shared", "moe_bias",
    "mla_q", "mla_kv", "mla_out", "mtp_module", "mtp_loss",
    "gdn_proj", "gdn_conv", "gdn_core", "gdn_out", "attn_gate",
    "block_diffusion_loss", "flash_attention_bwd", "gated_delta_bwd",
    "ssm_proj", "ssm_conv", "ssm_scan", "ssm_out", "gmu", "diff_attn",
    "selective_scan_bwd", "mha_window",
    "ssd_proj", "ssd_conv", "ssd_rule", "ssd_norm", "ssd_out",
    "kda_proj", "kda_conv", "kda_core", "kda_out")
#: Of those, the ones that say which RUN of layers an instruction
#: belongs to, not which part of a layer: they own no instruction (the
#: part's scope does), and ``device_time_by_scope(..., within=)`` keeps
#: the rows that lie under one.  ``mha_window``: an ``mha`` run under a
#: window, beside the runs that see everything before.
RUN_SCOPES = ("mha_window",)
#: The names the train path's ``pallas_call``s are given: a device
#: trace's events of these kernels start with them.
KERNEL_EVENTS = ("flash_attention_fwd", "flash_attention_bwd",
                 "gated_delta_fwd", "gated_delta_bwd",
                 "selective_scan_fwd", "selective_scan_bwd",
                 "ssd_fwd", "ssd_bwd", "kda_fwd", "kda_bwd")
#: The passes of a step an instruction can belong to: the forward pass,
#: the backward pass, and the forward that ``jax.checkpoint`` runs again
#: inside the backward pass.
PHASES = ("fwd", "bwd", "recompute")
_SCOPES = frozenset(STEP_SCOPES) - frozenset(RUN_SCOPES)
_ENCLOSING_OPCODES = ("while", "conditional", "call")
_MEMORY_KEYS = ("temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes", "peak_memory_in_bytes")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
# The opcode is the first lower-case word before a "(" that follows
# white space: shapes and tiled layouts (``{1,0:T(8,128)}``) have none.
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRANSFORMED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")


def _scope_and_phase(path: str) -> Tuple[Optional[str], str, Optional[str]]:
    """``op_name`` -> (innermost component that is in ``STEP_SCOPES``
    and no run's, phase, the ``RUN_SCOPES`` component or None).  A
    component arrives bare or inside its transforms: ``attention``,
    ``jvp(ffn)``, ``transpose(jvp(head_loss))``."""
    parts = path.split("/")
    if "rematted_computation" in parts:
        phase = "recompute"
    elif any(part.startswith("transpose(") for part in parts):
        phase = "bwd"
    else:
        phase = "fwd"
    scope = run = None
    for part in reversed(parts):
        inner = _TRANSFORMED.match(part)
        while inner is not None:
            part = inner.group(1)
            inner = _TRANSFORMED.match(part)
        if scope is None and part in _SCOPES:
            scope = part
        if run is None and part in RUN_SCOPES:
            run = part
    return scope, phase, run


def manifest_of_text(text: str) -> dict:
    """The text of a compiled HLO module -> ``{"scopes": {instruction:
    (scope or None, phase)}, "enclosing": {instruction, ...}, "runs":
    {instruction: its ``RUN_SCOPES`` scope}}`` (``runs``: of the
    instructions under one alone): the one place where the rules are
    applied.  A fusion has the ``op_name`` XLA
    gave the fusion instruction; a Pallas kernel's custom call is its
    ``KERNEL_EVENTS`` name whatever scope encloses it; the expert
    layer's grouped product loses its scope in XLA (a ``ragged-dot``
    without metadata, on the TPU the custom calls ``ragged-dot-none.<n>``
    and ``ragged-dot-metadata.<n>`` under an ``op_name`` of their own, so
    also its pass: it is counted as ``fwd``) and is ``moe_experts``."""
    scopes, enclosing, runs = {}, set(), {}
    for line in text.splitlines():
        found = _INSTRUCTION.match(line)
        if found is None:
            continue
        name = found.group(1)
        call = _OPCODE.search(line, found.end() - 1)
        opcode = call.group(1) if call else ""
        if opcode in _ENCLOSING_OPCODES:
            enclosing.add(name)
        path = _OP_NAME.search(line, found.end())
        scope, phase, run = _scope_and_phase(path.group(1) if path else "")
        if opcode == "custom-call":
            scope = next((k for k in KERNEL_EVENTS if name.startswith(k)),
                         scope)
        if scope is None and name.startswith("ragged-dot"):
            scope = "moe_experts"
        if name not in scopes:
            scopes[name] = (scope, phase)
            if run is not None:
                runs[name] = run
    return {"scopes": scopes, "enclosing": enclosing, "runs": runs}


class _Program:
    """One registered program.  Subscripted (``["scopes"]``,
    ``["enclosing"]``, ``["memory"]``, ``["resolve_s"]``,
    ``["text_bytes"]``) it resolves first: ``lower(*abstract).compile()``
    of the jitted function and one pass over the executable's text.
    After a call of the function with arguments the abstract ones
    describe, jit's own caches answer both (JAX 0.9.0: no lowering, no
    compile, and the executable is the one that ran); otherwise it is a
    load from the persistent compile cache or a second compile.  Never
    inside a timed window: the jitted function and the abstract
    arguments are all it holds until then, and it lets go of them
    afterwards."""

    def __init__(self, jitted, abstract_args, memory=None):
        self._unresolved = (jitted, abstract_args, memory)
        self._manifest = None
        self._resolving = diag_lock("tracing._Program._resolving")

    def __getitem__(self, key):
        with self._resolving:
            if self._manifest is None:
                self._manifest = self._resolve(*self._unresolved)
                self._unresolved = None
        return self._manifest[key]

    @staticmethod
    def _resolve(jitted, abstract_args, more_memory) -> dict:
        t0 = time.perf_counter()
        compiled = jitted.lower(*abstract_args).compile()
        text = compiled.as_text()
        manifest = manifest_of_text(text)
        memory = compiled.memory_analysis()
        manifest["memory"] = {} if memory is None else {
            key: int(getattr(memory, key, 0)) for key in _MEMORY_KEYS}
        if more_memory is not None:
            manifest["memory"].update(more_memory())
        manifest["text_bytes"] = len(text)
        manifest["resolve_s"] = time.perf_counter() - t0
        return manifest


_programs: Dict[str, _Program] = {}


def register_program(name: str, jitted, *abstract_args,
                     memory=None) -> None:
    """Offer the program that ``jitted(*args)`` runs under ``name``;
    ``abstract_args`` are the arguments as ``jax.ShapeDtypeStruct``
    leaves (a donated state cannot be kept).  ``memory()``: what the
    program itself knows of its memory (the train step: what its layer
    scans keep for the backward pass), joined to the entry's
    ``"memory"`` when it resolves.  Costs nothing until
    ``programs()[name]`` is subscripted.  One entry a name: the last
    registration replaces, and frees, the one before."""
    program = _Program(jitted, abstract_args, memory)
    with _lock:
        _programs[name] = program


def programs() -> Dict[str, _Program]:
    """name -> the registered program, resolved when subscripted."""
    with _lock:
        return dict(_programs)


def device_time_by_scope(rows: Iterable[Tuple[str, float]],
                         program: str = "train_step",
                         within: Optional[str] = None) -> dict:
    """Device seconds by the program's own layers.  ``rows`` are
    ``(instruction name, seconds)`` of a device trace (any xplane's
    ``XLA Ops`` events, the name as the HLO has it, with or without
    ``%``) -> ``{scope or None: {"fwd": s, "bwd": s, "recompute": s},
    ..., "unknown": s}``: ``None`` holds what no scope of ``STEP_SCOPES``
    owns, ``"unknown"`` the rows of instructions the program does not
    have (another program's events in the window).  Instructions that
    only enclose others are left out, so time is counted once.
    ``within``: one of ``RUN_SCOPES`` -- the program's instructions
    under that run's scope alone (kernels' calls too: a call keeps the
    path it was made under).  Raises ``KeyError`` where no such program
    is registered."""
    entry = programs()[program]
    scopes, enclosing = entry["scopes"], entry["enclosing"]
    runs = entry["runs"] if within is not None else {}
    out = {"unknown": 0.0}
    for name, seconds in rows:
        name = name.lstrip("%")
        if name in enclosing:
            continue
        if name not in scopes:
            out["unknown"] += seconds
            continue
        if within is not None and runs.get(name) != within:
            continue
        scope, phase = scopes[name]
        out.setdefault(scope, dict.fromkeys(PHASES, 0.0))[phase] += seconds
    return out


# /metrics surface for the ring's loss accounting — a scrape-time
# collector on a module-lifetime owner (the tracing buffer is process
# state, so its series never need churn-pruning).
class _TracingStatsOwner:
    pass


_stats_owner = _TracingStatsOwner()


def _register_stats_collector():
    try:
        from ray_tpu._private.metrics_agent import (get_metrics_registry,
                                                    record_internal)
    except Exception:       # circular-import guard at bootstrap
        return

    def _collect(_owner):
        with _lock:
            dropped, buffered = _dropped, len(_events)
        record_internal("ray_tpu.tracing.dropped_events", dropped)
        record_internal("ray_tpu.tracing.buffered_events", buffered)

    get_metrics_registry().register_collector(_stats_owner, _collect)


_register_stats_collector()
