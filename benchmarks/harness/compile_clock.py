"""Seconds JAX spent tracing, lowering and compiling, and the persistent
cache's hits and misses, from ``jax.monitoring`` -- so work on worker
threads counts too.  Copied from ``chip_smoke.CompileClock`` (the
yardstick keeps its own copy); ``lowerings`` is added so a window can
say "nothing compiled in here"."""

_DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileClock:
    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.lowerings = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name in _DURATIONS:
            self.seconds += secs
        if name == _LOWERING:
            self.lowerings += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "cache_hits": self.hits,
                "cache_misses": self.misses, "lowerings": self.lowerings}


_CLOCK = None


def clock() -> CompileClock:
    """The process's one clock (listeners cannot be unregistered, and a
    worker thread must read the same object the entry point made)."""
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK
