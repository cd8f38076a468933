"""What jax spent obtaining executables, read from its monitoring events.

Copied in idea from chip_smoke._CompileClock (sound source, see PERF.md):
`backend_compile_duration` fires once per executable obtained, whether
XLA compiled it or the persistent cache answered, so its count inside the
measured window is the number of programs the warm-up missed.
"""
import time

_BACKEND = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


class CompileClock:
    """Register once, before the first compile. `executables` holds
    (monotonic time at the end, seconds, function name) per executable
    obtained; `trace_lower_s` is the seconds jax spent tracing to jaxprs
    and lowering them to MLIR (python work no cache saves)."""

    def __init__(self):
        import jax.monitoring as mon
        self.executables = []
        self.trace_lower_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == _BACKEND:
            self.executables.append(
                (time.monotonic(), secs, kw.get("fun_name", "?")))
        elif event in (_TRACE, _LOWER):
            self.trace_lower_s += secs

    def _event(self, event, **_):
        if event == _HIT:
            self.cache_hits += 1
        elif event == _MISS:
            self.cache_misses += 1

    def compile_s(self, before):
        """Seconds obtaining executables that ended before `before`."""
        return sum(s for t, s, _ in self.executables if t <= before)

    def obtained_between(self, t0, t1):
        """Names of the executables obtained in [t0, t1]."""
        return [name for t, _, name in self.executables if t0 <= t <= t1]
