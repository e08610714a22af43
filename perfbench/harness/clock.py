"""Compile accounting from JAX's monitoring events."""
from __future__ import annotations

import time


class CompileClock:
    """Seconds JAX spends lowering and compiling (persistent-cache reads
    included), the number of backend compiles, and persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def now() -> float:
    return time.perf_counter()


class Profiler:
    """The profiler over the first part of a window, inside a
    ``bench.window`` span; the seconds spent starting and stopping it are
    kept apart, so that rates in a traced run leave them out."""

    def __init__(self, trace_dir, obs, t0: float):
        import jax

        self.obs, self.t0 = obs, t0
        t = now()
        jax.profiler.start_trace(str(trace_dir))
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        self.start = now() - t0
        obs.profiler_s += now() - t
        self.on = True

    def stop(self) -> None:
        if not self.on:
            return
        import jax

        self.span.__exit__(None, None, None)
        end = now() - self.t0
        jax.profiler.stop_trace()
        self.obs.trace_window = (self.start, end)
        self.obs.profiler_s += now() - self.t0 - end
        self.on = False
