"""Spans and counters on the JAX path, into one process-wide registry.

A :class:`Span` is a named site in the program.  Each time it is entered it

* adds its wall nanoseconds and one call to the always-on counters
  ``span_ns{span=<name>}`` and ``span_count{span=<name>}`` of
  :data:`REGISTRY`, and
* while the JAX profiler is tracing, also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so that the span lands
  on the profiler's host plane, on the clock its device planes are read
  against.  Arguments (``step``, ``job``) are formatted only then.

Nothing switches this on or off: the counters always count, and the
profiler being on is what puts the spans in a trace.  The package stays
importable without JAX: a span looks for JAX's profiler only where JAX has
been imported, and no trace can be running where it has not.

:func:`install` adds two process-wide hooks, once however often it is
called: every garbage collection becomes a ``repro.gc`` span (argument
``generation``) and counts ``gc_collections{generation=<g>}``; every backend
compile (a persistent-cache read included) counts
``jax_compiles{program=<fun_name>}`` and adds its seconds to
``jax_compile_ns{program=<fun_name>}``.
"""
from __future__ import annotations

import gc
import sys
import threading
import time

from .metrics import MetricsRegistry

__all__ = ["REGISTRY", "Span", "Spanned", "install", "counter_deltas"]

#: The process's registry of spans, collections and compiles.
REGISTRY = MetricsRegistry()

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _annotation():
    """``jax.profiler.TraceAnnotation`` where a trace is running, else None."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    cls = profiler.TraceAnnotation
    return cls if cls.is_enabled() else None


class Span:
    """A named span site: ``with SITE(step=3): ...``."""

    __slots__ = ("name", "_ns", "_count")

    def __init__(self, name: str):
        self.name = name
        self._ns = REGISTRY.counter("span_ns", span=name)
        self._count = REGISTRY.counter("span_count", span=name)

    def __call__(self, **args) -> "_Open":
        return _Open(self, args)

    def record(self, ns: int) -> None:
        with self._ns._lock:  # the registry's one lock, held once for both
            self._ns.value += ns
            self._count.value += 1


class _Open:
    __slots__ = ("site", "args", "annotation", "t0")

    def __init__(self, site: Span, args: dict):
        self.site, self.args = site, args

    def __enter__(self) -> "_Open":
        cls = _annotation()
        self.annotation = None if cls is None else cls(self.site.name, **self.args)
        if self.annotation is not None:
            self.annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def annotate(self, **args) -> None:
        """Arguments known only inside the span (a job submitted in it)."""
        if self.annotation is not None:
            self.annotation.set_metadata(**args)

    def __exit__(self, *exc) -> None:
        self.site.record(time.perf_counter_ns() - self.t0)
        if self.annotation is not None:
            self.annotation.__exit__(*exc)


class Spanned:
    """``fn`` called inside ``span``; every other attribute (``lower``,
    ``trace``, ``__name__``) is ``fn``'s own."""

    def __init__(self, span: Span, fn):
        self._span, self._fn = span, fn

    def __call__(self, *args, **kwargs):
        with self._span():
            return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


# ------------------------------------------------------ process-wide hooks
_GC = Span("repro.gc")
_gc_open: list = []  # the collection in progress (CPython runs one at a time)
_install_lock = threading.Lock()
_installed = False


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_open.append(_GC(generation=info["generation"]).__enter__())
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)
        REGISTRY.counter("gc_collections", generation=info["generation"]).inc()


def _on_compile(event: str, duration_s: float, **kw) -> None:
    if event == COMPILE_EVENT:
        program = kw.get("fun_name", "unknown")
        REGISTRY.counter("jax_compiles", program=program).inc()
        REGISTRY.counter("jax_compile_ns", program=program).inc(int(duration_s * 1e9))


def install() -> None:
    """Add the collection and compile hooks to this process, once."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax

        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _installed = True


def counter_deltas(before: dict, after: dict) -> dict:
    """``after`` less ``before``, two ``snapshot()["counters"]``, for every
    counter of ``after`` (zero where it did not move)."""
    return {k: v - before.get(k, 0) for k, v in after.items()}
