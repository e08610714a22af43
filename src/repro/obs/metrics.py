"""Labelled counters, gauges and histograms: the telemetry plane's registry.

This module imports nothing from the package, so that every layer can count
into a :class:`MetricsRegistry`: the Fix frontend (which the runtime imports,
so it cannot import the runtime), the JAX path (:mod:`repro.obs.spans`) and
the runtime (:mod:`repro.runtime.telemetry` re-exports these names).
"""
from __future__ import annotations

import threading

__all__ = ["DEFAULT_BUCKETS", "Counter", "Gauge", "Histogram", "MetricsRegistry"]

#: Fixed histogram bucket edges (seconds): µs-scale codelets up through
#: multi-minute jobs.  Fixed at import time so snapshots never depend on
#: observed data — the determinism requirement.
DEFAULT_BUCKETS = (
    1e-6, 1e-5, 1e-4, 1e-3, 5e-3, 0.025, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0)


def _label_key(name: str, labels: dict) -> str:
    """Render ``name{k=v,...}`` with sorted keys — the snapshot key."""
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class Counter:
    """Monotonically increasing count (jobs, transfers, bytes)."""

    __slots__ = ("_lock", "value")

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self.value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += n


class Gauge:
    """A level that moves both ways (queue depth, backlog bytes)."""

    __slots__ = ("_lock", "value")

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self.value = 0

    def set(self, v) -> None:
        with self._lock:
            self.value = v

    def inc(self, n=1) -> None:
        with self._lock:
            self.value += n

    def dec(self, n=1) -> None:
        with self._lock:
            self.value -= n


class Histogram:
    """Fixed-edge histogram: ``counts[i]`` observations ≤ ``edges[i]``,
    one overflow bucket, plus exact ``sum``/``count``."""

    __slots__ = ("_lock", "edges", "counts", "sum", "count")

    def __init__(self, lock: threading.Lock,
                 edges: tuple = DEFAULT_BUCKETS):
        self._lock = lock
        self.edges = tuple(edges)
        self.counts = [0] * (len(self.edges) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        with self._lock:
            i = 0
            for edge in self.edges:
                if v <= edge:
                    break
                i += 1
            self.counts[i] += 1
            self.sum += v
            self.count += 1


class MetricsRegistry:
    """Named, labelled metrics with a deterministic snapshot.

    Instruments call ``registry.counter("jobs_finished", tenant="t0")``
    on the hot path; instances are cached per (name, labels) so repeat
    lookups are one dict hit.  :meth:`snapshot` renders everything into
    plain sorted dicts — the shape ``Cluster.stats()`` /
    ``RemoteBackend.stats()`` / ``FixServeEngine.stats()`` embed under
    their ``"metrics"`` key, and what ``repro.obs.top`` renders.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}

    def counter(self, name: str, **labels) -> Counter:
        key = _label_key(name, labels)
        c = self._counters.get(key)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(key, Counter(self._lock))
        return c

    def gauge(self, name: str, **labels) -> Gauge:
        key = _label_key(name, labels)
        g = self._gauges.get(key)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(key, Gauge(self._lock))
        return g

    def histogram(self, name: str, edges: tuple = DEFAULT_BUCKETS,
                  **labels) -> Histogram:
        key = _label_key(name, labels)
        h = self._histograms.get(key)
        if h is None:
            with self._lock:
                h = self._histograms.setdefault(
                    key, Histogram(self._lock, edges))
        return h

    def snapshot(self) -> dict:
        """Plain sorted dicts; byte-stable for a deterministic workload."""
        with self._lock:
            return {
                "counters": {k: c.value
                             for k, c in sorted(self._counters.items())},
                "gauges": {k: g.value
                           for k, g in sorted(self._gauges.items())},
                "histograms": {
                    k: {"edges": list(h.edges), "counts": list(h.counts),
                        "sum": h.sum, "count": h.count}
                    for k, h in sorted(self._histograms.items())},
            }
