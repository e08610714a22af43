"""Live telemetry plane: metrics registry, causal spans, codelet profiles.

Three small pieces, shared by all three backends (``fix.local()``,
``fix.on(cluster)``, ``fix.remote()``) and the serving engine:

* :class:`MetricsRegistry` — an always-on, low-overhead registry of
  labelled counters / gauges / histograms (defined in
  :mod:`repro.obs.metrics`, which the Fix frontend and the JAX path can
  import, and re-exported here).  Metrics are pure in-memory
  arithmetic: they never touch a clock, never emit trace events, and
  never block on anything but one uncontended lock — so enabling them
  does not perturb a ``VirtualClock`` schedule (the golden trace stays
  byte-identical with telemetry at defaults).  Histograms use *fixed*
  bucket edges chosen at construction, so two runs of a deterministic
  workload produce byte-identical :meth:`MetricsRegistry.snapshot`
  output.

* :class:`SpanEmitter` — opt-in causal spans layered on the PR-4 trace
  stream.  Every request → job → stage → transfer gets a ``span_begin``
  / ``span_end`` event pair with a parent link and a monotonic *wall*
  timestamp (``wall_ns``) alongside the backend clock's ``t``.  Spans
  are off by default (``Cluster(spans=True)`` turns them on), so the
  default event vocabulary — and the committed golden fixture — is
  untouched.

* :class:`CodeletProfile` — per-codelet wall durations.  The evaluator
  times every APPLICATION body (``Evaluator.codelets``); real
  ``fix.remote()`` workers ship deltas back in their ``ran`` replies as
  integer nanoseconds (the wire codec has no float tag), and
  :meth:`CodeletProfile.calibrate` fits per-codelet mean seconds — the
  constants a ``VirtualClock`` cluster charges via
  ``Cluster(compute_model=...)``.  That is the record → model → replay
  seam of ROADMAP item 3: record wall timings once on real processes,
  then study placement/speculation in simulation with compute no longer
  free.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from typing import Iterable, Optional

from ..obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "CodeletProfile",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SpanEmitter",
    "job_wall_durations",
]


# ------------------------------------------------------------------ spans
class SpanEmitter:
    """Causal spans over a :class:`~repro.runtime.trace.TraceRecorder`.

    ``begin`` allocates a monotonically increasing span id and emits a
    ``span_begin`` event carrying ``span``, ``parent`` (another span id
    or None — a request root), ``name`` (``job`` / ``stage`` / ``run`` /
    ``transfer``) and ``wall_ns``, the *monotonic wall* timestamp that
    gives real runs usable durations even when the backend clock is
    virtual.  ``end`` closes it.  Span events ride the ordinary trace
    stream (same lock, same seq numbers) so they interleave causally
    with the events they annotate; they are **not** fault kinds and do
    not change ``verify_invariants``.
    """

    __slots__ = ("_trace", "_now", "_ids")

    def __init__(self, trace, *, now=time.monotonic):
        self._trace = trace
        self._now = now
        self._ids = itertools.count(1)

    def begin(self, name: str, parent: Optional[int] = None,
              **fields) -> int:
        sid = next(self._ids)
        self._trace.emit("span_begin", span=sid, parent=parent, name=name,
                         wall_ns=int(self._now() * 1e9), **fields)
        return sid

    def end(self, span: Optional[int], **fields) -> None:
        if span is None:
            return
        self._trace.emit("span_end", span=span,
                         wall_ns=int(self._now() * 1e9), **fields)


# -------------------------------------------------------- codelet profiles
class CodeletProfile:
    """Per-codelet wall-time table: name → (count, total integer ns).

    Integer nanoseconds end to end — that is what
    ``time.perf_counter_ns`` yields, what the remote wire codec can
    carry (no float tag), and what merges without rounding drift.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._t: dict[str, list] = {}  # name -> [count, total_ns]

    def __len__(self) -> int:
        return len(self._t)

    def names(self) -> list:
        return sorted(self._t)

    def record(self, name: str, total_ns: int, count: int = 1) -> None:
        with self._lock:
            ent = self._t.get(name)
            if ent is None:
                self._t[name] = [count, total_ns]
            else:
                ent[0] += count
                ent[1] += total_ns

    def update(self, items: Iterable) -> None:
        """Fold ``(name, count, total_ns)`` triples — the shape remote
        ``ran`` replies carry."""
        for name, count, total_ns in items:
            self.record(str(name), int(total_ns), int(count))

    def merge(self, other: "CodeletProfile") -> None:
        with other._lock:
            triples = [(n, e[0], e[1]) for n, e in other._t.items()]
        self.update(triples)

    def to_dict(self) -> dict:
        with self._lock:
            return {n: {"count": e[0], "total_ns": e[1]}
                    for n, e in sorted(self._t.items())}

    @classmethod
    def from_dict(cls, d: dict) -> "CodeletProfile":
        p = cls()
        for name, ent in d.items():
            p.record(name, int(ent["total_ns"]), int(ent["count"]))
        return p

    def calibrate(self) -> dict:
        """Mean seconds per codelet — the constants
        ``Cluster(compute_model=...)`` charges on a ``VirtualClock``."""
        with self._lock:
            return {n: (e[1] / e[0]) * 1e-9
                    for n, e in sorted(self._t.items()) if e[0] > 0}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)

    @classmethod
    def load(cls, path: str) -> "CodeletProfile":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def job_wall_durations(events: Iterable[dict]) -> dict:
    """``job_start``/``job_finish`` pairs → job id → run seconds on the
    recording clock.  On a *wall* trace these are real durations — the
    coarse (per-job, not per-codelet) half of the calibration story."""
    started: dict = {}
    out: dict = {}
    for ev in events:
        if ev["kind"] == "job_start":
            started[ev["job"]] = ev["t"]
        elif ev["kind"] == "job_finish" and ev["job"] in started:
            out[ev["job"]] = ev["t"] - started.pop(ev["job"])
    return out
