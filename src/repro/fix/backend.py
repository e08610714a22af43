"""One Backend protocol over the local Evaluator and the Cluster.

``fix.local()`` and ``fix.on(cluster)`` expose the same four operations —

* ``submit(program) -> Future``   — compile a :class:`~repro.fix.lazy.Lazy`
  graph (or accept a raw Handle) against the backend's client repository,
  wrap it in a strict Encode if needed, and hand it to the engine.  One
  submission per program, however deep.
* ``evaluate(program) -> Handle`` — submit + wait; the content-addressed
  result name.
* ``fetch(source) -> value``      — localize the result's bytes (charged
  with link costs on a cluster) and decode them using the program's static
  result type; ``run()`` is the submit+fetch convenience.
* ``as_completed(futures)``       — completion-order iteration.

The protocol deliberately has no escape hatch into engine internals: a
program that runs on ``fix.local()`` runs unchanged on ``fix.on(cluster)``
(asserted by tests/test_fix_backend.py), because both sides consume the
same compiled Table-1 representation.

This module must not import :mod:`repro.runtime` — the cluster imports
*us* (its ``submit``/``evaluate``/``fetch_result`` are thin delegates to
:class:`ClusterBackend`), so the cluster side is duck-typed here.
"""
from __future__ import annotations

import abc
import itertools
import queue
import threading
from typing import Any, Iterable, Optional

from ..core import Evaluator, Repository
from ..core.handle import BLOB, TREE, Handle
from ..obs.spans import Span
from .future import DeadlineExceeded, Future, as_completed
from .lazy import Lazy
from .marshal import MarshalError, _element_hints, unmarshal

_USE_STATIC = object()  # sentinel: "decode with the program's static type"
FETCH = Span("repro.fix.fetch")
EVALUATE = Span("repro.fix.evaluate")


class Backend(abc.ABC):
    """The one submission surface for Fix programs."""

    # ------------------------------------------------------------ protocol
    @property
    @abc.abstractmethod
    def repo(self) -> Repository:
        """The client repository programs compile against."""

    @abc.abstractmethod
    def submit(self, program, *, deadline_s: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        """Compile ``program`` (Lazy or Handle) and start evaluating it.

        ``deadline_s`` bounds the whole job in backend-clock seconds from
        submission (simulated seconds on a virtual-clock cluster): expiry
        fails the future with :class:`~repro.fix.future.DeadlineExceeded`
        and — where the backend can — cancels orphaned child work.

        ``tenant`` is an opaque accounting tag: backends with a trace plane
        thread it onto the job's ``job_submit``/``job_memo_hit`` events (and
        child jobs inherit it), so per-tenant latency/starvation reports
        fall out of ordinary trace analysis
        (:func:`repro.runtime.trace.tenant_report`).  Semantics are
        unaffected — same program, same content keys, same memoization."""

    def evaluate(self, program, timeout: Optional[float] = 120.0) -> Handle:
        """Submit and wait; returns the result Handle."""
        return self.submit(program).result(timeout)

    def fetch(self, source, as_type: Any = _USE_STATIC,
              timeout: Optional[float] = 120.0) -> Any:
        """Result bytes, decoded to a Python value.

        ``source`` may be a Future (waits for it), a result Handle, or a
        Lazy program (submitted first).  ``as_type`` overrides the decode
        annotation; by default a Future's statically-inferred type is used,
        and with no type at all blobs decode to ``bytes`` and trees to
        tuples.  A ``repro.fix.fetch`` span covers it all, with the
        submission's ``job`` where the backend numbers its jobs.
        """
        with FETCH() as span:
            if isinstance(source, Lazy):
                source = self.submit(source)
            if isinstance(source, Future):
                if source.job is not None:
                    span.annotate(job=source.job)
                handle = source.result(timeout)
                if as_type is _USE_STATIC:
                    as_type = source.out_type
            else:
                handle = source
                if as_type is _USE_STATIC:
                    as_type = None
            if not isinstance(handle, Handle):
                raise MarshalError(f"cannot fetch {type(handle).__name__}")
            if handle.is_ref():
                handle = handle.as_object()  # fetch = demand the bytes
            self._localize(handle)
            return unmarshal(self.repo, handle, as_type)

    def run(self, program, timeout: Optional[float] = 120.0) -> Any:
        """submit + fetch: the one-liner for "give me the value"."""
        return self.fetch(self.submit(program), timeout=timeout)

    def fetch_stream(self, source, as_type: Any = _USE_STATIC,
                     timeout: Optional[float] = 120.0):
        """Yield a Tree result's children as their bytes arrive.

        Where :meth:`fetch` localizes the whole closure before decoding
        anything, this generator pulls only the tree *node* up front
        (:meth:`_localize_shallow`), then localizes and decodes one child
        per iteration — so a consumer starts working on child 0 while
        children 1..n-1 are still remote, and a consumer that stops early
        never pays for the tail.  Non-tree results yield exactly one
        value (the plain ``fetch``), so callers can stream unconditionally.
        """
        if isinstance(source, Lazy):
            source = self.submit(source)
        if isinstance(source, Future):
            handle = source.result(timeout)
            if as_type is _USE_STATIC:
                as_type = source.out_type
        else:
            handle = source
            if as_type is _USE_STATIC:
                as_type = None
        if not isinstance(handle, Handle):
            raise MarshalError(f"cannot fetch {type(handle).__name__}")
        if handle.is_ref():
            handle = handle.as_object()
        if handle.content_type != TREE or not handle.is_data():
            self._localize(handle)
            yield unmarshal(self.repo, handle, as_type)
            return
        self._localize_shallow(handle)
        kids = self.repo.get_tree(handle)
        hints = (_element_hints(as_type, len(kids))
                 if as_type not in (None, tuple, list) else [None] * len(kids))
        for kid, hint in zip(kids, hints):
            child = kid.as_object() if kid.is_ref() else kid
            if child.is_data() and not child.is_literal:
                self._localize(child)
            yield unmarshal(self.repo, child, hint)

    @staticmethod
    def as_completed(futures: Iterable[Future],
                     timeout: Optional[float] = None):
        return as_completed(futures, timeout)

    def stats(self) -> dict:
        """One live telemetry snapshot, same shape on every backend:
        ``backend`` (which engine), ``metrics`` (a
        :class:`~repro.runtime.telemetry.MetricsRegistry` snapshot — may
        be empty), and ``codelets`` (per-codelet wall accounting,
        ``name -> {"count", "total_ns"}``), plus backend-specific
        sections.  This is what ``repro.obs.top`` renders."""
        return {"backend": "none", "metrics": {}, "codelets": {}}

    # ---------------------------------------------------------- internals
    @abc.abstractmethod
    def _localize(self, handle: Handle) -> None:
        """Make ``handle``'s bytes resident in :attr:`repo`."""

    def _localize_shallow(self, handle: Handle) -> None:
        """Make only ``handle``'s *own* content resident (a tree node
        without its children) — the streaming-fetch hop.  Backends without
        a cheaper path fall back to the full closure."""
        self._localize(handle)

    def _compile(self, program) -> tuple[Handle, Any]:
        """(top-level Encode handle, static result type) for a program."""
        out_type = None
        if isinstance(program, Lazy):
            h = program.compile(self.repo)
            out_type = program.out_type
        elif isinstance(program, Handle):
            h = program
        else:
            raise MarshalError(
                f"a program is a Lazy expression or a Handle, not "
                f"{type(program).__name__}")
        if h.is_thunk():
            h = h.strict()
        elif h.is_data():
            h = h.identification().strict()
        return h, out_type

    # --------------------------------------------------------- lifecycle
    def close(self) -> None:  # pragma: no cover - overridden where needed
        pass

    def __enter__(self) -> "Backend":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class LocalBackend(Backend):
    """Single-process backend: the paper's semantics with zero deployment.

    Submissions run on one daemon worker thread over a private
    :class:`~repro.core.evaluator.Evaluator`, so ``submit`` is asynchronous
    and ``as_completed`` behaves like the cluster's.  Each submission's
    future carries a ``job`` number, the argument of the worker's
    ``repro.fix.evaluate`` span and of the ``repro.fix.fetch`` that waits
    for it."""

    def __init__(self, repo: Optional[Repository] = None):
        self._repo = repo if repo is not None else Repository("local")
        self.evaluator = Evaluator(self._repo)
        self._jobs = itertools.count(1)
        self._q: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._closed = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="fix-local")
        self._thread.start()

    @property
    def repo(self) -> Repository:
        return self._repo

    def submit(self, program, *, deadline_s: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        if self._closed:
            raise RuntimeError("backend is closed")
        del tenant  # no trace plane locally; accepted for portability
        encode, out_type = self._compile(program)
        fut = Future()
        fut.out_type = out_type
        fut.job = next(self._jobs)
        if deadline_s is not None:
            # Local evaluation is uninterruptible (one synchronous
            # evaluator call), so a deadline can only fail the future;
            # the worker skips items whose future already completed.
            timer = threading.Timer(
                deadline_s, lambda: fut.set_exception(
                    DeadlineExceeded("job deadline exceeded")))
            timer.daemon = True
            timer.start()
            fut.add_done_callback(lambda _f: timer.cancel())
        self._q.put((encode, fut))
        return fut

    def evaluate(self, program, timeout: Optional[float] = 120.0) -> Handle:
        """With a timeout, runs through the worker so the bound is honored
        (same portability contract as the cluster).  ``timeout=None`` is the
        synchronous fast path: inline on the calling thread, unbounded
        (memoization is first-write-wins, so racing the worker is safe)."""
        if timeout is not None:
            return self.submit(program).result(timeout)
        encode, _ = self._compile(program)
        return self.evaluator.evaluate(encode)

    def _localize(self, handle: Handle) -> None:
        pass  # results are already local

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            encode, fut = item
            if fut.done():
                continue  # deadline expired (or cancelled) while queued
            try:
                with EVALUATE(job=fut.job):
                    result = self.evaluator.evaluate(encode)
                fut.set(result)
            except BaseException as e:  # noqa: BLE001 — delivered via the future
                fut.set_exception(e)

    def stats(self) -> dict:
        # codelet table inlined from the evaluator (this module cannot
        # import repro.runtime, where CodeletProfile lives)
        return {
            "backend": "local",
            "metrics": {},
            "codelets": {name: {"count": ent[0], "total_ns": ent[1]}
                         for name, ent
                         in sorted(self.evaluator.codelets.items())},
            "evaluator": self.evaluator.stats(),
        }

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._q.put(None)
            self._thread.join(timeout=5)


class ClusterBackend(Backend):
    """Backend over a :class:`~repro.runtime.cluster.Cluster` (duck-typed).

    Owns the client-facing halves the scheduler shouldn't: program
    compilation, result fetch (charged with link latency/serialization and
    *accounted* in ``cluster.transfers`` / ``cluster.bytes_moved``), and
    decode.  ``Cluster.submit/evaluate/fetch_result`` delegate here."""

    def __init__(self, cluster):
        self.cluster = cluster

    @property
    def repo(self) -> Repository:
        return self.cluster.client_repo

    def submit(self, program, *, deadline_s: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        encode, out_type = self._compile(program)
        fut = self.cluster._submit_encode(encode, deadline_s=deadline_s,
                                          tenant=tenant)
        fut.out_type = out_type
        return fut

    def _localize(self, handle: Handle) -> None:
        self.fetch_result(handle)

    def _localize_shallow(self, handle: Handle) -> None:
        """One tree node's bytes (children stay remote), paying and
        accounting the link cost of just those bytes — what makes
        ``fetch_stream`` incremental on a cluster."""
        c = self.cluster
        if handle.is_ref():
            handle = handle.as_object()
        if handle.is_literal or c.client_repo.contains(handle):
            return
        src = c._find_source_name(handle)
        if src is None or src == "client":
            return
        size = handle.size if handle.content_type == BLOB else 32 * handle.size
        link = c.network.link(src, "client")
        c.clock.sleep(link.latency_s + link.serialized_s(size))
        payload = c.nodes[src].repo.raw_payload(handle)
        if c.client_repo.put_handle_data(handle, payload):
            c._account_transfer(1, size)

    def fetch_result(self, handle: Handle,
                     into: Optional[Repository] = None) -> Repository:
        """Pull a result's bytes to the client (or ``into``), paying and
        accounting the link costs — result-fetch traffic shows up in
        ``transfers``/``bytes_moved`` like any other movement."""
        c = self.cluster
        into = into if into is not None else c.client_repo
        if handle.is_ref():
            handle = handle.as_object()  # fetching = demanding the bytes
        src = c._find_source_name(handle)
        if src is not None and src != "client":
            link = c.network.link(src, "client")
            size = c._deep_size(handle)
            c.clock.sleep(link.latency_s + link.serialized_s(size))
            moved = c.nodes[src].repo.export(handle, into)
            if moved:
                c._account_transfer(1, moved)
        return into

    def stats(self) -> dict:
        return self.cluster.stats()

    def close(self) -> None:
        self.cluster.shutdown()


def local(repo: Optional[Repository] = None) -> LocalBackend:
    """A fresh single-process backend."""
    return LocalBackend(repo)


def on(cluster) -> ClusterBackend:
    """The backend view of a running cluster (``cluster.backend`` is the
    same object the cluster's own thin delegates use)."""
    backend = getattr(cluster, "backend", None)
    return backend if isinstance(backend, ClusterBackend) else ClusterBackend(cluster)
