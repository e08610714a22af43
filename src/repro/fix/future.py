"""Future: the handle to an in-flight Fix submission.

Dependency-light on purpose — the cluster scheduler imports this module, so
it must not import the runtime (or anything above the stdlib).  Completion
callbacks and :func:`as_completed` are the coordination surface the
:class:`~repro.fix.backend.Backend` protocol builds on.

Callbacks run on whichever thread completes the future (the cluster's
scheduler thread, or a local backend's worker) — keep them cheap and never
block in one.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional


class CancelledError(RuntimeError):
    """The future's job was cancelled before producing a result."""


class DeadlineExceeded(TimeoutError):
    """The job's submit-time deadline elapsed before it finished.

    Unlike the plain :class:`TimeoutError` from ``result(timeout=...)`` —
    which only bounds the *caller's wait* — a deadline cancels the job
    itself: orphaned child work is pruned and the future is failed with
    this error on every waiter."""


class Future:
    """Result of a submitted Fix program.

    ``result()`` returns the result *Handle* (use ``Backend.fetch`` to decode
    it into a Python value).  ``out_type`` carries the static result type the
    frontend inferred at submit time, if any — ``fetch`` uses it to decode.

    ``_clock`` (set by the cluster at submit time, duck-typed — this module
    must stay import-light) makes deadlines clock-aware: under a virtual
    clock a ``timeout`` is *simulated* seconds, waited via the clock's
    deterministic event loop, so a virtual-clock program can neither
    wall-block on a timeout that never elapses in simulated time nor burn
    real seconds waiting for one that does.
    """

    def __init__(self):
        self._ev = threading.Event()
        self._lock = threading.Lock()
        self._result = None
        self._exc: Optional[BaseException] = None
        self._callbacks: list[Callable[["Future"], Any]] = []
        self.out_type = None  # static result type, set by the frontend
        self.job = None       # submission number, where the backend keeps one
        self._clock = None    # set by clock-owning backends (cluster)
        # Backends that can prune in-flight work install a canceller:
        # ``_canceller(future)`` must eventually fail the future (the
        # cluster routes it through the scheduler thread so child
        # submissions are pruned too).  Without one, cancel() just fails
        # the future in place.
        self._canceller: Optional[Callable[["Future"], Any]] = None

    # ------------------------------------------------------------- setters
    def set(self, result) -> None:
        with self._lock:
            if self._ev.is_set():
                return  # first write wins (determinism makes dupes identical)
            self._result = result
            self._ev.set()
            callbacks, self._callbacks = self._callbacks, []
        self._run_callbacks(callbacks)

    def set_exception(self, exc: BaseException) -> None:
        with self._lock:
            if self._ev.is_set():
                return
            self._exc = exc
            self._ev.set()
            callbacks, self._callbacks = self._callbacks, []
        self._run_callbacks(callbacks)

    def _run_callbacks(self, callbacks) -> None:
        for cb in callbacks:
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — a callback must not kill the setter
                pass

    # ------------------------------------------------------------- getters
    def _wait(self, timeout: Optional[float]) -> bool:
        clk = self._clock
        if clk is None or not getattr(clk, "is_virtual", False) or self._ev.is_set():
            return self._ev.wait(timeout)
        # Virtual clock: park on a clock event whose timeout elapses in
        # *simulated* seconds — time advances straight to the deadline when
        # the cluster is quiescent, and never before something earlier
        # could happen.
        waker = clk.make_event()
        cb = lambda _f: waker.set()  # noqa: E731 — identity matters for removal
        self.add_done_callback(cb)
        waker.wait(timeout)
        self._discard_callback(cb)  # a timed-out poll must not leak its waker
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = 120.0):
        if not self._wait(timeout):
            raise TimeoutError("fix job timed out")
        if self._exc is not None:
            raise self._exc
        return self._result

    def exception(self, timeout: Optional[float] = 120.0) -> Optional[BaseException]:
        if not self._wait(timeout):
            raise TimeoutError("fix job timed out")
        return self._exc

    def done(self) -> bool:
        return self._ev.is_set()

    # ------------------------------------------------------------ cancel
    def cancel(self) -> bool:
        """Request cancellation.  Returns False if the future already
        completed; True once cancellation is underway (the future will
        complete with :class:`CancelledError`, possibly asynchronously —
        the cluster prunes orphaned child jobs on its scheduler thread)."""
        if self.done():
            return False
        canceller = self._canceller
        if canceller is not None:
            canceller(self)
        else:
            self.set_exception(CancelledError("future cancelled"))
        return True

    def cancelled(self) -> bool:
        return self.done() and isinstance(self._exc, CancelledError)

    def add_done_callback(self, fn: Callable[["Future"], Any]) -> None:
        """``fn(future)`` runs when the future completes (immediately if it
        already has)."""
        with self._lock:
            if not self._ev.is_set():
                self._callbacks.append(fn)
                return
        self._run_callbacks([fn])

    def _discard_callback(self, fn: Callable[["Future"], Any]) -> None:
        """Unregister a pending callback (timed-out waits must not leak)."""
        with self._lock:
            if fn in self._callbacks:
                self._callbacks.remove(fn)


def as_completed(futures: Iterable[Future],
                 timeout: Optional[float] = None) -> Iterator[Future]:
    """Yield futures as they finish, whichever order that happens in.

    ``timeout`` bounds the *total* wait; expiry raises :class:`TimeoutError`
    with the futures still pending left unconsumed.  When the futures carry
    a virtual clock, the bound is *simulated* seconds (see
    :meth:`Future._wait`).
    """
    futs = list(futures)
    clk = next((f._clock for f in futs
                if getattr(f._clock, "is_virtual", False)), None)
    if clk is not None:
        yield from _as_completed_virtual(clk, futs, timeout)
        return
    done_q: "queue.Queue[Future]" = queue.Queue()
    for f in futs:
        f.add_done_callback(done_q.put)
    deadline = None if timeout is None else time.monotonic() + timeout
    try:
        for _ in range(len(futs)):
            if deadline is None:
                yield done_q.get()
            else:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError("as_completed timed out")
                try:
                    yield done_q.get(timeout=remaining)
                except queue.Empty:
                    raise TimeoutError("as_completed timed out") from None
    finally:
        for f in futs:  # a timed-out/abandoned iteration must not leak
            f._discard_callback(done_q.put)


def _as_completed_virtual(clk, futs: list, timeout: Optional[float]) -> Iterator[Future]:
    """Completion-order iteration in simulated time: completions and the
    (virtual) deadline land in one clock queue, so the expiry can only win
    when nothing else can happen first."""
    done_q = clk.make_queue()
    expired = object()
    for f in futs:
        f.add_done_callback(done_q.put)
    timer = None
    if timeout is not None:
        timer = clk.call_at(clk.now() + timeout, lambda: done_q.put(expired))
    try:
        for _ in range(len(futs)):
            got = done_q.get()
            if got is expired:
                raise TimeoutError("as_completed timed out")
            yield got
    finally:
        if timer is not None:
            timer.cancel()
        for f in futs:
            f._discard_callback(done_q.put)
