"""``fix.remote(...)`` — the first off-simulation deployment path.

The coordinator runs the same scheduling algorithm as the in-process
:class:`~repro.runtime.cluster.Cluster` (one ``think``/``strictify`` step
per dispatch, children as jobs, memoized encodes folded into the step's
minimum repository), but places steps on **real worker processes** over
local sockets, with every byte of data movement routed through a
content-addressed :class:`~repro.remote.storage.ObjectStore`:

* **invocation plane** — one control socket per worker carrying framed
  ``submit`` / ``ran`` / ``error`` messages (names and memo pairs only,
  never content);
* **liveness plane** — one heartbeat socket per worker, answered by a
  sidecar thread inside the worker, polled by the backend's monitor
  thread: a worker that misses ``heartbeat_miss_budget`` consecutive
  pings is *fenced* (SIGKILL) so the control socket's EOF turns a silent
  hang into an ordinary observable death;
* **storage plane** — one store socket per worker.  The coordinator pushes
  a step's needs client→store before dispatch; the worker pre-stages
  store→worker before computing and pushes everything it creates
  worker→store before replying.  Workers never talk to each other, so all
  inter-worker movement is two observable hops through the platform-owned
  store — the paper's externalized I/O across a real process boundary.

**Failure model.**  Results are re-derivable (deterministic codelets over
content-addressed inputs), so failures cost retries, not answers:

* a dead worker is *replaced* (up to ``max_respawns``) and its in-flight
  steps are resubmitted with capped exponential backoff — safe
  exactly-once-by-content-key, because results land in the store and a
  duplicate ``ran`` is a dup-put no-op;
* a rotten store payload (``verify_reads``) is quarantined and recovered:
  re-put from the client repository, pulled back from a live worker that
  holds it, or recomputed through the recorded lineage encode;
* exhausted budgets surface as *typed* errors — :class:`WorkerCrashed`
  only when respawn+resubmit ran out, :class:`TransferFailed` /
  :class:`~repro.core.repository.CorruptData` /
  :class:`~repro.fix.future.DeadlineExceeded` /
  :class:`~repro.fix.future.CancelledError` otherwise;
* ``close()`` drains recovery in progress before tearing down.

Residency ground truth is the store's put *notifications* plus the
workers' per-reply fetched/created reports — not in-process repository
listeners — feeding the same :class:`~repro.runtime.transfers.LocationIndex`
the simulated cluster uses.  With ``trace=`` the run emits the PR-4 JSONL
schema plus the PR-6 fault vocabulary (``fault``, ``worker_respawn``,
``job_resubmit``, ``corruption_detected``, ``quarantine``,
``transfer_retry``) and passes fault-mode ``verify_invariants`` — the same
seeded-schedule invariant the simulator checks, now on real processes.

Content addressing is what makes this backend small: a handle is its own
checksum, so every hop verifies its delivery, and content keys are
process-independent, so strict-memo and dedup work unchanged across the
boundary.
"""
from __future__ import annotations

import builtins
import itertools
import multiprocessing
import os
import queue
import socket
import tempfile
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Optional

from ..core.handle import (
    APPLICATION,
    BLOB,
    IDENTIFICATION,
    SELECTION,
    STRICT,
    TREE,
    Handle,
)
from ..core.repository import (
    CorruptData,
    MissingData,
    Repository,
    walk_object_closure,
)
from ..fix.backend import Backend
from ..fix.future import CancelledError, DeadlineExceeded, Future
from ..runtime.faults import TransferFailed
from ..runtime.telemetry import CodeletProfile, MetricsRegistry, SpanEmitter
from ..runtime.transfers import LocationIndex
from .protocol import ProtocolError, recv_msg, retriable, send_msg
from .storage import (
    FileStore,
    MemoryStore,
    ObjectStore,
    StoreError,
    StoreServer,
    decode_tree_payload,
    encode_tree_payload,
    payload_nbytes,
)
from .worker import worker_main

RESOLVE, WAIT_CHILDREN, RUNNING, STRICT_WAIT, DONE, RETRY_WAIT = range(6)


class WorkerCrashed(RuntimeError):
    """Worker death exhausted the respawn+resubmit budget (typed, not a
    hang) — every other failure surfaces as a more specific error."""


class RemoteError(RuntimeError):
    """A worker-side failure that has no builtin exception to rebuild."""

    def __init__(self, etype: str, emsg: str):
        super().__init__(f"{etype}: {emsg}")
        self.etype = etype
        self.emsg = emsg


class _MonotonicClock:
    """now() for TraceRecorder.bind: wall-monotonic seconds since start."""

    def __init__(self):
        self._t0 = time.monotonic()

    def now(self) -> float:
        return time.monotonic() - self._t0


@dataclass
class _RJob:
    id: int
    encode: Handle
    thunk: Handle
    strict: bool
    tenant: Optional[str] = None   # accounting tag, inherited by children
    phase: int = RESOLVE
    epoch: int = 0
    node: Optional[str] = None
    kind: str = "think"            # op of the in-flight dispatch
    retries: int = 0               # recovery attempts consumed
    dispatched_at: float = 0.0     # monotonic instant of the last dispatch
    futures: list = field(default_factory=list)
    parents: list = field(default_factory=list)
    children: set = field(default_factory=set)
    pending_children: set = field(default_factory=set)
    whnf: Optional[Handle] = None
    result: Optional[Handle] = None
    strict_children: list = field(default_factory=list)
    strict_stage: list = field(default_factory=list)
    span: Optional[int] = None     # causal span id (spans=True only)
    _metric_t0: float = 0.0        # monotonic submit instant


class _Worker:
    __slots__ = ("wid", "proc", "ctl", "hb", "send_lock", "hb_lock", "reader",
                 "alive", "outstanding", "log_path", "gen", "hb_misses",
                 "hb_lost", "jobs_reported")

    def __init__(self, wid: str, proc, ctl, hb, log_path: str, gen: int):
        self.wid = wid
        self.proc = proc
        self.ctl = ctl
        self.hb = hb
        self.send_lock = threading.Lock()
        self.hb_lock = threading.Lock()
        self.reader: Optional[threading.Thread] = None
        self.alive = True
        self.outstanding: set[int] = set()
        self.log_path = log_path
        self.gen = gen            # respawn generation under this wid
        self.hb_misses = 0        # consecutive missed heartbeats
        self.hb_lost = False      # fenced by the monitor (budget exhausted)
        self.jobs_reported = 0    # steps-completed count from the last pong


class RemoteBackend(Backend):
    """Real worker processes + pluggable content-addressed object storage.

    ``store`` is ``"memory"`` (server-backed, default), ``"file"`` (a
    :class:`FileStore` under ``store_dir`` — persistent, so two runs of the
    same program share content), or any :class:`ObjectStore` instance.
    Worker stdout/stderr land in per-worker files under ``log_dir``
    (default: ``$FIX_REMOTE_LOGDIR`` or a fresh temp dir) — these are what
    CI uploads when the smoke job fails.

    Workers are forked, and a worker forked from a process that holds an
    accelerator cannot use it: codelets that need the chip run in the
    process that holds it, not here.

    Recovery knobs (defaults tuned for tests; production would scale them
    with the deployment):

    * ``heartbeat_s`` / ``heartbeat_miss_budget`` / ``heartbeat_timeout_s``
      — monitor cadence, consecutive-miss budget before a worker is fenced,
      and per-ping wait (defaults to ``heartbeat_s``);
    * ``max_respawns`` — total replacement workers across the backend's
      lifetime (default ``4 * n_workers``); ``0`` restores fail-fast;
    * ``job_retry_limit`` / ``retry_backoff_s`` / ``retry_backoff_cap_s``
      — per-job resubmit budget and capped exponential backoff;
    * ``store_retry_limit`` — attempts per client→store put before a typed
      :class:`TransferFailed`;
    * ``dispatch_timeout_s`` — optional watchdog: a step RUNNING longer
      than this is resubmitted (dup results are dup-put no-ops), turning a
      dropped control frame into a retry instead of a hang;
    * ``drain_timeout_s`` — how long ``close()`` waits for in-flight work
      (including recovery) to finish before failing the remainder;
    * ``chaos`` — a :class:`~repro.remote.chaos.RemoteChaos` schedule; arms
      ``store.verify_reads`` and routes control-plane sends through the
      injection shim.
    """

    def __init__(self, n_workers: int = 2, *, store="memory",
                 store_dir: Optional[str] = None, trace=None,
                 log_dir: Optional[str] = None, chaos=None,
                 heartbeat_s: float = 1.0, heartbeat_miss_budget: int = 5,
                 heartbeat_timeout_s: Optional[float] = None,
                 max_respawns: Optional[int] = None,
                 job_retry_limit: int = 3, retry_backoff_s: float = 0.05,
                 retry_backoff_cap_s: float = 2.0, store_retry_limit: int = 3,
                 dispatch_timeout_s: Optional[float] = None,
                 drain_timeout_s: float = 10.0,
                 recover_wait_s: float = 5.0,
                 metrics: bool = True, spans: bool = False):
        if n_workers < 1:
            raise ValueError("need at least one worker process")
        self._repo = Repository("client")
        self.trace = trace
        if trace is not None:
            trace.bind(_MonotonicClock())
        self.metrics = MetricsRegistry() if metrics else None
        self.spans = (SpanEmitter(trace)
                      if spans and trace is not None else None)
        self.profile = CodeletProfile()  # folded from worker ran replies
        self._locs = LocationIndex()
        self._store_mutex = threading.Lock()
        self.store = self._resolve_store(store, store_dir)
        self.store.add_put_listener(self._on_store_put)
        self._repo.add_put_listener(self._on_client_put)
        self.log_dir = (log_dir or os.environ.get("FIX_REMOTE_LOGDIR")
                        or tempfile.mkdtemp(prefix="fix-remote-logs-"))
        os.makedirs(self.log_dir, exist_ok=True)

        # recovery configuration
        self.heartbeat_s = heartbeat_s
        self.heartbeat_miss_budget = heartbeat_miss_budget
        self.heartbeat_timeout_s = (heartbeat_timeout_s
                                    if heartbeat_timeout_s is not None
                                    else heartbeat_s)
        self.max_respawns = (max_respawns if max_respawns is not None
                             else 4 * n_workers)
        self.job_retry_limit = job_retry_limit
        self.retry_backoff_s = retry_backoff_s
        self.retry_backoff_cap_s = retry_backoff_cap_s
        self.store_retry_limit = store_retry_limit
        self.dispatch_timeout_s = dispatch_timeout_s
        self.drain_timeout_s = drain_timeout_s
        self.recover_wait_s = recover_wait_s

        # recovery counters (stats() / benchmarks)
        self.respawns = 0
        self.resubmits = 0
        self.quarantines = 0
        self.recomputes = 0
        self.hb_fences = 0

        # scheduler state (coordinator thread only, except _memo reads)
        self._jobs: dict[int, _RJob] = {}
        self._by_encode: dict[bytes, int] = {}
        self._memo: dict[bytes, Handle] = {}
        self._reach: dict[bytes, tuple] = {}
        self._lineage: dict[bytes, bytes] = {}    # content key -> creator encode
        self._quarantined: set[bytes] = set()     # rot detected, not yet re-put
        self._recomputing: set[bytes] = set()     # recovery in flight
        self._quar_lock = threading.Lock()
        self._ids = itertools.count()
        self._nonces = itertools.count()
        self._events: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._timers: set[threading.Timer] = set()
        self._graveyard: list[_Worker] = []
        self._respawns_used = 0
        self.transfers = 0
        self.bytes_moved = 0
        self._closed = False
        self._closing = False

        self._chaos = chaos
        if chaos is not None:
            self.store.verify_reads = True
            chaos.bind(self)

        self._store_server = StoreServer(self.store, mutex=self._store_mutex)
        self._store_server.on_corrupt = (
            lambda h, peer: self._quarantine(h, via="read", dst=peer))
        self._workers: dict[str, _Worker] = {}
        self._ctx = multiprocessing.get_context("fork")
        for i in range(n_workers):
            self._spawn_worker(f"w{i}")
        self._coord = threading.Thread(target=self._loop, daemon=True,
                                       name="fix-remote-coord")
        self._coord.start()
        self._stop_monitor = threading.Event()
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         daemon=True,
                                         name="fix-remote-monitor")
        self._monitor.start()

    # ----------------------------------------------------------- lifecycle
    @staticmethod
    def _resolve_store(store, store_dir: Optional[str]) -> ObjectStore:
        if isinstance(store, ObjectStore):
            return store
        if store == "memory":
            return MemoryStore()
        if store == "file":
            return FileStore(store_dir or tempfile.mkdtemp(prefix="fix-store-"))
        raise ValueError(f"store must be 'memory', 'file' or an ObjectStore, "
                         f"not {store!r}")

    def _spawn_worker(self, wid: str, gen: int = 0) -> None:
        ctl_parent, ctl_child = socket.socketpair()
        store_parent, store_child = socket.socketpair()
        hb_parent, hb_child = socket.socketpair()
        log_path = os.path.join(self.log_dir, f"{wid}.log")
        proc = self._ctx.Process(
            target=worker_main,
            args=(ctl_child, store_child, wid, log_path, hb_child),
            daemon=True, name=f"fix-remote-{wid}-g{gen}")
        proc.start()
        # Close the child ends NOW, before the next worker forks: a later
        # child inheriting these fds would keep this worker's sockets open
        # past its death and break EOF-based crash detection.
        ctl_child.close()
        store_child.close()
        hb_child.close()
        old = self._workers.get(wid)
        if old is not None:
            self._graveyard.append(old)
        w = _Worker(wid, proc, ctl_parent, hb_parent, log_path, gen)
        self._workers[wid] = w
        self._store_server.serve(store_parent, wid)
        w.reader = threading.Thread(target=self._read_loop, args=(w,),
                                    daemon=True,
                                    name=f"fix-remote-rx-{wid}-g{gen}")
        w.reader.start()

    def _read_loop(self, w: _Worker) -> None:
        fatal: Optional[BaseException] = None
        try:
            while True:
                msg = recv_msg(w.ctl)
                if msg is None:
                    break
                if self._chaos is not None:
                    self._chaos.on_ctl_recv(w)
                if msg.get("op") == "pong":
                    continue  # legacy between-steps pong: liveness moved to hb
                self._events.put(("msg", w.wid, msg, w.gen))
        except ProtocolError as e:
            # FrameTruncated is a channel casualty (retriable); BadTag /
            # FrameTooLarge mean a poisoned conversation (fatal for the
            # steps that died with it — resending could only repeat it).
            fatal = None if retriable(e) else e
        except OSError:
            pass
        self._events.put(("worker_died", w.wid, w.gen, fatal))

    def _ctl_send(self, w: _Worker, msg: dict) -> None:
        """Control-plane send, routed through the chaos shim when armed."""
        if self._chaos is not None:
            self._chaos.ctl_send(w, msg)
        else:
            send_msg(w.ctl, msg, lock=w.send_lock)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True                 # no new submissions
        self._drain(self.drain_timeout_s)   # let recovery in progress finish
        self._closing = True
        self._stop_monitor.set()
        self._monitor.join(timeout=5)
        for t in list(self._timers):
            t.cancel()
        # anything still pending after the drain fails typed, not hanging
        self._events.put(("teardown",))
        for w in self._workers.values():
            if w.alive:
                try:
                    send_msg(w.ctl, {"op": "shutdown"}, lock=w.send_lock)
                except OSError:
                    pass
        everyone = list(self._workers.values()) + self._graveyard
        for w in everyone:
            w.proc.join(timeout=5)
            if w.proc.is_alive():
                w.proc.terminate()
                w.proc.join(timeout=2)
            if w.proc.is_alive():  # pragma: no cover - last resort
                w.proc.kill()
                w.proc.join(timeout=2)
        self._events.put(None)
        self._coord.join(timeout=5)
        for w in everyone:
            for sock in (w.ctl, w.hb):
                try:
                    sock.close()
                except OSError:
                    pass
            if w.reader is not None:
                w.reader.join(timeout=5)
        for t in list(self._timers):
            t.join(timeout=1)
        self._store_server.close()
        self.store.close()
        if self._chaos is not None:
            self._chaos.close()

    def _drain(self, timeout: float) -> None:
        """Wait (bounded) for the event queue and every job to settle —
        recovery that is mid-flight at close() is never truncated."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            busy = (not self._events.empty()
                    or any(j.phase != DONE
                           for j in list(self._jobs.values())))
            if not busy:
                return
            time.sleep(0.02)

    # --------------------------------------------------------------- public
    @property
    def repo(self) -> Repository:
        return self._repo

    def submit(self, program, *, deadline_s: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        if self._closed:
            raise RuntimeError("backend is closed")
        encode, out_type = self._compile(program)
        fut = Future()
        fut.out_type = out_type
        fut._canceller = lambda f: self._request_cancel(f, "cancel")
        if deadline_s is not None:
            timer = threading.Timer(
                deadline_s, lambda: self._request_cancel(fut, "deadline"))
            timer.daemon = True
            timer.start()
            fut.add_done_callback(lambda _f: timer.cancel())
        self._events.put(("submit", encode, fut, None, False, tenant))
        return fut

    def _request_cancel(self, fut: Future, reason: str) -> None:
        """Route a cancel/deadline through the coordinator so the job (and
        its orphaned children) are pruned, not just the future failed."""
        if fut.done():
            return
        if self._coord.is_alive() and not self._closing:
            self._events.put(("cancel", fut, reason))
        else:
            fut.set_exception(self._cancel_exc(reason))

    @staticmethod
    def _cancel_exc(reason: str) -> BaseException:
        if reason == "deadline":
            return DeadlineExceeded("job deadline exceeded")
        return CancelledError("future cancelled")

    def ping(self, timeout: float = 5.0) -> dict[str, bool]:
        """Heartbeat every live worker; {worker id: answered in time}.

        Pings travel the dedicated heartbeat socket (answered by a sidecar
        thread in the worker), so a pong bounds process liveness even while
        a codelet runs.  Stale pongs left in the buffer by a timed-out
        earlier ping are drained by nonce, never miscounted."""
        out: dict[str, bool] = {}
        for wid, w in self._workers.items():
            out[wid] = w.alive and self._hb_ping_worker(w, timeout)
        return out

    def _hb_ping_worker(self, w: _Worker, timeout: float) -> bool:
        nonce = next(self._nonces)
        deadline = time.monotonic() + timeout
        try:
            with w.hb_lock:
                send_msg(w.hb, {"op": "heartbeat", "nonce": nonce})
                while True:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return False
                    w.hb.settimeout(remaining)
                    try:
                        msg = recv_msg(w.hb)
                    finally:
                        try:
                            w.hb.settimeout(None)
                        except OSError:
                            return False
                    if msg is None:
                        return False  # EOF: the worker is gone
                    if msg.get("op") != "pong" or msg.get("nonce") != nonce:
                        continue      # stale pong from a timed-out ping
                    if (self._chaos is not None
                            and not self._chaos.take_pong(w.wid)):
                        return False  # injected heartbeat stall
                    w.jobs_reported = msg.get("jobs", w.jobs_reported)
                    return True
        except (OSError, ProtocolError):
            return False

    def _count_job(self, job: _RJob, outcome: str) -> None:
        m = self.metrics
        if m is not None:
            tl = {} if job.tenant is None else {"tenant": job.tenant}
            m.counter("jobs_" + outcome, **tl).inc()

    def codelet_profile(self) -> CodeletProfile:
        return self.profile

    def stats(self) -> dict:
        return {
            "backend": "remote",
            "metrics": (self.metrics.snapshot()
                        if self.metrics is not None else {}),
            "codelets": self.profile.to_dict(),
            "store": self.store.stats(),
            "workers": {wid: {"alive": w.alive, "pid": w.proc.pid,
                              "gen": w.gen, "jobs": w.jobs_reported,
                              "log": w.log_path}
                        for wid, w in self._workers.items()},
            "transfers": self.transfers,
            "bytes_moved": self.bytes_moved,
            "recovery": {"respawns": self.respawns,
                         "resubmits": self.resubmits,
                         "quarantines": self.quarantines,
                         "recomputes": self.recomputes,
                         "hb_fences": self.hb_fences},
        }

    # ------------------------------------------------------ event loop
    def _loop(self) -> None:
        while True:
            ev = self._events.get()
            if ev is None:
                return
            try:
                kind = ev[0]
                if kind == "submit":
                    self._on_submit(*ev[1:])
                elif kind == "msg":
                    self._on_msg(ev[1], ev[2], ev[3])
                elif kind == "worker_died":
                    self._on_worker_died(ev[1], ev[2], ev[3])
                elif kind == "retry_job":
                    self._on_retry(ev[1], ev[2])
                elif kind == "job_timeout":
                    self._on_job_timeout(ev[1], ev[2])
                elif kind == "cancel":
                    self._on_cancel(ev[1], ev[2])
                elif kind == "teardown":
                    self._on_teardown()
            except BaseException:  # pragma: no cover - coordinator must live
                traceback.print_exc()

    # ------------------------------------------------------ monitor thread
    def _monitor_loop(self) -> None:
        """Active failure detection: heartbeat every worker each period;
        a worker over the miss budget is fenced (SIGKILL) so its control
        socket EOFs and the ordinary death path takes over.  Optionally
        also watches for dispatches that outlive ``dispatch_timeout_s``
        (a dropped frame leaves a step RUNNING forever otherwise)."""
        while not self._stop_monitor.wait(self.heartbeat_s):
            if self._closing:
                return
            for w in list(self._workers.values()):
                if not w.alive or self._closing:
                    continue
                if self._hb_ping_worker(w, self.heartbeat_timeout_s):
                    w.hb_misses = 0
                    continue
                w.hb_misses += 1
                if w.hb_misses < self.heartbeat_miss_budget or w.hb_lost:
                    continue
                w.hb_lost = True
                self.hb_fences += 1
                try:
                    w.proc.kill()  # fence: make the silence a real death
                except Exception:  # noqa: BLE001 - already gone
                    pass
            if self.dispatch_timeout_s is None:
                continue
            now = time.monotonic()
            for job in list(self._jobs.values()):
                if (job.phase == RUNNING and job.dispatched_at
                        and now - job.dispatched_at > self.dispatch_timeout_s):
                    self._events.put(("job_timeout", job.id, job.epoch))

    # ------------------------------------------------------------ submit
    def _on_submit(self, encode: Handle, fut: Optional[Future],
                   parent: Optional[int], ignore_memo: bool,
                   tenant: Optional[str] = None) -> None:
        tr = self.trace
        if tenant is None and parent is not None:
            # child work bills to whoever submitted the root program
            pj = self._jobs.get(parent)
            if pj is not None:
                tenant = pj.tenant
        if not ignore_memo:
            memo = self._memo.get(encode.raw)
            if memo is not None:
                # the content universe (client repo ∪ store) never evicts,
                # so a memoized result is always fetchable
                if tr is not None:
                    extra = {} if tenant is None else {"tenant": tenant}
                    tr.emit("job_memo_hit", encode=encode.raw.hex(), **extra)
                if self.metrics is not None:
                    tl = {} if tenant is None else {"tenant": tenant}
                    self.metrics.counter("jobs_memo_hit", **tl).inc()
                if fut is not None:
                    fut.set(memo)
                if parent is not None:
                    self._child_resolved(parent, encode)
                return
            existing = self._by_encode.get(encode.raw)
            if existing is not None and self._jobs[existing].phase != DONE:
                job = self._jobs[existing]
                if fut is not None:
                    fut._jid = existing
                    job.futures.append(fut)
                if parent is not None:
                    job.parents.append(parent)
                    pj = self._jobs.get(parent)
                    if pj is not None:
                        pj.children.add(existing)
                return
        jid = next(self._ids)
        job = _RJob(jid, encode, encode.unwrap_encode(),
                    encode.interp == STRICT, tenant=tenant)
        if fut is not None:
            fut._jid = jid
            job.futures.append(fut)
        if parent is not None:
            job.parents.append(parent)
            pj = self._jobs.get(parent)
            if pj is not None:
                pj.children.add(jid)
        self._jobs[jid] = job
        if not ignore_memo:
            self._by_encode[encode.raw] = jid
        if tr is not None:
            # tenant only when tagged: untagged runs keep byte-identical
            # traces (the golden-fixture replay diff)
            extra = {} if tenant is None else {"tenant": tenant}
            tr.emit("job_submit", job=jid, encode=encode.raw.hex(),
                    strict=job.strict, parent=parent, recompute=ignore_memo,
                    **extra)
        job._metric_t0 = time.monotonic()
        self._count_job(job, "submitted")
        if self.spans is not None:
            pj = self._jobs.get(parent) if parent is not None else None
            job.span = self.spans.begin(
                f"job:{jid}", parent=(pj.span if pj is not None else None),
                job=jid)
        self._advance_guarded(job)

    def _advance_guarded(self, job: _RJob) -> None:
        try:
            self._advance(job)
        except (MissingData, CorruptData) as e:
            self._handle_content_loss(job, e)
        except BaseException as e:  # noqa: BLE001 — failures stay job-scoped
            self._fail_job(job, e)

    def _strictify_guarded(self, job: _RJob) -> None:
        try:
            self._begin_strictify(job)
        except (MissingData, CorruptData) as e:
            self._handle_content_loss(job, e)
        except BaseException as e:  # noqa: BLE001
            self._fail_job(job, e)

    # ------------------------------------------------------------- advance
    def _advance(self, job: _RJob) -> None:
        thunk = job.thunk
        if thunk.is_data():  # encode over an already-data handle
            job.whnf = thunk
            if job.strict:
                self._begin_strictify(job)
            else:
                self._finalize(job, thunk.as_ref())
            return
        needs, children, memo_pairs = self._step_needs(thunk)
        unresolved = [c for c in children if self._memo.get(c.raw) is None]
        if unresolved:
            job.phase = WAIT_CHILDREN
            job.pending_children = {c.raw for c in unresolved}
            for c in unresolved:
                self._events.put(("submit", c, None, job.id, False, None))
            return
        for enc in children:
            res = self._memo[enc.raw]
            memo_pairs.append((enc, res))
            needs.extend(self._deep_object_handles(res))
        self._dispatch(job, "think", job.thunk, needs, memo_pairs)

    def _child_resolved(self, parent_id: int, child_encode: Handle) -> None:
        job = self._jobs.get(parent_id)
        if job is None or job.phase == DONE:
            return
        job.pending_children.discard(child_encode.raw)
        if job.pending_children or job.phase not in (WAIT_CHILDREN,
                                                     STRICT_WAIT):
            return
        if job.phase == WAIT_CHILDREN:
            job.phase = RESOLVE
            self._advance_guarded(job)
        else:  # children of the WHNF walk resolved: re-walk, now memoized
            self._strictify_guarded(job)

    # --------------------------------------------------------- strictify
    def _begin_strictify(self, job: _RJob) -> None:
        """Deep-evaluate the WHNF result (mirror of the cluster's walk):
        nested thunks/encodes become child jobs, Ref'd data is staged."""
        whnf = job.whnf
        children: list[Handle] = []
        stage: list[Handle] = []
        stack = [whnf]
        seen: set[bytes] = set()
        while stack:
            h = stack.pop()
            if h.raw in seen or h.is_literal:
                continue
            seen.add(h.raw)
            if h.is_encode():
                res = self._memo.get(h.raw)
                if res is None:
                    children.append(h)
                else:
                    stack.append(res)
                continue
            if h.is_thunk():
                children.append(h.strict())
                continue
            stage.append(h)
            if h.content_type == TREE:
                kids = self._tree_children(h)
                if kids is not None:
                    stack.extend(kids)
        job.strict_stage = stage
        job.strict_children = children
        unresolved = [c for c in children if self._memo.get(c.raw) is None]
        if unresolved:
            job.phase = STRICT_WAIT
            job.pending_children = {c.raw for c in unresolved}
            for c in unresolved:
                self._events.put(("submit", c, None, job.id, False, None))
            return
        self._advance_strict(job)

    def _advance_strict(self, job: _RJob) -> None:
        if job.whnf.content_type == BLOB and job.whnf.is_data():
            # a blob is its own strict form: no worker round-trip
            self._finalize(job, job.whnf.as_object())
            return
        needs = list(job.strict_stage)
        memo_pairs: list[tuple] = []
        for c in job.strict_children:
            res = self._memo[c.raw]
            memo_pairs.append((c, res))
            needs.extend(self._deep_object_handles(res))
        self._dispatch(job, "strictify", job.whnf, needs, memo_pairs)

    # ---------------------------------------------------------- stepneeds
    def _step_needs(self, thunk: Handle):
        """(stage handles, child encodes, memo pairs) for one reduction —
        the cluster's algorithm verbatim, over client repo ∪ store."""
        interp = thunk.interp
        if interp == IDENTIFICATION:
            return [], [], []
        if interp == SELECTION:
            pair_h = thunk.unwrap_thunk()
            needs = [pair_h]
            pair = self._tree_children(pair_h)
            if pair is None:
                raise MissingData(pair_h)
            target, idx = pair
            if not idx.is_literal:
                needs.append(idx)
            children: list[Handle] = []
            memo_pairs: list[tuple] = []
            if target.is_encode():
                res = self._memo.get(target.raw)
                if res is None:
                    return needs, [target], []
                memo_pairs.append((target, res))
                target = res
            if target.is_thunk():
                res = self._memo.get(target.shallow().raw)
                if res is None:
                    return needs, [target.shallow()], []
                memo_pairs.append((target.shallow(), res))
                target = res
            if not target.is_literal:
                needs.append(target)  # the node itself; children stay put
            return needs, children, memo_pairs
        if interp == APPLICATION:
            defn = thunk.unwrap_thunk()
            needs, children, memo_pairs = [], [], []
            stack = [defn]
            seen: set[bytes] = set()
            while stack:
                h = stack.pop()
                if h.raw in seen or h.is_literal:
                    continue
                seen.add(h.raw)
                if h.is_encode():
                    res = self._memo.get(h.raw)
                    if res is None:
                        children.append(h)
                    else:
                        memo_pairs.append((h, res))
                        stack.append(res)
                    continue
                if h.is_thunk() or h.is_ref():
                    continue  # lazy / metadata-only
                needs.append(h)
                if h.content_type == TREE:
                    kids = self._tree_children(h)
                    if kids is None:
                        raise MissingData(h)
                    stack.extend(kids)
            return needs, children, memo_pairs
        raise ValueError(f"not a thunk: {thunk!r}")

    def _tree_children(self, h: Handle) -> Optional[tuple]:
        try:
            return self._repo.get_tree(h)
        except MissingData:
            payload = self._store_read(h, dst="client")
            if payload is None:
                return None
            return decode_tree_payload(payload)

    def _deep_object_handles(self, handle: Handle) -> list[Handle]:
        return list(walk_object_closure(
            handle, lambda h: self._memo.get(h.raw),
            self._tree_children, self._reach))

    # ----------------------------------------------------------- dispatch
    def _dispatch(self, job: _RJob, kind: str, target: Handle,
                  needs: list, memo_pairs: list) -> None:
        uniq: list[Handle] = []
        seen: set[bytes] = set()
        for h in needs:
            if h.is_literal or h.raw in seen:
                continue
            seen.add(h.raw)
            uniq.append(h)
        wid = self._pick_worker(uniq)
        if wid is None:
            self._fail_job(job, WorkerCrashed("no live worker processes"))
            return
        # Storage plane first: every need must be servable from the store
        # before the step is dispatched (client→store is an accounted,
        # traced transfer like any other).  The mutex makes the residency
        # check and the trace choreography atomic against worker pushes.
        with self._store_mutex:
            for h in uniq:
                self._ensure_in_store_locked(job.id, h)
        missing = [h for h in uniq
                   if wid not in self._locs.nodes_for(h.content_key())]
        tr = self.trace
        job.node = wid
        job.kind = kind
        if tr is not None:
            tr.emit("job_place", job=job.id, node=wid, epoch=job.epoch,
                    n_missing=len(missing),
                    missing_nbytes=sum(payload_nbytes(h) for h in missing))
        job.phase = RUNNING
        job.dispatched_at = time.monotonic()
        if tr is not None:
            tr.emit("job_start", job=job.id, node=wid, epoch=job.epoch,
                    op="run" if kind == "think" else "strictify", internal=0)
        w = self._workers[wid]
        w.outstanding.add(job.id)
        try:
            self._ctl_send(w, {
                "op": "submit", "job": job.id, "epoch": job.epoch,
                "kind": kind, "target": target.raw,
                "memos": [[e.raw, r.raw] for e, r in memo_pairs],
                "needs": [h.raw for h in uniq],
            })
        except OSError:
            # the reader's worker_died event will resubmit the job; doing
            # it here too would race the reader thread
            pass

    def _pick_worker(self, uniq: list) -> Optional[str]:
        """Place where the fewest bytes of the step's needs are missing
        (the location index knows worker residency), breaking ties toward
        the shorter outstanding queue, then by worker order."""
        live = [w for w in self._workers.values() if w.alive]
        if not live:
            return None
        best, best_cost = None, None
        for w in live:
            missing = sum(payload_nbytes(h) for h in uniq
                          if w.wid not in self._locs.nodes_for(h.content_key()))
            cost = (missing, len(w.outstanding))
            if best_cost is None or cost < best_cost:
                best, best_cost = w, cost
        return best.wid

    def _ensure_in_store_locked(self, jid: Optional[int], h: Handle) -> None:
        """Client→store movement for one handle (store mutex held), with
        capped-backoff retry and a typed :class:`TransferFailed` give-up."""
        if self.store.contains(h):
            return
        if h.content_type == BLOB:
            payload = self._repo.get_blob(h)
        else:
            payload = encode_tree_payload(self._repo.get_tree(h))
        nbytes = payload_nbytes(h)
        tr = self.trace
        key_hex = h.content_key().hex()
        if tr is not None:
            tr.emit("stage_request", job=jid, dst="store", key=key_hex,
                    nbytes=nbytes, action="enqueue", src="client")
        attempts = 0
        while True:
            attempts += 1
            try:
                self.store.put(h, payload, src="client")  # put(node="store")
                break
            except (OSError, StoreError) as e:
                if attempts >= self.store_retry_limit:
                    if tr is not None:
                        tr.emit("transfer_gaveup", dst="store", key=key_hex,
                                jobs=[], attempts=attempts)
                    raise TransferFailed(key_hex, "store", attempts,
                                         str(e)) from e
                if tr is not None:
                    tr.emit("transfer_retry", dst="store", key=key_hex,
                            attempt=attempts, reason=str(e))
                time.sleep(min(self.retry_backoff_s * 2 ** (attempts - 1),
                               self.retry_backoff_cap_s))
        if tr is not None:
            tr.emit("transfer_deliver", src="client", dst="store", n=1,
                    nbytes=nbytes, keys=[key_hex], ok=True, via="store")
        self.transfers += 1
        self.bytes_moved += nbytes
        if self.metrics is not None:
            self.metrics.counter("transfers_total").inc()
            self.metrics.counter("bytes_moved_total").inc(nbytes)

    # ------------------------------------------------------------- replies
    def _on_msg(self, wid: str, msg: dict, gen: int) -> None:
        w = self._workers.get(wid)
        if w is None or w.gen != gen:
            return  # a message from a replaced generation: nothing current
        jid = msg.get("job")
        w.outstanding.discard(jid)
        # Residency/trace accounting first — the movement happened whether
        # or not the job is still current; same for codelet wall time
        # (the profile deltas are high-water-marked worker-side, so folding
        # a stale reply cannot double-count).
        self._record_movement(wid, msg, jid)
        prof = msg.get("profile")
        if prof:
            self.profile.update(prof)
        job = self._jobs.get(jid)
        if job is None or job.phase != RUNNING or msg.get("epoch") != job.epoch:
            return  # stale reply (job failed over or already finished)
        if msg["op"] == "error":
            exc = self._rebuild_exc(msg)
            if msg.get("etype") == "MissingData":
                # the store lost (or quarantined) content between staging
                # and the worker's fetch: recovery may repopulate it, so
                # this is a retry, not a verdict
                self._retry_or_fail(job, "content missing at worker", exc)
            else:
                self._fail_job(job, exc)
            return
        result = Handle(bytes(msg["result"]))
        if job.kind == "strictify":
            self._finalize(job, result)
            return
        if result.is_thunk():  # tail call: fresh placement (paper §4.2.2)
            job.thunk = result
            job.epoch += 1
            job.phase = RESOLVE
            self._advance_guarded(job)
            return
        job.whnf = result
        job.epoch += 1
        if not job.strict:
            self._finalize(job, result.as_ref() if result.is_data() else result)
            return
        self._strictify_guarded(job)

    def _record_movement(self, wid: str, msg: dict, jid) -> None:
        """Fold a reply's fetched/created reports into the trace and the
        location index — the worker's ground truth of what actually moved
        store→worker and what fresh content it produced.  Created entries
        also record lineage (content key → creator encode) so quarantined
        content can be recomputed through the memo machinery."""
        tr = self.trace
        resident = self._locs
        job = self._jobs.get(jid)
        enc_raw = job.encode.raw if job is not None else None
        for raw, nbytes in msg.get("fetched", ()):
            h = Handle(bytes(raw))
            key = h.content_key()
            if tr is not None:
                key_hex = key.hex()
                tr.emit("stage_request", job=jid, dst=wid, key=key_hex,
                        nbytes=nbytes, action="enqueue", src="store")
                tr.emit("transfer_deliver", src="store", dst=wid, n=1,
                        nbytes=nbytes, keys=[key_hex], ok=True, via="store")
                tr.emit("put", node=wid, key=key_hex, nbytes=nbytes)
            resident.add(key, wid)
            self.transfers += 1
            self.bytes_moved += nbytes
            if self.metrics is not None:
                self.metrics.counter("transfers_total").inc()
                self.metrics.counter("bytes_moved_total").inc(nbytes)
        for raw, nbytes in msg.get("created", ()):
            h = Handle(bytes(raw))
            key = h.content_key()
            if enc_raw is not None:
                self._lineage.setdefault(key, enc_raw)
            if wid in resident.nodes_for(key):
                continue  # already accounted (identical content re-derived)
            if tr is not None:
                tr.emit("put", node=wid, key=key.hex(), nbytes=nbytes)
            resident.add(key, wid)

    @staticmethod
    def _rebuild_exc(msg: dict) -> BaseException:
        etype, emsg = msg.get("etype", "Exception"), msg.get("emsg", "")
        cls = getattr(builtins, etype, None)
        if not (isinstance(cls, type) and issubclass(cls, BaseException)):
            # the repro exception types a shim can raise — rebuilding them
            # keeps error behavior identical to fix.local()
            from ..core.evaluator import FixError
            from ..fix.marshal import MarshalError
            cls = {"FixError": FixError,
                   "MarshalError": MarshalError}.get(etype)
        if cls is not None:
            try:
                return cls(emsg)
            except Exception:  # noqa: BLE001 - exotic signature
                pass
        if etype == "MissingData":
            return RemoteError(etype, emsg or "content unavailable at worker")
        return RemoteError(etype, emsg)

    # ------------------------------------------------------------ recovery
    def _on_worker_died(self, wid: str, gen: int, fatal) -> None:
        w = self._workers.get(wid)
        if w is None or w.gen != gen or not w.alive:
            return
        w.alive = False
        self._locs.drop_node(wid)
        victims = sorted(w.outstanding)
        w.outstanding.clear()
        if self._closing:
            return
        reason = ("heartbeat_lost" if w.hb_lost
                  else type(fatal).__name__ if fatal is not None else "crash")
        tr = self.trace
        if tr is not None:
            tr.emit("fault", fault="crash", node=wid, applied=True,
                    reason=reason)
        respawned = False
        if self._respawns_used < self.max_respawns:
            self._respawns_used += 1
            self.respawns += 1
            try:
                self._spawn_worker(wid, gen=gen + 1)
                respawned = True
                nw = self._workers[wid]
                if tr is not None:
                    tr.emit("worker_respawn", node=wid, pid=nw.proc.pid,
                            gen=nw.gen, reason=reason)
                    tr.emit("node_join", node=wid, fresh=False)
            except BaseException:  # pragma: no cover - fork failure
                traceback.print_exc()
        crashed = WorkerCrashed(
            f"worker {wid} (pid {w.proc.pid}) died ({reason}); "
            f"log: {w.log_path}")
        have_live = respawned or any(x.alive for x in self._workers.values())
        for jid in victims:
            job = self._jobs.get(jid)
            if job is None or job.phase != RUNNING or job.node != wid:
                continue
            if fatal is not None and not retriable(fatal):
                self._fail_job(job, fatal)       # poisoned conversation
            elif not have_live:
                self._fail_job(job, crashed)     # nowhere left to retry
            else:
                self._retry_or_fail(job, f"worker {wid} died ({reason})",
                                    crashed)

    def _handle_content_loss(self, job: _RJob, exc: BaseException) -> None:
        """A step's needs hit missing/quarantined store content.  The read
        that detected it already kicked off recovery (re-put, worker push
        or lineage recompute); back off and retry the step, giving up with
        the typed loss itself."""
        self._retry_or_fail(job, f"content loss ({type(exc).__name__})", exc)

    def _retry_or_fail(self, job: _RJob, reason: str,
                       give_up: BaseException) -> None:
        if job.phase in (DONE, RETRY_WAIT):
            return
        job.retries += 1
        if job.retries > self.job_retry_limit:
            self._fail_job(job, give_up)
            return
        delay = min(self.retry_backoff_s * 2 ** (job.retries - 1),
                    self.retry_backoff_cap_s)
        if self.trace is not None:
            self.trace.emit("job_resubmit", job=job.id, epoch=job.epoch,
                            attempt=job.retries, delay_s=delay, reason=reason)
        job.phase = RETRY_WAIT
        jid, epoch = job.id, job.epoch
        box: dict = {}

        def fire() -> None:
            self._timers.discard(box["t"])
            self._events.put(("retry_job", jid, epoch))

        timer = box["t"] = threading.Timer(delay, fire)
        timer.daemon = True
        self._timers.add(timer)
        timer.start()

    def _on_retry(self, jid: int, epoch: int) -> None:
        job = self._jobs.get(jid)
        if job is None or job.phase != RETRY_WAIT or job.epoch != epoch:
            return
        self._redispatch(job)

    def _on_job_timeout(self, jid: int, epoch: int) -> None:
        job = self._jobs.get(jid)
        if job is None or job.phase != RUNNING or job.epoch != epoch:
            return
        w = self._workers.get(job.node) if job.node else None
        if w is not None:
            w.outstanding.discard(jid)
        self._retry_or_fail(
            job, "dispatch timed out",
            TransferFailed("control", job.node or "?", job.retries + 1,
                           "dispatch timed out"))

    def _redispatch(self, job: _RJob) -> None:
        """Resubmit from the job's current step.  The epoch bump makes any
        late reply from the previous dispatch stale; duplicate results are
        harmless anyway (dup-put no-ops in the content-addressed store)."""
        self.resubmits += 1
        job.epoch += 1
        job.node = None
        job.phase = RESOLVE
        if job.whnf is not None and job.strict:
            self._strictify_guarded(job)
        else:
            self._advance_guarded(job)

    # ---------------------------------------------------------- quarantine
    def _store_read(self, h: Handle, dst: str) -> Optional[bytes]:
        """Store read with rot handling: CorruptData quarantines the entry
        and starts recovery; the caller sees 'absent', never the rot."""
        try:
            return self.store.get(h)
        except CorruptData:
            self._quarantine(h, via="read", dst=dst)
            try:
                # the client-repo re-put branch of recovery is synchronous:
                # the content may already be back, verified
                return self.store.get(h)
            except CorruptData:  # pragma: no cover - re-rotted immediately
                return None

    def _quarantine(self, h: Handle, via: str, dst: str) -> None:
        """Evict a rotten store entry and start recovery: re-put from the
        client repo, ask a live worker that holds the content to push it
        back, or recompute it through the recorded lineage encode."""
        key = h.content_key()
        with self._quar_lock:
            if key in self._quarantined:
                return  # already quarantined; recovery underway
            self._quarantined.add(key)
        with self._store_mutex:
            self.store.delete(h)
        self.quarantines += 1
        key_hex = key.hex()
        tr = self.trace
        if tr is not None:
            tr.emit("corruption_detected", dst="store", key=key_hex, via=via,
                    reader=dst)
            tr.emit("quarantine", node="store", key=key_hex)
        self._locs.discard(key, "store")
        if self._repo.contains(h):
            with self._store_mutex:
                self._ensure_in_store_locked(None, h)
            return
        holders = [n for n in self._locs.nodes_for(key)
                   if n in self._workers and self._workers[n].alive]
        if holders:
            w = self._workers[holders[0]]
            self._recomputing.add(key)
            if tr is not None:
                tr.emit("stage_request", job=None, dst="store", key=key_hex,
                        nbytes=payload_nbytes(h), action="push",
                        src=holders[0])
            try:
                self._ctl_send(w, {"op": "push", "raws": [h.raw]})
                return
            except OSError:
                pass  # the holder died under us: fall through to recompute
        enc_raw = self._lineage.get(key)
        if enc_raw is not None:
            self._recomputing.add(key)
            self.recomputes += 1
            if tr is not None:
                tr.emit("stage_request", job=None, dst="store", key=key_hex,
                        nbytes=payload_nbytes(h), action="recompute",
                        src=None)
            self._events.put(("submit", Handle(enc_raw), None, None, True,
                              None))

    # ------------------------------------------------------------ terminal
    def _finalize(self, job: _RJob, result: Handle) -> None:
        job.result = result
        job.phase = DONE
        if self.trace is not None:
            self.trace.emit("job_finish", job=job.id, node=job.node,
                            result=result.raw.hex())
        self._count_job(job, "finished")
        if self.metrics is not None:
            tl = {} if job.tenant is None else {"tenant": job.tenant}
            self.metrics.histogram("job_latency_s", **tl).observe(
                time.monotonic() - job._metric_t0)
        if self.spans is not None and job.span is not None:
            self.spans.end(job.span, status="ok")
            job.span = None
        self._memo.setdefault(job.encode.raw, result)
        for f in job.futures:
            f.set(result)
        for pid in job.parents:
            self._child_resolved(pid, job.encode)

    def _fail_job(self, job: _RJob, exc: BaseException) -> None:
        if job.phase == DONE:
            return
        job.phase = DONE
        if self.trace is not None:
            self.trace.emit("job_fail", job=job.id, error=type(exc).__name__)
        self._count_job(job, "failed")
        if self.spans is not None and job.span is not None:
            self.spans.end(job.span, status="fail")
            job.span = None
        for f in job.futures:
            f.set_exception(exc)
        self._notify_parents_exc(job, exc)

    def _notify_parents_exc(self, job: _RJob, exc: BaseException) -> None:
        for pid in job.parents:
            parent = self._jobs.get(pid)
            if parent is not None and parent.phase != DONE:
                self._fail_job(parent, exc)

    # -------------------------------------------------------------- cancel
    def _on_cancel(self, fut: Future, reason: str) -> None:
        exc = self._cancel_exc(reason)
        jid = getattr(fut, "_jid", None)
        job = self._jobs.get(jid) if jid is not None else None
        if job is None or job.phase == DONE:
            fut.set_exception(exc)  # no-op if it already completed
            return
        others = [f for f in job.futures if f is not fut]
        if others or job.parents:
            # the job is shared (dedup or a parent's child): cancel only
            # this waiter, the computation itself is still wanted
            fut.set_exception(exc)
            job.futures = others
            return
        self._cancel_job(job, reason)

    def _cancel_job(self, job: _RJob, reason: str) -> None:
        if job.phase == DONE:
            return
        job.phase = DONE
        if self.trace is not None:
            self.trace.emit("job_cancel", job=job.id, reason=reason)
        self._count_job(job, "cancelled")
        if self.spans is not None and job.span is not None:
            self.spans.end(job.span, status="cancel")
            job.span = None
        exc = self._cancel_exc(reason)
        for f in job.futures:
            f.set_exception(exc)
        job.futures = []
        if job.node is not None:
            w = self._workers.get(job.node)
            if w is not None:
                w.outstanding.discard(job.id)
        # prune orphaned children: a child submitted only on behalf of
        # this job (no other parent, no direct waiter) is cancelled too
        for cid in sorted(job.children):
            child = self._jobs.get(cid)
            if child is None or child.phase == DONE:
                continue
            if job.id in child.parents:
                child.parents.remove(job.id)
            if not child.parents and not child.futures:
                self._cancel_job(child, reason)

    def _on_teardown(self) -> None:
        exc = WorkerCrashed("backend closed with work outstanding")
        for job in list(self._jobs.values()):
            if job.phase != DONE:
                self._fail_job(job, exc)

    # ------------------------------------------------------------ localize
    def _localize(self, handle: Handle) -> None:
        """Pull a result's object closure store→client (the accounted,
        traced fetch hop — the remote analogue of the cluster's
        ``fetch_result`` link charges)."""
        if handle.is_ref():
            handle = handle.as_object()
        closure = walk_object_closure(
            handle, lambda h: self._memo.get(h.raw),
            self._tree_children, {})
        for h in closure:
            self._pull_to_client(h)

    def _localize_shallow(self, handle: Handle) -> None:
        """Pull only this handle's own content (a tree node, not its
        children) — the streaming-fetch hop."""
        if handle.is_ref():
            handle = handle.as_object()
        self._pull_to_client(handle)

    def _pull_to_client(self, h: Handle) -> None:
        if h.is_literal or self._repo.contains(h):
            return
        key = h.content_key()
        payload = self._store_read(h, dst="client")
        if payload is None and key in self._recomputing:
            # quarantine recovery is in flight: wait (bounded) for the
            # re-put/recompute to land rather than failing a good answer
            deadline = time.monotonic() + self.recover_wait_s
            while payload is None and time.monotonic() < deadline:
                if key not in self._recomputing:
                    payload = self._store_read(h, dst="client")
                    break
                time.sleep(0.02)
                payload = self._store_read(h, dst="client")
        if payload is None:
            if key in self._quarantined:
                raise CorruptData(h)
            raise MissingData(h)
        nbytes = payload_nbytes(h)
        data = (payload if h.content_type == BLOB
                else decode_tree_payload(payload))
        tr = self.trace
        key_hex = key.hex()
        with self._store_mutex:
            if self._repo.contains(h):
                return
            if tr is not None:
                tr.emit("stage_request", job=None, dst="client", key=key_hex,
                        nbytes=nbytes, action="enqueue", src="store")
            self._repo.put_handle_data(h, data)  # fires put(node="client")
            if tr is not None:
                tr.emit("transfer_deliver", src="store", dst="client", n=1,
                        nbytes=nbytes, keys=[key_hex], ok=True, via="store")
        self.transfers += 1
        self.bytes_moved += nbytes
        if self.metrics is not None:
            self.metrics.counter("transfers_total").inc()
            self.metrics.counter("bytes_moved_total").inc(nbytes)

    # ----------------------------------------------------------- listeners
    def _on_store_put(self, handle: Handle, nbytes: int, src: str) -> None:
        key = handle.content_key()
        self._locs.add(key, "store")
        self._quarantined.discard(key)   # verified content re-installed
        self._recomputing.discard(key)   # recovery (if any) has landed
        if self.trace is not None:
            self.trace.emit("put", node="store", key=key.hex(), nbytes=nbytes)

    def _on_client_put(self, handle: Handle) -> None:
        self._locs.add(handle.content_key(), "client")
        if self.trace is not None:
            self.trace.emit("put", node="client",
                            key=handle.content_key().hex(),
                            nbytes=payload_nbytes(handle))


def remote(n_workers: int = 2, **kwargs) -> RemoteBackend:
    """Spawn a multi-process backend: ``fix.remote(n_workers=4)``."""
    return RemoteBackend(n_workers, **kwargs)
