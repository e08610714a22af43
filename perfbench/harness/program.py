"""The program's own spans, counters and scopes, read over a window.

The program counts into one process-wide registry (``repro.obs.spans``):
``span_ns`` / ``span_count`` per ``repro.*`` span, ``gc_collections`` and
``jax_compiles``.  A window's readings are the deltas of those counters per
step of the window.

In a traced window the same spans are on the profiler's host plane.  The
device's operation events carry no ``jax.named_scope`` path (on a v5e their
stats are durations and offsets only), so each operation's scope comes from
the ``op_name`` metadata of the instruction of that name in the compiled
program's text (:func:`scope_map`).  The trace is the plain structure of
:func:`harness.trace.load_xplane`, reduced on the clock
:func:`harness.trace.clock_offset` gives: device seconds of the innermost
operations under each scope, and the device's idle time by the innermost
``repro.*`` span open on any host thread.
"""
from __future__ import annotations

import re
from collections import defaultdict

from .trace import (
    DEVICE_PLANE,
    WINDOW_SPAN,
    _clip,
    _union,
    clock_offset,
    host_dispatches,
    op_name,
    program_name,
)

SPAN_PREFIX = "repro."
SCOPES = ("mamba2.ssd",)
FETCH, STEP, GC = "repro.fix.fetch", "repro.train.step", "repro.gc"
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*\bop_name="([^"]*)"')


def scope_map(hlo_text: str) -> dict:
    """{instruction name: its ``op_name``, the path of named scopes it was
    traced under} from a compiled program's ``as_text()``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def innermost(ops: list) -> list:
    """The operations that hold no other operation of the same line: a
    ``while`` holds its body's operations, which are counted in its place.
    Events of no duration (a fusion can open with one) hold nothing and are
    held by nothing."""
    ops = sorted((o for o in ops if o[2] > 0), key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= o[1] + o[2]]


def program_spans(trace: dict) -> list:
    """[(name, start_ns, end_ns)] of every ``repro.*`` span on any host line."""
    return [(n, s, s + d) for plane in trace["planes"] if plane["name"].startswith("/host:")
            for line in plane["lines"] for n, s, d in line["events"]
            if n.startswith(SPAN_PREFIX)]


def _idle_by_span(idle: list, spans: list) -> dict:
    """Idle nanoseconds by the innermost span open over them (None: none)."""
    spans = [sp for sp in spans if any(sp[1] < e and s < sp[2] for s, e in idle)]
    out: dict = defaultdict(float)
    for s, e in idle:
        cuts = sorted({s, e} | {x for _, a, b in spans for x in (a, b) if s < x < e})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_spans = [(z - y, n) for n, y, z in spans if y <= mid <= z]
            out[min(open_spans)[1] if open_spans else None] += b - a
    return out


def reduce_program(trace: dict, op_scopes: dict) -> dict | None:
    """Over the ``bench.window`` span (else the device events' extent):
    device seconds of the innermost operations whose scope path (from
    ``op_scopes``, a :func:`scope_map`) holds each of ``SCOPES``, counted
    for the program runs whose middle falls in the window; those runs by
    program; idle seconds by the innermost ``repro.*`` span open in them,
    and the idle no such span covers (each averaged over the devices).
    None where no device plane holds an operation."""
    spans = program_spans(trace)
    windows = [(s, s + d) for plane in trace["planes"] if plane["name"].startswith("/host:")
               for line in plane["lines"] for n, s, d in line["events"] if n == WINDOW_SPAN]
    dispatches = host_dispatches(trace)
    devices = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
            ops, modules = lines.get("XLA Ops", []), lines.get("XLA Modules", [])
            if ops:
                dt = clock_offset(modules, dispatches)
                devices.append(([(n, s + dt, d) for n, s, d in ops],
                                [(n, s + dt, d) for n, s, d in modules]))
    if not devices:
        return None
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for ops, _ in devices for _, s, _ in ops)
        hi = max(s + d for ops, _ in devices for _, s, d in ops)
    scope_ns: dict = defaultdict(float)
    runs: dict = defaultdict(int)
    idle_ns: dict = defaultdict(float)
    for ops, modules in devices:
        counted = [(s, s + d) for _, s, d in modules if lo <= s + d / 2 <= hi]
        for name, s, d in modules:
            if lo <= s + d / 2 <= hi:
                runs[program_name(name)] += 1
        for name, s, d in innermost(ops):
            parts = op_scopes.get(op_name(name), "").split("/")
            if any(a <= s < b for a, b in counted):
                for want in SCOPES:
                    if want in parts:
                        scope_ns[want] += d
        busy = _union(_clip([[s, s + d] for _, s, d in ops], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, ns in _idle_by_span(idle, spans).items():
            idle_ns[name] += ns
    n = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "program_calls": {k: v // n for k, v in runs.items()},
        "scope_s": {k: scope_ns.get(k, 0.0) / n / 1e9 for k in SCOPES},
        "idle_by_span": sorted(([k or "no span", v / n / 1e9] for k, v in idle_ns.items()),
                               key=lambda kv: -kv[1]),
        "unattributed_idle_s": idle_ns.get(None, 0.0) / n / 1e9,
    }


def per_step_ms(counters: dict, span: str, steps: int) -> float | None:
    """A span's window nanoseconds in milliseconds per window step; None
    where the program has no such span."""
    if not steps or f"span_count{{span={span}}}" not in counters:
        return None
    return counters[f"span_ns{{span={span}}}"] / 1e6 / steps


def readings(counters: dict, steps: int, program: dict | None) -> dict:
    """The per-layer readings of the program's spans and scopes: the
    counters' window deltas per window step, and from a traced window the
    SSD's device milliseconds per ``train_step`` run and the idle share no
    program span explains.  A reading with nothing to read is left out."""
    out = {"fix.fetch_ms": per_step_ms(counters, FETCH, steps),
           "train.dispatch_ms": per_step_ms(counters, STEP, steps),
           "python.gc_ms": per_step_ms(counters, GC, steps)}
    if program:
        calls = program["program_calls"].get("train_step", 0)
        out["train.ssd_device_ms"] = (1000.0 * program["scope_s"]["mamba2.ssd"] / calls
                                      if calls and program["scope_s"].get("mamba2.ssd")
                                      else None)
        out["device_idle_share.unattributed"] = (
            100.0 * program["unattributed_idle_s"] / program["window_s"]
            if program["window_s"] > 0 else None)
    return {k: v for k, v in out.items() if v is not None}
