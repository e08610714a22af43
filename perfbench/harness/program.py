"""The program's own spans, counters and scopes, read over a window.

The program counts into one process-wide registry (``repro.obs.spans``):
``span_ns`` / ``span_count`` per ``repro.*`` span, ``gc_collections`` and
``jax_compiles``.  A window's readings are the deltas of those counters per
step of the window.

In a traced window the same spans are on the profiler's host plane.  The
device's operation events carry no ``jax.named_scope`` path (on a v5e their
stats are durations and offsets only), so each operation's scope comes from
the ``op_name`` metadata of the instruction of that name in the compiled
program's text (:func:`scope_map` of :func:`fresh_compile_text`).  The trace
is the plain structure of :func:`harness.trace.load_xplane`, reduced on the
clock :func:`harness.trace.clock_offset` gives: device seconds of the
innermost operations under each scope and of each such operation, and the
device's idle time by the innermost ``repro.*`` span open on any host
thread.
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


def fresh_compile_text(fn, *args) -> str:
    """``fn`` compiled anew for ``args``, as text: with JAX's in-memory
    caches cleared and the persistent cache off.  The persistent cache's key
    leaves out debug information, so an executable read from it carries the
    ``op_name`` metadata of whichever program wrote it; compilation is
    deterministic, so a fresh one names every instruction as the executable
    that ran does."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return fn.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


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
    """Over the ``bench.window`` span (else the device events' extent), for
    the program runs whose middle falls in the window: device seconds of the
    innermost operations under each scope, every component of their
    ``op_name`` path but the last (the primitive) being a scope, with
    ``op_scopes`` a :func:`scope_map`; each innermost operation's device
    seconds and runs; the runs by program; idle seconds by the innermost
    ``repro.*`` span open in them, and the idle no such span covers.  Each is
    averaged over the devices.  None where no device plane holds an
    operation."""
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
    op_ns: dict = defaultdict(float)
    op_runs: dict = defaultdict(int)
    runs: dict = defaultdict(int)
    idle_ns: dict = defaultdict(float)
    for ops, modules in devices:
        counted = [(s, s + d) for _, s, d in modules if lo <= s + d / 2 <= hi]
        for name, s, d in modules:
            if lo <= s + d / 2 <= hi:
                runs[program_name(name)] += 1
        for name, s, d in innermost(ops):
            if any(a <= s < b for a, b in counted):
                op = op_name(name)
                op_ns[op] += d
                op_runs[op] += 1
                for scope in set(op_scopes.get(op, "").split("/")[:-1]):
                    scope_ns[scope] += d
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
        "scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
        "ops": {k: [op_ns[k] / n / 1e9, op_runs[k] // n] for k in op_ns},
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


def scope_ms_per_run(program: dict | None, scope: str, prog: str) -> float | None:
    """Device milliseconds under ``scope`` per run of the program ``prog``,
    from a :func:`reduce_program` reduction; None where the reduction, the
    scope or the program's runs are absent."""
    if not program:
        return None
    calls = program["program_calls"].get(prog, 0)
    seconds = program["scope_s"].get(scope)
    return 1000.0 * seconds / calls if calls and seconds else None


def readings(counters: dict, steps: int, program: dict | None) -> dict:
    """The readings of the program's spans that no metric of the benchmark
    reads: the fetch's and the collector's window milliseconds per window
    step, and from a traced window the idle share no program span explains.
    A reading with nothing to read is left out."""
    out = {"fix.fetch_ms": per_step_ms(counters, FETCH, steps),
           "python.gc_ms": per_step_ms(counters, GC, steps)}
    if program:
        out["device_idle_share.unattributed"] = (
            100.0 * program["unattributed_idle_s"] / program["window_s"]
            if program["window_s"] > 0 else None)
    return {k: v for k, v in out.items() if v is not None}
