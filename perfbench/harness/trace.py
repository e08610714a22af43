"""Reduce a profiler trace to device busy time, time per jitted program,
the operations that took most time, and idle gaps by host span.

The reduction works on a plain structure, so that a small recorded trace
can be kept as a test fixture:

    {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
      "events": [[name, start_ns, duration_ns], ...]}, ...]}, ...]}

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation and their ``XLA Modules`` line one per program
run.  Host spans are the benchmark's own ``bench.*`` annotations, on any
line of the ``/host:CPU`` plane.  The device's clock is off the host's by
about a millisecond (on a v5e its events read 1.3 ms early), so device
events are moved by the median gap between each program run and the
nearest host dispatch of that program (``PjitFunction(<name>)``).
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.window"


def load_xplane(trace_dir: Path) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir`` as the plain structure."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    planes = []
    for plane in pd.planes:
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def op_name(event: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event.split(" = ", 1)[0].lstrip("%")


def program_name(module_event: str) -> str:
    """``jit_decode_jit(1234)`` -> ``decode_jit``."""
    name = module_event.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def host_dispatches(trace: dict) -> dict:
    """{program: [start_ns]} of the host's ``PjitFunction(<program>)`` events."""
    out: dict = defaultdict(list)
    for plane in trace["planes"]:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for name, start, _ in line["events"]:
                    if name.startswith("PjitFunction(") and name.endswith(")"):
                        out[name[len("PjitFunction("):-1]].append(start)
    return out


def clock_offset(modules: list, dispatches: dict) -> float:
    """Median of (nearest host dispatch - device run start) over the runs."""
    diffs = []
    for name, s, _ in modules:
        starts = dispatches.get(program_name(name))
        if starts:
            diffs.append(min((h - s for h in starts), key=abs))
    return float(sorted(diffs)[len(diffs) // 2]) if diffs else 0.0


def host_spans(trace: dict) -> list:
    """[(name, start_ns, end_ns)] of every ``bench.*`` span on the host."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith("bench."):
                    out.append((name, start, start + dur))
    return out


def reduce_trace(trace: dict, top: int = 10) -> dict | None:
    """Busy and window seconds (averaged over the devices), device seconds
    per program, the ``top`` operations by device seconds, and idle
    seconds by the innermost host span that was open in each gap.

    The window is the ``bench.window`` host span, or the extent of the
    device events where there is none.  None where no device plane holds
    an operation."""
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    spans = host_spans(trace)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    dispatches = host_dispatches(trace)
    per_device = []
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops, modules = lines.get("XLA Ops", []), lines.get("XLA Modules", [])
        if ops:
            dt = clock_offset(modules, dispatches)
            per_device.append(([(n, s + dt, d) for n, s, d in ops],
                               [(n, s + dt, d) for n, s, d in modules]))
    if not per_device:
        return None
    if windows:
        lo, hi = windows[0]
    else:
        starts = [s for ops, _ in per_device for _, s, _ in ops]
        ends = [s + d for ops, _ in per_device for _, s, d in ops]
        lo, hi = min(starts), max(ends)
    window_ns = hi - lo
    busy_ns, gaps = [], []
    op_ns: dict = defaultdict(float)
    prog_ns: dict = defaultdict(float)
    prog_calls: dict = defaultdict(int)
    for ops, modules in per_device:
        busy = _union(_clip([[s, s + d] for _, s, d in ops], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, d in ops:
            if s < hi and s + d > lo:
                op_ns[op_name(name)] += min(s + d, hi) - max(s, lo)
        for name, s, d in modules:  # a run counts where its middle falls
            if lo <= s + d / 2 <= hi:
                prog_ns[program_name(name)] += d
                prog_calls[program_name(name)] += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    idle_by_span: dict = defaultdict(float)
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    for s, e in gaps:
        mid = (s + e) / 2
        open_spans = [(e2 - s2, n) for n, s2, e2 in inner if s2 <= mid <= e2]
        idle_by_span[min(open_spans)[1] if open_spans else "no span"] += e - s
    n_dev = len(per_device)
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": window_ns / 1e9,
        "devices": n_dev,
        "program_s": {k: v / n_dev / 1e9 for k, v in prog_ns.items()},
        "program_calls": {k: v // n_dev for k, v in prog_calls.items()},
        "device_ops": [[n, v / n_dev / 1e9] for n, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v / n_dev / 1e9] for n, v in
                      sorted(idle_by_span.items(), key=lambda kv: -kv[1])[:top]],
        "window_ns": [lo, hi],
    }
