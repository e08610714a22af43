"""``run.py``: one cell of BENCHMARK.json, run once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: each number the
correctness check compared, with its limit.  The same numbers are the last
lines of standard error.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
from pathlib import Path

from .clock import now
from .spec import ROOT, SpecError, load_cell

TRACE_DIR = ROOT / ".bench_trace"


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench", type=Path, default=None,
                    help="a file shaped like BENCHMARK.json that names the cell "
                         "(default: BENCHMARK.json at the checkout's root)")
    return ap.parse_args(argv)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {len(devices)} {dev.platform} "
                         f"device(s) ({dev.device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": chips}


def memory_peak(chips: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(cell, obs, trace: bool) -> dict:
    out = {}
    for m in cell.metrics(trace):
        value = cell.reader(m["name"]).read(obs)
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def read_trace(run, obs, trace_dir: Path, log=log) -> None:
    """The window's profiler trace into ``obs.device``
    (:func:`harness.trace.reduce_trace`) and, where a device plane holds
    operations, ``obs.program`` (:func:`harness.program.reduce_program`),
    the scope of each operation from the run's program compiled anew (one
    compile, after the window).  The trace is then deleted."""
    from .program import reduce_program, scope_map
    from .trace import load_xplane, reduce_trace

    trace = load_xplane(trace_dir)
    obs.device = reduce_trace(trace)
    if obs.device is not None:
        t = now()
        scopes = scope_map(run.compiled_text())
        log(f"scope map: the program compiled anew in {now() - t!r} s")
        t = now()
        obs.program = reduce_program(trace, scopes)
        log(f"program trace reduced in {now() - t!r} s")
    shutil.rmtree(trace_dir, ignore_errors=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, fault: str | None = None,
             bench_path: Path | None = None, emit=print,
             compile_cache: bool = True) -> dict:
    """Run the cell and return (and emit) its result line."""
    import jax

    cell = load_cell(workload, bench_path)
    device = device_info(cell.chips, require_tpu)
    from repro.launch.compile_cache import enable_compile_cache

    from . import peaks as P
    from .clock import CompileClock
    from .observed import Observed

    cache_dir = None
    if compile_cache:
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    peaks = P.peaks_for(device["kind"]) if require_tpu else P.PEAKS["TPU v5 lite"]
    obs = Observed(cell=workload, config=cell.config, traffic=cell.traffic, peaks=peaks)
    trace_dir = TRACE_DIR / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"{workload}: seed {seed}, {seconds} s, trace {int(trace)}; device "
        f"{device}; compile cache {cache_dir}")
    D = cell.driver()
    run = D.Run(cell, seed, seconds, trace, trace_dir, obs, t_start, fault, log)
    run.setup()
    obs.setup_s = now() - t_start
    log(f"set-up {obs.setup_s!r} s; compile {clock.seconds!r} s in "
        f"{clock.compiles} compiles, {clock.cache_hits} persistent-cache hits")
    run.window(clock)
    log(f"window {obs.window_s!r} s; compiles inside the window: "
        f"{run.compiles_in_window}")
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    if trace:
        read_trace(run, obs, trace_dir)
    metrics = read_metrics(cell, obs, trace)
    t_check = now()
    result_check = D.check(run, cell.reference(), cell.limits)
    log(f"check {now() - t_check!r} s: " + json.dumps(
        {k: v for k, v in result_check.items() if k not in ("numbers", "correct")}))
    line = {"correct": bool(result_check["correct"]),
            "attempted": run.attempted(), "failed": run.failed(),
            "metrics": metrics, "device": device}
    if trace and obs.device is not None:
        device["busy_s"] = obs.device["busy_s"]
        device["window_s"] = obs.device["window_s"]
        line["breakdown"] = {"device_ops": obs.device["device_ops"],
                             "idle_gaps": obs.device["idle_gaps"]}
    line["checks"] = {name: {"value": v, "limit": lim, "pass_if": rel}
                      for name, (v, lim, rel) in result_check["numbers"].items()}
    for name, c in line["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r}, pass if {c['pass_if']} limit)")
    emit(json.dumps(line))
    return line


def main(argv=None, t_start: float | None = None) -> int:
    t_start = now() if t_start is None else t_start
    args = parse(argv)
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start,
                 bench_path=args.bench)
    except SystemExit as e:
        log(f"run.py: {e}")
        return 1
    except (SpecError, ImportError) as e:
        log(f"run.py: {type(e).__name__}: {e}")
        return 2
    return 0
