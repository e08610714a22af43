#!/usr/bin/env python3
"""The program's own spans, counters and scopes over a training cell's
window, on the chip (never run by the benchmark's own runs):

    python3 perfbench/spans.py --workload mamba2_780m.train_b4s2048 \
        --seeds 11,12 --seconds 40 --trace 1

Each seed runs the cell's set-up and window as ``run.py`` does, the trace
read by the same harness calls, without the correctness check, and prints
one JSON line: the cell's per-layer metrics; the readings of the program's
spans that no metric reads (``harness.program.readings``: ``fix.fetch_ms``,
``python.gc_ms``; traced, ``device_idle_share.unattributed``), and traced,
the device time by scope and the idle by span; the window's compiles and
collections by program and generation; and what ``run.py`` does not give:
the median step with the profiler on and off, and the slowest steps with
the collection time and compiles inside each.
"""
import argparse
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--bench", type=Path, default=None,
                    help="a file shaped like BENCHMARK.json (default: BENCHMARK.json)")
    args = ap.parse_args(argv)

    import jax

    from harness import peaks as P
    from harness.cli import TRACE_DIR, device_info
    from harness.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(args.workload, args.bench)
    if cell.traffic["kind"] != "train":
        raise SystemExit(f"{args.workload} is not a training cell")
    device = device_info(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    for seed in [int(s) for s in args.seeds.split(",")]:
        line = window_readings(cell, seed, args.seconds, bool(args.trace),
                               TRACE_DIR / f"spans.{args.workload}",
                               P.peaks_for(device["kind"]), log)
        print(json.dumps({"workload": args.workload, "seed": seed, "device": device, **line}),
              flush=True)
    return 0


def window_readings(cell, seed: int, seconds: float, traced: bool, trace_dir, peaks: dict,
                    log) -> dict:
    import numpy as np

    from harness import program as PG
    from harness import train as TR
    from harness.cli import read_metrics, read_trace
    from harness.clock import CompileClock, now
    from harness.observed import Observed
    from repro.obs.spans import REGISTRY

    gc_ns = REGISTRY.counter("span_ns", span=PG.GC)

    class Steps(TR.Run):
        """The training run, logging each window step: its seconds, whether
        the profiler was on, and the collection time and compiles in it."""

        log_steps: list = []  # set anew by each window

        def _one(self, state):
            import jax

            traced_step = jax.profiler.TraceAnnotation.is_enabled()
            g0, c0, t = gc_ns.value, compiles(), now()
            out = super()._one(state)
            self.log_steps.append((now() - t, traced_step, (gc_ns.value - g0) / 1e6,
                                   compiles() - c0))
            return out

        def window(self, compile_clock):
            self.log_steps = []
            super().window(compile_clock)

    def compiles():
        return sum(v for k, v in REGISTRY.snapshot()["counters"].items()
                   if k.startswith("jax_compiles{"))

    shutil.rmtree(trace_dir, ignore_errors=True)
    obs = Observed(cell=cell.name, config=cell.config, traffic=cell.traffic, peaks=peaks)
    run = Steps(cell, seed, seconds, traced, trace_dir, obs, now(), None, log)
    run.setup()
    run.window(CompileClock())
    out = {}
    if traced:
        read_trace(run, obs, trace_dir, log)
        out["idle_gaps"] = obs.device["idle_gaps"] if obs.device else None
        if obs.program:
            out["program"] = {k: v for k, v in obs.program.items() if k != "ops"}
    run.release()
    took = np.array([s[0] for s in run.log_steps])
    on = np.array([s[1] for s in run.log_steps], bool)
    slow = np.argsort(took)[::-1][:3]
    return {
        "steps": obs.steps,
        "window_s": obs.window_s,
        "metrics": {k: v["value"] for k, v in read_metrics(cell, obs, True).items()},
        "readings": PG.readings(obs.counters, obs.steps, obs.program),
        **out,
        "counters": {k: v for k, v in obs.counters.items() if v and k.startswith(
            ("span_", "gc_collections", "jax_compiles"))},
        "median_step_s": {"profiler_on": float(np.median(took[on])) if on.any() else None,
                          "profiler_off": float(np.median(took[~on])) if (~on).any() else None},
        "slowest_steps": [{"step": int(i), "s": float(took[i]), "traced": bool(on[i]),
                           "gc_ms": run.log_steps[i][2], "compiles": run.log_steps[i][3]}
                          for i in slow],
    }


if __name__ == "__main__":
    sys.exit(main())
