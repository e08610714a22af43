#!/usr/bin/env python3
"""The program's own spans, counters and scopes over a training cell's
window, on the chip (never run by the benchmark's own runs):

    python3 perfbench/spans.py --workload mamba2_780m.train_b4s2048 \
        --seeds 11,12 --seconds 40 --trace 1

Each seed runs the cell's set-up and window as ``run.py`` does, without the
correctness check, and prints one JSON line: the readings of
``harness.program`` (``fix.fetch_ms``, ``train.dispatch_ms``,
``python.gc_ms``; traced, ``train.ssd_device_ms`` and
``device_idle_share.unattributed``) beside the benchmark's own
``train.data_wait_ms`` and, traced, ``device_idle_share.train``; the
window's compiles and collections by program and generation; the median
step with the profiler on and off; and the slowest steps with the
collection time and compiles inside each.
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
                               TRACE_DIR / f"spans.{args.workload}", log)
        print(json.dumps({"workload": args.workload, "seed": seed, "device": device, **line}),
              flush=True)
    return 0


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


def window_readings(cell, seed: int, seconds: float, traced: bool, trace_dir, log) -> dict:
    import numpy as np

    from harness import program as PG
    from harness import train as TR
    from harness.clock import CompileClock, now
    from harness.observed import Observed
    from harness.readers import idle_share
    from harness.trace import load_xplane, reduce_trace
    from repro.obs.spans import REGISTRY, counter_deltas

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
    obs = Observed(cell=cell.name, config=cell.config, traffic=cell.traffic, peaks={})
    run = Steps(cell, seed, seconds, traced, trace_dir, obs, now(), None, log)
    run.setup()
    before = REGISTRY.snapshot()["counters"]
    run.window(CompileClock())
    counters = counter_deltas(before, REGISTRY.snapshot()["counters"])
    program, out = None, {}
    if traced:
        batch = run.pipe.batch_for_step(run.backend, 0)
        hlo = fresh_compile_text(run.step_fn, run.state, batch)
        trace = load_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        program = PG.reduce_program(trace, PG.scope_map(hlo))
        obs.device = reduce_trace(trace)
        out["device_idle_share.train"] = idle_share(obs)
        out["idle_gaps"] = obs.device["idle_gaps"] if obs.device else None
        out["program"] = program
    run.release()
    took = np.array([s[0] for s in run.log_steps])
    on = np.array([s[1] for s in run.log_steps], bool)
    slow = np.argsort(took)[::-1][:3]
    return {
        "steps": obs.steps,
        "window_s": obs.window_s,
        "readings": PG.readings(counters, obs.steps, program),
        "train.data_wait_ms": 1000.0 * obs.spans["batch_for_step_s"] / obs.steps,
        **out,
        "counters": {k: v for k, v in counters.items() if v and k.startswith(
            ("span_", "gc_collections", "jax_compiles"))},
        "median_step_s": {"profiler_on": float(np.median(took[on])) if on.any() else None,
                          "profiler_off": float(np.median(took[~on])) if (~on).any() else None},
        "slowest_steps": [{"step": int(i), "s": float(took[i]), "traced": bool(on[i]),
                           "gc_ms": run.log_steps[i][2], "compiles": run.log_steps[i][3]}
                          for i in slow],
    }


if __name__ == "__main__":
    sys.exit(main())
