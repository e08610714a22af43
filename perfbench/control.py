#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip at the cell's
own size (never run by the benchmark's own runs):

    python3 perfbench/control.py --workload mamba2_780m.train_b4s2048 \
        --seeds 11,12,13 --seconds 1 [--control] [--faults half_batch]
    python3 perfbench/control.py --workload qwen3_4b.agent_sysprompt \
        --seeds 21,22,23 --seconds 20 [--witness]

One process runs every seed; each prints one JSON line, also appended to
the file ``--out`` names.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="",
                    help="comma-separated faults planted in the program (training)")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--out", type=Path, default=None,
                    help="append each reading's line to this file")
    ap.add_argument("--bench", type=Path, default=None,
                    help="a file shaped like BENCHMARK.json (default: BENCHMARK.json)")
    args = ap.parse_args()

    import jax

    from harness.cli import device_info
    from harness.readings import serve_readings, train_readings
    from harness.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(args.workload, args.bench)
    device_info(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    for seed in [int(s) for s in args.seeds.split(",")]:
        if cell.traffic["kind"] == "serve":
            r = serve_readings(cell, seed, args.seconds, witness=args.witness, log=log)
        else:
            r = train_readings(cell, seed, args.seconds, control=args.control,
                               faults=tuple(f for f in args.faults.split(",") if f),
                               log=log)
        r["workload"] = args.workload
        line = json.dumps(r)
        print(line, flush=True)
        if args.out:
            with args.out.open("a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
