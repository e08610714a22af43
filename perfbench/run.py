#!/usr/bin/env python3
"""Run one benchmark cell once; see ``harness/cli.py``.

    python3 perfbench/run.py --workload qwen3_4b.agent_sysprompt --seed 7 \
        --seconds 40 --trace 0
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from harness.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
