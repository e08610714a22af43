"""Whole runs of a cell at the tiny sizes on the CPU, past the chip check:
the last line's keys and metrics; and a run without a TPU prints nothing."""
import json
import os
import subprocess
import sys
import time

import pytest

import tiny
from harness.cli import run_cell

REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def bench_path(tmp_path_factory):
    return tiny.build(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload,trace", [
    ("mamba2_780m.train_b4s2048", False),
    ("mamba2_780m.train_b4s2048", True),
    ("qwen3_4b.agent_sysprompt", True),
    ("qwen3_4b.agent_sysprompt", False),
    ("qwen3_4b.reasoning_backlog", False),
    ("qwen3_4b.reasoning_backlog", True),
])
def test_last_line_keys_and_metrics(bench_path, workload, trace, capsys):
    line = run_cell(workload, 2**31 + 17, 1.5, trace, time.perf_counter(),
                    require_tpu=False, compile_cache=False, bench_path=bench_path)
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last == line
    keys = list(last)
    assert keys[:5] == REQUIRED
    assert set(keys) <= set(REQUIRED) | {"breakdown", "checks"}
    assert keys[-1] == "checks" and last["checks"]
    # each compared number is on standard error's last lines with its limit
    err = out.err.strip().splitlines()
    assert all(any(f"check {k} = " in e and "limit" in e for e in err[-len(last["checks"]):])
               for k in last["checks"])
    bench = json.loads(bench_path.read_text())
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[kind] if workload in m.get("workloads", [workload])}
    assert set(last["metrics"]) == want
    assert all(set(v) == {"value", "unit"} for v in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    if workload.startswith("mamba2_780m."):
        assert last["correct"] is True
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(last["device"])


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(tiny.BENCH_DIR / "run.py"), "--workload",
                        "mamba2_780m.train_b4s2048", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tiny.ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
