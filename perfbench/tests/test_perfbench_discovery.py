"""A cell, a traffic mix, a metric, and a model family's counts and scope
readers added as new files plus entries in BENCHMARK.json are found by
name, with no edit to any file there."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import tiny
from harness.cli import run_cell


def test_new_cell_mix_and_metric_are_found_by_name(tmp_path, capsys):
    bench_path = tiny.build(tmp_path)
    pb = tmp_path / "perfbench"
    before = {p: p.read_bytes() for p in pb.rglob("*") if p.is_file()}
    mix = json.loads((pb / "traffic" / "train_b4s2048.json").read_text())
    mix.update(batch=4, seq=16)
    (pb / "traffic" / "train_b4s16.json").write_text(json.dumps(mix))
    (pb / "cells" / "mamba2_780m.train_b4s16.json").write_text(
        (pb / "cells" / "mamba2_780m.train_b4s2048.json").read_text())
    (pb / "metrics" / "train.steps_in_window.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    bench = json.loads(bench_path.read_text())
    bench["workloads"].append({"name": "mamba2_780m.train_b4s16", "config": "mamba2_780m",
                               "traffic": "train_b4s16", "chips": 1, "why": "new"})
    bench["per_layer"].append({"name": "train.steps_in_window", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "train loop", "moves": "train_tokens_per_s",
                               "workloads": ["mamba2_780m.train_b4s16"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("mamba2_780m.train_b4s16")
    bench_path.write_text(json.dumps(bench))
    line = run_cell("mamba2_780m.train_b4s16", 4, 1.0, True, time.perf_counter(),
                    require_tpu=False, compile_cache=False, bench_path=bench_path, emit=lambda _l: None)
    assert line["metrics"]["train.steps_in_window"]["value"] >= 1
    assert set(line["metrics"]) == {"train.steps_in_window"}
    after = {p: p.read_bytes() for p in before}
    assert after == before


# A family the counts know nothing of, as a later configuration brings it:
# its operation count, and a reader of a scope its step is traced under.
PROBE_COUNTS = """
def train_flops(cfg, batch, seq):
    return 6 * cfg["params"] * batch * seq
"""
PROBE_READER = """
from harness.program import scope_ms_per_run


def read(run):
    return scope_ms_per_run(run.program, "ssd_chunked", "train_step")
"""
# Reads both metrics with the harness of the copy, on a window of 3 steps
# whose trace is the recorded one of a tiny step on one v5e.
READ = """
import json, sys
from pathlib import Path
pb = Path(sys.argv[1])
sys.path[:0] = [str(pb)]
from harness.observed import Observed
from harness.peaks import PEAKS
from harness.program import reduce_program
from harness.spec import load_module, reader_path
fx = json.loads((pb / "tests" / "data" / "trace_program_small.json").read_text())
obs = Observed(cell="probe.train", config={"params": 1000, "program_fixed": {"family": "probe"}},
               traffic={"batch": 2, "seq": 32}, peaks=PEAKS["TPU v5 lite"], window_s=2.0,
               steps=3)
obs.program = reduce_program(fx["trace"], fx["op_scopes"])
print(json.dumps({m: load_module(reader_path(pb, m), m).read(obs)
                  for m in ("train.mfu", "train.probe_ms")}))
"""


def _hashes(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_family_brings_its_counts_and_scope_reader_as_files(tmp_path):
    pb = tmp_path / "perfbench"
    shutil.copytree(tiny.BENCH_DIR, pb, ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(pb)
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1")

    def read():
        return subprocess.run([sys.executable, "-c", READ, str(pb)], cwd=tmp_path, env=env,
                              capture_output=True, text=True, timeout=120)

    unknown = read()
    assert unknown.returncode != 0 and "families/probe.py" in unknown.stderr
    (pb / "families" / "probe.py").write_text(PROBE_COUNTS)
    (pb / "metrics" / "train.probe_ms.py").write_text(PROBE_READER)
    got = read()
    assert got.returncode == 0, got.stderr
    values = json.loads(got.stdout)
    assert values["train.mfu"] == 100.0 * (6 * 1000 * 2 * 32) * 3 / 2.0 / 197e12
    assert values["train.probe_ms"] == pytest.approx(1000 * 5.0147e-05 / 3)
    after = _hashes(pb)
    assert {p: after[p] for p in before} == before
    assert set(after) - set(before) == {Path("families/probe.py"),
                                        Path("metrics/train.probe_ms.py")}
