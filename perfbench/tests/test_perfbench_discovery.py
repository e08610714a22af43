"""A cell, a traffic mix and a metric added as new files plus entries in
BENCHMARK.json are found by name, with no edit to any file there."""
import json
import time

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
