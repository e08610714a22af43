"""BENCHMARK.json, and the tiny copy of every cell the harness can run,
name only files that exist, and keep the naming rules."""
import json
import re

import pytest

import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
from harness.spec import SpecError, load_cell, reader_path

BENCHMARK = tiny.ROOT / "BENCHMARK.json"


@pytest.mark.parametrize("which", ["BENCHMARK", "tiny"])
def test_every_name_resolves_to_a_file(which, tmp_path):
    path = BENCHMARK if which == "BENCHMARK" else tiny.build(tmp_path)
    b = json.loads(path.read_text())
    pb = path.parent / "perfbench"
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        cfg = json.loads((path.parent / c["file"]).read_text())
        assert (pb / "configs" / f"{cfg['reference']}.py").is_file()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs and w["chips"] in (1, 4)
        assert (pb / "traffic" / f"{w['traffic']}.json").is_file()
        assert (pb / "cells" / f"{w['name']}.json").is_file()
        assert len(w["why"]) <= 200
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert reader_path(pb, m["name"]).is_file()
    for m in b["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w])
    for w in b["workloads"]:
        reports = [m for m in b["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reports) >= 2
        assert any(w["name"] in m["workloads"] for m in b["per_layer"])


def test_benchmark_keys_and_command():
    b = json.loads(BENCHMARK.read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "perfbench/run.py"] and b["paths"] == ["perfbench"]
    assert 1 <= b["run_seconds"] <= 51


def test_a_reader_serves_the_names_that_add_a_suffix_to_its_own(tmp_path):
    (tmp_path / "metrics").mkdir()
    for name in ("device_idle_share", "engine.prefix_hit_share", "x.y"):
        (tmp_path / "metrics" / f"{name}.py").write_text("")
    got = {m: reader_path(tmp_path, m).name for m in
           ("device_idle_share.train", "engine.prefix_hit_share", "x.y.z", "x.y")}
    assert got == {"device_idle_share.train": "device_idle_share.py",
                   "engine.prefix_hit_share": "engine.prefix_hit_share.py",
                   "x.y.z": "x.y.py", "x.y": "x.y.py"}


def test_a_traffic_kind_with_no_driver_is_refused(tmp_path):
    bench_path = tiny.build(tmp_path)
    mix = tmp_path / "perfbench" / "traffic" / "train_b4s2048.json"
    mix.write_text(json.dumps({**json.loads(mix.read_text()), "kind": "../serve"}))
    with pytest.raises(SpecError):
        load_cell("mamba2_780m.train_b4s2048", bench_path).driver()
