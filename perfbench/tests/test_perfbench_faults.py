"""The correctness check fails a broken timed path: each fault the training
cell can have, planted under a whole run, and the control (the reference
one precision down, in the program's place) through the same check."""
import time

import pytest

import tiny
from harness.cli import run_cell
from harness.readings import train_readings
from harness.spec import load_cell

TRAIN = "mamba2_780m.train_b4s2048"


@pytest.fixture(scope="module")
def bench_path(tmp_path_factory):
    return tiny.build(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch", "control"])
def test_training_faults_come_out_not_correct(bench_path, fault):
    line = run_cell(TRAIN, 3, 1.0, False, time.perf_counter(), require_tpu=False, compile_cache=False,
                    fault=fault, bench_path=bench_path, emit=lambda _l: None)
    assert line["correct"] is (fault is None)


def test_training_control_and_faults_fail_the_limits(bench_path):
    cell = load_cell(TRAIN, bench_path)
    r = train_readings(cell, 5, 0.5, control=True, faults=("half_batch",),
                       log=lambda *a: None)
    assert all(r["program"][k] <= v for k, v in cell.limits.items())
    assert any(r["control"][k] > v for k, v in cell.limits.items())
    assert any(r["half_batch"][k] > v for k, v in cell.limits.items())
