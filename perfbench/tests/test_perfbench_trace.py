"""The trace reduction on a small recorded trace: busy and idle time,
device time per program, and the breakdown."""
import json

import pytest

import tiny
from harness.trace import program_name, reduce_trace

FIXTURE = tiny.BENCH_DIR / "tests" / "data" / "trace_small.json"


def synthetic():
    ms = 1_000_000
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 0, 100 * ms],
            ["bench.step", 5 * ms, 40 * ms],
            ["bench.decode_fn", 10 * ms, 30 * ms],
            ["bench.submit", 60 * ms, 10 * ms],
            ["unrelated", 0, 100 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_decode_jit(11)", 10 * ms, 20 * ms],
                ["jit_prefill_jit(12)", 70 * ms, 10 * ms]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10 * ms, 15 * ms],
                ["fusion.2", 25 * ms, 5 * ms],
                ["dot.3", 70 * ms, 10 * ms],
                ["fusion.1", 90 * ms, 20 * ms]]}]}]}


def test_reduce_synthetic_trace():
    r = reduce_trace(synthetic())
    assert r["window_s"] == pytest.approx(0.1)
    # busy: 10-30 ms, 70-80 ms and 90-100 ms (clipped at the window's end)
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["program_s"] == {"decode_jit": pytest.approx(0.02),
                              "prefill_jit": pytest.approx(0.01)}
    assert r["program_calls"] == {"decode_jit": 1, "prefill_jit": 1}
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]
    # idle 0-10 ms (the step is open at its middle), 30-70 and 80-90 ms
    # (nothing open at their middles)
    assert dict(r["idle_gaps"]) == {"bench.step": pytest.approx(0.010),
                                    "no span": pytest.approx(0.050)}
    assert r["idle_gaps"][0][0] == "no span"


def test_reduce_recorded_trace():
    trace = json.loads(FIXTURE.read_text())
    r = reduce_trace(trace)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["program_calls"]["decode_jit"] == 3
    assert r["program_s"]["decode_jit"] <= 1.01 * r["busy_s"]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert all(s >= 0 for _, s in r["idle_gaps"])


def test_no_device_plane_reads_nothing():
    t = synthetic()
    t["planes"] = t["planes"][:1]
    assert reduce_trace(t) is None


def test_program_name():
    assert program_name("jit_decode_jit(1234)") == "decode_jit"
    assert program_name("jit_train_step") == "train_step"
