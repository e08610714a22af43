"""The program's spans, counters and scopes read over a window
(``harness.program``, ``spans.py``): the reduction of ``repro.*`` host spans
and scoped, nested device operations, the readings from counter deltas, a
tiny whole run on the CPU, and ``reduce_trace`` reading as it did."""
import importlib.util
import json

import pytest

import tiny
from harness.program import innermost, readings, reduce_program, scope_map
from harness.spec import load_cell
from harness.trace import op_name, reduce_trace

DATA = tiny.BENCH_DIR / "tests" / "data"
MS = 1_000_000
SSD = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/mamba2.ssd/dot_general"
OTHER = "jit(train_step)/jvp()/while/body/closed_call/dot_general"


def synthetic():
    """One train_step run of 60 ms inside a 100-ms window: a while of 40 ms
    holding two SSD ops (10 ms, 5 ms) and one other (20 ms), then a 10-ms
    op; the host fetches (a 15-ms span holding a 5-ms evaluation on
    another thread) and collects (2 ms) while the device is idle."""
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench.window", 0, 100 * MS],
                ["repro.train.step", 1 * MS, 2 * MS],
                ["PjitFunction(train_step)", 1 * MS, 2 * MS],
                ["repro.fix.fetch", 70 * MS, 15 * MS],
                ["repro.gc", 90 * MS, 2 * MS],
                ["bench.train_step", 0, 100 * MS]]},
            {"name": "python", "events": [["repro.fix.evaluate", 72 * MS, 5 * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_train_step(7)", 1 * MS, 60 * MS]]},
            {"name": "XLA Ops", "events": [
                ["%while.17 = (s32[]) while(%tuple.1)", 1 * MS, 40 * MS],
                ["%fusion.1 = bf16[4] fusion(%p.1)", 1 * MS, 10 * MS],
                ["%fusion.2 = bf16[4] fusion(%p.2)", 11 * MS, 5 * MS],
                ["%fusion.3 = bf16[4] fusion(%p.3)", 16 * MS, 20 * MS],
                ["%fusion.4 = bf16[4] fusion(%p.4)", 51 * MS, 10 * MS]]}]}]}


HLO = f"""HloModule jit_train_step
  %fusion.1 = bf16[4]{{0}} fusion(%p.1), kind=kLoop, metadata={{op_name="{SSD}" stack_frame_id=3}}
  %fusion.2 = bf16[4]{{0}} fusion(%p.2), kind=kLoop, metadata={{op_name="{SSD}"}}
  ROOT %fusion.3 = bf16[4]{{0}} fusion(%p.3), kind=kLoop, metadata={{op_name="{OTHER}"}}
  %fusion.4 = bf16[4]{{0}} fusion(%p.4), kind=kLoop, metadata={{op_name="{OTHER}"}}
  %while.17 = (s32[]) while(%tuple.1), metadata={{op_name="jit(train_step)/jvp()/while"}}
  %tuple.1 = (s32[]) tuple(%p.0)
"""


def test_scope_map_reads_each_instructions_op_name():
    scopes = scope_map(HLO)
    assert scopes["fusion.1"] == SSD and scopes["fusion.3"] == OTHER
    assert scopes["while.17"] == "jit(train_step)/jvp()/while"
    assert "tuple.1" not in scopes


def test_innermost_leaves_out_what_holds_other_ops():
    ops = synthetic()["planes"][1]["lines"][1]["events"]
    assert [op_name(o[0]) for o in innermost(ops)] == ["fusion.1", "fusion.2", "fusion.3",
                                                       "fusion.4"]


def test_reduce_synthetic_program_trace():
    r = reduce_program(synthetic(), scope_map(HLO))
    assert r["window_s"] == pytest.approx(0.1)
    assert r["program_calls"] == {"train_step": 1}
    assert r["scope_s"] == {"mamba2.ssd": pytest.approx(0.015)}
    # idle 0-1, 41-51 and 61-100 (the while is busy throughout): fetch
    # 70-85 less its evaluation 72-77, the evaluation, gc 90-92, and 33 ms
    # with no program span open
    idle = dict(r["idle_by_span"])
    assert idle["repro.fix.fetch"] == pytest.approx(0.010)
    assert idle["repro.fix.evaluate"] == pytest.approx(0.005)
    assert idle["repro.gc"] == pytest.approx(0.002)
    assert r["unattributed_idle_s"] == pytest.approx(0.033)
    assert r["unattributed_idle_s"] == pytest.approx(idle["no span"])


def test_no_device_plane_reads_nothing():
    t = synthetic()
    t["planes"] = t["planes"][:1]
    assert reduce_program(t, scope_map(HLO)) is None


def test_readings_from_counters_and_reduction():
    counters = {"span_ns{span=repro.fix.fetch}": 8_000_000, "span_count{span=repro.fix.fetch}": 8,
                "span_ns{span=repro.train.step}": 16_000_000,
                "span_count{span=repro.train.step}": 8,
                "span_ns{span=repro.gc}": 400_000, "span_count{span=repro.gc}": 3,
                "gc_collections{generation=0}": 3}
    r = readings(counters, 8, reduce_program(synthetic(), scope_map(HLO)))
    assert r == {"fix.fetch_ms": pytest.approx(1.0), "train.dispatch_ms": pytest.approx(2.0),
                 "python.gc_ms": pytest.approx(0.05), "train.ssd_device_ms": pytest.approx(15.0),
                 "device_idle_share.unattributed": pytest.approx(33.0)}


def test_a_program_without_spans_reads_nothing():
    """A program that counts nothing (no registry, no ``repro.*`` span, no
    scope) gives no reading and raises nothing."""
    t = synthetic()
    for plane in t["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"] if not e[0].startswith("repro.")]
    assert readings({}, 8, reduce_program(t, {})) == {
        "device_idle_share.unattributed": pytest.approx(50.0)}
    assert readings({}, 8, None) == {}


def test_reduce_recorded_program_trace():
    """Three steps of the tiny Mamba2 train step traced on one v5e, with
    the ``op_name`` of each operation from the same program compiled anew:
    scoped operations nested in the layer loops, ``repro.*`` spans on two
    host threads."""
    fx = json.loads((DATA / "trace_program_small.json").read_text())
    r = reduce_program(fx["trace"], fx["op_scopes"])
    whole = reduce_trace(fx["trace"])
    assert r["program_calls"] == {"train_step": 3} == whole["program_calls"]
    assert r["window_s"] == whole["window_s"]
    # the SSD's innermost operations, not the loops that hold them
    assert r["scope_s"]["mamba2.ssd"] == pytest.approx(0.000231287)
    assert r["scope_s"]["mamba2.ssd"] < whole["busy_s"]
    # every idle nanosecond is put down to one span, or to none
    idle = dict(r["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(whole["window_s"] - whole["busy_s"])
    assert set(idle) == {"no span", "repro.fix.evaluate", "repro.train.step",
                         "repro.fix.fetch", "repro.data.batch"}
    assert r["unattributed_idle_s"] == idle["no span"] == pytest.approx(0.004016919)


def test_reduce_trace_reads_as_before_on_the_recorded_trace():
    trace = json.loads((DATA / "trace_small.json").read_text())
    want = json.loads((DATA / "trace_small.reduced.json").read_text())
    assert reduce_trace(trace) == want


@pytest.fixture(scope="module")
def spans_tool():
    spec = importlib.util.spec_from_file_location("spans_tool", tiny.BENCH_DIR / "spans.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fresh_compile_text_compiles_anew(spans_tool):
    """The step that ran is in JAX's caches with the ``op_name``s of
    whichever program compiled it first; the text comes from a compile."""
    import jax
    import jax.numpy as jnp

    from repro.obs import spans

    spans.install()

    @jax.jit
    def fresh_probe(x):
        with jax.named_scope("mamba2.ssd"):
            return jnp.sin(x) * 2

    x = jnp.ones(8)
    fresh_probe(x)
    key = "jax_compiles{program=jit(fresh_probe)}"
    before = spans.REGISTRY.snapshot()["counters"][key]
    text = spans_tool.fresh_compile_text(fresh_probe, x)
    assert spans.REGISTRY.snapshot()["counters"][key] == before + 1
    assert "mamba2.ssd" in "".join(scope_map(text).values())


@pytest.mark.parametrize("traced", [True, False])
def test_tiny_whole_run_reads_the_program_spans(spans_tool, traced, tmp_path):
    cell = load_cell("mamba2_780m.train_b4s2048", tiny.build(tmp_path / "bench"))
    line = spans_tool.window_readings(cell, 2**31 + 17, 1.0, traced, tmp_path / "trace",
                                      lambda *a: None)
    steps = line["steps"]
    assert steps >= 1
    got = line["readings"]
    # the CPU has no TPU plane: the device readings need a chip
    assert set(got) == {"fix.fetch_ms", "train.dispatch_ms", "python.gc_ms"}
    assert 0 < got["fix.fetch_ms"] <= line["train.data_wait_ms"]
    assert got["train.dispatch_ms"] > 0 and got["python.gc_ms"] >= 0
    c = line["counters"]
    assert c["span_count{span=repro.fix.fetch}"] == steps
    assert c["span_count{span=repro.train.step}"] == steps
    assert c["span_count{span=repro.fix.evaluate}"] == steps
    assert not any(k.startswith("jax_compiles") for k in c)  # nothing compiles in the window
    assert (line["median_step_s"]["profiler_on"] is not None) == traced
    assert not (tmp_path / "trace").exists()
