"""The program's spans, counters and scopes read over a window
(``harness.program``, the readers of them, ``spans.py``): the reduction of
``repro.*`` host spans and scoped, nested device operations, the readings
from counter deltas, tiny whole runs on the CPU, and ``reduce_trace``
reading as it did."""
import importlib.util
import json
import time

import pytest

import tiny
from harness import train as TR
from harness.cli import run_cell
from harness.clock import CompileClock
from harness.observed import Observed
from harness.peaks import PEAKS
from harness.program import (
    fresh_compile_text,
    innermost,
    readings,
    reduce_program,
    scope_map,
)
from harness.spec import load_cell, load_module, reader_path
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
    # every component of an innermost operation's path but the primitive
    assert r["scope_s"] == {"mamba2.ssd": pytest.approx(0.015),
                            "checkpoint": pytest.approx(0.015),
                            "transpose(jvp())": pytest.approx(0.015),
                            "jvp()": pytest.approx(0.030),
                            **{k: pytest.approx(0.045) for k in
                               ("jit(train_step)", "while", "body", "closed_call")}}
    assert r["ops"] == {"fusion.1": [pytest.approx(0.010), 1],
                        "fusion.2": [pytest.approx(0.005), 1],
                        "fusion.3": [pytest.approx(0.020), 1],
                        "fusion.4": [pytest.approx(0.010), 1]}
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
    assert r == {"fix.fetch_ms": pytest.approx(1.0), "python.gc_ms": pytest.approx(0.05),
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


def test_every_scope_and_operation_on_the_recorded_program_trace():
    """Every scope of the recorded step, and every innermost operation with
    its runs: those outside ``reduce_trace``'s top 10 too."""
    fx = json.loads((DATA / "trace_program_small.json").read_text())
    r = reduce_program(fx["trace"], fx["op_scopes"])
    want = {part for path in fx["op_scopes"].values() for part in path.split("/")[:-1]}
    assert set(r["scope_s"]) <= want
    assert {"mamba2.ssd", "ssd_chunked", "checkpoint", "jit(train_step)"} <= set(r["scope_s"])
    assert r["scope_s"]["jit(train_step)"] == pytest.approx(0.000340347)
    assert r["scope_s"]["ssd_chunked"] == pytest.approx(5.0147e-05)
    top = {name for name, _ in reduce_trace(fx["trace"])["device_ops"]}
    assert len(r["ops"]) == 446 and len(set(r["ops"]) - top) > 400
    # two layers a step, three steps: an operation runs once or twice a step
    assert {runs for _, runs in r["ops"].values()} == {3, 6}
    assert r["ops"]["pad.675"] == [pytest.approx(6.504e-06), 6]
    under = sum(s for op, (s, _) in r["ops"].items()
                if "mamba2.ssd" in fx["op_scopes"].get(op, "").split("/")[:-1])
    assert under == pytest.approx(r["scope_s"]["mamba2.ssd"])


def test_reduce_trace_reads_as_before_on_the_recorded_trace():
    trace = json.loads((DATA / "trace_small.json").read_text())
    want = json.loads((DATA / "trace_small.reduced.json").read_text())
    assert reduce_trace(trace) == want


def recorded_obs(**kw) -> Observed:
    """What a traced window of the tiny Mamba2 step on one v5e observed: its
    recorded trace reduced, 3 steps."""
    fx = json.loads((DATA / "trace_program_small.json").read_text())
    mix = tiny.tiny_mixes()["train_b4s2048"]
    obs = Observed(cell=tiny.T, config=tiny.tiny_mamba(), traffic=mix,
                   peaks=PEAKS["TPU v5 lite"], steps=3, **kw)
    obs.program = reduce_program(fx["trace"], fx["op_scopes"])
    obs.device = reduce_trace(fx["trace"])
    return obs


def reader(name: str):
    return load_module(reader_path(tiny.BENCH_DIR, name), name)


def test_the_ssd_readers_read_the_scope_per_step():
    obs = recorded_obs()
    ms = 1000 * 0.000231287 / 3
    assert reader("train.ssd_device_ms").read(obs) == pytest.approx(ms)
    # the tiny step's SSD (B 2, S 32, 2 layers of 8 heads of 16, state 16,
    # chunk 8) over the same time
    fam = load_module(tiny.BENCH_DIR / "families" / "mamba2.py", "mamba2")
    flops, nbytes = fam.ssd_train_cost(obs.config, 2, 32)
    least = max(flops / 197e12, nbytes / 819e9)
    assert reader("train.ssd_roofline").read(obs) == pytest.approx(100 * least / (ms / 1000))


def test_the_dispatch_reader_reads_the_step_span_per_step():
    obs = recorded_obs(counters={"span_ns{span=repro.train.step}": 4_500_000,
                                 "span_count{span=repro.train.step}": 3})
    assert reader("train.dispatch_ms").read(obs) == pytest.approx(1.5)


@pytest.mark.parametrize("name", ["train.ssd_device_ms", "train.ssd_roofline",
                                  "train.dispatch_ms"])
def test_the_new_readers_read_nothing_without_their_input(name):
    """No trace (an untraced run, or no device plane), no counters, no
    scope of that name or no ``train_step`` run: nothing, never 0."""
    empty = Observed(cell=tiny.T, config=tiny.tiny_mamba(),
                     traffic=tiny.tiny_mixes()["train_b4s2048"],
                     peaks=PEAKS["TPU v5 lite"], steps=3)
    assert reader(name).read(empty) is None
    obs = recorded_obs()
    obs.program["scope_s"].pop("mamba2.ssd")
    assert reader(name).read(obs) is None
    obs = recorded_obs()
    obs.program["program_calls"] = {}
    obs.steps = 0
    assert reader(name).read(obs) is None


def test_window_counters_land_in_obs(tmp_path):
    """The window's deltas of the program's counters, snapshot outside the
    timed loop: one fetch, one evaluation and one step span each step, and
    no compile."""
    cell = load_cell(tiny.T, tiny.build(tmp_path))
    obs = Observed(cell=cell.name, config=cell.config, traffic=cell.traffic, peaks={})
    run = TR.Run(cell, 2**31 + 23, 1.0, False, None, obs, time.perf_counter(), None,
                 lambda *a: None)
    run.setup()
    run.window(CompileClock())
    run.release()
    c = obs.counters
    assert obs.steps >= 1
    for span in ("repro.fix.fetch", "repro.fix.evaluate", "repro.train.step"):
        assert c[f"span_count{{span={span}}}"] == obs.steps
        assert c[f"span_ns{{span={span}}}"] > 0
    assert not any(v for k, v in c.items() if k.startswith("jax_compiles"))


def test_a_traced_run_hands_the_program_trace_to_the_readers(tmp_path, monkeypatch):
    """``run_cell`` reduces the window's trace by scope, with the scopes of
    the step's program compiled anew: here the recorded v5e trace and the
    scopes recorded with it stand in for the CPU's trace, which has no
    device plane, and the CPU's compile."""
    import harness.trace

    fx = json.loads((DATA / "trace_program_small.json").read_text())
    text = "".join(f'  %{op} = f32[] custom-call(), metadata={{op_name="{path}"}}\n'
                   for op, path in fx["op_scopes"].items())
    assert scope_map(text) == fx["op_scopes"]
    compiled = []
    monkeypatch.setattr(harness.trace, "load_xplane", lambda _d: fx["trace"])
    monkeypatch.setattr(TR.Run, "compiled_text", lambda self: compiled.append(1) or text)
    bench_path = tiny.build(tmp_path)
    bench = json.loads(bench_path.read_text())
    cells = [tiny.T]
    bench["per_layer"] += [
        tiny.layer("train.ssd_device_ms", "ms", "lower", "device_trace", "SSD kernels",
                   "train_tokens_per_s", cells),
        tiny.layer("train.ssd_roofline", "%", "higher", "device_trace", "SSD kernels",
                   "train_tokens_per_s", cells),
        tiny.layer("device_idle_share.train", "%", "lower", "device_trace", "device",
                   "train_tokens_per_s", cells)]
    bench_path.write_text(json.dumps(bench))
    line = run_cell(tiny.T, 2**31 + 29, 1.0, True, time.perf_counter(), require_tpu=False,
                    compile_cache=False, bench_path=bench_path, emit=lambda _l: None)
    assert compiled == [1]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["train.ssd_device_ms"] == pytest.approx(1000 * 0.000231287 / 3)
    assert 0 < m["train.ssd_roofline"] < 100
    assert m["train.dispatch_ms"] > 0 and m["train.mfu"] > 0
    assert set(m) == {"train.ssd_device_ms", "train.ssd_roofline", "device_idle_share.train",
                      "train.dispatch_ms", "train.data_wait_ms", "train.mfu"}
    assert line["correct"] is True


@pytest.fixture(scope="module")
def spans_tool():
    spec = importlib.util.spec_from_file_location("spans_tool", tiny.BENCH_DIR / "spans.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fresh_compile_text_compiles_anew():
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
    text = fresh_compile_text(fresh_probe, x)
    assert spans.REGISTRY.snapshot()["counters"][key] == before + 1
    assert "mamba2.ssd" in "".join(scope_map(text).values())


@pytest.mark.parametrize("traced", [True, False])
def test_tiny_whole_run_reads_the_program_spans(spans_tool, traced, tmp_path):
    cell = load_cell("mamba2_780m.train_b4s2048", tiny.build(tmp_path / "bench"))
    line = spans_tool.window_readings(cell, 2**31 + 17, 1.0, traced, tmp_path / "trace",
                                      PEAKS["TPU v5 lite"], lambda *a: None)
    steps = line["steps"]
    assert steps >= 1
    got = line["readings"]
    # the CPU has no TPU plane: the device readings need a chip
    assert set(got) == {"fix.fetch_ms", "python.gc_ms"}
    assert set(line["metrics"]) == {"train.data_wait_ms", "train.mfu", "train.dispatch_ms"}
    assert 0 < got["fix.fetch_ms"] <= line["metrics"]["train.data_wait_ms"]
    assert line["metrics"]["train.dispatch_ms"] > 0 and got["python.gc_ms"] >= 0
    c = line["counters"]
    assert c["span_count{span=repro.fix.fetch}"] == steps
    assert c["span_count{span=repro.train.step}"] == steps
    assert c["span_count{span=repro.fix.evaluate}"] == steps
    assert not any(k.startswith("jax_compiles") for k in c)  # nothing compiles in the window
    assert (line["median_step_s"]["profiler_on"] is not None) == traced
    assert not (tmp_path / "trace").exists()
