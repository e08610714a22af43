"""Spans and counters on the JAX path (``repro.obs.spans``): the counters of
a span and of nested spans, the spans in a profiler trace with the ``job``
shared across the ``fix-local`` thread, collections and compiles counted by
the process-wide hooks, and the spans the training path opens."""
import gc
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fix as fix
from repro.configs import get_config
from repro.core import Repository
from repro.core.stdlib import add
from repro.data import TokenPipeline, corpus_handle
from repro.obs import spans
from repro.obs.spans import REGISTRY, Span, Spanned, counter_deltas
from repro.parallel.steps import RunConfig, build_train_step


def counters() -> dict:
    return REGISTRY.snapshot()["counters"]


def moved(before: dict) -> dict:
    return counter_deltas(before, counters())


def host_events(trace_dir) -> list:
    """[(line, name, start_ns, end_ns, stats)] of every ``repro.*`` event
    on the host plane of the trace under ``trace_dir``; a line (one per
    thread) is its index in the plane."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [(i, e.name, e.start_ns, e.start_ns + e.duration_ns,
                         {k: v for k, v in e.stats})
                        for e in line.events if e.name.startswith("repro.")]
    return out


def test_span_counts_calls_and_nanoseconds_and_nests():
    outer, inner = Span("test.outer"), Span("test.inner")
    before = counters()
    with outer(step=1):
        for _ in range(3):
            with inner():
                time.sleep(0.002)
    d = moved(before)
    assert d["span_count{span=test.outer}"] == 1
    assert d["span_count{span=test.inner}"] == 3
    assert d["span_ns{span=test.inner}"] >= 3 * 2_000_000
    assert d["span_ns{span=test.outer}"] >= d["span_ns{span=test.inner}"]


def test_span_counts_a_call_that_raises():
    site = Span("test.raises")
    before = counters()
    with pytest.raises(ValueError):
        with site():
            raise ValueError("inside")
    assert moved(before)["span_count{span=test.raises}"] == 1


def test_spanned_counts_calls_and_forwards_attributes():
    @jax.jit
    def train_step(x):
        return x * 2

    wrapped = Spanned(Span("test.step"), train_step)
    before = counters()
    assert float(wrapped(jnp.float32(3))) == 6.0
    assert moved(before)["span_count{span=test.step}"] == 1
    assert wrapped.__name__ == "train_step"
    assert "mul" in wrapped.lower(jnp.float32(1)).as_text()


def test_fetch_and_evaluate_spans_share_the_job_across_threads(tmp_path):
    with fix.local() as be:
        be.run(add(1, 2))  # the worker thread and the codelet are warm
        before = counters()
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert be.run(add(40, 2)) == 42
        finally:
            jax.profiler.stop_trace()
    d = moved(before)
    assert d["span_count{span=repro.fix.fetch}"] == 1
    assert d["span_count{span=repro.fix.evaluate}"] == 1
    events = {name: (line, s, e, stats) for line, name, s, e, stats in host_events(tmp_path)}
    f_line, f_start, f_end, f_stats = events["repro.fix.fetch"]
    e_line, e_start, e_end, e_stats = events["repro.fix.evaluate"]
    assert f_stats["job"] == e_stats["job"]
    assert f_line != e_line  # the evaluation ran on the fix-local thread
    assert f_start <= e_start and e_end <= f_end


def test_no_trace_no_annotation():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with Span("test.quiet")(step=1) as open_span:
        assert open_span.annotation is None
        open_span.annotate(job=3)  # a no-op without a trace


def test_gc_span_and_counter_under_a_forced_collection():
    spans.install()
    spans.install()  # idempotent: one hook
    assert gc.callbacks.count(spans._on_gc) == 1
    before = counters()
    gc.collect()
    d = moved(before)
    assert d["gc_collections{generation=2}"] >= 1
    assert d["span_count{span=repro.gc}"] >= 1
    assert d["span_ns{span=repro.gc}"] > 0


def test_jax_compiles_counts_a_new_program_once():
    spans.install()

    @jax.jit
    def obs_spans_probe(x):
        return x + 1

    key = "jax_compiles{program=jit(obs_spans_probe)}"
    before = counters()
    obs_spans_probe(jnp.ones(3))
    assert moved(before)[key] == 1
    assert moved(before)["jax_compile_ns{program=jit(obs_spans_probe)}"] > 0
    again = counters()
    obs_spans_probe(jnp.ones(3))
    assert moved(again)[key] == 0


def test_training_path_opens_its_spans_and_names_the_ssd():
    cfg = get_config("mamba2_780m", smoke=True)
    step, _sh, _bsh, abstract = build_train_step(cfg, RunConfig(optimizer="adafactor"), None)
    assert isinstance(step, Spanned) and step.__name__ == "train_step"
    repo = Repository("t")
    pipe = TokenPipeline(repo, corpus_handle(repo, 1 << 14), seq_len=16, batch=2,
                         vocab=cfg.vocab)
    data = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32) for k in ("tokens", "labels")}
    hlo = step.lower(abstract, data).compile().as_text()
    assert "mamba2.ssd" in hlo  # the scope reaches the compiled ops' metadata
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abstract)
    with fix.local(repo) as be:
        before = counters()
        batch = pipe.batch_for_step(be, 0)
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
    d = moved(before)
    assert d["span_count{span=repro.data.batch}"] == 1
    assert d["span_count{span=repro.fix.fetch}"] == 1
    assert d["span_count{span=repro.train.step}"] == 1


def test_lowering_a_mamba2_step_counts_its_ssd_path():
    """On the CPU the SSD lowers to the jnp path, once per train step."""
    cfg = get_config("mamba2_780m", smoke=True)
    step, _sh, _bsh, abstract = build_train_step(cfg, RunConfig(optimizer="adafactor"), None)
    data = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32) for k in ("tokens", "labels")}
    before = counters()
    step.lower(abstract, data)
    d = moved(before)
    assert d["ssd_lowerings{path=jnp}"] == 1
    assert d.get("ssd_lowerings{path=fused}", 0) == 0


def test_train_log_leaves_the_first_step_out(capsys):
    from repro.launch.train import train

    cfg = get_config("mamba2_780m", smoke=True)
    train(cfg, RunConfig(optimizer="adafactor"), steps=3, batch=2, seq=16, log_every=1)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("step")]
    assert len(lines) == 3
    assert "first step" in lines[0] and "s/step" not in lines[0]
    assert all("s/step" in ln for ln in lines[1:])
    assert all("train_step compiles" in ln for ln in lines)
    assert all("ssd jnp=" in ln for ln in lines)  # the path the step lowered
