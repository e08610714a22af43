"""Serving cells: an open loop over the program's ``ServeEngine``.

Set-up makes the weights from the seed, builds the model fns with
``launch.serve.build_model_fns``, and warms every shape the cell's traffic
uses: the first-block prefill, the system prompt's replay into the prefix
cache, and one decode call at each group size from 1 to the slot count.
The window submits each request at its due time and stamps every output
token when ``ServeEngine.step`` returns.  Afterwards a sample of the
served requests is checked against the plain reference.
"""
from __future__ import annotations

import gc
import sys
import time
from collections import deque

import jax
import numpy as np

from . import traffic as T
from . import weights as W
from .clock import Profiler, now
from .observed import Call, Observed, Served


def program_config(cfg: dict):
    """The configuration file's keys as the program's ``ModelConfig``."""
    from repro.models import ModelConfig

    kw = {field: cfg[key] for field, key in cfg["program_keys"].items()}
    kw.update(cfg.get("program_fixed", {}))
    return ModelConfig(**kw)


class SeededWeights:
    """The reference's view of the weights: made again from the seed, one
    layer at a time, in float32 holding the values the program holds."""

    def __init__(self, seed: int, abstract, rules: dict, n_layers: int):
        self.seed, self.abstract, self.rules, self.n_layers = seed, abstract, rules, n_layers

    def layer(self, i: int) -> dict:
        return W.make_layer(self.seed, self.abstract, self.rules, i)

    def globals(self) -> dict:
        return W.make_globals(self.seed, self.abstract, self.rules)


class ModelFns:
    """``prefill_fn`` / ``decode_fn`` with host timing and spans, for traced
    runs.  The prefill wrapper waits for its output, so its host time is
    work, not enqueue."""

    def __init__(self, prefill_fn, decode_fn, t0: float):
        self._prefill, self._decode = prefill_fn, decode_fn
        self.t0 = t0
        self.calls: list = []

    def prefill(self, tokens, state=None):
        start = 0 if state is None else int(state["pos"])
        t = now()
        with jax.profiler.TraceAnnotation("bench.prefill_fn"):
            out = jax.block_until_ready(self._prefill(tokens, state))
        self.calls.append(Call("prefill" if state is None else "replay",
                               t - self.t0, now() - self.t0, len(tokens), [start]))
        return out

    def decode(self, states, tokens):
        positions = [int(s["pos"]) for s in states]
        t = now()
        with jax.profiler.TraceAnnotation("bench.decode_fn"):
            logits, out = self._decode(states, tokens)
        self.calls.append(Call("decode", t - self.t0, now() - self.t0,
                               len(states), positions))
        return logits, out


def _live(engine) -> bool:
    return bool(engine.pending()) or any(r is not None for r in engine.active)


def _stamp(track: dict, t: float) -> None:
    for rid, (req, s) in list(track.items()):
        s.token_times.extend([t] * (len(req.out_tokens) - len(s.token_times)))
        if req.done:
            del track[rid]


class ServeRun:
    def __init__(self, cell, seed: int, seconds: float, traced: bool,
                 trace_dir, obs: Observed, t_start: float, fault=None,
                 log=print):
        self.cell, self.seed, self.seconds, self.traced = cell, seed, seconds, traced
        self.trace_dir, self.obs, self.t_start, self.fault = trace_dir, obs, t_start, fault
        self.mix, self.cfg = cell.traffic, cell.config
        self.log = log

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.launch.serve import build_model_fns
        from repro.models import abstract_params, ops_for, serving_specs
        from repro.serving import PrefixCache, ServeEngine

        mc = program_config(self.cfg)
        self.abstract = abstract_params(serving_specs(ops_for(mc).specs(mc), mc), mc)
        params = W.make_params(self.seed, self.abstract, self.cfg["init"])
        prefill_fn, decode_fn = build_model_fns(mc, params, int(self.mix["max_seq"]))
        del params  # the model fns hold the one reference
        self.fns = ModelFns(prefill_fn, decode_fn, self.t_start)
        wrap = self.traced
        engine = ServeEngine(self.fns.prefill if wrap else prefill_fn,
                             self.fns.decode if wrap else decode_fn,
                             batch=int(self.mix["slots"]), eos=-1,
                             prefix_cache=PrefixCache(int(self.mix["prefix_cache_entries"])),
                             block=int(self.mix["block"]))
        self.engine = engine
        vocab = int(self.cfg["vocab_size"])
        self.arrivals = T.serve_arrivals(self.mix, self.seed, self.seconds, vocab)
        self.track: dict = {}
        self.requests: dict = {}
        self.refused = 0
        rng = np.random.default_rng([self.seed, 3])
        # the first block's prefill and a replayed token, or the whole
        # shared prefix, through the engine and into the prefix cache
        prefix = T.shared_prefix(self.mix, self.seed, vocab)
        block = int(self.mix["block"])
        warm = prefix if len(prefix) else rng.integers(1, vocab, block + 1).astype(np.int32)
        self._run_one(warm, max_new=1)
        # one decode call at each group size the slots can form
        state = (self.fns.prefill if wrap else prefill_fn)(
            rng.integers(1, vocab, block).astype(np.int32))
        for g in range(1, int(self.mix["slots"]) + 1):
            tokens = rng.integers(1, vocab, (g, 1)).astype(np.int32)
            jax.block_until_ready(engine.decode_fn([state] * g, tokens))
        del state
        if self.mix["arrival"]["kind"] == "backlog":
            # the first wave is admitted (prefilled) during set-up
            first = self.arrivals[: int(self.mix["slots"])]
            self.arrivals = self.arrivals[len(first):]
            for a in first:
                self._submit(a, -1.0, carried=True)
            engine.step()
            _stamp(self.track, now() - self.t_start - 0.0)
        self.fns.calls.clear()
        gc.collect()

    def _run_one(self, prompt, max_new: int) -> None:
        from repro.serving import Request

        req = Request(rid=-1, prompt=prompt, max_new=max_new)
        self.engine.submit(req)
        while not req.done:
            self.engine.step()
        self.engine.finished.clear()

    def _submit(self, a, t: float, carried: bool = False) -> None:
        from repro.serving import Request, RequestError

        req = Request(rid=a.rid, prompt=a.prompt, max_new=a.max_new)
        s = Served(a.rid, a.due, len(a.prompt), a.max_new, submitted=t)
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                self.engine.submit(req)
        except RequestError as e:
            self.log(f"request {a.rid} refused: {e}")
            self.refused += 1
            return
        self.track[a.rid] = (req, s)
        self.requests[a.rid] = req
        (self.obs.carried if carried else self.obs.served).append(s)

    # ------------------------------------------------------------ window
    def window(self, compile_clock) -> None:
        engine, obs = self.engine, self.obs
        cache = engine.cache
        hits0, misses0 = cache.hits, cache.misses
        compiles0 = compile_clock.compiles
        pending = deque(self.arrivals)
        trace_s = float(self.mix.get("trace_seconds", 4.0))
        late = []
        t0 = now()
        self.fns.t0 = t0
        # carried requests' set-up stamps move onto the window's clock
        for s in obs.carried:
            s.token_times = [x - (t0 - self.t_start) for x in s.token_times]
        profiler = Profiler(self.trace_dir, obs, t0) if self.traced else None
        while True:
            t = now() - t0
            if profiler and t >= profiler.start + trace_s:
                profiler.stop()
            if t >= self.seconds:
                break
            while pending and pending[0].due <= t:
                a = pending.popleft()
                late.append(t - a.due)
                self._submit(a, t)
            if _live(engine):
                if self.traced:
                    with jax.profiler.TraceAnnotation("bench.step"):
                        engine.step()
                else:
                    engine.step()
                _stamp(self.track, now() - t0)
            else:
                nxt = pending[0].due if pending else self.seconds
                time.sleep(max(0.0, min(nxt, self.seconds) - (now() - t0)))
        if profiler:
            profiler.stop()
        obs.window_s = now() - t0
        obs.counters = {"prefix_block_hits": cache.hits - hits0,
                        "prefix_block_misses": cache.misses - misses0,
                        "engine_steps": engine.steps}
        obs.calls = list(self.fns.calls)
        self.compiles_in_window = compile_clock.compiles - compiles0
        if late:
            self.log(f"generator: {len(late)} submits, late by median "
                     f"{float(np.median(late))!r} s, max {max(late)!r} s")

    # ------------------------------------------------------- correctness
    def sample(self, target_tokens: int) -> list:
        """The served requests with the most tokens first, then others in
        an order drawn from the seed, until ``target_tokens`` are covered."""
        served = [r for r in self.requests.values() if r.out_tokens]
        if not served:
            return []
        served.sort(key=lambda r: (-len(r.out_tokens), r.rid))
        rest = served[1:]
        order = np.random.default_rng([self.seed, 4]).permutation(len(rest))
        out, total = [served[0]], len(served[0].out_tokens)
        for i in order:
            if total >= target_tokens:
                break
            out.append(rest[i])
            total += len(rest[i].out_tokens)
        return [(np.asarray(r.prompt, np.int32), np.asarray(r.out_tokens, np.int32))
                for r in out]

    def compiled_text(self) -> str:
        """No reader of a serving cell reads a scope: nothing to compile."""
        return ""

    def attempted(self) -> int:
        return len(self.obs.served) + len(self.obs.carried) + self.refused

    def failed(self) -> int:
        return self.refused

    def release(self) -> None:
        """Drop every reference to the program's state before the check."""
        self.engine = self.fns = self.track = None
        gc.collect()


def served_inputs(sample: list):
    """Reference inputs: each prompt with its served tokens, the positions
    whose logits predicted each served token, and those tokens."""
    seqs, want, targets = [], [], []
    for prompt, out in sample:
        seqs.append(np.concatenate([prompt, out[:-1]]))
        want.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(out)))
        targets.append(out)
    return seqs, want, targets


def check(run: ServeRun, ref, limits: dict) -> dict:
    """The widest logit gap of a sample of the served tokens against the
    reference.  With the fault ``control`` the float8 pass stands in the
    program's place: its gap is that of the token it puts first at each
    position of the same prompts and tokens."""
    control = run.fault == "control"
    sample = run.sample(int(run.mix["check_tokens"]))
    n_tokens = sum(len(o) for _, o in sample)
    run.release()
    if not sample:
        return {"numbers": {"served_tokens_checked": (0, 1, ">=")}, "correct": False}
    weights = SeededWeights(run.seed, run.abstract, run.cfg["init"],
                            int(run.cfg["num_hidden_layers"]))
    seqs, want, targets = served_inputs(sample)
    gaps, ctrl = ref.served_gaps(run.cfg, weights, seqs, want, targets, control=control)
    gap = max(float(g.max()) for g in (ctrl if control else gaps))
    numbers = {"logit_gap": (gap, float(limits["logit_gap"]), "<=")}
    return {"numbers": numbers, "correct": gap <= float(limits["logit_gap"]),
            "tokens_checked": n_tokens, "requests_checked": len(sample)}


Run = ServeRun
