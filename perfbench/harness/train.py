"""The training cell: the ``launch/train.py`` composition, timed.

Set-up builds one object, the compiled step (``parallel.steps.build_train_step``)
with its state made from the seed, and drives it through its first steps
with the window's own call and feed: each batch is a ``fix.local`` shard
recipe of ``data.TokenPipeline.batch_for_step`` over a corpus made from the
seed, and the loss is read each step, as ``train()`` does.  The window runs
the same loop on.  Afterwards the plain reference repeats the first steps.
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from . import traffic as T
from . import weights as W
from .clock import Profiler, now
from .program import fresh_compile_text
from .serve import program_config


def grad_sq_from_state(opt_v: dict, params_abstract) -> dict:
    """Each leaf's squared first gradient, from Adafactor's second moments
    after one step (decay 0 at step 1: ``v = g^2`` or its row and column
    means)."""
    out = {}
    for path, s in W.paths(params_abstract).items():
        node = opt_v
        for part in path.split("/"):
            node = node[part]
        if "vr" in node:
            out[path] = jnp.sum(node["vr"]) * s.shape[-1]
        else:
            out[path] = jnp.sum(node["v"])
    return out


def change_norms(params, seed: int, abstract, rules: dict) -> dict:
    """Each leaf's norm of (``params`` - the parameters the seed made),
    the seed's made again inside the one program."""
    flat = W.paths(abstract)

    @jax.jit
    def norms(params, words):
        got = W.paths(params)
        return {p: jnp.sqrt(jnp.sum(jnp.square(
            got[p].astype(jnp.float32)
            - W.make_leaf(words, p, s.shape, s.dtype, W.rule_for(rules, p),
                          p.startswith("layers/")).astype(jnp.float32))))
            for p, s in flat.items()}

    return {k: float(v) for k, v in norms(params, W.seed_words(seed)).items()}


class Run:
    def __init__(self, cell, seed, seconds, traced, trace_dir, obs, t_start,
                 fault=None, log=print):
        self.cell, self.seed, self.seconds, self.traced = cell, seed, seconds, traced
        self.trace_dir, self.obs, self.t_start, self.fault = trace_dir, obs, t_start, fault
        self.mix, self.cfg, self.log = cell.traffic, cell.config, log

    def setup(self) -> None:
        from repro import fix
        from repro.core import Repository
        from repro.data import TokenPipeline
        from repro.parallel.steps import RunConfig, build_train_step

        mix = self.mix
        self.B, self.S = int(mix["batch"]), int(mix["seq"])
        mc = program_config(self.cfg)
        runcfg = RunConfig(optimizer=mix["optimizer"], remat=mix["remat"])
        step_fn, _sh, _bsh, abstract = build_train_step(mc, runcfg, None)
        self.abstract = abstract["params"]
        params = W.make_params(self.seed, self.abstract, self.cfg["init"])
        opt = jax.jit(lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                           abstract["opt"]))()
        state = {"params": params, "opt": opt}
        self.text = T.corpus(mix, self.seed)
        repo = Repository("bench")
        self.backend = fix.local(repo)
        self.pipe = TokenPipeline(repo, repo.put_blob(self.text), seq_len=self.S,
                                  batch=self.B, vocab=mc.vocab)
        self.step_fn = step_fn
        self.losses, self.grad_sq, self.change = [], None, None
        self.step, self.data_wait = 0, 0.0
        n_check = int(mix["check_steps"])
        grad_sq = jax.jit(lambda v: grad_sq_from_state(v, self.abstract))
        for _ in range(n_check):
            state, loss = self._one(state)
            self.losses.append(loss)
            if self.step == 1:
                self.grad_sq = {k: float(v) for k, v in grad_sq(state["opt"]["v"]).items()}
        self.change = change_norms(state["params"], self.seed, self.abstract,
                                   self.cfg["init"])
        self.state = state

    def _one(self, state):
        """One step as the window runs it: fetch, step, read the loss."""
        t = now()
        with jax.profiler.TraceAnnotation("bench.batch_for_step"):
            batch = self.pipe.batch_for_step(self.backend, self.step)
        self.data_wait += now() - t
        if self.fault == "half_batch":
            batch = {k: v[: self.B // 2] for k, v in batch.items()}
        with jax.profiler.TraceAnnotation("bench.train_step"):
            if self.fault == "state_unchanged":
                kept = jax.tree.map(jnp.copy, state)
                _, metrics = self.step_fn(state, batch)
                state = kept
            else:
                state, metrics = self.step_fn(state, batch)
            loss = float(metrics["loss"])
        self.step += 1
        return state, loss

    def window(self, compile_clock) -> None:
        """The loop over the window; the program's counters' window deltas
        into ``obs.counters``, read outside the timed loop."""
        from repro.obs.spans import REGISTRY, counter_deltas

        obs = self.obs
        compiles0 = compile_clock.compiles
        trace_s = float(self.mix.get("trace_seconds", 6.0))
        self.data_wait = 0.0
        state, steps, nonfinite = self.state, 0, 0
        self.state = None
        before = REGISTRY.snapshot()["counters"]
        t0 = now()
        profiler = Profiler(self.trace_dir, obs, t0) if self.traced else None
        ends = [0.0]
        while now() - t0 < self.seconds:
            state, loss = self._one(state)
            ends.append(now() - t0)
            steps += 1
            nonfinite += not np.isfinite(loss)
            if profiler and now() - t0 >= trace_s:
                profiler.stop()
        if profiler:
            profiler.stop()
        obs.window_s = now() - t0
        obs.counters = counter_deltas(before, REGISTRY.snapshot()["counters"])
        obs.steps = steps
        obs.tokens_per_step = self.B * self.S
        obs.spans = {"batch_for_step_s": self.data_wait}
        self.nonfinite = nonfinite
        self.compiles_in_window = compile_clock.compiles - compiles0
        took = np.diff(ends)
        slow = np.argsort(took)[::-1][:3]
        self.log(f"steps: median {float(np.median(took))!r} s; slowest "
                 + ", ".join(f"#{i} {float(took[i])!r} s" for i in slow))
        self.state = state

    def compiled_text(self) -> str:
        """The step's program compiled anew for the window's state and a
        batch of its shape, as text: the names of its operations with the
        scopes each was traced under (:func:`harness.program.scope_map`)."""
        return fresh_compile_text(self.step_fn, self.state,
                                  self.pipe.batch_for_step(self.backend, 0))

    def attempted(self) -> int:
        return self.obs.steps

    def failed(self) -> int:
        return self.nonfinite

    def release(self) -> None:
        self.state = self.step_fn = None
        self.backend.close()
        gc.collect()


def _gaps(prog: dict, ref: dict, keys) -> dict:
    """Each leaf's gap between the program's norm and the reference's, over
    the larger of the reference leaf's norm and the median leaf's."""
    med = float(np.median([ref[k] for k in keys]))
    return {k: abs(float(prog[k]) - float(ref[k])) / max(float(ref[k]), med) for k in keys}


def compare(prog: dict, ref: dict, floor_share: float = 1e-3) -> dict:
    """The three numbers: the worst relative loss gap over the steps and,
    by the worst leaf, the gap of the first gradient's norms and of the
    parameters' change.  Leaves whose reference gradient is under
    ``floor_share`` of the median leaf's move by round-off alone and are
    left out of the change."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    g_ref = {k: float(v) for k, v in ref["grad_norms"].items()}
    g_prog = {k: float(np.sqrt(max(v, 0.0))) for k, v in prog["grad_sq"].items()}
    grad = _gaps(g_prog, g_ref, list(g_ref))
    g_med = float(np.median(list(g_ref.values())))
    moved = [k for k in g_ref if g_ref[k] >= floor_share * g_med]
    change = _gaps(prog["change"], ref["change"], moved)
    worst = lambda d: max(d, key=d.get)  # noqa: E731
    return {"loss_gap": max(gaps), "grad_norm_gap": max(grad.values()),
            "change_norm_gap": max(change.values()),
            "left_out": sorted(set(g_ref) - set(moved)),
            "worst_grad_leaf": worst(grad), "worst_change_leaf": worst(change)}


def program_readings(run: Run) -> dict:
    return {"losses": run.losses, "grad_sq": run.grad_sq, "change": run.change}


def reference_readings(run: Run, ref, low: bool = False) -> dict:
    """The plain reference's readings over the program's first batches, from
    the parameters the seed makes, in the form ``program_readings`` has."""
    batches = [T.train_batch(run.text, s, run.B, run.S, int(run.cfg["vocab_size"]))
               for s in range(int(run.mix["check_steps"]))]
    params0 = W.make_params(run.seed, run.abstract, run.cfg["init"])
    out = ref.train_steps(run.cfg, params0, batches, run.mix["adafactor"], low)
    out["change"] = change_norms(out.pop("params"), run.seed, run.abstract,
                                 run.cfg["init"])
    out["grad_sq"] = {k: v * v for k, v in out["grad_norms"].items()}
    return out


def check(run: Run, ref, limits: dict) -> dict:
    """The numbers the cell's limits file names, each against its limit.
    With the fault ``control`` the reference one precision down stands in
    the program's place."""
    prog = program_readings(run)
    run.release()
    if run.fault == "control":
        prog = reference_readings(run, ref, low=True)
    got = compare(prog, reference_readings(run, ref))
    numbers = {k: (got[k], float(lim), "<=") for k, lim in limits.items()}
    return {"numbers": numbers, "correct": all(v <= lim for v, lim, _ in numbers.values()),
            "program_losses": prog["losses"], "left_out": got["left_out"],
            "worst_grad_leaf": got["worst_grad_leaf"],
            "worst_change_leaf": got["worst_change_leaf"]}
