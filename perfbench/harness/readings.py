"""Readings that set a cell's limits: the program's numbers on many seeds,
the control's (the reference in the program's place at the precision
below the configuration's), and planted faults.  ``control.py`` runs them;
the benchmark's own runs never do.
"""
from __future__ import annotations

import time

import numpy as np

from . import serve as S
from . import train as TR
from .clock import CompileClock
from .observed import Observed


def _run(cell, seed: int, seconds: float, fault=None, log=print):
    obs = Observed(cell=cell.name, config=cell.config, traffic=cell.traffic, peaks={})
    run = cell.driver().Run(cell, seed, seconds, False, None, obs, time.perf_counter(), fault, log)
    run.setup()
    run.window(CompileClock())
    return run


def serve_readings(cell, seed: int, seconds: float, witness: bool = False, log=print) -> dict:
    """The program's widest served-token gap and the float8 control's, on
    the same prompts and tokens.  With ``witness``: the same gap if the
    engine's input had its last prompt token twice, and the gap of
    ``launch.serve.greedy_stream`` (which feeds every token once) over the
    same prompts through the same model fns."""
    from repro.launch.serve import greedy_stream

    run = _run(cell, seed, seconds, log=log)
    sample = run.sample(int(run.mix["check_tokens"]))
    greedy = []
    if witness:
        fns = run.fns
        for prompt, out in sample[:3]:
            toks, logits = greedy_stream(fns._prefill, fns._decode, prompt, len(out),
                                         int(run.mix["block"]))
            gen = np.concatenate([toks[len(prompt):], [int(np.argmax(logits[-1]))]])
            greedy.append((prompt, gen.astype(np.int32)))
    run.release()
    ref = cell.reference()
    weights = S.SeededWeights(seed, run.abstract, run.cfg["init"],
                              int(run.cfg["num_hidden_layers"]))
    seqs, want, targets = S.served_inputs(sample)
    gaps, ctrl = ref.served_gaps(run.cfg, weights, seqs, want, targets, control=True)
    out = {"seed": seed, "program": max(float(g.max()) for g in gaps),
           "control": max(float(g.max()) for g in ctrl),
           "tokens": int(sum(len(t) for t in targets)), "requests": len(sample)}
    if witness:
        seqs2 = [np.concatenate([p, p[-1:], o[:-1]]) for p, o in sample]
        want2 = [np.arange(len(p), len(p) + len(o)) for p, o in sample]
        dup, _ = ref.served_gaps(run.cfg, weights, seqs2, want2, targets)
        out["program_if_last_prompt_token_twice"] = max(float(g.max()) for g in dup)
        gs, gw, gt = S.served_inputs(greedy)
        g_gaps, _ = ref.served_gaps(run.cfg, weights, gs, gw, gt)
        out["greedy_stream"] = max(float(g.max()) for g in g_gaps)
        out["greedy_equals_engine"] = [bool(np.array_equal(a[1], b[1]))
                                       for a, b in zip(greedy, sample)]
    return out


def train_readings(cell, seed: int, seconds: float, control: bool = False,
                   faults: tuple = (), log=print) -> dict:
    """Every number ``train.compare`` gives, against the float32 reference:
    the program's; with ``faults``, the program's with each fault planted;
    with ``control``, the reference one precision down (bfloat16
    parameters, float8 products).  ``raw`` holds each side's losses and
    leaf norms."""
    sides, first = {}, None
    for fault in (None,) + tuple(faults):
        run = _run(cell, seed, seconds, fault=fault, log=log)
        sides[fault or "program"] = TR.program_readings(run)
        run.release()
        first = first or run
    run, ref = first, cell.reference()
    t = time.perf_counter()
    f32 = TR.reference_readings(run, ref)
    out = {"seed": seed, "reference_s": time.perf_counter() - t}
    if control:
        sides["control"] = TR.reference_readings(run, ref, low=True)
    for name, r in sides.items():
        out[name] = TR.compare(r, f32)
    out["raw"] = {name: {"losses": r["losses"],
                         "grad_norms": {k: float(np.sqrt(max(v, 0.0)))
                                        for k, v in r["grad_sq"].items()},
                         "change": r["change"]}
                  for name, r in dict(sides, float32=f32).items()}
    return out
