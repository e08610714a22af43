"""Training driver: the Fix-orchestrated loop.

Data shards are Application Thunks over a content-addressed corpus
(recompute-on-loss for free); the jitted train_step is the codelet; every
checkpoint is a content-addressed Tree whose unchanged leaves dedup.  On a
pod this same driver runs once per host with the production mesh; here it
runs real steps on CPU for the smoke/e2e examples.

Usage (the published config unless ``--smoke``):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_8b --smoke \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..checkpoint import dedup_stats, load_step, save_step
from ..configs import ARCHS, get_config
from ..core import Repository
from .. import fix
from ..data import TokenPipeline, corpus_handle
from ..models import init_params
from ..models.base import tree_map_specs
from ..obs.spans import REGISTRY
from ..optim import adafactor as _adafactor
from ..optim import adamw as _adamw
from ..parallel.steps import RunConfig, build_train_step
from .compile_cache import enable_compile_cache


def init_state(cfg, runcfg: RunConfig, seed: int = 0):
    from ..models import ops_for

    specs = ops_for(cfg).specs(cfg)
    params = init_params(specs, cfg, seed)
    if runcfg.optimizer == "adafactor":
        o_specs = _adafactor.state_specs(specs, runcfg.adafactor)
    else:
        o_specs = _adamw.state_specs(specs, runcfg.optim)
    opt = init_params(o_specs, cfg, seed)
    return {"params": params, "opt": opt}


def ssd_paths() -> str:
    """The SSD paths lowered so far in this process (``fused=1``), from
    ``ssd_lowerings{path=...}``; empty where no SSD was lowered."""
    head = "ssd_lowerings{path="
    return " ".join(f"{k[len(head):-1]}={v}"
                    for k, v in REGISTRY.snapshot()["counters"].items() if k.startswith(head))


def train(cfg, runcfg: RunConfig, steps: int, batch: int, seq: int,
          mesh=None, checkpoint_every: int = 0, resume=None,
          repo: Repository | None = None, log_every: int = 10,
          seed: int = 0):
    """Returns (final state, losses, checkpoint roots, repo)."""
    repo = repo or Repository("train")
    backend = fix.local(repo)  # shard recipes run through the one protocol
    corpus = corpus_handle(repo, n_bytes=max(batch * (seq + 1) * 64, 1 << 20),
                           seed=seed)
    pipe = TokenPipeline(repo, corpus, seq_len=seq, batch=batch,
                        vocab=cfg.vocab)

    step_fn, state_sh, _bs, _abs = build_train_step(cfg, runcfg, mesh)
    if resume is not None:
        meta, state = load_step(repo, resume)
        start = meta["step"]
        state = jax.tree.map(jax.numpy.asarray, state)
    else:
        state = init_state(cfg, runcfg, seed)
        start = 0

    losses, roots = [], []
    compiles = REGISTRY.counter("jax_compiles", program="jit(train_step)")
    t0 = time.perf_counter()
    for step in range(start, start + steps):
        batch_np = pipe.batch_for_step(backend, step)  # Fix recipe -> bytes
        state, metrics = step_fn(state, batch_np)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step == start:  # the first step compiles: time the rest apart
            first_s, t0 = time.perf_counter() - t0, time.perf_counter()
        if log_every and (step % log_every == 0 or step == start + steps - 1):
            pace = (f"first step {first_s:.2f}s" if step == start else
                    f"{(time.perf_counter() - t0) / (step - start):.2f}s/step")
            ssd = ssd_paths()
            print(f"step {step:5d}  loss {loss:8.4f}  "
                  f"gnorm {float(metrics['grad_norm']):8.3f}  {pace}  "
                  f"train_step compiles {compiles.value}"
                  + (f"  ssd {ssd}" if ssd else ""), flush=True)
        if checkpoint_every and (step + 1) % checkpoint_every == 0:
            roots.append(save_step(repo, state, step + 1,
                                   {"arch": cfg.name}))
    backend.close()
    return state, losses, roots, repo


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_8b", choices=ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--optimizer", default="adamw")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    runcfg = RunConfig(microbatches=args.microbatches, remat="none",
                       optimizer=args.optimizer)
    state, losses, roots, repo = train(
        cfg, runcfg, args.steps, args.batch, args.seq,
        checkpoint_every=args.checkpoint_every)
    print(f"\nfinal loss: {losses[-1]:.4f} (from {losses[0]:.4f})")
    if roots:
        print("checkpoints:", [r.raw[:6].hex() for r in roots],
              dedup_stats(repo, roots))


if __name__ == "__main__":
    main()
