"""Serving driver: resumable prefill + group-batched decode on a real model.

Bridges the family ops to the engine's contracts
(:class:`~repro.serving.engine.ServeEngine`):

* ``prefill_fn(tokens, state) -> state`` — ``state=None`` runs the jitted
  full-block prefill; with a cached state the uncovered tail is fed through
  the decode step (resume-from-KV, the per-boundary states land in the
  :class:`~repro.serving.engine.PrefixCache`);
* ``decode_fn(states, tokens[B,1]) -> (logits[B,1,V], states)`` — the
  batched contract from ``parallel.steps``.  Per-row caches are stacked
  along the batch axis (every family lays caches out ``[layers, batch,
  ...]`` with scalar counters) and decoded in one jitted call per group of
  rows whose cache shapes/counters agree — rows admitted together stay in
  lockstep, so continuous batching forms groups naturally; a lone ragged
  row decodes at width 1.

Usage (the published config unless ``--smoke``):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b --requests 8
  PYTHONPATH=src python -m repro.launch.serve --smoke
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, get_config
from ..models import init_params, ops_for, serving_specs
from ..parallel.sharding import Sharder
from ..serving import PrefixCache, Request, ServeEngine
from .compile_cache import enable_compile_cache


def _group_key(cache) -> tuple:
    """Rows are batchable iff their cache pytrees agree on structure, leaf
    shapes, and scalar counters (``pos`` — SSM states are O(1)-shaped, so
    shape alone can't prove rows are at the same position)."""
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    return (treedef,
            tuple((x.shape, int(x) if x.ndim == 0 else None) for x in leaves))


def _stack_rows(caches: list):
    """Concatenate per-row (batch-1) caches along the batch axis."""
    return jax.tree.map(
        lambda *xs: xs[0] if xs[0].ndim == 0 else jnp.concatenate(xs, axis=1),
        *caches)


def _split_rows(cache, n: int) -> list:
    return [jax.tree.map(
        lambda x, i=i: x if x.ndim == 0 else x[:, i: i + 1], cache)
        for i in range(n)]


def serving_params(cfg, seed: int = 0):
    """Serving weights, held in the compute dtype and made on the device by
    one program (no float32 copy of the whole model ever exists)."""
    specs = serving_specs(ops_for(cfg).specs(cfg), cfg)
    return jax.jit(lambda: init_params(specs, cfg, seed))()


def build_model_fns(cfg, params, max_seq: int):
    """(prefill_fn, decode_fn) in the engine contracts, over family ops.

    Prefill hands decode a cache sized by the family's ``cache_specs`` for
    ``max_seq`` tokens (prompt plus token budget), so every decoded token
    gets a slot of its own; a row that would run past ``max_seq`` raises
    rather than let the cache write clamp onto its last slot.
    """
    ops = ops_for(cfg)
    sh = Sharder(None)
    full = ops.cache_specs(cfg, 1, max_seq)

    def grow(x, spec):  # zero-pad each axis up to the decode cache's extent
        pad = [(0, max(n - m, 0)) for m, n in zip(x.shape, spec.shape)]
        return jnp.pad(x, pad) if any(hi for _, hi in pad) else x

    @jax.jit
    def prefill_jit(params, tokens):
        _logits, cache = ops.prefill(params, {"tokens": tokens[None]}, cfg, sh)
        return jax.tree.map(grow, cache, full)

    @jax.jit
    def decode_jit(params, cache, tokens):
        return ops.decode_step(params, cache, tokens, cfg, sh)

    def check_room(pos: int, n: int) -> None:
        if pos + n > max_seq:
            raise ValueError(f"{n} more tokens overflow the {max_seq}-token cache")

    def prefill_fn(tokens, state=None):
        tokens = np.ascontiguousarray(tokens, np.int32)
        if state is None:
            check_room(0, len(tokens))
            return prefill_jit(params, jnp.asarray(tokens))
        # resume from a cached boundary: append the uncovered tail through
        # the decode step (same KV entries as a fresh prefill would write)
        check_room(int(state["pos"]), len(tokens))
        cache = state
        for t in tokens:
            _logits, cache = decode_jit(params, cache,
                                        jnp.asarray([[int(t)]], jnp.int32))
        return cache

    def decode_fn(states, tokens):
        tokens = np.ascontiguousarray(tokens, np.int32)
        groups: dict = {}
        for i, c in enumerate(states):
            groups.setdefault(_group_key(c), []).append(i)
        out_states: list = [None] * len(states)
        logits_rows: list = [None] * len(states)
        for rows in groups.values():
            cache = _stack_rows([states[i] for i in rows])
            check_room(int(cache["pos"]), 1)
            toks = jnp.asarray(tokens[rows], jnp.int32)
            logits, cache = decode_jit(params, cache, toks)
            logits = np.asarray(logits, np.float32)
            for row_pos, i in enumerate(rows):
                logits_rows[i] = logits[row_pos: row_pos + 1]
            for i, st in zip(rows, _split_rows(cache, len(rows))):
                out_states[i] = st
        return np.concatenate(logits_rows, axis=0), out_states

    return prefill_fn, decode_fn


def make_requests(vocab: int, n: int, prompt_len: int, max_new: int,
                  block: int, seed: int = 0) -> list:
    """``n`` prompts that share their first block, then differ."""
    rng = np.random.default_rng(seed)
    shared_prefix = rng.integers(1, vocab, block)
    reqs = []
    for i in range(n):
        tail = rng.integers(1, vocab, prompt_len - block)
        prompt = np.concatenate([shared_prefix, tail]).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=max_new))
    return reqs


def serve(prefill_fn, decode_fn, reqs, batch: int, block: int,
          cache_capacity: int):
    """Run ``reqs`` to completion; returns (engine, host seconds)."""
    engine = ServeEngine(prefill_fn, decode_fn, batch=batch, eos=-1,
                         prefix_cache=PrefixCache(capacity=cache_capacity),
                         block=block)
    for r in reqs:
        engine.submit(r)
    t0 = time.time()
    engine.run()
    return engine, time.time() - t0


def greedy_stream(prefill_fn, decode_fn, prompt, n_new: int, block: int):
    """Greedy-decode ``n_new`` tokens, feeding every token exactly once.

    Returns (tokens, logits): ``tokens`` is the prompt followed by the first
    ``n_new - 1`` generated tokens, and ``logits[i]`` [V] is the prediction
    made at position ``len(prompt) - 1 + i`` — what a full forward over
    ``tokens`` gives at the same positions.
    """
    prompt = np.asarray(prompt, np.int32)
    state = prefill_fn(prompt[:block])
    if len(prompt) - 1 > block:
        state = prefill_fn(prompt[block:-1], state)
    tok, toks, rows = int(prompt[-1]), list(prompt), []
    for _ in range(n_new):
        logits, (state,) = decode_fn([state], np.asarray([[tok]], np.int32))
        rows.append(logits[0, -1])
        tok = int(np.argmax(logits[0, -1]))
        toks.append(tok)
    return np.asarray(toks[:-1], np.int32), np.stack(rows)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_8b", choices=ARCHS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--smoke", action="store_true",
                    help="CI preset: the arch's reduced config, a tiny "
                         "workload, and a cached-vs-uncached stream check")
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.prompt_len, args.max_new, args.batch = 4, 24, 4, 2

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    prefill_fn, decode_fn = build_model_fns(
        cfg, serving_params(cfg), args.prompt_len + args.max_new)

    def run(cache_capacity):
        reqs = make_requests(cfg.vocab, args.requests, args.prompt_len,
                             args.max_new, args.block)
        engine, dt = serve(prefill_fn, decode_fn, reqs, args.batch,
                           args.block, cache_capacity)
        return reqs, engine, dt

    reqs, engine, dt = run(cache_capacity=64)
    total_new = sum(len(r.out_tokens) for r in reqs)
    print(f"served {len(reqs)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s), {engine.steps} engine steps")
    print(f"prefix cache: {engine.cache.hits} block hits / "
          f"{engine.cache.misses} block misses")
    assert all(r.done for r in reqs)
    assert engine.cache.hits > 0, "shared prefix block never hit"

    if args.smoke:
        # cached streams must be bit-identical to the cache-disabled run
        # (capacity 0 => every lookup misses, every insert evicts)
        base, _, _ = run(cache_capacity=0)
        for a, b in zip(reqs, base):
            assert a.out_tokens == b.out_tokens, \
                f"request {a.rid}: cached stream diverged from uncached"
        print(f"smoke: cached == uncached streams for {len(reqs)} requests")


if __name__ == "__main__":
    main()
