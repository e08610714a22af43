#!/usr/bin/env python3
"""Bring-up smoke test: the repo's JAX path on a TPU, at full width.

    python chip_smoke.py             # one chip: device, kernels, serve, train
    python chip_smoke.py --chips 4   # only the sharded train step on a 2x2
                                     # data x model mesh, against one chip

Everything runs in this one process, which holds the chip; nothing is
forked or spawned.  Each phase prints one line (what it checked, compile
seconds, run seconds, peak device bytes) before the next starts, and any
failed check exits non-zero.  The last line of a run that passed is the
device as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the script exits non-zero and prints no result.  Weights and
data are random, made from fixed seeds; timings are smoke timings of a cold
or warm compile cache, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

# (optimizer, batch, seq) in order of preference; the first whose compiled
# train step fits the free device memory is run.  Mamba2 trains at 2k
# context; AdamW's two float32 moments of the 780M model do not fit one
# 16 GB v5e beside its activations at batch 4 (17.2 GiB by the compiler's
# memory analysis), Adafactor's factored moment does (10.8 GiB).
TRAIN_CANDIDATES = (("adamw", 4, 2048), ("adafactor", 4, 2048))
TRAIN_STEPS = 4
SERVE = dict(requests=4, prompt_len=128, block=16, max_new=16, batch=4)
# decode vs full forward, both bf16: relative L2 error of the logits
DECODE_TOL = 5e-2
KERNEL_TOL = 2e-2        # bf16 inputs: max error relative to max |reference|
MESH_LOSS_TOL = 2e-2     # relative; bf16 matmuls reduce in another order


class CompileClock:
    """Seconds JAX spends lowering and compiling (persistent-cache reads
    included), and persistent-cache hits, from JAX's monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.seconds += duration

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def require(cond, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name: str, clock: CompileClock, fn, *args, **kwargs) -> None:
    """Run one phase, which returns what it checked, and print its line."""
    c0, t0 = clock.seconds, time.perf_counter()
    summary = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    print(f"{name}: {summary}; compile_s={compile_s!r} run_s={wall - compile_s!r} "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


# ------------------------------------------------------------------ kernels
def kernel_cases():
    """name -> (kernel, reference, argument maker): the main path's Pallas
    kernels at qwen3-4b (attention, norm) and mamba2-780m (SSD) widths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.ssd_scan import ssd_scan

    def normal(i, shape, dtype=jnp.bfloat16, scale=1.0):
        x = jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
        return (x * scale).astype(dtype)

    def flash_args():
        return [normal(i, (1, 4096, 32, 128)) for i in range(3)]

    def decode_args():
        return [normal(0, (4, 1, 32, 128)), normal(1, (4, 4096, 32, 128)),
                normal(2, (4, 4096, 32, 128)), jnp.int32(3001)]

    def rmsnorm_args():
        return [normal(0, (4096, 2560)), 1.0 + normal(1, (2560,), jnp.float32, 0.1)]

    def ssd_args():
        S, H, P, N = 2048, 48, 64, 128
        return [normal(0, (1, S, H, P)),
                jax.nn.softplus(normal(1, (1, S, H), jnp.float32)),
                -jnp.exp(normal(2, (H,), jnp.float32, 0.3)),
                normal(3, (1, S, N), scale=0.5), normal(4, (1, S, N), scale=0.5)]

    return {
        "flash_attention": (functools.partial(flash_attention, causal=True),
                            functools.partial(ref.flash_attention_ref, causal=True),
                            flash_args),
        "decode_attention": (decode_attention, ref.decode_attention_ref,
                             decode_args),
        "rmsnorm": (rmsnorm, ref.rmsnorm_ref, rmsnorm_args),
        "ssd_scan": (functools.partial(ssd_scan, chunk=256), ref.ssd_scan_ref,
                     ssd_args),
    }


def kernels_phase(cases, prefill_cfg) -> str:
    """Each kernel compiled (not interpreted) and compared with its
    reference; then ``ops.prefill`` of ``prefill_cfg`` compiled for a 4k
    prompt must contain the Mosaic kernel: the model path reaches it."""
    import jax
    import jax.numpy as jnp

    from repro.models import abstract_params, ops_for, serving_specs
    from repro.parallel.sharding import Sharder

    errs = {}
    for name, (kernel, reference, make_args) in cases.items():
        args = make_args()
        out = jax.block_until_ready(jax.jit(kernel).lower(*args).compile()(*args))
        want = jax.jit(reference)(*args)
        outs = out if isinstance(out, tuple) else (out,)
        wants = want if isinstance(want, tuple) else (want,)
        errs[name] = max(rel_err(o, w) for o, w in zip(outs, wants))
        require(errs[name] <= KERNEL_TOL,
                f"{name} differs from its reference by {errs[name]!r}")

    ops = ops_for(prefill_cfg)
    params = abstract_params(serving_specs(ops.specs(prefill_cfg), prefill_cfg),
                             prefill_cfg)
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    text = jax.jit(lambda p, t: ops.prefill(p, {"tokens": t}, prefill_cfg,
                                            Sharder(None))
                   ).lower(params, tokens).compile().as_text()
    require("tpu_custom_call" in text,
            f"{prefill_cfg.name} prefill at S=4096 never reaches the flash kernel")
    return ("kernels match kernels/ref.py (max rel err " + ", ".join(
        f"{k}={v!r}" for k, v in errs.items()) + f"; tol {KERNEL_TOL}); "
        f"{prefill_cfg.name} ops.prefill at S=4096 compiles with tpu_custom_call")


# -------------------------------------------------------------------- serve
def serve_phase(cfg, requests: int, prompt_len: int, block: int, max_new: int,
                batch: int) -> str:
    """The launch/serve.py path: bf16 weights, caches sized for prompt plus
    budget, a shared first block through the prefix cache."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import (build_model_fns, greedy_stream,
                                    make_requests, serve, serving_params)
    from repro.models import ops_for
    from repro.parallel.sharding import Sharder

    params = serving_params(cfg)
    prefill_fn, decode_fn = build_model_fns(cfg, params, prompt_len + max_new)
    streams, hits = {}, {}
    for capacity in (64, 0):  # capacity 0: every prefix lookup misses
        reqs = make_requests(cfg.vocab, requests, prompt_len, max_new, block)
        engine, _secs = serve(prefill_fn, decode_fn, reqs, batch, block, capacity)
        require(all(r.done and len(r.out_tokens) == max_new for r in reqs),
                f"not every request was answered with {max_new} tokens")
        streams[capacity] = [r.out_tokens for r in reqs]
        hits[capacity] = engine.cache.hits
    require(hits[64] > 0, "the shared prefix block never hit the cache")
    require(streams[64] == streams[0], "cached streams differ from uncached")

    # decode logits against one full forward over the same tokens
    tokens, logits = greedy_stream(prefill_fn, decode_fn, reqs[0].prompt,
                                   max_new, block)
    ops = ops_for(cfg)
    fwd = jax.jit(lambda p, t: ops.forward(p, {"tokens": t}, cfg, Sharder(None)))
    ref = np.asarray(fwd(params, jnp.asarray(tokens)[None])[0, prompt_len - 1:],
                     np.float32)
    err = float(np.linalg.norm(logits - ref) / np.linalg.norm(ref))
    require(err <= DECODE_TOL, f"decode logits differ from the forward by {err!r}")
    # greedy top-1 must agree wherever the reference's top two are further
    # apart than the two paths differ on that row
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 2 * np.abs(logits - ref).max(-1)
    agree = logits.argmax(-1) == ref.argmax(-1)
    require(agree[decided].all(), "greedy top-1 differs from the forward's")
    summary = (f"{cfg.name} served {requests} requests x {max_new} new tokens "
               f"({prompt_len}-token prompts, one shared {block}-token block, "
               f"{hits[64]} block hits), cached == uncached streams; decode vs "
               f"full forward over {len(tokens)} tokens: rel L2 err {err!r} "
               f"(tol {DECODE_TOL}), top-1 agrees at {int(agree.sum())}/"
               f"{len(agree)} positions ({int(decided.sum())} decided)")
    return summary


# -------------------------------------------------------------------- train
def choose_train_run(cfg, candidates):
    """The first (optimizer, batch, seq) whose compiled step fits 90% of
    the free device memory, by the compiler's memory analysis."""
    import jax
    import jax.numpy as jnp

    from repro.parallel.steps import RunConfig, build_train_step

    stats = jax.devices()[0].memory_stats() or {}
    free = stats.get("bytes_limit", math.inf) - stats.get("bytes_in_use", 0)
    for optimizer, batch, seq in candidates:
        runcfg = RunConfig(optimizer=optimizer)
        step, _sh, _bsh, abstract = build_train_step(cfg, runcfg, None)
        data = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32)
                for k in ("tokens", "labels")}
        m = step.lower(abstract, data).compile().memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        if need <= 0.9 * free:
            return runcfg, batch, seq, need, free
    raise RuntimeError(f"no train candidate fits {free} free bytes")


def train_phase(cfg, candidates, steps: int) -> str:
    """launch/train.train for ``steps`` steps, a checkpoint saved as a
    repository tree, then load_step and one more step."""
    from repro.launch.train import train

    runcfg, batch, seq, need, free = choose_train_run(cfg, candidates)
    state, losses, roots, repo = train(cfg, runcfg, steps, batch, seq,
                                       checkpoint_every=steps, log_every=0)
    del state  # the resumed state is loaded from the checkpoint alone
    require(len(roots) == 1, "no checkpoint was saved")
    _state, resumed, _, _ = train(cfg, runcfg, 1, batch, seq,
                                  resume=roots[0], repo=repo, log_every=0)
    all_losses = losses + resumed
    require(all(math.isfinite(x) for x in all_losses), f"losses {all_losses}")
    return (f"{cfg.name} trained {steps} steps + 1 after load_step "
            f"(optimizer {runcfg.optimizer}, batch {batch}, seq {seq}: "
            f"compiled step needs {need} of {free} free bytes); "
            f"losses {all_losses}")


def mesh_phase(cfg, candidates, steps: int) -> str:
    """The sharded train step on a 2x2 data x model mesh against the same
    steps on one chip; parameters must be spread over all four devices."""
    import jax

    from repro.launch.train import train

    runcfg, batch, seq, _need, _free = choose_train_run(cfg, candidates)
    state, one_chip, _, _ = train(cfg, runcfg, steps, batch, seq, log_every=0)
    del state
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:4])
    state, sharded, _, _ = train(cfg, runcfg, steps, batch, seq, mesh=mesh,
                                 log_every=0)
    jax.block_until_ready(state)
    held = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(state["params"]):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    total = sum(x.nbytes for x in jax.tree.leaves(state["params"]))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in mesh.devices.flat]
    require(min(held.values()) >= total / 8,
            f"parameter bytes not spread over the mesh: {list(held.values())}")
    gaps = [abs(a - b) / abs(a) for a, b in zip(one_chip, sharded)]
    require(max(gaps) <= MESH_LOSS_TOL,
            f"sharded losses {sharded} differ from one chip's {one_chip}")
    return (f"{cfg.name} {steps} steps ({runcfg.optimizer}, batch {batch}, "
            f"seq {seq}) on a 2x2 data x model mesh vs one chip: losses "
            f"{sharded} vs {one_chip} (max rel gap {max(gaps)!r}, tol "
            f"{MESH_LOSS_TOL}); parameter bytes per device "
            f"{list(held.values())} of {total}; bytes_in_use per device "
            f"{in_use}")


# --------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on a 2x2 mesh "
                         "and its one-chip comparison")
    args = ap.parse_args()

    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {len(devices)} "
              f"{dev.platform} device(s) ({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"device: {len(devices)} x {dev.platform} {dev.device_kind}; "
          f"compile cache {cache_dir}; compile_s=0.0 "
          f"run_s={time.perf_counter() - t0!r} peak_bytes_in_use={peak_bytes()}",
          flush=True)

    mamba = get_config("mamba2_780m")
    if args.chips == 4:
        run_phase("mesh", clock, mesh_phase, mamba, TRAIN_CANDIDATES, TRAIN_STEPS)
    else:
        qwen = get_config("qwen3_4b")
        run_phase("kernels", clock, kernels_phase, kernel_cases(), qwen)
        run_phase("serve", clock, serve_phase, qwen, **SERVE)
        run_phase("train", clock, train_phase, mamba, TRAIN_CANDIDATES,
                  TRAIN_STEPS)
    print(f"compile total: {clock.seconds!r} s, {clock.cache_hits} persistent "
          f"cache hits", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
