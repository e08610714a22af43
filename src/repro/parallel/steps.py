"""Step builders: jitted, sharded train_step / serve_step per architecture.

This is where Fix's contract meets XLA: every input/output of a step has a
declared sharding (the step's "minimum repository" and its layout), buffers
are donated (late binding of HBM), and all data movement — FSDP gathers, TP
all-reduces, EP combines, cross-pod grad sync — is emitted by the
partitioner from those declarations rather than issued by model code.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import (
    ModelConfig,
    abstract_params,
    ce_loss,
    input_specs,
    loss_mask,
    ops_for,
    param_shardings,
    serving_specs,
)
from ..models.base import tree_map_specs
from ..obs import spans
from ..optim import AdamWConfig, ef_int8_allreduce, ef_state_specs
from ..optim import adafactor as _adafactor
from ..optim import adamw as _adamw
from .sharding import RULE_VARIANTS, Sharder, make_rules


@dataclass(frozen=True)
class RunConfig:
    microbatches: int = 1
    remat: str = "dots"            # none | dots | full
    remat_group: int = 1            # checkpoint every G layers (sqrt-L saves)
    rules: str = "baseline"        # see sharding.RULE_VARIANTS
    rule_overrides: tuple = ()      # extra (logical, mesh-axis) overrides
    dp_sync: str = "auto"          # auto | int8_pod (EF-compressed DCN sync)
    optimizer: str = "adamw"       # adamw | adafactor (factored 2nd moment)
    mtp_weight: float = 0.0         # DeepSeek MTP auxiliary loss weight
    optim: AdamWConfig = field(default_factory=AdamWConfig)
    adafactor: _adafactor.AdafactorConfig = field(
        default_factory=_adafactor.AdafactorConfig)


def _resolve_remat(name: str):
    if name == "none":
        return None
    if name == "full":
        return "full"
    if name == "dots":
        return jax.checkpoint_policies.nothing_saveable  # per-layer full remat
    if name == "save_dots":
        return jax.checkpoint_policies.checkpoint_dots
    raise ValueError(name)


def make_sharder(mesh: Optional[Mesh], runcfg: RunConfig) -> Sharder:
    rules = dict(RULE_VARIANTS[runcfg.rules])
    rules.update(dict(runcfg.rule_overrides))
    return Sharder(mesh, rules)


# -------------------------------------------------------------- train step
TRAIN_STEP = spans.Span("repro.train.step")


def build_train_step(cfg: ModelConfig, runcfg: RunConfig, mesh: Optional[Mesh]):
    """Returns (step, state_shardings, batch_shardings, abstract_state).

    state = {"params": ..., "opt": {mu, nu, step}[, "ef": ...]}
    step(state, batch) -> (state, metrics)

    ``step`` is the jitted ``train_step`` called inside a
    ``repro.train.step`` span (argument handling, the host-to-device copy
    of a numpy batch, the enqueue); ``lower`` and its other attributes are
    the jitted function's.  The first build in a process installs the
    collection and compile counters (:func:`repro.obs.spans.install`).
    """
    spans.install()
    ops = ops_for(cfg)
    specs = ops.specs(cfg)
    sh = make_sharder(mesh, runcfg)
    remat = _resolve_remat(runcfg.remat)
    n_pods = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pod", 1) if mesh else 1
    use_ef = runcfg.dp_sync == "int8_pod" and n_pods > 1

    def loss_fn(params, mb):
        params_c = jax.tree.map(lambda p: p.astype(cfg.compute_dtype)
                                if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)
        fwd_kwargs = {}
        if cfg.family in ("dense", "vlm", "moe", "mamba2") and runcfg.remat_group > 1:
            fwd_kwargs["remat_group"] = runcfg.remat_group
        out = ops.forward(params_c, mb, cfg, sh, remat_policy=remat, **fwd_kwargs)
        if isinstance(out, tuple):  # MTP: (main logits, mtp logits)
            logits, mtp_logits = out
            loss, metrics = ce_loss(logits, mb["labels"], cfg, loss_mask(cfg, mb["labels"]))
            if runcfg.mtp_weight:
                mtp_loss, _ = ce_loss(mtp_logits, mb["labels"][:, 1:], cfg)
                loss = loss + runcfg.mtp_weight * mtp_loss
                metrics = {**metrics, "mtp_loss": mtp_loss}
            return loss, metrics
        loss, metrics = ce_loss(out, mb["labels"], cfg, loss_mask(cfg, mb["labels"]))
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        nmb = runcfg.microbatches
        if nmb <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return grads, metrics
        split = {k: v.reshape((nmb, v.shape[0] // nmb) + v.shape[1:])
                 for k, v in batch.items()}

        inv = 1.0 / nmb
        scaled_grad_fn = jax.value_and_grad(
            lambda p, b: loss_fn(p, b)[0] * inv)

        def micro(carry, mb):
            gsum, lsum = carry
            loss, g = scaled_grad_fn(params, mb)
            gsum = jax.tree.map(jnp.add, gsum, g)
            # barrier: stops XLA:CPU carrying an f32 twin of the bf16
            # accumulator across the loop (convert-hoisting pass)
            gsum = jax.lax.optimization_barrier(gsum)
            return (gsum, lsum + loss), None

        # accumulate in f32 for f32 masters; bf16 masters (400B+ MoE) keep
        # the accumulator in bf16 — an f32 buffer alone would blow HBM
        acc_dt = jnp.float32 if cfg.param_dtype == jnp.float32 else cfg.param_dtype
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
        (gsum, lsum), _ = jax.lax.scan(micro, (zeros, jnp.zeros((), jnp.float32)), split)
        # grads are pre-scaled by 1/nmb through the cotangent: no full-size
        # divide (which legalizes to an f32 copy of every stacked leaf)
        return gsum, {"loss": lsum}

    if use_ef:
        # pod-local grads via shard_map over "pod" ONLY (data/model stay
        # automatic so the model's sharding constraints keep working), then
        # EF-int8 all-reduce across the DCN link

        def synced_grads(params, batch, ef):
            def per_pod(params, batch, ef):
                grads, metrics = compute_grads(params, batch)
                out = jax.tree.map(
                    lambda g, e: ef_int8_allreduce(g, e, "pod", n_pods), grads, ef)
                grads = jax.tree.map(lambda t: t[0], out,
                                     is_leaf=lambda t: isinstance(t, tuple))
                new_ef = jax.tree.map(lambda t: t[1], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
                metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"), metrics)
                return grads, new_ef, metrics

            rep = jax.tree.map(lambda _: P(), params)
            efspec = jax.tree.map(lambda _: P(), ef)
            bspec = {k: P("pod") for k in batch}
            mspec = P()
            return jax.shard_map(
                per_pod, mesh=mesh,
                in_specs=(rep, bspec, efspec),
                out_specs=(rep, efspec, mspec),
                check_vma=False, axis_names=frozenset({"pod"}),
            )(params, batch, ef)
    else:
        synced_grads = None

    def train_step(state, batch):
        params = state["params"]
        if use_ef:
            grads, new_ef, metrics = synced_grads(params, batch, state["ef"])
        else:
            grads, metrics = compute_grads(params, batch)
            new_ef = None
        if runcfg.optimizer == "adafactor":
            new_params, new_opt, lr = _adafactor.apply_updates(
                params, grads, state["opt"], runcfg.adafactor)
        else:
            new_params, new_opt, lr = _adamw.apply_updates(
                params, grads, state["opt"], runcfg.optim)
        # per-leaf reduce; f32 accumulation INSIDE the contraction (an
        # elementwise astype would materialize an f32 copy of every grad —
        # measured 3.3 GiB per expert stack; a ravel/vdot would all-gather)
        def _ss(g):
            letters = "abcdefghij"[: g.ndim]
            return jnp.einsum(f"{letters},{letters}->", g, g,
                              preferred_element_type=jnp.float32)
        gnorm = jnp.sqrt(sum(_ss(g) for g in jax.tree.leaves(grads)))
        metrics = {**metrics, "lr": lr, "grad_norm": gnorm}
        new_state = {"params": new_params, "opt": new_opt}
        if new_ef is not None:
            new_state["ef"] = new_ef
        return new_state, metrics

    # shardings
    p_shard = param_shardings(specs, sh) if mesh is not None else None
    if runcfg.optimizer == "adafactor":
        o_specs = _adafactor.state_specs(specs, runcfg.adafactor)
    else:
        o_specs = _adamw.state_specs(specs, runcfg.optim)
    state_shardings = {"params": p_shard,
                       "opt": tree_map_specs(lambda _p, s: sh.named(s.axes, s.shape),
                                             o_specs) if mesh is not None else None}
    abstract = {"params": abstract_params(specs, cfg),
                "opt": abstract_params(o_specs, cfg)}
    if use_ef:
        e_specs = ef_state_specs(specs)
        state_shardings["ef"] = tree_map_specs(
            lambda _p, s: sh.named(s.axes, s.shape), e_specs)
        abstract["ef"] = abstract_params(e_specs, cfg)
    if mesh is None:
        state_shardings = None

    def batch_shardings(bspecs: dict) -> dict:
        return {k: sh.named(("batch",) + (None,) * (len(v.shape) - 1), v.shape)
                for k, v in bspecs.items()}

    metrics_sharding = None  # replicated scalars
    jitted = jax.jit(
        train_step,
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )
    return spans.Spanned(TRAIN_STEP, jitted), state_shardings, batch_shardings, abstract


# -------------------------------------------------------------- serve step
DECODE_RULES = dict(heads=None, kv_heads=None, seq=None)


def build_serve_step(cfg: ModelConfig, runcfg: RunConfig, mesh: Optional[Mesh],
                     batch: int, max_seq: int, mode: str = "decode"):
    """decode: (params, cache, tokens) -> (logits, cache), cache donated.
    prefill: (params, batch) -> (logits, cache)."""
    ops = ops_for(cfg)
    specs = ops.specs(cfg)
    sh = make_sharder(mesh, runcfg)
    if mode == "prefill" and mesh is not None:
        model_ext = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        if cfg.n_heads % model_ext:
            # heads don't divide the model axis (arctic: 56 on 16) — fall
            # back to context parallelism: shard the query sequence instead
            sh = sh.with_rules(seq="model", heads=None, kv_heads=None)
    p_shard = param_shardings(specs, sh) if mesh is not None else None
    abstract_p = abstract_params(serving_specs(specs, cfg), cfg)

    if mode == "prefill":
        def prefill(params, b):
            return ops.prefill(params, b, cfg, sh)

        # the emitted cache leaves in decode layout (kv_seq context-parallel)
        # via constraints inside each family's prefill; unsharded it costs
        # ~16x HBM on long-prompt cells
        jitted = jax.jit(prefill, in_shardings=(p_shard, None))
        return jitted, p_shard, abstract_p, None

    dsh = sh.with_rules(**DECODE_RULES)
    c_specs = ops.cache_specs(cfg, batch, max_seq)
    c_shard = tree_map_specs(lambda _p, s: dsh.named(s.axes, s.shape),
                             c_specs) if mesh is not None else None
    abstract_c = abstract_params(c_specs, cfg)

    def decode(params, cache, tokens):
        return ops.decode_step(params, cache, tokens, cfg, dsh)

    tok_shard = dsh.named(("batch", None), (batch, 1)) if mesh is not None else None
    jitted = jax.jit(
        decode,
        in_shardings=(p_shard, c_shard, tok_shard),
        out_shardings=(None, c_shard),
        donate_argnums=(1,),
    )
    return jitted, p_shard, abstract_p, (c_shard, abstract_c)
