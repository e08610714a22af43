"""Zamba2-style hybrid: Mamba2 backbone with a *shared* attention block
applied every ``attn_every`` SSM layers (arXiv:2411.15242).

The shared block is one set of weights applied at G = floor(L/k) points —
a natural fit for Fix's content-addressing story: the block's weights are
one Handle referenced G times (checkpoints dedupe it automatically).

Long-context decode uses a windowed KV policy (``cfg.attn_window``) for the
shared-attention caches, keeping the 500k-token cell sub-quadratic; the SSM
states are O(1) regardless.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import ModelConfig, apply_remat, embed_tokens, ps, rmsnorm, unembed
from .mamba2 import mamba_block, mamba_layer_specs
from .transformer import attn_block, dense_layer_specs, mlp_block


def _group_shape(cfg: ModelConfig) -> tuple[int, int, int]:
    """(n_groups, layers_per_group, tail_layers)."""
    k = cfg.attn_every
    g = cfg.n_layers // k
    return g, k, cfg.n_layers - g * k


def hybrid_specs(cfg: ModelConfig) -> dict:
    Vp, D = cfg.vocab_padded, cfg.d_model
    g, k, tail = _group_shape(cfg)
    shared = {n: s for n, s in dense_layer_specs(cfg, 1).items()}
    specs = {
        "embed": ps((Vp, D), ("p_vocab", "p_embed"), init="embed", scale=0.02),
        # grouped mamba stack: [G, k, ...] — outer scan over groups
        "groups": {
            name: ps((g,) + s.shape, ("p_layers",) + s.axes, s.init, s.scale, s.dtype)
            for name, s in mamba_layer_specs(cfg, k, layer_axis="p_layers").items()
        },
        "shared_attn": shared,  # ONE copy, applied after every group
        "tail": mamba_layer_specs(cfg, tail) if tail else {},
        "final_norm": ps((D,), ("p_none",), init="ones"),
        "unembed": ps((D, Vp), ("p_embed", "p_vocab")),
    }
    return specs


def _shared_block(x, sp, cfg: ModelConfig, sh, positions, kv_cache=None):
    """The shared transformer block (attn + mlp); params have a leading
    length-1 'layer' dim from dense_layer_specs(cfg, 1)."""
    lp = jax.tree.map(lambda a: a[0], sp)
    x, kv = attn_block(x, lp, cfg, sh, positions, kv_cache)
    x = mlp_block(x, lp, cfg, sh)
    return x, kv


def hybrid_forward(params, batch, cfg: ModelConfig, sh, remat_policy=None):
    x = embed_tokens(params["embed"].astype(cfg.compute_dtype), batch["tokens"], sh)
    positions = jnp.arange(x.shape[1])[None, :]
    g, k, tail = _group_shape(cfg)

    def inner(x, lp):
        x, _ = mamba_block(x, lp, cfg, sh)
        return x, None

    def group_body(x, gp):
        x, _ = jax.lax.scan(inner, x, gp)
        x, _ = _shared_block(x, params["shared_attn"], cfg, sh, positions)
        return x, None

    group_body = apply_remat(group_body, remat_policy)
    x, _ = jax.lax.scan(group_body, x, params["groups"])
    if tail:
        x, _ = jax.lax.scan(inner, x, params["tail"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return unembed(x, params["unembed"].astype(x.dtype), sh)


def hybrid_cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    from .mamba2 import mamba_cache_specs

    g, k, tail = _group_shape(cfg)
    W = min(max_seq, cfg.attn_window) if cfg.attn_window else max_seq
    Kv, hd = cfg.n_kv_heads, cfg.head_dim_eff
    ssm = mamba_cache_specs(cfg, batch, max_seq)
    return {
        "ssm_g": ps((g, k) + ssm["ssm"].shape[1:], ("p_layers",) + ssm["ssm"].axes,
                    init="zeros", dtype=jnp.float32),
        "conv_g": ps((g, k) + ssm["conv"].shape[1:], ("p_layers",) + ssm["conv"].axes,
                     init="zeros", dtype=cfg.compute_dtype),
        "ssm_t": ps((max(tail, 1),) + ssm["ssm"].shape[1:], ssm["ssm"].axes,
                    init="zeros", dtype=jnp.float32),
        "conv_t": ps((max(tail, 1),) + ssm["conv"].shape[1:], ssm["conv"].axes,
                     init="zeros", dtype=cfg.compute_dtype),
        # one KV cache per shared-block application (windowed)
        "attn_k": ps((g, batch, W, Kv, hd),
                     ("p_layers", "batch", "kv_seq", "kv_heads", "p_none"),
                     init="zeros", dtype=cfg.compute_dtype),
        "attn_v": ps((g, batch, W, Kv, hd),
                     ("p_layers", "batch", "kv_seq", "kv_heads", "p_none"),
                     init="zeros", dtype=cfg.compute_dtype),
        "pos": ps((), (), init="zeros", dtype=jnp.int32),
    }


def hybrid_decode_step(params, cache, tokens, cfg: ModelConfig, sh):
    x = embed_tokens(params["embed"].astype(cfg.compute_dtype), tokens, sh)
    pos = cache["pos"]
    W = cache["attn_k"].shape[2]
    # windowed KV: wrap the write cursor (mask is exact until the first wrap;
    # see DESIGN.md §Arch-applicability on the rolling-window approximation)
    write_pos = pos % W if cfg.attn_window else pos
    positions = jnp.full((x.shape[0], 1), pos, dtype=jnp.int32)
    g, k, tail = _group_shape(cfg)

    def inner(carry, layer):
        x = carry
        lp, s, c = layer
        x, (s2, c2) = mamba_block(x, lp, cfg, sh, ssm_state=s, conv_state=c)
        return x, (s2, c2)

    def group_body(x, layer):
        gp, s, c, k_all, v_all = layer
        x, (s2, c2) = jax.lax.scan(inner, x, (gp, s, c))
        x, (k2, v2) = _shared_block(x, params["shared_attn"], cfg, sh, positions,
                                    kv_cache=(k_all, v_all, write_pos))
        return x, (s2, c2, k2, v2)

    x, (ssm_g, conv_g, k_g, v_g) = jax.lax.scan(
        group_body, x,
        (params["groups"], cache["ssm_g"], cache["conv_g"],
         cache["attn_k"], cache["attn_v"]))
    ssm_t, conv_t = cache["ssm_t"], cache["conv_t"]
    if tail:
        x, (ssm_t, conv_t) = jax.lax.scan(
            inner, x, (params["tail"], cache["ssm_t"], cache["conv_t"]))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params["unembed"].astype(x.dtype), sh)
    new_cache = {"ssm_g": ssm_g, "conv_g": conv_g, "ssm_t": ssm_t, "conv_t": conv_t,
                 "attn_k": k_g, "attn_v": v_g, "pos": pos + 1}
    return logits, new_cache


def hybrid_prefill(params, batch, cfg: ModelConfig, sh):
    """Chunked SSD over the prompt + full attention at each shared block,
    emitting all decode states (window == prompt length at prefill)."""
    from .mamba2 import mamba_block_prefill

    x = embed_tokens(params["embed"].astype(cfg.compute_dtype), batch["tokens"], sh)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    g, k, tail = _group_shape(cfg)

    def inner(x, lp):
        x, state, conv = mamba_block_prefill(x, lp, cfg, sh)
        return x, (state, conv)

    def group_body(x, gp):
        x, (s, c) = jax.lax.scan(inner, x, gp)
        x, (k_full, v_full) = _shared_block(x, params["shared_attn"], cfg, sh, positions)
        return x, (s, c, k_full, v_full)

    x, (ssm_g, conv_g, k_g, v_g) = jax.lax.scan(group_body, x, params["groups"])
    if tail:
        x, (ssm_t, conv_t) = jax.lax.scan(inner, x, params["tail"])
    else:
        B = x.shape[0]
        ssm_t = jnp.zeros((1, B, cfg.n_ssm_heads, cfg.ssm_headdim, cfg.ssm_state),
                          jnp.float32)
        conv_t = jnp.zeros((1, B, cfg.conv_width, cfg.d_inner), cfg.compute_dtype)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x[:, -1:], params["unembed"].astype(x.dtype), sh)
    k_g = sh(k_g, None, "batch", "kv_seq", "kv_heads", None)
    v_g = sh(v_g, None, "batch", "kv_seq", "kv_heads", None)
    cache = {"ssm_g": ssm_g, "conv_g": conv_g, "ssm_t": ssm_t, "conv_t": conv_t,
             "attn_k": k_g, "attn_v": v_g, "pos": jnp.asarray(S, jnp.int32)}
    return logits, cache
