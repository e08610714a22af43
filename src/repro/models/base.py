"""Model substrate: config, parameter-spec machinery, and shared layers.

Parameters are declared as ParamSpecs carrying *logical axis names*; the
parallel.sharding resolver turns those into NamedShardings per mesh.  This
is the bridge between Fix's worldview (every tensor's placement declared
before execution) and XLA SPMD (the platform performs all resulting I/O).

All model families are pure functions over pytrees — no module framework —
so ``jax.eval_shape`` gives the dry-run's abstract params for free and
checkpointing sees a plain dict of arrays.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field, replace
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

# ------------------------------------------------------------------ config
@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"  # dense | moe | mamba2 | hybrid | encdec | vlm
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 512
    vocab: int = 1024
    head_dim: int = 0           # 0 => d_model // n_heads
    qk_norm: bool = False
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    dense_residual: bool = False      # Arctic: dense MLP in parallel with MoE
    capacity_factor: float = 1.25
    # MLA (DeepSeek-V3)
    mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    rope_head_dim: int = 0
    nope_head_dim: int = 0
    v_head_dim: int = 0
    mtp: bool = False                  # extra multi-token-prediction head
    # SSM (Mamba2 SSD)
    ssm_state: int = 0
    ssm_headdim: int = 64
    expand: int = 2
    conv_width: int = 4
    ssm_chunk: int = 256
    # Hybrid (Zamba2)
    attn_every: int = 0                # shared attn block every k ssm layers
    attn_window: int = 0               # KV window for long-context decode
    # Enc-dec (Seamless backbone)
    n_enc_layers: int = 0
    n_dec_layers: int = 0
    cross_len: int = 4096              # encoder-memory length at decode time
    # VLM (InternVL backbone)
    n_patches: int = 0
    # numerics
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @property
    def head_dim_eff(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def vocab_padded(self) -> int:
        """Pad vocab so the 'model' axis always divides it (MaxText-style)."""
        return -(-self.vocab // 128) * 128

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim


# ------------------------------------------------------------- param specs
@dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple                 # logical names (len == len(shape))
    init: str = "normal"        # normal | zeros | ones | embed
    scale: float = 1.0          # multiplies the fan-in-scaled std
    dtype: Any = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def ps(shape, axes, init="normal", scale=1.0, dtype=None) -> ParamSpec:
    return ParamSpec(tuple(shape), tuple(axes), init, scale, dtype)


def _leaf_paths(tree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaf_paths(tree[k], prefix + (k,))
    else:
        yield prefix, tree


def tree_map_specs(fn, specs):
    """Map fn(path, ParamSpec) over a nested dict of specs."""
    def rec(node, prefix):
        if isinstance(node, dict):
            return {k: rec(v, prefix + (k,)) for k, v in node.items()}
        return fn(prefix, node)
    return rec(specs, ())


def abstract_params(specs, cfg: ModelConfig):
    return tree_map_specs(
        lambda _p, s: jax.ShapeDtypeStruct(s.shape, s.dtype or cfg.param_dtype), specs
    )


def init_params(specs, cfg: ModelConfig, seed: int = 0):
    """Deterministic init: each leaf's key derives from its path (content-
    addressable — the Fix angle: params are a pure function of (specs, seed))."""

    def init_leaf(path, s: ParamSpec):
        dtype = s.dtype or cfg.param_dtype
        if s.init == "zeros":
            return jnp.zeros(s.shape, dtype)
        if s.init == "ones":
            return jnp.ones(s.shape, dtype)
        digest = hashlib.blake2b("/".join(path).encode() + str(seed).encode(),
                                 digest_size=4).digest()
        key = jax.random.PRNGKey(int.from_bytes(digest, "little"))
        if s.init == "embed":
            std = s.scale
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            std = s.scale / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, s.shape, jnp.float32) * std).astype(dtype)

    return tree_map_specs(init_leaf, specs)


def serving_specs(specs, cfg: ModelConfig):
    """Inference holds weights in compute dtype — no f32 masters."""
    def leaf(_p, s: ParamSpec):
        if (s.dtype or cfg.param_dtype) == jnp.float32 and len(s.shape) >= 2:
            return replace(s, dtype=cfg.compute_dtype)
        return s
    return tree_map_specs(leaf, specs)


def param_pspecs(specs, sharder):
    """Nested dict of PartitionSpecs resolved from each leaf's logical axes."""
    return tree_map_specs(lambda _p, s: sharder.spec(s.axes, s.shape), specs)


def param_shardings(specs, sharder):
    return tree_map_specs(lambda _p, s: sharder.named(s.axes, s.shape), specs)


def count_params(specs) -> int:
    return sum(int(np.prod(s.shape)) for _, s in _leaf_paths(specs))


# ------------------------------------------------------------------ remat
def apply_remat(body, remat_policy):
    """Wrap a scan body in jax.checkpoint.  ``remat_policy`` is None (off),
    "full" (save nothing — recompute everything in backward), or a
    jax.checkpoint_policies policy object."""
    if remat_policy is None:
        return body
    policy = None if remat_policy == "full" else remat_policy
    return jax.checkpoint(body, policy=policy)


def scan_layers(body, x, layers, remat_policy, remat_group: int = 1):
    """Scan a stacked layer pytree with grouped activation checkpointing.

    remat_group=G saves activations only every G layers (sqrt(L)-style):
    the residual-save stack shrinks Gx at the cost of one extra in-group
    forward during backward — the standard memory-term lever for deep
    stacks (95-layer deepseek-67b: 12.7 GiB of saves at G=1).
    Only for ys-free bodies (training forwards).
    """
    if remat_group <= 1:
        return jax.lax.scan(apply_remat(body, remat_policy), x, layers)
    L = jax.tree.leaves(layers)[0].shape[0]
    G = remat_group
    assert L % G == 0, (L, G)
    grouped = jax.tree.map(lambda a: a.reshape((L // G, G) + a.shape[1:]), layers)

    def group_body(x, gp):
        x, _ = jax.lax.scan(body, x, gp)
        return x, None

    return jax.lax.scan(apply_remat(group_body, remat_policy), x, grouped)


# ----------------------------------------------------------------- layers
@jax.custom_vjp
def _rmsnorm_core(x, w, eps):
    ss = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)[..., None]
    inv = jax.lax.rsqrt(ss / x.shape[-1] + eps).astype(x.dtype)
    return x * inv * w.astype(x.dtype)


def _rmsnorm_fwd(x, w, eps):
    ss = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)[..., None]
    inv = jax.lax.rsqrt(ss / x.shape[-1] + eps)
    return x * inv.astype(x.dtype) * w.astype(x.dtype), (x, w, inv)


def _rmsnorm_bwd(res, dy):
    x, w, inv = res
    D = x.shape[-1]
    g = dy * w.astype(dy.dtype)
    gx = jnp.einsum("...d,...d->...", g, x,
                    preferred_element_type=jnp.float32)[..., None]
    inv_b = inv.astype(x.dtype)
    coef = (inv ** 3 * gx / D).astype(x.dtype)
    dx = g * inv_b - x * coef
    dw_shape = w.shape
    dw = jnp.einsum("...d,...d->...d" if w.ndim == 1 else "...d,...d->...d",
                    dy, x * inv_b)
    # reduce leading dims down to w's shape
    while dw.ndim > w.ndim:
        dw = dw.sum(0)
    return dx, dw.astype(w.dtype), None


_rmsnorm_core.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(x, w, eps: float = 1e-6):
    """RMSNorm with f32 statistics kept strictly inside reductions.

    Hand-written VJP: the naive autodiff of an f32-stats norm promotes the
    backward residual stream to f32 (f32 d_stats x bf16 x -> f32 dx), which
    makes XLA materialize an f32 copy of every remat-saved activation
    (measured: +2x activation memory and +60% backward FLOP time).  The
    custom rule returns dx in x's dtype with f32 used only in the two
    sum-of-squares/inner-product reductions."""
    return _rmsnorm_core(x, w, eps)


def rope(x, positions, theta: float):
    """Rotate-half RoPE.  x: [..., S, H, hd]; positions: [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# above this, materializing [S,T] scores is a memory cliff; route causal /
# full patterns through the flash path (Pallas on TPU, blocked jnp here)
_ATTN_BLOCK_THRESHOLD = 2048 * 8192  # (perf iter 1 refuted: at S=4k
# the jnp blocked twin costs MORE HBM traffic than one S^2 tile; the win is
# Pallas-on-TPU keeping tiles in VMEM, or S>=32k where S^2 is prohibitive)


def attend(q, k, v, mask, sh, pattern: Optional[str] = None):
    """Softmax attention.  q: [B,S,H,hd]  k,v: [B,T,H,hd]  mask: [.., S, T]
    broadcastable boolean (True = attend).  f32 softmax for stability.

    ``pattern`` ("causal" | "full") marks masks expressible by the flash
    kernel; large instances stream KV blocks instead of materializing
    [S, T] scores (arctic-480b prefill_32k: 997 GiB -> < 16 GiB)."""
    S, T = q.shape[1], k.shape[1]
    if pattern in ("causal", "full") and S > 1 and S * T >= _ATTN_BLOCK_THRESHOLD:
        from ..kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=(pattern == "causal"))
    if mask is None:  # lazily build small masks (callers pass None with a
        # pattern so the 32k x 32k boolean never materializes on the flash path)
        mask = causal_mask(S, T) if pattern == "causal" else \
            jnp.ones((1, 1, S, T), bool)
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return out


def repeat_kv(k, n_heads: int):
    """[B,T,Kv,hd] -> [B,T,H,hd] by repeating each kv head H/Kv times."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def causal_mask(s: int, t: Optional[int] = None):
    t = t or s
    return jnp.tril(jnp.ones((s, t), dtype=bool), k=t - s)[None, None]


def swiglu(x, w_gate, w_up, w_down, sh):
    g = jnp.einsum("bsd,df->bsf", x, w_gate)
    u = jnp.einsum("bsd,df->bsf", x, w_up)
    h = jax.nn.silu(g) * u
    h = sh(h, "batch", "seq", "mlp")
    return jnp.einsum("bsf,fd->bsd", h, w_down)


def embed_tokens(embedding, tokens, sh):
    x = jnp.take(embedding, tokens, axis=0)
    return sh(x, "batch", "seq", "embed")


def unembed(x, w, sh):
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return sh(logits, "batch", "seq", "vocab")


def ce_loss(logits, labels, cfg: ModelConfig, mask=None):
    """Stable cross-entropy in f32; ignores padded-vocab tail and masked
    positions.  Returns (mean loss, metrics)."""
    logits = logits.astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab:
        pad = jnp.arange(logits.shape[-1]) >= cfg.vocab
        logits = jnp.where(pad[None, None, :], -1e30, logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    return loss, {"loss": loss, "tokens": denom}
