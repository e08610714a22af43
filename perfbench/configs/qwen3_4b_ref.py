"""Plain float32 reference of Qwen3-4B: a dense decoder with grouped-query
attention, RMS norms on queries and keys, rotary positions, SwiGLU.

Follows the published architecture (Qwen3 technical report, arXiv:2505.09388;
the Hugging Face ``Qwen3ForCausalLM``): pre-norm blocks, RMSNorm without
bias, rotate-half RoPE with base ``rope_theta``, causal softmax attention in
which each of the ``num_key_value_heads`` key/value heads serves
``num_attention_heads / num_key_value_heads`` query heads.  The output head
is the embedding's transpose (``tie_word_embeddings`` true).

Every matrix product runs in float32 at ``Precision.HIGHEST``; there is no
cache and no batching of decode steps: one forward pass over each whole
sequence.  Weights come one layer at a time from a ``weights`` object
(``layer(i)``, ``globals()``), so the model never has to fit twice.

``control=True`` also runs the same pass with every matrix product's
operands rounded to float8 (e4m3, one scale per output channel for
weights and per token for activations): the precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def fp8(x, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec: str, x, w, quant: bool):
    """einsum of an activation and a weight; both contract the letters
    they share."""
    if quant:
        ins, _ = spec.split("->")
        a, b = ins.split(",")
        shared = set(a) & set(b)
        x = fp8(x, tuple(i for i, c in enumerate(a) if c in shared))
        w = fp8(w, tuple(i for i, c in enumerate(b) if c in shared))
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [B, S, H, hd], positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(x, w, *, eps, theta, n_kv, quant):
    B, S, _ = x.shape
    h = rmsnorm(x, w["attn_norm"], eps)
    q = mm("bsd,dhk->bshk", h, w["wq"], quant)
    k = mm("bsd,dhk->bshk", h, w["wk"], quant)
    v = mm("bsd,dhk->bshk", h, w["wv"], quant)
    q = rope(rmsnorm(q, w["q_norm"], eps), theta)
    k = rope(rmsnorm(k, w["k_norm"], eps), theta)
    H, hd = q.shape[2], q.shape[3]
    q = q.reshape(B, S, n_kv, H // n_kv, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v, precision=HIGHEST).reshape(B, S, H, hd)
    x = x + mm("bshk,hkd->bsd", o, w["wo"], quant)
    h = rmsnorm(x, w["mlp_norm"], eps)
    g = mm("bsd,df->bsf", h, w["w_gate"], quant)
    u = mm("bsd,df->bsf", h, w["w_up"], quant)
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "quant"))
def _head(x, rows, cols, final_norm, unembed, *, eps, vocab, quant):
    h = rmsnorm(x[rows, cols], final_norm, eps)
    logits = mm("nd,dv->nv", h, unembed, quant)
    return jnp.where(jnp.arange(logits.shape[-1]) < vocab, logits, -jnp.inf)


@jax.jit
def _gaps(ref_logits, targets, ctrl_logits):
    best = ref_logits.max(-1)
    served = jnp.take_along_axis(ref_logits, targets[:, None], -1)[:, 0]
    ctrl = jnp.take_along_axis(ref_logits, ctrl_logits.argmax(-1)[:, None], -1)[:, 0]
    return best - served, best - ctrl


def _blocks(lengths, max_tokens: int) -> list:
    order = sorted(range(len(lengths)), key=lambda i: -lengths[i])
    blocks, cur = [], []
    for i in order:
        s_max = lengths[cur[0]] if cur else lengths[i]
        if cur and (len(cur) + 1) * s_max > max_tokens:
            blocks.append(cur)
            cur = []
        cur.append(i)
    return blocks + [cur] if cur else blocks


def served_gaps(cfg: dict, weights, seqs, want, targets, control: bool = False,
                max_tokens: int = 8192):
    """For each sequence, the reference's best logit minus its logit for
    each target token, at the positions ``want`` (the positions whose
    output predicted each target).  With ``control``, also the same gap for
    the token the float8 pass puts first.  Returns (gaps, control gaps or
    None), lists of numpy arrays."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    n_kv, vocab = int(cfg["num_key_value_heads"]), int(cfg["vocab_size"])
    blocks = _blocks([len(s) for s in seqs], max_tokens)
    g = weights.globals()
    streams = [False, True] if control else [False]
    xs = {}
    for bi, idx in enumerate(blocks):
        s_max = -(-max(len(seqs[i]) for i in idx) // 128) * 128  # fewer shapes
        toks = np.zeros((len(idx), s_max), np.int32)
        for r, i in enumerate(idx):
            toks[r, : len(seqs[i])] = seqs[i]
        for q in streams:
            xs[bi, q] = g["embed"][jnp.asarray(toks)]
    step = {q: jax.jit(functools.partial(layer, eps=eps, theta=theta, n_kv=n_kv, quant=q))
            for q in streams}
    for li in range(weights.n_layers):
        w = weights.layer(li)
        for key in xs:
            xs[key] = step[key[1]](xs[key], w)
        del w
    gaps: list = [None] * len(seqs)
    ctrl: list = [None] * len(seqs)
    for bi, idx in enumerate(blocks):
        rows = np.concatenate([np.full(len(want[i]), r) for r, i in enumerate(idx)])
        cols = np.concatenate([want[i] for i in idx])
        tgt = jnp.asarray(np.concatenate([targets[i] for i in idx]), jnp.int32)
        logits = {q: _head(xs[bi, q], rows, cols, g["final_norm"], g["embed"].T,
                           eps=eps, vocab=vocab, quant=q) for q in streams}
        served, by_ctrl = _gaps(logits[False], tgt, logits[control])
        served, by_ctrl = np.asarray(served), np.asarray(by_ctrl)
        at = np.cumsum([0] + [len(want[i]) for i in idx])
        for r, i in enumerate(idx):
            gaps[i] = served[at[r]: at[r + 1]]
            ctrl[i] = by_ctrl[at[r]: at[r + 1]]
    return gaps, (ctrl if control else None)
