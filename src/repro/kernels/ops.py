"""Jitted wrappers + platform dispatch for the kernel layer.

TPU (target): Pallas kernels.  CPU (this container): interpret-mode for
tests, and for the dry-run the models use ``blocked_attention`` — an
online-softmax scan that is the exact jnp twin of the flash kernel, so the
lowered HLO has the kernel's memory behaviour (no S x T materialization)
even where Pallas can't lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ------------------------------------------------------ blocked attention
def blocked_attention(q, k, v, *, causal: bool = True, block_k: int = 1024):
    """Online-softmax attention via lax.scan over KV blocks.

    q: [B,S,H,hd]  k,v: [B,T,H,hd].  Never materializes [S, T]; the live
    set is one [B,S,H,block_k] score tile — the flash-attention memory
    profile expressed in pure jnp (XLA fuses the tile pipeline).
    """
    B, S, H, hd = q.shape
    hd_v = v.shape[-1]                 # MLA: v head dim may differ from q/k
    T = k.shape[1]
    block_k = min(block_k, T)
    pad = (-T) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Tp = T + pad
    nb = Tp // block_k
    scale = 1.0 / np.sqrt(hd)
    kb = k.reshape(B, nb, block_k, H, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block_k, H, hd_v).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(S)

    def step(carry, inp):
        m, l, acc = carry
        bi, k_blk, v_blk = inp
        s = jnp.einsum("bshd,bthd->bhst", q, k_blk).astype(jnp.float32) * scale
        k_pos = bi * block_k + jnp.arange(block_k)
        valid = k_pos[None, :] < T
        if causal:
            valid = valid & (q_pos[:, None] >= k_pos[None, :])
        s = jnp.where(valid[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p.astype(v_blk.dtype), v_blk).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, H, S, hd_v), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (jnp.arange(nb), kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# --------------------------------------------------------------- dispatch
# threshold above which the naive [S,T] materialization would blow VMEM/HBM
_BLOCK_THRESHOLD = 4096 * 4096


def flash_attention(q, k, v, mask=None, *, causal: bool = True):
    """Public attention entry used by models.  mask is accepted for parity
    with base.attend but only causal/full patterns route here."""
    if on_tpu():
        from .flash_attention import flash_attention as fa
        return fa(q, k, v, causal=causal)
    return blocked_attention(q, k, v, causal=causal)


def decode_attention(q, k, v, length):
    if on_tpu():
        from .decode_attention import decode_attention as da
        return da(q, k, v, length)
    from .ref import decode_attention_ref
    return decode_attention_ref(q, k, v, length)


def rmsnorm(x, w, eps: float = 1e-6):
    if on_tpu():
        from .rmsnorm import rmsnorm as rn
        return rn(x, w, eps)
    from .ref import rmsnorm_ref
    return rmsnorm_ref(x, w, eps)
