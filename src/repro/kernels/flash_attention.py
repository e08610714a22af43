"""Flash attention as a Pallas TPU kernel (online softmax over KV blocks).

TPU adaptation notes (vs the CUDA original): blocks are sized for VMEM and
the 128x128 MXU — (block_q x head_dim) and (block_k x head_dim) tiles with
head_dim padded to a lane multiple; running max/sum live in VREGs via SMEM-
free carries re-read from the output ref between grid steps (the standard
Pallas TPU pattern: the KV-block loop is the innermost grid dimension, so
carries persist in VMEM scratch across that dimension).

Grid: (batch*heads, q_blocks, kv_blocks); kv is the minormost (sequential)
axis, so m/l/acc scratch carries across kv steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  kv_blocks: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                      # [block_q, hd]
    k = k_ref[0]                      # [block_k, hd]
    v = v_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if kv_len < kv_blocks * block_k:   # keys padded up to the block
        s = jnp.where(k_pos < kv_len, s, NEG_INF)

    m_prev = m_scr[...]               # [block_q, 1]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)            # [block_q, block_k]
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False):
    """q: [B,S,H,hd]  k,v: [B,T,H,hd] -> [B,S,H,hd].

    The kernel runs per (batch*head); q/k/v are transposed to
    [B*H, seq, hd] so each grid cell streams KV blocks through VMEM.
    Lengths that are not a multiple of the block are padded up to it; the
    padded keys are masked and the padded query rows dropped.
    """
    B, S, H, hd = q.shape
    hd_v = v.shape[-1]                 # MLA: v head dim may differ from q/k
    T = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    Sp = -(-S // block_q) * block_q
    Tp = -(-T // block_k) * block_k
    kv_blocks = Tp // block_k

    def to_rows(x, n, n_pad):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, n, x.shape[-1])
        return jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0))) if n_pad > n else x

    qt = to_rows(q, S, Sp)
    kt = to_rows(k, T, Tp)
    vt = to_rows(v, T, Tp)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_blocks=kv_blocks, kv_len=T)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sp // block_q, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd_v), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd_v), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd_v), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out[:, :S].reshape(B, H, S, hd_v).transpose(0, 2, 1, 3)
