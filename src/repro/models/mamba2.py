"""Mamba2: the SSD (state-space duality) block, arXiv:2405.21060.

Training uses the chunked SSD algorithm: within-chunk terms are dense
matmuls (MXU-friendly — this is the hot-spot our Pallas ssd_scan kernel
tiles for VMEM), and inter-chunk state propagation is a parallel
associative scan.  Decode is the O(1)-per-token recurrence
``h = exp(dt·A)·h + dt·B⊗x`` — which is why ``long_500k`` runs for SSM
archs while pure-attention archs skip it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .base import ModelConfig, apply_remat, embed_tokens, ps, rmsnorm, scan_layers, unembed


# ------------------------------------------------------------------- specs
def mamba_layer_specs(cfg: ModelConfig, n_layers: int,
                      layer_axis: str = "p_layers") -> dict:
    L, D = n_layers, cfg.d_model
    Din, H, N, W = cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_state, cfg.conv_width
    la = layer_axis
    return {
        "norm": ps((L, D), (la, "p_none"), init="ones"),
        "in_z": ps((L, D, Din), (la, "p_embed", "p_conv_dim")),
        "in_x": ps((L, D, Din), (la, "p_embed", "p_conv_dim")),
        "in_B": ps((L, D, N), (la, "p_embed", "p_none")),
        "in_C": ps((L, D, N), (la, "p_embed", "p_none")),
        "in_dt": ps((L, D, H), (la, "p_embed", "p_ssm_heads")),
        "conv_x": ps((L, cfg.conv_width, Din), (la, "p_none", "p_conv_dim"),
                     init="normal", scale=1.0),
        "conv_b": ps((L, Din), (la, "p_conv_dim"), init="zeros"),
        "A_log": ps((L, H), (la, "p_ssm_heads"), init="zeros"),
        "dt_bias": ps((L, H), (la, "p_ssm_heads"), init="zeros"),
        "D_skip": ps((L, H), (la, "p_ssm_heads"), init="ones"),
        "gate_norm": ps((L, Din), (la, "p_conv_dim"), init="ones"),
        "out": ps((L, Din, D), (la, "p_conv_dim", "p_embed")),
    }


def mamba_specs(cfg: ModelConfig) -> dict:
    Vp, D = cfg.vocab_padded, cfg.d_model
    return {
        "embed": ps((Vp, D), ("p_vocab", "p_embed"), init="embed", scale=0.02),
        "layers": mamba_layer_specs(cfg, cfg.n_layers),
        "final_norm": ps((D,), ("p_none",), init="ones"),
        "unembed": ps((D, Vp), ("p_embed", "p_vocab")),
    }


# ------------------------------------------------------------ SSD training
def _causal_conv(x, w, b):
    """Depthwise causal conv.  x: [B,S,C], w: [W,C], b: [C]."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i : i + x.shape[1]] * w[i][None, None, :] for i in range(W))
    return out + b[None, None, :]


def ssd_chunked(x, dt, A, B_, C_, chunk: int, use_kernel: bool = False):
    """SSD forward.  x: [B,S,H,P]  dt: [B,S,H]  A: [H]  B_,C_: [B,S,N].

    Returns y: [B,S,H,P] and the final state [B,H,P,N].
    """
    if use_kernel:
        from ..kernels import ops as kops
        return kops.ssd_scan(x, dt, A, B_, C_, chunk)
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    f32 = jnp.float32
    # pad to a chunk multiple; dt=0 on padding makes it a no-op (decay 1,
    # zero state update), so states and unpadded outputs are exact
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
    S_pad = S + pad
    nc = S_pad // Q

    xr = x.reshape(Bsz, nc, Q, H, P)
    dtr = dt.reshape(Bsz, nc, Q, H).astype(f32)
    Br = B_.reshape(Bsz, nc, Q, N).astype(f32)
    Cr = C_.reshape(Bsz, nc, Q, N).astype(f32)
    dA = dtr * A[None, None, None, :]                      # [B,nc,Q,H] (<=0)
    cum = jnp.cumsum(dA, axis=2)                           # within-chunk cumsum

    # within-chunk (diagonal) term: causal decay kernel  L[i,j]=exp(cum_i-cum_j)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [B,nc,Q,Q,H]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # mask before exp: above the diagonal diff is positive and exp overflows,
    # and where(mask, inf, 0)'s gradient is 0 * inf = NaN
    Lmat = jnp.exp(jnp.where(causal[None, None, :, :, None], diff, -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", Cr, Br)             # [B,nc,Q,Q]
    scores = cb[:, :, :, :, None] * Lmat                    # [B,nc,Q,Q,H]
    xdt = xr * dtr[..., None].astype(x.dtype)               # dt_j · x_j
    y_diag = jnp.einsum("bcijh,bcjhp->bcihp",
                        scores.astype(x.dtype), xdt)

    # chunk-local end states: S_c = sum_j exp(cum_Q - cum_j) * dt_j B_j x_j
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)         # [B,nc,Q,H]
    wx = xr * (dtr * decay_to_end)[..., None].astype(x.dtype)
    s_local = jnp.einsum("bcqn,bcqhp->bchpn", Br.astype(x.dtype), wx)  # [B,nc,H,P,N]

    # inter-chunk: associative scan of (decay, state) pairs
    a_chunk = jnp.exp(cum[:, :, -1, :]).astype(f32)         # [B,nc,H]

    def combine(l, r):
        al, sl = l
        ar, sr = r
        return al * ar, sr + sl * ar[..., None, None].astype(sl.dtype)

    _, s_cum = jax.lax.associative_scan(combine, (a_chunk, s_local), axis=1)
    s_prev = jnp.concatenate(
        [jnp.zeros_like(s_cum[:, :1]), s_cum[:, :-1]], axis=1)  # state entering chunk

    # off-diagonal: y_off[j] = exp(cum_j) * C_j . S_prev, weighted by dt? no —
    # state already carries dt·B·x; contribution is C_j (decay_in) S_prev
    decay_in = jnp.exp(cum).astype(x.dtype)                  # [B,nc,Q,H]
    y_off = jnp.einsum("bcqn,bchpn->bcqhp", Cr.astype(x.dtype), s_prev)
    y_off = y_off * decay_in[..., None]

    y = (y_diag + y_off).reshape(Bsz, S_pad, H, P)[:, :S]
    final_state = s_cum[:, -1]                               # [B,H,P,N]
    return y, final_state


def mamba_block(x, lp, cfg: ModelConfig, sh, ssm_state=None, conv_state=None,
                use_kernel: bool = False):
    """One Mamba2 block.  Train: ssm_state None.  Decode: states provided,
    S must be 1.  Returns (residual out, (ssm_state, conv_state))."""
    Bsz, S, D = x.shape
    H, P, N = cfg.n_ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    dt_ = x.dtype
    h = rmsnorm(x, lp["norm"], cfg.norm_eps)
    z = jnp.einsum("bsd,di->bsi", h, lp["in_z"].astype(dt_))
    xc = jnp.einsum("bsd,di->bsi", h, lp["in_x"].astype(dt_))
    B_ = jnp.einsum("bsd,dn->bsn", h, lp["in_B"].astype(dt_))
    C_ = jnp.einsum("bsd,dn->bsn", h, lp["in_C"].astype(dt_))
    dt = jnp.einsum("bsd,dh->bsh", h, lp["in_dt"].astype(dt_))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"][None, None, :])
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))

    if ssm_state is None:  # train / prefill
        xc = _causal_conv(xc, lp["conv_x"].astype(dt_), lp["conv_b"].astype(dt_))
        xc = jax.nn.silu(xc)
        xc = sh(xc, "batch", "seq", "conv_dim")
        xh = xc.reshape(Bsz, S, H, P)
        xh = sh(xh, "batch", "seq", "ssm_heads", None)
        y, final_state = ssd_chunked(xh, dt, A, B_, C_, cfg.ssm_chunk, use_kernel)
        y = y + xh * lp["D_skip"].astype(dt_)[None, None, :, None]
        new_conv = None  # prefill conv-state emission handled by caller if needed
    else:  # decode: O(1) recurrence
        conv_state = jnp.concatenate([conv_state[:, 1:], xc], axis=1)  # [B,W,Din]
        w = lp["conv_x"].astype(dt_)
        xc = (conv_state * w[None]).sum(1, keepdims=True) + lp["conv_b"].astype(dt_)
        xc = jax.nn.silu(xc)
        xh = xc.reshape(Bsz, 1, H, P)
        dA = jnp.exp(dt[:, 0] * A[None, :])                       # [B,H]
        upd = jnp.einsum("bhp,bn->bhpn", (dt[:, 0, :, None] * xh[:, 0].astype(jnp.float32)),
                         B_[:, 0].astype(jnp.float32))
        new_state = ssm_state * dA[..., None, None] + upd          # [B,H,P,N]
        y = jnp.einsum("bhpn,bn->bhp", new_state, C_[:, 0].astype(jnp.float32))
        y = y[:, None].astype(dt_) + xh * lp["D_skip"].astype(dt_)[None, None, :, None]
        final_state = new_state
        new_conv = conv_state

    y = y.reshape(Bsz, S, cfg.d_inner)
    y = rmsnorm(y * jax.nn.silu(z), lp["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("bsi,id->bsd", y, lp["out"].astype(dt_))
    return x + sh(out, "batch", "seq", "embed"), (final_state, new_conv)


# ----------------------------------------------------------------- forward
def mamba_forward(params, batch, cfg: ModelConfig, sh, remat_policy=None,
                  use_kernel: bool = False, remat_group: int = 1):
    x = embed_tokens(params["embed"].astype(cfg.compute_dtype), batch["tokens"], sh)

    def body(x, lp):
        x, _ = mamba_block(x, lp, cfg, sh, use_kernel=use_kernel)
        return x, None

    x, _ = scan_layers(body, x, params["layers"], remat_policy, remat_group)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return unembed(x, params["unembed"].astype(x.dtype), sh)


def mamba_cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    """The SSM 'KV cache' is O(1) in sequence length: the recurrent state
    plus the conv window.  max_seq only sets the position counter's range."""
    L, H, P, N = cfg.n_layers, cfg.n_ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    return {
        "ssm": ps((L, batch, H, P, N),
                  ("p_layers", "batch", "ssm_heads", "p_none", "p_none"),
                  init="zeros", dtype=jnp.float32),
        "conv": ps((L, batch, cfg.conv_width, cfg.d_inner),
                   ("p_layers", "batch", "p_none", "conv_dim"),
                   init="zeros", dtype=cfg.compute_dtype),
        "pos": ps((), (), init="zeros", dtype=jnp.int32),
    }


def mamba_decode_step(params, cache, tokens, cfg: ModelConfig, sh):
    x = embed_tokens(params["embed"].astype(cfg.compute_dtype), tokens, sh)

    def body(x, layer):
        lp, s, c = layer
        x, (s2, c2) = mamba_block(x, lp, cfg, sh, ssm_state=s, conv_state=c)
        return x, (s2, c2)

    x, (s_stack, c_stack) = jax.lax.scan(body, x, (params["layers"], cache["ssm"], cache["conv"]))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params["unembed"].astype(x.dtype), sh)
    return logits, {"ssm": s_stack, "conv": c_stack, "pos": cache["pos"] + 1}


def mamba_block_prefill(x, lp, cfg: ModelConfig, sh, use_kernel: bool = False):
    """Block forward that also emits decode-ready (ssm, conv) states."""
    S = x.shape[1]
    h = rmsnorm(x, lp["norm"], cfg.norm_eps)
    xc = jnp.einsum("bsd,di->bsi", h, lp["in_x"].astype(x.dtype))
    conv_tail = xc[:, S - (cfg.conv_width - 1):]  # last W-1 pre-conv inputs
    pad = jnp.zeros((x.shape[0], 1, cfg.d_inner), xc.dtype)
    conv_state = jnp.concatenate([pad, conv_tail], axis=1)
    x, (state, _) = mamba_block(x, lp, cfg, sh, use_kernel=use_kernel)
    return x, state, conv_state


def mamba_prefill(params, batch, cfg: ModelConfig, sh):
    """Prefill: chunked forward, emitting final SSM + conv states."""
    x = embed_tokens(params["embed"].astype(cfg.compute_dtype), batch["tokens"], sh)
    S = x.shape[1]

    def body(x, lp):
        x, state, conv_state = mamba_block_prefill(x, lp, cfg, sh)
        return x, (state, conv_state)

    x, (s_stack, c_stack) = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x[:, -1:], params["unembed"].astype(x.dtype), sh)
    cache = {"ssm": s_stack, "conv": c_stack, "pos": jnp.asarray(S, jnp.int32)}
    return logits, cache
