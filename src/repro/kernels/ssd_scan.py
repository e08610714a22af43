"""Mamba2 SSD chunk scan as a Pallas TPU kernel.

The SSD duality splits the recurrence into (a) within-chunk dense matmuls
(MXU work: C B^T masked by the decay kernel, times dt-weighted X) and (b) a
sequential inter-chunk state pass.  The kernel walks chunks as the minor
grid axis, carrying the [P, N] state in VMEM scratch — so the O(S) history
never round-trips HBM and each chunk's tiles are read once.

Grid: (batch*heads, chunks).  Per-cell tiles: x [Q, P], dt [Q, 1],
B/C [Q, N] with Q = chunk length; the per-(batch, head) decay rate is one
SMEM scalar indexed by the grid's first axis.

Mosaic has no cumsum lowering, so the within-chunk prefix sums are masked
reductions over a [Q, Q] tile: summing ``tril``-masked rows gives the sums
as a row, and reading that row's diagonal back gives them as a column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_scr, *, chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)       # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)     # [Q, 1]
    b = b_ref[0].astype(jnp.float32)       # [Q, N]
    c = c_ref[0].astype(jnp.float32)       # [Q, N]
    a = a_ref[pl.program_id(0)]             # scalar decay rate (negative)

    dA = dt * a                             # [Q, 1]
    Q = dA.shape[0]
    q_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    k_j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = q_i >= k_j
    # inclusive within-chunk prefix sums, as a row [1, Q] and a column [Q, 1]
    cum_row = jnp.sum(jnp.where(q_i <= k_j, dA, 0.0), axis=0, keepdims=True)
    cum = jnp.sum(jnp.where(q_i == k_j, cum_row, 0.0), axis=1, keepdims=True)
    total = jnp.sum(dA, axis=0, keepdims=True)      # [1, 1] chunk log-decay
    # within-chunk causal decay kernel L[i, j] = exp(cum_i - cum_j), i >= j
    L = jnp.exp(jnp.where(causal, cum - cum_row, -jnp.inf))
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)      # [Q, Q]
    y_diag = jax.lax.dot_general(cb * L, x * dt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Q, P]
    # inter-chunk: contribution of the entering state
    state = state_scr[...]                  # [N, P]
    y_off = jax.lax.dot_general(c, state, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * jnp.exp(cum)
    # state update: decay then add this chunk's outer products
    w = jnp.exp(total - cum) * dt           # [Q, 1] decay-to-end * dt
    s_new = jax.lax.dot_general(b.T, x * w, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)   # [N, P]
    state_scr[...] = state * jnp.exp(total) + s_new

    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ci == chunks - 1)
    def _finish():
        state_out_ref[0] = state_scr[...].astype(state_out_ref.dtype)


def ssd_scan(x, dt, A, B_, C_, chunk: int = 256, *, interpret: bool = False):
    """x: [B,S,H,P]  dt: [B,S,H]  A: [H]  B_,C_: [B,S,N].

    Returns (y [B,S,H,P], final_state [B,H,P,N]).  B_/C_ are shared across
    heads (broadcast into the per-(batch,head) grid).
    """
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    chunks = S // Q

    xt = x.transpose(0, 2, 1, 3).reshape(Bsz * H, S, P)
    dtt = dt.transpose(0, 2, 1).reshape(Bsz * H, S, 1)
    bt = jnp.broadcast_to(B_[:, None], (Bsz, H, S, N)).reshape(Bsz * H, S, N)
    ct = jnp.broadcast_to(C_[:, None], (Bsz, H, S, N)).reshape(Bsz * H, S, N)
    at = jnp.broadcast_to(A[None, :], (Bsz, H)).reshape(Bsz * H).astype(jnp.float32)

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, chunks=chunks),
        grid=(Bsz * H, chunks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz * H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(at, xt, dtt, bt, ct)
    y = y.reshape(Bsz, H, S, P).transpose(0, 2, 1, 3)
    state = state.reshape(Bsz, H, N, P).transpose(0, 1, 3, 2)  # [B,H,P,N]
    return y, state
