"""Mamba2's chunked SSD as one differentiable pair of Pallas TPU kernels.

The SSD duality splits the recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t
x_t ⊗ B_t``, ``y_t = h_t C_t`` into chunks of Q steps: within a chunk the
output is a masked matmul, ``y = (C Bᵀ ∘ L ∘ dt) x`` with the decay kernel
``L[i, j] = exp(cum_i - cum_j)`` (i >= j, ``cum`` the chunk's running sum
of ``dt A``), plus the entering state read out, ``exp(cum_i) C_i S``; the
state then decays by ``exp(cum_Q)`` and takes the chunk's outer products.

The forward kernel walks the grid ``(batch, head blocks, chunks)``, the
chunk axis last and sequential, and carries the ``[N, hb·P]`` state of its
``hb`` heads in VMEM.  It reads ``x`` as ``[B, S, H·P]``, the layout the
model holds, and ``B``, ``C`` once per chunk for all heads of the block;
``C Bᵀ`` is formed once per cell.  The ``[Q, Q]`` decay kernel of each head
lives only in VMEM.  Besides ``y`` and the final state it can write the
state entering each chunk, the residual the backward kernel reads.

The backward kernel walks the chunks in reverse and carries the gradient
of the state leaving the chunk.  It works in the transposed frame
``[j, i]`` (source step on sublanes), where every product is a plain or a
right-transposed matmul.  It returns ``dx``, ``ddt``, the gradient of the
running sums (whose matmul's transpose turns it into ``ddt`` and
``dA``), and ``dB``, ``dC`` per head block, which the caller sums.

Heads are handled in lane groups of 128 lanes (two heads of 64): each
head's matmul runs on its group's lanes and a select keeps its own, so
every load and store is lane-aligned.

Precision: running sums, the decay kernel and its ``exp`` in float32;
``C Bᵀ`` from the inputs' values with float32 accumulation; products with
``x``, with the states and with the gradient of ``C Bᵀ`` on operands of
``x``'s dtype with float32 accumulation (XLA's default precision for the
jnp path's float32 products on a TPU); states carried in float32.  The
masked triangle is masked before the ``exp``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128
VMEM_LIMIT = 64 * 1024 * 1024   # of a v5e core's 128 MiB
# Heads per grid cell, at most.  At mamba2-780m's widths on a v5e, 16 heads
# a cell ran the forward kernel 12% faster than 8, and the backward, which
# holds about three times the forward's values per head, 4% slower.
FWD_HEADS, BWD_HEADS = 16, 8


def heads_per_block(H: int, P: int, most: int) -> int:
    """The most heads, up to ``most``, whose lanes fill whole 128-lane tiles
    and whose rows fill whole 8-row tiles; all H where no divisor does."""
    for hb in range(min(H, most), 0, -1):
        if H % hb == 0 and (hb * P) % LANES == 0 and (hb % 8 == 0 or hb == H):
            return hb
    return H


def chunk_len(chunk: int, S: int) -> int:
    """The chunk the kernels run: a multiple of 128 (the lane tile of the
    per-head rows), no longer than the padded sequence needs."""
    up = lambda n: -(-n // LANES) * LANES  # noqa: E731
    return min(up(chunk), up(S))


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=F32)


_NT = ((1,), (1,))   # a @ bᵀ
_TN = ((0,), (0,))   # aᵀ @ b


def _groups(hb: int, P: int):
    """[(first lane, lane count, [(head, its lanes' mask)])] per lane group."""
    per = math.gcd(hb, max(1, LANES // P))
    width = per * P
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [(k * width, width,
             [(k * per + i, (lane >= i * P) & (lane < (i + 1) * P)) for i in range(per)])
            for k in range(hb // per)]


def _spread(cols, heads):
    """Lane group values from per-head columns ``cols`` ([R, hb]): each
    head's column across its own lanes."""
    (h0, _), *rest = heads
    out = cols[:, h0:h0 + 1]
    for h, mask in rest:
        out = jnp.where(mask, cols[:, h:h + 1], out)
    return out


# ------------------------------------------------------------------ forward
def _fwd_kernel(dt_ref, cum_ref, x_ref, b_ref, c_ref, y_ref, last_ref, *rest,
                P: int, emit: bool):
    states_ref, state_scr = rest if emit else (None, rest[0])
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    s0 = state_scr[...]                                   # [N, hb·P] f32
    if emit:
        states_ref[...] = s0
    hb, Q = dt_ref.shape
    mm = x_ref.dtype
    b, cm = b_ref[...].astype(mm), c_ref[...].astype(mm)  # [Q, N]
    bt = b.T                                              # [N, Q]
    cb = _dot(cm, b, _NT)                                 # [Q, Q]  C Bᵀ
    dtr, cumr = dt_ref[...], cum_ref[...]                 # [hb, Q] f32
    dtc, cumc = dtr.T, cumr.T                             # [Q, hb]
    tot = cumr[:, Q - 1:Q].T                              # [1, hb]  chunk log-decay
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = row >= col
    e_in = jnp.exp(cumc)                                  # [Q, hb]  read-out decay
    w_end = jnp.exp(tot - cumc) * dtc                     # [Q, hb]  decay to end · dt
    d_all = jnp.exp(tot)                                  # [1, hb]

    for lo, width, heads in _groups(hb, P):
        lanes = slice(lo, lo + width)
        xk = x_ref[:, lanes]                              # [Q, W]
        s0k = s0[:, lanes]                                # [N, W]
        y = None
        for h, mask in heads:
            L = jnp.exp(jnp.where(causal, cumc[:, h:h + 1] - cumr[h:h + 1, :], -jnp.inf))
            M = (cb * L * dtr[h:h + 1, :]).astype(mm)
            yh = _dot(M, xk)                              # [Q, W]
            y = yh if y is None else jnp.where(mask, yh, y)
        y = y + _dot(cm, s0k.astype(mm)) * _spread(e_in, heads)
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        xw = (xk * _spread(w_end, heads)).astype(mm)
        state_scr[:, lanes] = s0k * _spread(d_all, heads) + _dot(bt, xw)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finish():
        last_ref[...] = state_scr[...]


def _forward(x2, dt_r, cum_r, B_, C_, *, Q, hb, P, emit, interpret):
    """(y [B,S,H·P], final state [B,N,H·P] f32[, entering states
    [B,nc,N,H·P] f32])."""
    Bsz, S, HP = x2.shape
    H, N = HP // P, B_.shape[-1]
    nc = S // Q
    hbP = hb * P
    rows = pl.BlockSpec((None, hb, Q), lambda b, g, c: (b, g, c))
    seq = pl.BlockSpec((None, Q, hbP), lambda b, g, c: (b, c, g))
    bc = pl.BlockSpec((None, Q, N), lambda b, g, c: (b, c, 0))
    out_specs = [seq, pl.BlockSpec((None, N, hbP), lambda b, g, c: (b, 0, g))]
    out_shape = [jax.ShapeDtypeStruct((Bsz, S, HP), x2.dtype),
                 jax.ShapeDtypeStruct((Bsz, N, HP), F32)]
    if emit:
        out_specs.append(pl.BlockSpec((None, None, N, hbP), lambda b, g, c: (b, c, 0, g)))
        out_shape.append(jax.ShapeDtypeStruct((Bsz, nc, N, HP), F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, emit=emit),
        grid=(Bsz, H // hb, nc),
        in_specs=[rows, rows, seq, bc, bc],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, hbP), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(dt_r, cum_r, x2, B_, C_)


# ----------------------------------------------------------------- backward
def _bwd_kernel(dt_ref, cum_ref, x_ref, b_ref, c_ref, s_ref, dy_ref, dlast_ref,
                dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, ds_scr, *, P: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = dlast_ref[...]

    hb, Q = dt_ref.shape
    mm = x_ref.dtype
    b, cm = b_ref[...].astype(mm), c_ref[...].astype(mm)  # [Q, N]
    ct = cm.T                                             # [N, Q]
    cbT = _dot(b, cm, _NT)                                # [Q(j), Q(i)]  (C Bᵀ)ᵀ
    dtr, cumr = dt_ref[...], cum_ref[...]                 # [hb, Q]
    dtc, cumc = dtr.T, cumr.T                             # [Q, hb]
    tot = cumr[:, Q - 1:Q].T                              # [1, hb]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causalT = col >= row
    e_in = jnp.exp(cumc)
    d_end = jnp.exp(tot - cumc)
    w_end = d_end * dtc
    d_all = jnp.exp(tot)
    hlane = jax.lax.broadcasted_iota(jnp.int32, (1, hb), 1)
    hrow = jax.lax.broadcasted_iota(jnp.int32, (hb, 1), 0)
    last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1

    s0, ds1 = s_ref[...], ds_scr[...]                     # [N, hb·P] f32
    dcbT = jnp.zeros((Q, Q), F32)
    db = jnp.zeros(db_ref.shape, F32)
    dcn = jnp.zeros(dc_ref.shape, F32)
    # per-head sums, gathered as columns [Q, hb] (a row [hb, Q] for the
    # sums over i) and combined once per cell
    gl_c = jnp.zeros((Q, hb), F32)      # Σ_i dM∘C Bᵀ∘L        (ddt, diagonal)
    dw_c = jnp.zeros((Q, hb), F32)      # x_j · (B_j dS1ᵀ)      (state update)
    dyo_c = jnp.zeros((Q, hb), F32)     # dy_i · (C_i S0ᵀ)      (read-out)
    sds_r = jnp.zeros((1, hb), F32)     # <S0, dS1>
    dcum_r = jnp.zeros((hb, Q), F32)    # Σ_j G[j, i]
    for lo, width, heads in _groups(hb, P):
        lanes = slice(lo, lo + width)
        xk, dyk = x_ref[:, lanes], dy_ref[:, lanes]       # [Q, W]
        s0k, ds1k = s0[:, lanes], ds1[:, lanes]           # [N, W]
        ek, wk = _spread(e_in, heads), _spread(w_end, heads)
        u = _dot(b, ds1k.astype(mm))                      # [Q, W]  B dS1ᵀ
        xu = xk * u
        dyy = dyk * _dot(cm, s0k.astype(mm))              # dy ∘ (C S0ᵀ)
        sds = s0k * ds1k
        dx = wk * u
        for h, mask in heads:
            dyh = jnp.where(mask, dyk, 0).astype(mm)      # head h's lanes of dy
            dc = dtc[:, h:h + 1]                          # [Q, 1]
            LT = jnp.exp(jnp.where(causalT, cumr[h:h + 1, :] - cumc[:, h:h + 1], -jnp.inf))
            cbLT = cbT * LT
            dx = dx + _dot((cbLT * dc).astype(mm), dyh)
            PL = _dot(xk, dyh, _NT) * LT                  # dMᵀ ∘ Lᵀ  [Q(j), Q(i)]
            dcbT = dcbT + PL * dc
            GL = PL * cbT
            at = hlane == h
            gl_c = jnp.where(at, jnp.sum(GL, axis=1, keepdims=True), gl_c)
            dw_c = jnp.where(at, jnp.sum(jnp.where(mask, xu, 0.0), axis=1, keepdims=True), dw_c)
            dyo_c = jnp.where(at, jnp.sum(jnp.where(mask, dyy, 0.0), axis=1, keepdims=True), dyo_c)
            sds_r = jnp.where(at, jnp.sum(jnp.where(mask, sds, 0.0), keepdims=True), sds_r)
            dcum_r = jnp.where(hrow == h, jnp.sum(GL * dc, axis=0, keepdims=True), dcum_r)
        dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
        dye = (dyk * ek).astype(mm)
        ds_scr[:, lanes] = ds1k * _spread(d_all, heads) + _dot(ct, dye)
        db = db + _dot((xk * wk).astype(mm), ds1k.astype(mm), _NT)
        dcn = dcn + _dot(dye, s0k.astype(mm), _NT)
    dcb = dcbT.astype(mm)
    db_ref[...] = db + _dot(dcb, cm)
    dc_ref[...] = dcn + _dot(dcb, b, _TN)
    dT = jnp.sum(dw_c * w_end, axis=0, keepdims=True) + d_all * sds_r        # [1, hb]
    dcum_c = e_in * dyo_c - dtc * gl_c - dw_c * w_end + jnp.where(last, dT, 0.0)
    ddt_ref[...] = (gl_c + dw_c * d_end).T
    dcum_ref[...] = dcum_r + dcum_c.T


def _backward(x2, dt_r, cum_r, B_, C_, states, dy, dlast, *, Q, hb, P, interpret):
    Bsz, S, HP = x2.shape
    H, N = HP // P, B_.shape[-1]
    nc, G = S // Q, H // hb
    hbP = hb * P
    rev = lambda c: nc - 1 - c  # noqa: E731
    rows = pl.BlockSpec((None, hb, Q), lambda b, g, c: (b, g, rev(c)))
    seq = pl.BlockSpec((None, Q, hbP), lambda b, g, c: (b, rev(c), g))
    bc = pl.BlockSpec((None, Q, N), lambda b, g, c: (b, rev(c), 0))
    per_block = pl.BlockSpec((None, None, Q, N), lambda b, g, c: (b, g, rev(c), 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, P=P),
        grid=(Bsz, G, nc),
        in_specs=[rows, rows, seq, bc, bc,
                  pl.BlockSpec((None, None, N, hbP), lambda b, g, c: (b, rev(c), 0, g)),
                  seq,
                  pl.BlockSpec((None, N, hbP), lambda b, g, c: (b, 0, g))],
        out_specs=[seq, rows, rows, per_block, per_block],
        out_shape=[jax.ShapeDtypeStruct((Bsz, S, HP), x2.dtype),
                   jax.ShapeDtypeStruct((Bsz, H, S), F32),
                   jax.ShapeDtypeStruct((Bsz, H, S), F32),
                   jax.ShapeDtypeStruct((Bsz, G, S, N), F32),
                   jax.ShapeDtypeStruct((Bsz, G, S, N), F32)],
        scratch_shapes=[pltpu.VMEM((N, hbP), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(dt_r, cum_r, x2, B_, C_, states, dy, dlast)


# -------------------------------------------------------- differentiable op
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _ssd(x2, dt_r, cum_r, B_, C_, Q, hb, P, interpret):
    """``hb``: heads per cell of the forward and of the backward kernel."""
    y, last = _forward(x2, dt_r, cum_r, B_, C_, Q=Q, hb=hb[0], P=P, emit=False,
                       interpret=interpret)
    return y, last


def _ssd_fwd(x2, dt_r, cum_r, B_, C_, Q, hb, P, interpret):
    y, last, states = _forward(x2, dt_r, cum_r, B_, C_, Q=Q, hb=hb[0], P=P, emit=True,
                               interpret=interpret)
    return (y, last), (x2, dt_r, cum_r, B_, C_, states)


def _ssd_bwd(Q, hb, P, interpret, res, cotangents):
    x2, dt_r, cum_r, B_, C_, states = res
    dy, dlast = cotangents
    dx, ddt, dcum, db, dc = _backward(x2, dt_r, cum_r, B_, C_, states,
                                      dy.astype(x2.dtype), dlast.astype(F32),
                                      Q=Q, hb=hb[1], P=P, interpret=interpret)
    return (dx, ddt, dcum, db.sum(1).astype(B_.dtype), dc.sum(1).astype(C_.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B_, C_, chunk: int = 256, *, hb: int | None = None,
             interpret: bool = False):
    """x: [B,S,H,P]  dt: [B,S,H]  A: [H]  B_,C_: [B,S,N].

    Returns (y [B,S,H,P] in x's dtype, final state [B,H,P,N] float32), as
    ``models.mamba2.ssd_chunked`` does, and is differentiable in every
    argument.  Any S: the sequence is padded to whole chunks with dt = 0,
    which leaves states and outputs unchanged.  ``hb`` heads share a grid
    cell of both kernels (default :func:`heads_per_block` with
    ``FWD_HEADS`` and ``BWD_HEADS``).
    """
    Bsz, S, H, P = x.shape
    hb = (hb, hb) if hb else (heads_per_block(H, P, FWD_HEADS),
                              heads_per_block(H, P, BWD_HEADS))
    Q = chunk_len(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    dt_r = dt.astype(F32).transpose(0, 2, 1)                          # [B,H,S]
    dA = (dt_r * A.astype(F32)[None, :, None]).reshape(Bsz, H, Sp // Q, Q)
    # within-chunk running sums as a float32 matmul with a triangle of ones
    # (XLA's cumsum is a reduce-window, several times slower on the chip)
    cum_r = jnp.einsum("bhck,kj->bhcj", dA, jnp.triu(jnp.ones((Q, Q), F32)),
                       precision=jax.lax.Precision.HIGHEST).reshape(Bsz, H, Sp)
    y, last = _ssd(x.reshape(Bsz, Sp, H * P), dt_r, cum_r, B_, C_, Q, hb, P, interpret)
    N = B_.shape[-1]
    return (y[:, :S].reshape(Bsz, S, H, P),
            last.reshape(Bsz, N, H, P).transpose(0, 2, 3, 1))
