"""Plain float32 reference of Mamba2-780M training: forward, loss,
gradients by autodiff, and Adafactor, for a few steps.

The block follows the Mamba-2 paper (arXiv:2405.21060, section 7 and the
"SSD minimal" listing): RMSNorm, input projections to the gate ``z``, the
stream ``x``, ``B``, ``C`` (one group) and ``dt``; a causal depthwise
convolution and SiLU on ``x``; ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``; the state-space recurrence
``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``
computed by the chunked (dual) form; gated RMSNorm ``norm(y * silu(z))``;
the output projection and the residual.  Departures, as the configuration
file states: the convolution covers ``x`` only, and the output head is a
matrix of its own.

Loss: mean cross-entropy over every position, the padded vocabulary
columns excluded.  Adafactor (Shazeer and Stern, 2018), as the mix file
configures it: no first moment, second moments factored over the last two
axes of a matrix whose last two axes are both at least 128, decay
``1 - t^-decay_pow``, update clipped to RMS ``clip_threshold``, step
``lr * min(1, 10 / sqrt(t)) * max(RMS(param), eps2)``.  Each layer's
matrix is its own parameter.

Everything runs in float32 at ``Precision.HIGHEST``.  ``low=True`` is the
control, each precision the configuration states taken one step down:
parameters held in bfloat16 (each update rounded as it is stored) in
place of float32, and every matrix product's operands rounded to float8
(e4m3: 4 exponent and 3 mantissa bits, one scale per output channel or
token) in place of bfloat16.  Rounding goes through
``lax.reduce_precision``, which XLA keeps: a round trip through a
narrower dtype may be folded away inside a compiled program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


F8_MAX = 240.0  # the largest e4m3 number under IEEE rules (4, 3 bits)


def fp8(x, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``; the
    gradient passes straight through in float32."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    q = jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(spec, a, b, quant=False):
    """einsum of an activation and a weight; with ``quant`` both operands
    are rounded to float8 over the letters they contract."""
    if quant:
        x, w = spec.split("->")[0].split(",")
        shared = set(x) & set(w)
        a = fp8(a, tuple(i for i, c in enumerate(x) if c in shared))
        b = fp8(b, tuple(i for i, c in enumerate(w) if c in shared))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def segsum(a):
    """a: [..., T] -> [..., T, T], entry (i, j) = sum a[j+1..i], -inf above
    the diagonal."""
    T = a.shape[-1]
    x = jnp.repeat(a[..., None], T, axis=-1)          # x[..., i, j] = a[i]
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x, 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def ssd(x, a, b, c, chunk):
    """SSD minimal (chunked dual form).  x: [B, S, H, P] (already times
    dt), a: [B, S, H] (dt * A), b, c: [B, S, N]."""
    Bsz, S, H, P = x.shape
    nc = S // chunk
    x = x.reshape(Bsz, nc, chunk, H, P)
    a = a.reshape(Bsz, nc, chunk, H).transpose(0, 3, 1, 2)     # b h c l
    b = b.reshape(Bsz, nc, chunk, -1)
    c = c.reshape(Bsz, nc, chunk, -1)
    a_cum = jnp.cumsum(a, axis=-1)
    L = jnp.exp(segsum(a))                                       # b h c l s
    cb = jnp.einsum("bcln,bcsn->bcls", c, b, precision=HIGHEST)
    y_diag = jnp.einsum("bcls,bhcls,bcshp->bclhp", cb, L, x, precision=HIGHEST)
    decay = jnp.exp(a_cum[..., -1:] - a_cum)                     # b h c l
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", b, decay, x, precision=HIGHEST)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    chunk_decay = jnp.exp(segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states, precision=HIGHEST)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cum),
                       precision=HIGHEST)
    return (y_diag + y_off).reshape(Bsz, S, H, P)


def block(x, w, *, cfg, quant=False):
    eps, P, chunk, W = cfg["norm_epsilon"], cfg["headdim"], cfg["chunk_size"], cfg["d_conv"]
    Bsz, S, _ = x.shape
    h = rmsnorm(x, w["norm"], eps)
    z = mm("bsd,di->bsi", h, w["in_z"], quant)
    xc = mm("bsd,di->bsi", h, w["in_x"], quant)
    b = mm("bsd,dn->bsn", h, w["in_B"], quant)
    c = mm("bsd,dn->bsn", h, w["in_C"], quant)
    dt = jax.nn.softplus(mm("bsd,dh->bsh", h, w["in_dt"], quant) + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    xp = jnp.pad(xc, ((0, 0), (W - 1, 0), (0, 0)))
    xc = sum(xp[:, i: i + S] * w["conv_x"][i] for i in range(W)) + w["conv_b"]
    xh = jax.nn.silu(xc).reshape(Bsz, S, -1, P)
    y = ssd(xh * dt[..., None], dt * A, b, c, chunk) + xh * w["D_skip"][:, None]
    y = rmsnorm(y.reshape(Bsz, S, -1) * jax.nn.silu(z), w["gate_norm"], eps)
    return x + mm("bsi,id->bsd", y, w["out"], quant)


def loss_fn(params, tokens, labels, *, cfg, quant=False, loss_chunk: int = 512):
    x = params["embed"][tokens]

    def body(x, w):
        return jax.checkpoint(functools.partial(block, cfg=cfg, quant=quant))(x, w), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg["norm_epsilon"])
    vocab = cfg["vocab_size"]
    Bsz, S, D = x.shape
    loss_chunk = min(loss_chunk, S)
    xs = x.reshape(Bsz, S // loss_chunk, loss_chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(Bsz, S // loss_chunk, loss_chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_nll(xc, lc):
        logits = mm("bsd,dv->bsv", xc, params["unembed"], quant)
        logits = jnp.where(jnp.arange(logits.shape[-1]) < vocab, logits, -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return (lse - jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]).sum()

    total = jax.lax.map(lambda xl: chunk_nll(*xl), (xs, ls)).sum()
    return total / (Bsz * S)


def _factored(shape) -> bool:
    return len(shape) >= 2 and shape[-1] >= 128 and shape[-2] >= 128


def _rms(x):
    return jnp.sqrt(jnp.mean(x * x))


def adafactor_param(p, g, v, t, o):
    """One parameter: returns (new p, new second moment)."""
    beta2 = 1.0 - t ** (-o["decay_pow"])
    lr = o["lr"] * jnp.minimum(1.0, 10.0 / jnp.sqrt(t))
    g2 = g * g + o["eps1"]
    if _factored(p.shape):
        vr = beta2 * v["vr"] + (1 - beta2) * g2.mean(-1)
        vc = beta2 * v["vc"] + (1 - beta2) * g2.mean(-2)
        pre = (vr / jnp.maximum(vr.mean(-1, keepdims=True), o["eps1"]))[..., None] * vc[..., None, :]
        new_v = {"vr": vr, "vc": vc}
    else:
        pre = beta2 * v["v"] + (1 - beta2) * g2
        new_v = {"v": pre}
    u = g * jax.lax.rsqrt(pre + o["eps1"])
    u = u / jnp.maximum(1.0, _rms(u) / o["clip_threshold"])
    return p - lr * jnp.maximum(_rms(p), o["eps2"]) * u, new_v


def _zeros_v(shape, lead=()):
    """Second moments of one parameter of ``shape`` (``lead``: stacked)."""
    if _factored(shape):
        return {"vr": jnp.zeros(lead + shape[:-1]),
                "vc": jnp.zeros(lead + shape[:-2] + shape[-1:])}
    return {"v": jnp.zeros(lead + shape)}


def _flat(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flat(tree[k], f"{prefix}{k}/")
    else:
        yield prefix[:-1], tree


def _set(tree, path, value):
    parts = path.split("/")
    for p in parts[:-1]:
        tree = tree[p]
    tree[parts[-1]] = value


def train_steps(cfg: dict, params, batches: list, optimizer: dict,
                low: bool = False) -> dict:
    """Run ``len(batches)`` steps from ``params`` (float32).  Returns the
    loss of each step, each leaf's gradient norm at the first step, and
    each leaf's parameters after the last step (float32).  ``low``: the
    control (see the module's docstring)."""
    def store(p):  # parameters as the control holds them: bfloat16 values
        return jax.lax.reduce_precision(p, exponent_bits=8, mantissa_bits=7) if low else p

    params = jax.tree.map(store, params)
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(loss_fn, cfg=cfg, quant=low)))

    @functools.partial(jax.jit, static_argnames=("stacked",), donate_argnums=(0, 2))
    def update(p, g, v, t, stacked):
        fn = functools.partial(adafactor_param, t=t, o=optimizer)
        new_p, new_v = jax.vmap(fn)(p, g, v) if stacked else fn(p, g, v)
        return store(new_p), new_v

    @jax.jit
    def leaf_norms(g):
        return {k: jnp.sqrt(jnp.sum(x * x)) for k, x in _flat(g)}

    v = {path: (_zeros_v(x.shape[1:], x.shape[:1]) if path.startswith("layers/")
                else _zeros_v(x.shape))
         for path, x in _flat(params)}
    losses, grad_norms = [], None
    for i, b in enumerate(batches):
        loss, grads = grad_fn(params, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(x) for k, x in leaf_norms(grads).items()}
        t = jnp.float32(i + 1)
        flat_g = dict(_flat(grads))
        for path, p in list(_flat(params)):
            new_p, v[path] = update(p, flat_g[path], v[path], t, path.startswith("layers/"))
            _set(params, path, new_p)
        del grads, flat_g
    return {"losses": losses, "grad_norms": grad_norms, "params": params}
