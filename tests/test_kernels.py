"""Pallas kernel sweeps: interpret-mode execution vs pure-jnp oracles
across shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm as rmsnorm_kernel
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels import ops as kops
from repro.kernels.ref import (
    decode_attention_ref,
    flash_attention_ref,
    rmsnorm_ref,
    ssd_scan_ref,
)


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("B,S,H,hd", [(1, 128, 1, 64), (2, 256, 2, 64),
                                      (1, 512, 4, 128), (2, 128, 2, 32),
                                      (1, 100, 2, 64)])  # not a block multiple
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, hd, dtype, causal):
    q = _rand(0, (B, S, H, hd), dtype)
    k = _rand(1, (B, S, H, hd), dtype)
    v = _rand(2, (B, S, H, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_asymmetric_v_dim():
    """MLA: v head dim != q/k head dim."""
    q = _rand(0, (1, 128, 2, 64), jnp.float32)
    k = _rand(1, (1, 128, 2, 64), jnp.float32)
    v = _rand(2, (1, 128, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_blocked_attention_matches_ref():
    """The jnp twin used by the dry-run must match the oracle too."""
    q = _rand(0, (2, 256, 2, 64), jnp.float32)
    k = _rand(1, (2, 256, 2, 64), jnp.float32)
    v = _rand(2, (2, 256, 2, 64), jnp.float32)
    for causal in (True, False):
        out = kops.blocked_attention(q, k, v, causal=causal, block_k=96)
        ref = flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,T,H,hd,length", [(1, 256, 2, 64, 100),
                                             (2, 512, 1, 128, 512),
                                             (2, 128, 4, 32, 1)])
def test_decode_attention_sweep(B, T, H, hd, length):
    q = _rand(0, (B, 1, H, hd), jnp.float32)
    k = _rand(1, (B, T, H, hd), jnp.float32)
    v = _rand(2, (B, T, H, hd), jnp.float32)
    out = decode_attention(q, k, v, length, block_k=64, interpret=True)
    ref = decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [(4, 64), (2, 8, 128), (3, 5, 7, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = _rand(0, shape, dtype)
    w = 1.0 + 0.1 * _rand(1, shape[-1:], jnp.float32)
    out = rmsnorm_kernel(x, w, block_rows=8, interpret=True)
    ref = rmsnorm_ref(x, w)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def _ssd_inputs(B, S, H, P, N, unit_decay=False):
    x = _rand(0, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(1, (B, S, H), jnp.float32))
    A = -jnp.ones((H,)) if unit_decay else -jnp.exp(_rand(2, (H,), jnp.float32) * 0.3)
    B_ = _rand(3, (B, S, N), jnp.float32) * 0.5
    C_ = _rand(4, (B, S, N), jnp.float32) * 0.5
    return x, dt, A, B_, C_


# (B, S, H, P, N, chunk, heads per block, unit decay): S a multiple of the
# chunk and not; N 64 and 128; 2 and 3 head blocks; lane groups of one, two
# and four heads; the default blocks (16 heads forward, 8 backward); one full
# 256-step chunk with unit decay, whose masked triangle overflows exp unless
# it is masked first
@pytest.mark.parametrize("B,S,H,P,N,chunk,hb,unit", [
    (1, 256, 4, 16, 64, 128, 2, False),
    (2, 200, 6, 16, 128, 128, 2, False),
    (1, 300, 4, 32, 128, 128, 2, False),
    (1, 128, 6, 128, 64, 128, 3, False),
    (1, 128, 16, 64, 64, 128, None, False),
    (1, 256, 8, 32, 64, 256, 4, True),
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk, hb, unit):
    """The fused SSD (interpret mode) against the sequential recurrence,
    and its gradients against the jnp chunked SSD's."""
    from repro.models.mamba2 import ssd_jnp

    args = _ssd_inputs(B, S, H, P, N, unit)
    fused = jax.jit(lambda *a: ssd_scan(*a, chunk, hb=hb, interpret=True))
    y, state = fused(*args)
    y_ref, state_ref = ssd_scan_ref(*args)
    np.testing.assert_allclose(y, y_ref, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(state, state_ref, atol=5e-4, rtol=5e-4)

    w = _rand(5, (B, S, H, P), jnp.float32)
    v = _rand(6, (B, H, P, N), jnp.float32)

    def grads(f):
        def loss(*a):
            y, s = f(*a)
            return jnp.sum(y * w) + jnp.sum(s * v)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)

    got = grads(lambda *a: ssd_scan(*a, chunk, hb=hb, interpret=True))
    want = grads(lambda *a: ssd_jnp(*a, chunk))
    # both float32 paths lie up to ~3e-5 of the largest entry from a float64
    # reference (dA, a sum over every step, the furthest)
    for name, g, r in zip(("x", "dt", "A", "B", "C"), got, want):
        assert bool(jnp.isfinite(g).all()), name
        scale = float(jnp.abs(r).max())
        np.testing.assert_allclose(g, r, atol=1e-4 * scale, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("backend,devices,path", [("tpu", 1, "fused"),
                                                  ("tpu", 4, "jnp"),
                                                  ("cpu", 1, "jnp")])
def test_ssd_path_rule(backend, devices, path):
    """The fused SSD where it can run: a TPU, inputs on one device."""
    from repro.models.mamba2 import ssd_path

    assert ssd_path(backend, devices) == path


def test_ssd_chunked_model_path_matches_oracle():
    """models.mamba2.ssd_chunked (the jnp path the dry-run lowers) vs the
    sequential recurrence."""
    from repro.models.mamba2 import ssd_chunked

    B, S, H, P, N = 2, 80, 2, 16, 24
    x = _rand(0, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(1, (B, S, H), jnp.float32))
    A = -jnp.exp(_rand(2, (H,), jnp.float32) * 0.3)
    B_ = _rand(3, (B, S, N), jnp.float32) * 0.5
    C_ = _rand(4, (B, S, N), jnp.float32) * 0.5
    y, state = ssd_chunked(x, dt, A, B_, C_, chunk=32)
    y_ref, state_ref = ssd_scan_ref(x, dt, A, B_, C_)
    np.testing.assert_allclose(y, y_ref, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(state, state_ref, atol=5e-4, rtol=5e-4)


def test_ssd_chunked_gradients_finite_over_a_full_chunk():
    """A 256-step chunk with unit decay: cum_i - cum_j above the diagonal
    overflows exp, and the masked entries must not turn gradients NaN."""
    from repro.models.mamba2 import ssd_chunked

    B, S, H, P, N = 1, 256, 2, 8, 8
    x = _rand(0, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(1, (B, S, H), jnp.float32))
    A = -jnp.ones((H,), jnp.float32)
    B_ = _rand(3, (B, S, N), jnp.float32)
    C_ = _rand(4, (B, S, N), jnp.float32)
    grads = jax.grad(lambda x, dt: ssd_chunked(x, dt, A, B_, C_, 256)[0].sum(),
                     argnums=(0, 1))(x, dt)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
