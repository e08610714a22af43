"""The main path's Pallas kernels compile for a described TPU v5e.

Interpret mode (``tests/test_kernels.py``) checks what the kernels compute;
it cannot see what the chip's compiler refuses: tiling rules, primitives
Mosaic cannot lower, more fast memory than a kernel may use.  Here each
kernel is compiled at the real widths of the configurations the chip runs
(qwen3-4b attention and norm; the SSD, forward and backward, of
mamba2-780m and zamba2-7b) for a v5e that is described and not attached,
so a refusal fails a test with no chip time spent.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def _ssd_shapes(H: int, N: int, B: int = 4, S: int = 2048, P: int = 64) -> list:
    return [((B, S, H, P), BF16), ((B, S, H), F32), ((H,), F32),
            ((B, S, N), BF16), ((B, S, N), BF16)]


def _ssd_grad(x, dt, A, B_, C_, chunk):
    """The fused SSD's forward and backward kernels, as training runs them."""
    def loss(*args):
        y, state = ssd_scan(*args, chunk)
        return jnp.sum(y.astype(F32)) + jnp.sum(state)
    return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, dt, A, B_, C_)


# name -> (kernel, argument shapes, static keyword arguments)
CASES = {
    # qwen3-4b: 32 query heads of 128, a 4k prompt
    "flash_attention": (flash_attention,
                        [((1, 4096, 32, 128), BF16)] * 3, {"causal": True}),
    # a prompt length that is not a multiple of the block
    "flash_attention_ragged": (flash_attention,
                               [((1, 4100, 32, 128), BF16)] * 3, {"causal": True}),
    "decode_attention": (decode_attention,
                         [((4, 1, 32, 128), BF16), ((4, 4096, 32, 128), BF16),
                          ((4, 4096, 32, 128), BF16), ((), I32)], {}),
    "rmsnorm": (rmsnorm, [((4096, 2560), BF16), ((2560,), F32)], {}),
    # the fused SSD, forward and forward+backward, at training widths:
    # mamba2-780m (4 x 2048, 48 heads of 64, state 128) and zamba2-7b (112
    # heads of 64, state 64), chunk 256
    "ssd_mamba2_780m": (ssd_scan, _ssd_shapes(48, 128), {"chunk": 256}),
    "ssd_mamba2_780m_grad": (_ssd_grad, _ssd_shapes(48, 128), {"chunk": 256}),
    "ssd_zamba2_7b": (ssd_scan, _ssd_shapes(112, 64), {"chunk": 256}),
    "ssd_zamba2_7b_grad": (_ssd_grad, _ssd_shapes(112, 64), {"chunk": 256}),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    kernel, shapes, kwargs = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(lambda *a: kernel(*a, **kwargs)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Mosaic kernel in the compiled program"
