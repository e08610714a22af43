"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step on CPU, shape + finiteness assertions (deliverable f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import ce_loss, concrete_batch, init_params, loss_mask, ops_for
from repro.parallel import Sharder
from repro.parallel.steps import RunConfig, build_train_step

SH = Sharder(None)
B, S = 2, 16


def _smoke_cfg(arch):
    cfg = get_config(arch, smoke=True)
    # f32 end-to-end on CPU for numeric checks
    return cfg.__class__(**{**cfg.__dict__, "param_dtype": jnp.float32,
                            "compute_dtype": jnp.float32})


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = _smoke_cfg(arch)
    ops = ops_for(cfg)
    params = init_params(ops.specs(cfg), cfg)
    batch = concrete_batch(cfg, "train", B, S)
    out = ops.forward(params, batch, cfg, SH)
    if isinstance(out, tuple):
        out = out[0]
    assert out.shape[0] == B and out.shape[-1] == cfg.vocab_padded
    assert bool(jnp.isfinite(out).all()), f"{arch}: non-finite logits"


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_improves_loss(arch):
    cfg = _smoke_cfg(arch)
    runcfg = RunConfig(microbatches=1, remat="none",
                       optimizer="adafactor" if cfg.n_experts else "adamw")
    step_fn, *_ = build_train_step(cfg, runcfg, None)
    from repro.launch.train import init_state

    state = init_state(cfg, runcfg)
    batch = {k: np.asarray(v) for k, v in concrete_batch(cfg, "train", B, S).items()}
    losses = []
    for _ in range(3):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1]), f"{arch}: loss diverged"
    assert losses[-1] < losses[0], f"{arch}: loss did not improve {losses}"


@pytest.mark.parametrize("arch", [a for a in ARCHS if a != "internvl2_26b"])
def test_decode_step_runs(arch):
    cfg = _smoke_cfg(arch)
    ops = ops_for(cfg)
    if ops.decode_step is None:
        pytest.skip("family has no decode step")
    params = init_params(ops.specs(cfg), cfg)
    cache = init_params(ops.cache_specs(cfg, B, S), cfg)
    tok = jnp.ones((B, 1), jnp.int32)
    logits, cache2 = ops.decode_step(params, cache, tok, cfg, SH)
    assert logits.shape == (B, 1, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all())
    logits3, _ = ops.decode_step(params, cache2, tok, cfg, SH)
    assert bool(jnp.isfinite(logits3).all())


@pytest.mark.parametrize("prompt_len", [12, 21])
def test_dense_decode_matches_full_forward(prompt_len):
    """Prefill then greedy decode through the serving path gives, at every
    position, the logits of one full forward over the same tokens: decode
    writes each new token into a cache slot of its own."""
    from repro.launch.serve import build_model_fns, greedy_stream, serving_params

    cfg = _smoke_cfg("qwen3_4b")
    n_new = 6
    params = serving_params(cfg)
    prefill_fn, decode_fn = build_model_fns(cfg, params, prompt_len + n_new)
    prompt = np.random.default_rng(0).integers(1, cfg.vocab, prompt_len)
    tokens, logits = greedy_stream(prefill_fn, decode_fn, prompt, n_new, block=8)
    ref = ops_for(cfg).forward(params, {"tokens": jnp.asarray(tokens)[None]},
                               cfg, SH)[0, prompt_len - 1:]
    np.testing.assert_allclose(logits, np.asarray(ref), atol=1e-4, rtol=1e-4)
    assert (logits.argmax(-1) == np.asarray(ref).argmax(-1)).all()


def test_decode_past_the_cache_raises():
    """A token past prompt plus budget raises instead of clamping onto the
    cache's last slot."""
    from repro.launch.serve import build_model_fns, greedy_stream, serving_params

    cfg = _smoke_cfg("qwen3_4b")
    prefill_fn, decode_fn = build_model_fns(cfg, serving_params(cfg), 12)
    prompt = np.arange(1, 12)          # 11 tokens, then 2 fit
    greedy_stream(prefill_fn, decode_fn, prompt, 2, block=8)
    with pytest.raises(ValueError, match="overflow"):
        greedy_stream(prefill_fn, decode_fn, prompt, 3, block=8)


def test_full_configs_match_assignment():
    """The full-scale configs carry the exact assigned hyperparameters."""
    expect = {
        "qwen3_8b": dict(n_layers=36, d_model=4096, n_heads=32, n_kv_heads=8,
                         d_ff=12288, vocab=151936, qk_norm=True),
        "deepseek_67b": dict(n_layers=95, d_model=8192, n_heads=64,
                             n_kv_heads=8, d_ff=22016, vocab=102400),
        "internlm2_20b": dict(n_layers=48, d_model=6144, n_heads=48,
                              n_kv_heads=8, d_ff=16384, vocab=92544),
        "qwen3_4b": dict(n_layers=36, d_model=2560, n_heads=32, n_kv_heads=8,
                         d_ff=9728, vocab=151936, qk_norm=True),
        "deepseek_v3_671b": dict(n_layers=61, d_model=7168, n_heads=128,
                                 d_ff=2048, vocab=129280, n_experts=256,
                                 top_k=8, mla=True),
        "arctic_480b": dict(n_layers=35, d_model=7168, n_heads=56,
                            n_kv_heads=8, d_ff=4864, vocab=32000,
                            n_experts=128, top_k=2, dense_residual=True),
        "seamless_m4t_medium": dict(d_model=1024, n_heads=16, d_ff=4096,
                                    vocab=256206, n_enc_layers=12,
                                    n_dec_layers=12),
        "mamba2_780m": dict(n_layers=48, d_model=1536, vocab=50280,
                            ssm_state=128),
        "internvl2_26b": dict(n_layers=48, d_model=6144, n_heads=48,
                              n_kv_heads=8, d_ff=16384, vocab=92553,
                              n_patches=1024),
        "zamba2_7b": dict(n_layers=81, d_model=3584, n_heads=32,
                          n_kv_heads=32, d_ff=14336, vocab=32000,
                          ssm_state=64),
    }
    for arch, fields in expect.items():
        cfg = get_config(arch)
        for k, v in fields.items():
            assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_vlm_loss_mask_excludes_patches():
    cfg = _smoke_cfg("internvl2_26b")
    labels = jnp.zeros((2, 16), jnp.int32)
    mask = loss_mask(cfg, labels)
    assert mask is not None
    assert float(mask[:, : cfg.n_patches].sum()) == 0.0
    assert float(mask[:, cfg.n_patches:].sum()) == 2 * (16 - cfg.n_patches)
