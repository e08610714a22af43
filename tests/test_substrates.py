"""Substrate tests: data pipeline, checkpointing, serving, optimizers,
gradient compression, elastic restore."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import dedup_stats, load_step, save_step
from repro.core import Evaluator, Repository
from repro.data import TokenPipeline, corpus_handle
from repro.models import ModelConfig, init_params, ops_for
from repro.optim import adafactor, adamw
from repro.optim.compress import ef_int8_allreduce
from repro.serving import PrefixCache, Request, ServeEngine, prompt_key

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=256,
                  param_dtype=jnp.float32, compute_dtype=jnp.float32)


# ---------------------------------------------------------------- data
class TestDataPipeline:
    def test_deterministic_batches(self):
        repo = Repository()
        ev = Evaluator(repo)
        corpus = corpus_handle(repo, 1 << 16)
        pipe = TokenPipeline(repo, corpus, seq_len=32, batch=4, vocab=256)
        b1 = pipe.batch_for_step(ev, 3)
        b2 = pipe.batch_for_step(ev, 3)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        # labels are next-token shifted
        np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])

    def test_shard_is_recomputable(self):
        """The shard thunk re-derives identical bytes in a fresh repo."""
        r1, r2 = Repository(), Repository()
        c1 = corpus_handle(r1, 1 << 14)
        c2 = corpus_handle(r2, 1 << 14)
        assert c1 == c2  # same seed => same corpus hash
        p1 = TokenPipeline(r1, c1, 16, 2)
        p2 = TokenPipeline(r2, c2, 16, 2)
        o1 = Evaluator(r1).evaluate(p1.shard_thunk(5).strict())
        o2 = Evaluator(r2).evaluate(p2.shard_thunk(5).strict())
        assert o1.content_key() == o2.content_key()


# ------------------------------------------------------------ checkpoint
class TestCheckpoint:
    def test_save_load_roundtrip_and_dedup(self):
        repo = Repository()
        ops = ops_for(CFG)
        params = init_params(ops.specs(CFG), CFG)
        state = {"params": params, "opt": {"step": jnp.zeros((), jnp.int32)}}
        r1 = save_step(repo, state, 1)
        # mutate one leaf only
        state2 = jax.tree.map(lambda x: x, state)
        state2["params"]["final_norm"] = state["params"]["final_norm"] + 1
        r2 = save_step(repo, state2, 2)
        meta, back = load_step(repo, r2)
        assert meta["step"] == 2
        np.testing.assert_allclose(back["params"]["final_norm"],
                                   np.asarray(state2["params"]["final_norm"]))
        stats = dedup_stats(repo, [r1, r2])
        assert stats["unique_leaves"] < stats["leaf_refs"]  # dedup happened

    def test_elastic_restore_reshards(self):
        """Restore onto a different mesh: arrays go to new shardings."""
        import os

        repo = Repository()
        ops = ops_for(CFG)
        params = init_params(ops.specs(CFG), CFG)
        root = save_step(repo, {"params": params}, 7)
        meta, back = load_step(repo, root)  # host "mesh"
        assert meta["step"] == 7
        for path in (("params", "embed"), ("params", "final_norm")):
            a = back
            b = {"params": params}
            for k in path:
                a, b = a[k], b[k]
            np.testing.assert_array_equal(a, np.asarray(b))


# --------------------------------------------------------------- serving
class TestServing:
    def test_engine_continuous_batching(self):
        # toy "model" in the batched contracts: state = last token seen;
        # next token = (last + 1) % 7 (eos -1 never fires)
        def prefill(tokens, state=None):
            return int(tokens[-1])

        def decode(states, tokens):
            logits = np.zeros((len(states), 1, 8), np.float32)
            out = []
            for b, last in enumerate(tokens[:, 0]):
                nxt = (int(last) + 1) % 7
                logits[b, 0, nxt] = 1.0
                out.append(nxt)
            return logits, out

        eng = ServeEngine(prefill, decode, batch=2, eos=-1, block=16)
        reqs = [Request(rid=i, prompt=np.asarray([i, i + 1], np.int32), max_new=5)
                for i in range(5)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done and len(r.out_tokens) == 5 for r in reqs)
        # batch never exceeded 2 live rows: steps >= ceil(5*5/2)
        assert eng.steps >= 13

    def test_prefix_cache_block_identity(self):
        a = np.arange(64, dtype=np.int32)
        b = np.concatenate([np.arange(32, dtype=np.int32),
                            np.arange(100, 132, dtype=np.int32)])
        ka, kb = prompt_key(a, block=16), prompt_key(b, block=16)
        assert ka[0] == kb[0] and ka[1] == kb[1]  # shared 32-token prefix
        assert ka[2] != kb[2]
        cache = PrefixCache(4)
        # states are per-boundary: a 4-block insert without its ancestors
        # would dangle (the seed engine cached one whole-prompt state here,
        # which a shorter lookup then wrongly resumed from) — refused now
        assert not cache.insert(ka, "state-a3")
        for j in range(4):
            assert cache.insert(ka[: j + 1], f"state-a{j}")
        n, st = cache.lookup(kb)
        assert n == 2 and st == "state-a1"  # the state of exactly 2 blocks


# -------------------------------------------------------------- optimizers
class TestOptimizers:
    def _quad_problem(self):
        params = {"w": jnp.asarray([3.0, -2.0, 1.0])}
        grads_fn = lambda p: {"w": 2 * p["w"]}
        return params, grads_fn

    def test_adamw_converges(self):
        params, grads_fn = self._quad_problem()
        ocfg = adamw.AdamWConfig(lr=0.1, warmup_steps=1, weight_decay=0.0)
        specs = {"w": __import__("repro.models.base", fromlist=["ps"]).ps(
            (3,), ("p_none",))}
        state = {"mu": {"w": jnp.zeros(3)}, "nu": {"w": jnp.zeros(3)},
                 "step": jnp.zeros((), jnp.int32)}
        for _ in range(200):
            params, state, _ = adamw.apply_updates(params, grads_fn(params),
                                                   state, ocfg)
        assert float(jnp.abs(params["w"]).max()) < 0.1

    def test_adafactor_converges_and_is_factored(self):
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (128, 256)) * 3}
        specs = {"w": __import__("repro.models.base", fromlist=["ps"]).ps(
            (128, 256), ("p_none", "p_none"))}
        st_specs = adafactor.state_specs(specs, adafactor.AdafactorConfig())
        assert "vr" in st_specs["v"]["w"]  # factored: O(R+C) not O(RC)
        state = {"v": {"w": {"vr": jnp.zeros(128), "vc": jnp.zeros(256)}},
                 "step": jnp.zeros((), jnp.int32)}
        ocfg = adafactor.AdafactorConfig(lr=0.05)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state, _ = adafactor.apply_updates(params, grads, state, ocfg)
        assert float(jnp.abs(params["w"]).mean()) < 0.05

    def test_ef_int8_compression_bounded_error(self):
        """Single-host simulation of the 2-pod EF-int8 all-reduce."""
        mesh = jax.make_mesh((1,), ("pod",), (jax.sharding.AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 0.01
        err = jnp.zeros_like(g)

        def run(g, err):
            return ef_int8_allreduce(g, err, "pod", 1)

        from jax.sharding import PartitionSpec as P

        out, new_err = jax.jit(jax.shard_map(run, mesh=mesh,
                                             in_specs=(P(), P()),
                                             out_specs=(P(), P()),
                                             check_vma=False))(g, err)
        # quantization error bounded by scale/2, and error feedback captures it
        scale = float(jnp.abs(g).max()) / 127
        assert float(jnp.abs(out - g).max()) <= scale
        np.testing.assert_allclose(np.asarray(out + new_err),
                                   np.asarray(g), atol=1e-6)
