"""The traffic generator: deterministic per seed, stated ranges, and the
same set of sizes for every seed."""
import numpy as np
import pytest

import tiny  # noqa: F401  (puts the harness on the path)
from harness import traffic as T

AGENT = {"kind": "serve", "slots": 8, "block": 16,
         "arrival": {"kind": "poisson", "rate_per_s": 1.5},
         "shared_prefix_tokens": 512, "prompt_tokens": [16, 64], "output_tokens": [8, 48]}
BACKLOG = {"kind": "serve", "slots": 8, "block": 16,
           "arrival": {"kind": "backlog", "requests": 48},
           "shared_prefix_tokens": 0, "prompt_tokens": [32, 256],
           "output_tokens": [256, 1024]}
BIG_SEED = 2**31 + 987654321


@pytest.mark.parametrize("mix", [AGENT, BACKLOG], ids=["poisson", "backlog"])
def test_serve_arrivals_are_deterministic_per_seed(mix):
    a = T.serve_arrivals(mix, BIG_SEED, 40.0, 151936)
    b = T.serve_arrivals(mix, BIG_SEED, 40.0, 151936)
    c = T.serve_arrivals(mix, BIG_SEED + 1, 40.0, 151936)
    key = lambda xs: [(x.due, x.max_new, x.prompt.tobytes()) for x in xs]  # noqa: E731
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("mix", [AGENT, BACKLOG], ids=["poisson", "backlog"])
def test_serve_arrivals_keep_their_ranges(mix):
    prefix = mix["shared_prefix_tokens"]
    for seed in (0, 7, BIG_SEED):
        xs = T.serve_arrivals(mix, seed, 40.0, 151936)
        lo, hi = mix["prompt_tokens"]
        assert all(lo <= len(x.prompt) - prefix <= hi for x in xs)
        lo, hi = mix["output_tokens"]
        assert all(lo <= x.max_new <= hi for x in xs)
        assert all(1 <= x.prompt.min() and x.prompt.max() < 151936 for x in xs)
        dues = [x.due for x in xs]
        assert dues == sorted(dues) and all(0 <= d < 40.0 for d in dues)


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    a = T.serve_arrivals(AGENT, 1, 40.0, 1000)
    b = T.serve_arrivals(AGENT, 2, 40.0, 1000)
    assert len(a) == len(b) == 60
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    gaps = lambda xs: sorted(np.round(np.diff([0.0] + [x.due for x in xs]), 9))  # noqa: E731
    assert gaps(a) == gaps(b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_shared_prefix_starts_every_prompt():
    xs = T.serve_arrivals(AGENT, 3, 10.0, 1000)
    prefix = T.shared_prefix(AGENT, 3, 1000)
    assert len(prefix) == 512
    assert all(np.array_equal(x.prompt[:512], prefix) for x in xs)


def test_backlog_first_wave_has_fixed_sizes():
    firsts = [sorted(x.max_new for x in T.serve_arrivals(BACKLOG, s, 40.0, 1000)[:8])
              for s in (1, 2, 3)]
    assert firsts[0] == firsts[1] == firsts[2]


def test_corpus_and_batches_match_the_pipeline_contract():
    from repro.core import Repository
    from repro.data import TokenPipeline
    from repro import fix

    mix = {"corpus_bytes": 1 << 16}
    text = T.corpus(mix, BIG_SEED)
    assert text == T.corpus(mix, BIG_SEED) and len(text) == 1 << 16
    assert text != T.corpus(mix, BIG_SEED + 1)
    repo = Repository("t")
    backend = fix.local(repo)
    try:
        pipe = TokenPipeline(repo, repo.put_blob(text), seq_len=64, batch=4, vocab=50277)
        for step in (0, 1, 5):
            got = pipe.batch_for_step(backend, step)
            want = T.train_batch(text, step, 4, 64, 50277)
            for k in ("tokens", "labels"):
                np.testing.assert_array_equal(np.asarray(got[k]), want[k])
    finally:
        backend.close()
