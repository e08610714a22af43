"""The one traffic generator: a mix file of parameters in, inputs out.

Sizes and gaps are stratified: for ``n`` draws the generator takes the
distribution's quantiles at (i + 0.5) / n and the seed only permutes them
(and picks the token ids).  So every seed gets the same set of sizes and
arrivals in another order, and the work of a run does not swing with the
seed.

Serving mixes (``"kind": "serve"``):

* ``arrival``: ``{"kind": "poisson", "rate_per_s": r}`` (open loop, the
  requests due in a window of ``seconds``) or ``{"kind": "backlog",
  "requests": n}`` (all due at the window's start; the first ``slots`` are
  admitted during set-up);
* ``shared_prefix_tokens``: one prefix every prompt starts with (0: none);
* ``prompt_tokens`` / ``output_tokens``: ``[min, max]``, log-uniform.

Training mixes (``"kind": "train"``) give ``batch``, ``seq`` and
``corpus_bytes``; the corpus is byte text made from the seed.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Arrival:
    rid: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32
    max_new: int


def loguniform_quantiles(n: int, lo: int, hi: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    x = np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
    return np.clip(np.rint(x).astype(np.int64), lo, hi)


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed))


def shared_prefix(mix: dict, seed: int, vocab: int) -> np.ndarray:
    n = int(mix.get("shared_prefix_tokens", 0))
    rng = np.random.default_rng([int(seed), 1])
    return rng.integers(1, vocab, n).astype(np.int32)


def _lengths(rng, n: int, bounds) -> np.ndarray:
    return rng.permutation(loguniform_quantiles(n, int(bounds[0]), int(bounds[1])))


def serve_arrivals(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one run, in order of due time."""
    rng = rng_for(seed)
    prefix = shared_prefix(mix, seed, vocab)
    arrival = mix["arrival"]
    if arrival["kind"] == "poisson":
        rate = float(arrival["rate_per_s"])
        n = max(1, int(rate * seconds))
        gaps = rng.permutation(exponential_quantiles(n, rate))
        dues = np.cumsum(gaps)
        dues *= seconds * (n - 0.5) / n / dues[-1]
        prompts = _lengths(rng, n, mix["prompt_tokens"])
        outs = _lengths(rng, n, mix["output_tokens"])
    elif arrival["kind"] == "backlog":
        n = int(arrival["requests"])
        first = int(mix["slots"])
        dues = np.zeros(n)
        prompts = np.concatenate([_lengths(rng, first, mix["prompt_tokens"]),
                                  _lengths(rng, n - first, mix["prompt_tokens"])])
        outs = np.concatenate([_lengths(rng, first, mix["output_tokens"]),
                               _lengths(rng, n - first, mix["output_tokens"])])
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    out = []
    for i in range(n):
        tail = rng.integers(1, vocab, int(prompts[i])).astype(np.int32)
        out.append(Arrival(i, float(dues[i]), np.concatenate([prefix, tail]),
                           int(outs[i])))
    return out


def corpus(mix: dict, seed: int) -> bytes:
    """Byte text: words of 2-8 lowercase letters, Zipf-distributed."""
    rng = rng_for(seed)
    n_bytes = int(mix["corpus_bytes"])
    lens = rng.integers(2, 9, 512)
    words = [bytes(rng.integers(97, 123, k).astype(np.uint8)) + b" " for k in lens]
    p = 1.0 / np.arange(1, 513)
    idx = rng.choice(512, size=n_bytes // 3 + 16, p=p / p.sum())
    text = b"".join(words[i] for i in idx)
    if len(text) < n_bytes:
        raise ValueError("corpus came out short")
    return text[:n_bytes]


def train_batch(text: bytes, step: int, batch: int, seq: int, vocab: int) -> dict:
    """Step ``step``'s batch as the data pipeline's contract defines it:
    consecutive ``batch * (seq + 1)`` bytes from offset ``step * need``
    modulo ``len - need``, byte ids modulo the vocabulary, labels shifted."""
    need = batch * (seq + 1)
    offset = (step * need) % max(len(text) - need, 1)
    arr = np.frombuffer(text[offset: offset + need], np.uint8).astype(np.int32)
    arr = (arr % vocab).reshape(batch, seq + 1)
    return {"tokens": arr[:, :-1], "labels": arr[:, 1:]}
