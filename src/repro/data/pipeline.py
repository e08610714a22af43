"""Content-addressed data pipeline: shards as Fix thunks.

A training corpus is a content-addressed Blob; shards are *derived values*
— ``slice_blob(corpus, offset, len)`` Application Thunks — so a shard's
identity is its recipe, not its bytes.  Consequences the trainer exploits:

* **Recompute-over-transfer** (paper §1's sixth strategy, §6 computational
  GC): a lost shard is re-derived from its thunk instead of re-fetched; the
  Fixpoint cluster does this automatically through lineage.
* **Deterministic global order**: shard k of epoch e is a pure function of
  (corpus hash, k, e) — any worker can re-produce any other worker's batch,
  which is what makes elastic rescale and straggler duplication exact.

Tokenization is byte-level (deterministic, dependency-free); real
deployments would register their tokenizer as another codelet.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass

import numpy as np

from ..core import Handle, Repository
from ..core.stdlib import slice_blob
from ..fix import Backend, Lazy
from ..obs.spans import Span

BATCH = Span("repro.data.batch")


def synth_corpus(n_bytes: int, seed: int = 0) -> bytes:
    """Deterministic synthetic corpus (zipf-ish byte text)."""
    rng = np.random.default_rng(seed)
    words = [bytes(rng.integers(97, 123, rng.integers(2, 9)).astype(np.uint8))
             for _ in range(512)]
    probs = 1.0 / np.arange(1, 513)
    probs /= probs.sum()
    out = bytearray()
    idx = rng.choice(512, size=n_bytes // 5 + 16, p=probs)
    for i in idx:
        out += words[i] + b" "
        if len(out) >= n_bytes:
            break
    return bytes(out[:n_bytes])


@dataclass
class TokenPipeline:
    """Byte-level LM batches derived from a content-addressed corpus."""

    repo: Repository
    corpus: Handle
    seq_len: int
    batch: int
    vocab: int = 256

    def shard_expr(self, step: int) -> Lazy:
        """The Fix recipe for step ``step``'s bytes (pure function), as a
        typed frontend expression — submit it to any Backend."""
        need = self.batch * (self.seq_len + 1)
        total = self.corpus.size
        offset = (step * need) % max(total - need, 1)
        return slice_blob(self.corpus, offset, need)

    def shard_thunk(self, step: int) -> Handle:
        """The recipe compiled to its Table-1 Application Thunk handle
        (byte-identical to the hand-built ``combination`` tree)."""
        return self.shard_expr(step).compile(self.repo)

    def materialize(self, shard_bytes: bytes):
        """bytes -> {tokens, labels} int32 arrays (numpy; cast on device)."""
        need = self.batch * (self.seq_len + 1)
        arr = np.frombuffer(shard_bytes[:need], dtype=np.uint8).astype(np.int32)
        arr = arr % self.vocab
        arr = arr.reshape(self.batch, self.seq_len + 1)
        return {"tokens": arr[:, :-1], "labels": arr[:, 1:]}

    def batch_for_step(self, engine, step: int):
        """Shard bytes -> arrays via a Backend or a bare local Evaluator,
        inside a ``repro.data.batch`` span."""
        with BATCH(step=step):
            if isinstance(engine, Backend):
                return self.materialize(engine.fetch(self.shard_expr(step),
                                                     as_type=bytes))
            th = self.shard_thunk(step)
            out = engine.evaluate(th.strict())
            return self.materialize(self.repo.get_blob(out))


def corpus_handle(repo: Repository, n_bytes: int = 1 << 20, seed: int = 0) -> Handle:
    return repo.put_blob(synth_corpus(n_bytes, seed))
