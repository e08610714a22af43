"""Operations and bytes of the algorithm, from the configuration's shapes.

These count the work a call needs, not what today's implementation does:
a decode step reads every weight once and the keys and values of the live
positions only, whatever size of cache the program holds.  Configuration
keys are the source's (``hidden_size``, ``num_hidden_layers`` ...).
"""
from __future__ import annotations

from .spec import BENCH_DIR, SpecError, load_module

#: Bytes of each compute dtype a configuration can state.
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def vocab_padded(cfg: dict) -> int:
    return -(-int(cfg["vocab_size"]) // 128) * 128


# ------------------------------------------------------------------ dense
def dense_layer_matmul_params(cfg: dict) -> int:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * ff


def dense_param_count(cfg: dict) -> dict:
    """Parameters by part: layers (matmuls + norms), embed, unembed, norm."""
    L, d, hd = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["head_dim"]
    vp = vocab_padded(cfg)
    norms = 2 * d + (2 * hd if cfg.get("qk_norm", True) else 0)
    return {"layers": L * (dense_layer_matmul_params(cfg) + norms),
            "layer_matmuls": L * dense_layer_matmul_params(cfg),
            "embed": vp * d,
            "unembed": 0 if cfg["tie_word_embeddings"] else vp * d,
            "final_norm": d}


def dense_weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    return sum(v for k, v in dense_param_count(cfg).items()
               if k != "layer_matmuls") * dtype_bytes


def kv_bytes_per_position(cfg: dict, dtype_bytes: int = 2) -> int:
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"] * dtype_bytes


def _attn_flops(cfg: dict, keys: int) -> int:
    """q.k and p.v for one query over ``keys`` positions, all layers."""
    return 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def dense_token_flops(cfg: dict, position: int, logits: bool = True) -> int:
    """One token at ``position`` (0-based), with the output head if
    ``logits``: matmuls, attention over ``position + 1`` keys."""
    d = cfg["hidden_size"]
    head = 2 * d * vocab_padded(cfg) if logits else 0
    return 2 * dense_layer_matmul_params(cfg) * cfg["num_hidden_layers"] + head \
        + _attn_flops(cfg, position + 1)


def decode_call(cfg: dict, positions: list, dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) of one decode call over rows at ``positions``: each
    row writes its token's keys and values at its position and attends
    over positions 0..pos.  Weights are read once per call; the embedding
    table only for the rows' own tokens."""
    pc = dense_param_count(cfg)
    d, vp = cfg["hidden_size"], vocab_padded(cfg)
    rows = len(positions)
    head = pc["unembed"] or pc["embed"]
    weights = (pc["layers"] + head + pc["final_norm"]) * dtype_bytes
    kv = kv_bytes_per_position(cfg, dtype_bytes)
    nbytes = (weights + rows * d * dtype_bytes               # embedding rows
              + sum((p + 1) * kv for p in positions)      # keys/values read
              + rows * kv                                  # keys/values written
              + rows * vp * dtype_bytes)                   # logits
    flops = sum(dense_token_flops(cfg, p) for p in positions)
    return flops, nbytes


def prefill_flops(cfg: dict, start: int, n: int) -> int:
    """Tokens start..start+n-1 of a prompt, the head on the last only."""
    return sum(dense_token_flops(cfg, start + i, logits=(i == n - 1)) for i in range(n))


# --------------------------------------------------------------- families
def family(cfg: dict):
    """The counts of the configuration's family (``program_fixed.family``):
    the module ``families/<family>.py``, found by that name, so that a new
    family brings its counts as a file of its own.  Each gives
    ``train_flops(cfg, batch, seq)`` and may give counts of its kernels."""
    name = str(cfg["program_fixed"]["family"])
    if not name.isidentifier():
        raise SpecError(f"no counts for family {name!r}")
    return load_module(BENCH_DIR / "families" / f"{name}.py", f"family_{name}")


def train_flops(cfg: dict, batch: int, seq: int) -> int:
    """One training step of the configuration's family: forward plus
    backward, recompute not counted."""
    return family(cfg).train_flops(cfg, batch, seq)
