"""Operations and bytes of the Mamba2 family, from the configuration's
shapes (mamba_ssm's ``MambaConfig`` keys: ``d_model``, ``n_layer``,
``d_state`` ...).  Found by ``harness.counts.family``; like every count
there, these count the work the algorithm needs, not what today's
implementation does.
"""
from harness.counts import DTYPE_BYTES, vocab_padded


def dims(cfg: dict) -> dict:
    d = cfg["d_model"]
    din = cfg["expand"] * d
    return {"d": d, "din": din, "h": din // cfg["headdim"], "p": cfg["headdim"],
            "n": cfg["d_state"], "q": cfg["chunk_size"], "w": cfg["d_conv"],
            "L": cfg["n_layer"]}


def matmul_params(cfg: dict) -> dict:
    m = dims(cfg)
    per_layer = m["d"] * (2 * m["din"] + 2 * m["n"] + m["h"]) + m["din"] * m["d"]
    return {"layers": m["L"] * per_layer, "unembed": m["d"] * vocab_padded(cfg)}


def ssd_forward_flops(cfg: dict, batch: int, seq: int) -> int:
    """The chunked SSD's forward in one layer: causal halves within a chunk
    (``C_i . B_j``, then ``(CB * L) x``), and the chunk states in and out."""
    m = dims(cfg)
    q = min(m["q"], seq)
    chunks = batch * (-(-seq // q))
    pairs = q * (q + 1) // 2
    ssd_chunk = (2 * pairs * m["n"]                         # C_i . B_j
                 + 2 * pairs * m["h"] * m["p"]              # (CB * L) x
                 + 2 * q * m["h"] * m["p"] * m["n"] * 2)    # states in and out
    return chunks * ssd_chunk


def forward_flops(cfg: dict, batch: int, seq: int) -> int:
    """Forward of ``batch`` sequences of ``seq`` tokens: projections, the
    output head, the depthwise conv, and the chunked SSD."""
    m = dims(cfg)
    p = matmul_params(cfg)
    tokens = batch * seq
    per_layer = ssd_forward_flops(cfg, batch, seq) + tokens * 2 * m["w"] * m["din"]
    return 2 * tokens * (p["layers"] + p["unembed"]) + m["L"] * per_layer


def train_flops(cfg: dict, batch: int, seq: int) -> int:
    """Forward plus backward (twice the forward); recompute not counted."""
    return 3 * forward_flops(cfg, batch, seq)


def ssd_train_cost(cfg: dict, batch: int, seq: int) -> tuple:
    """(operations, bytes) of the chunked SSD in one training step, all
    layers.  Operations: the forward and the backward, counted as twice the
    forward; recompute not counted.  Bytes: the forward reads ``x``, ``dt``,
    ``B``, ``C`` and writes ``y``; the backward reads the same and ``dy``
    and writes ``dx``, ``ddt``, ``dB``, ``dC``.  ``x``, ``B``, ``C`` and
    their gradients are in the compute dtype, ``dt`` in float32 (the model
    takes its softplus in float32)."""
    m = dims(cfg)
    tokens = batch * seq
    act = DTYPE_BYTES[cfg["compute_dtype"]]
    x = tokens * m["din"] * act                               # x, y, dy, dx alike
    dt = tokens * m["h"] * 4                                  # dt, ddt alike
    bc = 2 * tokens * cfg.get("ngroups", 1) * m["n"] * act    # B and C, dB and dC
    forward = x + dt + bc + x
    backward = (x + dt + bc + x) + (x + dt + bc)
    return (3 * m["L"] * ssd_forward_flops(cfg, batch, seq),
            m["L"] * (forward + backward))
