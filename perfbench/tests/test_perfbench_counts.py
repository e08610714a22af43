"""Operation and byte counts from shapes, checked against hand-worked
numbers, each family's counts found by its name, and the peaks table."""
import json

import pytest

import tiny
from harness import counts as C
from harness import peaks as P
from harness.observed import Observed
from harness.spec import SpecError, load_module, reader_path

QWEN = json.loads((tiny.BENCH_DIR / "configs" / "qwen3_4b.json").read_text())
MAMBA = json.loads((tiny.BENCH_DIR / "configs" / "mamba2_780m.json").read_text())
MAMBA2 = C.family(MAMBA)


def test_qwen3_4b_weight_bytes():
    # layers: 36 x (2560*4096*2 + 2560*1024*2 + 3*2560*9728) matmul weights
    # plus 36 x (2*2560 + 2*128) norm weights; the embedding 151936*2560,
    # which is also the head (tied); final norm 2560; two bytes each
    layers = 36 * (2560 * 4096 * 2 + 2560 * 1024 * 2 + 3 * 2560 * 9728 + 2 * 2560 + 256)
    assert C.dense_weight_bytes(QWEN) == 2 * (layers + 151936 * 2560 + 2560)
    assert C.dense_weight_bytes(QWEN) == 8_044_936_192


def test_qwen3_4b_kv_bytes_per_position():
    assert C.kv_bytes_per_position(QWEN) == 2 * 36 * 8 * 128 * 2 == 147_456


def test_decode_call_counts_live_positions_only():
    f1, b1 = C.decode_call(QWEN, [99])
    f2, b2 = C.decode_call(QWEN, [99, 99])
    # one row at position 99 reads every weight once (the tied embedding as
    # the head), 100 positions, and writes one
    assert b1 == 8_044_936_192 + 2560 * 2 + 101 * 147_456 + 151936 * 2
    # a second row adds its own embedding row, cache and logits, no weights
    assert b2 - b1 == 2560 * 2 + 101 * 147_456 + 151936 * 2
    per_token = 2 * 36 * (2560 * 4096 * 2 + 2560 * 1024 * 2 + 3 * 2560 * 9728) \
        + 2 * 2560 * 151936 + 4 * 36 * 32 * 128 * 100
    assert f1 == per_token and f2 == 2 * per_token


def test_prefill_counts_the_head_once():
    assert C.prefill_flops(QWEN, 0, 16) == sum(
        C.dense_token_flops(QWEN, p, logits=(p == 15)) for p in range(16))


def test_mamba2_780m_training_flops():
    # per layer 1536*(2*3072 + 2*128 + 48) + 3072*1536 projection weights,
    # 48 layers; head 1536*50304
    per_layer = 1536 * (2 * 3072 + 2 * 128 + 48) + 3072 * 1536
    params = 48 * per_layer + 1536 * 50304
    assert MAMBA2.matmul_params(MAMBA)["layers"] == 48 * per_layer
    tokens = 4 * 2048
    pairs = 256 * 257 // 2
    ssd = 32 * (2 * pairs * 128 + 2 * pairs * 48 * 64 + 4 * 256 * 48 * 64 * 128)
    conv = tokens * 2 * 4 * 3072
    want = 3 * (2 * tokens * params + 48 * (ssd + conv))
    assert MAMBA2.train_flops(MAMBA, 4, 2048) == C.train_flops(MAMBA, 4, 2048) == want
    # about 6 N D with N = 0.78e9: 4.2e13 per step, plus the scan
    assert 4.0e13 < want < 5.0e13


def test_mamba2_780m_mfu_reads_as_before_its_counts_moved():
    """``train.mfu`` on the cell, bit for bit what it read while the count
    lived in ``harness/counts.py``."""
    assert C.train_flops(MAMBA, 4, 2048) == 41151709052928
    obs = Observed(cell="mamba2_780m.train_b4s2048", config=MAMBA,
                   traffic={"batch": 4, "seq": 2048}, peaks=P.peaks_for("TPU v5 lite"),
                   window_s=40.123456789, steps=77, profiler_s=0.25)
    mfu = load_module(reader_path(tiny.BENCH_DIR, "train.mfu"), "train.mfu").read(obs)
    assert mfu == 100.0 * 41151709052928 * 77 / (40.123456789 - 0.25) / 197e12


def test_mamba2_780m_ssd_train_cost():
    # per layer and chunk (Q 256, 48 heads of 64, state 128): C.B over the
    # 256*257/2 pairs, (CB*L) x, states in and out; 32 chunks, 48 layers,
    # forward plus a backward of twice the forward
    pairs = 256 * 257 // 2
    chunk = 2 * pairs * 128 + 2 * pairs * 48 * 64 + 4 * 256 * 48 * 64 * 128
    flops = 3 * 48 * 32 * chunk
    # x, y, dy, dx: 4*2048*3072 in bf16; dt, ddt: 4*2048*48 in float32;
    # B, C and dB, dC: 2 * 4*2048*128 in bf16
    x, dt, bc = 4 * 2048 * 3072 * 2, 4 * 2048 * 48 * 4, 2 * 4 * 2048 * 128 * 2
    nbytes = 48 * ((x + dt + bc + x) + (x + dt + bc + x) + (x + dt + bc))
    assert MAMBA2.ssd_train_cost(MAMBA, 4, 2048) == (flops, nbytes)
    assert (flops, nbytes) == (2_825_568_387_072, 12_910_067_712)
    p = P.peaks_for("TPU v5 lite")
    # bound by bytes: 15.8 ms against 14.3 ms of operations
    assert nbytes / p["hbm_bytes_per_s"] == pytest.approx(0.01576, abs=1e-5)
    assert flops / p["bf16_flops_per_s"] == pytest.approx(0.01434, abs=1e-5)


def test_a_family_with_no_counts_file_is_refused():
    with pytest.raises(SpecError):
        C.train_flops({"program_fixed": {"family": "no_such_family"}}, 4, 16)
    with pytest.raises(SpecError):
        C.family({"program_fixed": {"family": "../harness/counts"}})


def test_peaks_are_keyed_by_device_kind():
    p = P.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        P.peaks_for("TPU v99")


def test_least_seconds_takes_the_larger_bound():
    p = P.peaks_for("TPU v5 lite")
    assert P.least_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    assert P.least_seconds(1.0, 819e9 * 2, p) == pytest.approx(2.0)
