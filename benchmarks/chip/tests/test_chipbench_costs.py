"""Operation and byte counts of the chip benchmark, against hand counts at
the cells' shapes."""
import os
import sys

import pytest

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))]

from benchmarks.chip import loader  # noqa: E402

SMOLLM = loader.config("smollm_135m")["model"]
V5E = loader.peaks("TPU v5 lite")


def test_layer_params_match_published_parameter_counts():
    fwd = loader.kernel_cost("forward")
    # SmolLM-135M: 30 x (576*64*(2*9 + 2*3) + 3*576*1536) = 106,168,320
    # in the layers, plus the tied 49152 x 576 embedding: 134.5M in all
    assert 30 * fwd.layer_params(SMOLLM) == 106_168_320


def test_forward_flops_hand_count():
    fwd = loader.kernel_cost("forward")
    # one decoded SmolLM token attending 1000 keys:
    # 2 * 106,168,320 + 4 * 30 layers * 9 heads * 64 * 1000 + 2 * 576 * 49152
    want = 2 * 106_168_320 + 4 * 30 * 9 * 64 * 1000 + 2 * 576 * 49152
    assert fwd.decode_flops(SMOLLM, 1000) == want
    # a 3-token prompt attends 1 + 2 + 3 = 6 keys, one LM head
    want = 3 * 2 * 106_168_320 + 4 * 30 * 9 * 64 * 6 + 2 * 576 * 49152
    assert fwd.prefill_flops(SMOLLM, 3) == want
    assert fwd.step_flops([3], [1000, 1000], SMOLLM) == (
        fwd.prefill_flops(SMOLLM, 3) + 2 * fwd.decode_flops(SMOLLM, 1000))
    # calibration: 8 x 2048 tokens of SmolLM, no head
    want = (2 * 106_168_320 * 8 * 2048
            + 8 * 4 * 30 * 9 * 64 * (2048 * 2049 // 2))
    assert fwd.calib_flops(SMOLLM, 8, 2048) == want


def test_paged_attention_hand_count():
    pa = loader.kernel_cost("paged_attention")
    # SmolLM row of 1000 keys: QK and PV, 4 * 9 heads * 64 * 1000
    assert pa.flops([1000], SMOLLM) == 2_304_000
    # GQA reads 3 KV heads for 9 query heads: K and V in bf16,
    # 2 * 3 * 64 * 1000 * 2 bytes; q and o 2 * 9 * 64 * 2
    assert pa.hbm_bytes([1000], SMOLLM) == 768_000 + 2_304
    assert pa.hbm_bytes([100, 200], SMOLLM) == (
        2 * 3 * 64 * 300 * 2 + 2 * 2 * 9 * 64 * 2)
    t, bound = pa.least_seconds([1000] * 32, SMOLLM, V5E)
    assert bound == "memory"
    assert t == pytest.approx(pa.hbm_bytes([1000] * 32, SMOLLM) / 819e9)


def test_tsqr_fold_hand_count():
    qr = loader.kernel_cost("tsqr_fold")
    # Householder R of (8192 + 576) x 576: 2 m n^2 - 2 n^3 / 3
    assert qr.fold_flops(576, 8192) == pytest.approx(5_690_621_952)
    assert qr.fold_flops(576, 8192, first=True) == pytest.approx(
        2 * 8192 * 576 ** 2 - 2 * 576 ** 3 / 3)
    # a 16384-row batch folds as two 8192-row chunks per linear
    assert qr.batch_flops([576, 1536], 16384, 8192) == pytest.approx(
        2 * qr.fold_flops(576, 8192) + 2 * qr.fold_flops(1536, 8192))


def test_peaks_table_refuses_unknown_devices():
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        loader.peaks("TPU v9 imaginary")
