"""FLOPs and bytes from shapes (``bench/work.py``)."""

import json
from pathlib import Path

from bench import work

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs" / "qwen2_5_3b.json").read_text())


def test_qwen_matmul_params_by_hand():
    d, f, v, L = 2048, 11008, 151936, 36
    per_layer = d * 16 * 128 + 2 * d * 2 * 128 + 16 * 128 * d + 3 * d * f
    assert work.matmul_params(CFG) == L * per_layer + v * d
    # ~3.09B matmul weights: qwen2.5-3b's 3.09B parameters less biases and norms
    assert 3.0e9 < work.matmul_params(CFG) < 3.1e9


def test_token_flops_is_twice_params_plus_attention():
    p = work.matmul_params(CFG)
    for pos in (0, 127, 4095):
        attn = 4 * 36 * 16 * 128 * (pos + 1)
        assert work.token_flops(CFG, pos) == 2 * p + attn
    assert work.span_flops(CFG, 100, 8) == sum(work.token_flops(CFG, 100 + i) for i in range(8))
    assert work.span_flops(CFG, 5, 0) == 0


def test_kv_bytes_follow_lengths():
    one = work.kv_bytes(2, 128, 2, [1])
    assert one == 2 * 2 * 128 * 2
    assert work.kv_bytes(2, 128, 2, [100, 300]) == 400 * one
    assert work.kv_bytes(2, 128, 2, [0, 0]) == 0
    assert work.decode_attention_flops(16, 128, [10, 20]) == 4 * 16 * 128 * 30


def test_roofline_picks_the_binding_bound():
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.roofline_seconds(1e6, 819e9, peak)
    assert bound == "bytes" and abs(t - 1.0) < 1e-12
    t, bound = work.roofline_seconds(197e12, 1.0, peak)
    assert bound == "flops" and abs(t - 1.0) < 1e-12


def test_peaks_table_has_the_v5e():
    peaks = json.loads((Path(__file__).resolve().parents[1] / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
