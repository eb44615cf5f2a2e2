"""The benchmark's operation and byte counts against hand counts."""
import json

import flops
from conftest import ROOT


def test_gpt125m_train_flops_per_token_hand_count():
    model = json.loads((ROOT / "bench/configs/paper-gpt-125m.json").read_text())["model"]
    # per layer: q, k, v, o (4 x 768 x 768) and the MLP (2 x 768 x 3072);
    # then the tied head, 768 x 50304
    matmul = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50304
    assert flops.gpt_matmul_params(model) == matmul == 123_568_128
    attention = 12 * 12 * 768 * 1024
    assert flops.gpt_train_flops_per_token(model, 1024) == 6 * matmul + attention
    # 8 x 1024 tokens a step: about 7.0 TFLOP of model arithmetic
    assert abs(8 * 1024 * flops.gpt_train_flops_per_token(model, 1024) - 7.0e12) < 0.05e12


def test_fused_tick_counts_hand_count():
    cells = 64 * 20 * 128 * 6
    assert flops.tick_flops(64, 20, 128, 6) == 16 * cells
    # window read once; shares + gains [64, 6], leader [64, 20, 6], what-if [64, 6, 128]
    outputs = 64 * 6 * 2 + 64 * 20 * 6 + 64 * 6 * 128
    assert flops.tick_bytes(64, 20, 128, 6) == 4 * (cells + outputs) == 4_162_560


def test_roofline_names_the_bound():
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(flops.tick_flops(64, 20, 128, 6),
                                      flops.tick_bytes(64, 20, 128, 6), peak)
    assert bound == "bytes" and t == 4_162_560 / 819e9
    t, bound = flops.roofline_seconds(1e15, 1.0, peak)
    assert bound == "flops" and t == 1e15 / 197e12
