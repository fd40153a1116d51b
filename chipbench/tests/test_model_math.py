"""FLOP counts against a hand count for both configurations."""
import json
import os

import pytest

import model_math as mm

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_base_train_flops_by_hand():
    c = config("bert_base")
    # one block: q,k,v,o 4 x 768^2 = 2,359,296; FFN 2 x 768 x 3072 =
    # 4,718,592 -> 7,077,888 weights, 14,155,776 flops a token;
    # attention over 512 keys: 4 x 512 x 768 = 1,572,864
    assert mm.layer_matmul_params(c) == 7_077_888
    assert mm.attention_flops(c, 512) == 1_572_864
    # head: 768^2 + 768 x 30522 = 24,030,720 weights
    assert mm.head_matmul_params(c) == 24_030_720
    per_layer = 14_155_776 + 1_572_864
    share = 77 / 512.0
    fwd = 12 * per_layer + share * 2 * 24_030_720
    assert mm.train_flops_per_token(c, 512, share) == pytest.approx(3 * fwd)
    # 0.588 GFLOP a token; with the head on every position it would be 0.711
    assert mm.train_flops_per_token(c, 512, share) == pytest.approx(
        0.5879e9, rel=1e-3)
    assert mm.train_flops_per_token(c, 512, 1.0) == pytest.approx(
        0.7104e9, rel=1e-3)


def test_bert_large_decoder_serve_flops_by_hand():
    c = config("bert_large_decoder")
    # one block: 4 x 1024^2 + 2 x 1024 x 4096 = 12,582,912 weights
    assert mm.layer_matmul_params(c) == 12_582_912
    assert mm.head_matmul_params(c) == 1024 * 1024 + 1024 * 30522
    # one decode row at position 299 (300 keys), sampled
    want = 24 * (2 * 12_582_912 + 4 * 300 * 1024) \
        + 2 * (1024 * 1024 + 1024 * 30522)
    assert mm.serve_flops(c, 1, 300, 1) == want
    # a 64-token prompt: contexts 1..64, one sampled row
    want = 24 * (2 * 12_582_912 * 64 + 4 * 1024 * (64 * 65 // 2)) \
        + 2 * (1024 * 1024 + 1024 * 30522)
    assert mm.serve_flops(c, 64, 64 * 65 // 2, 1) == want


def test_peaks_known_and_unknown():
    p = mm.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        mm.peaks("TPU v9 imaginary")
