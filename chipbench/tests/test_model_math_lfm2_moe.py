"""The cut's counts for ``lfm2_8b_a1b_l12`` against a hand count (a file of
its own: ``test_model_math.py`` is the accepted benchmark's and is not
edited)."""
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_lfm2_8b_a1b_l12_counts_by_hand():
    """The cut's arithmetic (ISSUE 36): an expert 3 x 2,048 x 1,792 =
    11.01 M, a layer's 32 experts and router 352.4 M; a convolution
    operator 2,048 x 6,144 + 2,048 x 2,048 + 3 x 2,048 = 16.78 M, an
    attention operator 10.49 M, a dense SwiGLU 44.04 M, the embedding
    134.2 M: 3.93 G parameters, 7.86 GB in bfloat16; pages 16,385 x 32
    KiB x 3 layers = 1.61 GB, windows 129 x 8 KiB x 9 layers = 9.5 MB."""
    import model_math_lfm2_moe as lm
    c = config("lfm2_8b_a1b_l12")
    assert lm.layer_kinds(c) == (9, 3, 2, 10)
    assert lm.expert_matmul_params(c) == 11_010_048
    assert 32 * lm.expert_matmul_params(c) + lm.router_params(c) \
        == 352_321_536 + 65_568
    assert lm.conv_operator_params(c) == 16_783_360
    assert lm.attention_operator_params(c) == 10_485_888
    assert lm.dense_ffn_params(c) == 44_040_192
    assert lm.embedding_params(c) == 134_217_728
    total = 9 * 16_783_360 + 3 * 10_485_888 + 2 * 44_040_192 \
        + 10 * (352_321_536 + 65_568) + 134_217_728
    assert lm.total_params(c) == total == 3_928_677_056
    e = c["engine"]
    pages = e["num_slots"] * e["pages_per_slot"] + 1
    assert pages * lm.kv_read_bytes(c, 1) == 16_385 * 32_768 * 3
    assert (e["num_slots"] + 1) * lm.window_bytes(c) == 129 * 8_192 * 9
    # a decode row at position 1,000, sampled, 4 experts in 10 layers
    want = 2 * lm.row_matmul_params(c) + 3 * 4 * 1_001 * 2_048 \
        + 2 * 40 * 11_010_048 + 2 * 134_217_728
    assert lm.serve_flops(c, 1, 1_001, 1, 40) == want
