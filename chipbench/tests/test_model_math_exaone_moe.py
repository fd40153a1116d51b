"""The cut's counts for ``k_exaone_236b_l5_ep8`` against a hand count (a
file of its own: ``test_model_math.py`` is the accepted benchmark's and is
not edited)."""
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_k_exaone_236b_l5_ep8_counts_by_hand():
    """The cut's arithmetic: an attention operator 6,144 x 8,192 + 2 x
    6,144 x 1,024 + 8,192 x 6,144 = 113.25 M (and 256 norm gains), an
    expert 3 x 6,144 x 2,048 = 37.75 M, the router 6,145 x 128; an expert
    layer on this chip 755.76 M, the dense layer 452.98 M, the embedding
    and the head 117.96 M each: 3.712 G parameters, 7.42 GB in bfloat16;
    pages 40,961 x 64 KiB = 2.68 GB for the one full layer, rings 129 x 4
    x 512 KiB = 0.27 GB."""
    import model_math_exaone_moe as em
    c = config("k_exaone_236b_l5_ep8")
    assert em.layer_kinds(c) == (4, 1, 1, 4)
    attn = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144 + 2 * 128
    assert em.attention_operator_params(c) == attn == 113_246_464
    assert em.expert_matmul_params(c) == 37_748_736
    assert em.router_params(c) == 6145 * 128
    layer = attn + 17 * 37_748_736 + 6145 * 128
    assert em.expert_layer_params(c) + attn == layer == 755_761_536
    assert em.dense_ffn_params(c) + attn == 452_985_088
    assert em.embedding_params(c) == 6144 * 19200 == 117_964_800
    total = 452_985_088 + 4 * layer + 2 * 117_964_800
    assert em.total_params(c) == total == 3_711_960_832
    e = c["engine"]
    pages = e["num_slots"] * e["pages_per_slot"] + 1
    assert pages * em.kv_read_bytes(c, 1) == 40_961 * 65_536
    assert (e["num_slots"] + 1) * em.ring_bytes(c) == 129 * 4 * 524_288
    # a decode row at position 1,000, sampled, 8 held pairs over 4 layers:
    # the full layer reads 1,001 keys, each sliding layer 128
    want = 2 * em.row_matmul_params(c) + 4 * 1_001 * 64 * 128 \
        + 4 * 4 * 128 * 64 * 128 + 2 * 8 * 37_748_736 + 2 * 117_964_800
    assert em.serve_flops(c, 1, 1_001, 128, 1, 8) == want
