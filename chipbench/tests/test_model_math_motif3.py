"""The cut's counts for ``motif3_beta_l5_ep8`` against a hand count, and the
bytes a step's latent rows take, on a hand-worked step."""
import json
import os

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_motif3_beta_l5_ep8_counts_by_hand():
    """An attention operator: q_a 4,096 x 1,024, q_b 1,024 x 80 x 192,
    kv_a 4,096 x 576, kv_b 512 x 16 x 256, lambda 4,096 x 64, the gate
    4,096 x 8,192 and o 8,192 x 4,096 = 91.75 M; the mHC maps 2 x 16,384
    x 24 = 0.79 M; an expert 3 x 4,096 x 1,280 = 15.73 M; the router
    4,097 x 384.  The dense layer 243.53 M, an expert layer on this chip
    864.81 M, the embedding and the head 112.72 M each: 3.928 G
    parameters, 7.86 GB in bfloat16."""
    import model_math_motif3 as mm
    c = config("motif3_beta_l5_ep8")
    assert mm.windows(c) == [128, 128, 128, 0, 128]
    assert mm.layer_kinds(c) == (4, 1, 1, 4)
    assert mm.heads(c) == (80, 16, 64)
    attn = 4096 * 1024 + 1024 * 80 * 192 + 4096 * 576 + 512 * 16 * 256 \
        + 4096 * 64 + 2 * 4096 * 8192
    assert mm.attention_operator_params(c) == attn == 91_750_400
    assert mm.mhc_params(c) == 2 * 16384 * 24 == 786_432
    assert mm.expert_matmul_params(c) == 15_728_640
    assert mm.router_params(c) == 4097 * 384
    dense = attn + 786_432 + 3 * 4096 * 12288
    assert dense == 243_531_776
    layer = attn + 786_432 + 49 * 15_728_640 + 4097 * 384
    assert mm.expert_layer_params(c) + attn + mm.mhc_params(c) == layer \
        == 864_813_440
    assert mm.embedding_params(c) == 4096 * 27520 == 112_721_920
    total = dense + 4 * layer + 2 * 112_721_920
    assert mm.total_params(c) == total == 3_928_229_376
    # the pools: 40,961 pages of 16 rows of 640 lanes for the one full
    # layer, 129 rings of 128 such rows for each of the four sliding ones
    assert mm.stored_row_bytes(c) == 1280 and mm.latent_row_bytes(c) == 1152
    pages, rings = mm.pool_bytes(c)
    assert pages == 40_961 * 16 * 1280 == 838_881_280
    assert rings == 4 * 129 * 128 * 1280 == 84_541_440


def test_motif3_step_flops_and_bytes_by_hand():
    """A decode row at position 1,000, sampled, with 8 held pairs over the
    four expert layers: the full layer reads 1,001 latent rows, each
    sliding layer 128; every row passes the absorbed attention, both mHC
    maps and mixes of every layer, the dense layer's MLP and the shared
    experts and routers; the PolyNorms of the dense MLP, the shared
    experts and the 8 pairs."""
    import model_math_motif3 as mm
    c = config("motif3_beta_l5_ep8")
    attn_row = 4096 * 1024 + 1024 * 80 * 192 + 4096 * 576 \
        + 512 * (80 * 128 + 64 * 128) + 4096 * 64 + 2 * 4096 * 8192
    assert mm.attention_row_params(c) == attn_row
    row = 5 * (attn_row + 786_432) + 3 * 4096 * 12288 \
        + 4 * (15_728_640 + 4096 * 384)
    assert mm.row_matmul_params(c) == row
    mix = 2 * (3 * 4 * 4096 + 16 * 4096)
    elementwise = 5 * 2 * mix + 18 * 12288 + 4 * 18 * 1280
    assert mm.row_elementwise_flops(c) == elementwise
    per_key = 2 * 80 * (576 + 512)
    want = 2 * row + elementwise + 1 * 1_001 * per_key + 4 * 128 * per_key \
        + 8 * (2 * 15_728_640 + 18 * 1280) + 2 * 112_721_920
    assert mm.serve_flops(c, 1, 1_001, 128, 1, 8) == want
    assert mm.windowed_prompt_context(5, 128) == sum(range(5))
    assert mm.windowed_prompt_context(300, 128) \
        == sum(min(i, 128) for i in range(300))
    # a step of 256 rows whose walks read 9,000 row-pages of the full
    # layer and whose rings 100,000 rows over the four sliding layers:
    # the latent rows' required bytes, 1,152 a row (576 values)
    assert mm.latent_walk_bytes(c, 9_000) == 9_000 * 16 * 1152
    assert mm.ring_read_bytes(c, 100_000) == 100_000 * 1152


@pytest.mark.parametrize("name,counter,per_step", [
    ("latent_walk_bw_share.serve", "kv_pages_read", 9_000 * 16 * 1152),
    ("latent_ring_bw_share.serve", "win_positions", 100_000 * 1152)])
def test_latent_readers_on_a_hand_worked_step(name, counter, per_step):
    """Four steps of the hand-worked step above, 18 ms each on the device:
    the full layer's walk reads 165.9 MB a step, 1.125% of 18 ms at
    819 GB/s; the rings 115.2 MB, 0.781%.  Silent without the counter,
    the steps, a trace or this family's sizes."""
    reader = run.load_module("layer_metrics", name)
    cell = {"config": config("motif3_beta_l5_ep8"),
            "device": {"kind": "TPU v5 lite"}}
    counters = {"steps": 4, counter: 4 * per_step // (
        16 * 1152 if counter == "kv_pages_read" else 1152)}
    got = reader.read(cell, {}, counters, {"step_device_ms": 18.0})
    assert got == pytest.approx(100.0 * per_step / (819e9 * 18e-3))
    assert round(got, 3) == (1.125 if counter == "kv_pages_read" else 0.781)
    assert reader.read(cell, {}, {"steps": 4}, {"step_device_ms": 18.0}) \
        is None
    assert reader.read(cell, {}, dict(counters, steps=0),
                       {"step_device_ms": 18.0}) is None
    assert reader.read(cell, {}, counters, None) is None
    for other in ("gigachat3_702b_l5_ep16", "k_exaone_236b_l5_ep8"):
        assert reader.read({"config": config(other),
                            "device": {"kind": "TPU v5 lite"}}, {},
                           counters, {"step_device_ms": 18.0}) is None
