"""The DeepSeek-V3 family (latent attention, group-limited sigmoid
routing, a share of the routed experts; GigaChat3.1-702B-A36B's
``model_type``) through ``models/deepseek_v3.py`` and the paged serving
engine, held to the plain reference ``chipbench/reference/deepseek_v3.py``
on the CPU: toy sizes, seeded weights, float32.

Tolerances.  The dense forward and the reference compute the same
function in float32 with another order of operations (absorbed queries
against the latent row, a grouped product over sorted pairs against a
loop over experts): their logits (deviation 2) agree to 1e-4 of the
largest logit.  The engine adds the paged softmax's order of summation: a
served token's reference logit lies within 1e-3 of the reference's best,
and is the reference's own choice wherever the reference's top-2 margin
exceeds that.  The same engine computing in bfloat16 misses that by two
orders (``test_bfloat16_fails_the_float32_tolerance``).
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import pools_seen_on

import jax
import jax.numpy as jnp

from mxnet_tpu.models import deepseek_v3 as M
from mxnet_tpu.parallel import moe
from mxnet_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "gigachat3_702b_l5_ep16.long_decode"
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
import run as chipbench_run                                   # noqa: E402

# the published flags and ratios at a toy size of the same structure (the
# configuration file's own ``rehearse`` group): 1 dense + 2 expert layers,
# 32 routed experts in 4 groups of which rank 0 of 8 holds 4
_CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "gigachat3_702b_l5_ep16.json")))
TOY = chipbench_run._overlay(_CONFIG, {
    k: v for k, v in _CONFIG["rehearse"].items() if k != "engine"})


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_deepseek_v3",
        os.path.join(ROOT, "chipbench", "reference", "deepseek_v3.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model(ref):
    params = ref.make_params(7, TOY, "float32")
    return params, M.DeepseekV3Config.from_hf(TOY, dtype="float32")


def test_reference_imports_nothing_of_the_program(ref):
    src = open(ref.__file__).read()
    assert "mxnet_tpu" not in src and "ragged" not in src
    assert 'default_matmul_precision("highest")' in src


def test_config_file_states_the_cut():
    c = _CONFIG
    assert c["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                            "n_routed_experts", "vocab_size",
                            "num_nextn_predict_layers"]
    assert (c["n_routed_experts"], c["router_width"], c["ep_chips"],
            c["ep_rank"]) == (16, 256, 16, 0)
    cfg = M.DeepseekV3Config.from_hf(c)
    assert (cfg.held_first, cfg.held_count, cfg.n_routed_experts) \
        == (0, 16, 256)
    assert cfg.latent_row == (512, 64)
    # s = 192^-0.5 x (0.1 ln 64 + 1)^2
    assert abs(cfg.softmax_scale - 0.14468) < 1e-5
    inv, factor = M.yarn_inv_freq(cfg)
    assert factor == 1.0 and inv[0] == 1.0      # the fastest pair keeps
    assert abs(inv[-1] * 64 - 100000 ** (-62 / 64)) < 1e-12   # its turn


@pytest.mark.parametrize("absorbed", [True, False])
def test_forward_matches_reference(ref, model, absorbed):
    """The served (absorbed) attention and the published (expanded) one,
    each against the reference, which expands."""
    params, cfg = model
    tokens = np.random.RandomState(0).randint(1, TOY["vocab_size"],
                                              (2, 40)).astype(np.int32)
    want = ref.decoder_logits(params, tokens, TOY)
    got = jax.jit(lambda p, t: M.forward(p, cfg, t, absorbed=absorbed))(
        params, jnp.asarray(tokens))
    assert float(jnp.std(want)) > 1.0        # the logits are alive
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 1e-4 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("fault", [
    "no_group_limit", "no_shared_expert", "k_pe_not_rotated",
    "no_yarn_blend", "no_mscale"])
def test_reference_faults_move_the_logits(ref, model, fault):
    """Each planted departure from the published layer is far outside
    the tolerance the forward is held to."""
    params, _ = model
    tokens = np.random.RandomState(0).randint(1, TOY["vocab_size"],
                                              (1, 48)).astype(np.int32)
    want = ref.decoder_logits(params, tokens, TOY)
    bad = ref.decoder_logits(params, tokens, TOY, fault=fault)
    assert float(jnp.max(jnp.abs(bad - want))) > 1.0


def test_init_params_layout_is_the_references(ref, model):
    params, cfg = model
    mine = M.init_params(jax.random.PRNGKey(0), cfg, "float32")
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(params)
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(mine)] \
        == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(params)]
    assert "router" not in params["layers"][0]       # the leading dense
    assert params["layers"][1]["router"].shape == (128, 32)
    assert params["layers"][1]["ew_gate"].shape == (4, 128, 64)


# ------------------------------------------------------------ routing ---

def _route(ref, scores, bias, sizes):
    """The reference's choice on given sigmoid scores: a router that is
    the identity on logits whose sigmoid they are."""
    E = scores.shape[-1]
    layer = {"router": jnp.eye(E, dtype=jnp.float32),
             "router_bias": jnp.asarray(bias, jnp.float32)}
    logits = jnp.log(scores) - jnp.log1p(-scores)
    return ref.route(logits, layer, sizes)


def test_routing_matches_reference_on_planted_near_ties(ref):
    """32 experts, 4 groups of 8, best 2 groups, top 4: rows with a
    near-tie inside the top-k, with a near-tie between groups, and with a
    bias that flips a choice; the weights are the chosen SCORES over
    their sum times the scaling factor."""
    sizes = dict(TOY, router_width=32)
    rs = np.random.RandomState(3)
    s = rs.uniform(0.1, 0.6, (6, 32))
    s[0, 3], s[0, 5] = 0.9, 0.9 - 1e-6       # a near-tie, both chosen
    s[1, [0, 1]] = 0.85                      # group 0 first; group 1
    s[1, [8, 9]] = 0.8                       # stays and group 2 goes,
    s[1, [16, 17]] = 0.8 - 1e-6              # by 2e-6
    s[2, 30] = 0.95                          # the bias takes this one out
    bias = np.zeros(32)
    bias[30] = -0.9
    s[3, 12] = 0.61                          # ... and puts this one in
    bias[12] = 0.3
    s = jnp.asarray(s, jnp.float32)
    want_idx, want_w = _route(ref, s, bias, sizes)
    idx, w = moe.route_group_limited(
        s, jnp.asarray(bias, jnp.float32), n_group=sizes["n_group"],
        topk_group=sizes["topk_group"], top_k=sizes["num_experts_per_tok"],
        norm_topk_prob=True, scale=sizes["routed_scaling_factor"])
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.asarray(want_idx), -1))
    order = np.argsort(np.asarray(idx), -1)
    want_order = np.argsort(np.asarray(want_idx), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1),
        np.take_along_axis(np.asarray(want_w), want_order, -1), rtol=1e-6)
    chosen = [set(r) for r in np.asarray(idx)]
    assert {3, 5} <= chosen[0]
    assert chosen[1] == {0, 1, 8, 9}
    assert 30 not in chosen[2] and 12 in chosen[3]
    # the bias chose; it does not weigh: 2.5 x s / sum(s)
    k = list(np.asarray(idx)[3]).index(12)
    picked = np.asarray(s)[3][np.asarray(idx)[3]]
    assert abs(float(w[3, k]) - 2.5 * 0.61 / picked.sum()) < 1e-6
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)


def test_shares_add_up_to_the_uncut_expert_layer(ref, model):
    """The 8 ranks' routed parts, with the shared expert counted once,
    are the uncut reference's expert layer."""
    rs = np.random.RandomState(5)
    whole = dict(TOY, n_routed_experts=32, router_width=32, ep_rank=0)
    layer = ref.make_params(11, dict(whole, num_hidden_layers=2),
                            "float32")["layers"][1]
    m = jnp.asarray(rs.randn(24, 128), jnp.float32)
    want = ref.expert_layer(m, layer, whole)
    cfg = M.DeepseekV3Config.from_hf(whole, dtype="float32")
    idx, w = moe.route_group_limited(
        jax.nn.sigmoid(jnp.dot(m, layer["router"],
                               precision=jax.lax.Precision.HIGHEST)),
        layer["router_bias"], n_group=cfg.n_group,
        topk_group=cfg.topk_group, top_k=cfg.top_k,
        scale=cfg.routed_scaling_factor)
    total, pairs = 0.0, 0
    for rank in range(8):
        cut = slice(4 * rank, 4 * rank + 4)
        y, n, hit, _, _ = moe.held_experts_ffn(
            m, layer["ew_gate"][cut], layer["ew_up"][cut],
            layer["ew_down"][cut], idx, w, held_first=4 * rank)
        # the reference, given the same share, agrees rank by rank
        share = dict(TOY, ep_rank=rank)
        mine = {k: (v[cut] if k.startswith("ew_") else v)
                for k, v in layer.items()}
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.expert_layer(
                m, mine, share, shared=False)), rtol=2e-5, atol=2e-5)
        assert int(hit) <= 4
        total, pairs = total + y, pairs + int(n)
    assert pairs == 24 * 4                       # every pair, once
    shared = ref.expert_layer(m, layer, whole) \
        - ref.expert_layer(m, layer, whole, shared=False)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_dead_rows_dispatch_no_pair():
    rs = np.random.RandomState(6)
    x = jnp.asarray(rs.randn(6, 16), jnp.float32)
    wg, wu = (jnp.asarray(rs.randn(2, 16, 8), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rs.randn(2, 8, 16), jnp.float32)
    idx = jnp.asarray([[0, 5], [1, 0], [4, 5], [1, 7], [0, 1], [6, 1]],
                      jnp.int32)
    w = jnp.ones((6, 2), jnp.float32)
    live = jnp.asarray([True, True, True, False, True, False])
    y, pairs, hit, sizes, fetches = moe.held_experts_ffn(
        x, wg, wu, wd, idx, w, held_first=0, live=live)
    assert (int(pairs), int(hit), int(fetches)) == (5, 2, 6)
    assert sizes.tolist() == [3, 2]
    assert float(jnp.max(jnp.abs(y[jnp.asarray([2, 3, 5])]))) == 0.0
    y2, pairs2, _, _, _ = moe.held_experts_ffn(x, wg, wu, wd, idx, w,
                                            held_first=0)
    assert int(pairs2) == 7
    np.testing.assert_allclose(np.asarray(y2)[[0, 1, 4]],
                               np.asarray(y)[[0, 1, 4]], rtol=1e-6)


# --------------------------------------------------------- the engine ---

def _engine(model, overlap=False, kernel="xla", **kw):
    params, cfg = model
    args = dict(num_slots=3, page_size=8, pages_per_slot=8,
                prefill_chunk=8)
    args.update(kw)
    with pools_seen_on("tpu" if overlap else "cpu"):
        eng = ServingEngine(params, cfg, kernel=kernel, **args)
    assert eng.overlap is overlap
    return eng


def _held_to_reference(ref, params, eng, rids):
    """Every served token of ``rids`` against the reference's one full
    forward pass: (widest logit gap, tokens that differ where the
    reference's top-2 margin exceeds the tolerance)."""
    worst, wrong = 0.0, 0
    for rid in rids:
        req = eng.requests[rid]
        seq = np.concatenate([req.prompt,
                              np.asarray(req.generated, np.int32)])[None]
        logits = np.asarray(ref.decoder_logits(params, seq, TOY))[0]
        for i, tok in enumerate(req.generated):
            row = np.sort(logits[req.prompt.size - 1 + i])
            gap = float(row[-1] - logits[req.prompt.size - 1 + i][tok])
            worst = max(worst, gap)
            wrong += gap > 0 and row[-1] - row[-2] > 1e-3
    return worst, wrong


REQUESTS = ((5, 10), (19, 12), (30, 6), (9, 20), (17, 9))


@pytest.mark.parametrize("kernel,overlap", [
    ("xla", False), ("pallas", False), ("xla", True), ("pallas", True)])
def test_engine_serves_the_reference_tokens(ref, model, kernel, overlap):
    """Chunked prefill of several slots (prompts longer than a chunk
    among them), decode through the latent pages, five requests over
    three slots (slot reuse)."""
    rs = np.random.RandomState(1)
    eng = _engine(model, kernel=kernel, overlap=overlap)
    assert eng.cache.pools[0]["kv"].shape == (3 * 8 + 1, 8, 128)
    rids = [eng.submit(rs.randint(1, TOY["vocab_size"], n), m)
            for n, m in REQUESTS]
    out = eng.run()
    eng.close()
    assert sorted(out) == rids
    assert all(len(eng.requests[r].generated) == m
               for r, (_, m) in zip(rids, REQUESTS))
    worst, wrong = _held_to_reference(ref, model[0], eng, rids)
    assert worst <= 1e-3 and wrong == 0
    # the expert layers' counts came back with the tokens: every live
    # row's held pairs, in both expert layers
    s = eng.stats
    rows = s["decode_rows"] + s["prefill_rows"]
    assert 0 < s["moe_pairs"] <= rows * 2 * 4
    assert 0 < s["moe_experts_hit"] <= min(s["moe_pairs"],
                                           s["steps"] * 2 * 4)
    assert s["moe_weight_fetches"] == 3 * s["moe_experts_hit"]
    import model_math_deepseek_v3 as mm
    assert s["moe_expert_bytes"] == s["moe_experts_hit"] \
        * mm.expert_bytes(TOY, itemsize=4)
    # the walk reads each row's own pages; the gather the whole window
    assert (s["kv_pages_read"] < s["kv_pages_window"]) \
        == (kernel == "pallas")


def test_bfloat16_fails_the_float32_tolerance(ref, model):
    """The tolerance is tight enough to tell a lower precision."""
    params, cfg = model
    import dataclasses
    low = (jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 and a.ndim > 1 else a, params),
        dataclasses.replace(cfg, dtype="bfloat16"))
    rs = np.random.RandomState(1)
    eng = _engine(low)
    rids = [eng.submit(rs.randint(1, TOY["vocab_size"], n), m)
            for n, m in REQUESTS]
    eng.run()
    worst, _ = _held_to_reference(ref, params, eng, rids)
    assert worst > 1e-2


def test_engine_preempt_resumes_by_recomputation(ref, model):
    rs = np.random.RandomState(2)
    eng = _engine(model, overlap=True)
    rids = [eng.submit(rs.randint(1, TOY["vocab_size"], n), m)
            for n, m in REQUESTS]
    for _ in range(6):
        eng.step()
    victim = next(r for r in eng._slots if r is not None and r.generated)
    assert eng.preempt(victim.rid) is False      # no tier: recompute
    out = eng.run()
    eng.close()
    assert sorted(out) == rids and eng.stats["preemptions"] == 1
    worst, wrong = _held_to_reference(ref, model[0], eng, rids)
    assert worst <= 1e-3 and wrong == 0


def test_context_is_bounded_by_the_pool(model):
    eng = _engine(model)                         # 8 pages of 8
    assert model[1].max_len is None
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(np.ones(60, np.int32), 5)
    eng.submit(np.ones(60, np.int32), 4)         # 64 positions: fits
    with pytest.raises(ValueError, match="pages_per_slot"):
        ServingEngine(*model, num_slots=2)


def test_transformer_engine_books_no_expert_counters():
    from mxnet_tpu.models import gpt
    cfg = gpt.gpt_tiny(dtype="float32", param_dtype="float32")
    eng = ServingEngine(gpt.init_params(jax.random.PRNGKey(0), cfg), cfg,
                        num_slots=2)
    assert not any(k.startswith("moe_") for k in eng.stats)


@pytest.mark.parametrize("how,names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_K=2), "spec_K"),
    (dict(tier_bytes=1 << 20), "tier"),
    (dict(kv_int8=True), "kv_int8"),
    (dict(tp=2), "tp > 1"),
    ("admit_prefilled", "hand-off"),
])
def test_engine_refuses_by_name_what_latent_pages_lack(model, how, names):
    with pytest.raises(ValueError, match="latent.*" + names):
        if how == "admit_prefilled":
            _engine(model).admit_prefilled(
                np.ones(4, np.int32), [1], [1], max_new_tokens=4)
        else:
            _engine(model, **how)


# ------------------------------------------------ latent paged attention ---

def _latent_case(T, H, rank, rope, ps, PP, dtype, seed):
    from mxnet_tpu.serving.paged_kv import latent_width
    rs = np.random.RandomState(seed)
    W, NP = latent_width(rank, rope), T * PP + 1
    rows = np.zeros((NP, ps, W), np.float32)
    rows[..., :rank + rope] = rs.randn(NP, ps, rank + rope)
    q = jnp.asarray(rs.randn(T, H, rank + rope), dtype)
    bt = jnp.asarray(rs.permutation(np.arange(1, NP))[:T * PP]
                     .reshape(T, PP), jnp.int32)
    pos = jnp.asarray(rs.randint(0, PP * ps, T), jnp.int32)
    return q, jnp.asarray(rows, dtype), bt, pos


@pytest.mark.parametrize("ps,PP,dtype,tol", [
    (8, 20, "float32", 2e-6),        # two groups of 16 and 4 pages
    (16, 40, "bfloat16", 2e-2),      # one group of 40
    (16, 130, "float32", 5e-6),      # 128 pages a group, a tail of 2
])
def test_latent_fold_matches_reference(ps, PP, dtype, tol):
    """The walk's latent fold, interpreted, at page and group edges: 4
    query heads against one 64 + 16 row a token padded to 128 lanes."""
    from mxnet_tpu.kernels.paged_attention import (
        paged_attention, paged_attention_reference, walk_geometry)
    G, F, R, _ = walk_geometry(1, 64, ps, PP, dtype, flat=True, latent=True)
    assert F == G and (G % 8 == 0 or G == PP)
    q, pool, bt, pos = _latent_case(R + 3, 4, 64, 16, ps, PP, dtype, ps)
    edges = [0, PP * ps - 1, ps - 1, ps, G * ps - 1, min(G, PP - 1) * ps]
    pos = pos.at[:len(edges)].set(jnp.asarray(edges))
    kw = dict(page_size=ps, latent=(64, 16), scale=0.3)
    got = paged_attention(q, pool, None, bt, pos, interpret=True, **kw)
    want = paged_attention_reference(q, pool, None, bt, pos, **kw)
    assert got.shape == (R + 3, 4, 64)
    assert float(jnp.max(jnp.abs(got - want))) <= tol


def test_latent_reference_is_the_softmax_over_the_shared_row():
    """The XLA path against the attention written out: every head
    against the same rows, the value their first ``rank`` lanes."""
    from mxnet_tpu.kernels.paged_attention import paged_attention_reference
    q, pool, bt, pos = _latent_case(3, 2, 8, 4, 4, 3, "float32", 9)
    got = paged_attention_reference(q, pool, None, bt, pos, page_size=4,
                                    latent=(8, 4), scale=0.5)
    for t in range(3):
        rows = np.asarray(pool)[np.asarray(bt[t])].reshape(12, -1)
        rows = rows[:int(pos[t]) + 1]
        s = np.asarray(q[t]) @ rows[:, :12].T * 0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, :8]
        np.testing.assert_allclose(np.asarray(got[t]), want, rtol=1e-5,
                                   atol=1e-5)


def test_write_latent_pads_the_row_to_the_page():
    from mxnet_tpu.serving.paged_kv import (kv_geometry, latent_width,
                                            write_latent)
    assert latent_width(512, 64) == 640 and latent_width(64, 16) == 128
    cfg = M.DeepseekV3Config.from_hf(_CONFIG)
    assert kv_geometry(cfg) == (1, 320, True)
    pool = jnp.ones((3, 4, 128), jnp.float32)
    row = jnp.full((2, 80), 7.0)
    got = np.asarray(write_latent(pool, jnp.asarray([2, 1]),
                                  jnp.asarray([0, 3]), row))
    want = np.ones((3, 4, 128), np.float32)
    want[[2, 1], [0, 3], :80] = 7.0
    want[[2, 1], [0, 3], 80:] = 0.0
    np.testing.assert_array_equal(got, want)


def test_paged_attention_refuses_a_latent_pool_it_cannot_walk():
    from mxnet_tpu.kernels.paged_attention import paged_attention
    q, pool, bt, pos = _latent_case(3, 2, 64, 16, 4, 2, "bfloat16", 0)
    with pytest.raises(ValueError, match="page walk alone"):
        paged_attention(q, pool, None, bt, pos, page_size=4,
                        latent=(64, 16), scale=0.3, interpret=True)
    with pytest.raises(ValueError, match="latent pool is"):
        paged_attention(q, pool, None, bt, pos, page_size=4,
                        latent=(64, 16))


# ------------------------------------------------ the benchmark's cell ---

def test_model_math_counts_the_share():
    """The cut's arithmetic: 132.58 M attention, 528.94 M a dense
    layer, 178.45 M an expert layer outside its routed experts, 44.04 M
    an expert; 139 kFLOP a cached token a layer over 1,152 B."""
    import model_math_deepseek_v3 as mm
    c = _CONFIG
    attn = mm.attention_matmul_params(c)
    assert round(attn / 1e6, 2) == 132.58
    assert round(mm.expert_matmul_params(c) / 1e6, 2) == 44.04
    assert mm.row_matmul_params(c) == 5 * attn + 3 * 7168 * 18432 \
        + 4 * (mm.expert_matmul_params(c) + 7168 * 256)
    assert round((attn + 3 * 7168 * 18432) / 1e6, 2) == 528.94
    assert round((attn + mm.expert_matmul_params(c) + 7168 * 256)
                 / 1e6, 2) == 178.45
    assert mm.attention_flops(c, 1) == 2 * 64 * (576 + 512) == 139264
    assert mm.latent_row_bytes(c) == 1152
    assert mm.latent_read_bytes(c, 10, 16) == 10 * 16 * 1152 * 5
    assert mm.serve_flops(c, 1, 0, 0, 3) - mm.serve_flops(c, 1, 0, 0, 0) \
        == 2 * 3 * mm.expert_matmul_params(c)


def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", script),
         "--workload", CELL, "--seconds", "1", "--rehearse"] + list(args),
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    return r


@pytest.mark.parametrize("trace", [0, 1])
def test_chipbench_rehearses_the_cell(trace):
    """``chipbench/run.py --rehearse`` of the cell at the configuration
    file's toy size, in a process of its own: paths, control flow and the
    comparison against the reference, no device metric."""
    r = _run("run.py", "--seed", str(2 ** 31 + 32), "--trace", str(trace))
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {"bad_answers", "missing_answers",
                                     "logit_gap", "logit_gap_p99"}
    assert line["rehearse"] is True and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    turns = json.loads(next(ln for ln in r.stderr.splitlines()
                            if ln.startswith("turns "))[6:])
    assert turns["steps"] > 0 and "engine.wait" in \
        turns["longest"][0]["phases"]


def test_chipbench_control_and_faults_come_out_not_correct():
    """``calibrate.py --rehearse``: the program inside the toy limits,
    the fp8 control and every planted fault outside one of them."""
    import compare
    limits = compare.load_limits(CELL, rehearse=True)
    r = _run("calibrate.py", "--seeds", str(2 ** 31 + 33), "--controls",
             "1")
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert compare.judge(out["program"], limits)[0] is True
    others = {k: v for k, v in out.items()
              if k.startswith(("control_", "fault_"))}
    assert sorted(others) == sorted(
        ["control_fp8"] + ["fault_" + f for f in (
            "no_group_limit", "no_shared_expert", "k_pe_not_rotated",
            "no_yarn_blend", "no_mscale")])
    for name, readings in others.items():
        assert compare.judge(readings, limits)[0] is False, name


def test_benchmark_json_names_the_cell_and_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = {"name": CELL, "bench": bench}
    per_layer = [m["name"] for m in chipbench_run.metrics_for(
        cell, "per_layer")]
    # (the last four: what a stall was, every serving cell's, PR 38)
    assert per_layer[-4:] == ["turn_stall_max_ms.serve",
                              "stall_offcpu_share.serve",
                              "stall_host_late_share.serve",
                              "stall_runtime_busy_share.serve"]
    per_layer = per_layer[:-4]
    assert per_layer[-5:] == ["moe_expert_bw_share.serve",
                              "latent_read_bw_share.serve",
                              "moe_rows_per_expert.serve",
                              "kv_chain_fill_share.serve",
                              "moe_weight_fetch_ratio.serve"]
    assert "ssm_state_bw_share.serve" not in per_layer
    assert "step_mfu.serve" in per_layer and len(per_layer) == 22
    assert [m["name"] for m in chipbench_run.metrics_for(
        cell, "end_to_end")] == ["setup_s", "serve_tok_s", "itl_p95_ms"]
    # each new reader is silent where the program books no such counter
    for name in per_layer[-5:]:
        reader = chipbench_run.load_module("layer_metrics", name)
        assert reader.read({"config": {}, "device": {"kind": "TPU v5 lite"}},
                           {}, {"steps": 5, "kv_pages_read": 7},
                           {"step_device_ms": 9.0}) is None
