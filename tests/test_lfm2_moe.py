"""LFM2-MoE (gated short convolutions that keep a window and no pages
beside QK-normed grouped-query attention layers that keep pages and no
window; sigmoid-routed experts all held; LFM2-8B-A1B's ``model_type``)
through ``models/lfm2_moe.py`` and the paged serving engine, held to the
plain reference ``chipbench/reference/lfm2_moe.py`` on the CPU: toy
sizes of the same structure (the configuration file's ``rehearse``
group), seeded weights, float32.

Tolerances.  The dense forward and the reference compute the same
function in float32 with another order of operations (the window over a
step's flat rows against a padded sum of shifted products, a grouped
product over sorted pairs against a loop over experts): their logits
(deviation 2) agree to 1e-4 of the largest logit.  The engine adds the
paged softmax's order of summation: a served token's reference logit
lies within 1e-3 of the reference's best, and is the reference's own
choice wherever the reference's top-2 margin exceeds that.  The same
engine computing in bfloat16 misses that by an order and more
(``test_bfloat16_fails_the_float32_tolerance``).
"""
import dataclasses
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

from mxnet_tpu.models import lfm2_moe as M
from mxnet_tpu.parallel import moe
from mxnet_tpu.serving import ServingEngine
from mxnet_tpu.serving.paged_kv import PagedKVCache, layer_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2_8b_a1b_l12.long_decode"
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
import run as chipbench_run                                   # noqa: E402

# the published flags and ratios at a toy size of the same structure:
# conv, conv, attention, conv; 1 dense + 3 expert layers of 8 experts, 2
# a token; 4 query heads over 2 key/value heads of 16
_CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "lfm2_8b_a1b_l12.json")))
TOY = chipbench_run._overlay(_CONFIG, {
    k: v for k, v in _CONFIG["rehearse"].items() if k != "engine"})
EXPERT_LAYERS = TOY["num_hidden_layers"] - TOY["num_dense_layers"]


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_lfm2_moe",
        os.path.join(ROOT, "chipbench", "reference", "lfm2_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model(ref):
    params = ref.make_params(7, TOY, "float32")
    return params, M.Lfm2MoeConfig.from_hf(TOY, dtype="float32")


def _tokens(seed, *shape):
    return np.random.RandomState(seed).randint(
        1, TOY["vocab_size"], shape).astype(np.int32)


def test_reference_imports_nothing_of_the_program(ref):
    src = open(ref.__file__).read()
    assert "mxnet_tpu" not in src and "ragged" not in src
    assert 'default_matmul_precision("highest")' in src


def test_config_file_states_the_cut():
    """Every published width and count of the catalog row, but the depth
    and the layer list cut with it; the deployment, the departures and
    what was assumed."""
    c = _CONFIG
    assert c["reduced"] == ["num_hidden_layers", "layer_types"]
    assert set(c["reduced_why"]) == set(c["reduced"])
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k: c[k] for k in published} == published
    period = ["conv", "conv", "full_attention", "conv"]
    assert c["num_hidden_layers"] == 12 and c["layer_types"] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention",
        "conv"]
    assert TOY["layer_types"] == period
    for key in ("deployment", "departures", "assumed"):
        assert c[key]
    cfg = M.Lfm2MoeConfig.from_hf(c)
    assert (cfg.head_dim, cfg.n_heads, cfg.n_kv_heads) == (64, 32, 8)
    assert [pages for pages, _ in M.layer_cache(cfg)].count(True) == 3


def test_forward_matches_reference(ref, model):
    params, cfg = model
    tokens = _tokens(0, 2, 40)
    want = ref.decoder_logits(params, tokens, TOY)
    got = jax.jit(lambda p, t: M.forward(p, cfg, t))(params,
                                                     jnp.asarray(tokens))
    assert float(jnp.std(want)) > 1.0        # the logits are alive
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 1e-4 * float(jnp.max(jnp.abs(want)))


def test_reference_faults_and_fp8_move_the_logits(ref, model):
    """Each planted departure from the published layer, and the fp8
    control, is far outside the tolerance the forward is held to."""
    params, _ = model
    tokens = _tokens(0, 1, 48)
    want = ref.decoder_logits(params, tokens, TOY)
    assert sorted(ref.FAULTS) == [
        "b_c_exchanged", "no_expert_bias", "no_norm_topk", "no_qk_norm",
        "window_not_carried"]
    others = [dict(fault=f) for f in ref.FAULTS] + [dict(precision="fp8")]
    for how in others:
        bad = ref.decoder_logits(params, tokens, TOY, **how)
        assert float(jnp.max(jnp.abs(bad - want))) > 0.5, how


def test_init_params_layout_is_the_references(ref, model):
    params, cfg = model
    mine = M.init_params(jax.random.PRNGKey(0), cfg, "float32")
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(params)
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(mine)] \
        == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(params)]
    kinds = [("conv_w" in p, "wq" in p, "router" in p)
             for p in params["layers"]]
    assert kinds == [(True, False, False), (True, False, True),
                     (False, True, True), (True, False, True)]
    assert "lm_head" not in params               # the head is tied
    assert params["layers"][1]["ew_gate"].shape == (8, 64, 32)


# -------------------------------------------------- the window in chunks ---

def _chunked_logits(params, cfg, tokens, chunk):
    """One sequence through ``serve_block`` in calls of ``chunk`` rows:
    the windows carried in a two-slot pool (slot 1 the scratch), the
    keys and values in a growing list with a causal softmax.  The last
    row's logits."""
    T = tokens.shape[0]
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keeps = M.layer_cache(cfg)
    pools = [{n: jnp.full((2,) + s, 9.0, d) for n, (s, d) in st.items()}
             for _, st in keeps]                 # dirty: fresh must mask
    cache = [([], []) for _ in keeps]
    for lo in range(0, T, chunk):
        rows = np.arange(lo, min(lo + chunk, T))
        row_pos = jnp.asarray(rows, jnp.int32)
        fresh = jnp.asarray([lo == 0, False])
        counts = M.StepCounts(jnp.ones(rows.size, bool))
        x = M.serve_embed(params, cfg, jnp.asarray(tokens[rows]), row_pos)
        for i, layer in enumerate(params["layers"]):
            def attend(q, k, v, i=i):
                cache[i][0].append(k)
                cache[i][1].append(v)
                K = jnp.repeat(jnp.concatenate(cache[i][0]), Hq // Hkv, 1)
                V = jnp.repeat(jnp.concatenate(cache[i][1]), Hq // Hkv, 1)
                s = jnp.einsum("qhd,khd->hqk", q, K) / np.sqrt(dh)
                seen = jnp.arange(K.shape[0])[None] <= row_pos[:, None]
                p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), -1)
                return jnp.einsum("hqk,khd->qhd", p, V)
            state = M.SlotState(pools[i], jnp.zeros(rows.size, jnp.int32),
                                fresh, chunk)
            x = M.serve_block(layer, cfg, x, row_pos, attend, state,
                              counts)
            pools[i] = state.pools
    return M.serve_logits(params, cfg, x,
                          jnp.asarray([[rows.size - 1]]))[0, 0]


@pytest.mark.parametrize("chunk", [1, 2, 3, 8])
def test_chunks_give_the_logits_of_one_chunk(model, chunk):
    """A prompt cut into calls of 1, 2 and 3 rows (fewer than, as many
    as and more than the window holds) and of 8: each call's rows read
    the window for their first two taps and leave their last two gated
    rows in it."""
    params, cfg = model
    tokens = _tokens(4, 19)
    whole = _chunked_logits(params, cfg, tokens, 19)
    dense = M.forward(params, cfg, jnp.asarray(tokens)[None])[0, -1]
    got = _chunked_logits(params, cfg, tokens, chunk)
    scale = float(jnp.max(jnp.abs(dense)))
    assert float(jnp.max(jnp.abs(whole - dense))) <= 1e-5 * scale
    assert float(jnp.max(jnp.abs(got - whole))) <= 1e-5 * scale


# ------------------------------------------------------ the expert layer ---

def test_held_experts_ffn_over_all_experts_is_the_references_loop(ref,
                                                                   model):
    """``route_group_limited`` with one group, then ``held_experts_ffn``
    with every expert held, against the reference's top-k and its loop
    over the experts; the pairs and the heaviest expert's share."""
    params, cfg = model
    layer = params["layers"][1]
    m = jnp.asarray(np.random.RandomState(5).randn(24, 64), jnp.float32)
    want = ref.expert_layer(m, layer, TOY)
    want_idx, want_w = ref.route(m, layer, TOY)
    idx, w = moe.route_group_limited(
        jax.nn.sigmoid(jnp.dot(m, layer["router"],
                               precision=jax.lax.Precision.HIGHEST)),
        layer["router_bias"], n_group=1, topk_group=1, top_k=cfg.top_k,
        norm_topk_prob=True, scale=1.0, eps=1e-6)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w),
                               rtol=1e-6)
    # the weights are the chosen scores over their sum PLUS 1e-6
    s = np.asarray(jax.nn.sigmoid(m @ layer["router"]))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-5)
    y, pairs, hit, sizes, fetches = moe.held_experts_ffn(
        m, layer["ew_gate"], layer["ew_up"], layer["ew_down"], idx, w,
        held_first=0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert int(pairs) == 24 * 2 == int(jnp.sum(sizes))
    assert sizes.tolist() == np.bincount(np.asarray(idx).ravel(),
                                         minlength=8).tolist()
    assert int(hit) == int(jnp.sum(sizes > 0))
    # a toy product has one K tile: each hit expert copied once of three
    assert int(fetches) == 3 * int(hit)


def test_expert_bias_chooses_and_does_not_weigh(ref):
    """A bias that takes the best expert out of the choice and puts a
    worse one in; the weights are of the scores alone."""
    sizes = dict(TOY, num_experts=4, num_experts_per_tok=2)
    s = np.asarray([[0.9, 0.6, 0.5, 0.2]], np.float32)
    layer = {"router": jnp.eye(4, dtype=jnp.float32),
             "router_bias": jnp.asarray([-0.5, 0.0, 0.0, 0.35])}
    logits = jnp.log(s) - jnp.log1p(-s)
    idx, w = ref.route(logits, layer, sizes)
    assert sorted(np.asarray(idx)[0]) == [1, 3]
    np.testing.assert_allclose(sorted(np.asarray(w)[0]),
                               [0.2 / 0.800001, 0.6 / 0.800001], rtol=1e-5)
    idx, _ = ref.route(logits, layer, sizes, fault="no_expert_bias")
    assert sorted(np.asarray(idx)[0]) == [0, 1]


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


def _submit_all(eng, seed=1):
    rs = np.random.RandomState(seed)
    return [eng.submit(rs.randint(1, TOY["vocab_size"], n), m)
            for n, m in REQUESTS]


def _dirty(eng):
    """Every window of every slot full of another request's rows: a
    slot's first chunk has to start from zeros all the same."""
    eng.cache.pools = [
        {name: jnp.full_like(a, 7.0) if name == "conv" else a
         for name, a in pool.items()} for pool in eng.cache.pools]


@pytest.mark.parametrize("kernel,overlap", [
    ("xla", False), ("pallas", False), ("xla", True), ("pallas", True)])
def test_engine_serves_the_reference_tokens(ref, model, kernel, overlap):
    """Chunked prefill of several slots (prompts longer than a chunk
    among them), decode through the pages of the attention layer and the
    windows of the convolution layers, five requests over three slots
    (slot reuse: a reused slot starts from a zero window, and so does a
    first one whose pool is dirty)."""
    eng = _engine(model, kernel=kernel, overlap=overlap)
    _dirty(eng)
    rids = _submit_all(eng)
    counted = []
    while True:
        before = dict(eng.stats)
        if eng.step() is False:
            break
        counted.append({k: eng.stats[k] - before[k] for k in (
            "decode_rows", "prefill_rows", "moe_pairs", "moe_pairs_max",
            "moe_experts_hit", "moe_weight_fetches")})
    eng.close()
    assert all(eng.requests[r].state == "done"
               and len(eng.requests[r].generated) == m
               for r, (_, m) in zip(rids, REQUESTS))
    worst, wrong = _held_to_reference(ref, model[0], eng, rids)
    assert worst <= 1e-3 and wrong == 0
    # dropless, every expert held: a step's pairs are top_k x its live
    # rows x the expert layers, exactly; the heaviest expert of a layer
    # holds at least the mean expert's share (the commit of a pipelined
    # step trails its rows by one step: the sums agree)
    k, E = TOY["num_experts_per_tok"], TOY["num_experts"]
    rows = [c["decode_rows"] + c["prefill_rows"] for c in counted]
    pairs = [c["moe_pairs"] for c in counted]
    if not overlap:
        assert pairs == [k * r * EXPERT_LAYERS for r in rows]
    assert sum(pairs) == k * sum(rows) * EXPERT_LAYERS > 0
    for c in counted:
        assert c["moe_pairs_max"] * E >= c["moe_pairs"]
        assert c["moe_pairs_max"] <= c["moe_pairs"]
        assert c["moe_experts_hit"] <= E * EXPERT_LAYERS
        assert c["moe_weight_fetches"] == 3 * c["moe_experts_hit"]
    s = eng.stats
    import model_math_lfm2_moe as mm
    assert s["moe_expert_bytes"] == s["moe_experts_hit"] \
        * mm.expert_bytes(TOY, itemsize=4)
    # the windows: every live slot's once a step, a reset an admission,
    # the bytes of the three layers that keep one
    assert s["ssm_state_resets"] == len(REQUESTS)
    assert s["ssm_state_updates"] >= s["decode_rows"] + len(REQUESTS)
    assert eng.cache.bytes_per_slot_state \
        == mm.window_bytes(TOY, itemsize=4) == 3 * 2 * 64 * 4
    assert s["ssm_state_bytes"] == 2 * s["ssm_state_updates"] \
        * eng.cache.bytes_per_slot_state
    # the toy's flat page is 64 lanes, no whole tile: the per-page grid
    # serves under "pallas" and both lowerings book the whole window (the
    # cell's 1,024-lane page walks: test_flat_walk_folds_whole_pairs)
    assert s["kv_pages_read"] == s["kv_pages_window"] > 0


@pytest.mark.parametrize("chunk", [1, 2, 3])
def test_engine_prefills_in_chunks_shorter_than_the_window(ref, model,
                                                           chunk):
    eng = _engine(model, prefill_chunk=chunk)
    _dirty(eng)
    rids = _submit_all(eng, seed=chunk)
    eng.run()
    worst, wrong = _held_to_reference(ref, model[0], eng, rids)
    assert worst <= 1e-3 and wrong == 0


def test_bfloat16_fails_the_float32_tolerance(ref, model):
    """The tolerance is tight enough to tell a lower precision."""
    params, cfg = model
    low = (jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 and a.ndim > 1 else a, params),
        dataclasses.replace(cfg, dtype="bfloat16"))
    eng = _engine(low)
    rids = _submit_all(eng)
    eng.run()
    worst, _ = _held_to_reference(ref, params, eng, rids)
    assert worst > 1e-2


@pytest.mark.parametrize("overlap", [False, True])
def test_engine_preempt_resumes_to_the_same_tokens(ref, model, overlap):
    """A preempted request's windows are rebuilt by recomputation: it
    ends with the tokens an undisturbed engine serves."""
    calm = _engine(model, overlap=overlap)
    rids = _submit_all(calm, seed=2)
    calm.run()
    eng = _engine(model, overlap=overlap)
    assert _submit_all(eng, seed=2) == rids
    for _ in range(6):
        eng.step()
    victim = next(r for r in eng._slots if r is not None and r.generated)
    resets = eng.stats["ssm_state_resets"]
    assert eng.preempt(victim.rid) is False      # no tier: recompute
    out = eng.run()
    eng.close()
    assert sorted(out) == rids and eng.stats["preemptions"] == 1
    assert eng.stats["ssm_state_resets"] > resets
    assert all(eng.requests[r].generated == calm.requests[r].generated
               for r in rids)
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


@pytest.mark.parametrize("how,names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_K=2), "spec_K"),
    (dict(tier_bytes=1 << 20), "tier"),
    (dict(kv_int8=True), "kv_int8"),
    (dict(tp=2), "tp > 1"),
    ("admit_prefilled", "hand-off"),
])
def test_engine_refuses_by_name_what_a_window_lacks(model, how, names):
    with pytest.raises(ValueError,
                       match="Lfm2MoeConfig.*recurrent state.*" + names):
        if how == "admit_prefilled":
            _engine(model).admit_prefilled(
                np.ones(4, np.int32), [1], [1], max_new_tokens=4)
        else:
            _engine(model, **how)


# ------------------------------------------------- per-layer kinds of cache ---

def _leaves(pools):
    return [{k: (tuple(a.shape), str(a.dtype)) for k, a in p.items()}
            for p in pools]


def test_pools_follow_the_layers(model):
    """``"kv"`` on the attention layer only, ``"conv"`` on the
    convolution layers only; the page's bytes count the layers that have
    pages and the slot's state the layers that have a window."""
    eng = _engine(model)
    kv = {"kv": ((3 * 8 + 1, 8, 2 * 2 * 16), "float32")}
    conv = {"conv": ((3 + 1, 2, 64), "float32")}
    assert _leaves(eng.cache.pools) == [conv, conv, kv, conv]
    assert eng.cache.bytes_per_page == 8 * 2 * 2 * 16 * 4      # 1 of 4
    assert eng.cache.bytes_per_slot_state == 3 * 2 * 64 * 4    # 3 of 4
    eng.step()                                   # nothing to do: no work
    # the published depth cut: 3 of 12 layers keep pages, 9 a window
    cfg = M.Lfm2MoeConfig.from_hf(_CONFIG)
    cache = PagedKVCache(cfg, 2, 16, num_slots=1)
    kinds = [sorted(p) for p in cache.pools]
    assert kinds == [["conv"] if t == "conv" else ["kv"]
                     for t in _CONFIG["layer_types"]]
    assert cache.bytes_per_page == 3 * 16 * 8 * 2 * 64 * 2 == 3 * 32768
    assert cache.bytes_per_slot_state == 9 * 2 * 2048 * 2
    assert cache.pools[2]["kv"].shape == (2, 16, 1024)
    assert cache.pools[0]["conv"].shape == (2, 2, 2048)
    # page transfer follows the layers that have pages
    out = cache.export_pages([1])
    assert [sorted(layer) for layer in out] \
        == [[] if t == "conv" else ["kv"] for t in _CONFIG["layer_types"]]
    cache.install_pages([1], out)
    assert [sorted(p) for p in cache.pools] == kinds


def _family(name):
    if name == "transformer":
        from mxnet_tpu.models import gpt
        cfg = gpt.gpt_tiny(dtype="float32", param_dtype="float32")
        dh = cfg.d_model // cfg.n_heads
        return cfg, {"kv": ((9, 4, cfg.n_heads, 2 * dh), "float32")}
    file = {"falcon_h1": "falcon_h1_34b_l6",
            "deepseek_v3": "gigachat3_702b_l5_ep16"}[name]
    c = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                    file + ".json")))
    c = chipbench_run._overlay(c, {k: v for k, v in c["rehearse"].items()
                                   if k != "engine"})
    if name == "falcon_h1":
        from mxnet_tpu.models.falcon_h1 import FalconH1Config
        return FalconH1Config.from_hf(c, dtype="float32"), {
            "kv": ((9, 4, 2 * 2 * 32), "float32"),
            "conv": ((3, 3, 128 + 2 * 2 * 16), "float32"),
            "ssm": ((3, 4, 32, 16), "float32")}
    from mxnet_tpu.models.deepseek_v3 import DeepseekV3Config
    return DeepseekV3Config.from_hf(c, dtype="float32"), {
        "kv": ((9, 4, 128), "float32")}


@pytest.mark.parametrize("family", ["transformer", "falcon_h1",
                                    "deepseek_v3"])
def test_other_families_build_the_pools_they_built(family):
    """A module that says nothing of its layers, or the same of every
    one, keeps pages everywhere and the same state everywhere: leaf for
    leaf what it built before layers could differ."""
    cfg, want = _family(family)
    assert layer_cache(cfg) == [(True, {
        k: (s[1:], d) for k, (s, d) in want.items() if k != "kv"})] \
        * cfg.n_layers
    cache = PagedKVCache(cfg, 9, 4, num_slots=2)
    assert _leaves(cache.pools) == [want] * cfg.n_layers
    per_page = int(np.prod(want["kv"][0][1:])) * 4
    assert cache.bytes_per_page == per_page * cfg.n_layers
    assert cache.bytes_per_slot_state == cfg.n_layers * sum(
        int(np.prod(s[1:])) * 4 for k, (s, _) in want.items() if k != "kv")


# ------------------------------------------------------------ the scopes ---

def test_step_scopes_in_lowered_text(model):
    from test_spans import _has_scope, _scope_paths
    from mxnet_tpu.serving import engine as E
    params, cfg = model
    S, R, PP, ps = 2, 6, 4, 8
    fn = E._make_step(cfg, S, R, PP, ps, False, kernel="xla")
    pools = jax.eval_shape(
        lambda: PagedKVCache(cfg, S * PP + 1, ps, num_slots=S).pools)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
    lowered = fn.lower(jax.eval_shape(lambda: params), pools, i32(R),
                       i32(R), i32(R), jax.ShapeDtypeStruct((R,), bool),
                       i32(S + 1, PP), i32(S, 1),
                       jax.ShapeDtypeStruct((S + 1,), bool))
    _, locs = _scope_paths(lowered)
    for scope in ("embed", "norm", "conv_in", "short_conv", "conv_out",
                  "qkv", "qk_norm", "rope", "kv_write", "paged_attn",
                  "attn_out", "ffn", "moe_route", "moe_experts", "head",
                  "sample"):
        assert _has_scope(locs, scope), scope


# ------------------------------------------------ the flat walk's ring ---

@pytest.mark.parametrize("Hkv,dh,Hq", [(8, 64, 32), (4, 128, 20),
                                       (8, 128, 64)],
                         ids=["heads-of-64", "heads-of-128",
                              "heads-of-128-x8"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                       ("bfloat16", 2e-2)])
def test_flat_walk_folds_whole_pairs(Hkv, dh, Hq, dtype, tol):
    """The flat 1,024-lane page of this cell (32 query heads over 8
    key/value heads of 64: a head's ``[k | v]`` pair one lane tile), of
    the Falcon-H1 cell (20 over 4 heads of 128: a pair two lane tiles)
    and the 2,048-lane page of the EXAONE cell's full layer (64 over 8
    heads of 128, 64 KiB a bf16 page): the walk (interpreted here) takes
    them through the ring with the dense form of the flat fold: groups
    of 16 pages a turn (8 of the 64 KiB pages, 8 in float32), several
    groups a row, a short last block of rows."""
    from mxnet_tpu.kernels.paged_attention import (
        paged_attention, paged_attention_reference, walk_geometry)
    G16 = 16 if Hkv * dh == 512 else 8
    assert walk_geometry(Hkv, dh, 16, 128, "bfloat16", flat=True) \
        == (G16, G16, 32, 4)
    rs = np.random.RandomState(3)
    T, PP, ps = 35, 20, 16
    G = walk_geometry(Hkv, dh, ps, PP, dtype, flat=True)[0]
    assert G < PP
    NP = T * PP + 1
    pool = jnp.asarray(rs.randn(NP, ps, Hkv * 2 * dh), dtype)
    q = jnp.asarray(rs.randn(T, Hq, dh), dtype)
    bt = jnp.asarray(rs.permutation(np.arange(1, NP))[:T * PP]
                     .reshape(T, PP), jnp.int32)
    pos = jnp.asarray(rs.randint(0, PP * ps, T), jnp.int32).at[:6].set(
        jnp.asarray([0, PP * ps - 1, ps - 1, ps, G * ps - 1, G * ps]))
    got = paged_attention(q, pool, None, bt, pos, page_size=ps,
                          interpret=True)
    want = paged_attention_reference(q, pool, None, bt, pos, page_size=ps)
    assert got.shape == (T, Hq, dh)
    assert float(jnp.max(jnp.abs(got - want))) <= tol


# ------------------------------------------------ the benchmark's cell ---

def test_model_math_counts_the_cut():
    """The cut's arithmetic: 16.78 M a convolution operator, 10.49 M an
    attention operator, 44.04 M a dense SwiGLU, 11.01 M an expert, 352.4 M
    an expert layer's experts and router, 134.2 M the embedding: 3.93 G
    parameters; 32 KiB a page of one attention layer, 8 KiB a window."""
    import model_math_lfm2_moe as mm
    c = _CONFIG
    assert mm.layer_kinds(c) == (9, 3, 2, 10)
    assert mm.conv_operator_params(c) == 2048 * 6144 + 2048 * 2048 + 3 * 2048
    assert round(mm.conv_operator_params(c) / 1e6, 2) == 16.78
    assert mm.attention_operator_params(c) == 2 * 2048 * 2048 \
        + 2 * 2048 * 512 + 2 * 64
    assert round(mm.attention_operator_params(c) / 1e6, 2) == 10.49
    assert round(mm.dense_ffn_params(c) / 1e6, 2) == 44.04
    assert round(mm.expert_matmul_params(c) / 1e6, 2) == 11.01
    assert round((32 * mm.expert_matmul_params(c) + mm.router_params(c))
                 / 1e6, 1) == 352.4
    assert round(mm.embedding_params(c) / 1e6, 1) == 134.2
    assert round(mm.total_params(c) / 1e9, 2) == 3.93
    assert mm.expert_bytes(c) == 3 * 2048 * 1792 * 2
    assert mm.page_bytes(c) == 32768 and mm.window_bytes(c) == 9 * 8192
    assert mm.kv_read_bytes(c, 10) == 10 * 32768 * 3
    assert mm.attention_flops(c, 1) == 4 * 32 * 64
    assert mm.serve_flops(c, 1, 0, 0, 3) - mm.serve_flops(c, 1, 0, 0, 0) \
        == 2 * 3 * mm.expert_matmul_params(c)
    assert mm.serve_flops(c, 0, 5, 0, 0) == 3 * 5 * 4 * 32 * 64


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
    r = _run("run.py", "--seed", str(2 ** 31 + 36), "--trace", str(trace))
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
    assert sum(k["resets"] for k in turns["kinds"].values()) > 0


def test_chipbench_control_and_faults_come_out_not_correct(ref):
    """``calibrate.py --rehearse``: the program inside the toy limits,
    the fp8 control and every planted fault outside one of them."""
    import compare
    limits = compare.load_limits(CELL, rehearse=True)
    r = _run("calibrate.py", "--seeds", str(2 ** 31 + 37), "--controls",
             "1")
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert compare.judge(out["program"], limits)[0] is True
    others = {k: v for k, v in out.items()
              if k.startswith(("control_", "fault_"))}
    assert sorted(others) == sorted(
        ["control_fp8"] + ["fault_" + f for f in ref.FAULTS])
    for name, readings in others.items():
        assert compare.judge(readings, limits)[0] is False, name


def test_benchmark_json_names_the_cell_and_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {
        "name": CELL, "config": "lfm2_8b_a1b_l12",
        "traffic": "closed128_p256-1024_o512-1000", "chips": 1,
        "why": entry["why"]}
    assert next(c for c in bench["configs"]
                if c["name"] == "lfm2_8b_a1b_l12")["reduced"] \
        == ["num_hidden_layers", "layer_types"]
    cell = {"name": CELL, "bench": bench}
    per_layer = [m["name"] for m in chipbench_run.metrics_for(
        cell, "per_layer")]
    # (the last four: what a stall was, every serving cell's, PR 38;
    # two more of the training cell close ``per_layer``)
    assert per_layer[-4:] == ["turn_stall_max_ms.serve",
                              "stall_offcpu_share.serve",
                              "stall_host_late_share.serve",
                              "stall_runtime_busy_share.serve"]
    per_layer = per_layer[:-4]
    assert per_layer[-5:] == ["moe_expert_bw_share.serve",
                              "moe_rows_per_expert.serve",
                              "kv_chain_fill_share.serve",
                              "moe_load_max_ratio.serve",
                              "moe_weight_fetch_ratio.serve"]
    assert next(m for m in bench["per_layer"]
                if m["name"] == "moe_load_max_ratio.serve") == {
        "name": "moe_load_max_ratio.serve", "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "expert layer",
        "moves": "serve_tok_s", "workloads": [CELL]}
    assert "ssm_state_bw_share.serve" not in per_layer
    assert "latent_read_bw_share.serve" not in per_layer
    assert "step_mfu.serve" in per_layer and len(per_layer) == 22
    assert [m["name"] for m in chipbench_run.metrics_for(
        cell, "end_to_end")] == ["setup_s", "serve_tok_s", "itl_p95_ms"]
    # the new reader: the heaviest expert over the mean, the experts from
    # the configuration; silent where the program books no such counter
    reader = chipbench_run.load_module("layer_metrics",
                                       "moe_load_max_ratio.serve")
    cell = {"config": {"num_experts": 32}, "device": {"kind": "TPU v5 lite"}}
    assert reader.read(cell, {}, {"moe_pairs": 1024, "moe_pairs_max": 48},
                       None) == 1.5
    assert reader.read(cell, {}, {"steps": 5, "moe_pairs": 7}, None) is None
    assert reader.read({"config": {}, "device": {}}, {},
                       {"moe_pairs": 7, "moe_pairs_max": 2}, None) is None
