"""EXAONE-MoE (sliding-window attention layers that keep a ring of their
last positions per slot and no pages beside NoPE full-attention layers
that keep pages; norms after each branch and none before; a share of the
routed experts and a shared expert; K-EXAONE-236B-A23B's ``model_type``)
through ``models/exaone_moe.py`` and the paged serving engine, held to the
plain reference ``chipbench/reference/exaone_moe.py`` on the CPU: toy sizes
of the same structure (the configuration file's ``rehearse`` group: a
window of 8, 2 of 16 experts held), seeded weights, float32.

Tolerances.  The dense forward and the reference compute the same
function in float32 with another order of operations (the window from a
ring and the call's rows against an explicit mask over the whole
sequence, a grouped product over sorted pairs against a loop over
experts): their logits (deviation 2) agree to 1e-4 of the largest logit.
The engine adds the paged softmax's order of summation: a served token's
reference logit lies within 1e-3 of the reference's best, and is the
reference's own choice wherever the reference's top-2 margin exceeds
that.  The same engine computing in bfloat16 misses that by an order and
more (``test_bfloat16_fails_the_float32_tolerance``), and so does the
engine held to the reference with any one of its planted departures
(``test_planted_faults_fail_the_engines_tolerance``).
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

from mxnet_tpu.models import exaone_moe as M
from mxnet_tpu.parallel import moe
from mxnet_tpu.serving import ServingEngine
from mxnet_tpu.serving.paged_kv import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "k_exaone_236b_l5_ep8.reasoning_decode"
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
import run as chipbench_run                                   # noqa: E402

# the published flags and ratios at a toy size of the same structure:
# sliding x 3, full, sliding; 1 dense + 4 expert layers of 2 held experts
# of 16, 4 a token, a shared expert; 4 query heads over 2 key/value heads
# of 16; a window of 8
_CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "k_exaone_236b_l5_ep8.json")))
TOY = chipbench_run._overlay(_CONFIG, {
    k: v for k, v in _CONFIG["rehearse"].items() if k != "engine"})
W = TOY["sliding_window"]
SLIDING = sum(w > 0 for w in TOY["sliding_windows"])


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_exaone_moe",
        os.path.join(ROOT, "chipbench", "reference", "exaone_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model(ref):
    params = ref.make_params(7, TOY, "float32")
    return params, M.ExaoneMoeConfig.from_hf(TOY, dtype="float32")


def _tokens(seed, *shape):
    return np.random.RandomState(seed).randint(
        1, TOY["vocab_size"], shape).astype(np.int32)


def test_reference_imports_nothing_of_the_program(ref):
    src = open(ref.__file__).read()
    assert "mxnet_tpu" not in src and "ragged" not in src
    assert 'default_matmul_precision("highest")' in src


def test_config_file_states_the_cut():
    """Every published width of the catalog row; the depth, the layer
    lists, the experts held, the vocabulary and the MTP layer cut; the
    deployment, the departures and what was assumed, with the local
    source of the layer's equations."""
    c = _CONFIG
    assert c["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "sliding_windows", "num_experts", "vocab_size",
        "num_nextn_predict_layers", "mtp_layer_types", "mtp_sliding_windows"]
    assert set(c["reduced_why"]) == set(c["reduced"])
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "max_position_embeddings": 262144, "model_type": "exaone_moe",
        "moe_intermediate_size": 2048, "n_group": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 8, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "sliding_window": 128,
        "sliding_window_pattern": "LLLG", "tie_word_embeddings": False,
        "topk_group": 1,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: c[k] for k in published} == published
    assert (c["num_hidden_layers"], c["num_experts"], c["router_width"],
            c["ep_chips"], c["ep_rank"], c["vocab_size"]) \
        == (5, 16, 128, 8, 0, 153600 // 8)
    assert c["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert c["sliding_windows"] == [128, 128, 128, 0, 128]
    for key in ("deployment", "departures", "assumed"):
        assert c[key]
    assert "modeling_exaone4.py lines 217-228" in \
        c["assumed"]["layer equations"]
    cfg = M.ExaoneMoeConfig.from_hf(c)
    assert (cfg.head_dim, cfg.n_heads, cfg.n_kv_heads) == (128, 64, 8)
    assert (cfg.held_first, cfg.held_count, cfg.n_routed_experts) \
        == (0, 16, 128)
    assert [pages for pages, _ in M.layer_cache(cfg)] \
        == [False, False, False, True, False]
    assert M.layer_cache(cfg)[0][1] == {"win": ((128, 2048), "bfloat16")}


def test_forward_matches_reference(ref, model):
    params, cfg = model
    tokens = _tokens(0, 2, 40)
    want = ref.decoder_logits(params, tokens, TOY)
    got = jax.jit(lambda p, t: M.forward(p, cfg, t))(params,
                                                     jnp.asarray(tokens))
    assert float(jnp.std(want)) > 1.0        # the logits are alive
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 1e-4 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("how", [
    dict(fault="window_left_out"), dict(fault="window_off_by_one"),
    dict(fault="rope_on_global"), dict(fault="pre_norm"),
    dict(fault="no_qk_norm"), dict(fault="no_shared_expert"),
    dict(fault="ring_zero_filled"), dict(precision="fp8")],
    ids=lambda how: next(iter(how.values())))
def test_reference_faults_and_fp8_move_the_logits(ref, model, how):
    """Each planted departure from the published layer, and the fp8
    control, is far outside the tolerance the forward is held to."""
    params, _ = model
    tokens = _tokens(0, 1, 48)
    want = ref.decoder_logits(params, tokens, TOY)
    assert sorted(ref.FAULTS) == sorted(
        h["fault"] for h in (
            dict(fault="window_left_out"), dict(fault="window_off_by_one"),
            dict(fault="rope_on_global"), dict(fault="pre_norm"),
            dict(fault="no_qk_norm"), dict(fault="no_shared_expert"),
            dict(fault="ring_zero_filled")))
    bad = ref.decoder_logits(params, tokens, TOY, **how)
    assert float(jnp.max(jnp.abs(bad - want))) > 0.5


def test_init_params_layout_is_the_references(ref, model):
    params, cfg = model
    mine = M.init_params(jax.random.PRNGKey(0), cfg, "float32")
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(params)
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(mine)] \
        == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(params)]
    assert ["router" in p for p in params["layers"]] \
        == [False, True, True, True, True]
    assert params["lm_head"].shape == (64, 2048)        # untied
    assert params["layers"][1]["router"].shape == (64, 16)
    assert params["layers"][1]["ew_gate"].shape == (2, 64, 32)
    assert params["layers"][1]["sw_gate"].shape == (64, 32)


# ------------------------------------------------------------ the ring ---

def _dense_window(q, row_pos, row_slot, hist):
    """Each row's attention over (p - W, p] of its own sequence, from a
    dense history: ``hist[s]`` (n, Hkv, 2 dh) every position of slot s
    up to the call, the call's rows after."""
    Hq, dh = q.shape[1], q.shape[2]
    rep = Hq // hist[0].shape[1]
    out = []
    for r in range(q.shape[0]):
        s, p = int(row_slot[r]), int(row_pos[r])
        keys = hist[s][max(0, p - W + 1):p + 1]
        K = jnp.repeat(keys[..., :dh], rep, axis=1)
        V = jnp.repeat(keys[..., dh:], rep, axis=1)
        sc = jnp.einsum("hd,khd->hk", q[r], K) / np.sqrt(dh)
        out.append(jnp.einsum("hk,khd->hd", jax.nn.softmax(sc, -1), V))
    return jnp.stack(out)


def test_slot_window_against_a_dense_window():
    """One call of decode rows and two prefill chunks over dirty rings:
    slots whose sequences start in the call (their old entries absent,
    not zero), one past the window, chunks that straddle the ring at odd
    offsets; a dead row in the scratch slot.  Then the rings hold each
    slot's last W positions at ``p mod W``."""
    rs = np.random.RandomState(0)
    Hq, Hkv, dh, S = 4, 2, 16, 5
    L = Hkv * 2 * dh
    # slot: (positions before the call, rows in the call)
    plan = {0: (13, 1), 1: (3, 1), 2: (0, 1), 3: (5, 7), 4: (19, 6)}
    hist = {s: jnp.asarray(rs.randn(n + m, Hkv, 2 * dh), jnp.float32)
            for s, (n, m) in plan.items()}
    pool = jnp.asarray(rs.randn(S + 1, W, L), jnp.float32)      # dirty
    for s, (n, _) in plan.items():
        for p in range(n):
            pool = pool.at[s, p % W].set(hist[s][p].reshape(L))
    row_slot, row_pos = [], []
    for s, (n, m) in plan.items():
        row_slot += [s] * m
        row_pos += list(range(n, n + m))
    row_slot += [S]                                     # a dead row
    row_pos += [0]
    T = len(row_slot)
    row_slot = jnp.asarray(row_slot, jnp.int32)
    row_pos = jnp.asarray(row_pos, jnp.int32)
    kv = jnp.concatenate([hist[s][n:] for s, (n, _) in plan.items()]
                         + [jnp.zeros((1, Hkv, 2 * dh))])
    q = jnp.asarray(rs.randn(T, Hq, dh), jnp.float32)
    out, new, rows, read = jax.jit(
        M.slot_window, static_argnums=6)(q, kv[..., :dh], kv[..., dh:],
                                         row_pos, row_slot, pool, 7)
    want = _dense_window(q[:-1], row_pos[:-1], row_slot[:-1], hist)
    assert float(jnp.max(jnp.abs(out[:-1] - want))) <= 1e-5
    # what the attention had to read: min(p0, W - 1) entries and the rows
    assert int(rows) == T - 1
    assert int(read) == sum(min(n, W - 1) + m for n, m in plan.values())
    for s, (n, m) in plan.items():
        for p in range(max(0, n + m - W), n + m):
            np.testing.assert_array_equal(
                np.asarray(new[s, p % W]), np.asarray(hist[s][p].reshape(L)))


def _chunked_logits(params, cfg, tokens, chunk):
    """One sequence through ``serve_block`` in calls of ``chunk`` rows:
    the rings carried in a two-slot pool (slot 1 the scratch) that starts
    dirty, the full layer's keys and values in an array as long as the
    sequence under a causal mask.  Every row's logits."""
    T = tokens.shape[0]
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keeps = M.layer_cache(cfg)
    pools = [{n: jnp.full((2,) + s, 9.0, d) for n, (s, d) in st.items()}
             for _, st in keeps]
    cache = [None if st else jnp.zeros((T, Hkv, 2 * dh)) for _, st in keeps]

    @jax.jit
    def call(params, pools, cache, toks, row_pos):
        counts = M.StepCounts(jnp.ones(toks.size, bool))
        x = M.serve_embed(params, cfg, toks, row_pos)
        pools, cache = list(pools), list(cache)
        for i, layer in enumerate(params["layers"]):
            def attend(q, k, v, i=i):
                kv = cache[i] = cache[i].at[row_pos].set(
                    jnp.concatenate([k, v], -1))
                K = jnp.repeat(kv[..., :dh], Hq // Hkv, 1)
                V = jnp.repeat(kv[..., dh:], Hq // Hkv, 1)
                s = jnp.einsum("qhd,khd->hqk", q, K) / np.sqrt(dh)
                seen = jnp.arange(T)[None] <= row_pos[:, None]
                p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), -1)
                return jnp.einsum("hqk,khd->qhd", p, V)
            state = M.SlotState(pools[i], jnp.zeros(toks.size, jnp.int32),
                                None, chunk) if keeps[i][1] else None
            x = M.serve_block(layer, cfg, x, row_pos, attend, state,
                              counts)
            if state is not None:
                pools[i] = state.pools
        return M.serve_logits(params, cfg, x,
                              jnp.arange(toks.size)[None])[0], pools, cache

    logits = []
    for lo in range(0, T, chunk):
        rows = np.arange(lo, min(lo + chunk, T))
        out, pools, cache = call(params, pools, cache,
                                 jnp.asarray(tokens[rows]),
                                 jnp.asarray(rows, jnp.int32))
        logits.append(out)
    return jnp.concatenate(logits)


@pytest.mark.parametrize("chunk", [1, 3, 5, 8, 13])
def test_chunks_give_the_logits_of_one_chunk(model, chunk):
    """A sequence of 29 positions (3.6 windows) cut into calls of 1, 3,
    5, 8 and 13 rows: fewer than, as many as and more than the window,
    straddling the ring at odd offsets; rings that start dirty."""
    params, cfg = model
    tokens = _tokens(4, 29)
    dense = M.forward(params, cfg, jnp.asarray(tokens)[None])[0]
    got = _chunked_logits(params, cfg, tokens, chunk)
    scale = float(jnp.max(jnp.abs(dense)))
    assert float(jnp.max(jnp.abs(got - dense))) <= 1e-5 * scale


# ------------------------------------------------------ the expert layer ---

def test_shares_add_up_to_the_uncut_expert_layer(ref):
    """The 8 ranks' routed parts (2 held experts each), with the shared
    expert counted once, are the uncut reference's expert layer."""
    rs = np.random.RandomState(5)
    whole = dict(TOY, num_experts=16, router_width=16, ep_rank=0)
    layer = ref.make_params(11, dict(
        whole, num_hidden_layers=2, mlp_layer_types=["dense", "sparse"]),
        "float32")["layers"][1]
    m = jnp.asarray(2.0 * rs.randn(24, 64), jnp.float32)
    want = ref.expert_layer(m, layer, whole)
    cfg = M.ExaoneMoeConfig.from_hf(whole, dtype="float32")
    idx, w = moe.route_group_limited(
        jax.nn.sigmoid(jnp.dot(m, layer["router"],
                               precision=jax.lax.Precision.HIGHEST)),
        layer["router_bias"], n_group=1, topk_group=1, top_k=cfg.top_k,
        scale=cfg.routed_scaling_factor)
    want_idx, want_w = ref.route(m, layer, whole)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), rtol=1e-6)
    total, pairs = 0.0, 0
    for rank in range(8):
        cut = slice(2 * rank, 2 * rank + 2)
        y, n, hit, _, _ = moe.held_experts_ffn(
            m, layer["ew_gate"][cut], layer["ew_up"][cut],
            layer["ew_down"][cut], idx, w, held_first=2 * rank)
        # the reference, given the same share, agrees rank by rank
        share = dict(TOY, ep_rank=rank)
        mine = {k: (v[cut] if k.startswith("ew_") else v)
                for k, v in layer.items()}
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.expert_layer(
                m, mine, share, shared=False)), rtol=2e-5, atol=2e-5)
        assert int(hit) <= 2
        total, pairs = total + y, pairs + int(n)
    assert pairs == 24 * 4                       # every pair, once
    shared = ref.expert_layer(m, layer, whole) \
        - ref.expert_layer(m, layer, whole, shared=False)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


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


def _held_to_reference(ref, params, eng, rids, **how):
    """Every served token of ``rids`` against the reference's one full
    forward pass (computed ``how``), all requests in one array as long as
    a slot's pool (the zeros after a request touch none of its rows):
    (widest logit gap, tokens that differ where the reference's top-2
    margin exceeds the tolerance)."""
    reqs = [eng.requests[rid] for rid in rids]
    tokens = np.zeros((len(reqs), eng.max_seq), np.int32)
    for i, req in enumerate(reqs):
        seq = np.concatenate([req.prompt, np.asarray(req.generated,
                                                     np.int32)])
        tokens[i, :seq.size] = seq
    logits = np.asarray(ref.decoder_logits(params, tokens, TOY, **how))
    worst, wrong = 0.0, 0
    for req, rows in zip(reqs, logits):
        for i, tok in enumerate(req.generated):
            row = rows[req.prompt.size - 1 + i]
            top = np.sort(row)
            gap = float(top[-1] - row[tok])
            worst = max(worst, gap)
            wrong += gap > 0 and top[-1] - top[-2] > 1e-3
    return worst, wrong


# prompts from under a window to several windows, answers that carry the
# contexts to 5-7 windows
REQUESTS = ((5, 10), (19, 12), (30, 6), (9, 20), (17, 9), (26, 30))


def _submit_all(eng, seed=1):
    rs = np.random.RandomState(seed)
    return [eng.submit(rs.randint(1, TOY["vocab_size"], n), m)
            for n, m in REQUESTS]


def _dirty(eng):
    """Every ring of every slot full of another request's rows: a slot's
    first positions have to find them absent all the same."""
    eng.cache.pools = [
        {name: jnp.full_like(a, 7.0) if name == "win" else a
         for name, a in pool.items()} for pool in eng.cache.pools]


def _window_reads(eng):
    """Wrap the engine's dispatch: for every step, the live rows and what
    the sliding layers' attention had to read, from the rows staged."""
    seen = []
    real = eng._dispatch

    def dispatch(plan):
        b = plan.buf
        live = b.row_live
        slots = {}
        for s, p in zip(b.row_slot[live], b.row_pos[live]):
            p0, n = slots.get(int(s), (int(p), 0))
            slots[int(s)] = (min(p0, int(p)), n + 1)
        seen.append((int(live.sum()),
                     sum(min(p0, W - 1) + n for p0, n in slots.values())))
        return real(plan)
    eng._dispatch = dispatch
    return seen


@pytest.mark.parametrize("kernel,overlap", [
    ("xla", False), ("pallas", False), ("xla", True), ("pallas", True)])
def test_engine_serves_the_reference_tokens(ref, model, kernel, overlap):
    """Chunked prefill of several slots (prompts longer than a chunk and
    than the window among them), decode through the full layer's pages
    and the sliding layers' rings far past the window, six requests over
    three slots (slot reuse: a reused slot's old ring entries are absent,
    as a first one's dirty pool is)."""
    eng = _engine(model, kernel=kernel, overlap=overlap)
    _dirty(eng)
    seen = _window_reads(eng)
    rids = _submit_all(eng)
    eng.run()
    eng.close()
    assert all(eng.requests[r].state == "done"
               and len(eng.requests[r].generated) == m
               for r, (_, m) in zip(rids, REQUESTS))
    worst, wrong = _held_to_reference(ref, model[0], eng, rids)
    assert worst <= 1e-3 and wrong == 0
    s = eng.stats
    # the rings' counts, booked with the step's tokens: every live row
    # through the four sliding layers, and what their attention had to
    # read, min(p0, W - 1) ring entries and the call's rows a slot
    rows = s["decode_rows"] + s["prefill_rows"]
    assert s["win_rows"] == SLIDING * rows == SLIDING * sum(
        r for r, _ in seen)
    assert s["win_positions"] == SLIDING * sum(p for _, p in seen)
    assert s["win_rows"] < s["win_positions"] < SLIDING * W * rows
    # the expert layers' counts beside them, a share held
    assert 0 < s["moe_pairs"] <= rows * 4 * 4
    assert s["moe_weight_fetches"] == 3 * s["moe_experts_hit"]
    import model_math_exaone_moe as mm
    assert s["moe_expert_bytes"] == s["moe_experts_hit"] \
        * mm.expert_bytes(TOY, itemsize=4)
    # the walk reads each row's own pages of the one full layer; the
    # gather the whole window (the toy's flat page is 64 lanes: the
    # per-page grid, which books the window too)
    assert s["kv_pages_read"] == s["kv_pages_window"] > 0


@pytest.mark.parametrize("chunk", [3, 5, 13])
def test_engine_prefills_in_chunks_that_straddle_the_ring(ref, model, chunk):
    eng = _engine(model, prefill_chunk=chunk)
    _dirty(eng)
    rids = _submit_all(eng, seed=chunk)
    eng.run()
    worst, wrong = _held_to_reference(ref, model[0], eng, rids)
    assert worst <= 1e-3 and wrong == 0


def test_planted_faults_fail_the_engines_tolerance(ref, model):
    """The engine's tokens, held to the reference with ONE planted
    departure from the published layer, miss the tolerance that the
    reference as published keeps: each fault is seen."""
    eng = _engine(model)
    rids = _submit_all(eng, seed=3)
    eng.run()
    assert _held_to_reference(ref, model[0], eng, rids)[0] <= 1e-3
    for fault in ref.FAULTS:
        worst, _ = _held_to_reference(ref, model[0], eng, rids,
                                      fault=fault)
        assert worst > 1e-2, fault


def test_bfloat16_fails_the_float32_tolerance(ref, model):
    """The tolerance is tight enough to tell a lower precision."""
    params, cfg = model
    low = (jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 and a.ndim > 1 else a, params),
        dataclasses.replace(cfg, dtype="bfloat16"))
    eng = _engine(low)
    # 174 tokens: bfloat16 turns the argmax at about 4% of positions
    rids = _submit_all(eng) + _submit_all(eng, seed=5)
    eng.run()
    worst, _ = _held_to_reference(ref, params, eng, rids)
    assert worst > 1e-2


@pytest.mark.parametrize("overlap", [False, True])
def test_engine_preempt_resumes_to_the_same_tokens(ref, model, overlap):
    """A preempted request's rings are rebuilt by recomputation: it ends
    with the tokens an undisturbed engine serves."""
    calm = _engine(model, overlap=overlap)
    rids = _submit_all(calm, seed=2)
    calm.run()
    eng = _engine(model, overlap=overlap)
    assert _submit_all(eng, seed=2) == rids
    for _ in range(12):
        eng.step()
    victim = next(r for r in eng._slots if r is not None
                  and len(r.generated) + r.prompt.size > W)
    assert eng.preempt(victim.rid) is False      # no tier: recompute
    out = eng.run()
    eng.close()
    assert sorted(out) == rids and eng.stats["preemptions"] == 1
    assert all(eng.requests[r].generated == calm.requests[r].generated
               for r in rids)
    worst, wrong = _held_to_reference(ref, model[0], eng, rids)
    assert worst <= 1e-3 and wrong == 0


@pytest.mark.parametrize("how,names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_K=2), "spec_K"),
    (dict(tier_bytes=1 << 20), "tier"),
    (dict(kv_int8=True), "kv_int8"),
    (dict(tp=2), "tp > 1"),
    ("admit_prefilled", "hand-off"),
])
def test_engine_refuses_by_name_what_a_ring_lacks(model, how, names):
    with pytest.raises(ValueError,
                       match="ExaoneMoeConfig.*recurrent state.*" + names):
        if how == "admit_prefilled":
            _engine(model).admit_prefilled(
                np.ones(4, np.int32), [1], [1], max_new_tokens=4)
        else:
            _engine(model, **how)


def _leaves(pools):
    return [{k: (tuple(a.shape), str(a.dtype)) for k, a in p.items()}
            for p in pools]


def test_pools_follow_the_layers(model):
    """``"kv"`` on the full layer only, ``"win"`` on the sliding layers
    only; at the published sizes a ring is 128 rows of 2,048 lanes a
    slot, a page 16 of them."""
    eng = _engine(model)
    kv = {"kv": ((3 * 8 + 1, 8, 2 * 2 * 16), "float32")}
    win = {"win": ((3 + 1, W, 2 * 2 * 16), "float32")}
    assert _leaves(eng.cache.pools) == [win, win, win, kv, win]
    assert eng.cache.bytes_per_page == 8 * 2 * 2 * 16 * 4      # 1 of 5
    assert eng.cache.bytes_per_slot_state == 4 * W * 64 * 4    # 4 of 5
    cfg = M.ExaoneMoeConfig.from_hf(_CONFIG)
    cache = jax.eval_shape(lambda: PagedKVCache(cfg, 2, 16,
                                                num_slots=1).pools)
    assert [sorted(p) for p in cache] == [["win"]] * 3 + [["kv"], ["win"]]
    assert cache[3]["kv"].shape == (2, 16, 2048)
    assert cache[0]["win"].shape == (2, 128, 2048)


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
    for scope in ("embed", "qkv", "qk_norm", "rope", "win_attn",
                  "kv_write", "paged_attn", "attn_out", "post_norm", "ffn",
                  "moe_route", "moe_experts", "moe_shared", "head",
                  "sample"):
        assert _has_scope(locs, scope), scope


# ------------------------------------------------ the benchmark's cell ---

def test_model_math_counts_the_cut():
    """The cut's arithmetic: 113.25 M an attention operator, 37.75 M an
    expert, 755.76 M an expert layer on this chip, 452.98 M the dense
    layer, 117.96 M the embedding and as much the head: 3.712 G
    parameters; 64 KiB a page of the full layer, 512 KiB a ring."""
    import model_math_exaone_moe as mm
    c = _CONFIG
    assert mm.layer_kinds(c) == (4, 1, 1, 4)
    assert round(mm.attention_operator_params(c) / 1e6, 2) == 113.25
    assert round(mm.expert_matmul_params(c) / 1e6, 2) == 37.75
    assert round((mm.expert_layer_params(c)
                  + mm.attention_operator_params(c)) / 1e6, 2) == 755.76
    assert mm.attention_operator_params(c) + mm.dense_ffn_params(c) \
        == 452_985_088
    assert round(mm.embedding_params(c) / 1e6, 2) == 117.96
    assert round(mm.total_params(c) / 1e9, 3) == 3.712
    assert mm.page_bytes(c) == 65536 and mm.kv_row_bytes(c) == 4096
    assert mm.ring_bytes(c) == 4 * 128 * 4096
    assert mm.windowed_prompt_context(5, 128) == sum(range(5))
    assert mm.windowed_prompt_context(300, 128) \
        == sum(min(i, 128) for i in range(300))
    # a decode row at context 1,000 reads 1,000 keys in the full layer
    # and 128 in each of the four sliding ones
    assert mm.serve_flops(c, 0, 1000, 128, 0, 0) \
        == mm.attention_flops(c, 1000) + 4 * mm.attention_flops(c, 128)
    assert mm.serve_flops(c, 0, 0, 0, 0, 3) \
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
    r = _run("run.py", "--seed", str(2 ** 31 + 40), "--trace", str(trace))
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {"bad_answers", "missing_answers",
                                     "logit_gap", "logit_gap_p99"}
    assert line["rehearse"] is True and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    turns = json.loads(next(ln for ln in r.stderr.splitlines()
                            if ln.startswith("turns "))[6:])
    assert turns["steps"] > 0
    assert sum(k["resets"] for k in turns["kinds"].values()) > 0


def test_chipbench_control_and_faults_come_out_not_correct(ref):
    """``calibrate.py --rehearse``: the program inside the toy limits,
    the fp8 control and every planted fault outside one of them."""
    import compare
    limits = compare.load_limits(CELL, rehearse=True)
    r = _run("calibrate.py", "--seeds", str(2 ** 31 + 41), "--controls",
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
        "name": CELL, "config": "k_exaone_236b_l5_ep8",
        "traffic": "closed128_p512-2048_o1024-3072", "chips": 1,
        "why": entry["why"]}
    conf = next(c for c in bench["configs"]
                if c["name"] == "k_exaone_236b_l5_ep8")
    assert conf["reduced"] == _CONFIG["reduced"]
    assert conf["source"] == _CONFIG["source"]
    # no metric of its own: the accepted per_layer list is left as it was,
    # and what later cells append after it is not reported in this one
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("turn_stall_max_ms.serve")
    assert names[at:at + 6] == [
        "turn_stall_max_ms.serve", "stall_offcpu_share.serve",
        "stall_host_late_share.serve", "stall_runtime_busy_share.serve",
        "step_stall_share.train", "turn_stall_max_ms.train"]
    assert all(CELL not in m.get("workloads", [CELL])
               for m in bench["per_layer"][at + 6:])
    cell = {"name": CELL, "bench": bench}
    per_layer = [m["name"] for m in chipbench_run.metrics_for(
        cell, "per_layer")]
    # every routed paged cell's metric; not another family's state,
    # latent rows or heaviest expert
    gigachat = {"name": "gigachat3_702b_l5_ep16.long_decode",
                "bench": bench}
    theirs = [m["name"] for m in chipbench_run.metrics_for(
        gigachat, "per_layer")]
    assert per_layer == [m for m in theirs
                         if m != "latent_read_bw_share.serve"]
    assert "ssm_state_bw_share.serve" not in per_layer
    assert "moe_load_max_ratio.serve" not in per_layer
    assert [m["name"] for m in chipbench_run.metrics_for(
        cell, "end_to_end")] == ["setup_s", "serve_tok_s", "itl_p95_ms"]
