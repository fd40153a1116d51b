"""Motif-3 (grouped differential latent attention in sliding-window layers
that keep a ring of latent rows per slot beside full layers that keep
latent pages; a four-stream mHC residual; PolyNorm MLPs; a share of the
routed experts and a shared expert; Motif-3-Beta's ``model_type``)
through ``models/motif3.py`` and the paged serving engine, held to the
plain reference ``chipbench/reference/motif3.py`` on the CPU: toy sizes of
the same structure (the configuration file's ``rehearse`` group: five
heads a group, a window of 16, 2 of 16 experts held), seeded weights,
float32.

Tolerances.  The dense forward and the reference compute the same
function in float32 with another order of operations (the absorbed
attention with its differential in latent space against the expanded
one, the window from a ring against an explicit mask, a grouped product
over sorted pairs against a loop over experts): their logits (deviation
2) agree to 1e-4 of the largest logit.  The engine adds the paged
softmax's order of summation: a served token's reference logit lies
within 1e-3 of the reference's best, and is the reference's own choice
wherever the reference's top-2 margin exceeds that.  The same engine
computing in bfloat16 misses that by an order and more
(``test_bfloat16_fails_the_float32_tolerance``), and so does the engine
held to the reference with any one of its planted departures or in fp8
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

from mxnet_tpu.models import motif3 as M
from mxnet_tpu.parallel import moe
from mxnet_tpu.serving import ServingEngine
from mxnet_tpu.serving.paged_kv import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "motif3_beta_l5_ep8.reasoning_decode"
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
import run as chipbench_run                                   # noqa: E402

# the published flags and ratios at a toy size of the same structure:
# sliding x 3, full, sliding; 1 dense + 4 expert layers of 2 held experts
# of 16, 4 a token, a shared expert; 10 heads over 2 groups (4 signal
# heads and a noise head each) against a latent row of 56 + 8; a window
# of 16; 4 streams
_CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "motif3_beta_l5_ep8.json")))
TOY = chipbench_run._overlay(_CONFIG, {
    k: v for k, v in _CONFIG["rehearse"].items() if k != "engine"})
W = TOY["sliding_window"]
SLIDING = 4


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_motif3",
        os.path.join(ROOT, "chipbench", "reference", "motif3.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model(ref):
    params = ref.make_params(7, TOY, "float32")
    return params, M.Motif3Config.from_hf(TOY, dtype="float32")


def _tokens(seed, *shape):
    return np.random.RandomState(seed).randint(
        1, TOY["vocab_size"], shape).astype(np.int32)


def test_reference_imports_nothing_of_the_program(ref):
    src = open(ref.__file__).read()
    assert "mxnet_tpu" not in src and "ragged" not in src
    assert 'default_matmul_precision("highest")' in src


def test_config_file_states_the_cut():
    """Every published key of the catalog row; the depth, the dense
    layers, the experts held, the vocabulary and the MTP layer cut; the
    deployment, the departures and every reading assumed."""
    c = _CONFIG
    assert c["reduced"] == ["num_hidden_layers", "n_dense_first_layers",
                            "num_experts", "vocab_size",
                            "num_nextn_predict_layers"]
    assert set(c["reduced_why"]) == set(c["reduced"])
    published = {
        "attention_cls": "gdla", "diff_v2": True,
        "elementwise_attn_output_gate": True, "experts_top_k": 8,
        "head_dim": 192, "headwise_attn_output_gate": False,
        "hidden_act": "poly_norm", "hidden_size": 4096,
        "intermediate_size": 12288, "kv_lora_rank": 512,
        "max_window_layers": 9, "mhc_enabled": True,
        "mhc_expansion_rate": 4, "mhc_identity_init": False,
        "mhc_sinkhorn_iters": 20, "model_type": "Motif",
        "moe_intermediate_size": 1280, "num_attention_heads": 80,
        "num_key_value_heads": 16, "num_noise_heads": 16,
        "num_shared_experts": 1, "q_lora_rank": 1024,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2, "score_before_experts": False,
        "score_func": "sigmoid", "sliding_window": 128,
        "sliding_window_pattern": "interleave", "sliding_window_period": 4,
        "swa_rope_theta": 10000, "tie_word_embeddings": False,
        "use_sliding_window": True, "v_head_dim": 128,
        "polynorm_output_scale": 0.5, "polynorm_bias_clamp": 0.5,
        "hidden_clamp": 1000000}
    assert {k: c[k] for k in published} == published
    assert c["rope_scaling"]["apply_yarn_scaling"] is False
    assert (c["num_hidden_layers"], c["n_dense_first_layers"],
            c["num_experts"], c["router_width"], c["ep_chips"], c["ep_rank"],
            c["vocab_size"], c["num_nextn_predict_layers"]) \
        == (5, 1, 48, 384, 8, 0, 220160 // 8, 0)
    for key in ("deployment", "departures", "assumed"):
        assert c[key]
    for reading in ("heads", "differential", "output gate", "rotary",
                    "layer pattern", "mhc", "mhc init", "polynorm",
                    "routing", "weight scales"):
        assert c["assumed"][reading], reading
    cfg = M.Motif3Config.from_hf(c)
    assert cfg.sliding_windows == (128, 128, 128, 0, 128)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.group_heads,
            cfg.signal_heads) == (80, 16, 5, 64)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) \
        == (128, 64, 128)
    assert cfg.softmax_scale == 192 ** -0.5 and cfg.rope_factor == 1.0
    assert (cfg.held_first, cfg.held_count, cfg.n_routed_experts) \
        == (0, 48, 384)
    assert (cfg.routed_scaling_factor, cfg.mhc_rate,
            cfg.mhc_sinkhorn_iters) == (2.0, 4, 20)
    assert [pages for pages, _ in M.layer_cache(cfg)] \
        == [False, False, False, True, False]
    assert M.layer_cache(cfg)[0][1] == {"win": ((128, 640), "bfloat16")}


def test_forward_matches_reference(ref, model):
    params, cfg = model
    tokens = _tokens(0, 2, 40)
    want = ref.decoder_logits(params, tokens, TOY)
    got = jax.jit(lambda p, t: M.forward(p, cfg, t))(params,
                                                     jnp.asarray(tokens))
    assert float(jnp.std(want)) > 1.0        # the logits are alive
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 1e-4 * float(jnp.max(jnp.abs(want)))


def test_init_params_layout_is_the_references(ref, model):
    params, cfg = model
    mine = M.init_params(jax.random.PRNGKey(0), cfg, "float32")
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(params)
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(mine)] \
        == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(params)]
    assert ["router" in p for p in params["layers"]] \
        == [False, True, True, True, True]
    layer = params["layers"][1]
    assert layer["wq_b"].shape == (32, 10 * 24)
    assert layer["wkv_b"].shape == (56, 2 * (16 + 16))     # 2 groups
    assert layer["w_lambda"].shape == (64, 8)              # signal heads
    assert layer["w_ogate"].shape == (64, 8 * 16)
    assert layer["wo"].shape == (8 * 16, 64)
    assert layer["mhc_attn"].shape == (4 * 64, 24)
    assert layer["ew_poly"].shape == (2, 4) and layer["sw_poly"].shape == (4,)
    # what stays float32 whatever the weights' dtype
    low = M.init_params(jax.random.PRNGKey(0), cfg, "bfloat16")["layers"][1]
    assert {k for k, v in low.items() if v.dtype == jnp.float32} \
        == set(M.FLOAT32) - {"poly"}


# ------------------------------------------------------- the pieces ---

@pytest.mark.parametrize("window", [0, W], ids=["full", "sliding"])
def test_absorbed_differential_is_the_expanded_one(ref, model, window):
    """One layer's attention: the program's absorbed heads, their
    differential taken in latent space before ``W_uv``, against the
    reference's per-group keys and values and the differential of the
    heads' outputs; the full layer over a causal softmax, the sliding one
    through its ring, both for two sequences of 3 windows."""
    params, cfg = model
    layer = params["layers"][1]
    B, T = 2, 3 * W
    u = jnp.asarray(np.random.RandomState(3).randn(B, T, 64), jnp.float32)
    want = ref._attention(u, layer, TOY, window, "float32", None)
    H, R = cfg.n_heads, cfg.kv_lora_rank
    row_pos = jnp.tile(jnp.arange(T, dtype=jnp.int32), B)
    row_slot = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)

    def attend(q, row):
        q, row = q.reshape(B, T, H, -1), row.reshape(B, T, -1)
        s = jnp.einsum("bqhw,bkw->bhqk", q, row) * cfg.softmax_scale
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.einsum("bhqk,bkr->bqhr", jax.nn.softmax(s, -1),
                          row[..., :R]).reshape(B * T, H, R)
    state = None
    if window:
        _, keeps = M.layer_cache(cfg)[0]
        state = M.SlotState({n: jnp.zeros((B + 1,) + s, d)
                             for n, (s, d) in keeps.items()},
                            row_slot, None, B * T)
    got = M._gdla(layer, cfg, u.reshape(B * T, 64), row_pos, attend, state,
                  M.StepCounts(jnp.ones(B * T, bool)))
    assert float(jnp.std(want)) > 0.1
    np.testing.assert_allclose(np.asarray(got).reshape(B, T, 64),
                               np.asarray(want), rtol=1e-4, atol=1e-5)


def test_sinkhorn_is_doubly_stochastic_after_20_rounds(ref, model):
    """Every token's ``H_res`` of every branch and layer of the seeded
    model, on streams that differ: rows and columns sum to 1 within 1e-5
    after the published 20 rounds; the program's and the reference's
    maps agree; ``H_res`` is no permutation and not uniform."""
    params, cfg = model
    x = jnp.asarray(np.random.RandomState(2).randn(64, 4, 64), jnp.float32)
    for layer in params["layers"]:
        for branch in ("attn", "ffn"):
            u, (post, res) = M._mhc_pre(layer, cfg, branch, x)
            pre_r, post_r, res_r = ref.mhc_maps(x, layer, branch, TOY)
            np.testing.assert_allclose(np.asarray(res), np.asarray(res_r),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(post), np.asarray(post_r),
                                       rtol=1e-5)
            r = np.asarray(res, np.float64)
            assert np.abs(r.sum(-1) - 1).max() <= 1e-5
            assert np.abs(r.sum(-2) - 1).max() <= 1e-5
            assert 0.28 < r.max(-1).mean() < 0.6
    # the published width's draws (``assumed``): logits of deviation
    # sqrt(mhc_alpha^2 + mhc_res_bias_std^2), many tokens
    a = _CONFIG["assumed"]
    sd = (a["mhc_alpha"] ** 2 + a["mhc_res_bias_std"] ** 2) ** 0.5
    m = np.exp(sd * np.random.RandomState(0).randn(20000, 4, 4))
    r = np.asarray(M.sinkhorn(jnp.asarray(m, jnp.float32), 20), np.float64)
    assert np.abs(r.sum(-1) - 1).max() <= 1e-5


@pytest.mark.parametrize("rows", [False, True], ids=["shared", "per_row"])
def test_polynorm_is_its_formula(rows):
    """``PN(z) = s (w0 N(z^3) + w1 N(z^2) + w2 N(z)) + clamp(b, +-c)``
    against float64, one set of weights for all rows or a row's own
    (an expert's); biases past the clamp among them."""
    rs = np.random.RandomState(4)
    z = rs.randn(6, 40) * 1.5
    w = rs.randn(6, 4) if rows else np.array([0.4, -0.2, 0.7, -0.9])
    got = M.polynorm(jnp.asarray(z, jnp.float32), jnp.asarray(w, jnp.float32),
                     0.5, 0.5, 1e-5)
    w = w if rows else np.broadcast_to(w, (6, 4))

    def n(v):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + 1e-5)
    want = 0.5 * (w[:, :1] * n(z ** 3) + w[:, 1:2] * n(z ** 2)
                  + w[:, 2:3] * n(z)) + np.clip(w[:, 3:], -0.5, 0.5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


def test_held_experts_ffn_default_is_swiglu_bit_for_bit():
    """Without ``act`` the expert layer is the SwiGLU it always was: the
    same numbers as ``silu(gate) * up`` passed as the activation."""
    rs = np.random.RandomState(8)
    T, D, F, E, K = 24, 32, 16, 4, 3
    x = jnp.asarray(rs.randn(T, D), jnp.float32)
    wg, wu = (jnp.asarray(rs.randn(E, D, F) / 6, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rs.randn(E, F, D) / 4, jnp.float32)
    idx = jnp.asarray(rs.randint(0, 8, (T, K)), jnp.int32)
    w = jnp.asarray(rs.rand(T, K), jnp.float32)
    live = jnp.asarray(rs.rand(T) > 0.2)
    plain = moe.held_experts_ffn(x, wg, wu, wd, idx, w, held_first=2,
                                 live=live)
    named = moe.held_experts_ffn(
        x, wg, wu, wd, idx, w, held_first=2, live=live,
        act=lambda g, u, e: jax.nn.silu(g) * u)
    for a, b in zip(plain, named):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the expert ids the activation sees: each sorted pair's own
    seen = []
    moe.held_experts_ffn(x, wg, wu, wd, idx, w, held_first=2, live=live,
                         act=lambda g, u, e: seen.append(e) or g * u)
    want = np.sort(np.where((np.asarray(idx) >= 2) & (np.asarray(idx) < 6)
                            & np.asarray(live)[:, None],
                            np.asarray(idx) - 2, E).reshape(-1))
    np.testing.assert_array_equal(np.asarray(seen[0]), want)


def test_shares_add_up_to_the_uncut_expert_layer(ref):
    """The 8 ranks' routed parts (2 held experts each, each with its own
    PolyNorm), with the shared expert counted once, are the uncut
    reference's expert layer."""
    rs = np.random.RandomState(5)
    whole = dict(TOY, num_experts=16, router_width=16, ep_rank=0,
                 num_hidden_layers=2, n_dense_first_layers=1)
    layer = ref.make_params(11, whole, "float32")["layers"][1]
    m = jnp.asarray(rs.randn(24, 64), jnp.float32)
    want = ref.expert_layer(m, layer, whole)
    cfg = M.Motif3Config.from_hf(whole, dtype="float32")
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
        mine = {k: (v[cut] if k.startswith("ew_") else v)
                for k, v in layer.items()}
        share = dataclasses.replace(cfg, held_first=2 * rank, held_count=2)
        counts = M.StepCounts(jnp.ones(24, bool))
        y = M._experts(mine, share, m, counts) \
            - M._poly_mlp(m, layer["sw_gate"], layer["sw_up"],
                          layer["sw_down"], layer["sw_poly"], share)
        # the reference, given the same share, agrees rank by rank
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.expert_layer(
                m, mine, dict(whole, num_experts=2, ep_rank=rank),
                shared=False)), rtol=2e-5, atol=2e-5)
        total, pairs = total + y, pairs + int(counts.counts[0])
    assert pairs == 24 * 4                       # every pair, once
    shared = ref.expert_layer(m, layer, whole) \
        - ref.expert_layer(m, layer, whole, shared=False)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ the ring ---

# slot: (positions before the call, rows in the call)
RING_PLANS = {
    # decode rows, two prefill chunks, a slot starting in the call
    "mixed": {0: (13, 1), 1: (3, 1), 2: (0, 1), 3: (5, 7), 4: (19, 6)},
    # decode rows alone: the window's pass does not run
    "decode": {0: (13, 1), 1: (3, 1), 2: (0, 1), 3: (21, 1)},
    # the call opens with a long chunk that wraps the ring twice over
    "prefill": {3: (2, 19), 1: (30, 1)},
}


@pytest.mark.parametrize("plan", sorted(RING_PLANS))
def test_latent_ring_against_a_dense_window(plan):
    """One call over dirty rings of latent rows at a window of W = 8:
    slots whose sequences start in the call (their old entries absent,
    not zero), one past the window, chunks that straddle the ring's wrap
    at odd offsets; a dead row in the scratch slot.  Every head reads
    the shared row: scores over its first ``dk`` lanes, values its first
    ``rank``.  Then the rings hold each slot's last W positions at ``p
    mod W``."""
    rs = np.random.RandomState(0)
    H, rank, dk, L, S, W = 5, 24, 32, 64, 5, 8
    scale = 0.3
    plan = RING_PLANS[plan]
    hist = {s: jnp.asarray(rs.randn(n + m, L), jnp.float32)
            for s, (n, m) in plan.items()}
    pool = jnp.asarray(rs.randn(S + 1, W, L), jnp.float32)      # dirty
    for s, (n, _) in plan.items():
        for p in range(n):
            pool = pool.at[s, p % W].set(hist[s][p])
    # decode rows first, then the prefill rows: the engine's order
    order = sorted(plan, key=lambda s: plan[s][1] > 1)
    row_slot, row_pos = [], []
    for s in order:
        n, m = plan[s]
        row_slot += [s] * m
        row_pos += list(range(n, n + m))
    row_slot += [S]                                     # a dead row
    row_pos += [0]
    T = len(row_slot)
    row_slot = jnp.asarray(row_slot, jnp.int32)
    row_pos = jnp.asarray(row_pos, jnp.int32)
    kv = jnp.concatenate([hist[s][plan[s][0]:] for s in order]
                         + [jnp.zeros((1, L))])
    q = jnp.asarray(rs.randn(T, H, dk), jnp.float32)
    # the chunk bounds the prefill rows of all slots together
    chunk = max(2, sum(m for n, m in plan.values() if m > 1))
    state = M.SlotState({"win": pool}, row_slot, None, chunk)
    counts = M.StepCounts(jnp.ones(T, bool))
    out = state.latent_window(q, kv, row_pos, rank, scale, counts)
    for r in range(T - 1):
        s, p = int(row_slot[r]), int(row_pos[r])
        keys = hist[s][max(0, p - W + 1):p + 1]
        sc = jnp.einsum("hd,kd->hk", q[r], keys[:, :dk]) * scale
        want = jnp.einsum("hk,kr->hr", jax.nn.softmax(sc, -1),
                          keys[:, :rank])
        np.testing.assert_allclose(np.asarray(out[r]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # what the attention had to read: min(p0, W - 1) entries and the rows
    assert int(counts.counts[-2]) == T - 1
    assert int(counts.counts[-1]) == sum(min(n, W - 1) + m
                                         for n, m in plan.values())
    new = state.pools["win"]
    for s, (n, m) in plan.items():
        for p in range(max(0, n + m - W), n + m):
            np.testing.assert_array_equal(np.asarray(new[s, p % W]),
                                          np.asarray(hist[s][p]))


def _chunked_logits(params, cfg, tokens, chunk):
    """One sequence through ``serve_block`` in calls of ``chunk`` rows:
    the rings carried in a two-slot pool (slot 1 the scratch) that starts
    dirty, the full layer's latent rows in an array as long as the
    sequence under a causal mask.  Every row's logits."""
    T = tokens.shape[0]
    H, R = cfg.n_heads, cfg.kv_lora_rank
    keeps = M.layer_cache(cfg)
    pools = [{n: jnp.full((2,) + s, 9.0, d) for n, (s, d) in st.items()}
             for _, st in keeps]
    width = R + cfg.qk_rope_head_dim
    cache = [None if st else jnp.zeros((T, width)) for _, st in keeps]

    @jax.jit
    def call(params, pools, cache, toks, row_pos):
        counts = M.StepCounts(jnp.ones(toks.size, bool))
        x = M.serve_embed(params, cfg, toks, row_pos)
        pools, cache = list(pools), list(cache)
        for i, layer in enumerate(params["layers"]):
            def attend(q, row, i=i):
                rows = cache[i] = cache[i].at[row_pos].set(row)
                s = jnp.einsum("qhw,kw->hqk", q, rows) * cfg.softmax_scale
                seen = jnp.arange(T)[None] <= row_pos[:, None]
                p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), -1)
                return jnp.einsum("hqk,kr->qhr", p, rows[:, :R])
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


@pytest.mark.parametrize("chunk", [5, 13])
def test_chunks_give_the_logits_of_one_chunk(model, chunk):
    """A sequence of 45 positions (2.8 windows) cut into calls of 5 and
    13 rows, straddling the ring at odd offsets; rings that start
    dirty."""
    params, cfg = model
    tokens = _tokens(4, 45)
    dense = M.forward(params, cfg, jnp.asarray(tokens)[None])[0]
    got = _chunked_logits(params, cfg, tokens, chunk)
    scale = float(jnp.max(jnp.abs(dense)))
    assert float(jnp.max(jnp.abs(got - dense))) <= 1e-5 * scale


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
    a slot's pool: (widest logit gap, tokens that differ where the
    reference's top-2 margin exceeds the tolerance)."""
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


# prompts from under a window to nearly two, answers that carry the
# contexts to 2-3.5 windows
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


@pytest.mark.parametrize("kernel,overlap", [
    ("xla", False), ("pallas", False), ("pallas", True)])
def test_engine_serves_the_reference_tokens(ref, model, kernel, overlap):
    """Chunked prefill of several slots (prompts longer than a chunk and
    than the window among them), decode through the full layer's latent
    pages and the sliding layers' latent rings far past the window, six
    requests over three slots (slot reuse: a reused slot's old ring
    entries are absent, as a first one's dirty pool is)."""
    eng = _engine(model, kernel=kernel, overlap=overlap)
    _dirty(eng)
    rids = _submit_all(eng)
    eng.run()
    eng.close()
    assert all(eng.requests[r].state == "done"
               and len(eng.requests[r].generated) == m
               for r, (_, m) in zip(rids, REQUESTS))
    worst, wrong = _held_to_reference(ref, model[0], eng, rids)
    assert worst <= 1e-3 and wrong == 0
    s = eng.stats
    # the latent rings' counts, booked with the step's tokens: every live
    # row through the four sliding layers, and what their attention had
    # to read
    rows = s["decode_rows"] + s["prefill_rows"]
    assert s["win_rows"] == SLIDING * rows
    assert s["win_rows"] < s["win_positions"] < SLIDING * W * rows
    # the expert layers' counts beside them, a share held
    assert 0 < s["moe_pairs"] <= rows * 4 * 4
    assert s["moe_expert_bytes"] == s["moe_experts_hit"] * 3 * 64 * 32 * 4
    # the full layer's latent pages: the walk reads each row's own
    assert 0 < s["kv_pages_read"] <= s["kv_pages_window"]
    if kernel == "pallas":
        assert s["kv_pages_read"] < s["kv_pages_window"]


@pytest.fixture(scope="module")
def served(model):
    eng = _engine(model)
    rids = _submit_all(eng, seed=3)
    eng.run()
    return eng, rids


FAULTS = ("window_left_out", "window_off_by_one", "noise_not_subtracted",
          "noise_head_first", "no_output_gate", "res_rows_only",
          "silu_for_polynorm", "no_shared_expert", "ring_zero_filled")


@pytest.mark.parametrize("how", [{"fault": f} for f in FAULTS]
                         + [{"precision": "fp8"}],
                         ids=lambda how: next(iter(how.values())))
def test_planted_faults_fail_the_engines_tolerance(ref, model, served, how):
    """The engine's tokens, held to the reference with ONE planted
    departure from the published layer (or computed in fp8), miss the
    tolerance that the reference as published keeps: each is seen."""
    eng, rids = served
    assert ref.FAULTS == FAULTS
    assert _held_to_reference(ref, model[0], eng, rids)[0] <= 1e-3
    worst, _ = _held_to_reference(ref, model[0], eng, rids, **how)
    assert worst > 1e-2


def test_bfloat16_fails_the_float32_tolerance(ref, model):
    """The tolerance is tight enough to tell a lower precision."""
    params, cfg = model
    low = (jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 and a.ndim > 1 else a, params),
        dataclasses.replace(cfg, dtype="bfloat16"))
    eng = _engine(low)
    # 348 tokens: bfloat16 turns the argmax at about 2% of positions
    rids = sum((_submit_all(eng, seed=s) for s in (2, 3, 4, 6)), [])
    eng.run()
    worst, _ = _held_to_reference(ref, params, eng, rids)
    assert worst > 1e-2


def test_engine_preempt_resumes_to_the_same_tokens(ref, model):
    """A preempted request's rings and pages are rebuilt by
    recomputation: it ends with the tokens an undisturbed engine
    serves."""
    calm = _engine(model)
    rids = _submit_all(calm, seed=2)
    calm.run()
    eng = _engine(model)
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
                       match="Motif3Config.*recurrent state.*" + names):
        if how == "admit_prefilled":
            _engine(model).admit_prefilled(
                np.ones(4, np.int32), [1], [1], max_new_tokens=4)
        else:
            _engine(model, **how)


def test_pools_follow_the_layers(model):
    """Latent pages on the full layer only, a latent ring on the sliding
    layers only; at the published sizes a page and a ring entry are one
    640-lane row a token."""
    eng = _engine(model)
    kv = {"kv": ((3 * 8 + 1, 8, 128), "float32")}
    win = {"win": ((3 + 1, W, 128), "float32")}
    assert [{k: (tuple(a.shape), str(a.dtype)) for k, a in p.items()}
            for p in eng.cache.pools] == [win, win, win, kv, win]
    cfg = M.Motif3Config.from_hf(_CONFIG)
    cache = jax.eval_shape(lambda: PagedKVCache(cfg, 2, 16,
                                                num_slots=1).pools)
    assert [sorted(p) for p in cache] == [["win"]] * 3 + [["kv"], ["win"]]
    assert cache[3]["kv"].shape == (2, 16, 640)
    assert cache[0]["win"].shape == (2, 128, 640)


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
    for scope in ("embed", "mhc_pre", "mhc_res", "mhc_post", "gdla_q",
                  "gdla_kv", "win_latent", "kv_write", "gdla_diff",
                  "attn_gate", "attn_out", "ffn", "polynorm", "moe_route",
                  "moe_experts", "moe_shared", "head", "sample"):
        assert _has_scope(locs, scope), scope


# ------------------------------------------------ the benchmark's cell ---

def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", script),
         "--workload", CELL, "--seconds", "1", "--rehearse"] + list(args),
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    return r


def test_chipbench_rehearses_the_cell():
    """``chipbench/run.py --rehearse --trace 1`` of the cell at the
    configuration file's toy size, in a process of its own: paths,
    control flow and the comparison against the reference, no device
    metric."""
    r = _run("run.py", "--seed", str(2 ** 31 + 44), "--trace", "1")
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {"bad_answers", "missing_answers",
                                     "logit_gap", "logit_gap_p99"}
    assert line["rehearse"] is True and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    turns = json.loads(next(ln for ln in r.stderr.splitlines()
                            if ln.startswith("turns "))[6:])
    assert turns["steps"] > 0


def test_benchmark_json_names_the_cell_and_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "motif3_beta_l5_ep8",
                    "traffic": "closed128_p512-2048_o1024-3072", "chips": 1,
                    "why": cell["why"]}
    conf = next(c for c in bench["configs"]
                if c["name"] == "motif3_beta_l5_ep8")
    assert conf["reduced"] == _CONFIG["reduced"]
    assert conf["source"] == _CONFIG["source"]
    # the two metrics of this family come last, after the stall metrics
    assert [m["name"] for m in bench["per_layer"][-8:]] == [
        "turn_stall_max_ms.serve", "stall_offcpu_share.serve",
        "stall_host_late_share.serve", "stall_runtime_busy_share.serve",
        "step_stall_share.train", "turn_stall_max_ms.train",
        *LATENT_READERS]
    mine = {"name": CELL, "bench": bench}
    exaone = {"name": "k_exaone_236b_l5_ep8.reasoning_decode",
              "bench": bench}
    # every metric of the EXAONE cell, whose readers read this one alike,
    # then the two of the latent walk and ring of this family; not
    # GigaChat's latent walk, which counts every layer as keeping pages
    theirs = [m["name"] for m in chipbench_run.metrics_for(
        exaone, "per_layer")]
    assert [m["name"] for m in chipbench_run.metrics_for(
        mine, "per_layer")] == theirs + LATENT_READERS
    assert "latent_read_bw_share.serve" not in theirs
    for m in bench["per_layer"]:
        if m["name"] in LATENT_READERS:
            assert m["workloads"] == [CELL] and m["unit"] == "%"
            assert m["source"] == "device_trace" and m["layer"] == "kernels"
            assert m["moves"] == "serve_tok_s" and m["better"] == "higher"
    assert [m["name"] for m in chipbench_run.metrics_for(
        mine, "end_to_end")] == ["setup_s", "serve_tok_s", "itl_p95_ms"]


LATENT_READERS = ["latent_walk_bw_share.serve", "latent_ring_bw_share.serve"]


@pytest.mark.parametrize("name", LATENT_READERS)
def test_latent_readers_read_the_engines_counters(served, name):
    """The two readers on the counters a served window books (the
    configuration's toy size): the bytes of the one full layer's latent
    walk or of the four sliding layers' rings a step, over an assumed
    device step; nothing from a configuration of another family or
    without a trace."""
    import model_math_motif3 as mm
    eng, _ = served
    reader = chipbench_run.load_module("layer_metrics", name)
    cell = {"config": TOY, "device": {"kind": "TPU v5 lite"}}
    counters = dict(eng.stats)
    got = reader.read(cell, {}, counters, {"step_device_ms": 2.0})
    moved = (mm.latent_walk_bytes(TOY, counters["kv_pages_read"])
             if name == LATENT_READERS[0]
             else mm.ring_read_bytes(TOY, counters["win_positions"]))
    assert moved > 0
    assert got == pytest.approx(
        100.0 * moved / counters["steps"] / (819e9 * 2e-3))
    assert reader.read(cell, {}, counters, None) is None
    for family in ("gigachat3_702b_l5_ep16", "k_exaone_236b_l5_ep8"):
        other = {"config": json.load(open(os.path.join(
            ROOT, "chipbench", "configs", family + ".json"))),
            "device": {"kind": "TPU v5 lite"}}
        assert reader.read(other, {}, counters,
                           {"step_device_ms": 2.0}) is None
