"""Falcon-H1 (parallel Mamba-2 + grouped-query attention blocks) through
``models/falcon_h1.py`` and the paged serving engine, held to the plain
reference ``chipbench/reference/falcon_h1.py`` on the CPU: toy sizes,
seeded weights, float32.

Tolerances.  The dense forward and the reference compute the same
function in float32 with another order of operations (the chunked scan
against the step-by-step recurrence, one fused qkv-less block against
the literal one): their logits (deviation 2) agree to 1e-4 of the
largest logit.  The engine adds the paged softmax's order of summation:
a served token's reference logit lies within 1e-3 of the reference's
best, and is the reference's own choice wherever the reference's top-2
margin exceeds that.
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

from mxnet_tpu.models import falcon_h1 as F
from mxnet_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the published multipliers and flags, at a toy size of the same
# structure (the configuration file's own ``rehearse`` group)
_CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "falcon_h1_34b_l6.json")))
TOY = dict(_CONFIG, **{k: v for k, v in _CONFIG["rehearse"].items()
                       if k != "engine"})


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_falcon_h1",
        os.path.join(ROOT, "chipbench", "reference", "falcon_h1.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model(ref):
    params = ref.make_params(7, TOY, "float32")
    return params, F.FalconH1Config.from_hf(TOY, dtype="float32")


def test_reference_imports_nothing_of_the_program(ref):
    src = open(ref.__file__).read()
    assert "mxnet_tpu" not in src.replace("nothing imported from", "")
    assert 'default_matmul_precision("highest")' in src


def test_forward_matches_reference(ref, model):
    params, cfg = model
    tokens = np.random.RandomState(0).randint(1, TOY["vocab_size"],
                                              (2, 40)).astype(np.int32)
    want = ref.decoder_logits(params, tokens, TOY)
    got = jax.jit(lambda p, t: F.forward(p, cfg, t))(params,
                                                     jnp.asarray(tokens))
    assert float(jnp.std(want)) > 1.0        # the logits are alive
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 1e-4 * float(jnp.max(jnp.abs(want)))


def test_init_params_layout_is_the_references(ref, model):
    params, cfg = model
    mine = F.init_params(jax.random.PRNGKey(0), cfg, "float32")
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(mine)] \
        == [a.shape for a in jax.tree_util.tree_leaves(params)]


# ------------------------------------------------- the scan in a step ---

H, P, G, N, K, C = 4, 8, 2, 6, 4, 10
SLOTS = 3                                    # slot 3 is the scratch slot


def _rows(rs, T):
    return dict(x=rs.randn(T, H, P), B=rs.randn(T, G, N),
                C=rs.randn(T, G, N), dt=0.05 + 0.5 * rs.rand(T, H),
                xBC=rs.randn(T, C))


def _literal(rows, slots, state, window, A, D, w, b):
    """The recurrence and the convolution row by row, as written."""
    ys, convs = [], []
    for t, s in enumerate(slots):
        Bh = np.repeat(rows["B"][t], H // G, axis=0)
        Ch = np.repeat(rows["C"][t], H // G, axis=0)
        dt = rows["dt"][t]
        state[s] = np.exp(dt * A)[:, None, None] * state[s] \
            + (dt[:, None] * rows["x"][t])[:, :, None] * Bh[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", state[s], Ch)
                  + D[:, None] * rows["x"][t])
        taps = np.concatenate([window[s], rows["xBC"][t][None]])
        convs.append(b + (w * taps).sum(0))
        window[s] = taps[1:]
    return np.stack(ys), np.stack(convs)


# calls of one case: per call the rows' slots (3 = dead) and the slots
# that start from zero in it
CASES = {
    "segments_of_one": [([0, 1, 2], [0, 1, 2]), ([0, 1, 2], []),
                        ([2, 0], [])],
    "a_full_chunk": [([1] * 8, [1]), ([1], [])],
    "a_slot_split_over_two_steps": [([0] * 5, [0]), ([0] * 4, []),
                                    ([0], [])],
    "dead_rows": [([0, 3, 3], [0]), ([0, 1, 1, 1, 3, 3], [1])],
    "decode_and_chunks_mixed": [([0, 0, 0], [0]),
                                ([0, 1, 1, 1, 1, 2, 2, 3], [1, 2]),
                                ([0, 2, 1, 1, 3, 3], []),
                                ([1, 0, 0, 0, 0], [0])],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_slot_scan_and_conv_match_the_literal_recurrence(case):
    rs = np.random.RandomState(sorted(CASES).index(case))
    A, D = -(1.0 + 3.0 * rs.rand(H)), rs.randn(H)
    w, b = rs.randn(K, C), rs.randn(C)
    f32 = lambda a: jnp.asarray(a, jnp.float32)    # noqa: E731
    # the pools start full of another request's state: ``fresh`` must
    # mask it
    pool = f32(rs.randn(SLOTS + 1, H, P, N))
    conv_pool = f32(rs.randn(SLOTS + 1, K - 1, C))
    state = {s: np.zeros((H, P, N)) for s in range(SLOTS + 1)}
    window = {s: np.zeros((K - 1, C)) for s in range(SLOTS + 1)}
    for slots, fresh_slots in CASES[case]:
        rows = _rows(rs, len(slots))
        for s in fresh_slots:
            state[s][:], window[s][:] = 0.0, 0.0
        want_y, want_conv = _literal(rows, slots, state, window, A, D, w, b)
        fresh = jnp.zeros(SLOTS + 1, bool).at[jnp.asarray(
            fresh_slots, jnp.int32)].set(True)
        row_slot = jnp.asarray(slots, jnp.int32)
        y, pool = F.slot_scan(f32(rows["x"]), f32(rows["B"]),
                              f32(rows["C"]), f32(rows["dt"]), f32(A),
                              f32(D), row_slot, fresh, pool, chunk=8)
        conv, conv_pool = F.slot_conv(f32(rows["xBC"]), f32(w), f32(b),
                                      row_slot, fresh, conv_pool)
        live = np.asarray(slots) != SLOTS
        np.testing.assert_allclose(np.asarray(y)[live], want_y[live],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(conv)[live],
                                   want_conv[live], rtol=2e-5, atol=2e-5)
        for s in set(slots) - {SLOTS}:
            np.testing.assert_allclose(np.asarray(pool[s]), state[s],
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(np.asarray(conv_pool[s]),
                                       window[s], rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------- the engine ---

def _engine(model, overlap=False, kernel="xla", **kw):
    """``overlap``: the schedule an engine takes where its pools live
    on a TPU, reached on the CPU by substituting that observation."""
    params, cfg = model
    args = dict(num_slots=3, page_size=8, pages_per_slot=8,
                prefill_chunk=8)
    args.update(kw)
    with pools_seen_on("tpu" if overlap else "cpu"):
        eng = ServingEngine(params, cfg, kernel=kernel, **args)
    assert eng.overlap is overlap
    return eng


def _held_to_reference(ref, model, eng, rids):
    """Every served token of ``rids`` against the reference's one full
    forward pass: (widest logit gap, tokens that differ where the
    reference's top-2 margin exceeds the tolerance)."""
    params, _ = model
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
    among them), decode, five requests over three slots (slot reuse)."""
    rs = np.random.RandomState(1)
    eng = _engine(model, kernel=kernel, overlap=overlap)
    rids = [eng.submit(rs.randint(1, TOY["vocab_size"], n), m)
            for n, m in REQUESTS]
    out = eng.run()
    eng.close()
    assert sorted(out) == rids
    assert all(len(eng.requests[r].generated) == m
               for r, (_, m) in zip(rids, REQUESTS))
    worst, wrong = _held_to_reference(ref, model, eng, rids)
    assert worst <= 1e-3 and wrong == 0
    # every live slot's state once a step; a reset per admission
    s = eng.stats
    assert s["ssm_state_resets"] == len(REQUESTS)
    assert s["ssm_state_updates"] >= s["decode_rows"] + len(REQUESTS)
    assert s["ssm_state_bytes"] == 2 * s["ssm_state_updates"] \
        * eng.cache.bytes_per_slot_state       # every layer's, together
    # ... which is what the benchmark's shapes-only arithmetic gives
    # (float32 state and window in this test)
    sys.path.insert(0, os.path.join(ROOT, "chipbench"))
    import model_math_falcon_h1 as mm
    assert s["ssm_state_bytes"] == mm.ssm_state_bytes(
        TOY, s["ssm_state_updates"], window_bytes=4)


@pytest.mark.parametrize("overlap", [False, True])
def test_engine_preempt_resumes_by_recomputation(ref, model, overlap):
    rs = np.random.RandomState(2)
    eng = _engine(model, overlap=overlap)
    rids = [eng.submit(rs.randint(1, TOY["vocab_size"], n), m)
            for n, m in REQUESTS]
    for _ in range(6):
        eng.step()
    victim = next(r for r in eng._slots
                  if r is not None and r.generated)
    resets = eng.stats["ssm_state_resets"]
    assert eng.preempt(victim.rid) is False      # no tier: recompute
    out = eng.run()
    eng.close()
    assert sorted(out) == rids
    assert eng.stats["preemptions"] == 1
    assert eng.stats["ssm_state_resets"] > resets
    worst, wrong = _held_to_reference(ref, model, eng, rids)
    assert worst <= 1e-3 and wrong == 0


def test_engine_cancel_frees_the_slot_for_a_fresh_state(ref, model):
    rs = np.random.RandomState(3)
    eng = _engine(model, num_slots=2)
    rids = [eng.submit(rs.randint(1, TOY["vocab_size"], n), m)
            for n, m in ((12, 30), (7, 8), (21, 7), (4, 9))]
    for _ in range(5):
        eng.step()
    eng.cancel(rids[0])                          # mid-decode
    out = eng.run()
    assert sorted(out) == rids[1:]
    assert eng.requests[rids[0]].state == "cancelled"
    worst, wrong = _held_to_reference(ref, model, eng, rids[1:])
    assert worst <= 1e-3 and wrong == 0


def test_context_is_bounded_by_the_pool_not_a_position_table(model):
    eng = _engine(model)                         # 8 pages of 8
    assert model[1].max_len is None
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(np.ones(60, np.int32), 5)
    eng.submit(np.ones(60, np.int32), 4)         # 64 positions: fits
    with pytest.raises(ValueError, match="pages_per_slot"):
        ServingEngine(*model, num_slots=2)


def test_transformer_engine_books_no_state_counters():
    from mxnet_tpu.models import gpt
    cfg = gpt.gpt_tiny(dtype="float32", param_dtype="float32")
    eng = ServingEngine(gpt.init_params(jax.random.PRNGKey(0), cfg), cfg,
                        num_slots=2)
    assert not any(k.startswith("ssm_") for k in eng.stats)
    assert set(eng.cache.pools[0]) == {"kv"}
    assert eng.cache.pools[0]["kv"].ndim == 4


@pytest.mark.parametrize("how,names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_K=2), "spec_K"),
    (dict(tier_bytes=1 << 20), "tier"),
    (dict(kv_int8=True), "kv_int8"),
    (dict(tp=2), "tp > 1"),
    ("admit_prefilled", "hand-off"),
])
def test_engine_refuses_what_needs_snapshots_of_the_state(model, how,
                                                          names):
    with pytest.raises(ValueError, match="recurrent state.*" + names):
        if how == "admit_prefilled":
            _engine(model).admit_prefilled(
                np.ones(4, np.int32), [1], [1], max_new_tokens=4)
        else:
            _engine(model, **how)


# --------------------------------------- grouped-query paged attention ---

def _paged_case(T, Hq, Hkv, dh, ps, PP, dtype, seed):
    rs = np.random.RandomState(seed)
    NP = T * PP + 1
    pool = jnp.asarray(rs.randn(NP, ps, Hkv * 2 * dh), dtype)
    q = jnp.asarray(rs.randn(T, Hq, dh), dtype)
    bt = jnp.asarray(rs.permutation(np.arange(1, NP))[:T * PP]
                     .reshape(T, PP), jnp.int32)
    pos = jnp.asarray(rs.randint(0, PP * ps, T), jnp.int32)
    return q, pool, bt, pos.at[0].set(0).at[1].set(PP * ps - 1)


@pytest.mark.parametrize("ps,dh,dtype,walks,tol", [
    (16, 128, "float32", True, 2e-6),    # the walk, interpreted
    (4, 128, "float32", False, 2e-6),    # pages no whole tiles: per-page grid
    (16, 128, "bfloat16", True, 2e-2),
    (8, 128, "bfloat16", False, 2e-2),
    # whole-tile pages, but a head's [k | v] pair half a lane tile: the
    # walk's fold cannot take it as the MXU's weights, the grid serves
    (16, 32, "float32", False, 2e-6),
    (16, 32, "bfloat16", False, 2e-2),
])
def test_grouped_paged_attention_matches_reference(ps, dh, dtype, walks,
                                                   tol):
    """20 query heads over 4 key/value heads of 128, as the cell has (and
    of 32)."""
    from mxnet_tpu.kernels.paged_attention import (
        paged_attention, paged_attention_reference, walk_geometry)
    assert (walk_geometry(4, dh, ps, 6, dtype, flat=True)
            is not None) == walks
    q, pool, bt, pos = _paged_case(5, 20, 4, dh, ps, 6, dtype, ps)
    got = paged_attention(q, pool, None, bt, pos, page_size=ps,
                          interpret=True)
    want = paged_attention_reference(q, pool, None, bt, pos, page_size=ps)
    assert got.shape == (5, 20, dh)
    assert float(jnp.max(jnp.abs(got - want))) <= tol


@pytest.mark.parametrize("page", [(4, 2, 16), (4, 32)])
def test_write_rows_lays_a_row_as_its_pool_does(page):
    """``paged_kv.write_rows`` on the per-head page and on the flat
    grouped-query page: row r's k then v, head by head, at its page and
    offset; every other position as it was."""
    from mxnet_tpu.serving.paged_kv import write_rows
    rs = np.random.RandomState(3)
    pool = jnp.asarray(rs.randn(5, *page), jnp.float32)
    k, v = (jnp.asarray(rs.randn(3, 2, 8), jnp.float32) for _ in "kv")
    pg, off = jnp.asarray([4, 1, 4]), jnp.asarray([0, 3, 2])
    got = np.asarray(write_rows(pool, pg, off, k, v)).reshape(5, 4, 2, 16)
    want = np.array(pool).reshape(5, 4, 2, 16)
    want[[4, 1, 4], [0, 3, 2]] = np.concatenate([k, v], axis=-1)
    np.testing.assert_array_equal(got, want)


def test_paged_attention_refuses_mismatched_pools():
    from mxnet_tpu.kernels.paged_attention import paged_attention
    q, pool, bt, pos = _paged_case(3, 4, 2, 32, 8, 2, "float32", 0)
    with pytest.raises(ValueError, match="flat pool"):
        paged_attention(q[:, :3], pool, None, bt, pos, page_size=8)
    with pytest.raises(ValueError, match="grouped-query pools are flat"):
        paged_attention(q, pool.reshape(pool.shape[0], 8, 2, 64), None,
                        bt, pos, page_size=8)


# ------------------------------------------------- the benchmark's cell ---

@pytest.mark.parametrize("trace", [0, 1])
def test_chipbench_rehearses_the_cell(trace):
    """``chipbench/run.py --rehearse`` of the cell at the configuration
    file's toy size, in a process of its own: paths, control flow and the
    comparison against the reference, no device metric."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "falcon_h1_34b_l6.chat_decode", "--seed",
         str(2 ** 31 + 28), "--seconds", "1", "--trace", str(trace),
         "--rehearse"], capture_output=True, text=True, env=env,
        timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["rehearse"] is True and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    # the run places its window's time turn by turn on standard error:
    # inside the steps and outside them, together the whole window
    turns = json.loads(next(ln for ln in r.stderr.splitlines()
                            if ln.startswith("turns "))[6:])
    assert turns["steps"] > 0 and len(turns["longest"]) > 0
    assert "engine.wait" in turns["longest"][0]["phases"]
    whole = turns["in_step_s"] + turns["outside_s"] \
        + turns["before_first_step_ms"] / 1e3
    assert abs(whole - line["window_s"]) < 0.05 * line["window_s"]
