"""Continuous-batching serving engine (mxnet_tpu/serving/): paged-KV
greedy decode must be token-identical to ``models/gpt.py generate``
under f32, page recycling must not leak across requests, and
preemption-recompute must stay exact.  Slow tier, group d."""
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (conftest device setup)


def _cfg(**kw):
    from mxnet_tpu.models import gpt
    base = dict(use_flash=False, remat=False, dropout=0.0,
                dtype="float32", vocab_size=128, max_len=64)
    base.update(kw)
    return gpt.gpt_tiny(**base)


def _ref(params, cfg, prompt, n, **kw):
    import jax.numpy as jnp
    from mxnet_tpu.models import gpt
    return np.asarray(
        gpt.generate(params, cfg, jnp.asarray(prompt)[None], n,
                     **kw))[0]


@pytest.mark.slow
def test_paged_greedy_token_identical_mixed_lengths():
    """The exactness pin: every request in a mixed prompt/output-length
    batch decodes token-identically to plain ``generate`` (f32 greedy),
    through admission waves, chunked prefill, and page recycling —
    for float and weight-only-int8 params."""
    import jax
    from mxnet_tpu.models import gpt, transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.RandomState(0)
    shapes = [(5, 8), (3, 12), (9, 4), (2, 6), (7, 10), (4, 9)]
    for p in (params, gpt.quantize_decode_params(params)):
        eng = ServingEngine(p, cfg, num_slots=3, page_size=4,
                            prefill_chunk=6)
        reqs = [(eng.submit(rng.randint(1, 90, P).astype(np.int32), N),
                 N) for P, N in shapes]
        outs = eng.run()
        assert eng.stats["admitted"] == len(shapes)
        for rid, N in reqs:
            req = eng.requests[rid]
            ref = _ref(p, cfg, req.prompt, N)
            np.testing.assert_array_equal(outs[rid], ref)
        # every page returned to the pool after the drain
        assert eng.cache.pages_in_use == 0


@pytest.mark.slow
def test_requests_join_in_flight():
    """Iteration-level batching: a request submitted while others are
    mid-decode joins the running batch and still decodes exactly."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.RandomState(1)
    eng = ServingEngine(params, cfg, num_slots=3, page_size=4,
                        prefill_chunk=8)
    r1 = eng.submit(rng.randint(1, 90, 6).astype(np.int32), 14)
    r2 = eng.submit(rng.randint(1, 90, 4).astype(np.int32), 10)
    for _ in range(4):
        eng.step()
    # r1/r2 are mid-decode now; r3 joins in flight
    r3 = eng.submit(rng.randint(1, 90, 5).astype(np.int32), 8)
    outs = eng.run()
    for rid, n in ((r1, 14), (r2, 10), (r3, 8)):
        np.testing.assert_array_equal(
            outs[rid], _ref(params, cfg, eng.requests[rid].prompt, n))


@pytest.mark.slow
def test_forced_retire_page_reuse_no_leakage():
    """Page recycling: force-retire a mid-flight request, then admit a
    new one into a single-request-sized pool so it MUST reuse the
    freed pages (no zero-fill on recycle) — its output must equal the
    isolated reference, i.e. no cross-request leakage through stale
    page contents."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(7), cfg)
    rng = np.random.RandomState(2)
    # pool = exactly one max-length request (+ scratch): a second
    # request's lifetime footprint (5 pages of 5) cannot be served
    # without consuming recycled pages
    eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                        pages_per_slot=5, num_pages=6, prefill_chunk=8)
    ra = eng.submit(rng.randint(1, 90, 8).astype(np.int32), 12)
    for _ in range(5):
        eng.step()
    req_a = eng.requests[ra]
    assert req_a.state == "running" and len(req_a.generated) > 0
    pages_a = set(req_a.pages)
    assert pages_a
    eng.cancel(ra)                        # forced retire mid-flight
    assert req_a.state == "cancelled"
    assert eng.cache.pages_in_use == 0

    rb = eng.submit(rng.randint(1, 90, 7).astype(np.int32), 12)
    req_b = eng.requests[rb]
    seen_b = set()
    while eng.step() is not False:
        seen_b |= set(req_b.pages)
    # the new request really did sit on recycled pages
    assert seen_b & pages_a, (seen_b, pages_a)
    assert req_b.state == "done"
    np.testing.assert_array_equal(
        req_b.output, _ref(params, cfg, req_b.prompt, 12))


@pytest.mark.slow
def test_preemption_recompute_exact():
    """An over-committed pool preempts the youngest running request
    (pages freed, requeued, committed tokens re-prefilled on
    re-admission) — greedy outputs must stay token-identical for every
    request, preempted or not."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(9), cfg)
    rng = np.random.RandomState(3)
    eng = ServingEngine(params, cfg, num_slots=4, page_size=4,
                        pages_per_slot=8, num_pages=12,
                        prefill_chunk=4)
    reqs = []
    for P, N in [(6, 20), (4, 24), (8, 16), (3, 22), (5, 18)]:
        rid = eng.submit(rng.randint(1, 90, P).astype(np.int32), N)
        reqs.append((rid, N))
    outs = eng.run()
    assert eng.stats["preemptions"] > 0, \
        "pool was sized to force preemption"
    for rid, N in reqs:
        np.testing.assert_array_equal(
            outs[rid], _ref(params, cfg, eng.requests[rid].prompt, N))
    assert eng.cache.pages_in_use == 0


@pytest.mark.slow
def test_pallas_kernel_token_identical():
    """Round-11 acceptance pin, engine level: the fused Pallas
    paged-attention step (``kernel="pallas"``, interpreter mode on
    CPU) decodes token-identically to plain ``generate`` through a
    mixed-length batch with admission waves — the 1–2 ulp
    online-softmax difference (kernels/paged_attention.py docstring)
    never flips an argmax on this pinned workload.  The broader
    kernel-vs-reference sweep is tier-1
    (tests/test_paged_attention.py); speculation × kernel combos are
    group g (tests/test_serving_spec.py)."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.RandomState(0)
    shapes = [(5, 8), (3, 12), (9, 4), (2, 6)]
    eng = ServingEngine(params, cfg, num_slots=3, page_size=4,
                        prefill_chunk=6, kernel="pallas")
    reqs = [(eng.submit(rng.randint(1, 90, P).astype(np.int32), N), N)
            for P, N in shapes]
    outs = eng.run()
    for rid, N in reqs:
        np.testing.assert_array_equal(
            outs[rid], _ref(params, cfg, eng.requests[rid].prompt, N))
    assert eng.cache.pages_in_use == 0
    with pytest.raises(ValueError):
        ServingEngine(params, cfg, num_slots=1, page_size=4,
                      kernel="mosaic")


def test_default_kernel_follows_the_devices_platform(monkeypatch):
    """``kernel=None`` resolves from where the engine's pools live —
    a TPU takes the Pallas page walk, anything else the XLA gather —
    and an explicit ``kernel=`` wins on either."""
    import jax
    from mxnet_tpu.kernels import platform
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    kw = dict(num_slots=2, page_size=4, prefill_chunk=4)
    assert ServingEngine(params, cfg, **kw).kernel == "xla"
    assert ServingEngine(params, cfg, kernel="pallas",
                         **kw).kernel == "pallas"
    asked = []

    def on_tpu(*operands):
        asked.append(operands)
        return "tpu"
    monkeypatch.setattr(platform, "platform_of", on_tpu)
    eng = ServingEngine(params, cfg, device=jax.devices()[0], **kw)
    assert eng.kernel == "pallas"
    # it asked about the pools it had just placed
    assert asked and asked[0][0] is eng.cache.pools
    assert ServingEngine(params, cfg, kernel="xla",
                         **kw).kernel == "xla"


@pytest.mark.parametrize("kernel,kv_int8", [
    ("xla", False), ("pallas", False), ("pallas", True)])
def test_kv_pages_counters(kernel, kv_int8):
    """``kv_pages_window`` books every dispatched step's whole
    attention window (rows x pages a slot); ``kv_pages_read`` equals
    it on the gather path and on a pool the walk cannot cut pages out
    of (int8), and equals the live rows' own page counts — position //
    page_size + 1, a dead row none: the walk stops at the last live
    row — on the walk."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    eng = ServingEngine(params, cfg, num_slots=3, page_size=4,
                        prefill_chunk=6, kernel=kernel,
                        kv_int8=kv_int8)
    own, steps = [], []
    dispatch = eng._dispatch

    def counting(plan):
        buf = plan.buf
        own.append(int(((buf.row_pos // 4 + 1) * buf.row_live).sum()))
        steps.append(plan.kv_pages)
        return dispatch(plan)
    eng._dispatch = counting
    rng = np.random.RandomState(0)
    for P, N in [(5, 8), (3, 12), (9, 4), (2, 6)]:
        eng.submit(rng.randint(1, 90, P).astype(np.int32), N)
    eng.run()
    window = len(own) * eng.n_rows * eng.pages_per_slot
    assert len(own) > 8 and eng.stats["kv_pages_window"] == window
    assert eng.stats["kv_pages_read"] == sum(steps)
    if kernel == "pallas" and not kv_int8:
        assert steps == own
        assert sum(own) < window // 2
    else:
        assert eng.stats["kv_pages_read"] == window


def _run_counting(eng, book):
    """Serve four requests with ``book(plan)`` called on every plan
    that is dispatched, before its dispatch."""
    dispatch = eng._dispatch

    def counting(plan):
        book(plan)
        return dispatch(plan)
    eng._dispatch = counting
    rng = np.random.RandomState(0)
    for P, N in [(5, 8), (3, 12), (9, 4), (2, 6)]:
        eng.submit(rng.randint(1, 90, P).astype(np.int32), N)
    eng.run()


@pytest.mark.parametrize("kernel,page_size,kv_int8", [
    ("pallas", 4, False), ("pallas", 64, False), ("xla", 4, False),
    ("pallas", 4, True)])
def test_kv_pages_folded_counter(kernel, page_size, kv_int8):
    """``kv_pages_folded`` books, per dispatched step, each row's live
    pages rounded up to the whole turns of F pages that the walk's two
    contractions run over (F from ``walk_geometry``: the whole group
    under the dense fold), a dead row none: at least ``kv_pages_read``,
    equal to it where every row ends on a fold boundary (one page a
    slot: F = 1), and nothing where no walk runs — the gather path, the
    per-page grid on an int8 pool."""
    import jax
    from mxnet_tpu.kernels.paged_attention import walk_geometry
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    eng = ServingEngine(params, cfg, num_slots=3, page_size=page_size,
                        prefill_chunk=6, kernel=kernel, kv_int8=kv_int8)
    walks = kernel == "pallas" and not kv_int8
    F = walk_geometry(cfg.n_heads, cfg.d_model // cfg.n_heads, page_size,
                      eng.pages_per_slot, "float32")[1]
    assert F == eng.pages_per_slot == 64 // page_size
    folded, seen = [], []

    def book(plan):
        live = (plan.buf.row_pos // page_size + 1) * plan.buf.row_live
        folded.append(int(((live + F - 1) // F * F).sum()))
        seen.append(eng.stats["kv_pages_folded"])
    _run_counting(eng, book)
    assert len(folded) > 8
    if not walks:
        assert eng.stats["kv_pages_folded"] == 0
        return
    # booked step by step, with the plan that is dispatched
    assert seen == list(np.cumsum(folded))
    assert eng.stats["kv_pages_folded"] == sum(folded)
    if F == 1:
        assert eng.stats["kv_pages_folded"] == eng.stats["kv_pages_read"]
    else:
        assert eng.stats["kv_pages_folded"] > eng.stats["kv_pages_read"]


@pytest.mark.parametrize("kernel,page_size,kv_int8,group_pages", [
    ("pallas", 4, False, None), ("pallas", 64, False, None),
    ("pallas", 4, False, 2), ("xla", 4, False, None),
    ("pallas", 4, True, None)])
def test_kv_chain_counters(kernel, page_size, kv_int8, group_pages,
                           monkeypatch):
    """``kv_groups_live`` books, per dispatched step, the groups of G
    pages that hold a live page, a dead row none; ``kv_chain_slots``
    those rounded up, block of R rows by block, to the whole trips of K
    chains that the walk's loop makes (G, R and K from
    ``walk_geometry``): at least the live groups, the same share
    wherever a row is one group (16 pages of 4 tokens or one of 64),
    and nothing where no walk runs — the gather path, the per-page grid
    on an int8 pool."""
    import jax
    from mxnet_tpu.kernels import paged_attention as PA
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    if group_pages:
        # groups of two pages: rows of up to eight groups
        monkeypatch.setattr(PA, "_GROUP_BYTES", group_pages * page_size
                            * 2 * cfg.d_model * 4)
        monkeypatch.setattr(PA, "_call_cache", {})
    eng = ServingEngine(params, cfg, num_slots=3, page_size=page_size,
                        prefill_chunk=6, kernel=kernel, kv_int8=kv_int8)
    walks = kernel == "pallas" and not kv_int8
    G, _, R, K = PA.walk_geometry(
        cfg.n_heads, cfg.d_model // cfg.n_heads, page_size,
        eng.pages_per_slot, "float32")
    assert G == (group_pages or eng.pages_per_slot) and K == 4
    live, slots, seen, rows = [], [], [], []

    def book(plan):
        buf = plan.buf
        groups = -(-(buf.row_pos // page_size + 1) // G) * buf.row_live
        rows.append(int(buf.row_live.sum()))
        live.append(int(groups.sum()))
        slots.append(sum(-(-int(groups[b:b + R].sum()) // K) * K
                         for b in range(0, len(groups), R)))
        seen.append((eng.stats["kv_groups_live"],
                     eng.stats["kv_chain_slots"]))
    _run_counting(eng, book)
    assert len(live) > 8
    if not walks:
        assert eng.stats["kv_groups_live"] == 0
        assert eng.stats["kv_chain_slots"] == 0
        return
    # booked step by step, with the plan that is dispatched
    assert seen == list(zip(np.cumsum(live), np.cumsum(slots)))
    assert (eng.stats["kv_groups_live"], eng.stats["kv_chain_slots"]) \
        == (sum(live), sum(slots))
    assert sum(live) <= sum(slots)
    # the step's live rows, all inside one block of R
    assert 0 < max(rows) <= min(R, 3 + 6)
    if group_pages:
        assert sum(live) > sum(rows)                # rows of several
    else:
        # one group a live row, whatever the page: the same share
        assert sum(live) == sum(rows)
        assert sum(slots) == sum(-(-n // K) * K for n in rows)


@pytest.mark.parametrize("pipelined", [False, True])
def test_plans_pack_live_rows_first(pipelined):
    """Every dispatched plan's live rows are a prefix of its rows, the
    dead ones after them: the page walk stops at the last live row and
    so skips the whole dead tail.  Serial with draft rows beside the
    decode and prefill rows (speculation runs at depth 0), and
    pipelined with carried decode rows (depth 1, the TPU's)."""
    import jax
    from conftest import pools_seen_on
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    kw = dict(num_slots=3, page_size=4, prefill_chunk=6, kernel="xla")
    if pipelined:
        with pools_seen_on("tpu"):
            eng = ServingEngine(params, cfg, **kw)
    else:
        # a drafter that always proposes: draft rows in every step
        eng = ServingEngine(params, cfg, spec_K=2, spec_drafter=lambda
                            toks, K: np.ones(K, np.int32), **kw)
    assert eng.overlap is pipelined
    kinds = np.zeros(4, int)        # carried, decode, draft, prefill rows

    def book(plan):
        live, r = plan.buf.row_live, plan.n_rows_used
        assert live[:r].all() and not live[r:].any()
        kinds[:] += (plan.carried, plan.n_dec_rows - plan.carried,
                     r - plan.n_dec_rows - plan.n_pre_rows,
                     plan.n_pre_rows)
    _run_counting(eng, book)
    carried, decode, draft, prefill = kinds
    assert prefill > 0 and bool(carried) == pipelined
    if not pipelined:
        assert decode > 0 and draft > 0


def test_step_program_traced_once_across_live_counts(monkeypatch):
    """The live rows' count reaches the walk as a traced scalar: steps
    whose live rows number differently run ONE step program over one
    built walk (a count fixed at trace time would trace, lower and
    compile a program per count, at every start-up)."""
    import jax
    from mxnet_tpu.kernels import paged_attention as PA
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine, engine

    # built afresh here, not found from an earlier test
    monkeypatch.setattr(engine, "_step_cache", {})
    monkeypatch.setattr(PA, "_call_cache", {})
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    eng = ServingEngine(params, cfg, num_slots=3, page_size=4,
                        prefill_chunk=6, kernel="pallas")
    counts = set()
    _run_counting(eng, lambda plan: counts.add(plan.n_rows_used))
    assert len(counts) > 2
    assert eng._step_fn._cache_size() == 1
    # one geometry, built for either platform's branch of the program
    # (``kernels/platform.run_kernel``): the keys differ in
    # ``interpret`` alone
    keys = list(PA._call_cache)
    assert len(keys) == 2 and len({k[:9] + k[10:] for k in keys}) == 1


def _counter_reader(metric):
    """``read(counters)`` of the benchmark's reader file of a
    ``program_counter`` metric, ``chipbench/layer_metrics/<metric>.py``."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "layer_metrics",
        metric + ".py")
    spec = importlib.util.spec_from_file_location("reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return lambda counters: reader.read({}, {}, counters, None)


def test_kv_page_read_share_reader():
    """The benchmark's reader of the two counters
    (``chipbench/layer_metrics/kv_page_read_share.serve.py``): their
    ratio in percent; nothing, without raising, from a program that
    books no such counters (the parent commit's ``stats``) or from a
    window in which no step was dispatched."""
    read = _counter_reader("kv_page_read_share.serve")
    assert read({"steps": 400, "dead_rows": 17000}) is None
    assert read({"kv_pages_window": 0, "kv_pages_read": 0}) is None
    assert read({"kv_pages_window": 5120, "kv_pages_read": 5120}) == 100.0
    assert read({"kv_pages_window": 5120, "kv_pages_read": 1385}) == \
        pytest.approx(27.05, abs=0.01)


def test_kv_fold_live_share_reader():
    """The reader of ``kv_fold_live_share.serve``: ``kv_pages_read``
    over ``kv_pages_folded`` in percent; nothing, without raising,
    from a program that books no such counter (the parent commit) or
    from a window in which no step walked (the gather path books 0)."""
    read = _counter_reader("kv_fold_live_share.serve")
    assert read({"kv_pages_window": 5120, "kv_pages_read": 1385}) is None
    assert read({"kv_pages_window": 5120, "kv_pages_read": 5120,
                 "kv_pages_folded": 0}) is None
    assert read({"kv_pages_read": 1380, "kv_pages_folded": 1380}) == 100.0
    assert read({"kv_pages_read": 1380, "kv_pages_folded": 1460}) == \
        pytest.approx(94.52, abs=0.01)


def test_kv_chain_fill_share_reader():
    """The reader of ``kv_chain_fill_share.serve``: ``kv_groups_live``
    over ``kv_chain_slots`` in percent; nothing, without raising, from
    a program that books no such counters (the parent commit) or from
    a window in which no step walked (the gather path books 0)."""
    read = _counter_reader("kv_chain_fill_share.serve")
    assert read({"kv_pages_read": 1385, "kv_pages_folded": 1460}) is None
    assert read({"kv_groups_live": 0, "kv_chain_slots": 0}) is None
    assert read({"kv_groups_live": 260, "kv_chain_slots": 260}) == 100.0
    assert read({"kv_groups_live": 257, "kv_chain_slots": 272}) == \
        pytest.approx(94.49, abs=0.01)


@pytest.mark.slow
def test_paged_int8_kv_agreement():
    """Paged int8-KV (per-(row, token) s8 pages + f32 scale pages)
    tracks contiguous ``generate(kv_int8=True)`` the same way the
    contiguous int8 path tracks fp — greedy agreement, not bit
    equality (page-view gathers reduce in a different order)."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg(vocab_size=512, d_model=128, n_heads=4, n_layers=3,
               d_ff=256)
    params = T.init_params(jax.random.PRNGKey(11), cfg)
    rng = np.random.RandomState(4)
    eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                        kv_int8=True, prefill_chunk=8)
    reqs = [eng.submit(rng.randint(1, 500, P).astype(np.int32), 12)
            for P in (5, 7)]
    outs = eng.run()
    for rid in reqs:
        ref = _ref(params, cfg, eng.requests[rid].prompt, 12,
                   kv_int8=True)
        assert (outs[rid] == ref).mean() >= 0.9, (outs[rid], ref)


@pytest.mark.slow
def test_serving_eos_stops_early():
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(13), cfg)
    prompt = np.arange(1, 6, dtype=np.int32)
    ref = _ref(params, cfg, prompt, 12)
    eos = int(ref[8])                     # a token greedy WILL emit
    eng = ServingEngine(params, cfg, num_slots=1, page_size=4)
    rid = eng.submit(prompt, 12, eos_id=eos)
    outs = eng.run()
    assert outs[rid].size <= ref.size
    assert outs[rid][-1] == eos
    np.testing.assert_array_equal(outs[rid], ref[:outs[rid].size])


@pytest.mark.slow
def test_serve_bench_smoke():
    """CI smoke of the serving bench harness (--quick preset): the e2e
    section must carry both the engine and fixed-batch rows with the
    accounting the gate and docs rely on."""
    import json
    import os
    import sys
    import tempfile
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark"))
    import serve_bench

    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "serve.json")
        rc = serve_bench.main(["--quick", "--kernel-ablation",
                               "--spec-sweep", "--json", out])
        assert rc == 0
        rows = json.load(open(out))
    e2e = {r["config"].split("_")[0]: r for r in rows
           if r["section"] == "e2e"}
    assert set(e2e) == {"engine", "fixed"}
    eng, base = e2e["engine"], e2e["fixed"]
    assert eng["tok_s"] > 0 and base["tok_s"] > 0
    assert 0.0 <= eng["occupancy"] <= 1.0
    assert eng["hbm_peak_held"] <= eng["hbm_pool"]
    # equal-HBM comparison: the page pool must not exceed the
    # baseline's contiguous allocation
    assert eng["hbm_pool"] <= base["hbm_held"]
    # round-11 sections: the kernel ablation carries a step-time pair
    # (xla + pallas) and the spec sweep carries accept accounting
    kern = {r["config"]: r for r in rows if r["section"] == "kernel"}
    assert set(kern) == {"kernel_xla", "kernel_pallas"}
    assert all(r["step_p50_ms"] > 0 for r in kern.values())
    spec = {r["config"]: r for r in rows if r["section"] == "spec"}
    assert set(spec) == {"spec_K0", "spec_K2", "spec_K4"}
    for name, r in spec.items():
        if r["config"] != "spec_K0":
            assert r["spec_drafted"] > 0
            assert 0.0 <= r["spec_accept_rate"] <= 1.0
            assert r["tokens_per_step"] >= 1.0


@pytest.mark.slow
def test_serving_validation():
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine, PagedKVCache

    cfg = _cfg(max_len=16)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, num_slots=1, page_size=4)
    with pytest.raises(ValueError):
        eng.submit(np.ones(10, np.int32), 10)    # 20 > max_len 16
    with pytest.raises(ValueError):
        eng.submit(np.ones(0, np.int32), 4)
    with pytest.raises(ValueError):
        eng.submit(np.ones(4, np.int32), 0)
    with pytest.raises(ValueError):
        ServingEngine(params, cfg, num_slots=1, page_size=4,
                      num_pages=3)               # < one request
    with pytest.raises(ValueError):
        PagedKVCache(cfg, num_pages=1, page_size=4)
    assert eng.step() is False                   # idle engine
    # indivisible page_size: the view rounds up past max_len (masked
    # tail), construction succeeds, submit stays max_len-gated
    eng7 = ServingEngine(params, cfg, num_slots=1, page_size=7)
    assert eng7.max_seq == 21
    with pytest.raises(ValueError):
        eng7.submit(np.ones(8, np.int32), 9)     # 17 > max_len 16


@pytest.mark.slow
def test_cancel_after_done_is_noop():
    """A cancel landing after completion (the inherent client race)
    must not drop the finished output."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingEngine

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    eng = ServingEngine(params, cfg, num_slots=1, page_size=4)
    rid = eng.submit(np.arange(1, 6, dtype=np.int32), 6)
    outs = eng.run()
    eng.cancel(rid)
    assert eng.requests[rid].state == "done"
    np.testing.assert_array_equal(eng.requests[rid].output, outs[rid])
