"""Latency-hiding overlap (round 21): the engine's step loop at
pipeline depth 1, with device-carried sampling, must be BIT-IDENTICAL
to depth 0 — and to ``models/gpt.py generate`` — under every stop
condition that can invalidate a speculatively dispatched step.

The depth is no argument: an engine reads it from the platform its
pools live on (1 on a TPU without speculation, 0 elsewhere), so on the
CPU these tests reach the TPU's schedule by substituting that
observation while the engine is built (``conftest.pools_seen_on``) and
naming the CPU's attention lowering themselves (``kernel="xla"``).

Exactness pins:

* depth 1 vs depth 0, mixed prompt/output lengths, through eos stops,
  mid-pipeline preemption, and a cancel between two steps — identical
  states and tokens for every non-cancelled request, zero leaked
  pages/refs either way;
* a cancelled request's committed tokens may legitimately differ by
  pipeline depth (the cancel lands one step earlier or later), but
  the shorter transcript must prefix the longer — a wrong carried
  token would break the prefix, not just the length;
* both cluster flavors (replicated ``ServingCluster`` and the
  ``DisaggServingCluster`` protocol) stay generate-identical with
  pipelined engines.

Slow tier, group o (own group: every scenario pays a second compiled
step variant — the ``tok_src`` program — on top of the serial one).

Fast tier (PR 29: the pipelined schedule is what an engine runs on a
TPU, so tier-1 guards it): the identity pin once more at the smallest
sizes, the engine's own choice of schedule, that the schedule is no
argument and the engine starts no thread (PR 30), and the benchmark's
two serving cells rehearsed with the engine seen on a TPU.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest
from conftest import pools_seen_on

import mxnet_tpu as mx  # noqa: F401  (conftest device setup)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(**kw):
    from mxnet_tpu.models import gpt
    base = dict(use_flash=False, remat=False, dropout=0.0,
                dtype="float32", vocab_size=97, max_len=96)
    base.update(kw)
    return gpt.gpt_tiny(**base)


def _setup(seed=0):
    import jax
    from mxnet_tpu.models import transformer as T
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    return params, cfg


def _ref(params, cfg, prompt, n):
    import jax.numpy as jnp
    from mxnet_tpu.models import gpt
    return np.asarray(
        gpt.generate(params, cfg, jnp.asarray(prompt)[None], n))[0]


def _mixed(rng, vocab, lens=(3, 11, 7, 19, 5, 13)):
    return [rng.randint(1, vocab, size=n).astype(np.int32)
            for n in lens]


def _drain_engine(eng, chaos=None, cancel_rid=None):
    """Step to completion, optionally injecting chaos at a fixed step
    index (same index whichever schedule the pipeline runs, so serial
    and overlapped runs face the same script)."""
    steps = 0
    while True:
        if eng.step() is False:
            break
        steps += 1
        if chaos == "preempt" and steps == 3:
            running = [r for r in eng._slots if r is not None]
            if running:
                eng.preempt(running[-1].rid)
        if chaos == "cancel" and steps == 4 and cancel_rid is not None:
            eng.cancel(cancel_rid)
    return steps


def _engine(params, cfg, overlap, **kw):
    """A ``ServingEngine`` on the CPU's gather at the depth asked for:
    the one a TPU's pools give it (``overlap``), or the CPU's own."""
    from mxnet_tpu.serving import ServingEngine
    with pools_seen_on("tpu" if overlap else "cpu"):
        eng = ServingEngine(params, cfg, kernel="xla", **kw)
    assert eng.overlap is overlap
    return eng


def _engine_run(params, cfg, overlap, eos=None, chaos=None,
                lens=(3, 11, 7, 19, 5, 13),
                maxnew=(9, 4, 1, 7, 12, 6), **engine):
    rng = np.random.RandomState(7)
    prompts = _mixed(rng, cfg.vocab_size, lens)
    engine = engine or dict(num_slots=3, page_size=8, prefill_chunk=6,
                            prefix_cache=True)
    eng = _engine(params, cfg, overlap, **engine)
    rids = [eng.submit(p, m, eos_id=eos)
            for p, m in zip(prompts, maxnew)]
    _drain_engine(eng, chaos=chaos, cancel_rid=rids[1])
    res = {rid: (req.state, list(req.generated))
           for rid, req in eng.requests.items()}
    if eng.prefix is not None:
        eng.prefix.evict(10 ** 9)
    held = eng.cache.pages_in_use
    stats = dict(eng.stats)
    eng.close()
    return res, held, stats


def _assert_equiv(a, b, name):
    """Serial run ``a`` vs overlapped run ``b``: same states
    everywhere; exact tokens except for cancelled requests, whose
    transcripts must be prefix-consistent (pipeline-depth slack)."""
    assert set(a) == set(b)
    for rid in a:
        sa, ga = a[rid]
        sb, gb = b[rid]
        assert sa == sb, (name, rid, a, b)
        if sa == "cancelled":
            n = min(len(ga), len(gb))
            assert ga[:n] == gb[:n], (name, rid, ga, gb)
        else:
            assert ga == gb, (name, rid, ga, gb)


@pytest.mark.slow
@pytest.mark.parametrize("scenario", ["plain", "eos", "preempt",
                                      "cancel"])
def test_overlap_bit_identical_to_serial(scenario):
    """The core pin: overlapped engine vs serial engine on the same
    mixed-length burst, with the speculatively dispatched step
    invalidated by eos stops, a mid-pipeline preemption, or a cancel
    between two steps — identical outcomes, zero leaks, and every
    committed step of the overlapped run was dispatched pipelined."""
    params, cfg = _setup()
    kw = {"plain": {}, "eos": {"eos": 5},
          "preempt": {"chaos": "preempt"},
          "cancel": {"chaos": "cancel"}}[scenario]
    a, held_a, _ = _engine_run(params, cfg, overlap=False, **kw)
    b, held_b, st = _engine_run(params, cfg, overlap=True, **kw)
    _assert_equiv(a, b, scenario)
    assert held_a == 0 and held_b == 0, (scenario, held_a, held_b)
    assert st["steps"] > 0 and st["overlap_steps"] == st["steps"]


@pytest.mark.slow
def test_overlap_matches_generate():
    """Single-request overlapped decode is token-identical to plain
    ``generate`` (the carried argmax is the same argmax the host
    would have fed back)."""
    params, cfg = _setup()
    rng = np.random.RandomState(11)
    for p, m in zip(_mixed(rng, cfg.vocab_size, (3, 11, 7)),
                    (8, 5, 6)):
        ref = _ref(params, cfg, p, m)
        eng = _engine(params, cfg, True, num_slots=2, page_size=8,
                      prefill_chunk=8)
        rid = eng.submit(p, m)
        out = eng.run()[rid]
        eng.close()
        assert np.array_equal(ref[:out.size], out), (ref, out)


@pytest.mark.slow
def test_overlap_eos_invalidates_speculative_step_no_leak():
    """An eos stop commits one step BEHIND an already-dispatched
    speculative step: the junk row the dead slot computed must never
    be committed, the slot's pages must come back, and a follow-up
    request reusing the slot must still be exact."""
    params, cfg = _setup()
    rng = np.random.RandomState(3)
    p = rng.randint(1, cfg.vocab_size, 6).astype(np.int32)
    full = _ref(params, cfg, p, 12)[p.size:]
    eos = int(full[2])                     # stop after 3 tokens
    eng = _engine(params, cfg, True, num_slots=2, page_size=8,
                  prefill_chunk=8)
    rid = eng.submit(p, 12, eos_id=eos)
    eng.run()
    got = list(eng.requests[rid].generated)
    assert got == [int(t) for t in full[:3]]
    assert eng.cache.pages_in_use == 0
    # slot reuse after the invalidated step: fresh request, exact
    q = rng.randint(1, cfg.vocab_size, 9).astype(np.int32)
    rid2 = eng.submit(q, 5)
    out = eng.run()[rid2]
    assert np.array_equal(out, _ref(params, cfg, q, 5))
    eng.close()


@pytest.mark.slow
def test_cluster_overlap_identity_and_cancel_race():
    """Replicated cluster of pipelined engines: mixed-length burst is
    generate-identical, a cancel fired from another thread mid-flight
    retires cleanly, and the drain leaves zero refs/pages on every
    replica."""
    from mxnet_tpu.serving import ServingCluster
    params, cfg = _setup()
    rng = np.random.RandomState(5)
    prompts = _mixed(rng, cfg.vocab_size)
    maxnew = [6, 4, 8, 5, 7, 3]
    with pools_seen_on("tpu"):
        cl = ServingCluster(params, cfg, replicas=2, num_slots=2,
                            page_size=8, prefill_chunk=6, kernel="xla")
    try:
        rids = [cl.submit(p, n) for p, n in zip(prompts, maxnew)]
        victim = rids[2]
        th = threading.Thread(target=lambda: cl.cancel(victim))
        th.start()
        for rid, p, n in zip(rids, prompts, maxnew):
            if rid == victim:
                continue
            out = cl.result(rid, timeout=300)
            assert np.array_equal(out, _ref(params, cfg, p, n))
        th.join(60)
        cr = cl.requests[victim]
        assert cr.state in ("done", "cancelled")
        if cr.state == "done":
            assert np.array_equal(cl.result(victim, timeout=60),
                                  _ref(params, cfg, prompts[2],
                                       maxnew[2]))
        else:
            exp = _ref(params, cfg, prompts[2],
                       maxnew[2])[prompts[2].size:]
            got = list(cr.committed)
            assert got == [int(t) for t in exp[:len(got)]]
        for rep in cl.replicas:
            eng = rep.engine
            assert eng.overlap
            assert eng.stats["overlap_steps"] > 0
            if eng.prefix is not None:
                assert eng.prefix.refs_total == 0
                assert eng.cache.pages_in_use == \
                    eng.prefix.cached_pages
    finally:
        cl.close(timeout=60)


@pytest.mark.slow
def test_disagg_cluster_overlap_identity():
    """The disaggregated protocol (1 prefill + 1 decode worker) over
    pipelined engines: outputs stay generate-identical, the decode
    worker actually pipelines (every step, by its stats snapshot), and
    no worker leaks pages, refs, or staged streams.  The workers are
    threads of this process (``spawn=False``): a spawned worker builds
    its engine in a process of its own, where this test's observation
    cannot be substituted."""
    import socket
    from mxnet_tpu.serving import DisaggServingCluster
    from mxnet_tpu.serving.cluster import _DisaggWorker
    params, cfg = _setup()
    rng = np.random.RandomState(9)
    prompts = _mixed(rng, cfg.vocab_size, (5, 9, 17, 3, 12))
    nnew = [6, 4, 8, 5, 7]
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    workers = [threading.Thread(
        target=lambda *a: _DisaggWorker(*a).run(), daemon=True,
        args=(role + "0", role, "127.0.0.1", port))
        for role in ("prefill", "decode")]
    with pools_seen_on("tpu"):
        for w in workers:
            w.start()
        cl = DisaggServingCluster(params, cfg, prefill=1, decode=1,
                                  num_slots=4, page_size=4,
                                  metrics=True, watchdog_s=60.0,
                                  kernel="xla", spawn=False, port=port)
    try:
        rids = [cl.submit(p, n) for p, n in zip(prompts, nnew)]
        for rid, p, n in zip(rids, prompts, nnew):
            out = cl.result(rid, timeout=180)
            assert np.array_equal(out, _ref(params, cfg, p, n))
        st = cl.cluster_stats()
        assert st["decode0"]["overlap_steps"] > 0
        for name, ws in st.items():
            assert ws["pages_in_use"] - ws["prefix_cached_pages"] \
                == 0, (name, ws)
            assert ws["prefix_refs"] == 0, (name, ws)
            assert ws["staged_rids"] == 0, (name, ws)
            assert ws["active_requests"] == 0, (name, ws)
    finally:
        cl.close()
        for w in workers:
            w.join(60)


# --------------------------------------------------------- fast tier ---

def _small_run(params, cfg, overlap, **kw):
    """``_engine_run`` at the smallest sizes that still mix the row
    kinds: two slots over five requests (slot reuse), prompts longer
    than a chunk, no prefix cache."""
    return _engine_run(params, cfg, overlap, lens=(3, 9, 5, 12, 4),
                       maxnew=(7, 4, 1, 6, 9), num_slots=2, page_size=4,
                       prefill_chunk=4, **kw)


@pytest.fixture(scope="module")
def small():
    import jax
    from mxnet_tpu.models import transformer as T
    cfg = _cfg(max_len=32, d_model=32, n_heads=2, n_layers=1, d_ff=64)
    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.mark.parametrize("scenario", ["plain", "eos", "preempt",
                                      "cancel"])
def test_pipelined_tokens_are_the_serial_ones(small, scenario):
    """``test_overlap_bit_identical_to_serial`` in the fast tier, one
    layer wide: the schedule a TPU engine takes by itself gives the
    serial schedule's tokens through an eos stop, a preemption and a
    cancel, leaks no page, and did pipeline — every committed step was
    dispatched pipelined (the cold start's too), none of a depth-0
    engine's."""
    params, cfg = small
    kw = {"chaos": scenario} if scenario in ("preempt", "cancel") else {}
    if scenario == "eos":
        # the third token of the longest answer: it stops there, one
        # step behind a row already dispatched for its fourth
        plain, _, _ = _small_run(params, cfg, overlap=False)
        kw = {"eos": max(plain.values(), key=lambda r: len(r[1]))[1][2]}
    a, held_a, sa = _small_run(params, cfg, overlap=False, **kw)
    b, held_b, sb = _small_run(params, cfg, overlap=True, **kw)
    _assert_equiv(a, b, scenario)
    # the scenario happened, in both runs
    for res, st in ((a, sa), (b, sb)):
        if scenario == "eos":
            assert max(len(g) for _, g in res.values()) < 9
        assert st["preemptions"] == (scenario == "preempt")
        assert [state for state, _ in res.values()].count(
            "cancelled") == (scenario == "cancel")
    assert held_a == 0 and held_b == 0, (scenario, held_a, held_b)
    assert sa["steps"] > 0 and sa["overlap_steps"] == 0
    assert sb["steps"] > 0 and sb["overlap_steps"] == sb["steps"]


# (pools on, spec_K) -> pipelined?
_CHOICES = {
    "cpu": ("cpu", 0, False),
    "tpu": ("tpu", 0, True),
    "tpu_speculating": ("tpu", 2, False),
}


@pytest.mark.parametrize("case", sorted(_CHOICES))
def test_engine_chooses_its_schedule(small, monkeypatch, case):
    """The schedule is read from what the engine can observe: the
    platform its pools were placed on (``kernels/platform.platform_of``,
    the test that chooses ``kernel``) and whether it speculates.
    Nothing compiles: no step runs."""
    from mxnet_tpu.kernels import platform
    from mxnet_tpu.serving import ServingEngine
    params, cfg = small
    plat, spec_K, pipelined = _CHOICES[case]
    if plat != "cpu":
        monkeypatch.setattr(platform, "platform_of", lambda *a: plat)
    eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                        prefill_chunk=4, spec_K=spec_K)
    assert platform.platform_of(eng.cache.pools) == plat
    assert eng.overlap is pipelined
    # the schedule and the kernel are read from the same observation
    assert eng.kernel == ("pallas" if plat == "tpu" else "xla")
    eng.close()


@pytest.mark.parametrize("owner", ["engine", "cluster", "disagg"])
def test_schedule_is_not_an_argument(small, owner):
    """``overlap=`` went with the loop it selected (PR 30): the engine
    and both clusters refuse it like any unknown argument, before
    anything is built (no worker is spawned)."""
    from mxnet_tpu import serving
    params, cfg = small
    cls = {"engine": serving.ServingEngine,
           "cluster": serving.ServingCluster,
           "disagg": serving.DisaggServingCluster}[owner]
    for value in (True, False, None):
        with pytest.raises(TypeError, match="overlap"):
            cls(params, cfg, num_slots=2, page_size=4, prefill_chunk=4,
                overlap=value)


def test_engine_starts_no_thread(small):
    """A pipelined run, from construction to the last commit, leaves
    the process's threads as it found them (the planner thread went
    with PR 30: the plan is built inside ``step()``), ``close()`` has
    nothing to join, and calling it again is harmless."""
    params, cfg = small
    before = threading.enumerate()
    eng = _engine(params, cfg, True, num_slots=2, page_size=4,
                  prefill_chunk=4)
    rng = np.random.RandomState(7)
    for p, m in zip(_mixed(rng, cfg.vocab_size, (3, 9, 5)), (7, 4, 6)):
        eng.submit(p, m)
    while eng.step() is not False:
        assert threading.enumerate() == before
    assert eng.stats["steps"] > 0
    assert eng.stats["overlap_steps"] == eng.stats["steps"]
    eng.close()
    eng.close()
    assert threading.enumerate() == before
    assert eng._inflight is None
    assert eng.step() is False


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["serial", "pipelined"])
def test_submit_between_steps_rides_the_next_plan(small, overlap):
    """A request submitted between two ``step()`` calls has its rows in
    the plan the next call dispatches, every time, at either depth: the
    plan is built inside the call, after whatever came before it (with
    a planner thread the next plan was already being built: a race)."""
    params, cfg = small
    eng = _engine(params, cfg, overlap, num_slots=4, page_size=4,
                  prefill_chunk=4)
    plans = []
    dispatch = eng._dispatch

    def recording(plan):
        plans.append(plan)
        return dispatch(plan)
    eng._dispatch = recording
    rng = np.random.RandomState(3)
    for n in (3, 4, 2, 4):
        rid = eng.submit(rng.randint(1, cfg.vocab_size, n)
                         .astype(np.int32), 12)
        seen = len(plans)
        assert eng.step() is not False
        assert len(plans) == seen + 1
        plan, req = plans[-1], eng.requests[rid]
        assert rid in plan.admit and req.slot is not None
        rows = (plan.buf.row_slot == req.slot) & plan.buf.row_live
        assert rows.sum() == n             # its whole prompt, one chunk
        assert eng.step() is not False     # and a step with no newcomer
    eng.run()
    eng.close()
    assert eng.cache.pages_in_use == 0


_ON_A_TPU = """
import sys
sys.path[:0] = [{root!r}, {bench!r}, {tests!r}]
from conftest import pools_seen_on
from mxnet_tpu.serving import engine as E
init = E.ServingEngine.__init__
seen = []
def on_a_tpu(self, *a, **kw):
    with pools_seen_on("tpu"):
        init(self, *a, **dict(kw, kernel="xla"))
    seen.append(self)
E.ServingEngine.__init__ = on_a_tpu
import run
rc = run.main({argv!r})
eng = seen[0]
print("schedule " + __import__("json").dumps(
    {{"overlap": eng.overlap, "steps": eng.stats["steps"],
      "overlap_steps": eng.stats["overlap_steps"]}}), file=sys.stderr)
sys.exit(rc)
"""


@pytest.mark.parametrize("cell", ["bert_large_decoder.decode_heavy",
                                  "falcon_h1_34b_l6.chat_decode",
                                  "lfm2_8b_a1b_l12.long_decode"])
def test_serving_cells_rehearse_pipelined(cell):
    """The benchmark's serving cells follow the engine's choice with no
    benchmark file edited, so their harness has to be schedule-agnostic:
    it reads ``Request.generated`` after each ``step()`` and never the
    call's return value.  ``chipbench/run.py --rehearse`` with every
    engine seen on a TPU (the CPU's own engines are serial) reaches
    ``correct`` against the reference, no request failed, every step of
    the run pipelined."""
    argv = ["--workload", cell, "--seed", str(2 ** 31 + 29),
            "--seconds", "1", "--trace", "0", "--rehearse"]
    code = _ON_A_TPU.format(
        root=ROOT, bench=os.path.join(ROOT, "chipbench"),
        tests=os.path.join(ROOT, "tests"), argv=argv)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    sched = json.loads(next(ln for ln in r.stderr.splitlines()
                            if ln.startswith("schedule "))[9:])
    assert sched["overlap"] is True
    assert sched["steps"] > 0
    assert sched["overlap_steps"] == sched["steps"]


def test_pipelined_step_share_reader(small):
    """The benchmark's reader of ``overlap_steps`` over ``steps``
    (``chipbench/layer_metrics/pipelined_step_share.serve.py``): their
    ratio in percent over a window's counter deltas; nothing, without
    raising, from a program that books no such counter or from a window
    in which no step was committed; 0 and 100 from a serial and a
    pipelined engine's own ``stats``."""
    import importlib.util
    path = os.path.join(ROOT, "chipbench", "layer_metrics",
                        "pipelined_step_share.serve.py")
    spec = importlib.util.spec_from_file_location("pipelined_share", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    read = lambda counters: reader.read({}, {}, counters, None)  # noqa: E731
    assert read({"steps": 400, "dead_rows": 17000}) is None
    assert read({"steps": 0, "overlap_steps": 0}) is None
    assert read({"overlap_steps": 3}) is None
    assert read({"steps": 2400, "overlap_steps": 0}) == 0.0
    assert read({"steps": 3400, "overlap_steps": 3397}) == \
        pytest.approx(99.91, abs=0.01)
    params, cfg = small
    for overlap, share in ((False, 0.0), (True, 100.0)):
        _, _, stats = _small_run(params, cfg, overlap=overlap)
        assert read(stats) == share
