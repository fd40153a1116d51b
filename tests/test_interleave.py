"""Deterministic interleaving explorer over the serving cluster
(ISSUE 7, dynamic half).  Slow tier, group h.

The sweep runs >= 200 seeded schedules (7 scripted workloads x 2
strategies x 20 seeds = 280; round 18 added the tier workload — spill
racing match racing preemption; round 21 added the overlap workload —
the pipelined step loop racing submits and a mid-pipeline cancel)
through
``tools.analysis.interleave``: every
schedule serializes the cluster's threads onto one runnable-at-a-time
order chosen by the seed, and asserts the same invariants the static
pass reasons about —

* **f32 greedy exactness**: every completed request is token-identical
  to single-engine ``generate`` whatever the interleaving;
* **refcount balance**: after drain, every replica's prefix-cache
  refcounts are zero and no page leaks (pages_in_use == cache-owned);
* **no deadlock**: the scheduler proves it by construction (all-blocked
  with no timed wait raises ``DeadlockError``), and the seeded-deadlock
  toy proves the detector actually fires.

Determinism pin: identical (workload, strategy, seed) triples produce
bit-identical yield-trace hashes.
"""
import numpy as np
import pytest
from conftest import pools_seen_on

import mxnet_tpu as mx  # noqa: F401  (conftest device setup)

from tools.analysis.interleave import DeadlockError, run_schedule

SEEDS = 20          # per (workload, strategy) cell; 7 * 2 * 20 = 280
MODES = ("random", "preempt")


def _cfg(**kw):
    from mxnet_tpu.models import gpt
    base = dict(use_flash=False, remat=False, dropout=0.0,
                dtype="float32", vocab_size=128, max_len=64)
    base.update(kw)
    return gpt.gpt_tiny(**base)


@pytest.fixture(scope="module")
def env():
    """Params/cfg + memoized single-engine references, with every
    compile warmed OUTSIDE the scheduler (the step/copy caches are
    config-keyed, so the schedules themselves never compile)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import gpt
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.serving import ServingCluster

    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    cl = ServingCluster(params, cfg, replicas=1, num_slots=2,
                        page_size=4, prefill_chunk=6)
    rid = cl.submit(np.arange(1, 7, dtype=np.int32), 4)
    cl.result(rid, timeout=300)
    cl.close(timeout=60)
    # the overlap (tok_src) step program is a DIFFERENT compiled
    # variant — warm it too, same engine geometry as the workloads
    # (wl_overlap_plan must never compile under the scheduler)
    from mxnet_tpu.serving import ServingEngine
    with pools_seen_on("tpu"):
        eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                            prefill_chunk=6, prefix_cache=True,
                            kernel="xla")
    assert eng.overlap
    eng.submit(np.arange(1, 7, dtype=np.int32), 4)
    eng.run()
    eng.close()

    refs = {}

    def ref(prompt, n):
        key = (prompt.tobytes(), n)
        if key not in refs:
            refs[key] = np.asarray(gpt.generate(
                params, cfg, jnp.asarray(prompt)[None], n))[0]
        return refs[key]

    return params, cfg, ref


# ---------------------------------------------------------------------------
# scripted workloads — each builds, drives, verifies, and closes one
# cluster; prompts are fixed (same work under every schedule)
# ---------------------------------------------------------------------------
def _prompts_mixed(n):
    rng = np.random.RandomState(0)
    shared = rng.randint(1, 90, 8).astype(np.int32)
    out = []
    for i in range(n):
        if i % 2 == 0:
            p = np.concatenate([shared, rng.randint(1, 90, 2 + i)
                                .astype(np.int32)])
        else:
            p = rng.randint(1, 90, 4 + i).astype(np.int32)
        out.append((p, 3 + (i % 3)))
    return out


def _check_refcounts(cl):
    for rep in cl.replicas:
        prefix = rep.engine.prefix
        if prefix is None or rep.dead:
            continue
        assert prefix.refs_total == 0, \
            "replica %d leaked prefix refs" % rep.idx
        assert rep.engine.cache.pages_in_use == prefix.cached_pages, \
            "replica %d leaked pages" % rep.idx


def wl_submit_burst(params, cfg, ref):
    from mxnet_tpu.serving import ServingCluster
    wl = _prompts_mixed(5)
    cl = ServingCluster(params, cfg, replicas=2, num_slots=2,
                        page_size=4, prefill_chunk=6)
    try:
        rids = [cl.submit(p, n) for p, n in wl]
        for rid, (p, n) in zip(rids, wl):
            np.testing.assert_array_equal(cl.result(rid, timeout=300),
                                          ref(p, n))
        _check_refcounts(cl)
    finally:
        cl.close(timeout=60)


def wl_failover(params, cfg, ref):
    """Replica 0's engine raises on its 3rd step: waiting + in-flight
    requests must resubmit to the survivor recompute-exact."""
    from mxnet_tpu.serving import ServingCluster
    wl = _prompts_mixed(4)
    cl = ServingCluster(params, cfg, replicas=2, num_slots=2,
                        page_size=4, prefill_chunk=6)
    try:
        eng0 = cl.replicas[0].engine
        orig_step = eng0.step
        calls = [0]

        def bomb():
            calls[0] += 1
            if calls[0] == 3:
                raise RuntimeError("injected replica failure")
            return orig_step()

        eng0.step = bomb
        rids = [cl.submit(p, n) for p, n in wl]
        for rid, (p, n) in zip(rids, wl):
            np.testing.assert_array_equal(cl.result(rid, timeout=300),
                                          ref(p, n))
        _check_refcounts(cl)
    finally:
        cl.close(timeout=60)


def wl_drain_while_submitting(params, cfg, ref):
    """drain_replica(0) racing a burst of submit(): every request —
    rerouted stray or post-drain submit — completes exactly."""
    from mxnet_tpu.serving import ServingCluster
    from mxnet_tpu.serving import cluster as cluster_mod
    wl = _prompts_mixed(6)
    cl = ServingCluster(params, cfg, replicas=2, num_slots=2,
                        page_size=4, prefill_chunk=6)
    try:
        rids = []

        def submitter():
            for p, n in wl:
                rids.append(cl.submit(p, n))

        # cluster_mod.threading is the scheduler shim inside a
        # schedule (and the real module outside one)
        th = cluster_mod.threading.Thread(target=submitter,
                                          name="submitter")
        th.start()
        assert cl.drain_replica(0, timeout=300)
        th.join(300)
        assert len(rids) == len(wl)
        for rid, (p, n) in zip(rids, wl):
            np.testing.assert_array_equal(cl.result(rid, timeout=300),
                                          ref(p, n))
        for cr in (cl.requests[r] for r in rids):
            assert cr.state == "done"
        _check_refcounts(cl)
    finally:
        cl.close(timeout=60)


def wl_ttl_expiry(params, cfg, ref):
    """A ttl_s=0 request expires while waiting; traffic around it is
    unaffected."""
    from mxnet_tpu.serving import (RequestExpired, ServingCluster)
    cl = ServingCluster(params, cfg, replicas=1, num_slots=1,
                        page_size=4, prefill_chunk=4)
    try:
        rng = np.random.RandomState(7)
        p_ok = rng.randint(1, 90, 4).astype(np.int32)
        r_ok = cl.submit(p_ok, 8)
        r_ttl = cl.submit(rng.randint(1, 90, 4).astype(np.int32), 4,
                          ttl_s=0.0)
        with pytest.raises(RequestExpired):
            cl.result(r_ttl, timeout=300)
        np.testing.assert_array_equal(cl.result(r_ok, timeout=300),
                                      ref(p_ok, 8))
        _check_refcounts(cl)
    finally:
        cl.close(timeout=60)


def wl_prefix_cow(params, cfg, ref):
    """Prefix-COW under scheduling: a cached chain is re-hit by a
    whole-input duplicate and a mid-page divergence — both exact, both
    COW, refcounts drain to zero."""
    from mxnet_tpu.serving import ServingCluster
    rng = np.random.RandomState(1)
    pa = rng.randint(1, 90, 16).astype(np.int32)     # 4 full pages
    pc = np.concatenate([pa[:14],
                         rng.randint(90, 120, 4).astype(np.int32)])
    cl = ServingCluster(params, cfg, replicas=1, num_slots=2,
                        page_size=4, prefill_chunk=8)
    try:
        ra = cl.submit(pa, 6)
        np.testing.assert_array_equal(cl.result(ra, timeout=300),
                                      ref(pa, 6))
        rb = cl.submit(pa, 6)          # whole-input match -> COW
        rc = cl.submit(pc, 6)          # diverges inside page 3 -> COW
        np.testing.assert_array_equal(cl.result(rb, timeout=300),
                                      ref(pa, 6))
        np.testing.assert_array_equal(cl.result(rc, timeout=300),
                                      ref(pc, 6))
        assert cl.replicas[0].engine.stats["cow_copies"] == 2
        assert cl.replicas[0].engine.stats["prefix_hit_tokens"] > 0
        _check_refcounts(cl)
    finally:
        cl.close(timeout=60)


def wl_tier_spill(params, cfg, ref):
    """Round 18: spill racing match racing preemption.  One replica,
    a pool tight enough that concurrent fillers force pressure spills
    of the cached chain to the host tier WHILE a duplicate prompt
    re-matches it (warm restore) and slot contention preempts
    (swap-out → install-exact resume).  Whatever the schedule
    interleaves — spill-then-match, match-then-spill, preempt in
    between — every output is exact and nothing leaks (pages, refs,
    or tier bytes for retired swaps)."""
    from mxnet_tpu.serving import ServingCluster
    from mxnet_tpu.serving import cluster as cluster_mod
    rng = np.random.RandomState(2)
    pa = rng.randint(1, 90, 16).astype(np.int32)     # 4 full pages
    fills = [rng.randint(1, 90, 12).astype(np.int32)
             for _ in range(3)]
    cl = ServingCluster(params, cfg, replicas=1, num_slots=2,
                        page_size=4, prefill_chunk=6,
                        pages_per_slot=6, num_pages=11,
                        tier_bytes=1 << 20)
    try:
        assert cl.replicas[0].engine.tier is not None
        ra = cl.submit(pa, 4)
        np.testing.assert_array_equal(cl.result(ra, timeout=300),
                                      ref(pa, 4))
        rids = []

        def filler():
            # pressure: each filler wants 4 pages of the 10-usable
            # pool while pa's 4-page chain sits cached refcount-0 —
            # the spills race the warm re-match below
            for f in fills:
                rids.append((cl.submit(f, 4), f, 4))

        th = cluster_mod.threading.Thread(target=filler,
                                          name="tier-filler")
        th.start()
        rb = cl.submit(pa, 4)            # re-match: hot, warm, or cold
        np.testing.assert_array_equal(cl.result(rb, timeout=300),
                                      ref(pa, 4))
        th.join(300)
        for rid, f, n in rids:
            np.testing.assert_array_equal(cl.result(rid, timeout=300),
                                          ref(f, n))
        _check_refcounts(cl)
        eng = cl.replicas[0].engine
        # retired/cancelled requests must not squat swap entries
        assert not any(isinstance(k, tuple) and k[0] == "swap"
                       for k in eng.tier._entries), \
            "stale swap entries after drain"
    finally:
        cl.close(timeout=60)


def wl_overlap_plan(params, cfg, ref):
    """Round 21: the pipelined step loop racing submits and cancels.
    One replica whose engine is pipelined (its pools seen on a TPU) —
    every step's plan is built under the engine lock while the
    previous step executes — with a submit burst arriving through a
    second thread and a cancel landing at whatever pipeline depth the
    schedule picks.  Every completed request must be exact (the
    carried-token reconciliation may never leak a speculatively
    dispatched token into a commit), the cancelled request must
    retire without leaking its pages, and the drain must leave zero
    refs — under EVERY schedule."""
    from mxnet_tpu.serving import ServingCluster
    from mxnet_tpu.serving import cluster as cluster_mod
    wl = _prompts_mixed(5)
    with pools_seen_on("tpu"):
        cl = ServingCluster(params, cfg, replicas=1, num_slots=2,
                            page_size=4, prefill_chunk=6, kernel="xla")
    try:
        assert cl.replicas[0].engine.overlap
        first = [cl.submit(p, n) for p, n in wl[:2]]
        rids = []

        def submitter():
            for p, n in wl[2:]:
                rids.append(cl.submit(p, n))
            # cancel the second request at whatever point this
            # schedule has the pipeline: queued, planned, dispatched
            # speculatively, or already done — all must be clean
            cl.cancel(first[1])

        th = cluster_mod.threading.Thread(target=submitter,
                                          name="overlap-submitter")
        th.start()
        np.testing.assert_array_equal(
            cl.result(first[0], timeout=300), ref(*wl[0]))
        th.join(300)
        for rid, (p, n) in zip(rids, wl[2:]):
            np.testing.assert_array_equal(cl.result(rid, timeout=300),
                                          ref(p, n))
        cr = cl.requests[first[1]]
        if cr.state == "done":            # finish beat the cancel
            np.testing.assert_array_equal(
                cl.result(first[1], timeout=300), ref(*wl[1]))
        else:
            assert cr.state == "cancelled"
            # whatever the pipeline committed before the cancel must
            # prefix the oracle (a bogus carried token would show up
            # exactly here)
            exp = ref(*wl[1])[wl[1][0].size:]
            got = list(cr.committed)
            assert got == list(exp[:len(got)])
        eng = cl.replicas[0].engine
        assert eng.stats["overlap_steps"] > 0
        _check_refcounts(cl)
    finally:
        cl.close(timeout=60)


WORKLOADS = {
    "burst": wl_submit_burst,
    "failover": wl_failover,
    "drain": wl_drain_while_submitting,
    "ttl": wl_ttl_expiry,
    "cow": wl_prefix_cow,
    "tier": wl_tier_spill,
    "overlap": wl_overlap_plan,
}


# ---------------------------------------------------------------------------
# the >= 200-schedule sweep (acceptance criterion)
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_schedule_sweep(env, name, mode):
    """20 seeds per (workload, strategy) cell — 200 schedules total
    across the parameterized matrix, every one clean."""
    params, cfg, ref = env
    wl = WORKLOADS[name]
    for seed in range(SEEDS):
        try:
            stats = run_schedule(lambda: wl(params, cfg, ref), seed,
                                 mode=mode)
        except BaseException as e:
            raise AssertionError(
                "schedule (workload=%s, mode=%s, seed=%d) failed: %r"
                % (name, mode, seed, e)) from e
        assert stats.yields > 0


# ---------------------------------------------------------------------------
# explorer properties
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_deterministic_per_seed(env):
    """Same (workload, strategy, seed) -> bit-identical trace hash;
    different seeds genuinely explore different interleavings."""
    params, cfg, ref = env
    hashes = {}
    for seed in range(6):
        a = run_schedule(lambda: wl_submit_burst(params, cfg, ref),
                         seed, mode="random")
        b = run_schedule(lambda: wl_submit_burst(params, cfg, ref),
                         seed, mode="random")
        assert a.trace_hash == b.trace_hash, "seed %d" % seed
        assert a.yields == b.yields and a.switches == b.switches
        hashes[seed] = a.trace_hash
    assert len(set(hashes.values())) >= 4, \
        "seeds barely explored: %r" % hashes
    assert a.switches > 0


@pytest.mark.slow
def test_preempt_mode_switches_more(env):
    """The targeted strategy forces a switch at every lock
    acquire/release — its switch/yield ratio must dominate random's."""
    params, cfg, ref = env
    r = run_schedule(lambda: wl_submit_burst(params, cfg, ref), 0,
                     mode="random")
    p = run_schedule(lambda: wl_submit_burst(params, cfg, ref), 0,
                     mode="preempt")
    assert p.switches / max(1, p.yields) > \
        r.switches / max(1, r.yields)


@pytest.mark.slow
def test_deadlock_detection_fires(env):
    """The explorer's verdict is trustworthy only if the detector
    provably fires: a two-lock opposite-order toy (forced across via
    events) must raise DeadlockError under EVERY seed."""
    def wl():
        from mxnet_tpu.serving import cluster as cm
        la, lb = cm.threading.Lock(), cm.threading.Lock()
        ea, eb = cm.threading.Event(), cm.threading.Event()

        def t1():
            with la:
                ea.set()
                eb.wait()
                with lb:
                    pass

        def t2():
            with lb:
                eb.set()
                ea.wait()
                with la:
                    pass

        th1 = cm.threading.Thread(target=t1, name="t1")
        th2 = cm.threading.Thread(target=t2, name="t2")
        th1.start()
        th2.start()
        th1.join()
        th2.join()

    for seed in range(3):
        with pytest.raises(DeadlockError):
            run_schedule(wl, seed, mode="random")


@pytest.mark.slow
def test_model_time_jumps(env):
    """Timed waits execute in model time: a full TTL workload (0.02 s
    idle waits, 0.25 s monitor periods) finishes in well under a
    second of wall clock, proving waits jump rather than sleep."""
    import time
    params, cfg, ref = env
    t0 = time.perf_counter()
    stats = run_schedule(lambda: wl_ttl_expiry(params, cfg, ref), 0,
                         mode="random")
    assert stats.model_time > 0
    assert time.perf_counter() - t0 < 30.0
