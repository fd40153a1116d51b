"""Disaggregated prefill/decode serving (round 15).

FAST tier: the wire layer (raw frames, bounded/garbage length
prefixes, peer killed mid-frame), page export/install roundtrips, the
cluster prefix index, and an in-process prefill→install→adopt
simulation of the cross-process handoff (``admit_prefilled``).

SLOW tier (group j): whole-OS-process clusters — f32-greedy
bit-identity to the single-engine ``generate`` oracle across the
prefill/decode split, cluster-level prefilled-exactly-once
reconciliation via the remote-hit counters, SIGKILL of a prefill
process mid-stream and of a decode process mid-decode with
recompute-exact completion and zero leaked pages/refs on survivors,
preemption/resume on the decode side, and int8-KV page transfer.
"""
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(dtype="float32"):
    import jax
    from mxnet_tpu.models import gpt as G
    cfg = G.gpt_tiny(dtype=dtype)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _gen_ref(params, cfg, prompt, n):
    from mxnet_tpu.models import gpt as G
    return np.asarray(G.generate(params, cfg, prompt[None, :], n))[0]


# ===========================================================================
# FAST tier — wire layer
# ===========================================================================

def test_raw_frame_roundtrip():
    from mxnet_tpu.parallel.dist import send_frame, recv_frame
    a, b = socket.socketpair()
    try:
        payload = [np.arange(100, dtype=np.int8).data,
                   np.arange(7, dtype=np.float32).data]
        send_frame(a, {"kind": "pages", "n": 2}, payload)
        meta, bufs = recv_frame(b)
        assert meta == {"kind": "pages", "n": 2}
        assert bytes(bufs[0]) == np.arange(100, dtype=np.int8).tobytes()
        assert bytes(bufs[1]) == \
            np.arange(7, dtype=np.float32).tobytes()
        # legacy pickled frames travel the same wire
        from mxnet_tpu.parallel.dist import _send
        _send(a, ("push", "k", 1))
        obj, none = recv_frame(b)
        assert obj == ("push", "k", 1) and none is None
    finally:
        a.close()
        b.close()


def test_recv_bounds_garbage_length_prefix():
    """A garbage/oversized length prefix (peer killed mid-frame, or a
    foreign protocol) must raise, not allocate gigabytes."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel.dist import _recv, recv_frame, \
        MAX_FRAME_BYTES
    for reader in (_recv, recv_frame):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack("<Q", MAX_FRAME_BYTES + 1))
            with pytest.raises(MXNetError, match="length"):
                reader(b)
        finally:
            a.close()
            b.close()
    # a raw frame on the kvstore's pickled-only path is also an error
    a, b = socket.socketpair()
    try:
        from mxnet_tpu.parallel.dist import send_frame
        send_frame(a, {"kind": "x"}, [])
        with pytest.raises(MXNetError, match="raw frame"):
            _recv(b)
    finally:
        a.close()
        b.close()


def test_recv_peer_closed_mid_frame_reads_as_eof():
    """Half a frame then an abortive close (the SIGKILL shape) must
    read as EOF (None), not an exception racing __del__."""
    from mxnet_tpu.parallel.dist import recv_frame
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack("<Q", 1000) + b"x" * 10)  # 990 short
        # abortive close: RST instead of FIN, like a killed process
        a.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        a.close()
        assert recv_frame(b) is None
    finally:
        b.close()


@pytest.mark.slow
def test_dist_kvstore_survives_server_sigkill_mid_frame():
    """Satellite regression: a kvstore worker whose server process is
    SIGKILLed mid-traffic must surface the failure at a sync point
    (deferred-error contract), and close()/__del__ must be safe —
    no hang, no exception out of the destructor."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel.dist import DistKVStore
    port_probe = socket.socket()
    port_probe.bind(("127.0.0.1", 0))
    port = port_probe.getsockname()[1]
    port_probe.close()
    server = subprocess.Popen(
        [sys.executable, "-c",
         "from mxnet_tpu.parallel.dist import DistServer;"
         "s = DistServer(port=%d, num_workers=1, sync_mode=True);"
         "s.serve_forever()" % port],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": REPO},
        cwd=REPO)
    old = dict(os.environ)
    os.environ.update({"DMLC_PS_ROOT_URI": "127.0.0.1",
                       "DMLC_PS_ROOT_PORT": str(port),
                       "DMLC_NUM_WORKER": "1", "DMLC_WORKER_ID": "0"})
    try:
        kv = DistKVStore("dist_sync")
        kv.init("w", mx.nd.zeros((4,)))
        os.kill(server.pid, signal.SIGKILL)
        server.wait(timeout=30)
        # pushes after the kill die on the wire; the error must
        # surface at the next sync op, not crash the sender thread
        with pytest.raises(mx.MXNetError):
            for _ in range(50):
                kv.push("w", mx.nd.ones((4,)))
                kv.barrier()
        t0 = time.perf_counter()
        kv.close()                        # bounded, no hang
        assert time.perf_counter() - t0 < 15
        kv.__del__()                      # destructor must not raise
    finally:
        os.environ.clear()
        os.environ.update(old)
        if server.poll() is None:
            server.kill()


# ===========================================================================
# FAST tier — page transfer + index
# ===========================================================================

def _fill_pages(cache, ids, seed=0):
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    pools = []
    for pool in cache.pools:
        lay = {}
        for k, v in pool.items():
            a = np.asarray(jax.device_get(v)).copy()
            a[ids] = rng.randint(-100, 100,
                                 a[ids].shape).astype(a.dtype)
            lay[k] = jnp.asarray(a)
        pools.append(lay)
    cache.pools = pools


@pytest.mark.parametrize("kv_int8", [False, True])
def test_page_export_install_roundtrip(kv_int8):
    from mxnet_tpu.models import gpt as G
    from mxnet_tpu.serving.paged_kv import PagedKVCache
    from mxnet_tpu.serving.page_streamer import pages_to_bufs, \
        bufs_to_pages, page_wire_bytes
    cfg = G.gpt_tiny()
    src = PagedKVCache(cfg, 9, 4, kv_int8=kv_int8)
    _fill_pages(src, [1, 2, 5])
    content = src.export_pages([1, 2, 5])
    # the wire layout: raw buffers, byte count == pool bytes
    bufs = pages_to_bufs(content)
    assert sum(memoryview(b).nbytes for b in bufs) == \
        page_wire_bytes(src, 3)
    dst = PagedKVCache(cfg, 9, 4, kv_int8=kv_int8)
    ids = dst.alloc(3)
    dst.install_pages(ids, bufs_to_pages(dst, 3, bufs))
    back = dst.export_pages(ids)
    for l1, l2 in zip(content, back):
        for k in l1:
            assert np.array_equal(np.asarray(l1[k]),
                                  np.asarray(l2[k]))


def test_install_pages_validates_shape():
    from mxnet_tpu.models import gpt as G
    from mxnet_tpu.serving.paged_kv import PagedKVCache
    cfg = G.gpt_tiny()
    c = PagedKVCache(cfg, 5, 4)
    content = c.export_pages([1, 2])
    with pytest.raises(ValueError, match="does not match"):
        c.install_pages([1], content)     # 2 pages of content, 1 id
    with pytest.raises(ValueError, match="layers"):
        c.install_pages([1, 2], content[:-1])


def test_transport_tree_roundtrip():
    from mxnet_tpu.serving.transport import tree_to_frames, \
        frames_to_tree
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "layers": [{"w": np.ones((2, 2), np.int8)},
                       {"w": np.zeros((1,), np.float64)}]}
    meta, bufs = tree_to_frames(tree)
    back = frames_to_tree(meta, [bytearray(b) for b in bufs])
    assert np.array_equal(back["a"], tree["a"])
    assert back["layers"][0]["w"].dtype == np.int8
    assert np.array_equal(back["layers"][1]["w"],
                          tree["layers"][1]["w"])


def test_put_transport_segment_roundtrip():
    """Round 22 put-path primitives: ``put_write`` lands buffers in
    one pid-prefixed shm segment, ``put_read`` maps them back
    byte-identical AND unlinks at open (on-disk segments exist only
    in flight), ``release`` is idempotent and balances the open
    counter, and ``put_sweep`` reclaims an unreceived segment by its
    writer's pid."""
    import glob
    from mxnet_tpu.serving.transport import (
        PUT_DIR, PUT_STATS, put_read, put_sweep, put_write)

    bufs = [np.arange(64, dtype=np.float32).tobytes(),
            np.arange(5, dtype=np.int8).tobytes()]
    path, sizes = put_write(bufs)
    assert os.path.exists(path) and sizes == [256, 5]
    assert str(os.getpid()) in os.path.basename(path)
    got = put_read(path, sizes)
    assert not os.path.exists(path)       # unlinked AT open
    assert bytes(got[0]) == bufs[0] and bytes(got[1]) == bufs[1]
    opens, rels = PUT_STATS["opens"], PUT_STATS["releases"]
    got.release()
    got.release()                         # idempotent
    assert PUT_STATS["releases"] == rels + 1
    assert PUT_STATS["opens"] == opens
    # a never-received segment sweeps by pid (the SIGKILL-recovery
    # path the router runs for a killed worker)
    path2, _ = put_write(bufs)
    assert put_sweep(os.getpid()) >= 1
    assert not os.path.exists(path2)
    assert not glob.glob(os.path.join(
        PUT_DIR, "mxserve-put-%d-*" % os.getpid()))


def test_put_capability_negotiation():
    """Eligibility is strictly both-sides-advertised + same shm
    domain; MXNET_SERVE_TRANSPORT=socket kills the advertisement
    entirely (the negotiated fallback every mismatch takes)."""
    from mxnet_tpu.serving.transport import (put_capability,
                                             put_eligible)
    mine = put_capability()
    assert mine is not None and mine["put_pages"]
    assert put_eligible(mine, dict(mine))
    assert not put_eligible(mine, None)
    assert not put_eligible(None, dict(mine))
    assert not put_eligible(mine, dict(mine, host="elsewhere"))
    assert not put_eligible(mine, dict(mine, put_pages=False))
    old = os.environ.get("MXNET_SERVE_TRANSPORT")
    os.environ["MXNET_SERVE_TRANSPORT"] = "socket"
    try:
        assert put_capability() is None
    finally:
        if old is None:
            del os.environ["MXNET_SERVE_TRANSPORT"]
        else:
            os.environ["MXNET_SERVE_TRANSPORT"] = old


def test_put_transport_conn_handshake_and_frames():
    """A live socket pair: caps frames record the peer capability on
    the connection, a put-carrying frame materializes as zero-copy
    views (body bytes NOT on the socket), and the receiver's recv
    unlinked the segment."""
    from mxnet_tpu.serving.transport import (Connection, Listener,
                                             connect, put_write)
    accepted, frames = [], []
    evt = threading.Event()

    def handler(conn):
        conn.send_caps()
        accepted.append(conn)
        evt.set()
        while True:
            got = conn.recv()
            if got is None:
                return
            frames.append(got)

    lis = Listener().start(handler)
    try:
        c = connect(lis.host, lis.port)
        c.send_caps()
        caps = c.wait_caps(timeout=5.0)
        assert caps is not None and caps["put_pages"]
        assert evt.wait(5.0)
        payload = [b"x" * 4096, b"y" * 128]
        path, sizes = put_write(payload)
        before = c.bytes_sent
        c.send("pages", {"srid": (1, 0), "start": 0, "n": 1,
                         "put": {"path": path, "sizes": sizes}}, ())
        # body did NOT ride the socket: only the header went out
        assert c.bytes_sent == before
        srv = accepted[0]
        deadline = time.time() + 5
        while len(frames) < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert srv.peer_put is not None   # our caps recorded over there
        kind, meta, bufs = frames[-1]
        assert kind == "pages" and meta["n"] == 1
        assert bytes(bufs[0]) == payload[0]
        assert bytes(bufs[1]) == payload[1]
        assert not os.path.exists(path)   # receiver unlinked at open
        bufs.release()
    finally:
        lis.close()


def test_page_receiver_releases_held_put_segments():
    """The unified hold representation: a pool-dry hold keeps the
    transport's buffers AS DELIVERED (no downgrade copy), and abort
    releases put-backed holds — segment lifetime is bounded by
    staging lifetime."""
    from mxnet_tpu.serving.page_streamer import PageReceiver

    class _Bufs(list):
        def __init__(self, it):
            super().__init__(it)
            self.released = False

        def release(self):
            self.released = True

    class _Cache:
        def alloc(self, n):
            return None                   # pool permanently dry

        def free(self, ids):
            pass

    class _Eng:
        cache = _Cache()

    rec = PageReceiver(_Eng())
    held = _Bufs([b"a", b"b"])
    rec.on_pages((7, 0), 0, 1, held)
    assert rec._staged[(7, 0)].held[0][1] is held   # no copy
    assert not held.released
    rec.abort((7, 0))
    assert held.released


def test_cluster_prefix_index_semantics():
    from mxnet_tpu.serving import ClusterPrefixIndex
    idx = ClusterPrefixIndex()
    k = [b"a", b"ab", b"abc"]
    assert idx.match(k) == (None, 0, None)
    idx.report_insert("p0", k[:2])
    assert idx.match(k) == ("p0", 2, "hbm")
    # first-inserter-wins: p1's duplicate insert does not steal keys
    idx.report_insert("p1", k)
    assert idx.match(k) == ("p0", 2, "hbm")  # k[2] now p1's, but chain
    # eviction only by the owner
    idx.report_evict("p1", [k[0]])
    assert idx.match(k) == ("p0", 2, "hbm")
    idx.report_evict("p0", [k[0]])
    assert idx.match(k) == (None, 0, None)   # chain head gone
    # a dead replica's keys drop wholesale
    idx.report_insert("p0", k)
    idx.drop_owner("p0")
    owner, d, _ = idx.match(k)
    assert owner in (None, "p1")          # p1 still owns k[2] only
    assert idx.match([k[2]]) == ("p1", 1, "hbm")


def test_cluster_prefix_index_tier_tags():
    """Round 18: per-key tier tags — only the owner may re-tag, a
    chain with any host-tier page summarizes as 'host', eviction and
    owner death clear the tags."""
    from mxnet_tpu.serving import ClusterPrefixIndex
    idx = ClusterPrefixIndex()
    k = [b"a", b"ab", b"abc"]
    idx.report_insert("p0", k)
    assert idx.match(k) == ("p0", 3, "hbm")
    # leaf spilled: the chain summary flips to host
    idx.report_tier("p0", [k[2]], "host")
    assert idx.match(k) == ("p0", 3, "host")
    assert idx.match(k[:2]) == ("p0", 2, "hbm")
    # a non-owner's re-tag is ignored
    idx.report_tier("p1", [k[0]], "host")
    assert idx.match(k[:1]) == ("p0", 1, "hbm")
    # warm restore re-tags back
    idx.report_tier("p0", [k[2]], "hbm")
    assert idx.match(k) == ("p0", 3, "hbm")
    assert idx.keys_retagged_total == 2
    # a real eviction clears key AND tag; a later insert is hbm again
    idx.report_tier("p0", [k[2]], "host")
    idx.report_evict("p0", [k[2]])
    idx.report_insert("p0", [k[2]])
    assert idx.match(k) == ("p0", 3, "hbm")
    import pytest
    with pytest.raises(ValueError):
        idx.report_tier("p0", [k[0]], "warm")


def test_admit_prefilled_adopts_handoff_exactly():
    """In-process simulation of the cross-process handoff: engine A
    prefills (1-token budget), its retire-snapshot pages export;
    engine B installs them and adopts the request mid-decode —
    output must be bit-identical to the ``generate`` oracle."""
    from mxnet_tpu.serving import ServingEngine
    from mxnet_tpu.serving.page_streamer import pages_to_bufs, \
        bufs_to_pages
    params, cfg = _tiny()
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, cfg.vocab_size, 13).astype(np.int32)
    n_new = 7

    snap = {}
    A = ServingEngine(params, cfg, num_slots=2, page_size=4,
                      prefix_cache=True)
    A.retire_cb = lambda req: snap.update(
        pages=list(req.pages), n_cached=req.n_cached)
    rid = A.submit(prompt, 1)
    A.run()
    t0 = int(A.requests[rid].generated[0])
    n_pages = -(-snap["n_cached"] // A.page_size)
    bufs = pages_to_bufs(A.cache.export_pages(
        snap["pages"][:n_pages]))

    B = ServingEngine(params, cfg, num_slots=2, page_size=4)
    ids = B.cache.alloc(n_pages)
    B.cache.install_pages(ids, bufs_to_pages(B.cache, n_pages, bufs))
    erid = B.admit_prefilled(prompt, [t0], ids,
                             max_new_tokens=n_new)
    B.run()
    out = B.requests[erid].output
    assert np.array_equal(out, _gen_ref(params, cfg, prompt, n_new))
    # no leaks: the adopted request retired and recycled its pages
    assert B.cache.pages_in_use == 0


def test_admit_prefilled_validation():
    from mxnet_tpu.serving import ServingEngine
    params, cfg = _tiny()
    eng = ServingEngine(params, cfg, num_slots=1, page_size=4)
    with pytest.raises(ValueError, match="committed token"):
        eng.admit_prefilled(np.ones(4, np.int32), [], [1],
                            max_new_tokens=2)
    with pytest.raises(ValueError, match="cannot cover"):
        eng.admit_prefilled(np.ones(9, np.int32), [5], [1],
                            max_new_tokens=2)


def test_spawn_on_a_tpu_host_is_one_chip_per_worker(monkeypatch):
    """A chip belongs to one process at a time (PR 21, four-chip run):
    ``spawn=True`` on a TPU host refuses at construction — with the
    reason — when this process already holds the chips or when there
    are fewer chips than workers, instead of workers that die at
    backend init behind a bare handshake failure."""
    from mxnet_tpu import context
    from mxnet_tpu.serving import DisaggServingCluster
    params, cfg = _tiny()
    kw = dict(prefill=1, decode=1, num_slots=2, page_size=4,
              prefill_chunk=4, spawn=True, ready_timeout=5)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")

    monkeypatch.setattr(context, "host_tpu_chips", lambda: 4)
    monkeypatch.setattr(context, "held_accelerator", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the host's chips"):
        DisaggServingCluster(params, cfg, **kw)

    # parent off the chips, two workers, one chip: the first worker
    # gets chip 0, the second is refused (and the first reaped)
    monkeypatch.setattr(context, "host_tpu_chips", lambda: 1)
    monkeypatch.setattr(context, "held_accelerator", lambda: None)
    with pytest.raises(RuntimeError, match="owned by live workers"):
        DisaggServingCluster(params, cfg, **kw)
    assert "TPU_VISIBLE_CHIPS" not in os.environ   # parent env restored
    assert context.one_chip_env(2) == {
        "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1"}


# ===========================================================================
# SLOW tier (group j) — whole-process disaggregated clusters
# ===========================================================================

def _cluster(params, cfg, **kw):
    from mxnet_tpu.serving import DisaggServingCluster
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("metrics", True)
    kw.setdefault("watchdog_s", 60.0)
    return DisaggServingCluster(params, cfg, **kw)


def _leak_check(cl):
    """Zero leaked pages/refs on every surviving worker: allocated
    pages are exactly the prefix trie's cached pages (prefill) or
    nothing (decode), no dangling refs, no staged streams."""
    for name, st in cl.cluster_stats().items():
        assert st["pages_in_use"] - st["prefix_cached_pages"] == 0, \
            (name, st)
        assert st["prefix_refs"] == 0, (name, st)
        assert st["staged_rids"] == 0, (name, st)
        assert st["active_requests"] == 0, (name, st)


@pytest.mark.slow
def test_disagg_identity_mixed_lengths():
    """Two OS processes (1 prefill + 1 decode) exchanging KV pages:
    f32-greedy outputs bit-identical to single-engine ``generate``
    across mixed prompt/output lengths."""
    params, cfg = _tiny()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, int(P)).astype(np.int32)
               for P in (5, 9, 17, 3, 21, 12)]
    nnew = [6, 4, 8, 5, 11, 1]            # incl. a 1-token request
    cl = _cluster(params, cfg, prefill=1, decode=1)
    try:
        assert len({w.proc.pid for w in cl.workers.values()}) == 2
        rids = [cl.submit(p, n) for p, n in zip(prompts, nnew)]
        for rid, p, n in zip(rids, prompts, nnew):
            out = cl.result(rid, timeout=180)
            assert np.array_equal(out, _gen_ref(params, cfg, p, n))
        st = cl.cluster_stats()
        assert st["prefill0"]["pages_streamed"] > 0
        assert st["decode0"]["pages_installed"] > 0
        assert st["decode0"]["decode_rows"] > 0
        # the decode side never prefilled anything (no preemption in
        # this sizing): the split is real, not a fallback
        assert st["decode0"]["prefill_rows"] == 0
        _leak_check(cl)
    finally:
        cl.close()


@pytest.mark.slow
def test_disagg_remote_prefix_prefilled_once_per_cluster():
    """K requests sharing a prefix, spread across 2 prefill
    processes: the prefix is COLD-prefilled exactly once cluster-wide
    — the other replica fetches the pages (remote hit), every later
    request hits locally.  Reconciled via the
    serving_prefix_remote_hits_total counter AND per-worker prefill
    row counts; outputs stay exact."""
    params, cfg = _tiny()
    rng = np.random.RandomState(0)
    ps = 4
    shared = rng.randint(1, cfg.vocab_size, 2 * ps).astype(np.int32)
    tails = [rng.randint(1, cfg.vocab_size, 3).astype(np.int32)
             for _ in range(6)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    cl = _cluster(params, cfg, prefill=2, decode=1, page_size=ps)
    try:
        # sequential submits: round-robin alternates the two prefill
        # workers, so the shared prefix MUST cross the process
        # boundary by request 2
        for p in prompts:
            out = cl.result(cl.submit(p, 4), timeout=180)
            assert np.array_equal(out, _gen_ref(params, cfg, p, 4))
        st = cl.cluster_stats()
        hits = sum(v.get("remote_hits", 0) for v in st.values())
        hit_toks = sum(v.get("remote_hit_tokens", 0)
                       for v in st.values())
        assert hits == 1, st              # fetched exactly once
        assert hit_toks == shared.size
        # prefill-row reconciliation: the shared prefix's rows were
        # paid once cluster-wide.  Every request = prefix (8) + tail
        # (3) + 0 extra rows; each worker pays the prefix rows at
        # most... exactly once would be 8; the remote-hit worker pays
        # zero.  Total rows = sum(prompts) - (K-1)*prefix_len -
        # (whatever partial-page tail reuse matched, >= 0).
        total_rows = sum(v["prefill_rows"] for v in st.values()
                         if v["role"] == "prefill")
        cold_total = sum(p.size for p in prompts)
        saved = cold_total - total_rows
        assert saved >= (len(prompts) - 1) * shared.size, st
        # router counters agree with the worker-side totals
        snap = cl.registry.snapshot()["counters"]
        assert snap["serving_prefix_remote_hits_total"] == 1
        assert snap["serving_prefix_remote_hit_tokens_total"] == \
            shared.size
        assert snap["cluster_page_bytes_streamed_total"] > 0
        _leak_check(cl)
    finally:
        cl.close()


def _wait_mid_decode(cl, timeout=90):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        with cl._lock:
            if any(r.state == "running" and r.phase == "decode"
                   and 0 < len(r.committed) < r.max_new_tokens
                   for r in cl.requests.values()):
                return True
        time.sleep(0.005)
    return False


def _wait_mid_prefill(cl, timeout=90):
    """True once some request is still in the prefill phase with
    pages already streamed (the mid-stream kill window)."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        with cl._lock:
            streaming = any(r.state == "running"
                            and r.phase == "prefill"
                            for r in cl.requests.values())
        if streaming:
            return True
        time.sleep(0.002)
    return False


@pytest.mark.slow
def test_disagg_sigkill_prefill_mid_stream():
    """SIGKILL (not a raised exception) of a whole prefill process
    mid-stream: every in-flight request completes recompute-exact on
    the survivors, zero leaked pages/refs."""
    params, cfg = _tiny()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, cfg.vocab_size,
                           int(P)).astype(np.int32)
               for P in rng.choice([9, 14, 21, 30], 10)]
    nnew = [int(n) for n in rng.choice([6, 10, 16], 10)]
    cl = _cluster(params, cfg, prefill=2, decode=1, watchdog_s=30.0)
    try:
        rids = [cl.submit(p, n) for p, n in zip(prompts, nnew)]
        assert _wait_mid_prefill(cl), "no prefill in flight to kill"
        cl.kill_worker("prefill0")
        for rid, p, n in zip(rids, prompts, nnew):
            out = cl.result(rid, timeout=180)
            assert np.array_equal(out, _gen_ref(params, cfg, p, n))
        snap = cl.registry.snapshot()["counters"]
        assert snap["cluster_failovers_total"] >= 1
        assert not cl.workers["prefill0"].proc.is_alive()
        _leak_check(cl)
    finally:
        cl.close()


@pytest.mark.slow
def test_disagg_sigkill_decode_mid_decode():
    """SIGKILL of a whole decode process while requests are actively
    decoding: the router's streamed committed tokens resubmit as
    prompt extension (recompute-exact) and every output stays
    bit-identical to the oracle."""
    params, cfg = _tiny()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, cfg.vocab_size,
                           int(P)).astype(np.int32)
               for P in rng.choice([5, 9, 14, 21], 8)]
    nnew = [48] * 8                       # long decodes: a real window
    cl = _cluster(params, cfg, prefill=1, decode=2, watchdog_s=30.0)
    try:
        rids = [cl.submit(p, n) for p, n in zip(prompts, nnew)]
        assert _wait_mid_decode(cl), "no request caught mid-decode"
        cl.kill_worker("decode0")
        for rid, p, n in zip(rids, prompts, nnew):
            out = cl.result(rid, timeout=180)
            assert np.array_equal(out, _gen_ref(params, cfg, p, n))
        snap = cl.registry.snapshot()["counters"]
        assert snap["cluster_failovers_total"] >= 1
        assert snap["cluster_requests_resubmitted_total"] >= 1
        _leak_check(cl)
    finally:
        cl.close()


@pytest.mark.slow
def test_disagg_preemption_resume_exact():
    """A decode pool too small for the whole batch forces
    preemption + recompute-exact resume ON THE DECODE SIDE (its local
    re-prefill path) — outputs stay bit-identical."""
    params, cfg = _tiny()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, cfg.vocab_size, 17).astype(np.int32)
               for _ in range(6)]
    n_new = 24
    # 4 slots x ceil((17+24)/4)=11 pages would want 44; give 25 so
    # concurrent decodes exhaust the pool and preempt
    cl = _cluster(params, cfg, prefill=1, decode=1, num_slots=4,
                  pages_per_slot=11, num_pages=25)
    try:
        rids = [cl.submit(p, n_new) for p in prompts]
        for rid, p in zip(rids, prompts):
            out = cl.result(rid, timeout=240)
            assert np.array_equal(out,
                                  _gen_ref(params, cfg, p, n_new))
        st = cl.cluster_stats()
        assert st["decode0"]["preemptions"] > 0, \
            "pool sizing failed to force a preemption"
        # the decode side re-prefilled its preemption victims locally
        assert st["decode0"]["prefill_rows"] > 0
        _leak_check(cl)
    finally:
        cl.close()


@pytest.mark.slow
def test_disagg_put_vs_socket_transport_bit_identical():
    """Round 22 tentpole pin: the same workload forced over the
    /dev/shm put transport and over plain socket frames produces
    BIT-IDENTICAL outputs (both equal to ``generate``), the put run
    really put (pages_put == pages_streamed on the prefill side, 0 on
    the socket run), zero page/ref leaks on both ends, and zero put
    segments left on disk after either run."""
    import glob
    from mxnet_tpu.serving.transport import PUT_DIR

    params, cfg = _tiny()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, int(P)).astype(np.int32)
               for P in (5, 9, 17, 3)]
    nnew = [6, 4, 8, 5]
    outs = {}
    old = os.environ.get("MXNET_SERVE_TRANSPORT")
    try:
        for mode in ("put", "socket"):
            os.environ["MXNET_SERVE_TRANSPORT"] = mode
            cl = _cluster(params, cfg, prefill=1, decode=1)
            try:
                rids = [cl.submit(p, n)
                        for p, n in zip(prompts, nnew)]
                outs[mode] = [cl.result(r, timeout=180)
                              for r in rids]
                st = cl.cluster_stats()
                if mode == "put":
                    assert st["prefill0"]["pages_put"] == \
                        st["prefill0"]["pages_streamed"] > 0
                    assert st["prefill0"]["put_bytes"] > 0
                else:
                    assert st["prefill0"]["pages_put"] == 0
                assert st["decode0"]["pages_installed"] > 0
                _leak_check(cl)
            finally:
                cl.close()
            assert not glob.glob(
                os.path.join(PUT_DIR, "mxserve-put-*")), \
                "put segments left on disk after %s run" % mode
    finally:
        if old is None:
            os.environ.pop("MXNET_SERVE_TRANSPORT", None)
        else:
            os.environ["MXNET_SERVE_TRANSPORT"] = old
    for a, b, p, n in zip(outs["put"], outs["socket"], prompts, nnew):
        assert np.array_equal(a, b)       # transport-invariant bytes
        assert np.array_equal(a, _gen_ref(params, cfg, p, n))


@pytest.mark.slow
def test_disagg_int8_kv_pages_transfer_exactly():
    """int8-KV mode: quantized pages + f32 scale pages stream in the
    int8 page-pool wire layout, and the disaggregated output is
    BIT-identical to a single engine in the same int8 mode (the
    transfer is lossless; int8-vs-f32 is the engine's own caveat,
    not the wire's)."""
    from mxnet_tpu.serving import ServingEngine
    params, cfg = _tiny()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, int(P)).astype(np.int32)
               for P in (7, 13, 18)]
    n_new = 9
    ref_eng = ServingEngine(params, cfg, num_slots=4, page_size=4,
                            kv_int8=True)
    refs = {}
    for p in prompts:
        rid = ref_eng.submit(p, n_new)
        refs[rid] = p
    ref_out = ref_eng.run()
    ref_by_prompt = {refs[rid].tobytes(): out
                     for rid, out in ref_out.items()}
    cl = _cluster(params, cfg, prefill=1, decode=1, kv_int8=True)
    try:
        rids = [cl.submit(p, n_new) for p in prompts]
        for rid, p in zip(rids, prompts):
            out = cl.result(rid, timeout=180)
            assert np.array_equal(out, ref_by_prompt[p.tobytes()])
        st = cl.cluster_stats()
        # int8 pages are ~4x smaller than f32 (+ scale pages): wire
        # bytes must match the int8 pool layout exactly
        from mxnet_tpu.serving.paged_kv import PagedKVCache
        probe = PagedKVCache(cfg, 2, 4, kv_int8=True)
        assert st["prefill0"]["bytes_streamed"] == \
            st["prefill0"]["pages_streamed"] * probe.bytes_per_page
        _leak_check(cl)
    finally:
        cl.close()
