"""Training scale-out tests (round 19, ROADMAP item 5): FSDP through
the mesh, the ICI-allreduce KVStore as the gradient-sync substrate, and
the exactness protocols the train-scale bench gates on.

Fast tier: mesh-free spec declarations, rule-table coverage, error
surfaces, optimizer sharded-state init, the DataParallelTrainer
zero-host-transfer regression.  Slow tier (group m): multi-device FSDP
byte accounting against live ``addressable_shards``, FSDP-vs-unsharded
trajectory equivalence, FSDP×tp composition, and the dp=2 BERT-grad
bit-identity protocol through the ICI store.
"""
import numpy as np
import pytest

import mxnet_tpu as mx


def _tiny_cfg(**kw):
    from mxnet_tpu.models import transformer as T
    base = dict(use_flash=False, remat=False, dropout=0.0)
    base.update(kw)
    return T.bert_tiny(**base)


def _mlm_batch(cfg, B=16, T_len=32, seed=2):
    import jax
    import jax.numpy as jnp
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, T_len), 0,
                                cfg.vocab_size)
    labels = jnp.where(jnp.arange(T_len)[None] % 5 == 0, tokens, -100)
    return {"tokens": tokens, "labels": labels,
            "mask": jnp.ones((B, T_len), bool)}


# ---------------------------------------------------------------------------
# fast tier
# ---------------------------------------------------------------------------

def test_fsdp_rules_cover_every_param():
    """The SNIPPETS [3] contract: every param leaf matches a rule, an
    invented leaf raises (silent replication is how FSDP quietly stops
    being FSDP), and MoE configs are refused loudly."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel.fsdp import (fsdp_rules,
                                         match_partition_rules,
                                         fsdp_param_specs)
    cfg = _tiny_cfg()
    shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    triples = match_partition_rules(fsdp_rules(), shapes)
    assert len(triples) == len(jax.tree_util.tree_leaves(shapes))
    with pytest.raises(mx.MXNetError, match="no partition rule"):
        match_partition_rules(fsdp_rules(), {"brand_new_table": shapes[
            "tok_emb"]})
    with pytest.raises(mx.MXNetError, match="MoE"):
        fsdp_param_specs(_tiny_cfg(n_experts=2, moe_every=1))


def test_fsdp_specs_compose_with_megatron_table():
    """dp lands on the dim the tp rule leaves free; with tp live the
    two stack (tp partitions first, dp subdivides)."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.fsdp import fsdp_param_specs
    cfg = _tiny_cfg()
    sp = fsdp_param_specs(cfg)
    assert sp["layers"][0]["wq"] == P("dp", None)
    assert sp["layers"][0]["wo"] == P(None, "dp")
    assert sp["type_emb"] == P(None, "dp")
    assert sp["layers"][0]["ln1"]["g"] == P("dp")
    sp_tp = fsdp_param_specs(cfg, tp="tp")
    assert sp_tp["layers"][0]["wq"] == P("dp", "tp")
    assert sp_tp["layers"][0]["wo"] == P("tp", "dp")
    assert sp_tp["layers"][0]["bq"] == P(("tp", "dp"))
    assert sp_tp["type_emb"] == P(None, ("tp", "dp"))


def test_train_step_specs_declared_and_audited():
    """The declared train-step in/out specs exist mesh-free (the
    serving ``step_input_specs`` convention) and graphlint's
    independent derivation agrees — the tier-1 wiring of the
    ROADMAP-5 closing criterion."""
    import sys, os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.models import transformer as T
    from tools.analysis import graphlint
    cfg = _tiny_cfg()
    pspecs, batch, rng = T.train_step_input_specs(cfg, tp="tp")
    assert batch["tokens"] == P("dp", None)
    assert rng == P()
    out_p, out_loss = T.train_step_output_specs(cfg, tp="tp")
    assert out_p == pspecs and out_loss == P()
    assert graphlint.train_sharding_readiness_findings(".") == []
    _, counts = graphlint._train_sharding_rows(cfg)
    assert counts["uncovered"] == 0 and counts["mismatched"] == 0
    assert counts["covered"] > 20


def test_train_audit_catches_drifted_declaration(monkeypatch):
    """A drifted declaration (params suddenly replicated) fires the
    train half of graph-sharding-readiness — the rule genuinely
    guards the declaration, PR-4/7/8 convention."""
    import sys, os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.models import transformer as T
    from tools.analysis import graphlint
    real = T.train_step_input_specs

    def drifted(cfg, dp="dp", tp=None, fsdp=True):
        pspecs, batch, rng = real(cfg, dp=dp, tp=tp, fsdp=fsdp)
        pspecs = jax.tree_util.tree_map(
            lambda s: P(), pspecs, is_leaf=lambda x: isinstance(x, P))
        return pspecs, batch, rng

    monkeypatch.setattr(T, "train_step_input_specs", drifted)
    fs = graphlint.train_sharding_readiness_findings(".")
    assert any(f.symbol == "train_step_input_specs.mismatch"
               for f in fs), [str(f) for f in fs]
    assert all(f.path == "mxnet_tpu/models/transformer.py"
               for f in fs)


def test_fsdp_requires_live_dp_axis():
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.fsdp import fsdp_param_shardings
    cfg = _tiny_cfg()
    with pytest.raises(mx.MXNetError, match="live 'dp' axis"):
        T.make_train_step(cfg, mesh=None, fsdp=True)
    with pytest.raises(mx.MXNetError, match="live"):
        fsdp_param_shardings(cfg, make_mesh({"tp": 8}))


def test_fsdp_vocab_that_does_not_divide_dp():
    """BERT-base's vocabulary (30522 = 2*3*5087) divides no dp of 4:
    bound to a mesh, the rule table moves dp to a dim that does divide
    or leaves the leaf replicated, and the step runs (first four-chip
    run, PR 21: device_put refused PartitionSpec('dp',) on (30522,))."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.fsdp import fsdp_param_shardings
    cfg = _tiny_cfg(vocab_size=1022)
    for axes, tok in (({"dp": 4}, P(None, "dp")),
                      ({"dp": 2, "tp": 2}, P("dp", "tp"))):
        mesh = make_mesh(axes, devices=jax.devices()[:4])
        sh = fsdp_param_shardings(cfg, mesh)
        assert sh["tok_emb"].spec == tok, (axes, sh["tok_emb"].spec)
    # dp=4: 1022 % 4 != 0 -> the bias has no other dim: replicated
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    sh = fsdp_param_shardings(cfg, mesh)
    assert sh["mlm_bias"].spec == P()
    assert sh["layers"][0]["wq"].spec == P("dp", None)   # rule's own dim
    init_state, step = T.make_train_step(cfg, mesh=mesh, fsdp=True)
    state = init_state(jax.random.PRNGKey(0))
    state, loss = step(state, _mlm_batch(cfg, B=8), jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))


def test_bucket_overlap_validation():
    """Round 21: bucket_overlap is fenced to the configs where the
    homogeneous layer scan is sound — requires fsdp, refuses bogus
    values, MoE stacks, and seq-parallel configs."""
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh
    cfg = _tiny_cfg()
    mesh = make_mesh({"dp": 8})
    with pytest.raises(mx.MXNetError, match="must be False, True"):
        T.make_train_step(cfg, mesh=mesh, fsdp=True,
                          bucket_overlap="yes")
    with pytest.raises(mx.MXNetError, match="requires fsdp=True"):
        T.make_train_step(cfg, mesh=mesh, bucket_overlap=True)
    with pytest.raises(mx.MXNetError, match="homogeneous"):
        T.make_train_step(_tiny_cfg(n_experts=2), mesh=mesh,
                          fsdp=True, bucket_overlap=True)
    with pytest.raises(mx.MXNetError, match="homogeneous"):
        T.make_train_step(_tiny_cfg(seq_parallel=True), mesh=mesh,
                          fsdp=True, bucket_overlap=True)


def test_optimizer_state_zeros_matches_weight_sharding():
    """optimizer.state_zeros: a mesh-sharded weight gets its moments
    allocated directly INTO the same sharding (no init-then-reshard
    peak, no per-update reshard); single-device weights keep the
    reference ctx behavior."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.optimizer.optimizer import state_zeros
    mesh = make_mesh({"dp": 8})
    w = jax.device_put(jnp.ones((64, 16)),
                       NamedSharding(mesh, P("dp", None)))
    s = state_zeros(NDArray(w))
    assert s._data.sharding == w.sharding
    assert s._data.addressable_shards[0].data.shape == (8, 16)
    # and the Adam updater path creates sharded moments from it
    opt = mx.optimizer.Adam(learning_rate=0.1)
    mu, nu = opt.create_state(0, NDArray(w))
    assert mu._data.sharding == w.sharding
    s2 = state_zeros(mx.nd.ones((4,), ctx=mx.tpu(1)))
    assert s2.context == mx.tpu(1)


def test_dpt_steady_state_step_is_host_transfer_free():
    """Round-19 DataParallelTrainer audit regression pin: with a live
    mesh and device-resident batches, the steady-state step performs
    ZERO host transfers (no param round-trip through host numpy, no
    hidden device_get) — enforced with jax's transfer guard."""
    import jax
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    from mxnet_tpu.parallel import multihost

    calls = []
    real = multihost.host_staged_put

    def spy(value, sharding):
        calls.append(type(value).__name__)
        return real(value, sharding)

    multihost.host_staged_put = spy
    try:
        np.random.seed(0)
        X = np.random.randn(16, 6).astype("float32")
        Y = X @ np.random.randn(6, 1).astype("float32")
        net = nn.Dense(1, use_bias=False)
        net.initialize(mx.initializer.Zero())
        tr = DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.05},
                                 mesh=make_mesh({"dp": 8}))
        tr.step(nd.array(X), nd.array(Y))      # build + first step
    finally:
        multihost.host_staged_put = real
    # single-process staging must not have gone through host numpy
    assert "ndarray" not in calls, calls
    dd = jax.device_put(X, tr._batch_sharding)
    ll = jax.device_put(Y, tr._batch_sharding)
    with jax.transfer_guard("disallow"):
        tr.step(dd, ll)
        loss = tr.step(dd, ll)
    assert np.isfinite(float(loss.asnumpy()))


# ---------------------------------------------------------------------------
# slow tier (group m)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fsdp_per_device_bytes_exactly_div_dp():
    """The PR-9 protocol for the train half: per-device param bytes
    and every param-shaped optimizer moment are EXACTLY total/dp,
    asserted against live ``addressable_shards`` (the only replicated
    opt leaf is adamw's scalar step count)."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.fsdp import shard_bytes
    dp = 8
    cfg = _tiny_cfg()
    init_state, _ = T.make_train_step(cfg, mesh=make_mesh({"dp": dp}),
                                      fsdp=True)
    params, opt = init_state(jax.random.PRNGKey(0))
    tot, per = shard_bytes(params)
    assert tot == per * dp, (tot, per)
    for leaf in jax.tree_util.tree_leaves(params):
        n_sh = len({str(sh.index) for sh in leaf.addressable_shards})
        assert n_sh == dp, (leaf.shape, n_sh)
    tot_o, per_o = shard_bytes(opt)
    # everything but the 4-byte scalar count divides exactly
    count_bytes = 4
    assert tot_o - count_bytes == (per_o - count_bytes) * dp, \
        (tot_o, per_o)


@pytest.mark.slow
def test_fsdp_trains_like_unsharded():
    """FSDP changes the placement, not the math: the loss trajectory
    matches the plain replicated-dp step to float tolerance and
    decreases."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh
    cfg = _tiny_cfg()
    batch = _mlm_batch(cfg)
    mesh = make_mesh({"dp": 8})

    def run(fsdp):
        init_state, step = T.make_train_step(cfg, mesh=mesh, fsdp=fsdp,
                                             learning_rate=1e-3)
        state = init_state(jax.random.PRNGKey(0))
        out = []
        for i in range(6):
            state, loss = step(state, batch,
                               jax.random.fold_in(jax.random.PRNGKey(1),
                                                  i))
            out.append(float(loss))
        return out

    fsdp_losses = run(True)
    ref_losses = run(False)
    np.testing.assert_allclose(fsdp_losses, ref_losses, rtol=2e-3,
                               atol=2e-3)
    assert fsdp_losses[-1] < fsdp_losses[0]


@pytest.mark.slow
def test_bucket_overlap_bitwise_vs_fused_and_tracks_legacy():
    """Round 21 HARD GATE: the layer-bucketed reduce-scatter step
    (``bucket_overlap=True``) must be BITWISE identical — losses and
    every updated weight — to its ``"fused"`` comparator (the same
    scan graph with the grad constraint deferred to one post-backward
    sync).  Identical graphs up to collective PLACEMENT is the whole
    claim: overlap moves the reduce-scatters, it may not change a
    single bit.  Against the round-20 autodiff path the scan backward
    is a different (valid) graph, so that comparison is tolerance-
    based, and training must still descend."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh
    cfg = _tiny_cfg()
    batch = _mlm_batch(cfg)
    mesh = make_mesh({"dp": 8})

    def run(bucket_overlap):
        init_state, step = T.make_train_step(cfg, mesh=mesh,
                                             fsdp=True,
                                             learning_rate=1e-3,
                                             bucket_overlap=
                                             bucket_overlap)
        state = init_state(jax.random.PRNGKey(0))
        losses = []
        for i in range(4):
            state, loss = step(state, batch,
                               jax.random.fold_in(
                                   jax.random.PRNGKey(1), i))
            losses.append(float(loss))
        return losses, jax.device_get(state[0])

    bk_losses, bk_params = run(True)
    fu_losses, fu_params = run("fused")
    assert bk_losses == fu_losses, (bk_losses, fu_losses)
    flat_b, _ = jax.tree_util.tree_flatten_with_path(bk_params)
    flat_f = jax.tree_util.tree_leaves(fu_params)
    for (path, leaf_b), leaf_f in zip(flat_b, flat_f):
        assert np.array_equal(np.asarray(leaf_b),
                              np.asarray(leaf_f)), \
            "bucketed != fused at %s" % jax.tree_util.keystr(path)

    legacy_losses, _ = run(False)
    np.testing.assert_allclose(bk_losses, legacy_losses, rtol=2e-3,
                               atol=2e-3)
    assert bk_losses[-1] < bk_losses[0], bk_losses


@pytest.mark.slow
def test_fsdp_composes_with_tensor_parallelism():
    """dp×tp mesh: the same step lowers with stacked (tp, dp) /
    split-dim shardings, trains, and divides the dominant bytes by the
    full mesh size — every 2-D weight splits into tp×dp distinct
    shards; the 1-D vectors the megatron table replicates w.r.t. tp
    shard ÷dp, so the tree total sits strictly below the dp-only
    bound."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.fsdp import shard_bytes
    cfg = _tiny_cfg()
    mesh = make_mesh({"dp": 4, "tp": 2})
    init_state, step = T.make_train_step(cfg, mesh=mesh, fsdp=True,
                                         learning_rate=1e-3)
    state = init_state(jax.random.PRNGKey(0))
    for leaf in jax.tree_util.tree_leaves(state[0]):
        if leaf.ndim >= 2:
            n_sh = len({str(sh.index)
                        for sh in leaf.addressable_shards})
            assert n_sh == 8, (leaf.shape, n_sh)
    tot, per = shard_bytes(state[0])
    assert per < tot / 4, (tot, per)
    batch = _mlm_batch(cfg, B=8)
    losses = []
    for i in range(5):
        state, loss = step(state, batch,
                           jax.random.fold_in(jax.random.PRNGKey(1), i))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_ici_dp2_bert_grad_sync_bit_identical_vs_accumulation():
    """The model-level exactness protocol the bench gates on: per-
    device BERT microbatch grads (the SAME jitted ``mlm_loss`` grad
    program on each device) synced through the ICI store must produce
    a loss trajectory BIT-identical to single-device accumulation of
    the same two microbatches — the dp=2 collective is one order-free
    f32 add per element."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.ndarray.ndarray import NDArray
    cfg = _tiny_cfg(dtype="float32")
    batch = _mlm_batch(cfg, B=8)
    devs = jax.devices()[:2]
    key = jax.random.PRNGKey(3)

    gfn = jax.jit(jax.value_and_grad(
        lambda p, b, r: T.mlm_loss(p, b, r, cfg)))
    upd = jax.jit(lambda p, g, lr: jax.tree_util.tree_map(
        lambda pv, gv: pv - lr * gv, p, g))

    def halves(dev):
        return [jax.tree_util.tree_map(
            lambda x: jax.device_put(x[sl], dev), batch)
            for sl, dev in zip((slice(0, 4), slice(4, 8)), dev)]

    def run_kv():
        kv = mx.kv.create("ici")
        params = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, devs[0]),
            T.init_params(jax.random.PRNGKey(0), cfg))
        flat, treedef = jax.tree_util.tree_flatten(params)
        for i, leaf in enumerate(flat):
            kv.init(i, NDArray(leaf) * 0)
        b0, b1 = halves(devs)
        losses = []
        for step_i in range(3):
            p1 = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, devs[1]), params)
            l0, g0 = gfn(params, b0, key)
            l1, g1 = gfn(p1, b1, key)
            f0 = jax.tree_util.tree_leaves(g0)
            f1 = jax.tree_util.tree_leaves(g1)
            keys = list(range(len(f0)))
            kv.push(keys, [[NDArray(a), NDArray(b)]
                           for a, b in zip(f0, f1)])
            outs = []
            for i in keys:
                o = NDArray(jnp.zeros(f0[i].shape, f0[i].dtype))
                kv.pull(i, out=o)
                outs.append(jax.device_put(o._data, devs[0]))
            gsum = jax.tree_util.tree_unflatten(treedef, outs)
            params = upd(params, gsum, 1e-2)
            losses.append((np.asarray(l0), np.asarray(l1)))
        assert kv.stats()["collectives"] >= 3
        return losses, params

    def run_accum():
        params = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, devs[0]),
            T.init_params(jax.random.PRNGKey(0), cfg))
        b0, b1 = halves((devs[0], devs[0]))
        losses = []
        for step_i in range(3):
            l0, g0 = gfn(params, b0, key)
            l1, g1 = gfn(params, b1, key)
            gsum = jax.tree_util.tree_map(lambda a, b: a + b, g0, g1)
            params = upd(params, gsum, 1e-2)
            losses.append((np.asarray(l0), np.asarray(l1)))
        return losses, params

    kv_losses, kv_params = run_kv()
    acc_losses, acc_params = run_accum()
    for (a0, a1), (b0_, b1_) in zip(kv_losses, acc_losses):
        assert a0.tobytes() == b0_.tobytes()
        assert a1.tobytes() == b1_.tobytes()
    for a, b in zip(jax.tree_util.tree_leaves(kv_params),
                    jax.tree_util.tree_leaves(acc_params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.slow
def test_ici_bucketed_training_sync_bit_identical():
    """Bucketed vs unbucketed sync of a full bert_tiny gradient set is
    bitwise identical while fusing the per-key collectives into a
    handful of flat ones."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.ndarray.ndarray import NDArray
    cfg = _tiny_cfg(dtype="float32")
    batch = _mlm_batch(cfg, B=8)
    devs = jax.devices()[:2]
    key = jax.random.PRNGKey(3)
    gfn = jax.jit(jax.value_and_grad(
        lambda p, b, r: T.mlm_loss(p, b, r, cfg)))
    params = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, devs[0]),
        T.init_params(jax.random.PRNGKey(0), cfg))
    p1 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, devs[1]), params)
    b0 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x[:4], devs[0]), batch)
    b1 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x[4:], devs[1]), batch)
    _, g0 = gfn(params, b0, key)
    _, g1 = gfn(p1, b1, key)
    f0 = jax.tree_util.tree_leaves(g0)
    f1 = jax.tree_util.tree_leaves(g1)

    def sync(bucket_bytes):
        kv = mx.kv.create("ici")
        kv.bucket_bytes = bucket_bytes
        keys = list(range(len(f0)))
        for i in keys:
            kv.init(i, NDArray(f0[i]) * 0)
        kv.push(keys, [[NDArray(a), NDArray(b)]
                       for a, b in zip(f0, f1)])
        outs = []
        import jax.numpy as jnp
        for i in keys:
            o = NDArray(jnp.zeros(f0[i].shape, f0[i].dtype))
            kv.pull(i, out=o)
            outs.append(np.asarray(o._data))
        return outs, kv.stats()

    fused, s_fused = sync(4 << 20)
    perkey, s_perkey = sync(0)
    assert s_fused["collectives"] < s_perkey["collectives"], \
        (s_fused, s_perkey)
    assert s_perkey["collectives"] == len(f0)
    for a, b in zip(fused, perkey):
        assert a.tobytes() == b.tobytes()
