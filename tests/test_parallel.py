"""Parallelism tests on the virtual 8-device CPU mesh (SURVEY.md §4.5:
distributed tests without a real cluster)."""
import numpy as np
import os
import pytest

import mxnet_tpu as mx


def test_make_mesh():
    import jax
    from mxnet_tpu.parallel import make_mesh
    n = len(jax.devices())
    assert n == 8, "conftest should provide 8 virtual devices"
    mesh = make_mesh({"dp": -1})
    assert mesh.shape["dp"] == 8
    mesh = make_mesh({"dp": 4, "tp": 2})
    assert mesh.shape == {"dp": 4, "tp": 2}
    with pytest.raises(mx.MXNetError):
        make_mesh({"dp": 3})


@pytest.mark.slow
def test_data_parallel_trainer_matches_single_device():
    """Sharded dp training must match the math of plain training."""
    import jax
    from mxnet_tpu import nd, gluon, autograd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    np.random.seed(0)
    X = np.random.randn(16, 6).astype("float32")
    Y = (X @ np.random.randn(6, 1).astype("float32"))

    def build():
        net = nn.Dense(1, use_bias=False)
        net.initialize(mx.initializer.Zero())
        return net

    # plain eager reference
    net_ref = build()
    tr = gluon.Trainer(net_ref.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    loss_fn = gluon.loss.L2Loss()
    for _ in range(5):
        with autograd.record():
            L = loss_fn(net_ref(nd.array(X)), nd.array(Y))
        L.backward()
        tr.step(16)
    w_ref = net_ref.weight.data().asnumpy()

    # sharded dp over 8 devices
    net_dp = build()
    mesh = make_mesh({"dp": 8})
    dpt = DataParallelTrainer(net_dp, loss_fn, "sgd",
                              {"learning_rate": 0.05}, mesh=mesh)
    for _ in range(5):
        loss = dpt.step(nd.array(X), nd.array(Y))
    dpt.sync_back()
    w_dp = net_dp.weight.data().asnumpy()
    assert np.allclose(w_ref, w_dp, rtol=1e-4, atol=1e-5), \
        (w_ref, w_dp)


@pytest.mark.slow
def test_transformer_train_step_dp_tp():
    """Full transformer step over dp x tp mesh compiles and decreases
    loss."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.models import transformer as T

    mesh = make_mesh({"dp": 4, "tp": 2})
    cfg = T.bert_tiny(use_flash=False, remat=False, dropout=0.0)
    init_state, step = T.make_train_step(cfg, mesh=mesh,
                                         learning_rate=1e-3)
    state = init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 128), 0,
                                cfg.vocab_size)
    labels = jnp.where(jnp.arange(128)[None] % 5 == 0, tokens, -100)
    batch = {"tokens": tokens, "labels": labels,
             "mask": jnp.ones((8, 128), dtype=bool)}
    losses = []
    for i in range(8):
        state, loss = step(state, batch, jax.random.fold_in(rng, i))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_param_shardings_layout():
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.models import transformer as T
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh({"dp": 4, "tp": 2})
    cfg = T.bert_tiny()
    sh = T.param_shardings(cfg, mesh)
    assert sh["layers"][0]["w1"].spec == P(None, "tp")
    assert sh["layers"][0]["w2"].spec == P("tp", None)
    assert sh["emb_ln"]["g"].spec == P()


def test_kvstore_multi_device_contexts():
    """Reference-style per-device replicas reduce correctly (the legacy
    Trainer path) on virtual devices."""
    from mxnet_tpu import nd
    kv = mx.kvstore.create("device")
    vals = [nd.ones((4,), ctx=mx.tpu(i)) * (i + 1) for i in range(4)]
    kv.init("w", nd.zeros((4,)))
    kv.push("w", vals)
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    assert np.allclose(out.asnumpy(), 1 + 2 + 3 + 4)


@pytest.mark.slow
def test_data_parallel_amp_learns():
    """amp=True (bf16 compute, f32 master) still converges."""
    import numpy as np
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    np.random.seed(0)
    X = np.random.randn(32, 10).astype("float32")
    W = np.random.randn(10, 3).astype("float32")
    Y = (X @ W).argmax(1)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.Dense(3))
    net.initialize(mx.initializer.Xavier())
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "sgd", {"learning_rate": 0.5},
                             mesh=make_mesh({"dp": 8}), amp=True)
    losses = [float(tr.step(nd.array(X), nd.array(Y)).asnumpy())
              for _ in range(12)]
    assert losses[-1] < losses[0] * 0.5, losses


@pytest.mark.slow
def test_data_parallel_bn_stats_update():
    """BatchNorm running stats must survive the jitted train step (the
    mutate=(3,4) contract carries through to the trainer state)."""
    import numpy as np
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    np.random.seed(0)
    X = np.random.randn(16, 4, 5, 5).astype("float32") * 2 + 1
    Y = np.random.randint(0, 2, (16,))
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(4, 3, padding=1), nn.BatchNorm(),
                nn.Activation("relu"), nn.GlobalAvgPool2D(),
                nn.Dense(2))
    net.initialize(mx.initializer.Xavier())
    net(nd.array(X))  # materialize deferred shapes
    bn = [b for b in net._children.values()
          if isinstance(b, nn.BatchNorm)][0]
    before = bn.running_mean.data().asnumpy().copy()
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "sgd", {"learning_rate": 0.1},
                             mesh=make_mesh({"dp": 8}))
    for _ in range(4):
        tr.step(nd.array(X), nd.array(Y))
    tr.sync_back()
    after = bn.running_mean.data().asnumpy()
    assert np.abs(after - before).max() > 1e-4


def test_multihost_single_process():
    """Single-process initialize is a no-op that still exposes the
    rank/num_hosts/global_mesh surface (reference: kvstore rank/size)."""
    from mxnet_tpu.parallel import multihost
    multihost.initialize()
    assert multihost.is_initialized()
    assert multihost.rank() == 0
    assert multihost.num_hosts() == 1
    assert len(multihost.local_devices()) == 8
    mesh = multihost.global_mesh({"dp": -1})
    assert mesh.shape["dp"] == 8
    multihost.shutdown()
    assert not multihost.is_initialized()


@pytest.mark.slow
def test_data_parallel_zero1_matches():
    """DataParallelTrainer(shard_optimizer=True) trains identically."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    np.random.seed(0)
    X = np.random.randn(16, 8).astype("float32")
    Y = np.random.randint(0, 3, (16,))

    def run(shard):
        mx.random.seed(7)
        np.random.seed(7)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
        net.initialize(mx.initializer.Xavier())
        tr = DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 0.05}, mesh=make_mesh({"dp": 8}),
            shard_optimizer=shard)
        losses = [float(tr.step(nd.array(X), nd.array(Y)).asnumpy())
                  for _ in range(5)]
        if shard:
            specs = [str(l.sharding.spec) for l in
                     jax.tree_util.tree_leaves(tr._state[1])
                     if isinstance(l.sharding, NamedSharding)]
            assert any("dp" in s for s in specs), specs
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


def test_run_steps_matches_python_loop():
    """The device-side multi-step loop (one jitted lax.scan dispatch)
    must produce the same trajectory as K individual step() calls, in
    both data modes (batch reuse and (K, batch, ...) superbatch)."""
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    np.random.seed(1)
    K, B = 4, 16
    Xs = np.random.randn(K, B, 6).astype("float32")
    Ys = np.einsum("kbi,io->kbo", Xs,
                   np.random.randn(6, 1).astype("float32"))

    def build():
        net = nn.Dense(1, use_bias=False)
        net.initialize(mx.initializer.Zero())
        return net

    def make(net):
        return DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                                   {"learning_rate": 0.05},
                                   mesh=make_mesh({"dp": 8}))

    # reference: python loop over the superbatch
    net_ref = build()
    tr_ref = make(net_ref)
    ref_losses = [float(tr_ref.step(nd.array(Xs[k]),
                                    nd.array(Ys[k])).asnumpy())
                  for k in range(K)]
    tr_ref.sync_back()
    w_ref = net_ref.weight.data().asnumpy()

    # superbatch mode: one dispatch
    net_sb = build()
    tr_sb = make(net_sb)
    losses = tr_sb.run_steps(nd.array(Xs), nd.array(Ys)).asnumpy()
    tr_sb.sync_back()
    assert losses.shape == (K,)
    assert np.allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    assert np.allclose(net_sb.weight.data().asnumpy(), w_ref,
                       rtol=1e-5, atol=1e-6)

    # reuse mode: same batch every step == python loop on that batch
    net_r1, net_r2 = build(), build()
    tr1, tr2 = make(net_r1), make(net_r2)
    for _ in range(3):
        tr1.step(nd.array(Xs[0]), nd.array(Ys[0]))
    losses2 = tr2.run_steps(nd.array(Xs[0]), nd.array(Ys[0]),
                            steps=3).asnumpy()
    tr1.sync_back(); tr2.sync_back()
    assert losses2.shape == (3,)
    assert np.allclose(net_r1.weight.data().asnumpy(),
                       net_r2.weight.data().asnumpy(),
                       rtol=1e-5, atol=1e-6)
    tr2.sync()  # exercises the hard sync path


@pytest.mark.slow
def test_multichip_dryrun_no_involuntary_remat():
    """The full multi-chip dryrun (dp/sp/tp, pp/dp, dp/ep/tp meshes with
    ZeRO-1) must compile without SPMD 'Involuntary full
    rematerialization' — those replicate-then-reshard transitions are
    what kills scaling on real hardware (round-1 verdict item #2).
    Subprocess because the warning is emitted by XLA C++ on stderr."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "__graft_entry__.py"),
         "multichip", "8"],
        capture_output=True, text=True, timeout=500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    # 3 transformer mesh configs + the conv+BN dp config (round 4)
    assert r.stdout.count("loss") == 4, r.stdout
    assert "Involuntary full rematerialization" not in r.stderr, \
        r.stderr[-3000:]


@pytest.mark.slow
def test_data_parallel_bn_is_global_stats():
    """Pin BatchNorm semantics under GSPMD dp (round-4 verdict item #2).

    GSPMD is semantics-preserving: ``jnp.mean`` over the batch axis of a
    dp-sharded array is the GLOBAL batch mean (XLA inserts the
    cross-replica reduce), so a dp-sharded ``nn.BatchNorm`` computes
    SyncBatchNorm statistics — unlike reference MXNet's data-parallel
    BN, which normalizes each device's shard with per-device stats
    (upstream SyncBatchNorm was the separate opt-in:
    ``src/operator/contrib/sync_batch_norm-inl.h``).  This test builds a
    batch whose two dp shards have wildly different means, so the two
    semantics produce far-apart losses, and asserts the dp loss equals
    the global-stats loss.  docs/architecture.md "BatchNorm under
    GSPMD" documents the contract.
    """
    import numpy as np
    from mxnet_tpu import nd, gluon, autograd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    np.random.seed(0)
    N, D = 4, 8                             # per-shard batch, dp degree
    shards = [np.random.randn(N, 4, 6, 6).astype("float32")
              + 10.0 * (i - D / 2) for i in range(D)]
    X = np.concatenate(shards)              # shard means far apart
    Y = np.tile(np.arange(2), N * D // 2).astype("int64")

    def build():
        np.random.seed(42)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Conv2D(4, 3, padding=1), nn.BatchNorm(),
                    nn.Activation("relu"), nn.GlobalAvgPool2D(),
                    nn.Dense(2))
        net.initialize(mx.initializer.Xavier(rnd_type="uniform",
                                             magnitude=2.0))
        net(nd.array(X[:2]))
        return net

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # dp=8: first step's loss, before any update
    tr = DataParallelTrainer(build(), loss_fn, "sgd",
                             {"learning_rate": 0.0},
                             mesh=make_mesh({"dp": D}))
    loss_dp = float(tr.step(nd.array(X), nd.array(Y)).asnumpy())

    # global-stats single-device run (train mode => batch stats)
    net = build()
    with autograd.record():
        l_global = loss_fn(net(nd.array(X)), nd.array(Y))
    loss_global = float(l_global.mean().asnumpy())

    # per-device-stats run: each shard normalized with its own stats
    net = build()
    with autograd.record():
        ls = [loss_fn(net(nd.array(s)),
                      nd.array(Y[i * N:(i + 1) * N])).mean()
              for i, s in enumerate(shards)]
    loss_perdev = float(sum(l.asnumpy() for l in ls)) / D

    # the two semantics must actually be distinguishable on this data
    assert abs(loss_global - loss_perdev) > 1e-2, \
        (loss_global, loss_perdev)
    # and the dp run must match the GLOBAL (SyncBatchNorm) semantics
    assert abs(loss_dp - loss_global) < 1e-3, \
        ("dp loss %.5f, global %.5f, perdev %.5f"
         % (loss_dp, loss_global, loss_perdev))
