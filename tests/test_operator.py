"""Operator tests with numpy-reference oracles + numeric gradient checks
(reference model: ``tests/python/unittest/test_operator.py`` with
``check_numeric_gradient`` / ``check_symbolic_forward`` from
``python/mxnet/test_utils.py`` — SURVEY.md §4.1)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd


def check_numeric_gradient(fn, inputs, eps=1e-3, rtol=1e-2, atol=1e-3):
    """Finite-difference vs autograd (reference: test_utils)."""
    nds = [nd.array(x) for x in inputs]
    for x in nds:
        x.attach_grad()
    with autograd.record():
        y = fn(*nds)
    y.backward()
    for i, x in enumerate(nds):
        analytic = x.grad.asnumpy()
        numeric = np.zeros_like(inputs[i])
        flat = inputs[i].reshape(-1)
        nflat = numeric.reshape(-1)
        for j in range(flat.size):
            orig = flat[j]
            flat[j] = orig + eps
            yp = fn(*[nd.array(v) for v in inputs]).asnumpy().sum()
            flat[j] = orig - eps
            ym = fn(*[nd.array(v) for v in inputs]).asnumpy().sum()
            flat[j] = orig
            nflat[j] = (yp - ym) / (2 * eps)
        assert np.allclose(analytic, numeric, rtol=rtol, atol=atol), \
            "grad mismatch for input %d: %s vs %s" % (i, analytic, numeric)


def test_unary_forward():
    x = np.random.uniform(0.5, 2.0, (3, 4)).astype("float32")
    a = nd.array(x)
    cases = [
        (nd.exp, np.exp), (nd.log, np.log), (nd.sqrt, np.sqrt),
        (nd.square, np.square), (nd.sin, np.sin), (nd.cos, np.cos),
        (nd.tanh, np.tanh), (nd.floor, np.floor), (nd.ceil, np.ceil),
        (nd.abs, np.abs), (nd.sign, np.sign),
    ]
    for mxf, npf in cases:
        assert np.allclose(mxf(a).asnumpy(), npf(x), rtol=1e-5, atol=1e-6)
    assert np.allclose(nd.relu(nd.array([-1.0, 2.0])).asnumpy(), [0, 2])
    assert np.allclose(nd.sigmoid(nd.array([0.0])).asnumpy(), [0.5])


def test_broadcast_ops():
    a = np.random.randn(3, 1, 4).astype("float32")
    b = np.random.randn(1, 5, 4).astype("float32")
    na, nb = nd.array(a), nd.array(b)
    assert np.allclose(nd.broadcast_add(na, nb).asnumpy(), a + b,
                       rtol=1e-5)
    assert np.allclose(nd.broadcast_mul(na, nb).asnumpy(), a * b,
                       rtol=1e-5)
    assert np.allclose(nd.broadcast_maximum(na, nb).asnumpy(),
                       np.maximum(a, b))
    assert np.allclose(nd.broadcast_to(nd.ones((1, 3)),
                                       shape=(2, 3)).asnumpy(),
                       np.ones((2, 3)))


def test_reductions():
    x = np.random.randn(2, 3, 4).astype("float32")
    a = nd.array(x)
    assert np.allclose(nd.sum(a).asnumpy(), x.sum(), rtol=1e-5)
    assert np.allclose(nd.sum(a, axis=1).asnumpy(), x.sum(1), rtol=1e-5)
    assert np.allclose(nd.sum(a, axis=1, keepdims=True).asnumpy(),
                       x.sum(1, keepdims=True), rtol=1e-5)
    # exclude semantics (MXNet-specific)
    assert np.allclose(nd.sum(a, axis=1, exclude=True).asnumpy(),
                       x.sum(axis=(0, 2)), rtol=1e-5)
    assert np.allclose(nd.mean(a, axis=(0, 2)).asnumpy(),
                       x.mean(axis=(0, 2)), rtol=1e-5)
    assert np.allclose(nd.max(a).asnumpy(), x.max())
    assert np.allclose(nd.argmax(a, axis=2).asnumpy(), x.argmax(2))
    assert np.allclose(nd.norm(a).asnumpy(),
                       np.sqrt((x ** 2).sum()), rtol=1e-5)


def test_dot():
    a = np.random.randn(3, 4).astype("float32")
    b = np.random.randn(4, 5).astype("float32")
    assert np.allclose(nd.dot(nd.array(a), nd.array(b)).asnumpy(),
                       a.dot(b), rtol=1e-4, atol=1e-5)
    assert np.allclose(
        nd.dot(nd.array(a), nd.array(b.T), transpose_b=True).asnumpy(),
        a.dot(b), rtol=1e-4, atol=1e-5)
    # batch_dot
    x = np.random.randn(2, 3, 4).astype("float32")
    y = np.random.randn(2, 4, 5).astype("float32")
    assert np.allclose(nd.batch_dot(nd.array(x), nd.array(y)).asnumpy(),
                       np.matmul(x, y), rtol=1e-4, atol=1e-5)


def test_fully_connected():
    x = np.random.randn(4, 10).astype("float32")
    w = np.random.randn(3, 10).astype("float32")
    b = np.random.randn(3).astype("float32")
    out = nd.FullyConnected(nd.array(x), nd.array(w), nd.array(b),
                            num_hidden=3)
    assert np.allclose(out.asnumpy(), x.dot(w.T) + b, rtol=1e-4,
                       atol=1e-5)
    # flatten semantics
    x4 = np.random.randn(4, 2, 5).astype("float32")
    out = nd.FullyConnected(nd.array(x4), nd.array(w), nd.array(b),
                            num_hidden=3, flatten=True)
    assert out.shape == (4, 3)


def test_convolution_vs_torch():
    import torch
    import torch.nn.functional as tF
    x = np.random.randn(2, 3, 8, 8).astype("float32")
    w = np.random.randn(5, 3, 3, 3).astype("float32")
    b = np.random.randn(5).astype("float32")
    out = nd.Convolution(nd.array(x), nd.array(w), nd.array(b),
                         kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                         num_filter=5)
    ref = tF.conv2d(torch.tensor(x), torch.tensor(w), torch.tensor(b),
                    stride=2, padding=1).numpy()
    assert np.allclose(out.asnumpy(), ref, rtol=1e-3, atol=1e-4)


def test_pooling_vs_torch():
    import torch
    import torch.nn.functional as tF
    x = np.random.randn(2, 3, 8, 8).astype("float32")
    out = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type="max")
    ref = tF.max_pool2d(torch.tensor(x), 2, 2).numpy()
    assert np.allclose(out.asnumpy(), ref)
    out = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type="avg")
    ref = tF.avg_pool2d(torch.tensor(x), 2, 2).numpy()
    assert np.allclose(out.asnumpy(), ref, rtol=1e-5)
    out = nd.Pooling(nd.array(x), global_pool=True, pool_type="avg",
                     kernel=(1, 1))
    assert np.allclose(out.asnumpy(), x.mean(axis=(2, 3), keepdims=True),
                       rtol=1e-5)


def test_softmax_family():
    x = np.random.randn(4, 10).astype("float32")
    a = nd.array(x)
    sm = nd.softmax(a).asnumpy()
    ex = np.exp(x - x.max(1, keepdims=True))
    ref = ex / ex.sum(1, keepdims=True)
    assert np.allclose(sm, ref, rtol=1e-5, atol=1e-6)
    lsm = nd.log_softmax(a).asnumpy()
    assert np.allclose(lsm, np.log(ref), rtol=1e-4, atol=1e-5)
    assert np.allclose(nd.softmax(a, axis=0).asnumpy().sum(0), 1.0,
                       rtol=1e-5)


def test_batchnorm_train_and_inference():
    x = np.random.randn(4, 3, 5, 5).astype("float32")
    gamma = np.ones(3, dtype="float32")
    beta = np.zeros(3, dtype="float32")
    mean = nd.zeros((3,))
    var = nd.ones((3,))
    # training mode: uses batch stats, updates running stats
    with autograd.train_mode():
        out = nd.BatchNorm(nd.array(x), nd.array(gamma), nd.array(beta),
                           mean, var, fix_gamma=False, momentum=0.9)
    o = out.asnumpy()  # aux states written back via mutation, one output
    bm = x.mean(axis=(0, 2, 3))
    bv = x.var(axis=(0, 2, 3))
    ref = (x - bm.reshape(1, 3, 1, 1)) / np.sqrt(
        bv.reshape(1, 3, 1, 1) + 1e-3)
    assert np.allclose(o, ref, rtol=1e-3, atol=1e-4)
    # running stats were mutated
    assert np.allclose(mean.asnumpy(), 0.1 * bm, rtol=1e-4, atol=1e-5)


def test_transpose_slice_ops():
    x = np.arange(24).reshape(2, 3, 4).astype("float32")
    a = nd.array(x)
    assert np.allclose(nd.transpose(a, axes=(2, 0, 1)).asnumpy(),
                       x.transpose(2, 0, 1))
    assert np.allclose(nd.slice_axis(a, axis=1, begin=1, end=3).asnumpy(),
                       x[:, 1:3])
    assert np.allclose(
        nd.slice(a, begin=(0, 1, 0), end=(2, 3, 2)).asnumpy(),
        x[0:2, 1:3, 0:2])
    assert np.allclose(nd.flip(a, axis=1).asnumpy(), x[:, ::-1])
    assert np.allclose(nd.tile(nd.array([1.0, 2.0]), reps=(2, 2)).asnumpy(),
                       np.tile([1, 2], (2, 2)))
    assert np.allclose(nd.repeat(a, repeats=2, axis=0).asnumpy(),
                       np.repeat(x, 2, 0))


def test_take_pick_onehot():
    x = np.random.randn(5, 4).astype("float32")
    a = nd.array(x)
    idx = nd.array([0, 2, 4])
    assert np.allclose(nd.take(a, idx).asnumpy(), x[[0, 2, 4]])
    picked = nd.pick(a, nd.array([0, 1, 2, 3, 0]), axis=1)
    assert np.allclose(picked.asnumpy(),
                       x[np.arange(5), [0, 1, 2, 3, 0]])
    oh = nd.one_hot(nd.array([0, 2]), depth=4)
    assert np.allclose(oh.asnumpy(), np.eye(4)[[0, 2]])


@pytest.mark.slow
def test_grads_of_common_ops():
    x = np.random.uniform(0.5, 1.5, (3, 4)).astype("float32")
    check_numeric_gradient(lambda a: (a * a).sum(), [x.copy()])
    check_numeric_gradient(lambda a: nd.exp(a).sum(), [x.copy()])
    check_numeric_gradient(lambda a: nd.log(a).sum(), [x.copy()])
    w = np.random.randn(4, 4).astype("float32") * 0.1
    check_numeric_gradient(
        lambda a, b: nd.dot(a, b).sum(), [x.copy(), w.copy()])
    check_numeric_gradient(
        lambda a: nd.softmax(a).sum(axis=0).max(), [x.copy()])


def test_embedding():
    w = np.random.randn(10, 4).astype("float32")
    idx = nd.array([1, 3, 5])
    out = nd.Embedding(idx, nd.array(w), input_dim=10, output_dim=4)
    assert np.allclose(out.asnumpy(), w[[1, 3, 5]])


def test_where_clip():
    a = nd.array([-2.0, -1.0, 1.0, 2.0])
    assert np.allclose(nd.clip(a, -1, 1).asnumpy(), [-1, -1, 1, 1])
    cond = nd.array([1.0, 0.0, 1.0, 0.0])
    assert np.allclose(nd.where(cond, a, nd.zeros_like(a)).asnumpy(),
                       [-2, 0, 1, 0])


def test_random_ops():
    mx.random.seed(0)
    u = nd.random_uniform(low=0, high=1, shape=(100,))
    assert u.shape == (100,)
    assert 0 <= float(u.min().asscalar()) and \
        float(u.max().asscalar()) <= 1
    n = nd.random_normal(loc=0, scale=1, shape=(500,))
    assert abs(float(n.mean().asscalar())) < 0.2
    # seeding reproduces
    mx.random.seed(123)
    a = nd.random_uniform(shape=(5,)).asnumpy()
    mx.random.seed(123)
    b = nd.random_uniform(shape=(5,)).asnumpy()
    assert np.allclose(a, b)
    r = nd.randint(low=0, high=10, shape=(20,))
    vals = r.asnumpy()
    assert vals.min() >= 0 and vals.max() < 10


def test_topk_sort():
    x = np.random.randn(3, 6).astype("float32")
    a = nd.array(x)
    idx = nd.topk(a, k=2, axis=1).asnumpy().astype(int)
    ref = np.argsort(-x, axis=1)[:, :2]
    assert np.allclose(np.sort(idx, 1), np.sort(ref, 1))
    both = nd.topk(a, k=2, axis=1, ret_typ="both")
    assert both[0].shape == (3, 2)
    s = nd.sort(a, axis=1).asnumpy()
    assert np.allclose(s, np.sort(x, 1))


def test_optimizer_ops_mutation():
    w = nd.ones((4,))
    g = nd.ones((4,)) * 0.5
    nd.sgd_update(w, g, out=w, lr=0.1)
    assert np.allclose(w.asnumpy(), 1 - 0.05)
    mom = nd.zeros((4,))
    nd.sgd_mom_update(w, g, mom, out=w, lr=0.1, momentum=0.9)
    assert np.allclose(mom.asnumpy(), -0.05)
    mean, var = nd.zeros((4,)), nd.zeros((4,))
    w2 = nd.ones((4,))
    nd.adam_update(w2, g, mean, var, out=w2, lr=0.1)
    assert not np.allclose(w2.asnumpy(), 1.0)
    assert not np.allclose(mean.asnumpy(), 0.0)


def test_cast_amp():
    a = nd.ones((2, 2))
    assert nd.cast(a, dtype="float16").dtype == np.float16
    assert nd.amp_cast(a, dtype="bfloat16").dtype.name == "bfloat16"
    outs = nd.amp_multicast(nd.ones((2,), dtype="float16"),
                            nd.ones((2,)), num_outputs=2)
    assert outs[0].dtype == np.float32 and outs[1].dtype == np.float32


def test_sequence_ops():
    x = np.arange(12).reshape(3, 2, 2).astype("float32")  # (T,N,D)
    lens = nd.array([2.0, 3.0])
    out = nd.SequenceMask(nd.array(x), lens, use_sequence_length=True,
                          value=-1.0)
    o = out.asnumpy()
    assert np.all(o[2, 0] == -1) and np.all(o[2, 1] == x[2, 1])
    last = nd.SequenceLast(nd.array(x), lens, use_sequence_length=True)
    assert np.allclose(last.asnumpy(), np.stack([x[1, 0], x[2, 1]]))


@pytest.mark.slow
def test_fused_multi_sgd_matches_loop():
    """Pallas grouped optimizer kernel == per-tensor sgd_update loop."""
    import numpy as np
    from mxnet_tpu import nd

    rng = np.random.RandomState(0)
    shapes = [(7, 5), (33,), (4, 4, 4), (129,)]
    ws = [nd.array(rng.randn(*s).astype("float32")) for s in shapes]
    gs = [nd.array(rng.randn(*s).astype("float32")) for s in shapes]
    ms = [nd.array(np.zeros(s, "float32")) for s in shapes]
    lrs = [0.1, 0.2, 0.05, 0.3]
    wds = [0.0, 0.01, 0.1, 0.0]

    # the eager-jit cache does not key on MXNET_FUSED_OPTIMIZER, so the
    # reference is the per-tensor op itself, not the same op re-run
    # with the variable flipped
    from mxnet_tpu.kernels import fused_optimizer
    calls = []
    orig = fused_optimizer.fused_multi_sgd
    fused_optimizer.fused_multi_sgd = \
        lambda *a, **k: calls.append(1) or orig(*a, **k)
    try:
        data = []
        moms_f = [m.copy() for m in ms]
        for w, g, m in zip(ws, gs, moms_f):
            data.extend([w.copy(), g, m])
        outs = nd.multi_sgd_mom_update(
            *data, lrs=lrs, wds=wds, momentum=0.9,
            rescale_grad=0.5, clip_gradient=1.0, num_weights=4)
    finally:
        fused_optimizer.fused_multi_sgd = orig
    assert calls, "multi_sgd_mom_update did not take the fused kernel"
    outs_f = [o.asnumpy() for o in outs[:4]]
    moms_f = [m.asnumpy() for m in moms_f]
    outs_r, moms_r = [], []
    for w, g, m, lr, wd in zip(ws, gs, ms, lrs, wds):
        m = m.copy()
        outs_r.append(nd.sgd_mom_update(
            w.copy(), g, m, lr=lr, wd=wd, momentum=0.9,
            rescale_grad=0.5, clip_gradient=1.0).asnumpy())
        moms_r.append(m.asnumpy())
    for a, b in zip(outs_f, outs_r):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    for a, b in zip(moms_f, moms_r):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_topk_values_differentiable():
    """topk ret_typ='value' carries gradients (reference: topk backward
    scatters into the selected positions); indices stay non-recorded."""
    x = np.array([[3.0, 1.0, 2.0], [0.5, 2.5, 1.5]], dtype="float32")
    a = nd.array(x)
    a.attach_grad()
    with autograd.record():
        v = nd.topk(a, k=2, ret_typ="value", axis=-1)
        L = (v * nd.array(np.array([[1, 10], [100, 1000]], "float32"))).sum()
    L.backward()
    # row0 top2 = [3, 2] -> grads 1 at col0, 10 at col2
    # row1 top2 = [2.5, 1.5] -> 100 at col1, 1000 at col2
    expect = np.array([[1, 0, 10], [0, 100, 1000]], dtype="float32")
    assert np.allclose(a.grad.asnumpy(), expect)
    # indices-only stays non-differentiable (not recorded on the tape)
    with autograd.record():
        idx = nd.topk(a, k=1)
    import pytest
    with pytest.raises(mx.base.MXNetError):
        idx.backward()


def test_topk_positional_ret_typ_grads():
    """Regression: attr-dependent no_grad must see POSITIONAL attrs too
    (nd.topk(a, axis, k, ret_typ) binds via the impl signature)."""
    x = np.array([[3.0, 1.0, 2.0]], dtype="float32")
    a = nd.array(x)
    a.attach_grad()
    with autograd.record():
        v = nd.topk(a, -1, 2, "value")
        L = (v * nd.array(np.array([[2.0, 3.0]], "float32"))).sum()
    L.backward()
    assert np.allclose(a.grad.asnumpy(), [[2, 0, 3]])


def test_topk_mask_scatter_backward():
    """ret_typ='mask' backward scatters out_grad into the selected
    positions (reference TopKImpl backward), not all-zeros."""
    x = np.array([[1.0, 3.0, 2.0], [5.0, 4.0, 6.0]], dtype="float32")
    a = nd.array(x)
    a.attach_grad()
    with autograd.record():
        m = nd.topk(a, k=2, ret_typ="mask", axis=-1)
        L = (m * nd.array(np.array([[1, 10, 100], [2, 20, 200]],
                                   "float32"))).sum()
    L.backward()
    # row0 top2 = cols 1,2 ; row1 top2 = cols 0,2
    expect = np.array([[0, 10, 100], [2, 0, 200]], dtype="float32")
    assert np.allclose(m.asnumpy(),
                       np.array([[0, 1, 1], [1, 0, 1]], "float32"))
    assert np.allclose(a.grad.asnumpy(), expect)


def test_topk_mask_non_last_axis():
    """mask shape/values must be correct for axis != -1 (regression:
    one_hot's appended trailing dim was summed on the wrong axis)."""
    x = np.arange(24, dtype="float32").reshape(2, 3, 4)
    for ax in (0, 1, 2, -2):
        m = nd.topk(nd.array(x), k=1, ret_typ="mask", axis=ax).asnumpy()
        assert m.shape == x.shape, (ax, m.shape)
        assert np.allclose(m.sum(axis=ax), 1.0), (ax, m)
        assert np.allclose((m * x).sum(axis=ax), x.max(axis=ax)), ax


def test_topk_both_backward():
    """ret_typ='both' under record: backward through both heads works
    (idx contributes zero gradient; vals scatter normally)."""
    x = np.array([[1.0, 3.0, 2.0], [5.0, 4.0, 6.0]], dtype="float32")
    a = nd.array(x)
    a.attach_grad()
    with autograd.record():
        vals, idx = nd.topk(a, k=2, ret_typ="both", axis=-1)
    autograd.backward([vals, idx])
    expect = np.array([[0, 1, 1], [1, 0, 1]], dtype="float32")
    assert np.allclose(a.grad.asnumpy(), expect)
