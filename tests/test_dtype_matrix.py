"""Dtype matrix for the forward oracles (round-2 verdict item #4):
every core op family at bfloat16 / float16 / float64 against its
float32 result, with dtype-aware tolerances (reference:
``check_consistency``'s per-dtype tolerance table, SURVEY.md §4.2)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd

pytestmark = pytest.mark.slow

# (name, fn, shapes, positive-data)
CASES = [
    ("relu", lambda a: nd.relu(a), [(4, 5)], False),
    ("sigmoid", lambda a: nd.sigmoid(a), [(4, 5)], False),
    ("tanh", lambda a: nd.tanh(a), [(4, 5)], False),
    ("exp", lambda a: nd.exp(a), [(4, 5)], False),
    ("log", lambda a: nd.log(a), [(4, 5)], True),
    ("sqrt", lambda a: nd.sqrt(a), [(4, 5)], True),
    ("erf", lambda a: nd.erf(a), [(4, 5)], False),
    ("softmax", lambda a: nd.softmax(a), [(4, 6)], False),
    ("log_softmax", lambda a: nd.log_softmax(a), [(4, 6)], False),
    ("gelu", lambda a: nd.LeakyReLU(a, act_type="gelu"), [(4, 5)],
     False),
    ("dot", lambda a, b: nd.dot(a, b), [(4, 5), (5, 6)], False),
    ("batch_dot", lambda a, b: nd.batch_dot(a, b),
     [(2, 3, 4), (2, 4, 5)], False),
    ("fully_connected",
     lambda a, w, b: nd.FullyConnected(a, w, b, num_hidden=6),
     [(3, 5), (6, 5), (6,)], False),
    ("convolution",
     lambda a, w, b: nd.Convolution(a, w, b, kernel=(3, 3),
                                    num_filter=4, pad=(1, 1)),
     [(2, 3, 6, 6), (4, 3, 3, 3), (4,)], False),
    ("pooling_max",
     lambda a: nd.Pooling(a, kernel=(2, 2), stride=(2, 2),
                          pool_type="max"), [(2, 2, 6, 6)], False),
    ("pooling_avg",
     lambda a: nd.Pooling(a, kernel=(2, 2), stride=(2, 2),
                          pool_type="avg"), [(2, 2, 6, 6)], False),
    ("layer_norm", lambda a, g, b: nd.LayerNorm(a, g, b),
     [(4, 6), (6,), (6,)], False),
    ("sum", lambda a: nd.sum(a, axis=1), [(4, 5)], False),
    ("mean", lambda a: nd.mean(a, axis=0), [(4, 5)], False),
    ("broadcast_add", lambda a, b: nd.broadcast_add(a, b),
     [(3, 4), (3, 1)], False),
    ("broadcast_mul", lambda a, b: nd.broadcast_mul(a, b),
     [(3, 4), (1, 4)], False),
    ("transpose", lambda a: nd.transpose(a), [(3, 4)], False),
    ("concat", lambda a, b: nd.Concat(a, b, dim=1), [(3, 2), (3, 3)],
     False),
    ("embedding",
     lambda w: nd.Embedding(nd.array(np.array([1., 0., 2.])), w,
                            input_dim=4, output_dim=3), [(4, 3)],
     False),
    ("take", lambda a: nd.take(a, nd.array(np.array([0, 2]))),
     [(4, 5)], False),
    ("clip", lambda a: nd.clip(a, a_min=-0.5, a_max=0.5), [(4, 5)],
     False),
    ("smooth_l1", lambda a: nd.smooth_l1(a, scalar=1.0), [(4, 5)],
     False),
    ("l2_normalization", lambda a: nd.L2Normalization(a), [(4, 5)],
     False),
    ("instance_norm", lambda a, g, b: nd.InstanceNorm(a, g, b),
     [(2, 3, 4, 4), (3,), (3,)], False),
    ("elemwise_div", lambda a, b: nd.elemwise_div(a, b),
     [(4, 5), (4, 5)], True),
]

TOL = {
    "bfloat16": dict(rtol=5e-2, atol=5e-2),
    "float16": dict(rtol=1e-2, atol=1e-2),
    "float64": dict(rtol=1e-5, atol=1e-6),
}


def _gen(shapes, positive):
    rng = np.random.RandomState(0)
    return [(rng.uniform(0.5, 1.5, s) if positive
             else rng.uniform(-1.0, 1.0, s)).astype("float32")
            for s in shapes]


@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "float64"])
@pytest.mark.parametrize("name,fn,shapes,positive", CASES,
                         ids=[c[0] for c in CASES])
def test_forward_dtype_matrix(name, fn, shapes, positive, dtype):
    """fwd(x.astype(dt)) ≈ fwd(x) within the dtype's tolerance."""
    if dtype == "float64":
        import jax
        ctx = jax.enable_x64(True)
    else:
        import contextlib
        ctx = contextlib.nullcontext()
    arrays = _gen(shapes, positive)
    ref = fn(*[nd.array(a) for a in arrays]).asnumpy().astype("float64")
    with ctx:
        inputs = [nd.array(a, dtype=dtype) for a in arrays]
        out = fn(*inputs)
        got = np.asarray(out.asnumpy(), dtype="float64")
    tol = TOL[dtype]
    np.testing.assert_allclose(got, ref, **tol)


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
@pytest.mark.parametrize(
    "name,fn,shapes,positive",
    [c for c in CASES if c[0] in ("dot", "convolution", "layer_norm",
                                  "softmax", "fully_connected")],
    ids=["dot", "convolution", "layer_norm", "softmax",
         "fully_connected"])
def test_backward_dtype_matrix(name, fn, shapes, positive, dtype):
    """Low-precision backward stays finite and tracks the f32 gradient
    direction (cosine > 0.99) — the property AMP training relies on."""
    arrays = _gen(shapes, positive)

    def grads(dt):
        inputs = [nd.array(a, dtype=dt) for a in arrays]
        for x in inputs:
            x.attach_grad()
        with autograd.record():
            out = fn(*inputs)
            loss = (nd.cast(out, dtype="float32") ** 2).sum()
        loss.backward()
        return [x.grad.asnumpy().astype("float64") for x in inputs]

    g32 = grads("float32")
    glow = grads(dtype)
    for a, b in zip(g32, glow):
        assert np.isfinite(b).all()
        na, nb = np.linalg.norm(a.ravel()), np.linalg.norm(b.ravel())
        if na < 1e-6 and nb < 1e-6:
            continue
        cos = float(a.ravel() @ b.ravel() / (na * nb + 1e-12))
        assert cos > 0.99, (name, dtype, cos)
