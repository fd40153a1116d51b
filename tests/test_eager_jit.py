"""Eager fast path — per-op compiled-callable cache (round-3 verdict
#3; reference: the Cython/FFI fast path, SURVEY.md §2.1 last row).
Unit coverage for the cache's semantic edges: identity-keyed safety,
dynamic lr, tracer bypass, blacklist fallback, LRU behavior, kill
switch."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.ops import registry as R


def setup_function(_fn):
    R._EAGER_CACHE.clear()
    R._EAGER_BLACKLIST.clear()


def test_cache_hit_is_single_entry_and_correct():
    a = nd.array(np.arange(12, dtype="float32").reshape(3, 4))
    r1 = nd.relu(a - 5).asnumpy()
    n0 = len(R._EAGER_CACHE)
    for _ in range(5):
        r2 = nd.relu(a - 5).asnumpy()
    assert len(R._EAGER_CACHE) == n0       # no growth on repeat calls
    np.testing.assert_array_equal(
        r1, np.maximum(np.arange(12).reshape(3, 4) - 5, 0))
    np.testing.assert_array_equal(r1, r2)


def test_distinct_attrs_get_distinct_entries():
    a = nd.array(np.random.rand(4, 6).astype("float32"))
    s1 = nd.sum(a, axis=0).asnumpy()
    s2 = nd.sum(a, axis=1).asnumpy()
    assert s1.shape == (6,) and s2.shape == (4,)
    np.testing.assert_allclose(s1, a.asnumpy().sum(0), rtol=1e-6)
    np.testing.assert_allclose(s2, a.asnumpy().sum(1), rtol=1e-6)


def test_lr_is_dynamic_not_a_cache_key():
    """Changing lr must not add cache entries (it is passed as a traced
    argument), and each call must use ITS lr value."""
    w = nd.ones((8,))
    g = nd.ones((8,))
    out = nd.sgd_update(w, g, lr=0.5)
    n0 = len(R._EAGER_CACHE)
    np.testing.assert_allclose(out.asnumpy(), 0.5)
    out2 = nd.sgd_update(w, g, lr=0.25)
    np.testing.assert_allclose(out2.asnumpy(), 0.75)
    assert len(R._EAGER_CACHE) == n0


def test_ephemeral_opdefs_are_not_cacheable():
    """Per-call OpDefs (getitem closures, autograd replay) must bypass
    the id-keyed cache — CPython reuses freed ids (round-3 bug class)."""
    from mxnet_tpu.ops.registry import OpDef
    op1 = OpDef("eph", lambda x: x * 2.0)
    assert not op1.cacheable
    handled, _ = R._eager_jit_call(op1, [nd.ones((2,))._data], (), {})
    assert not handled
    # registered ops ARE cacheable
    assert R.get_op("relu").cacheable


def test_slicing_values_are_not_cross_contaminated():
    """Regression: two different slice bounds through the stable
    _getitem op must not share a compiled callable."""
    x = nd.array(np.arange(64, dtype="float32").reshape(8, 8))
    a = x[0:2, 0:2]
    b = x[0:5, 0:3]
    assert a.shape == (2, 2) and b.shape == (5, 3)
    np.testing.assert_array_equal(b.asnumpy(),
                                  x.asnumpy()[0:5, 0:3])


def test_tracer_inputs_bypass_cache():
    """hybridize/vjp re-entry (tracer inputs) must not populate the
    eager cache."""
    import jax

    def f(v):
        op = R.get_op("relu")
        handled, _ = R._eager_jit_call(op, [v], (), {})
        assert not handled        # tracers are not concrete ArrayImpls
        return v

    jax.jit(f)(np.ones(3, "float32"))


def test_blacklist_falls_back_to_direct_path(caplog):
    """An impl that cannot trace gets blacklisted on first use and keeps
    working through the retracing path — announced by EXACTLY one log
    line (round-10 satellite: silent eager-path slowdowns were
    undiagnosable), repeat calls stay quiet."""
    import logging
    from mxnet_tpu.ops.registry import register, get_op, invoke

    name = "_test_untraceable_op"
    if not R.op_exists(name):
        @register(name, no_grad=True)
        def _untraceable(x):  # noqa: ANN001
            import numpy as _o
            return _o.asarray(x) * 2.0     # concretizes → untraceable

    # fresh state so the single-shot property is observable even when
    # another test already tripped this op
    R._EAGER_BLACKLIST.discard(name)
    R._EAGER_LOGGED.discard((name, "blacklisted"))
    op = get_op(name)
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.ops.registry"):
        out = invoke(op, [nd.ones((3,))])
        np.testing.assert_allclose(np.asarray(out._data), 2.0)
        assert name in R._EAGER_BLACKLIST
        out2 = invoke(op, [nd.ones((3,))])     # stays on direct path
        np.testing.assert_allclose(np.asarray(out2._data), 2.0)
        out3 = invoke(op, [nd.ones((3,))])
        np.testing.assert_allclose(np.asarray(out3._data), 2.0)
    recs = [r for r in caplog.records
            if name in r.getMessage() and "pinned" in r.getMessage()]
    assert len(recs) == 1, \
        "blacklist must log exactly once, got %d" % len(recs)
    assert (name, "blacklisted") in R._EAGER_LOGGED
    # leave the registry as the package built it: test_amp's sweep holds
    # every registered op to an AMP class, whichever file ran before it
    R._OPS.pop(name, None)


def test_autograd_and_cache_agree():
    """Recording mode replays through tracers; results must match the
    cached eager forward."""
    a = nd.array(np.random.RandomState(0).rand(4, 4).astype("float32"))
    eager = nd.sigmoid(a).asnumpy()
    a.attach_grad()
    with autograd.record():
        out = nd.sigmoid(a)
    out.backward()
    np.testing.assert_allclose(out.asnumpy(), eager, rtol=1e-6)
    s = eager * (1 - eager)
    np.testing.assert_allclose(a.grad.asnumpy(), s, rtol=1e-5)


def test_cache_lru_bound(monkeypatch):
    monkeypatch.setattr(R, "_EAGER_CACHE_MAX", 4)
    a = nd.ones((2, 2))
    for axis_pair in [(0,), (1,), (0, 1)]:
        nd.sum(a, axis=axis_pair)
    for k in range(2, 7):
        nd.reshape(nd.ones((4,)), shape=(2, 2))
        nd.sum(nd.ones((k, 2)), axis=1)
    assert len(R._EAGER_CACHE) <= 4


def test_kill_switch(monkeypatch):
    monkeypatch.setattr(R, "_EAGER_JIT", False)
    a = nd.ones((3, 3))
    out = nd.relu(a).asnumpy()
    np.testing.assert_array_equal(out, 1.0)
    assert len(R._EAGER_CACHE) == 0


def test_user_error_does_not_poison_blacklist():
    """Round-4 dispatch-tail fix: a caller error (wrong arity) on the
    FIRST call of an op must not blacklist it — only genuinely
    untraceable impls go to the retrace-per-call path."""
    from mxnet_tpu.ops.registry import get_op, invoke

    name = "_np_outer"
    R._EAGER_BLACKLIST.discard(name)
    op = get_op(name)
    with pytest.raises(Exception):
        invoke(op, [nd.ones((4,))])        # outer needs two operands
    assert name not in R._EAGER_BLACKLIST, \
        "user arity error poisoned the blacklist"
    out = invoke(op, [nd.ones((4,)), nd.ones((3,))])
    assert out.shape == (4, 3)
    # and the correct call landed in the compiled-callable cache
    assert any(k[0] == id(op) for k in R._EAGER_CACHE)
