"""Scale & compatibility tier (round-2 verdict missing item #7;
reference: tests/nightly/test_large_array.py +
model_backwards_compatibility_check/ — SURVEY.md §4.7).

* large-array: int64-indexing correctness on arrays whose element
  count exceeds int32 range.  Gated behind MXNET_TEST_LARGE_ARRAY=1
  like the reference's nightly (needs ~2.5 GB host RAM).
* checkpoint compat: golden checkpoints committed in round 2 must load
  bit-exactly in every future round (.params container, symbol JSON,
  trainer states).
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "tests", "golden")


# ---------------------------------------------------------------------------
# large array (int64 indexing)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("MXNET_TEST_LARGE_ARRAY") != "1",
                    reason="needs ~2.5GB RAM; set "
                           "MXNET_TEST_LARGE_ARRAY=1 (nightly tier, "
                           "like the reference)")
def test_int64_indexing_beyond_int32_elements():
    n = 2**31 + 8                      # element count > int32 max
    a = nd.zeros((n,), dtype="int8")
    assert a.shape[0] == n
    # writes at indices beyond 2^31 must land where they were aimed
    idx = [0, 2**31 - 1, 2**31, n - 1]
    for i, v in zip(idx, (1, 2, 3, 4)):
        a[i:i + 1] = v
    for i, v in zip(idx, (1, 2, 3, 4)):
        assert int(a[i:i + 1].asnumpy()[0]) == v
    s = int(nd.sum(a.astype("int32")).asnumpy())
    assert s == 1 + 2 + 3 + 4


def test_index_widening_machinery():
    """Cheap every-tier guard for the int64 indexing fix: the widen
    helper must upcast integer index arrays (XLA computes gather
    offsets in the index dtype), and the x64 scope must activate
    exactly at the 2^31-element threshold."""
    import contextlib
    import jax
    import jax.numpy as jnp
    a = nd.zeros((4, 4))
    with jax.enable_x64(True):
        k = a._widen_index_arrays((jnp.array([1, 2], jnp.int32),
                                   slice(None)))
        assert k[0].dtype == jnp.int64
        assert isinstance(k[1], slice)
    small = nd.zeros((8,))
    assert isinstance(small._int64_index_scope(),
                      contextlib.nullcontext().__class__)

    class _Huge(type(a)):
        @property
        def size(self):
            return 2**31

    huge = _Huge(a._data)
    assert not isinstance(huge._int64_index_scope(),
                          contextlib.nullcontext().__class__)


# ---------------------------------------------------------------------------
# checkpoint-format stability
# ---------------------------------------------------------------------------

def _golden_net():
    x = sym.Variable("data")
    h = sym.FullyConnected(x, num_hidden=5, name="fc1")
    h = sym.Activation(h, act_type="relu", name="r1")
    return sym.FullyConnected(h, num_hidden=3, name="fc2")


def _golden_params():
    rng = np.random.RandomState(123)
    return {
        "fc1_weight": rng.randn(5, 4).astype("float32"),
        "fc1_bias": rng.randn(5).astype("float32"),
        "fc2_weight": rng.randn(3, 5).astype("float32"),
        "fc2_bias": rng.randn(3).astype("float32"),
    }


def test_golden_checkpoint_roundtrip_current():
    """Current code writes and reads its own formats (sanity leg)."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "m.params")
        nd.save(p, {"arg:" + k: nd.array(v)
                    for k, v in _golden_params().items()})
        loaded = nd.load(p)
        for k, v in _golden_params().items():
            np.testing.assert_array_equal(loaded["arg:" + k].asnumpy(),
                                          v)


def test_golden_checkpoint_loads():
    """The round-2 golden files must keep loading IDENTICALLY in every
    later round — format drift across rounds is a release-breaking bug
    in the reference world (model_backwards_compatibility_check)."""
    params_path = os.path.join(GOLDEN, "golden-0000.params")
    json_path = os.path.join(GOLDEN, "golden-symbol.json")
    expect_path = os.path.join(GOLDEN, "golden-expect.json")
    assert os.path.exists(params_path), "golden checkpoint missing"

    loaded = nd.load(params_path)
    for k, v in _golden_params().items():
        np.testing.assert_array_equal(loaded["arg:" + k].asnumpy(), v,
                                      err_msg=k)

    s = sym.load(json_path)
    args = {k.split(":", 1)[1]: v for k, v in loaded.items()}
    data = np.arange(8, dtype="float32").reshape(2, 4) / 8.0
    ex = s.bind(ctx=mx.cpu(), args=dict(args, data=nd.array(data)))
    out = ex.forward()[0].asnumpy()
    with open(expect_path) as f:
        expect = np.array(json.load(f), dtype="float32")
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


if __name__ == "__main__":
    # regenerate the golden files (run once; outputs are committed)
    os.makedirs(GOLDEN, exist_ok=True)
    nd.save(os.path.join(GOLDEN, "golden-0000.params"),
            {"arg:" + k: nd.array(v)
             for k, v in _golden_params().items()})
    s = _golden_net()
    s.save(os.path.join(GOLDEN, "golden-symbol.json"))
    args = {k: nd.array(v) for k, v in _golden_params().items()}
    data = np.arange(8, dtype="float32").reshape(2, 4) / 8.0
    ex = s.bind(ctx=mx.cpu(), args=dict(args, data=nd.array(data)))
    out = ex.forward()[0].asnumpy()
    with open(os.path.join(GOLDEN, "golden-expect.json"), "w") as f:
        json.dump([[float(v) for v in row] for row in out], f)
    print("golden files written to", GOLDEN)
