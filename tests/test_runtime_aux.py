"""Runtime features, extension loading, rtc Pallas kernels, detection
augmenters, im2rec CLI, opperf harness (SURVEY.md §2 aux rows)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# runtime features
# ---------------------------------------------------------------------------

def test_runtime_features():
    feats = mx.runtime.Features()
    assert feats["XLA"].enabled
    assert feats["CPU"].enabled
    assert feats.is_enabled("xla")
    assert not feats.is_enabled("ONNX")  # not installed in this env
    with pytest.raises(KeyError):
        feats.is_enabled("NO_SUCH_FEATURE")
    names = {f.name for f in mx.runtime.feature_list()}
    assert {"TPU", "PALLAS", "DIST_KVSTORE"} <= names
    assert "✔" in repr(feats) or "✖" in repr(feats)


# ---------------------------------------------------------------------------
# library loading
# ---------------------------------------------------------------------------

def test_library_load_python_ext(tmp_path):
    ext = tmp_path / "my_ext.py"
    ext.write_text(
        "from mxnet_tpu.ops import registry\n"
        "import jax.numpy as jnp\n"
        "@registry.register('test_ext_double')\n"
        "def _double(x):\n"
        "    return x * 2\n")
    mx.library.load(str(ext), verbose=False)
    from mxnet_tpu import nd
    out = nd.array(np.ones((2, 2))) * 1  # ensure nd working
    y = getattr(nd, "test_ext_double", None)
    if y is None:  # generated stubs may not refresh; invoke via registry
        from mxnet_tpu.ops import registry
        assert registry.op_exists("test_ext_double")
    assert str(ext) in mx.library.loaded_libs()
    # leave the registry as the package built it (test_amp sweeps it)
    from mxnet_tpu.ops import registry
    registry._OPS.pop("test_ext_double", None)


def test_library_load_missing():
    with pytest.raises(mx.MXNetError):
        mx.library.load("/no/such/ext.py")
    with pytest.raises(mx.MXNetError):
        mx.library.load("/no/such/lib.so")


# ---------------------------------------------------------------------------
# rtc (user Pallas kernels)
# ---------------------------------------------------------------------------

def test_rtc_pallas_kernel():
    mod = mx.rtc.PallasModule(r"""
def scale2(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0
""", exports=["scale2"])
    k = mod.get_kernel("scale2")
    from mxnet_tpu import nd
    x = nd.array(np.arange(8, dtype="float32").reshape(2, 4))
    y = k(x)
    np.testing.assert_allclose(y.asnumpy(), x.asnumpy() * 2)
    with pytest.raises(mx.MXNetError):
        mod.get_kernel("nope")
    with pytest.raises(mx.MXNetError):
        mx.rtc.PallasModule("this is ( not python")


# ---------------------------------------------------------------------------
# detection augmenters
# ---------------------------------------------------------------------------

def _toy_img_label():
    from mxnet_tpu import nd
    img = nd.array(np.random.RandomState(0).randint(
        0, 255, (64, 96, 3)).astype("float32"))
    label = np.array([[1, 0.25, 0.25, 0.5, 0.5],
                      [3, 0.6, 0.1, 0.9, 0.4]], dtype=np.float32)
    return img, label


def test_det_horizontal_flip():
    from mxnet_tpu.image.detection import DetHorizontalFlipAug
    img, label = _toy_img_label()
    aug = DetHorizontalFlipAug(p=1.0)
    img2, lab2 = aug(img, label)
    assert img2.shape == img.shape
    np.testing.assert_allclose(lab2[0, 1], 1 - 0.5, atol=1e-6)
    np.testing.assert_allclose(lab2[0, 3], 1 - 0.25, atol=1e-6)
    # x-flip twice = identity
    _, lab3 = aug(img2, lab2)
    np.testing.assert_allclose(lab3, label, atol=1e-6)


def test_det_random_crop_keeps_constraint():
    from mxnet_tpu.image.detection import DetRandomCropAug
    img, label = _toy_img_label()
    aug = DetRandomCropAug(min_object_covered=0.1,
                           area_range=(0.5, 1.0), max_attempts=20)
    img2, lab2 = aug(img, label)
    assert lab2.shape[1] == 5
    kept = lab2[lab2[:, 0] >= 0]
    assert (kept[:, 1:5] >= 0).all() and (kept[:, 1:5] <= 1).all()


def test_det_random_pad_boxes_shrink():
    from mxnet_tpu.image.detection import DetRandomPadAug
    img, label = _toy_img_label()
    aug = DetRandomPadAug(area_range=(2.0, 2.0))
    img2, lab2 = aug(img, label)
    assert img2.shape[0] >= img.shape[0]
    assert img2.shape[1] >= img.shape[1]
    w_old = label[0, 3] - label[0, 1]
    w_new = lab2[0, 3] - lab2[0, 1]
    assert w_new < w_old + 1e-6


def test_create_det_augmenter_runs():
    from mxnet_tpu.image.detection import CreateDetAugmenter
    img, label = _toy_img_label()
    augs = CreateDetAugmenter((3, 32, 48), rand_crop=0.5,
                              rand_mirror=True, rand_pad=0.5,
                              mean=True, std=True)
    for aug in augs:
        img, label = aug(img, label)
    assert img.shape == (32, 48, 3)


def test_image_det_iter(tmp_path):
    """Pack 4 toy images with box labels, read through ImageDetIter."""
    from PIL import Image
    from mxnet_tpu import recordio
    from mxnet_tpu.image.detection import ImageDetIter, DetBorrowAug
    from mxnet_tpu import image as mximg

    rec_path = str(tmp_path / "det.rec")
    idx_path = str(tmp_path / "det.idx")
    rec = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    rng = np.random.RandomState(0)
    for i in range(4):
        import io as _io
        buf = _io.BytesIO()
        Image.fromarray(rng.randint(0, 255, (40, 60, 3),
                                    dtype=np.uint8)).save(buf, "JPEG")
        # header format: [A=2, w=5] + one object per image
        label = [2, 5, float(i), 0.1, 0.2, 0.8, 0.9]
        hdr = recordio.IRHeader(0, label, i, 0)
        rec.write_idx(i, recordio.pack(hdr, buf.getvalue()))
    rec.close()

    it = ImageDetIter(batch_size=2, data_shape=(3, 32, 32),
                      path_imgrec=rec_path, path_imgidx=idx_path,
                      aug_list=[DetBorrowAug(
                          mximg.ForceResizeAug((32, 32)))])
    batch = it.next()
    assert batch.data[0].shape == (2, 3, 32, 32)
    assert batch.label[0].shape[0] == 2
    assert batch.label[0].shape[2] == 5
    lab = batch.label[0].asnumpy()
    np.testing.assert_allclose(lab[0, 0], [0, 0.1, 0.2, 0.8, 0.9],
                               atol=1e-6)


# ---------------------------------------------------------------------------
# im2rec CLI
# ---------------------------------------------------------------------------

def test_im2rec_roundtrip(tmp_path):
    from PIL import Image
    rng = np.random.RandomState(0)
    for cls in ("cat", "dog"):
        d = tmp_path / "imgs" / cls
        d.mkdir(parents=True)
        for i in range(3):
            Image.fromarray(rng.randint(0, 255, (32, 32, 3),
                                        dtype=np.uint8)).save(
                str(d / ("%d.jpg" % i)))
    prefix = str(tmp_path / "pack")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r1 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         "--list", "--recursive", prefix, str(tmp_path / "imgs")],
        capture_output=True, text=True, env=env, timeout=120)
    assert r1.returncode == 0, r1.stderr
    assert os.path.exists(prefix + ".lst")
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         "--resize", "24", prefix, str(tmp_path / "imgs"),
         "--working-dir", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r2.returncode == 0, r2.stderr
    assert os.path.exists(prefix + ".rec")

    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                     "r")
    assert len(rec.keys) == 6
    hdr, img = recordio.unpack(rec.read_idx(0))
    from mxnet_tpu.image import imdecode
    arr = imdecode(img).asnumpy()
    assert min(arr.shape[:2]) == 24
    labels = set()
    for k in rec.keys:
        h, _ = recordio.unpack(rec.read_idx(k))
        labels.add(float(h.label))
    assert labels == {0.0, 1.0}


# ---------------------------------------------------------------------------
# opperf
# ---------------------------------------------------------------------------

def test_opperf_smoke():
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import opperf
        res = opperf.run_op_benchmarks(["relu", "dot", "softmax"],
                                       ctx=mx.cpu(), warmup=1, runs=3)
    finally:
        sys.path.pop(0)
    assert len(res) == 3
    for r in res:
        assert "error" not in r, r
        assert r["eager_us"] > 0


def test_image_det_iter_static_label_shape(tmp_path):
    """Every batch pads to one static (B, max_objects, w) shape."""
    from PIL import Image
    import io as _io
    from mxnet_tpu import recordio
    from mxnet_tpu.image.detection import ImageDetIter, DetBorrowAug
    from mxnet_tpu import image as mximg

    rec_path = str(tmp_path / "d.rec")
    idx_path = str(tmp_path / "d.idx")
    rec = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    rng = np.random.RandomState(0)
    for i in range(4):
        buf = _io.BytesIO()
        Image.fromarray(rng.randint(0, 255, (32, 32, 3),
                                    dtype=np.uint8)).save(buf, "JPEG")
        # record 1 has 3 objects, others 1
        n = 3 if i == 1 else 1
        label = [2, 5] + sum(
            ([float(i), .1, .1, .6, .6] for _ in range(n)), [])
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, label, i, 0), buf.getvalue()))
    rec.close()
    it = ImageDetIter(batch_size=2, data_shape=(3, 24, 24),
                      path_imgrec=rec_path, path_imgidx=idx_path,
                      aug_list=[DetBorrowAug(
                          mximg.ForceResizeAug((24, 24)))])
    assert it.provide_label[0].shape == (2, 3, 5)
    shapes = set()
    for batch in [it.next(), it.next()]:
        shapes.add(tuple(batch.label[0].shape))
    assert shapes == {(2, 3, 5)}


def test_im2rec_split_prefix_dir(tmp_path):
    """pack() finds split .lst files written next to a directory-prefixed
    prefix (the documented --train-ratio/--test-ratio flow)."""
    from PIL import Image
    rng = np.random.RandomState(0)
    d = tmp_path / "imgs"
    d.mkdir()
    for i in range(4):
        Image.fromarray(rng.randint(0, 255, (16, 16, 3),
                                    dtype=np.uint8)).save(
            str(d / ("%d.jpg" % i)))
    out = tmp_path / "out"
    out.mkdir()
    prefix = str(out / "pk")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r1 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         "--list", "--train-ratio", "0.5", "--test-ratio", "0.5",
         prefix, str(d)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r1.returncode == 0, r1.stderr
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         prefix, str(d)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r2.returncode == 0, r2.stderr
    assert os.path.exists(prefix + "_train.rec")
    assert os.path.exists(prefix + "_test.rec")


def test_env_var_doc_is_complete():
    """Every implemented MXNET_* switch must have a row in
    docs/env_vars.md so the doc cannot silently go stale (round-4
    verdict: MXNET_FLASH_MIN_SEQ — the most-referenced tunable — was
    missing).  Token scan over the package + native sources; C++
    include guards (``*_H_``) and wildcard doc mentions (trailing
    underscore) are not variables."""
    import re
    roots = [os.path.join(REPO, "mxnet_tpu"),
             os.path.join(REPO, "native", "src"),
             os.path.join(REPO, "tests", "conftest.py")]
    found = set()
    for root in roots:
        paths = [root] if os.path.isfile(root) else [
            os.path.join(dp, f) for dp, _, fs in os.walk(root)
            for f in fs if f.endswith((".py", ".cc", ".h"))]
        for p in paths:
            with open(p, encoding="utf-8", errors="ignore") as f:
                found.update(re.findall(r"MXNET_[A-Z0-9_]+", f.read()))
    vars_ = {v for v in found
             if not v.endswith("_") and not v.endswith("_H")}
    with open(os.path.join(REPO, "docs", "env_vars.md"),
              encoding="utf-8") as f:
        doc = f.read()
    undocumented = sorted(v for v in vars_ if v not in doc)
    assert not undocumented, (
        "implemented MXNET_* vars missing from docs/env_vars.md: %r"
        % undocumented)
