"""c_predict_api + cpp-package: standalone C++ inference against the
Python forward (SURVEY.md §2.1 "C API" / §2.3 "C++ frontend" rows)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")

CPP_MAIN = r"""
#include <cstdio>
#include <vector>
#include "mxnet_tpu/cpp/predictor.hpp"

int main(int argc, char** argv) {
  std::string json = mxnet_tpu::cpp::LoadFile(argv[1]);
  std::string params = mxnet_tpu::cpp::LoadFile(argv[2]);
  mxnet_tpu::cpp::Predictor pred(json, params, {{"data", {2, 6}}});
  std::vector<float> in(12);
  for (int i = 0; i < 12; ++i) in[i] = 0.1f * i - 0.5f;
  pred.SetInput("data", in);
  pred.Forward();
  auto shape = pred.GetOutputShape(0);
  printf("shape:");
  for (auto d : shape) printf(" %u", d);
  printf("\n");
  auto out = pred.GetOutput(0);
  for (float v : out) printf("%.6f ", v);
  printf("\n");
  return 0;
}
"""


@pytest.fixture(scope="module")
def predict_lib():
    r = subprocess.run(["make", "-C", NATIVE, "predict"],
                       capture_output=True, text=True, timeout=300)
    lib = os.path.join(NATIVE, "lib", "libmxnet_tpu_predict.so")
    if r.returncode != 0 or not os.path.exists(lib):
        pytest.skip("predict library build failed: %s" % r.stderr[-500:])
    return lib


def _export_mlp(tmp_path):
    x = sym.Variable("data")
    h = sym.FullyConnected(x, num_hidden=8, name="fc1")
    h = sym.Activation(h, act_type="relu", name="r1")
    o = sym.softmax(sym.FullyConnected(h, num_hidden=3, name="fc2"),
                    name="sm")
    rng = np.random.RandomState(0)
    params = {
        "fc1_weight": nd.array(rng.randn(8, 6).astype("float32") * 0.3),
        "fc1_bias": nd.array(rng.randn(8).astype("float32") * 0.1),
        "fc2_weight": nd.array(rng.randn(3, 8).astype("float32") * 0.3),
        "fc2_bias": nd.array(np.zeros(3, "float32")),
    }
    json_path = str(tmp_path / "mlp-symbol.json")
    params_path = str(tmp_path / "mlp-0000.params")
    o.save(json_path)
    nd.save(params_path, {"arg:" + k: v for k, v in params.items()})
    return o, params, json_path, params_path


@pytest.mark.slow
def test_cpp_predictor_matches_python(tmp_path, predict_lib):
    s, params, json_path, params_path = _export_mlp(tmp_path)

    # reference forward in-process
    data = (0.1 * np.arange(12, dtype=np.float32) - 0.5).reshape(2, 6)
    ex = s.bind(ctx=mx.cpu(), args=dict(params, data=nd.array(data)))
    ref = ex.forward()[0].asnumpy()

    # compile the standalone C++ client
    src = tmp_path / "main.cc"
    src.write_text(CPP_MAIN)
    binary = str(tmp_path / "predict_demo")
    inc = subprocess.run(["python3-config", "--includes"],
                         capture_output=True, text=True).stdout.split()
    r = subprocess.run(
        ["g++", "-std=c++14", str(src), "-o", binary,
         "-I", os.path.join(NATIVE, "include"),
         "-L", os.path.join(NATIVE, "lib"), "-lmxnet_tpu_predict",
         "-Wl,-rpath," + os.path.join(NATIVE, "lib")] + inc,
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.environ.get("PYTHONPATH", "") + ":" + REPO)
    run = subprocess.run([binary, json_path, params_path],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert run.returncode == 0, run.stdout + run.stderr
    lines = run.stdout.strip().splitlines()
    assert lines[0].strip() == "shape: 2 3"
    got = np.array([float(v) for v in lines[1].split()],
                   dtype=np.float32).reshape(2, 3)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
