"""Training-capable C++ frontend over the C train ABI (round-2 verdict
item #9; reference: cpp-package/include/mxnet-cpp/ — SURVEY.md §2.3
"C++ frontend" row): a standalone C++ program trains an MNIST-style MLP
through MXTrainOpInvoke/autograd/optimizer and its loss trajectory must
match the identical training loop run in Python."""
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu import optimizer as opt_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")

N, D, H, C = 64, 16, 16, 4
EPOCHS = 8
LR = 0.5

CPP_MAIN = r"""
#include <cstdio>
#include <cstdlib>
#include <fstream>
#include <vector>
#include "mxnet_tpu/cpp/train.hpp"

namespace mxcpp = mxnet_tpu::cpp;

static std::vector<float> ReadFloats(std::ifstream& f, size_t n) {
  std::vector<float> v(n);
  f.read(reinterpret_cast<char*>(v.data()), n * sizeof(float));
  return v;
}

int main(int argc, char** argv) {
  const int N = 64, D = 16, H = 16, C = 4, EPOCHS = 8;
  std::ifstream f(argv[1], std::ios::binary);
  auto X = ReadFloats(f, N * D);
  auto Y = ReadFloats(f, N);
  auto W1 = ReadFloats(f, H * D);
  auto B1 = ReadFloats(f, H);
  auto W2 = ReadFloats(f, C * H);
  auto B2 = ReadFloats(f, C);

  mxcpp::NDArray x({N, D}, X), y({N}, Y);
  mxcpp::NDArray w1({H, D}, W1), b1({H}, B1);
  mxcpp::NDArray w2({C, H}, W2), b2({C}, B2);
  w1.AttachGrad();
  b1.AttachGrad();
  w2.AttachGrad();
  b2.AttachGrad();

  mxcpp::Optimizer sgd("sgd", "{\"learning_rate\": 0.5}");

  for (int e = 0; e < EPOCHS; ++e) {
    mxcpp::Autograd::RecordStart();
    auto h = mxcpp::Operator("FullyConnected")
                 .SetAttr("num_hidden", H)
                 .Invoke({x, w1, b1});
    auto a = mxcpp::Operator("Activation")
                 .SetAttr("act_type", "relu")
                 .Invoke({h});
    auto o = mxcpp::Operator("FullyConnected")
                 .SetAttr("num_hidden", C)
                 .Invoke({a, w2, b2});
    auto lp = mxcpp::Operator("log_softmax").Invoke({o});
    auto picked = mxcpp::Operator("pick").Invoke({lp, y});
    auto mean = mxcpp::Operator("mean").Invoke({picked});
    auto loss = mxcpp::Operator("negative").Invoke({mean});
    mxcpp::Autograd::RecordStop();
    loss.Backward();
    printf("loss %.6f\n", loss.Scalar());
    mxcpp::NDArray* params[4] = {&w1, &b1, &w2, &b2};
    for (int i = 0; i < 4; ++i) {
      auto g = params[i]->Grad();
      sgd.Update(i, params[i], g);
      g.Free();
    }
    for (mxcpp::NDArray* t : {&h, &a, &o, &lp, &picked, &mean, &loss}) {
      t->Free();
    }
  }
  return 0;
}
"""


def _make_data():
    rng = np.random.RandomState(42)
    X = rng.randn(N, D).astype("float32")
    wt = rng.randn(D, C).astype("float32")
    Y = (X @ wt).argmax(axis=1).astype("float32")
    W1 = (rng.randn(H, D) * 0.3).astype("float32")
    B1 = np.zeros(H, "float32")
    W2 = (rng.randn(C, H) * 0.3).astype("float32")
    B2 = np.zeros(C, "float32")
    return X, Y, W1, B1, W2, B2


def _python_trajectory():
    X, Y, W1, B1, W2, B2 = _make_data()
    x, y = nd.array(X), nd.array(Y)
    params = [nd.array(a) for a in (W1, B1, W2, B2)]
    for p in params:
        p.attach_grad()
    updater = opt_mod.get_updater(opt_mod.create("sgd",
                                                 learning_rate=LR))
    losses = []
    for _ in range(EPOCHS):
        with autograd.record():
            h = nd.FullyConnected(x, params[0], params[1], num_hidden=H)
            a = nd.Activation(h, act_type="relu")
            o = nd.FullyConnected(a, params[2], params[3], num_hidden=C)
            loss = nd.negative(nd.mean(nd.pick(nd.log_softmax(o), y)))
        loss.backward()
        losses.append(float(loss.asnumpy()))
        for i, p in enumerate(params):
            updater(i, p.grad, p)
    return losses


@pytest.mark.slow
def test_cpp_training_matches_python(tmp_path):
    r = subprocess.run(["make", "-C", NATIVE, "train"],
                       capture_output=True, text=True, timeout=300)
    lib = os.path.join(NATIVE, "lib", "libmxnet_tpu_train.so")
    if r.returncode != 0 or not os.path.exists(lib):
        pytest.skip("train library build failed: %s" % r.stderr[-500:])

    data_file = tmp_path / "train_data.bin"
    blobs = _make_data()
    with open(data_file, "wb") as f:
        for b in blobs:
            f.write(np.ascontiguousarray(b, "<f4").tobytes())

    src = tmp_path / "train_demo.cc"
    src.write_text(CPP_MAIN)
    binary = str(tmp_path / "train_demo")
    inc = subprocess.run(["python3-config", "--includes"],
                         capture_output=True, text=True).stdout.split()
    r = subprocess.run(
        ["g++", "-std=c++14", str(src), "-o", binary,
         "-I", os.path.join(NATIVE, "include"),
         "-L", os.path.join(NATIVE, "lib"), "-lmxnet_tpu_train",
         "-Wl,-rpath," + os.path.join(NATIVE, "lib")] + inc,
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.environ.get("PYTHONPATH", "") + ":" + REPO)
    run = subprocess.run([binary, str(data_file)], capture_output=True,
                         text=True, timeout=300, env=env)
    assert run.returncode == 0, run.stdout + run.stderr
    cpp_losses = [float(l.split()[1]) for l in
                  run.stdout.strip().splitlines() if l.startswith("loss")]
    assert len(cpp_losses) == EPOCHS, run.stdout

    py_losses = _python_trajectory()
    np.testing.assert_allclose(cpp_losses, py_losses, rtol=1e-5,
                               atol=1e-6)
    # and it actually learns
    assert cpp_losses[-1] < cpp_losses[0] * 0.7
