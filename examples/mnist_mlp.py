"""Train a Gluon MLP classifier — the reference's first-steps example
(example/image-classification MLP; SURVEY.md §7 milestone 1).

Runs on synthetic MNIST-shaped data so it needs no downloads:

    JAX_PLATFORMS=cpu python examples/mnist_mlp.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def synthetic_mnist(n, seed=0):
    """Linearly-separable 784-dim 10-class blobs (stand-in for MNIST).
    Class centers are fixed across splits; ``seed`` varies the noise."""
    centers = np.random.RandomState(1234).randn(10, 784).astype(
        "float32") * 2
    rng = np.random.RandomState(seed)
    y = rng.randint(0, 10, n)
    x = centers[y] + rng.randn(n, 784).astype("float32")
    return x, y.astype("float32")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.05)
    args = p.parse_args()

    ctx = mx.tpu()   # device 0 of the default backend
    print("context:", ctx)

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(128, activation="relu"),
                nn.Dense(64, activation="relu"),
                nn.Dense(10))
    net.initialize(mx.initializer.Xavier(), ctx=ctx)
    net.hybridize()

    X, Y = synthetic_mnist(4096)
    Xv, Yv = synthetic_mnist(512, seed=1)
    train_iter = mx.io.NDArrayIter(X, Y, args.batch_size, shuffle=True)

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()

    for epoch in range(args.epochs):
        metric.reset()
        train_iter.reset()
        for batch in train_iter:
            data = batch.data[0].as_in_context(ctx)
            label = batch.label[0].as_in_context(ctx)
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])
            metric.update(label, out)
        name, acc = metric.get()
        print("epoch %d train %s=%.4f" % (epoch, name, acc))

    out = net(nd.array(Xv, ctx=ctx))
    val = mx.metric.Accuracy()
    val.update(nd.array(Yv, ctx=ctx), out)
    print("validation %s=%.4f" % val.get())


if __name__ == "__main__":
    main()
