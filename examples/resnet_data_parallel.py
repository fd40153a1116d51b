"""Data-parallel ResNet training over a device mesh — the reference's
8-GPU KVStore-`nccl` image-classification config (SURVEY.md §2.4 row 1),
compiled into one sharded XLA step.

    JAX_PLATFORMS=cpu python examples/resnet_data_parallel.py \
        --model resnet18_v1 --image-size 64 --iters 5

On a TPU host drop JAX_PLATFORMS to use the chip(s); bench.py runs the
resnet50_v1 config this script demonstrates.
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.parallel import DataParallelTrainer, make_mesh


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet18_v1")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--amp", action="store_true",
                   help="bf16 compute with f32 master params")
    args = p.parse_args()

    ctx = mx.tpu()   # device 0 of the default backend
    net = getattr(vision, args.model)()
    net.initialize(mx.initializer.Xavier(), ctx=ctx)

    mesh = make_mesh({"dp": -1})   # all visible devices
    print("mesh:", dict(mesh.shape))
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh,
        amp=args.amp)

    rng = np.random.RandomState(0)
    S = args.image_size
    data = nd.array(rng.randn(args.batch_size, 3, S, S).astype(
        "float32"), ctx=ctx)
    label = nd.array(rng.randint(0, 1000, (args.batch_size,)), ctx=ctx)

    # device-side loop: all iters in ONE jitted lax.scan dispatch, with
    # trainer.sync() performing a hard sync (docs/perf.md "Methodology")
    losses = trainer.run_steps(data, label, steps=args.iters)  # compile
    trainer.sync()
    t0 = time.time()
    losses = trainer.run_steps(data, label, steps=args.iters)
    trainer.sync()
    dt = time.time() - t0
    print("loss %.4f  |  %.1f images/sec"
          % (float(losses[-1].asnumpy()),
             args.batch_size * args.iters / dt))
    trainer.sync_back()   # write trained params into the Gluon block

    # --- the same loop fed by the prefetch-to-device pipeline --------
    # DevicePrefetchIter decodes + stacks `super_size` batches and
    # uploads the (S, B, ...) superbatch in a background thread while
    # the device still runs the previous run_steps dispatch — the
    # production input path (docs/perf.md "End-to-end pipeline").
    from mxnet_tpu.io import DevicePrefetchIter, NDArrayIter
    n = args.batch_size * 8
    X = rng.randn(n, 3, S, S).astype("float32")
    Y = rng.randint(0, 1000, (n,))
    pf = DevicePrefetchIter(NDArrayIter(X, Y,
                                        batch_size=args.batch_size),
                            super_size=4, ctx=ctx)
    for epoch in range(2):
        for batch in pf:
            losses = trainer.run_steps(batch.data[0], batch.label[0])
        if epoch == 0:
            pf.reset()     # between epochs only — a final reset would
                           # re-arm the worker for a wasted decode+H2D
    trainer.sync()
    trainer.sync_back()    # the block now holds the trained params
    print("prefetch-pipeline loss %.4f" % float(losses[-1].asnumpy()))
    pf.close()


if __name__ == "__main__":
    main()
