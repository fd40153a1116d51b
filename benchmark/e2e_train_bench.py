"""End-to-end input-pipeline -> device training benchmark (round-4
verdict item #5: SURVEY §7 hard-part 4 was only ever measured as two
disconnected halves — the native loader alone and synthetic-data train
steps alone).

Couples the native ``ImageRecordIter`` (C++ threaded JPEG decode) to
``DataParallelTrainer`` with the TPU-native pipeline shape:

    host decode S batches -> stack (superbatch) -> ONE H2D upload
    -> ONE ``run_steps`` dispatch scanning S train steps on device,
    while the host already decodes the NEXT superbatch (async dispatch
    = the double-buffering; the reference's PrefetchingIter + engine
    dependency overlap, compiled).

Per-batch dispatch (``trainer.step``) pays the per-dispatch host cost
every batch (not measured on the current machine — ROADMAP A2); the
superbatch scan amortizes it S ways (one dispatch per S steps).  Params MUST be initialized on the TPU
context — a trivial (1-device) mesh skips sharding commits by design,
so CPU-resident params silently train on the host CPU (measured
25 s/step for resnet18; the bug this bench caught in round 4).  The
bench reports each term so the pipeline efficiency (serial vs
overlapped) is readable independently of the host-to-device link and
the host's decode budget:

  loader   host decode+augment+batch only (img/s)
  upload   H2D of one superbatch
  device   run_steps on a resident superbatch (per-step, differenced)
  serial   decode -> upload -> run -> sync, strictly alternating
  overlap  decode of superbatch k+1 under the async run of k

    python benchmark/e2e_train_bench.py [--n 1024] [--batch 64]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--super", type=int, default=8,
                    dest="super_", help="batches per device dispatch")
    ap.add_argument("--hw", type=int, default=112)
    ap.add_argument("--epochs", type=int, default=2)
    args = ap.parse_args()

    if args.n < args.batch * args.super_:
        ap.error("--n must be >= batch*super (%d)"
                 % (args.batch * args.super_))

    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.io import ImageRecordIter
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    from benchmark.data_bench import make_rec

    import atexit
    import shutil
    tmp = tempfile.mkdtemp(prefix="e2e_bench_")
    atexit.register(shutil.rmtree, tmp, True)
    rec, idx = os.path.join(tmp, "d.rec"), os.path.join(tmp, "d.idx")
    print(json.dumps({"stage": "packing", "n": args.n}), flush=True)
    make_rec(rec, idx, args.n, hw=256)

    it = ImageRecordIter(
        path_imgrec=rec, path_imgidx=idx,
        data_shape=(3, args.hw, args.hw), batch_size=args.batch,
        shuffle=True, rand_crop=True, rand_mirror=True, resize=128,
        preprocess_threads=max(1, (os.cpu_count() or 1)),
        mean_r=123.68, mean_g=116.78, mean_b=103.94,
        std_r=58.4, std_g=57.12, std_b=57.38, ctx=mx.cpu())
    S = args.super_
    nsuper = args.n // (args.batch * S)
    imgs_per_super = args.batch * S

    def decode_super():
        """S decoded batches stacked on HOST -> (S, B, C, H, W)."""
        ds, ls = [], []
        for _ in range(S):
            try:
                b = it.next()
            except StopIteration:
                it.reset()
                b = it.next()
            ds.append(b.data[0].asnumpy())
            ls.append(b.label[0].asnumpy())
        return np.stack(ds), np.stack(ls)

    # -- loader only ---------------------------------------------------
    d_host, l_host = decode_super()            # warm threads/caches
    t0 = time.perf_counter()
    for _ in range(nsuper):
        d_host, l_host = decode_super()
    t_loader = (time.perf_counter() - t0) / nsuper
    print(json.dumps({"stage": "loader",
                      "ms_per_super": round(t_loader * 1e3, 1),
                      "img_s": round(imgs_per_super / t_loader, 1)}),
          flush=True)

    # -- upload only ---------------------------------------------------
    mb = d_host.nbytes / 1e6
    t0 = time.perf_counter()
    for _ in range(3):
        dd = nd.array(d_host, ctx=mx.tpu())
        ll = nd.array(l_host, ctx=mx.tpu())
        dd.wait_to_read()
    t_upload = (time.perf_counter() - t0) / 3
    print(json.dumps({"stage": "upload", "mb": round(mb, 1),
                      "ms_per_super": round(t_upload * 1e3, 1),
                      "mb_s": round(mb / t_upload, 1)}), flush=True)

    # -- model ---------------------------------------------------------
    from mxnet_tpu.gluon.model_zoo import vision as models
    net = models.resnet18_v1(classes=10)
    net.initialize(mx.initializer.Xavier(), ctx=mx.tpu())
    net(nd.array(d_host[0][:2], ctx=mx.tpu()))   # materialize shapes
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "sgd", {"learning_rate": 0.05,
                                     "momentum": 0.9},
                             mesh=make_mesh({"dp": len(jax.devices())}))
    losses = tr.run_steps(dd, ll)              # build + compile
    float(losses.asnumpy()[-1])

    # -- device only (resident superbatch, differenced) ----------------
    def run_k(k):
        t0 = time.perf_counter()
        for _ in range(k):
            losses = tr.run_steps(dd, ll)
        float(losses.asnumpy()[-1])
        return time.perf_counter() - t0
    run_k(1)
    t1, t4 = run_k(1), run_k(4)
    t_device = max((t4 - t1) / 3, 1e-6)
    print(json.dumps({"stage": "device",
                      "ms_per_super": round(t_device * 1e3, 1),
                      "img_s": round(imgs_per_super / t_device, 1)}),
          flush=True)

    # -- serial e2e ----------------------------------------------------
    t0 = time.perf_counter()
    for _ in range(args.epochs * nsuper):
        d_host, l_host = decode_super()
        dd = nd.array(d_host, ctx=mx.tpu())
        ll = nd.array(l_host, ctx=mx.tpu())
        losses = tr.run_steps(dd, ll)
        float(losses.asnumpy()[-1])            # strict alternation
    t_serial = (time.perf_counter() - t0) / (args.epochs * nsuper)
    print(json.dumps({"stage": "serial",
                      "ms_per_super": round(t_serial * 1e3, 1),
                      "img_s": round(imgs_per_super / t_serial, 1)}),
          flush=True)

    # -- overlapped e2e ------------------------------------------------
    t0 = time.perf_counter()
    d_host, l_host = decode_super()
    losses = None
    for i in range(args.epochs * nsuper):
        dd = nd.array(d_host, ctx=mx.tpu())
        ll = nd.array(l_host, ctx=mx.tpu())
        losses = tr.run_steps(dd, ll)          # async dispatch
        if i < args.epochs * nsuper - 1:
            d_host, l_host = decode_super()    # decode under the run
    float(losses.asnumpy()[-1])
    t_overlap = (time.perf_counter() - t0) / (args.epochs * nsuper)
    hidden = t_serial - t_overlap
    print(json.dumps({"stage": "overlap",
                      "ms_per_super": round(t_overlap * 1e3, 1),
                      "img_s": round(imgs_per_super / t_overlap, 1),
                      "hidden_ms": round(hidden * 1e3, 1),
                      "decode_hidden_frac":
                          round(min(1.0, max(0.0, hidden / t_loader)),
                                2)}), flush=True)

    # -- the same loop THROUGH the public API (round-5 item #3) --------
    # DevicePrefetchIter owns decode + superbatch + upload in its
    # worker thread; the consumer loop is just run_steps per super.
    from mxnet_tpu.io import DevicePrefetchIter
    it.reset()                  # earlier stages left the cursor mid-epoch
    pf = DevicePrefetchIter(it, super_size=S, ctx=mx.tpu())
    b0 = pf.next()                              # warm the pipeline
    losses = tr.run_steps(b0.data[0], b0.label[0])
    float(losses.asnumpy()[-1])
    t0 = time.perf_counter()
    done = 0
    while done < args.epochs * nsuper:
        try:
            b = pf.next()
        except StopIteration:
            pf.reset()
            continue
        losses = tr.run_steps(b.data[0], b.label[0])
        done += 1
    float(losses.asnumpy()[-1])
    t_api = (time.perf_counter() - t0) / (args.epochs * nsuper)
    pf.close()
    print(json.dumps({"stage": "api(DevicePrefetchIter)",
                      "ms_per_super": round(t_api * 1e3, 1),
                      "img_s": round(imgs_per_super / t_api, 1),
                      "vs_handrolled":
                          round(t_overlap / t_api, 3)}), flush=True)


if __name__ == "__main__":
    main()
