"""Self-checking multi-process GSPMD worker (round-3 verdict #4).

The PS tier (dist_sync_kvstore.py) covers the *parity* path; this
script covers the multi-chip *throughput* path: ``jax.distributed``
over the launch.py DMLC env contract, 2 processes x 4 CPU devices each,
one global dp=8 mesh whose collectives cross the process boundary
(gloo — the CPU stand-in for ICI/DCN; SURVEY.md §4.5 "real transport,
fake topology").

Launched as::

    tools/launch.py -n 2 -s 0 --launcher local \
        python tests/dist_gspmd_worker.py --expect-dp L1 --expect-tf L2

and asserts the final losses match the single-process 8-device run
(the --expect values, computed by the pytest driver).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _setup_process():
    """Worker-process initialization (NOT run when pytest imports this
    module for the single-process reference): 4 CPU devices per
    process, then jax.distributed via the DMLC env."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    from mxnet_tpu.parallel import multihost
    multihost.initialize()       # DMLC_* env → jax.distributed


def run_dp_trainer():
    """DataParallelTrainer (gluon path) on the global mesh."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import multihost
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer

    mx.random.seed(7)
    np.random.seed(7)
    net = nn.Dense(4, use_bias=True)
    net.initialize(mx.initializer.Xavier())
    mesh = multihost.global_mesh({"dp": -1})
    assert mesh.size == 8, mesh
    tr = DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                             {"learning_rate": 0.05}, mesh=mesh)
    rng = np.random.RandomState(3)
    X = rng.randn(32, 16).astype("float32")
    Y = rng.randn(32, 4).astype("float32")
    loss = None
    for _ in range(6):
        loss = tr.step(X, Y)        # numpy in → global sharded batch
    tr.sync()
    return float(loss.asnumpy())


def run_flagship():
    """Flagship transformer train step, dp sharded over both hosts."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.models import transformer as T

    mesh = make_mesh({"dp": 8})
    cfg = T.bert_tiny(use_flash=False, remat=False, dropout=0.0)
    init_state, step = T.make_train_step(cfg, mesh=mesh,
                                         learning_rate=1e-3)
    state = init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 128), 0,
                                cfg.vocab_size)
    labels = jnp.where(jnp.arange(128)[None] % 5 == 0, tokens, -100)
    batch = {"tokens": tokens, "labels": labels,
             "mask": jnp.ones((8, 128), dtype=bool)}
    loss = None
    for i in range(4):
        state, loss = step(state, batch, jax.random.fold_in(rng, i))
    return float(loss)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--expect-dp", type=float, default=None)
    ap.add_argument("--expect-tf", type=float, default=None)
    args = ap.parse_args()

    _setup_process()
    import jax
    from mxnet_tpu.parallel import multihost

    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    assert len(jax.local_devices()) == 4

    ldp = run_dp_trainer()
    ltf = run_flagship()
    rank = multihost.rank()
    print("rank %d: dp_loss=%.6f flagship_loss=%.6f"
          % (rank, ldp, ltf), flush=True)
    if args.expect_dp is not None:
        assert abs(ldp - args.expect_dp) < 1e-3 + abs(args.expect_dp) * 1e-3, \
            (ldp, args.expect_dp)
    if args.expect_tf is not None:
        assert abs(ltf - args.expect_tf) < 1e-3 + abs(args.expect_tf) * 1e-3, \
            (ltf, args.expect_tf)
    print("rank %d: GSPMD multi-process OK" % rank, flush=True)


if __name__ == "__main__":
    main()
