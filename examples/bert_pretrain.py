"""BERT-style MLM pretraining over a tp x dp (x sp) mesh — the
BASELINE.json BERT config, on synthetic token streams.

    JAX_PLATFORMS=cpu python examples/bert_pretrain.py --dp 4 --tp 2
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel size (0 = all devices)")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel size (ring attention)")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--size", choices=["tiny", "base"], default="tiny")
    args = p.parse_args()

    import jax

    # CPU demo runs: provision enough virtual devices for the requested
    # mesh before the backend initializes (same trick as tests/conftest)
    need = max(1, args.dp) * args.tp * args.sp
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_num_cpu_devices", need)

    import jax.numpy as jnp
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.models import transformer as T

    axes = {}
    if args.dp != 1:
        axes["dp"] = args.dp if args.dp > 0 else -1
    if args.sp > 1:
        axes["sp"] = args.sp
    if args.tp > 1:
        axes["tp"] = args.tp
    mesh = make_mesh(axes or {"dp": -1})
    print("mesh:", dict(mesh.shape))

    mk = T.bert_base if args.size == "base" else T.bert_tiny
    cfg = mk(max_len=args.seq_len, dropout=0.1, remat=True,
             use_flash=True,
             seq_parallel="ring" if args.sp > 1 else None)
    init_state, step = T.make_train_step(cfg, mesh=mesh,
                                         learning_rate=1e-4)
    state = init_state(jax.random.PRNGKey(0))

    rng = np.random.RandomState(0)
    B, L = args.batch_size, args.seq_len
    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, (B, L)),
                         jnp.int32)
    # mask 15% of positions for MLM
    mlm = rng.rand(B, L) < 0.15
    labels = jnp.asarray(np.where(mlm, np.asarray(tokens), -100),
                         jnp.int32)
    batch = {"tokens": tokens, "labels": labels,
             "mask": jnp.ones((B, L), bool)}

    # two warmups: the first compiles; the second absorbs the recompile
    # for the GSPMD-refined state shardings the first step emits (the
    # scanned loop below folds its own per-step keys from keys 2/3)
    for w in range(2):
        state, loss = step(state, batch, jax.random.PRNGKey(w))
        float(loss)

    # timed: device-side loop (one lax.scan dispatch for all steps) with
    # a hard sync on the STATE (the loss buffer alone can materialize
    # before the donated-state pipeline drains) — docs/perf.md
    # "Methodology"
    def hard_sync(state):
        jax.device_get(jax.tree_util.tree_leaves(state)[0].ravel()[:1])

    _, multi = T.make_train_step(cfg, mesh=mesh, learning_rate=1e-4,
                                 scan_steps=args.steps)
    # two warm calls again: compile, then absorb any sharding-refinement
    # recompile of the scanned program
    for w in (2, 3):
        state, losses = multi(state, batch, jax.random.PRNGKey(w))
        hard_sync(state)
    t0 = time.time()
    state, losses = multi(state, batch, jax.random.PRNGKey(4))
    hard_sync(state)
    dt = time.time() - t0
    loss = jax.device_get(losses[-1])
    toks = B * L * args.steps / dt
    print("loss %.4f  |  %.0f tokens/sec" % (float(loss), toks))


if __name__ == "__main__":
    main()
