"""Training scale-out benchmark (round 19, ROADMAP item 5): the
DP/FSDP pretrain step through the serving mesh, the ICI-allreduce
KVStore as the gradient-sync substrate, and the exactness protocols the
`bert_pretrain_ex_s` gate hard-fails on.

Sections (all rows JSON; ``--json`` writes the MULTICHIP_r10 file):

  exactness   dp=2 f32 BERT loss trajectory through the ICI-allreduce
              KVStore (per-device microbatch grads of the SAME jitted
              ``mlm_loss`` program, one collective per sync) must be
              BIT-identical to single-device accumulation of the same
              microbatches.  HARD-FAILS (RuntimeError) on any byte.
  fsdp_bytes  params + optimizer moments of ``make_train_step(
              fsdp=True)`` measured from live ``addressable_shards``:
              per-device bytes must be EXACTLY total/dp (the scalar
              adamw step count is the one replicated leaf).  HARD-FAILS.
  dp_sweep    weak-scaling curve dp={1,2,4,8} on the virtual mesh
              (per-device batch fixed): examples/s of the ONE jitted
              train step per dp (dp=1 = the unsharded step, dp>1 =
              FSDP), plus parallel efficiency vs dp=1.
  bucket      bucketed (one flat collective per <=bucket_bytes) vs
              unbucketed (one per key) gradient sync of a full BERT
              grad set: collective counts, sync wall time, and the
              bitwise-equality assertion (grouping is a dispatch-count
              lever, not a numeric one).
  bucket_overlap  (round 21, ``--bucket-overlap``; ``--json`` writes
              the MULTICHIP_r11 file) the layer-bucketed
              reduce-scatter overlap mode of the FSDP step
              (``make_train_step(bucket_overlap=True)`` — per-layer
              grad shards pinned INSIDE the backward scan, one
              reduce-scatter bucket per layer) vs the "fused"
              post-scan reduction, at dp={1,2,4,8}: ex/s per mode,
              with the run HARD-FAILING unless both modes' loss
              trajectories and final params are BITWISE identical at
              every dp (bucketing is a scheduling lever, not a
              numeric one).  dp=1 is the unsharded baseline row.

CPU-pricing caveat (same as the round-14 tp rows): the 8-device mesh
here is ``jax_num_cpu_devices`` over ONE host CPU —
the dp>1 ex/s prices emulated collectives and core-sharing, not ICI,
so the scaling curve's SHAPE is not a chip prediction; the exactness
and byte-accounting claims are placement facts and transfer.

    python benchmark/train_scale_bench.py --all [--preset mid]
        [--json MULTICHIP_r10.json]

``run_gate_pretrain`` feeds ``perf_regression.py bert_pretrain_ex_s``:
it runs the two hard-fail protocols first and only then reports ex/s,
with the config sha + seed carried on the row (reproducibility, the
goodput-gate convention).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PRESETS = {
    # name: (cfg kwargs beyond bert_tiny/bert_base, per-device batch,
    #        seq len, timed steps)
    "quick": (dict(), 4, 32, 3),
    "mid": (dict(d_model=128, d_ff=256, vocab_size=2048, max_len=64),
            8, 64, 5),
    # chip preset: bert_base shapes (the bert_base_tok_s config), only
    # sensible on a real multi-chip backend
    "full": (dict(), 16, 512, 20),
}


def _cfg(preset):
    from mxnet_tpu.models import transformer as T
    kw, B, T_len, steps = PRESETS[preset]
    base = dict(use_flash=False, remat=False, dropout=0.0)
    base.update(kw)
    cfg = (T.bert_base(**base) if preset == "full"
           else T.bert_tiny(**base))
    return cfg, B, T_len, steps


def _cfg_sha(cfg, B, T_len, steps, seed):
    """Provenance fingerprint: the exact (config, shapes, schedule)
    the row was measured on — the trace-sha convention."""
    blob = json.dumps([repr(cfg), B, T_len, steps, seed],
                      sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def _batch(cfg, B, T_len, seed):
    import jax
    import jax.numpy as jnp
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, T_len),
                                0, cfg.vocab_size)
    labels = jnp.where(jnp.arange(T_len)[None] % 5 == 0, tokens, -100)
    return {"tokens": tokens, "labels": labels,
            "mask": jnp.ones((B, T_len), bool)}


def _drain(tree):
    import jax
    jax.block_until_ready(tree)
    jax.device_get(jax.tree_util.tree_leaves(tree)[0].ravel()[:1])


# ---------------------------------------------------------------------------
# exactness: dp=2 KVStore sync bit-identical to dp=1 accumulation
# ---------------------------------------------------------------------------

def run_exactness(preset="mid", seed=0, steps=3):
    """dp=2 f32 loss trajectory through the ICI-allreduce KVStore vs
    single-device accumulation of the same microbatches: every loss
    value AND every final param byte must match exactly (the dp=2
    collective is one order-free f32 add per element).  Raises on the
    first differing byte."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import kv as mxkv
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.ndarray.ndarray import NDArray

    import dataclasses
    cfg, B, T_len, _ = _cfg(preset)
    cfg = dataclasses.replace(cfg, dtype="float32",
                              param_dtype="float32")
    batch = _batch(cfg, 2 * B, T_len, seed)
    devs = jax.devices()[:2]
    if len(devs) < 2:
        raise RuntimeError("exactness protocol needs >= 2 devices "
                           "(virtual CPU mesh ok)")
    key = jax.random.PRNGKey(seed + 1)
    gfn = jax.jit(jax.value_and_grad(
        lambda p, b, r: T.mlm_loss(p, b, r, cfg)))
    upd = jax.jit(lambda p, g, lr: jax.tree_util.tree_map(
        lambda pv, gv: pv - lr * gv, p, g))

    def halves(dev_pair):
        return [jax.tree_util.tree_map(
            lambda x: jax.device_put(x[sl], d), batch)
            for sl, d in zip((slice(0, B), slice(B, 2 * B)), dev_pair)]

    def run_kv():
        kv = mxkv.create("ici")
        params = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, devs[0]),
            T.init_params(jax.random.PRNGKey(seed), cfg))
        flat, treedef = jax.tree_util.tree_flatten(params)
        keys = list(range(len(flat)))
        for i, leaf in enumerate(flat):
            kv.init(i, NDArray(leaf) * 0)
        b0, b1 = halves(devs)
        losses = []
        for _ in range(steps):
            p1 = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, devs[1]), params)
            l0, g0 = gfn(params, b0, key)
            l1, g1 = gfn(p1, b1, key)
            f0 = jax.tree_util.tree_leaves(g0)
            f1 = jax.tree_util.tree_leaves(g1)
            kv.push(keys, [[NDArray(a), NDArray(b)]
                           for a, b in zip(f0, f1)])
            outs = []
            for i in keys:
                o = NDArray(jnp.zeros(f0[i].shape, f0[i].dtype))
                kv.pull(i, out=o)
                outs.append(jax.device_put(o._data, devs[0]))
            gsum = jax.tree_util.tree_unflatten(treedef, outs)
            params = upd(params, gsum, 1e-2)
            losses.append((float(l0), float(l1)))
        return losses, params, kv.stats()

    def run_accum():
        params = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, devs[0]),
            T.init_params(jax.random.PRNGKey(seed), cfg))
        b0, b1 = halves((devs[0], devs[0]))
        losses = []
        for _ in range(steps):
            l0, g0 = gfn(params, b0, key)
            l1, g1 = gfn(params, b1, key)
            gsum = jax.tree_util.tree_map(lambda a, b: a + b, g0, g1)
            params = upd(params, gsum, 1e-2)
            losses.append((float(l0), float(l1)))
        return losses, params

    kv_losses, kv_params, stats = run_kv()
    acc_losses, acc_params = run_accum()
    import numpy as np
    if kv_losses != acc_losses:
        raise RuntimeError(
            "bert_pretrain exactness: dp=2 ICI-synced loss trajectory "
            "diverged from dp=1 accumulation: %r vs %r"
            % (kv_losses, acc_losses))
    for a, b in zip(jax.tree_util.tree_leaves(kv_params),
                    jax.tree_util.tree_leaves(acc_params)):
        if np.asarray(a).tobytes() != np.asarray(b).tobytes():
            raise RuntimeError(
                "bert_pretrain exactness: final params differ "
                "(shape %r) between ICI sync and accumulation"
                % (a.shape,))
    return {
        "section": "train_scale", "config": "exactness_dp2",
        "preset": preset, "seed": seed, "steps": steps,
        # sha of the f32-REPLACED config actually measured, not the
        # preset's bf16-compute default
        "cfg_sha": _cfg_sha(cfg, B, T_len, steps, seed),
        "dp2_bit_identical": True,
        "losses": [l for pair in kv_losses for l in pair],
        "collectives": stats["collectives"],
        "reduced_bytes": stats["reduced_bytes"],
    }


# ---------------------------------------------------------------------------
# FSDP byte accounting: per-device bytes exactly / dp
# ---------------------------------------------------------------------------

def run_fsdp_bytes(preset="mid", dp=None, seed=0):
    """Params + optimizer state of the FSDP step, measured from live
    ``addressable_shards`` (the PR-9 protocol): per-device bytes must
    be EXACTLY total/dp (params) and (total - scalar count)/dp (opt).
    Raises on any deviation."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.fsdp import shard_bytes

    dp = dp or min(8, len(jax.devices()))
    cfg, B, T_len, steps = _cfg(preset)
    mesh = make_mesh({"dp": dp}, devices=list(jax.devices())[:dp])
    init_state, _ = T.make_train_step(cfg, mesh=mesh, fsdp=True)
    params, opt = init_state(jax.random.PRNGKey(seed))
    tot_p, per_p = shard_bytes(params)
    if tot_p != per_p * dp:
        raise RuntimeError(
            "fsdp bytes: per-device param bytes %d != total %d / dp=%d"
            % (per_p, tot_p, dp))
    tot_o, per_o = shard_bytes(opt)
    count_bytes = 4                     # adamw's scalar step count
    if tot_o - count_bytes != (per_o - count_bytes) * dp:
        raise RuntimeError(
            "fsdp bytes: per-device opt bytes %d (total %d) not "
            "exactly /dp=%d beyond the scalar count" % (per_o, tot_o,
                                                        dp))
    return {
        "section": "train_scale", "config": "fsdp_bytes_dp%d" % dp,
        "preset": preset, "seed": seed, "dp": dp,
        "cfg_sha": _cfg_sha(cfg, B, T_len, steps, seed),
        "param_bytes_total": tot_p, "param_bytes_per_device": per_p,
        "opt_bytes_total": tot_o, "opt_bytes_per_device": per_o,
        "div_dp_exact": True,
    }


# ---------------------------------------------------------------------------
# dp weak-scaling sweep
# ---------------------------------------------------------------------------

def _measure_step(cfg, mesh, B, T_len, steps, seed, fsdp,
                  bucket_overlap=False):
    import jax
    from mxnet_tpu.models import transformer as T
    init_state, step = T.make_train_step(cfg, mesh=mesh, fsdp=fsdp,
                                         bucket_overlap=bucket_overlap)
    state = init_state(jax.random.PRNGKey(seed))
    batch = _batch(cfg, B, T_len, seed)
    if mesh is not None and mesh.size > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        sb = NamedSharding(mesh, P("dp"))
        batch = {k: jax.device_put(v, sb) for k, v in batch.items()}
    k = jax.random.PRNGKey(seed + 1)
    state, _ = step(state, batch, k)    # compile + settle
    _drain(state)
    best = 1e9
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step(state, batch, k)
        _drain(state)
        best = min(best, time.perf_counter() - t0)
    return B * steps / best, float(loss)


def run_dp_sweep(preset="mid", dps=(1, 2, 4, 8), seed=0):
    """Weak scaling (per-device batch fixed): ex/s of the one jitted
    train step at each dp.  dp=1 is the unsharded step; dp>1 lowers
    FSDP through the mesh.  Efficiency is vs dp=1 linear scaling —
    on the virtual CPU mesh all shards share one host, so this prices
    GSPMD overhead, not ICI (the honest caveat on every row)."""
    import jax
    from mxnet_tpu.parallel import make_mesh
    cfg, B, T_len, steps = _cfg(preset)
    rows = []
    base_ex_s = None
    for dp in dps:
        if dp > len(jax.devices()):
            continue
        mesh = make_mesh({"dp": dp}, devices=list(jax.devices())[:dp])
        ex_s, last_loss = _measure_step(cfg, mesh if dp > 1 else None,
                                        B * dp, T_len, steps, seed,
                                        fsdp=dp > 1)
        if base_ex_s is None:
            base_ex_s = ex_s
        rows.append({
            "section": "train_scale", "config": "dp%d" % dp,
            "preset": preset, "seed": seed, "dp": dp,
            "cfg_sha": _cfg_sha(cfg, B, T_len, steps, seed),
            "global_batch": B * dp, "per_device_batch": B,
            "seq_len": T_len, "ex_s": ex_s,
            "efficiency_vs_dp1": ex_s / (base_ex_s * dp),
            "virtual_mesh": len(set(
                d.platform for d in jax.devices())) == 1
                and jax.devices()[0].platform == "cpu",
            "last_loss": last_loss,
        })
    return rows


# ---------------------------------------------------------------------------
# bucketed vs unbucketed gradient sync
# ---------------------------------------------------------------------------

def run_bucket_ablation(preset="mid", seed=0, reps=5):
    """The measured perf lever: one flat collective per <=bucket_bytes
    bucket vs one per key, over a full BERT grad set on 2 devices.
    Reports collective counts + best-of-``reps`` sync wall time per
    mode and ASSERTS bitwise equality across modes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu import kv as mxkv
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.ndarray.ndarray import NDArray

    cfg, B, T_len, _ = _cfg(preset)
    devs = jax.devices()[:2]
    batch = _batch(cfg, 2 * B, T_len, seed)
    key = jax.random.PRNGKey(seed + 1)
    gfn = jax.jit(jax.value_and_grad(
        lambda p, b, r: T.mlm_loss(p, b, r, cfg)))
    params = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, devs[0]),
        T.init_params(jax.random.PRNGKey(seed), cfg))
    p1 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, devs[1]), params)
    b0 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x[:B], devs[0]), batch)
    b1 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x[B:], devs[1]), batch)
    _, g0 = gfn(params, b0, key)
    _, g1 = gfn(p1, b1, key)
    f0 = jax.tree_util.tree_leaves(g0)
    f1 = jax.tree_util.tree_leaves(g1)
    grad_bytes = sum(l.nbytes for l in f0)

    def sync(bucket_bytes):
        kv = mxkv.create("ici")
        kv.bucket_bytes = bucket_bytes
        keys = list(range(len(f0)))
        for i in keys:
            kv.init(i, NDArray(f0[i]) * 0)
        vals = [[NDArray(a), NDArray(b)] for a, b in zip(f0, f1)]
        best = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            kv.push(keys, vals)
            outs = []
            for i in keys:
                o = NDArray(jnp.zeros(f0[i].shape, f0[i].dtype))
                kv.pull(i, out=o)
                outs.append(o)
            jax.block_until_ready([o._data for o in outs])
            best = min(best, time.perf_counter() - t0)
        stats = kv.stats()
        return ([np.asarray(o._data) for o in outs], best,
                stats["collectives"] // reps)

    out_b, t_b, n_b = sync(4 << 20)
    out_u, t_u, n_u = sync(0)
    for a, b in zip(out_b, out_u):
        if a.tobytes() != b.tobytes():
            raise RuntimeError(
                "bucket ablation: bucketed and unbucketed sync "
                "disagree (shape %r)" % (a.shape,))
    return {
        "section": "train_scale", "config": "bucket_ablation",
        "preset": preset, "seed": seed,
        "cfg_sha": _cfg_sha(cfg, B, T_len, reps, seed),
        "grad_keys": len(f0), "grad_bytes": grad_bytes,
        "bucket_bytes": 4 << 20,
        "bucketed_collectives": n_b, "unbucketed_collectives": n_u,
        "bucketed_sync_ms": t_b * 1e3, "unbucketed_sync_ms": t_u * 1e3,
        "speedup": t_u / t_b,
        "bit_identical": True,
    }


# ---------------------------------------------------------------------------
# layer-bucketed reduce-scatter overlap vs fused post-scan reduction
# ---------------------------------------------------------------------------

def run_bucket_overlap_sweep(preset="mid", dps=(1, 2, 4, 8), seed=0,
                             check_steps=3):
    """Round-21 lever sweep: ``make_train_step(fsdp=True,
    bucket_overlap=True)`` — per-layer grad shards constrained INSIDE
    the backward scan, so each layer's reduce-scatter bucket is
    issuable while the previous layer's backward matmuls run — vs the
    ``"fused"`` mode (identical math, whole-tree constraint AFTER the
    scan: everything the scheduler could NOT overlap), at each dp.

    The run HARD-FAILS (RuntimeError) unless the two modes' loss
    trajectories and every final param leaf are BITWISE identical at
    every dp — bucketing reorders collective ISSUE slots, never the
    f32 reduction tree — and only then times both modes (best-of-2,
    the ``_measure_step`` idiom).  dp=1 is the unsharded non-FSDP
    baseline row (there is no reduce-scatter to bucket; it anchors
    the efficiency column).  Same virtual-mesh caveat as the dp
    sweep: off-chip ex/s prices emulated collectives + core sharing,
    not ICI, so the MODE DELTA's sign is not a chip prediction — the
    bit-identity is a placement fact and transfers."""
    import jax
    import numpy as np
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh

    cfg, B, T_len, steps = _cfg(preset)
    rows = []
    base_ex_s = None
    for dp in dps:
        if dp > len(jax.devices()):
            continue
        if dp == 1:
            ex_s, _ = _measure_step(cfg, None, B, T_len, steps, seed,
                                    fsdp=False)
            base_ex_s = ex_s
            rows.append({
                "section": "train_scale",
                "config": "bucket_overlap_dp1_baseline",
                "preset": preset, "seed": seed, "dp": 1,
                "cfg_sha": _cfg_sha(cfg, B, T_len, steps, seed),
                "global_batch": B, "per_device_batch": B,
                "seq_len": T_len, "ex_s": ex_s,
                "bucket_overlap": None,
            })
            continue
        mesh = make_mesh({"dp": dp}, devices=list(jax.devices())[:dp])
        batch = _batch(cfg, B * dp, T_len, seed)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sb = NamedSharding(mesh, P("dp"))
        batch = {k: jax.device_put(v, sb) for k, v in batch.items()}

        def trajectory(mode):
            init_state, step = T.make_train_step(
                cfg, mesh=mesh, fsdp=True, bucket_overlap=mode)
            state = init_state(jax.random.PRNGKey(seed))
            losses = []
            for i in range(check_steps):
                state, loss = step(
                    state, batch,
                    jax.random.fold_in(jax.random.PRNGKey(seed + 1), i))
                losses.append(float(loss))
            return losses, jax.device_get(state[0])

        bk_losses, bk_params = trajectory(True)
        fu_losses, fu_params = trajectory("fused")
        if bk_losses != fu_losses:
            raise RuntimeError(
                "bucket_overlap dp=%d: bucketed loss trajectory "
                "diverged from fused: %r vs %r"
                % (dp, bk_losses, fu_losses))
        for a, b in zip(jax.tree_util.tree_leaves(bk_params),
                        jax.tree_util.tree_leaves(fu_params)):
            if np.asarray(a).tobytes() != np.asarray(b).tobytes():
                raise RuntimeError(
                    "bucket_overlap dp=%d: final params differ "
                    "(shape %r) between bucketed and fused modes"
                    % (dp, a.shape))
        ex_bk, _ = _measure_step(cfg, mesh, B * dp, T_len, steps,
                                 seed, fsdp=True, bucket_overlap=True)
        ex_fu, _ = _measure_step(cfg, mesh, B * dp, T_len, steps,
                                 seed, fsdp=True,
                                 bucket_overlap="fused")
        row = {
            "section": "train_scale",
            "config": "bucket_overlap_dp%d" % dp,
            "preset": preset, "seed": seed, "dp": dp,
            "cfg_sha": _cfg_sha(cfg, B, T_len, steps, seed),
            "global_batch": B * dp, "per_device_batch": B,
            "seq_len": T_len,
            "ex_s_bucketed": ex_bk, "ex_s_fused": ex_fu,
            "bucketed_vs_fused": ex_bk / ex_fu,
            "check_steps": check_steps,
            "bit_identical_vs_fused": True,
            "virtual_mesh": len(set(
                d.platform for d in jax.devices())) == 1
                and jax.devices()[0].platform == "cpu",
        }
        if base_ex_s is not None:
            row["efficiency_vs_dp1"] = ex_bk / (base_ex_s * dp)
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def run_gate_pretrain(preset="full", seed=0):
    """`bert_pretrain_ex_s` feeder: HARD-FAILS unless (1) the dp=2 f32
    loss trajectory through the ICI store is bit-identical to dp=1
    accumulation and (2) the FSDP per-device param+opt bytes are
    exactly /dp — only then measures and reports examples/s of the
    FSDP step at the largest available dp."""
    import jax
    dp = min(8, len(jax.devices()))
    if dp < 2:
        raise RuntimeError(
            "bert_pretrain gate needs >= 2 devices (virtual mesh ok: "
            "jax.config.update('jax_num_cpu_devices', 8))")
    ex_row = run_exactness("mid" if preset == "full" else preset,
                           seed=seed)
    by_row = run_fsdp_bytes(preset, dp=dp, seed=seed)
    from mxnet_tpu.parallel import make_mesh
    cfg, B, T_len, steps = _cfg(preset)
    mesh = make_mesh({"dp": dp}, devices=list(jax.devices())[:dp])
    ex_s, last_loss = _measure_step(cfg, mesh, B * dp, T_len, steps,
                                    seed, fsdp=True)
    return {
        "section": "train_scale", "config": "gate_dp%d" % dp,
        "preset": preset, "seed": seed, "dp": dp,
        "cfg_sha": _cfg_sha(cfg, B, T_len, steps, seed),
        "global_batch": B * dp, "seq_len": T_len,
        "ex_s": ex_s, "last_loss": last_loss,
        "dp2_bit_identical": ex_row["dp2_bit_identical"],
        "fsdp_div_dp_exact": by_row["div_dp_exact"],
        "param_bytes_per_device": by_row["param_bytes_per_device"],
        "opt_bytes_per_device": by_row["opt_bytes_per_device"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="mid",
                    choices=sorted(PRESETS))
    ap.add_argument("--dp-sweep", action="store_true")
    ap.add_argument("--bucket-ablation", action="store_true")
    ap.add_argument("--bucket-overlap", action="store_true",
                    help="round-21 sweep: layer-bucketed "
                         "reduce-scatter overlap vs fused post-scan "
                         "reduction at dp={1,2,4,8} (bitwise "
                         "hard-gated; --json writes MULTICHIP_r11)")
    ap.add_argument("--exactness", action="store_true")
    ap.add_argument("--fsdp-bytes", action="store_true")
    ap.add_argument("--gate", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    # request the virtual CPU mesh BEFORE any backend initializes (the
    # conftest / serve_bench --tp mechanism); the CPU backend is not
    # the default where a real chip is visible
    import jax
    jax.config.update("jax_num_cpu_devices", 8)

    rows = []
    if args.all or args.exactness:
        r = run_exactness(args.preset, seed=args.seed)
        rows.append(r)
        print(json.dumps(r), flush=True)
        print("exactness: dp=2 ICI-synced loss trajectory bit-"
              "identical to dp=1 accumulation over %d steps "
              "(%d collectives, %d B reduced)"
              % (r["steps"], r["collectives"], r["reduced_bytes"]),
              flush=True)
    if args.all or args.fsdp_bytes:
        import jax
        for dp in (2, 4, 8):
            if dp > len(jax.devices()):
                continue
            r = run_fsdp_bytes(args.preset, dp=dp, seed=args.seed)
            rows.append(r)
            print(json.dumps(r), flush=True)
            print("fsdp bytes dp=%d: params %d B -> %d B/device, opt "
                  "%d B -> %d B/device (exactly /dp beyond the "
                  "scalar count)"
                  % (dp, r["param_bytes_total"],
                     r["param_bytes_per_device"], r["opt_bytes_total"],
                     r["opt_bytes_per_device"]), flush=True)
    if args.all or args.dp_sweep:
        sweep = run_dp_sweep(args.preset, seed=args.seed)
        rows.extend(sweep)
        for r in sweep:
            print(json.dumps(r), flush=True)
        print("dp sweep (%s, weak scaling, per-device batch %d): "
              % (args.preset, sweep[0]["per_device_batch"])
              + ", ".join("dp=%d %.1f ex/s (eff %.2f)"
                          % (r["dp"], r["ex_s"],
                             r["efficiency_vs_dp1"]) for r in sweep)
              + (" — VIRTUAL CPU mesh: shards share one host; this "
                 "prices GSPMD overhead, not ICI"
                 if sweep[-1]["virtual_mesh"] else ""), flush=True)
    if args.all or args.bucket_ablation:
        r = run_bucket_ablation(args.preset, seed=args.seed)
        rows.append(r)
        print(json.dumps(r), flush=True)
        print("bucket ablation: %d grad keys (%d B) sync in %d "
              "collective(s) bucketed vs %d unbucketed; %.2f ms vs "
              "%.2f ms (%.2fx), bit-identical"
              % (r["grad_keys"], r["grad_bytes"],
                 r["bucketed_collectives"], r["unbucketed_collectives"],
                 r["bucketed_sync_ms"], r["unbucketed_sync_ms"],
                 r["speedup"]), flush=True)
    if args.all or args.bucket_overlap:
        sweep = run_bucket_overlap_sweep(args.preset, seed=args.seed)
        rows.extend(sweep)
        for r in sweep:
            print(json.dumps(r), flush=True)
        over = [r for r in sweep if r["dp"] > 1]
        print("bucket-overlap sweep (%s): " % args.preset
              + ", ".join("dp=%d bucketed %.1f ex/s vs fused %.1f "
                          "(%.2fx)"
                          % (r["dp"], r["ex_s_bucketed"],
                             r["ex_s_fused"], r["bucketed_vs_fused"])
                          for r in over)
              + "; bitwise-identical at every dp"
              + (" — VIRTUAL CPU mesh: shards share one host, so the "
                 "mode delta prices emulated collectives, not the "
                 "ICI overlap the mode exists for"
                 if over and over[-1]["virtual_mesh"] else ""),
              flush=True)
    if args.gate:
        r = run_gate_pretrain(args.preset, seed=args.seed)
        rows.append(r)
        print(json.dumps(r), flush=True)
        print("gate: %.1f ex/s at dp=%d (global batch %d, seq %d); "
              "exactness + /dp protocols passed"
              % (r["ex_s"], r["dp"], r["global_batch"], r["seq_len"]),
              flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
