#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the repo's main paths once, through the entry points a
user would call, at the full width of models the repo already has (depth
uncut, weights random from a seed), and checks what comes out by the repo's
own means:

* ``train_resnet50`` — ``bench.py``'s path: Gluon ``resnet50_v1`` +
  ``DataParallelTrainer`` (amp), batch 128 x 3x224x224.
* ``serve_full``     — the ``full`` serving preset through ``ServingEngine``
  ``submit()``/``run()``, both attention kernels; float32 token identity
  against ``models.gpt.generate()``.
* ``serve_schedules`` — the same seeded requests through a serial and a
  pipelined ``ServingEngine``: the schedule the engine reads from its
  platform (pipelined on a TPU) against the other one, which it is given
  by substituting that observation while it is built, the attention
  lowering held to the engine's own: identical tokens, for the ``full``
  preset as deployed and, at the benchmark configurations' rehearsal
  sizes, for Falcon-H1 (a recurrent state per slot) and LFM2-MoE (windows
  beside pages, routed experts), more requests than slots.
* ``kernels``        — the three Pallas kernels, each proven COMPILED (a
  ``tpu_custom_call`` in the compiled program) and compared on the chip
  with its plain-jnp reference.
* ``multichip``      — only when >= 4 chips are visible: data-parallel
  ResNet-50, FSDP BERT-base, tensor-parallel serving, a 4-replica cluster.

    python chip_smoke.py                 # needs a TPU; exits non-zero without
    python chip_smoke.py --rehearse-cpu  # toy sizes on CPU, kernels interpreted

Each leg prints one ``PASS``/``FAIL`` line with its wall time split into
compile and run.  A leg that raises is a ``FAIL``; any ``FAIL`` makes the
exit code non-zero.  On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

# --------------------------------------------------------------- sizes ---

# (prompt length, new tokens): prompts 16-192, 16-64 new tokens; repeated
# shapes keep the generate() oracle to five compiles
_REQUESTS = [(16, 16), (32, 32), (64, 64), (128, 16), (192, 32),
             (16, 16), (32, 32), (64, 64)]


def _serving_preset(name):
    """Model width and engine geometry of a ``benchmark/serve_bench.py``
    preset, sized the way that bench sizes its engine: ``full`` is the
    GPT-2-small-class model (12L, d768, vocab 32000), 16 slots, and the
    (353, 16, 12, 128) pool its workload's longest request gives."""
    from benchmark.serve_bench import PRESETS
    p = PRESETS[name]
    longest = max(p.prompt_lens) + max(p.out_lens)
    return dict(
        gpt=dict(vocab_size=p.vocab, max_len=p.max_len, d_model=p.d_model,
                 n_heads=p.n_heads, n_layers=p.n_layers, d_ff=p.d_ff),
        engine=dict(num_slots=p.num_slots, page_size=p.page_size,
                    prefill_chunk=p.prefill_chunk,
                    pages_per_slot=-(-longest // p.page_size)))


CHIP = dict(
    _serving_preset("full"),
    resnet=dict(model="resnet50_v1", batch=128, image=224, classes=1000,
                scan_steps=10, lr=0.05),
    requests=_REQUESTS,
    bert=dict(factory="bert_base", seq=4096, batch=1, width={}),
    # the `full` preset's 12 heads (a bf16 or int8 pool of them runs
    # the per-page grid, an f32 pool the walk), then 16 heads as the
    # benchmark's serving cell has them: the walk over a bf16 pool,
    # several page groups a row, the last row block short
    paged=[dict(T=32, H=12, dh=64, ps=16, PP=22, NP=353),
           dict(T=40, H=16, dh=64, ps=16, PP=32, NP=353)],
    # a latent (MLA) pool as the gigachat3_702b_l5_ep16 cell has it: 64
    # query heads against one 512 + 64 row a token (padded to 640
    # lanes), rows several groups of 24 pages deep, a short last block
    latent=dict(T=21, H=64, rank=512, rope=64, ps=16, PP=60, NP=353,
                scale=0.14468),
    fsdp=dict(factory="bert_base", seq=512, batch=8),
    cluster_requests=16,
)

REHEARSE = dict(
    _serving_preset("quick"),
    resnet=dict(model="resnet18_v1", batch=8, image=32, classes=10,
                scan_steps=3, lr=0.01),
    requests=[(5, 8), (3, 12), (9, 4), (5, 8)],
    # head dim 64: the smallest the flash kernels tile
    bert=dict(factory="bert_tiny", seq=256, batch=1,
              width=dict(d_model=128, n_heads=2)),
    paged=[dict(T=6, H=4, dh=64, ps=8, PP=4, NP=17),
           dict(T=19, H=8, dh=64, ps=8, PP=5, NP=17)],
    latent=dict(T=11, H=4, rank=64, rope=16, ps=16, PP=20, NP=53,
                scale=0.3),
    fsdp=dict(factory="bert_tiny", seq=64, batch=8),
    cluster_requests=8,
)

# kernel-vs-reference tolerances, as max |kernel - reference| over
# max |reference|.  f32: summation order only.  bf16/int8: the softmax
# weights round to bf16 before the V sum in both paths, at different
# points of the online recurrence (first chip run, PR 21: 1.4e-3 / 2.3e-3)
_PAGED_TOL = {"float32": 1e-5, "bfloat16": 1e-2, "int8": 1e-2}


def _resnet50_shapes():
    """Parameter shapes of ResNet-50 v1 (25.6M f32 elements, 161 tensors)
    for the grouped-optimizer kernel; built here so the kernels leg does
    not depend on the train leg."""
    shapes = [(64, 3, 7, 7), (64,), (64,)]
    cin = 64
    for blocks, mid in ((3, 64), (4, 128), (6, 256), (3, 512)):
        for b in range(blocks):
            shapes += [(mid, cin, 1, 1), (mid,), (mid,),
                       (mid, mid, 3, 3), (mid,), (mid,),
                       (4 * mid, mid, 1, 1), (4 * mid,), (4 * mid,)]
            if b == 0:
                shapes += [(4 * mid, cin, 1, 1), (4 * mid,), (4 * mid,)]
            cin = 4 * mid
    return shapes + [(1000, 2048), (1000,)]


# ------------------------------------------------------ compile clock ---

class _CompileClock:
    """Sums JAX's own durations for lowering and backend compilation (or,
    on a persistent-cache hit, retrieval) so a leg's wall time splits into
    compile and everything else — tracing, host work, device execution —
    and counts persistent-cache hits and writes.  Tracing is left out of
    "compile": JAX times nested traces inside their parents', so their sum
    double-counts."""

    def __init__(self):
        import jax.monitoring as M
        self.secs = 0.0
        self.hits = 0
        self.writes = 0
        M.register_event_duration_secs_listener(self._on_duration)
        M.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event in ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                     "/jax/core/compile/backend_compile_duration"):
            self.secs += secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


class Ctx:
    """What every leg gets: sizes, whether this is the CPU rehearsal, and
    results earlier legs leave for later ones."""

    def __init__(self, sizes, rehearse):
        self.sz = sizes
        self.rehearse = rehearse
        self.shared = {}

    def note(self, msg):
        print("    " + msg, flush=True)

    def assert_compiled(self, what, text):
        """The kernel was lowered by Mosaic, not interpreted or routed to
        a jnp fallback.  The rehearsal interprets by design."""
        n = text.count("tpu_custom_call")
        if self.rehearse:
            self.note("%s: interpreted (rehearsal)" % what)
            return
        if n == 0:
            raise AssertionError(
                "%s: no tpu_custom_call in the compiled program — the "
                "kernel did not run as a compiled Mosaic kernel" % what)
        self.note("%s: %d tpu_custom_call in the compiled program"
                  % (what, n))


def _on_platform(tree, platform):
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    bad = [d for x in leaves for d in x.devices()
           if d.platform != platform]
    if bad:
        raise AssertionError("%d of %d leaves not on a %s device: %s"
                             % (len(bad), len(leaves), platform, bad[:3]))
    return len(leaves)


def _finite_and_decreasing(what, losses):
    losses = [float(x) for x in losses]
    if not all(np.isfinite(losses)):
        raise AssertionError("%s: non-finite loss in %r" % (what, losses))
    if not losses[-1] < losses[0]:
        raise AssertionError("%s: loss did not decrease: first %.4f, "
                             "last %.4f" % (what, losses[0], losses[-1]))
    return losses


# ------------------------------------------------------ train_resnet50 ---

def _resnet_trainer(ctx, mesh):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import DataParallelTrainer

    r = ctx.sz["resnet"]
    dev = mx.tpu()
    net = getattr(vision, r["model"])(stem_s2d=True, classes=r["classes"])
    net.initialize(mx.initializer.Xavier(), ctx=dev)
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": r["lr"], "momentum": 0.9}, mesh=mesh, amp=True)
    rng = np.random.RandomState(0)
    data = nd.array(rng.randn(r["batch"], 3, r["image"], r["image"])
                    .astype("float32"), ctx=dev)
    label = nd.array(rng.randint(0, r["classes"], (r["batch"],)), ctx=dev)
    return trainer, data, label


def leg_train_resnet50(ctx):
    import jax
    from mxnet_tpu.parallel import make_mesh

    r = ctx.sz["resnet"]
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer, data, label = _resnet_trainer(ctx, mesh)
    losses = [float(trainer.step(data, label).asnumpy())
              for _ in range(3)]
    losses += list(trainer.run_steps(data, label,
                                     steps=r["scan_steps"]).asnumpy())
    trainer.sync()
    # trainer._state is the (params, optimizer state) pytree the step
    # program carries
    n = _on_platform(trainer._state, jax.devices()[0].platform)
    losses = _finite_and_decreasing("resnet", losses)
    ctx.note("%s b%d: %d steps, loss %.4f -> %.4f; %d param+optimizer "
             "leaves on %s" % (r["model"], r["batch"], len(losses),
                               losses[0], losses[-1], n,
                               jax.devices()[0]))


# ---------------------------------------------------------- serve_full ---

def _gpt(ctx, dtype, w8):
    import jax
    from mxnet_tpu.models import gpt
    cfg = gpt.gpt_config(dropout=0.0, use_flash=False, remat=False,
                         dtype=dtype, **ctx.sz["gpt"])
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    if w8:
        params = gpt.quantize_decode_params(params)
    return params, cfg


def _prompts(ctx, n=None):
    rng = np.random.RandomState(1)
    vocab = ctx.sz["gpt"]["vocab_size"]
    shapes = ctx.sz["requests"]
    if n is not None:
        shapes = [shapes[i % len(shapes)] for i in range(n)]
    return [(rng.randint(1, vocab, P).astype(np.int32), N)
            for P, N in shapes]


def _serve(ctx, params, cfg, prompts, pools_seen_on=None, **kw):
    """Submit every prompt, drain, and return the generated tokens per
    request after checking count and range.  ``pools_seen_on``: the
    platform the engine is to read as its pools' while it is built (it
    takes its schedule from there and has no argument for it)."""
    from mxnet_tpu.kernels import platform
    from mxnet_tpu.serving import ServingEngine
    real = platform.platform_of
    if pools_seen_on is not None:
        platform.platform_of = lambda *operands: pools_seen_on
    try:
        eng = ServingEngine(params, cfg, **dict(ctx.sz["engine"], **kw))
    finally:
        platform.platform_of = real
    if pools_seen_on is not None and \
            eng.overlap is not (pools_seen_on == "tpu"):
        raise AssertionError("engine built as on %r chose overlap=%r"
                             % (pools_seen_on, eng.overlap))
    rids = [eng.submit(p, n) for p, n in prompts]
    outs = eng.run()
    eng.close()
    gen = []
    for rid, (p, n) in zip(rids, prompts):
        out = np.asarray(outs[rid])
        if out.shape != (p.size + n,):
            raise AssertionError(
                "request %d: %d tokens back, wanted %d prompt + %d new"
                % (rid, out.size, p.size, n))
        if not np.array_equal(out[:p.size], p):
            raise AssertionError("request %d: prompt not echoed" % rid)
        new = out[p.size:]
        if new.min() < 0 or new.max() >= cfg.vocab_size:
            raise AssertionError("request %d: token id out of range"
                                 % rid)
        gen.append(new)
    if eng.cache.pages_in_use:
        raise AssertionError("%d pages still held after the drain"
                             % eng.cache.pages_in_use)
    return gen


def _identical(what, a, b):
    for i, (x, y) in enumerate(zip(a, b)):
        if not np.array_equal(x, y):
            k = int(np.argmax(x != y))
            raise AssertionError(
                "%s: request %d differs at new token %d of %d"
                % (what, i, k, x.size))


def leg_serve_full(ctx):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import gpt

    prompts = _prompts(ctx)
    total = sum(n for _, n in prompts)

    # the preset as deployed: bf16 compute, weight-only int8
    params, cfg = _gpt(ctx, "bfloat16", w8=True)
    _on_platform(params, jax.devices()[0].platform)
    gen = {k: _serve(ctx, params, cfg, prompts, kernel=k)
           for k in ("xla", "pallas")}
    agree = sum(int((a == b).sum())
                for a, b in zip(gen["xla"], gen["pallas"]))
    ctx.note("bf16+w8: %d requests, %d new tokens under each kernel; "
             "xla/pallas agree on %d/%d tokens (%.1f%%; reported, not "
             "gated: bf16 argmax ties)"
             % (len(prompts), total, agree, total, 100.0 * agree / total))
    del params

    # identity, the repo's own bar (tests/test_serving.py), at the same
    # width: float32, no w8, highest matmul precision
    with jax.default_matmul_precision("highest"):
        params, cfg = _gpt(ctx, "float32", w8=False)
        f32 = {k: _serve(ctx, params, cfg, prompts, kernel=k)
               for k in ("xla", "pallas")}
        _identical("f32 xla vs pallas", f32["xla"], f32["pallas"])
        ref = [np.asarray(gpt.generate(params, cfg, jnp.asarray(p)[None],
                                       n))[0, p.size:]
               for p, n in prompts]
        _identical("f32 engine vs generate()", f32["xla"], ref)
    ctx.note("f32/highest: xla, pallas and generate() token-identical on "
             "%d requests, %d tokens" % (len(prompts), total))
    ctx.shared["f32_tp1"] = f32["xla"]


# ----------------------------------------------------- serve_schedules ---

def _family_toy(family):
    """A family the engine serves beside ``TransformerConfig`` — Falcon-H1
    (a recurrent state per slot in every layer) or LFM2-MoE (layers that
    keep a window beside layers that keep pages, routed experts) — at
    the toy size of the benchmark's configuration file (its ``rehearse``
    group: the published multipliers and flags, a few layers), seeded
    weights, the configuration's own dtype; one chunk holds a prompt of
    every slot, so that a request's prompt is one chunk whichever step
    admits it (the chunked scan sums in another order than the
    step-by-step one)."""
    import importlib
    import jax
    config, file = {"falcon_h1": ("FalconH1Config", "falcon_h1_34b_l6"),
                    "lfm2_moe": ("Lfm2MoeConfig", "lfm2_8b_a1b_l12")}[family]
    model = importlib.import_module("mxnet_tpu.models." + family)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chipbench", "configs", file + ".json")) as f:
        c = json.load(f)
    toy = dict(c, **c["rehearse"])
    cfg = getattr(model, config).from_hf(toy, dtype=c["dtype"])
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    engine = dict(toy["engine"])
    longest = 24
    engine["prefill_chunk"] = engine["num_slots"] * longest
    rng = np.random.RandomState(2)
    prompts = [(rng.randint(1, cfg.vocab_size, P).astype(np.int32), N)
               for P, N in [(5, 12), (longest, 9), (11, 30), (17, 6),
                            (3, 21), (20, 14), (8, 8), (13, 25),
                            (longest, 5), (6, 17)]]
    return params, cfg, engine, prompts


def leg_serve_schedules(ctx):
    import jax
    from mxnet_tpu.kernels.platform import platform_of
    from mxnet_tpu.serving import ServingEngine

    # what an engine told nothing runs: pipelined on a TPU, serial on a
    # CPU; with speculation serial on either
    params, cfg = _gpt(ctx, "bfloat16", w8=True)
    on_tpu = platform_of(params) == "tpu"
    for kw, want in (({}, on_tpu), ({"spec_K": 2}, False)):
        eng = ServingEngine(params, cfg, **dict(ctx.sz["engine"], **kw))
        if eng.overlap is not want:
            raise AssertionError(
                "ServingEngine(%r) on %s chose overlap=%r"
                % (kw, jax.devices()[0].platform, eng.overlap))
        eng.close()
    ctx.note("an engine told nothing chose the %s schedule (spec_K=2: "
             "serial)" % ("pipelined" if on_tpu else "serial"))

    # serial against pipelined: each engine built as on that platform,
    # both with the attention lowering this platform's engines take
    both = {False: "cpu", True: "tpu"}
    kernel = "pallas" if on_tpu else "xla"

    # the preset as deployed, more requests than slots (slots reused)
    prompts = _prompts(ctx, n=3 * ctx.sz["engine"]["num_slots"])
    gen = {ov: _serve(ctx, params, cfg, prompts, pools_seen_on=plat,
                      kernel=kernel)
           for ov, plat in both.items()}
    _identical("bf16+w8 serial vs pipelined", gen[False], gen[True])
    ctx.note("full preset, bf16+w8: serial and pipelined token-identical "
             "on %d requests over %d slots, %d tokens"
             % (len(prompts), ctx.sz["engine"]["num_slots"],
                sum(n for _, n in prompts)))
    del params

    for family in ("falcon_h1", "lfm2_moe"):
        params, cfg, engine, prompts = _family_toy(family)
        gen = {ov: _serve(ctx, params, cfg, prompts, pools_seen_on=plat,
                          kernel=kernel, **engine)
               for ov, plat in both.items()}
        _identical(family + " serial vs pipelined", gen[False], gen[True])
        ctx.note("%s (toy, %s): serial and pipelined token-identical "
                 "on %d requests over %d slots, %d tokens"
                 % (family, cfg.dtype, len(prompts), engine["num_slots"],
                    sum(n for _, n in prompts)))


# ------------------------------------------------------------- kernels ---

def _mlm_batch(cfg, B, L):
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, (B, L)), jnp.int32)
    return {"tokens": tokens,
            "labels": jnp.where(jnp.asarray(rng.rand(B, L) < 0.15),
                                tokens, -100),
            "mask": jnp.ones((B, L), dtype=bool)}


def _kernel_flash_train(ctx):
    """(a) flash fwd+bwd inside the real train step."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as T

    b = ctx.sz["bert"]
    L, B = b["seq"], b["batch"]
    cfg = getattr(T, b["factory"])(max_len=L, use_flash=True, remat=True,
                                   dropout=0.1, **b["width"])
    init_state, step = T.make_train_step(cfg, learning_rate=1e-4)
    state = init_state(jax.random.PRNGKey(0))
    batch = _mlm_batch(cfg, B, L)
    key = jax.random.PRNGKey(1)
    compiled = step.lower(state, batch, key).compile()
    ctx.assert_compiled("flash fwd+bwd in the %s train step, L=%d"
                        % (b["factory"], L), compiled.as_text())
    losses = []
    for _ in range(2):
        state, loss = compiled(state, batch, key)
        losses.append(float(loss))
    losses = _finite_and_decreasing("flash train step", losses)
    ctx.note("flash train step: loss %.4f -> %.4f" % tuple(losses))


def _kernel_paged(ctx):
    """(b) paged_attention vs paged_attention_reference on random pools."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels.paged_attention import (
        paged_attention, paged_attention_reference)

    for g in ctx.sz["paged"]:
        T_, H, dh, ps, PP, NP = (
            g[k] for k in ("T", "H", "dh", "ps", "PP", "NP"))
        rng = np.random.RandomState(0)
        bt = jnp.asarray(rng.randint(1, NP, (T_, PP)), jnp.int32)
        pos = jnp.asarray(rng.randint(0, PP * ps, (T_,)), jnp.int32)
        for kv_dtype in ("float32", "bfloat16", "int8"):
            qdt = "float32" if kv_dtype == "float32" else "bfloat16"
            q = jnp.asarray(rng.randn(T_, H, dh), qdt)
            if kv_dtype == "int8":
                kv = jnp.asarray(
                    rng.randint(-127, 128, (NP, ps, H, 2 * dh)), jnp.int8)
                sc = jnp.asarray(
                    rng.uniform(0.005, 0.02, (NP, 2, ps, H)), jnp.float32)
            else:
                kv, sc = jnp.asarray(rng.randn(NP, ps, H, 2 * dh),
                                     kv_dtype), None
            kern = jax.jit(lambda *a: paged_attention(*a, page_size=ps))
            compiled = kern.lower(q, kv, sc, bt, pos).compile()
            what = "paged_attention %d heads %s" % (H, kv_dtype)
            ctx.assert_compiled(what + " pool", compiled.as_text())
            got = np.asarray(compiled(q, kv, sc, bt, pos))
            want = np.asarray(jax.jit(
                lambda *a: paged_attention_reference(*a, page_size=ps))(
                    q, kv, sc, bt, pos))
            err = float(np.abs(got - want).max() / np.abs(want).max())
            if not np.isfinite(got).all() or err > _PAGED_TOL[kv_dtype]:
                raise AssertionError(
                    "%s: max|kernel-ref|/max|ref| = %.3g > %.0e"
                    % (what, err, _PAGED_TOL[kv_dtype]))
            ctx.note("%s: max|kernel-ref|/max|ref| = %.2e (tolerance "
                     "%.0e)" % (what, err, _PAGED_TOL[kv_dtype]))


def _kernel_paged_latent(ctx):
    """(b') the walk's latent fold vs paged_attention_reference."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels.paged_attention import (
        paged_attention, paged_attention_reference, walk_geometry)
    from mxnet_tpu.serving.paged_kv import latent_width

    g = ctx.sz["latent"]
    T_, H, ps, PP, NP = (g[k] for k in ("T", "H", "ps", "PP", "NP"))
    latent = g["rank"], g["rope"]
    W = latent_width(*latent)
    kw = dict(page_size=ps, latent=latent, scale=g["scale"])
    rng = np.random.RandomState(0)
    bt = jnp.asarray(rng.randint(1, NP, (T_, PP)), jnp.int32)
    for dtype in ("float32", "bfloat16"):
        G = walk_geometry(1, W // 2, ps, PP, dtype, flat=True,
                          latent=True)[0]
        # a dead row, a full table, and the two sides of a group's edge
        pos = jnp.asarray(rng.randint(0, PP * ps, (T_,)), jnp.int32) \
            .at[:4].set(jnp.asarray([0, PP * ps - 1, G * ps - 1, G * ps]))
        rows = np.zeros((NP, ps, W), np.float32)
        rows[..., :sum(latent)] = rng.randn(NP, ps, sum(latent))
        kv = jnp.asarray(rows, dtype)
        q = jnp.asarray(rng.randn(T_, H, sum(latent)), dtype)
        compiled = jax.jit(lambda *a: paged_attention(*a, **kw)) \
            .lower(q, kv, None, bt, pos).compile()
        what = "paged_attention latent %d heads %s" % (H, dtype)
        ctx.assert_compiled(what + " pool", compiled.as_text())
        got = np.asarray(compiled(q, kv, None, bt, pos))
        want = np.asarray(jax.jit(
            lambda *a: paged_attention_reference(*a, **kw))(
                q, kv, None, bt, pos))
        err = float(np.abs(got - want).max() / np.abs(want).max())
        if not np.isfinite(got).all() or err > _PAGED_TOL[dtype]:
            raise AssertionError(
                "%s: max|kernel-ref|/max|ref| = %.3g > %.0e"
                % (what, err, _PAGED_TOL[dtype]))
        ctx.note("%s: max|kernel-ref|/max|ref| = %.2e (tolerance %.0e)"
                 % (what, err, _PAGED_TOL[dtype]))


def _kernel_fused_sgd(ctx):
    """(c) nd.multi_sgd_mom_update vs the per-tensor loop, bit for bit."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.ops import registry

    dev = mx.tpu()
    shapes = _resnet50_shapes()
    if ctx.rehearse:
        shapes = shapes[:12]
    rng = np.random.RandomState(0)
    mk = lambda s, scale: nd.array(                        # noqa: E731
        (rng.randn(*s) * scale).astype("float32"), ctx=dev)
    ws = [mk(s, 0.1) for s in shapes]
    gs = [mk(s, 0.01) for s in shapes]
    ms = [mk(s, 0.001) for s in shapes]
    n = len(shapes)
    lrs = (0.1,) * n
    attrs = dict(wds=[1e-4] * n, momentum=0.9, rescale_grad=1.0 / 128,
                 num_weights=n)

    # the compiled proof: the registered op's own impl, lowered for the
    # arrays it is about to run on the way the eager path jits it (lrs
    # as traced arguments — ops/registry.py _DYN_ATTR_NAMES)
    impl = registry.get_op("multi_sgd_mom_update").impl
    flat = [x._data for wgm in zip(ws, gs, ms) for x in wgm]
    text = jax.jit(lambda a, lrs: impl(list(a), lrs=lrs, **attrs)) \
        .lower(flat, lrs).compile().as_text()
    ctx.assert_compiled("fused_multi_sgd, %d tensors / %.1fM elements"
                        % (n, sum(int(np.prod(s)) for s in shapes) / 1e6),
                        text)

    moms = [m.copy() for m in ms]
    data = [x for wgm in zip((w.copy() for w in ws), gs, moms)
            for x in wgm]
    outs = nd.multi_sgd_mom_update(*data, lrs=list(lrs), **attrs)
    worst = 0.0
    for i in range(n):
        m = ms[i].copy()
        w = nd.sgd_mom_update(ws[i].copy(), gs[i], m, lr=0.1, wd=1e-4,
                              momentum=0.9, rescale_grad=1.0 / 128)
        for got, want in ((outs[i], w), (moms[i], m)):
            got, want = got.asnumpy(), want.asnumpy()
            if not np.array_equal(got, want):
                worst = max(worst, float(np.abs(got - want).max()))
    if worst:
        raise AssertionError(
            "fused_multi_sgd differs from the per-tensor loop: max "
            "|diff| %.3g (kernels/fused_optimizer.py promises bit-"
            "exact f32)" % worst)
    ctx.note("multi_sgd_mom_update: %d weights and momenta bit-identical "
             "to the per-tensor loop" % n)


def leg_kernels(ctx):
    _kernel_flash_train(ctx)
    _kernel_paged(ctx)
    _kernel_paged_latent(ctx)
    _kernel_fused_sgd(ctx)


# ----------------------------------------------------------- multichip ---

def _distinct_devices(tree):
    import jax
    return {d for x in jax.tree_util.tree_leaves(tree)
            for d in x.sharding.device_set}


def leg_multichip(ctx):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.serving import ServingCluster

    devs = jax.devices()[:4]
    ctx.note("devices: %s" % ", ".join(
        "%d@%s" % (d.id, getattr(d, "coords", "-")) for d in devs))

    # data-parallel ResNet over all four: batch sharded, params replicated
    mesh = make_mesh({"dp": 4}, devices=devs)
    trainer, data, label = _resnet_trainer(ctx, mesh)
    losses = [float(trainer.step(data, label).asnumpy())
              for _ in range(6)]
    trainer.sync()
    _finite_and_decreasing("dp resnet", losses)
    params = trainer._state[0]
    if any(x.sharding.device_set != set(devs) or
           not x.sharding.is_fully_replicated
           for x in jax.tree_util.tree_leaves(params)):
        raise AssertionError("dp resnet: params not replicated on the "
                             "four devices")
    ctx.note("dp=4 %s: loss %.4f -> %.4f; params replicated on %d "
             "devices; batch sharding %s"
             % (ctx.sz["resnet"]["model"], losses[0], losses[-1],
                len(_distinct_devices(params)),
                trainer._batch_sharding.spec))
    del trainer, params

    # FSDP BERT on dp=4 and on dp=2 x tp=2
    f = ctx.sz["fsdp"]
    L, B = f["seq"], f["batch"]
    for axes in ({"dp": 4}, {"dp": 2, "tp": 2}):
        mesh = make_mesh(axes, devices=devs)
        cfg = getattr(T, f["factory"])(max_len=L, use_flash=False,
                                       remat=True, dropout=0.1)
        init_state, step = T.make_train_step(cfg, mesh=mesh, fsdp=True,
                                             learning_rate=1e-4)
        state = init_state(jax.random.PRNGKey(0))
        batch = _mlm_batch(cfg, B, L)
        losses = []
        for _ in range(2):
            state, loss = step(state, batch, jax.random.PRNGKey(1))
            losses.append(float(loss))
        _finite_and_decreasing("fsdp %s" % axes, losses)
        leaves = jax.tree_util.tree_leaves(state[0])
        sharded = sum(not x.sharding.is_fully_replicated for x in leaves)
        if _distinct_devices(state) != set(devs) or not sharded:
            raise AssertionError("fsdp %s: state not sharded over the "
                                 "four devices" % axes)
        ctx.note("fsdp %s %s: loss %.4f -> %.4f; %d/%d param leaves "
                 "sharded over %d devices"
                 % (axes, f["factory"], losses[0], losses[1], sharded,
                    len(leaves), len(_distinct_devices(state))))
        del state

    # tensor-parallel serving: token-identical to tp=1 (f32 / highest)
    prompts = _prompts(ctx)
    with jax.default_matmul_precision("highest"):
        params, cfg = _gpt(ctx, "float32", w8=False)
        tp1 = ctx.shared.get("f32_tp1")     # serve_full's, if it ran
        if tp1 is None:
            tp1 = _serve(ctx, params, cfg, prompts, kernel="xla")
        for tp in (2, 4):
            for kernel in ("xla", "pallas"):
                gen = _serve(ctx, params, cfg, prompts, kernel=kernel,
                             tp=tp)
                _identical("tp=%d %s vs tp=1" % (tp, kernel), gen, tp1)
                ctx.note("tp=%d kernel=%s: token-identical to tp=1"
                         % (tp, kernel))
    del params

    # four one-chip replicas in this one process
    params, cfg = _gpt(ctx, "bfloat16", w8=True)
    reqs = _prompts(ctx, ctx.sz["cluster_requests"])
    cluster = ServingCluster(params, cfg, replicas=4,
                             **ctx.sz["engine"])
    try:
        placed = []
        for rep in cluster.replicas:
            pd = _distinct_devices(rep.engine.params)
            kd = _distinct_devices(rep.engine.cache.pools)
            if len(pd) != 1 or pd != kd:
                raise AssertionError("replica %d: params on %s, pools on "
                                     "%s" % (rep.idx, pd, kd))
            placed.append(next(iter(pd)))
            ctx.note("replica %d: params and pools on %s"
                     % (rep.idx, placed[-1]))
        if len(set(placed)) != 4:
            raise AssertionError("replicas share devices: %s" % placed)
        rids = [cluster.submit(p, n) for p, n in reqs]
        served = {}
        for rid, (p, n) in zip(rids, reqs):
            out = cluster.result(rid, timeout=600)
            if out.shape != (p.size + n,):
                raise AssertionError("cluster request %d: %d tokens, "
                                     "wanted %d" % (rid, out.size,
                                                    p.size + n))
            r = cluster.requests[rid].replica
            served[r] = served.get(r, 0) + 1
        ctx.note("cluster: %d requests answered; per replica %s"
                 % (len(reqs), dict(sorted(served.items()))))
    finally:
        cluster.close()


LEGS = [("train_resnet50", leg_train_resnet50),
        ("serve_full", leg_serve_full),
        ("serve_schedules", leg_serve_schedules),
        ("kernels", leg_kernels),
        ("multichip", leg_multichip)]


# ---------------------------------------------------------------- main ---

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU backend, kernels "
                         "interpreted; proves the command runs, nothing "
                         "about the chip")
    ap.add_argument("--legs", default=None,
                    help="comma-separated subset of legs (debugging); the "
                         "final JSON line is printed only for a full run")
    args = ap.parse_args()
    t_start = time.time()

    import jax
    if args.rehearse_cpu:
        print("REHEARSAL — not a chip run", flush=True)
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 4)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print("device: platform=%(platform)s kind=%(kind)s count=%(count)d"
          % device, flush=True)
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print("chip_smoke: JAX found platform %r, not a TPU; refusing to "
              "run (--rehearse-cpu runs the toy rehearsal)"
              % dev.platform, file=sys.stderr)
        return 2

    import mxnet_tpu as mx
    # nothing below needs the native library (a fresh checkout has none;
    # native.available() would build it, so only look)
    print("jax %s; compile cache %s; native library built: %s"
          % (jax.__version__,
             os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or jax.config.jax_compilation_cache_dir,
             os.path.exists(os.path.join(
                 os.path.dirname(os.path.abspath(__file__)), "native",
                 "lib", "libmxnet_tpu.so"))), flush=True)
    if args.rehearse_cpu:
        # flash_attention routes the CPU backend to its jnp reference;
        # the rehearsal wants the kernels themselves, interpreted
        from mxnet_tpu.kernels import flash_attention
        flash_attention._INTERPRET = True

    wanted = set(args.legs.split(",")) if args.legs else None
    unknown = (wanted or set()) - {n for n, _ in LEGS}
    if unknown:
        ap.error("unknown leg(s): %s" % ", ".join(sorted(unknown)))
    ctx = Ctx(REHEARSE if args.rehearse_cpu else CHIP, args.rehearse_cpu)
    clock = _CompileClock()
    failed = []
    for name, fn in LEGS:
        if wanted is not None and name not in wanted:
            continue
        if name == "multichip" and device["count"] < 4:
            print("multichip: not run (%d device(s) visible, needs 4)"
                  % device["count"], flush=True)
            continue
        print("%s ..." % name, flush=True)
        # weights are random, made from a seed (Gluon initializers draw
        # from numpy's global generator, random ops from mx.random)
        np.random.seed(0)
        mx.random.seed(0)
        t0, c0 = time.time(), clock.secs
        h0, w0 = clock.hits, clock.writes
        ok = True
        try:
            fn(ctx)
        except Exception:          # a leg that raises is a FAIL
            ok = False
            traceback.print_exc()
            failed.append(name)
        wall, comp = time.time() - t0, clock.secs - c0
        print("%s %s wall %.1fs = compile %.1fs + run %.1fs "
              "(persistent cache: %d hits, %d writes)"
              % ("PASS" if ok else "FAIL", name, wall, comp, wall - comp,
                 clock.hits - h0, clock.writes - w0), flush=True)

    print("total %.1fs (compile %.1fs; persistent cache %d hits, %d "
          "writes)" % (time.time() - t_start, clock.secs, clock.hits,
                       clock.writes), flush=True)
    if failed:
        print("chip_smoke: FAILED legs: %s" % ", ".join(failed),
              file=sys.stderr)
        return 1
    if wanted is not None:
        print("chip_smoke: partial run (--legs), no result line")
        return 0
    if args.rehearse_cpu:
        print("REHEARSAL — not a chip run: every leg passed at toy size")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
