"""Flagship transformer (BERT-style encoder) — TPU-first functional core.

Reference scope: GluonNLP BERT-base pretraining is a BASELINE.json config;
MXNet 1.x itself has no transformer in-tree, so this module is the
TPU-native implementation the Gluon/Module frontends wrap.

Design (scaling-book recipe): pure functions over a param pytree; the
train step is jitted over a ``Mesh`` with NamedShardings —

* params: attention/FFN hidden dims sharded over ``tp``; everything else
  replicated
* batch: sharded over ``dp``; activations sequence-sharded over ``sp``
  when the mesh has that axis (XLA GSPMD inserts the all-gathers;
  ring-attention via shard_map lives in ``parallel/ring_attention.py``)
* XLA inserts the gradient psum over ``dp`` because params are replicated
  w.r.t. ``dp`` while batch is sharded — no hand-written allreduce
  (this IS the ``kvstore_nccl`` path, compiled)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

from .. import profiler

__all__ = ["TransformerConfig", "init_params", "forward",
           "forward_with_aux", "mlm_loss", "make_train_step",
           "train_step_input_specs", "train_step_output_specs",
           "bert_base", "bert_tiny"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 30522
    max_len: int = 512
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    dropout: float = 0.1
    dtype: str = "bfloat16"       # MXU-native compute dtype
    param_dtype: str = "float32"  # master params
    use_flash: bool = True        # pallas flash attention on TPU
    remat: bool = True            # jax.checkpoint per layer
    # remat policy when remat=True: "nothing" recomputes everything
    # (minimum memory); "dots" saves MXU outputs (attention scores,
    # FFN matmuls) so the backward recompute is elementwise-only —
    # measured faster whenever it fits (docs/perf.md).  NOTE: bert-base
    # bs16/seq512 fits WITHOUT remat on one v5e chip — remat there is
    # pure cost (13% — round-2 measurement); reach for it at longer
    # sequences first.
    remat_policy: str = "nothing"
    # dropout PRNG: True converts the step rng to the TPU's hardware
    # RBG generator (counter-based like the reference's GPU Philox
    # dropout) — threefry bit generation measured 19% of the bert-base
    # step; RBG removes nearly all of it (97k->134k tok/s with
    # no-remat, docs/perf.md).  Mask streams differ from threefry but
    # are deterministic per key.
    fast_rng: bool = True
    type_vocab_size: int = 2
    # sequence/context parallelism over the mesh's 'sp' axis:
    # None = let GSPMD handle it; 'ring' = ring attention (ppermute K/V
    # blocks over ICI); 'ulysses' = all-to-all head scatter.
    seq_parallel: Optional[str] = None
    # Mixture-of-Experts (expert parallel over the mesh's 'ep' axis):
    # n_experts=0 → all-dense.  Layers with i % moe_every == moe_every-1
    # swap their FFN for a top-k routed MoE (parallel/moe.py).
    n_experts: int = 0
    moe_every: int = 2
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # GPipe microbatch count when the mesh has a 'pp' axis
    # (parallel/pipeline.py); ignored otherwise.
    pp_microbatches: int = 2
    # autoregressive (decoder/GPT) attention masking (models/gpt.py)
    causal: bool = False


def bert_base(**kw):
    return TransformerConfig(**kw)


def bert_tiny(**kw):
    base = dict(vocab_size=1024, max_len=128, d_model=64, n_heads=4,
                n_layers=2, d_ff=128)
    base.update(kw)
    return TransformerConfig(**base)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key, cfg: TransformerConfig) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    def dense_init(key, shape, scale=0.02):
        return (jax.random.normal(key, shape) * scale).astype(
            cfg.param_dtype)

    keys = jax.random.split(key, 6 + cfg.n_layers)
    D, F, H = cfg.d_model, cfg.d_ff, cfg.n_heads
    params = {
        "tok_emb": dense_init(keys[0], (cfg.vocab_size, D)),
        "pos_emb": dense_init(keys[1], (cfg.max_len, D)),
        "type_emb": dense_init(keys[2], (cfg.type_vocab_size, D)),
        "emb_ln": {"g": jnp.ones((D,), cfg.param_dtype),
                   "b": jnp.zeros((D,), cfg.param_dtype)},
        "mlm_dense": dense_init(keys[3], (D, D)),
        "mlm_ln": {"g": jnp.ones((D,), cfg.param_dtype),
                   "b": jnp.zeros((D,), cfg.param_dtype)},
        "mlm_bias": jnp.zeros((cfg.vocab_size,), cfg.param_dtype),
        "layers": [],
    }
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[6 + i], 8)
        layer = {
            "wq": dense_init(k[0], (D, D)),
            "wk": dense_init(k[1], (D, D)),
            "wv": dense_init(k[2], (D, D)),
            "wo": dense_init(k[3], (D, D)),
            "bq": jnp.zeros((D,), cfg.param_dtype),
            "bk": jnp.zeros((D,), cfg.param_dtype),
            "bv": jnp.zeros((D,), cfg.param_dtype),
            "bo": jnp.zeros((D,), cfg.param_dtype),
            "ln1": {"g": jnp.ones((D,), cfg.param_dtype),
                    "b": jnp.zeros((D,), cfg.param_dtype)},
            "ln2": {"g": jnp.ones((D,), cfg.param_dtype),
                    "b": jnp.zeros((D,), cfg.param_dtype)},
        }
        if _is_moe_layer(cfg, i):
            from ..parallel.moe import init_moe_ffn
            layer["moe"] = init_moe_ffn(k[6], D, F, cfg.n_experts,
                                        param_dtype=cfg.param_dtype)
        else:
            layer.update({
                "w1": dense_init(k[4], (D, F)),
                "b1": jnp.zeros((F,), cfg.param_dtype),
                "w2": dense_init(k[5], (F, D)),
                "b2": jnp.zeros((D,), cfg.param_dtype),
            })
        params["layers"].append(layer)
    return params


def _is_moe_layer(cfg: TransformerConfig, i: int) -> bool:
    return (cfg.n_experts > 0
            and i % cfg.moe_every == cfg.moe_every - 1)


def param_specs(cfg: TransformerConfig, tp="tp", ep="ep"):
    """Megatron partition rules as a MESH-FREE ``PartitionSpec`` pytree
    matching init_params: tp shards the hidden dims, everything else
    replicated (scaling-book megatron layout).  ``tp``/``ep`` name the
    mesh axes (pass ``None`` to drop an axis from the specs, e.g. for
    a mesh without it).  ``param_shardings`` binds these to a mesh;
    the serving engine's declared shardings (``serving/engine.py
    step_input_specs``) and graphlint's sharding-readiness audit both
    derive from THIS table, so there is exactly one copy of the
    rules."""
    from jax.sharding import PartitionSpec as P

    rep = P()

    def layer_spec(i):
        layer = {
            "wq": P(None, tp), "wk": P(None, tp), "wv": P(None, tp),
            "wo": P(tp, None),
            "bq": P(tp), "bk": P(tp), "bv": P(tp), "bo": rep,
            "ln1": {"g": rep, "b": rep},
            "ln2": {"g": rep, "b": rep},
        }
        if _is_moe_layer(cfg, i):
            from ..parallel.moe import moe_param_specs
            layer["moe"] = moe_param_specs(tp=tp, ep=ep)
        else:
            layer.update({"w1": P(None, tp), "b1": P(tp),
                          "w2": P(tp, None), "b2": rep})
        return layer

    return {
        "tok_emb": P(None, tp),
        "pos_emb": P(None, tp),
        "type_emb": P(None, tp),
        "emb_ln": {"g": rep, "b": rep},
        "mlm_dense": P(None, tp),
        "mlm_ln": {"g": rep, "b": rep},
        "mlm_bias": rep,
        "layers": [layer_spec(i) for i in range(cfg.n_layers)],
    }


def param_shardings(cfg: TransformerConfig, mesh):
    """NamedSharding pytree matching init_params — ``param_specs``
    bound to ``mesh`` (axes the mesh lacks are dropped from the
    specs)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    specs = param_specs(
        cfg,
        tp="tp" if "tp" in mesh.axis_names else None,
        ep="ep" if "ep" in mesh.axis_names else None)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer_norm(x, g, b, eps=1e-12):
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _attention(q, k, v, mask, cfg: TransformerConfig, mesh=None,
               dropout_key=None):
    """(B, T, H, dh) attention.  With ``cfg.seq_parallel`` and an 'sp'
    mesh axis the sequence stays sharded and attention runs as ring /
    Ulysses over ICI; otherwise the pallas flash kernel on TPU when
    enabled, jnp reference elsewhere (also the CPU/test path).

    ``dropout_key`` non-None enables attention-probability dropout at
    ``cfg.dropout`` — on the flash path it is FUSED into the Pallas
    kernels (round-4 item #7), never materializing the (T, T) mask."""
    import jax
    import jax.numpy as jnp
    # argument validation for EVERY attention path (flash, jnp, ring):
    # a bad dropout value is the caller's bug and must surface — the
    # jnp path would otherwise silently compute bernoulli(p<0) /
    # negative scaling (round-4 advisor; round-5 review).
    if dropout_key is not None and not 0.0 <= float(cfg.dropout) < 1.0:
        raise ValueError("attention dropout must be in [0, 1), "
                         "got %r" % (cfg.dropout,))
    if cfg.seq_parallel and mesh is not None and "sp" in mesh.axis_names \
            and mesh.shape["sp"] > 1:
        from ..parallel.ring_attention import sequence_parallel_attention
        return sequence_parallel_attention(
            q, k, v, mask, mesh=mesh, seq_axis="sp",
            method=cfg.seq_parallel, causal=cfg.causal)
    if cfg.use_flash:
        # no fallback around this call: a kernel that fails to trace
        # fails the step.  flash_attention's own routing (CPU backend,
        # short or untileable sequences → its jnp reference) is
        # documented there.
        from ..kernels.flash_attention import flash_attention
        if dropout_key is not None and cfg.dropout > 0:
            seed = jax.random.randint(dropout_key, (), 0,
                                      2**31 - 1, jnp.int32)
            return flash_attention(q, k, v, mask=mask,
                                   causal=cfg.causal,
                                   dropout=cfg.dropout,
                                   dropout_seed=seed)
        return flash_attention(q, k, v, mask=mask, causal=cfg.causal)
    dh = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, -1e9)
    if cfg.causal:
        T = q.shape[1]
        tri = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(tri[None, None], logits, -1e9)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
        q.dtype)
    if dropout_key is not None and cfg.dropout > 0:
        # the SAME positional-hash keep mask the fused flash kernels
        # regenerate (kernels/flash_attention.dense_keep_mask), seeded
        # identically — one dropout semantics across both paths, and
        # the hash is pure fusable integer elementwise over iotas, so
        # XLA folds it into the probs consumer instead of generating
        # and materializing (B, H, T, T) RNG uniforms (measured: the
        # bernoulli mask cost ~22% of the bert-base step — round 5)
        from ..kernels.flash_attention import dense_keep_mask
        B, T, H, _ = q.shape
        seed = jax.random.randint(dropout_key, (), 0, 2**31 - 1,
                                  jnp.int32)
        keep = dense_keep_mask(B, H, T, seed, cfg.dropout)
        probs = jnp.where(keep, probs / (1 - cfg.dropout),
                          0).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _encoder_layer(x, layer, mask, cfg: TransformerConfig, train, key,
                   mesh=None):
    import jax
    import jax.numpy as jnp
    B, T, D = x.shape
    H = cfg.n_heads
    dh = D // H
    cdt = x.dtype

    def dn(w):
        return w.astype(cdt)

    # named scopes: metadata on the compiled operations, by which a
    # device trace says what a fusion is.  No layer index in a name:
    # the unrolled layers add up under one.
    with jax.named_scope("attn"):
        with jax.named_scope("qkv"):
            q = (x @ dn(layer["wq"]) + dn(layer["bq"])).reshape(B, T, H, dh)
            k = (x @ dn(layer["wk"]) + dn(layer["bk"])).reshape(B, T, H, dh)
            v = (x @ dn(layer["wv"]) + dn(layer["bv"])).reshape(B, T, H, dh)
        if train and cfg.dropout > 0:
            key, attn_sub = jax.random.split(key)
        else:
            attn_sub = None
        with jax.named_scope("scores"):
            attn = _attention(q, k, v, mask, cfg, mesh,
                              dropout_key=attn_sub).reshape(B, T, D)
        with jax.named_scope("out"):
            attn = attn @ dn(layer["wo"]) + dn(layer["bo"])
            if train and cfg.dropout > 0:
                key, sub = jax.random.split(key)
                keep = jax.random.bernoulli(sub, 1 - cfg.dropout,
                                            attn.shape)
                attn = jnp.where(keep, attn / (1 - cfg.dropout),
                                 0).astype(cdt)
    with jax.named_scope("ln1"):
        x = _layer_norm(x + attn, dn(layer["ln1"]["g"]),
                        dn(layer["ln1"]["b"]))
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("ffn"):
        if "moe" in layer:
            from ..parallel.moe import moe_ffn
            h, aux = moe_ffn(x, layer["moe"], n_experts=cfg.n_experts,
                             top_k=cfg.expert_top_k,
                             capacity_factor=cfg.capacity_factor,
                             mesh=mesh, dtype=cdt)
        else:
            h = jax.nn.gelu(x @ dn(layer["w1"]) + dn(layer["b1"]),
                            approximate=True)
            h = h @ dn(layer["w2"]) + dn(layer["b2"])
        if train and cfg.dropout > 0:
            key, sub = jax.random.split(key)
            keep = jax.random.bernoulli(sub, 1 - cfg.dropout, h.shape)
            h = jnp.where(keep, h / (1 - cfg.dropout), 0).astype(cdt)
    with jax.named_scope("ln2"):
        x = _layer_norm(x + h, dn(layer["ln2"]["g"]),
                        dn(layer["ln2"]["b"]))
    return x, aux


def forward(params, tokens, cfg: TransformerConfig, *, type_ids=None,
            mask=None, train=False, rng=None, mesh=None):
    """tokens (B, T) int32 -> MLM logits (B, T, V)."""
    logits, _ = forward_with_aux(params, tokens, cfg, type_ids=type_ids,
                                 mask=mask, train=train, rng=rng,
                                 mesh=mesh)
    return logits


def forward_with_aux(params, tokens, cfg: TransformerConfig, *,
                     type_ids=None, mask=None, train=False, rng=None,
                     mesh=None):
    """Like :func:`forward` but also returns the scalar auxiliary loss
    (MoE load-balancing; 0 for all-dense configs)."""
    import jax
    import jax.numpy as jnp

    cdt = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = params["tok_emb"][tokens].astype(cdt)
        x = x + params["pos_emb"][:T][None].astype(cdt)
        if type_ids is not None:
            x = x + params["type_emb"][type_ids].astype(cdt)
        x = _layer_norm(x, params["emb_ln"]["g"].astype(cdt),
                        params["emb_ln"]["b"].astype(cdt))

    if mesh is not None:
        x = _constrain_act(x, mesh)

    if rng is None:
        rng = jax.random.PRNGKey(0)
    aux_total = jnp.zeros((), jnp.float32)

    pp = (mesh.shape.get("pp", 1) if mesh is not None
          and "pp" in mesh.axis_names else 1)
    if pp > 1:
        x, aux = _pipelined_layers(x, params["layers"], mask, cfg, train,
                                   rng, mesh)
        aux_total = aux_total + aux
    else:
        layer_fn = _make_layer_fn(cfg)
        for i, layer in enumerate(params["layers"]):
            rng, sub = jax.random.split(rng)
            x, aux = layer_fn(x, layer, mask, cfg, train, sub, mesh)
            aux_total = aux_total + aux
            if mesh is not None:
                x = _constrain_act(x, mesh)

    # MLM head (weight-tied to token embedding)
    with jax.named_scope("mlm_head"):
        h = jax.nn.gelu(x @ params["mlm_dense"].astype(cdt),
                        approximate=True)
        h = _layer_norm(h, params["mlm_ln"]["g"].astype(cdt),
                        params["mlm_ln"]["b"].astype(cdt))
        logits = h @ params["tok_emb"].T.astype(cdt) + \
            params["mlm_bias"].astype(cdt)
        return logits.astype(jnp.float32), aux_total


def _make_layer_fn(cfg: TransformerConfig):
    """Encoder layer, remat-wrapped per cfg — single construction point
    so the pp and sequential paths cannot drift."""
    import jax
    if not cfg.remat:
        return _encoder_layer
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_saveable
    elif cfg.remat_policy == "nothing":
        policy = jax.checkpoint_policies.nothing_saveable
    else:
        from ..base import MXNetError
        raise MXNetError("remat_policy must be 'nothing' or 'dots', "
                         "got %r" % (cfg.remat_policy,))
    return jax.checkpoint(
        _encoder_layer, static_argnums=(3, 4, 6), policy=policy)


def _pipelined_layers(x, layers, mask, cfg, train, rng, mesh):
    """GPipe the layer stack over the mesh's 'pp' axis
    (parallel/pipeline.py).  Requires homogeneous layer structure (all
    dense, or all-MoE via moe_every=1) and no sequence-parallel attention
    (a nested manual shard_map).  Returns (x, aux_loss)."""
    import jax
    import jax.numpy as jnp
    from ..base import MXNetError
    from ..parallel.pipeline import pipeline_apply, stack_layer_params

    if cfg.n_experts and 1 < cfg.moe_every <= len(layers):
        raise MXNetError("pipeline parallelism needs a homogeneous layer "
                         "stack; mixed dense/MoE (moe_every>1) is "
                         "unsupported — use moe_every=1 or drop 'pp'")
    if cfg.seq_parallel:
        raise MXNetError("seq_parallel attention cannot nest inside the "
                         "'pp' shard_map; drop one of sp/pp")
    stacked = stack_layer_params(layers)
    aux = {"mask": mask} if mask is not None else {}
    layer_fn = _make_layer_fn(cfg)

    def stage_fn(stage_p, xb, auxb, stage_idx, mub_idx):
        maskb = auxb.get("mask")
        key = jax.random.fold_in(jax.random.fold_in(rng, stage_idx),
                                 mub_idx)
        aux_sum = jnp.zeros((), jnp.float32)
        per_stage = jax.tree_util.tree_leaves(stage_p)[0].shape[0]
        for i in range(per_stage):
            layer_i = jax.tree_util.tree_map(lambda a: a[i], stage_p)
            key, sub = jax.random.split(key)
            xb, a = layer_fn(xb, layer_i, maskb, cfg, train, sub, None)
            aux_sum = aux_sum + a
        return xb, aux_sum

    return pipeline_apply(stage_fn, stacked, x, aux, mesh=mesh,
                          axis="pp", n_microbatches=cfg.pp_microbatches,
                          has_aux=True)


def _act_spec(mesh):
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import live_axis
    # constrain only along axes that actually partition — a trivial-axis
    # constraint materializes a copy per constraint on some PjRt
    # backends, measured 10-15x on the scanned BERT train step here
    # (docs/perf.md "Methodology")
    return P(live_axis(mesh, "dp"), live_axis(mesh, "sp"), None)


def _constrain_act(x, mesh):
    """Apply the activation sharding constraint, skipping trivial ones."""
    import jax
    spec = _act_spec(mesh)
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def _mlm_head_loss(outer, x, batch, cfg: TransformerConfig):
    """MLM head + masked-NLL on an encoder output ``x`` — the head and
    loss arithmetic of :func:`forward_with_aux`/:func:`mlm_loss` over
    the non-layer params only.  Factored out for the bucketed-overlap
    train step, whose manual backward needs the head as a separate
    vjp group (the weight-tied ``tok_emb`` collects grads from both
    the embed and head groups)."""
    import jax
    import jax.numpy as jnp

    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("mlm_head"):
        h = jax.nn.gelu(x @ outer["mlm_dense"].astype(cdt),
                        approximate=True)
        h = _layer_norm(h, outer["mlm_ln"]["g"].astype(cdt),
                        outer["mlm_ln"]["b"].astype(cdt))
        logits = (h @ outer["tok_emb"].T.astype(cdt)
                  + outer["mlm_bias"].astype(cdt)).astype(jnp.float32)
    return _masked_nll(logits, batch["labels"])


def _masked_nll(logits, labels):
    """Mean token NLL over the masked positions (``labels`` < 0 ≡
    unmasked), under the ``loss`` scope."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("loss"):
        valid = (labels >= 0)
        safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tok_loss = -jnp.take_along_axis(logp, safe[..., None],
                                        axis=-1)[..., 0]
        tok_loss = jnp.where(valid, tok_loss, 0.0)
        return tok_loss.sum() / jnp.maximum(valid.sum(), 1)


def _bucketed_loss_and_grads(params, batch, rng, cfg: TransformerConfig,
                             mesh, grad_shardings, bucketed):
    """Manual scan-carried forward/backward for the FSDP step's
    bucketed-overlap mode (ROADMAP item 4, the training half).

    The layer stack runs as ONE ``lax.scan`` forward (saving each
    layer's input — the remat residual) and one reverse scan backward
    in which every iteration re-runs its layer's vjp from the saved
    input.  With ``bucketed=True`` each layer's grads are pinned to
    their FSDP sharding INSIDE the reverse-scan body, so the dp
    reduce-scatter for layer L is issued the moment L's grads
    materialize — a per-layer-bucket collective overlapped with the
    backward of layer L-1, instead of one fused post-backward sync
    XLA schedules wherever it likes.  With ``bucketed=False`` (the
    "fused" comparator) the SAME scan graph defers the whole
    constraint to after the scan — the only difference between the
    two programs is collective placement, which is why the
    bucketed-vs-fused loss trajectory is gated BIT-identical
    (``tests/test_train_scale.py``; the reduce-scatter computes the
    same order-free per-shard sum either way).

    Refuses MoE / seq-parallel / pipeline configs — the scan needs a
    homogeneous dense layer stack and no nested shard_map.
    """
    import jax
    import jax.numpy as jnp
    from ..parallel.pipeline import stack_layer_params

    cdt = jnp.dtype(cfg.dtype)
    tokens = batch["tokens"]
    T_len = tokens.shape[1]
    mask = batch.get("mask")
    type_ids = batch.get("type_ids")
    n = cfg.n_layers

    outer = {k: v for k, v in params.items() if k != "layers"}
    stacked = stack_layer_params(params["layers"])
    # per-layer dropout keys: the SAME split sequence forward_with_aux
    # walks, stacked as raw key data so they ride the scan as an array
    # operand (unused ops when dropout=0, like the sequential path)
    subs = []
    r = rng
    for _ in range(n):
        r, sub = jax.random.split(r)
        subs.append(jax.random.key_data(sub))
    keys = jnp.stack(subs)
    impl = "rbg" if (cfg.fast_rng and cfg.dropout > 0) \
        else "threefry2x32"

    def embed_fn(outer):
        with jax.named_scope("embed"):
            x = outer["tok_emb"][tokens].astype(cdt)
            x = x + outer["pos_emb"][:T_len][None].astype(cdt)
            if type_ids is not None:
                x = x + outer["type_emb"][type_ids].astype(cdt)
            x = _layer_norm(x, outer["emb_ln"]["g"].astype(cdt),
                            outer["emb_ln"]["b"].astype(cdt))
        if mesh is not None:
            x = _constrain_act(x, mesh)
        return x

    def layer_body(x, layer, kd):
        key = jax.random.wrap_key_data(kd, impl=impl)
        x, _ = _encoder_layer(x, layer, mask, cfg, True, key, mesh)
        if mesh is not None:
            x = _constrain_act(x, mesh)
        return x

    # ---- forward: one scan over the stack, saving layer INPUTS (the
    # backward's recompute residual — the remat="nothing" memory
    # profile, carried explicitly instead of via jax.checkpoint) ----
    x0, embed_vjp = jax.vjp(embed_fn, outer)

    def fwd_body(x, sl):
        layer, kd = sl
        return layer_body(x, layer, kd), x

    xL, xs = jax.lax.scan(fwd_body, x0, (stacked, keys))

    loss, head_vjp = jax.vjp(
        lambda o, x: _mlm_head_loss(o, x, batch, cfg), outer, xL)
    d_outer_head, dx = head_vjp(jnp.ones((), loss.dtype))

    layer_sh = (jax.tree_util.tree_map(lambda s: s,
                                       grad_shardings["layers"][0])
                if grad_shardings is not None else None)

    def bwd_body(dx, sl):
        layer, kd, x_in = sl
        _, vjp = jax.vjp(lambda xx, ll: layer_body(xx, ll, kd),
                         x_in, layer)
        dx_prev, dlayer = vjp(dx)
        if bucketed and layer_sh is not None:
            # THE lever: pin this layer bucket's grads to their FSDP
            # shards here, inside the reverse scan, so its dp
            # reduce-scatter issues while the previous layer's
            # backward still runs
            dlayer = jax.lax.with_sharding_constraint(dlayer,
                                                      layer_sh)
        return dx_prev, dlayer

    dx0, dlayers = jax.lax.scan(bwd_body, dx, (stacked, keys, xs),
                                reverse=True)
    d_outer_emb = embed_vjp(dx0)[0]
    d_outer = jax.tree_util.tree_map(jnp.add, d_outer_head,
                                     d_outer_emb)
    grads = dict(d_outer)
    grads["layers"] = [
        jax.tree_util.tree_map(lambda a, i=i: a[i], dlayers)
        for i in range(n)]
    return loss, grads


def mlm_loss(params, batch, rng, cfg: TransformerConfig, mesh=None):
    """Masked-LM pretraining objective (BERT): mean token NLL over the
    masked positions (``labels`` -100 ≡ unmasked) plus the MoE
    auxiliary loss.  ONE implementation reused by every training path
    — the jitted mesh step below, the per-device-replica KVStore path
    (``benchmark/train_scale_bench.py`` computes per-shard grads of
    THIS function and syncs them through the ICI-allreduce store), and
    the bit-identity tests — so the objectives cannot drift apart."""
    logits, aux = forward_with_aux(
        params, batch["tokens"], cfg,
        type_ids=batch.get("type_ids"),
        mask=batch.get("mask"), train=True, rng=rng, mesh=mesh)
    return _masked_nll(logits, batch["labels"]) + cfg.moe_aux_weight * aux


def train_step_input_specs(cfg: TransformerConfig, dp="dp", tp=None,
                           fsdp=True):
    """DECLARED train-step input shardings, mesh-free (the serving
    engine's ``step_input_specs`` convention, round 14, extended to
    the train half this round): ``(param_specs_tree, batch_specs,
    rng_spec)`` for the state/batch/rng arguments of the step
    ``make_train_step`` builds.

    With ``fsdp=True`` params follow the FSDP rule-table composition
    (``parallel/fsdp.py`` — dp composed onto the megatron table);
    otherwise params replicate w.r.t. dp (plain data parallelism) and
    carry only the megatron tp entries.  Optimizer-state leaves are
    not declared here: param-shaped moments take their param's spec
    verbatim and non-param leaves (step counts) replicate — the
    ``mesh.zero1_sharding``/``init_sharded_opt_state`` contract,
    asserted against live ``addressable_shards`` in
    ``tests/test_train_scale.py``.  graphlint's sharding-readiness
    audit verifies THIS declaration against its own shape-aware
    derivation from the megatron table (docs/sharding_readiness.md)."""
    from jax.sharding import PartitionSpec as P

    if fsdp:
        from ..parallel.fsdp import fsdp_param_specs
        pspecs = fsdp_param_specs(cfg, dp=dp, tp=tp)
    else:
        pspecs = param_specs(cfg, tp=tp)
    row = P(dp, None)
    batch = {"tokens": row, "labels": row, "mask": row,
             "type_ids": row}
    return pspecs, batch, P()


def train_step_output_specs(cfg: TransformerConfig, dp="dp", tp=None,
                            fsdp=True):
    """DECLARED output shardings ``(param_specs_tree, loss_spec)``:
    updated params keep EXACTLY the input placement (the donation
    contract — a spec change here would force a reshard every step
    and break the in-place state update graphlint's donation rule
    pins), the loss replicates."""
    from jax.sharding import PartitionSpec as P

    pspecs, _, _ = train_step_input_specs(cfg, dp=dp, tp=tp, fsdp=fsdp)
    return pspecs, P()


class _SpannedStep:
    """The compiled training step as its callers dispatch it: every call
    is one ``train.step`` ``profiler.span`` (``os=True``; args ``step``,
    this callable's own count of dispatches, and ``steps`` for a
    ``scan_steps`` loop) around the dispatch.  The span ends when the
    dispatch returns, not when the device has run the step: nothing
    waits here, and the step's cadence is read from one span's start to
    the next (``profiler.stalls("train.step")``).  Everything else a
    caller reaches on the jitted function (``lower``, ``trace``,
    ``eval_shape``, ...) is the jitted function's own."""

    def __init__(self, jitted, **args):
        self._jitted = jitted
        self._args = args
        self._dispatched = 0

    def __call__(self, state, batch, rng):
        with profiler.span("train.step", os=True, step=self._dispatched,
                           **self._args):
            self._dispatched += 1
            return self._jitted(state, batch, rng)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def make_train_step(cfg: TransformerConfig, mesh=None, learning_rate=1e-4,
                    weight_decay=0.01, shard_optimizer=False,
                    scan_steps=None, scan_superbatch=False, fsdp=False,
                    bucket_overlap=False):
    """Build (init_state, step) for MLM pretraining.

    ``step(state, batch, rng) -> (state, loss)`` is jitted; with a mesh it
    is jitted with NamedShardings so GSPMD places tp/dp/sp collectives.
    ``batch`` = dict(tokens, labels, weights) — labels -100 ≡ unmasked.

    ``scan_steps=K`` returns a device-side training loop instead: one
    jitted ``lax.scan`` dispatch runs K steps and returns the K per-step
    losses (one dispatch and one host sync for K steps; the per-dispatch
    cost on the current machine is not measured — ROADMAP A2). With ``scan_superbatch=True`` every
    batch leaf carries a leading K axis and step ``i`` consumes slice
    ``i``; otherwise the same batch is reused each step (synthetic
    benchmarking). The step rng is folded per step either way.

    ``shard_optimizer=True`` shards the Adam moment buffers over the
    mesh's ``dp`` axis (ZeRO-1; SURVEY.md §2.4 maps the reference's
    server-side PS optimizer update to exactly this): each dp shard
    owns 1/dp of the optimizer state, GSPMD inserts the
    reduce-scatter/all-gather pair around the update.

    ``fsdp=True`` (round 19, ROADMAP item 5) shards the PARAMS as
    well, by the ``parallel/fsdp.py`` rule table composed onto the
    megatron specs: each device holds exactly 1/dp of every weight
    and every param-shaped optimizer moment.  GSPMD all-gathers each
    weight on use in the forward/backward and — because the grads are
    pinned to the same sharded specs — lowers the gradient sync to a
    reduce-scatter fused straight into the sharded optimizer update
    (no replicated grad ever materializes).  Requires a mesh with a
    live ``dp`` axis; implies ``shard_optimizer``.

    ``bucket_overlap=True`` (round 21, ROADMAP item 4's training
    half; requires ``fsdp=True``) swaps the autodiff backward for the
    scan-carried manual one (:func:`_bucketed_loss_and_grads`): the
    layer stack runs as one forward scan + one reverse scan, and each
    layer's grads are pinned to their FSDP shards INSIDE the reverse
    scan body, so per-layer-bucket dp reduce-scatters issue as each
    layer's grads materialize instead of one fused post-backward
    sync.  ``bucket_overlap="fused"`` builds the SAME scan graph with
    the constraint deferred to after the scan — the bit-identity
    comparator the ``test_train_scale.py`` hard gate pins the
    bucketed path against.  ``False`` (default) keeps the round-20
    autodiff path untouched.  Dense stacks only (no MoE / pp /
    seq-parallel — the scan needs homogeneous layers).
    """
    import jax
    import jax.numpy as jnp
    import optax

    tx = optax.adamw(learning_rate, weight_decay=weight_decay,
                     b1=0.9, b2=0.999, eps=1e-6)

    def loss_fn(params, batch, rng):
        return mlm_loss(params, batch, rng, cfg, mesh=mesh)

    if bucket_overlap not in (False, True, "fused"):
        from ..base import MXNetError
        raise MXNetError(
            "make_train_step: bucket_overlap must be False, True, or "
            "'fused', got %r" % (bucket_overlap,))
    if bucket_overlap:
        from ..base import MXNetError
        if not fsdp:
            raise MXNetError(
                "make_train_step: bucket_overlap requires fsdp=True "
                "(the per-layer buckets ARE the FSDP reduce-scatters)")
        if cfg.n_experts or cfg.seq_parallel or (
                mesh is not None and "pp" in mesh.axis_names
                and mesh.shape["pp"] > 1):
            raise MXNetError(
                "make_train_step: bucket_overlap needs a homogeneous "
                "dense layer stack with no nested shard_map — MoE / "
                "seq_parallel / pp configs use bucket_overlap=False")

    if fsdp:
        from ..base import MXNetError
        from ..parallel.mesh import live_axis
        from ..parallel.fsdp import fsdp_param_shardings
        if mesh is None or live_axis(mesh, "dp") is None:
            raise MXNetError(
                "make_train_step(fsdp=True) needs a mesh with a live "
                "'dp' axis (size > 1); got %s"
                % (dict(mesh.shape) if mesh is not None else None))
        grad_shardings = fsdp_param_shardings(cfg, mesh)
        shard_optimizer = True
    else:
        grad_shardings = (param_shardings(cfg, mesh)
                          if mesh is not None and mesh.size > 1 else None)

    # NOTE (round 5): constraining grads to the ZeRO-1 dp-composed
    # sharding here instead was tried and REVERTED — under dp·sp·tp it
    # fights the shardings the backward propagates and retriggers
    # "Involuntary full rematerialization" (caught by
    # test_multichip_dryrun_no_involuntary_remat).  It is also
    # unnecessary: with the moments sharded, GSPMD already consumes
    # the grad psum shard-wise under plain dp — the reduce-scatter-
    # equivalent pattern — as pinned by tests/test_collective_matrix.py.

    def step(state, batch, rng):
        params, opt_state = state
        if cfg.fast_rng and cfg.dropout > 0:
            # hardware RBG for dropout mask bits (see TransformerConfig
            # .fast_rng); derived from the caller's key so the stream
            # stays deterministic per (key, step)
            rng = jax.random.wrap_key_data(
                jax.random.bits(rng, (4,), "uint32"), impl="rbg")
        if bucket_overlap:
            loss, grads = _bucketed_loss_and_grads(
                params, batch, rng, cfg, mesh, grad_shardings,
                bucketed=bucket_overlap is not False
                and bucket_overlap != "fused")
            if grad_shardings is not None:
                if bucket_overlap == "fused":
                    # the comparator: same scan graph, the whole grad
                    # tree pinned in one post-backward constraint
                    grads = jax.lax.with_sharding_constraint(
                        grads, grad_shardings)
                else:
                    # layer buckets were pinned inside the reverse
                    # scan; only the small outer group (embeddings +
                    # head) still needs its constraint
                    outer_sh = {k: v for k, v in grad_shardings.items()
                                if k != "layers"}
                    outer_g = {k: v for k, v in grads.items()
                               if k != "layers"}
                    outer_g = jax.lax.with_sharding_constraint(
                        outer_g, outer_sh)
                    grads = dict(outer_g, layers=grads["layers"])
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch,
                                                      rng)
            if grad_shardings is not None:
                # pin grads to the params' own sharding before the
                # update.  Without this, grads reach tx.update with
                # whatever partial sharding GSPMD propagated out of the
                # backward (e.g. a pp dim from the pipeline shard_map),
                # and the transition to the ZeRO-1 dp-sharded moments
                # triggers "Involuntary full rematerialization"
                # (replicate-then-reshard).  An explicit all-gather
                # here is the same data movement without the wasted
                # remat.
                grads = jax.lax.with_sharding_constraint(
                    grads, grad_shardings)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return (params, opt_state), loss

    def init_state(key):
        params = init_params(key, cfg)
        # commit shardings only on a real multi-device mesh: arrays
        # committed to a trivial (1-device) mesh route execution through
        # the SPMD-partitioned path, which buys nothing on one device
        # (rounds 1-5 measured it much slower; not re-measured on the
        # current machine)
        shardings = grad_shardings      # same tree, same guard
        if shardings is not None:
            # host_staged_put: cross-process shardings need host-numpy
            # staging (init_params is deterministic per key, so every
            # process holds identical values)
            from ..parallel.multihost import host_staged_put
            params = jax.tree_util.tree_map(host_staged_put, params,
                                            shardings)
        if shard_optimizer and mesh is not None \
                and "dp" in mesh.axis_names and mesh.shape["dp"] > 1:
            # materialize the moments directly into their shards —
            # init-then-reshard would peak at full replicated size,
            # defeating the reason to enable ZeRO-1.  Pass the param
            # shardings so dp composes with tp instead of fighting it
            # (see zero1_sharding).
            from ..parallel.mesh import init_sharded_opt_state
            opt_state = init_sharded_opt_state(
                tx, params, mesh, param_shardings=shardings)
        else:
            opt_state = tx.init(params)
        return (params, opt_state)

    if fsdp:
        # jit with EXPLICIT state shardings: with only donate_argnums
        # the lowering defers input placements and cannot prove the
        # in-place aliasing; declaring (params, opt) shardings in/out
        # makes donation provable at lowering — gated by graphlint's
        # graph-donation rule on the bert_train_step_fsdp entries.
        # Batch/rng stay unspecified (None = follow the arrays).
        from ..parallel.mesh import opt_state_shardings
        pshapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        state_shardings = (grad_shardings, opt_state_shardings(
            tx, pshapes, mesh, param_shardings=grad_shardings))
        jit_kw = dict(donate_argnums=(0,),
                      in_shardings=(state_shardings, None, None),
                      out_shardings=(state_shardings, None))
    else:
        jit_kw = dict(donate_argnums=(0,))

    if scan_steps is None:
        return init_state, _SpannedStep(jax.jit(step, **jit_kw))

    def multi(state, batch, rng):
        def body(st, i):
            b = (jax.tree_util.tree_map(lambda x: x[i], batch)
                 if scan_superbatch else batch)
            return step(st, b, jax.random.fold_in(rng, i))
        return jax.lax.scan(body, state, jnp.arange(scan_steps))

    return init_state, _SpannedStep(jax.jit(multi, **jit_kw),
                                    steps=scan_steps)



