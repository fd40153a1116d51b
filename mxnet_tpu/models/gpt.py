"""Decoder-only (GPT-style) language model family.

No in-tree reference counterpart (MXNet 1.x shipped its LMs via
GluonNLP scripts); this reuses the flagship transformer core
(models/transformer.py) with ``causal=True``, a shifted next-token loss,
and an incremental KV-cache decode loop for generation — the decode
path is a ``lax.scan`` over positions with per-layer key/value caches,
so sampling jits into one XLA program.

The same tp/dp/sp/pp/ep mesh machinery applies: ``make_train_step``
delegates to the transformer's, with labels derived by shifting tokens.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from . import transformer as T

__all__ = ["gpt_config", "gpt_tiny", "init_params", "forward",
           "make_train_step", "generate", "generate_speculative",
           "quantize_decode_params", "decode_param_specs",
           "draft_slice_params"]


def gpt_config(**kw):
    """A TransformerConfig preset for decoder-only LM use."""
    base = dict(causal=True, type_vocab_size=1)
    base.update(kw)
    return T.TransformerConfig(**base)


def gpt_tiny(**kw):
    base = dict(vocab_size=1024, max_len=128, d_model=64, n_heads=4,
                n_layers=2, d_ff=128, causal=True, type_vocab_size=1)
    base.update(kw)
    return T.TransformerConfig(**base)


init_params = T.init_params
forward = T.forward


def make_train_step(cfg, mesh=None, learning_rate=1e-4,
                    weight_decay=0.01):
    """(init_state, step) for causal-LM training; ``step(state, batch,
    rng)`` where batch = dict(tokens[, mask]) — labels are the tokens
    shifted left (next-token prediction), last position ignored."""
    import jax.numpy as jnp

    if not cfg.causal:
        cfg = dataclasses.replace(cfg, causal=True)
    init_state, mlm_step = T.make_train_step(
        cfg, mesh=mesh, learning_rate=learning_rate,
        weight_decay=weight_decay)

    def step(state, batch, rng):
        tokens = batch["tokens"]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(tokens.shape, bool)
        labels = jnp.concatenate(
            [tokens[:, 1:],
             jnp.full((tokens.shape[0], 1), -100, tokens.dtype)],
            axis=1)
        # padded positions (shifted mask 0) must not contribute to the
        # next-token loss
        shifted_mask = jnp.concatenate(
            [mask[:, 1:], jnp.zeros((tokens.shape[0], 1), bool)], axis=1)
        labels = jnp.where(shifted_mask, labels, -100)
        lm_batch = {"tokens": tokens, "labels": labels, "mask": mask}
        return mlm_step(state, lm_batch, rng)

    return init_state, step


# ---------------------------------------------------------------------------
# incremental decoding
# ---------------------------------------------------------------------------

def quantize_decode_params(params):
    """Weight-only int8 quantization of the decode-path matmul weights.

    Per-output-channel symmetric s8 (the scheme `ops/quantization.py`'s
    MXU dots use): each 2-D weight becomes ``{"q": int8, "s": f32
    per-channel scale}`` with ``W ≈ q * s``.  Decode at small batch is
    weight-streaming-heavy (docs/hbm_bandwidth.md: bf16 decode runs
    ~4.5× below the HBM floor, and ~220 MB of the traffic is weights) —
    halving the weight bytes halves that term.  Activations stay bf16;
    the dequant convert fuses into the matmul operand, so int8 streams
    from HBM and the MXU still runs bf16.

    Biases, layer norms, pos_emb, and MoE blocks stay float.
    ``tok_emb`` is quantized per-ROW (vocab) so one table serves both
    the embedding lookup (``q[t] * s[t]``) and the logits projection
    (``h @ q.T * s``).
    """
    import jax.numpy as jnp

    def q_cols(w):                       # (in, out): per-column scale
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=0) / 127.0, 1e-8)
        qw = jnp.clip(jnp.round(w / s[None, :]), -127, 127
                      ).astype(jnp.int8)
        return {"q": qw, "s": s.astype(jnp.float32)}

    def q_rows(w):                       # (vocab, d): per-row scale
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=1) / 127.0, 1e-8)
        qw = jnp.clip(jnp.round(w / s[:, None]), -127, 127
                      ).astype(jnp.int8)
        return {"q": qw, "s": s.astype(jnp.float32)}

    out = dict(params)
    out["tok_emb"] = q_rows(params["tok_emb"])
    out["mlm_dense"] = q_cols(params["mlm_dense"])
    layers = []
    for layer in params["layers"]:
        nl = dict(layer)
        # attention projections exist in every layer (MoE swaps only
        # the FFN); gate just the dense-FFN weights on "moe"
        for k in ("wq", "wk", "wv", "wo"):
            nl[k] = q_cols(layer[k])
        if "moe" not in layer:
            for k in ("w1", "w2"):
                nl[k] = q_cols(layer[k])
        layers.append(nl)
    out["layers"] = layers
    return out


def decode_param_specs(params, cfg, tp="tp"):
    """Megatron partition rules for the DECODE param tree — float or
    ``quantize_decode_params`` weight-only int8 — as a mesh-free
    ``PartitionSpec`` pytree matching ``params`` leaf-for-leaf.

    Float leaves take their ``transformer.param_specs`` rule verbatim.
    int8 ``{"q", "s"}`` leaves DERIVE theirs from the float weight's
    rule (the ``docs/sharding_readiness.md`` derivation, now live
    code): ``q`` keeps the full 2-D rule (same shape as the float
    weight), and the 1-D scale ``s`` takes the rule entry of the dim
    it indexes — per-COLUMN for the matmul weights (``q_cols``: s is
    (out,), rule entry 1) and per-ROW for the embedding table
    (``q_rows``: s is (vocab,), rule entry 0).  So a ``P(None, tp)``
    weight yields ``s = P(tp)`` (w1/wq/…), a ``P(tp, None)`` weight
    yields a replicated ``s`` (wo/w2 — the out dim is unsharded), and
    ``tok_emb``'s per-row scales replicate.

    The serving engine binds these to its mesh
    (``serving/engine.py step_input_specs``); heads partition because
    the qkv out-dims shard over ``tp`` and ``d_model/n_heads`` stays
    whole — attention is head-local (softmax and the int8-KV quant
    stats reduce over head_dim only, no cross-head collective), and
    the one cross-device reduce is the ``P(tp, None)`` output
    projection GSPMD already handles."""
    from jax.sharding import PartitionSpec as P

    # ep=None: the serving mesh has no expert axis — MoE layers (when
    # present) declare experts replicated and only their FFN hidden
    # dim tp-sharded, so the specs bind over a 'tp'-only mesh
    base = T.param_specs(cfg, tp=tp, ep=None)

    def derive(leaf, spec, per_row=False):
        if isinstance(leaf, dict) and "q" in leaf and "s" in leaf:
            entries = tuple(spec) + (None,) * (2 - len(tuple(spec)))
            return {"q": spec,
                    "s": P(entries[0] if per_row else entries[1])}
        return spec

    out = {k: derive(params[k], base[k], per_row=(k == "tok_emb"))
           for k in params if k != "layers"}
    layers = []
    for layer, rules in zip(params["layers"], base["layers"]):
        layers.append({k: derive(layer[k], rules[k])
                       for k in layer})
    out["layers"] = layers
    return out


def _wmm(x, w, cdt):
    """x @ W for a float or weight-only-int8 ({"q","s"}) weight."""
    if isinstance(w, dict) and "q" in w:
        return (x @ w["q"].astype(cdt)) * w["s"].astype(cdt)
    return x @ w.astype(cdt)


def _embed(params, tokens, cdt):
    """Token embedding lookup for float or weight-only-int8 tables
    (shared by the prefill pass and the decode step)."""
    emb = params["tok_emb"]
    if isinstance(emb, dict):
        return emb["q"][tokens].astype(cdt) * \
            emb["s"][tokens].astype(cdt)[..., None]
    return emb[tokens].astype(cdt)


def _qkv(layer, x, cdt):
    """Fused QKV matmul (one (D, 3D) weight; the concat is
    loop/call-invariant so XLA hoists it) for float or int8 weights,
    bias included.  Shared by prefill and decode."""
    import jax.numpy as jnp
    wq, wk, wv = layer["wq"], layer["wk"], layer["wv"]
    if isinstance(wq, dict):
        qkv = (x @ jnp.concatenate(
            [wq["q"], wk["q"], wv["q"]], axis=1).astype(cdt)) * \
            jnp.concatenate([wq["s"], wk["s"], wv["s"]]).astype(cdt)
    else:
        qkv = x @ jnp.concatenate([wq, wk, wv], axis=1).astype(cdt)
    return qkv + jnp.concatenate(
        [layer["bq"].astype(cdt), layer["bk"].astype(cdt),
         layer["bv"].astype(cdt)])


def _lm_head(params, x, cdt):
    """gelu(mlm_dense) → LN → tied-embedding logits (+bias), f32 out.
    Shared by prefill and decode; handles the int8 embedding table's
    per-row scales on the output."""
    import jax
    import jax.numpy as jnp
    h = jax.nn.gelu(_wmm(x, params["mlm_dense"], cdt),
                    approximate=True)
    h = T._layer_norm(h, params["mlm_ln"]["g"].astype(cdt),
                      params["mlm_ln"]["b"].astype(cdt))
    emb = params["tok_emb"]
    if isinstance(emb, dict):
        logits = (h @ emb["q"].T.astype(cdt)).astype(jnp.float32) * \
            emb["s"][None, :]
    else:
        logits = (h @ emb.T.astype(cdt)).astype(jnp.float32)
    return logits + params["mlm_bias"].astype(jnp.float32)


def _kv_quantize(k, v):
    """Per-(row, token) symmetric s8 KV quantization over the head dim
    — the int8-KV cache layout (round 4): a fused k|v int8 buffer plus
    an f32 scale pair per (row, token).  Rank-agnostic (k/v may be
    (R, dh) or (R, S, dh)); returns (kv_q int8 (..., 2*dh),
    scales f32 (..., 2)).  Shared by prefill, both contiguous decode
    steps, and the paged serving step.

    The quantization accumulates in f32 (round 13, graphlint
    ``graph-dtype-drift``): k/v upcast ONCE at entry — the declared
    accumulation point, last dim = head_dim — so the scale and the
    quantization grid are f32-exact.  The previous version divided in
    bf16 and only upcast the stacked result, leaving the stored "f32"
    scales with bf16 mantissas (up to ~0.4% grid error) — the late
    cosmetic upcast graphlint now flags."""
    import jax.numpy as jnp
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    sk = jnp.maximum(jnp.max(jnp.abs(kf), axis=-1) / 127.0, 1e-8)
    sv = jnp.maximum(jnp.max(jnp.abs(vf), axis=-1) / 127.0, 1e-8)
    kq = jnp.clip(jnp.round(kf / sk[..., None]), -127, 127
                  ).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vf / sv[..., None]), -127, 127
                  ).astype(jnp.int8)
    return (jnp.concatenate([kq, vq], axis=-1),
            jnp.stack([sk, sv], axis=-1))


# ------------------------------------------------ the serving protocol --
# What ``serving/engine.py``'s step program is built from, for this
# family: it asks the model for its embedding, its block and its
# sampling rows' logits, and hands the block the attention over its own
# paged K/V (``attend(q, k, v)`` writes the rows' keys and values, then
# reads each row's sequence back).  ``models/falcon_h1.py`` has the same
# three under the same names.

def serve_embed(params, cfg, tokens, row_pos):
    """(T,) ids at positions ``row_pos`` -> (T, D) rows."""
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    x = _embed(params, tokens, cdt)                # (T, D)
    x = x + params["pos_emb"][row_pos].astype(cdt)
    return T._layer_norm(x, params["emb_ln"]["g"].astype(cdt),
                         params["emb_ln"]["b"].astype(cdt))


def serve_block(layer, cfg, x, row_pos, attend, state=None, counts=None):
    """One post-LN block on (T, D) rows; ``attend(q, k, v)`` over
    (T, H, dh) each returns (T, H, dh) float32.  ``state`` is the slot
    state of families that keep one and ``counts`` the step counters of
    families that count; this one keeps pages alone and counts
    nothing."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    D, H = cfg.d_model, cfg.n_heads
    dh = D // H
    n = x.shape[0]

    def dn(w):
        return w.astype(cdt)
    with jax.named_scope("qkv"):
        qkv = _qkv(layer, x, cdt)                  # (T, 3D)
        q = qkv[:, :D].reshape(n, H, dh)
        k = qkv[:, D:2 * D].reshape(n, H, dh)
        v = qkv[:, 2 * D:].reshape(n, H, dh)
    attn = attend(q, k, v)
    with jax.named_scope("attn_out"):
        attn = attn.reshape(n * H, dh)             # (T*H, dh) f32
        attn = attn.astype(cdt)
        attn = _wmm(attn.reshape(n, D), layer["wo"], cdt) + \
            dn(layer["bo"])
        x = T._layer_norm(x + attn, dn(layer["ln1"]["g"]),
                          dn(layer["ln1"]["b"]))
    with jax.named_scope("ffn"):
        if "moe" in layer:
            from ..parallel.moe import moe_ffn
            h, _ = moe_ffn(x[:, None, :], layer["moe"],
                           n_experts=cfg.n_experts,
                           top_k=cfg.expert_top_k,
                           capacity_factor=cfg.capacity_factor,
                           dtype=cdt)
            h = h[:, 0, :]
        else:
            h = jax.nn.gelu(
                _wmm(x, layer["w1"], cdt) + dn(layer["b1"]),
                approximate=True)
            h = _wmm(h, layer["w2"], cdt) + dn(layer["b2"])
        return T._layer_norm(x + h, dn(layer["ln2"]["g"]),
                             dn(layer["ln2"]["b"]))


def serve_logits(params, cfg, x, slot_rows):
    """Float32 logits at the (S, n) sampling rows: (S, n, V).  The head
    runs over all rows and the sampling rows are picked from it."""
    import jax.numpy as jnp
    return _lm_head(params, x, jnp.dtype(cfg.dtype))[slot_rows]


def _attend_rows(q, ckv, cs, pos, dh):
    """Single-token attention over a fused (R, L, 2*dh) KV view.

    q: (R, dh); pos: scalar or (R,) per-row absolute position — each
    row attends to view slots <= its pos.  cs: the int8-KV (R, L, 2)
    scale view, or None for a float view.  Returns (R, dh) f32.

    The view is LAYOUT-AGNOSTIC: the contiguous path passes the cache
    buffer itself ((B*H, L, 2*dh) fused batch·head rows — the
    formulation that streams caches at HBM bandwidth, see the round-4
    notes in ``_decode_one``), the paged path passes a block-table
    gather of the page pool (mxnet_tpu/serving/) — so both share this
    attention code, and per-row ``pos`` is what lets one program mix
    rows at different sequence positions (continuous batching).

    int8 views fold the dequant scales into the dots: the k scale
    multiplies the scores (contraction is over dh), the v scale folds
    into the softmax weights before the second dot."""
    import jax
    import jax.numpy as jnp
    cdt = q.dtype
    L = ckv.shape[1]
    if cs is not None:
        s = jax.lax.dot_general(
            ckv[:, :, :dh].astype(cdt), q,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # (R, L)
        s = s * cs[:, :, 0] / jnp.sqrt(jnp.float32(dh))
    else:
        s = jax.lax.dot_general(
            ckv[:, :, :dh], q, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # (R, L)
        s = s / jnp.sqrt(jnp.float32(dh))
    valid = jnp.arange(L)[None, :] <= \
        jnp.expand_dims(jnp.asarray(pos), -1)
    s = jnp.where(valid, s, -1e30)
    if cs is not None:
        p = jax.nn.softmax(s, axis=-1)
        attn = jax.lax.dot_general(
            (p * cs[:, :, 1]).astype(cdt),
            ckv[:, :, dh:].astype(cdt),
            (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # (R, dh)
    else:
        p = jax.nn.softmax(s, axis=-1).astype(cdt)
        attn = jax.lax.dot_general(
            p, ckv[:, :, dh:], (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # (R, dh)
    return attn


def _attend_block(q, ckv, cs, pos, dh):
    """Block (multi-token) attention over a fused (R, L, 2*dh) KV view:
    q is (R, S, dh) occupying positions [pos, pos+S) — block row i
    attends to view slots <= pos+i.  cs as in ``_attend_rows``.
    Returns (R, S, dh) f32.  The speculative-verify forward and the
    contiguous prefill-by-block path ride this."""
    import jax
    import jax.numpy as jnp
    cdt = q.dtype
    L = ckv.shape[1]
    S = q.shape[1]
    if cs is not None:
        s = jax.lax.dot_general(
            ckv[:, :, :dh].astype(cdt), q,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # (R, L, S)
        s = s * cs[:, :, 0][:, :, None] / jnp.sqrt(jnp.float32(dh))
    else:
        s = jax.lax.dot_general(
            ckv[:, :, :dh], q, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # (R, L, S)
        s = s / jnp.sqrt(jnp.float32(dh))
    valid = jnp.arange(L)[None, :, None] <= \
        pos + jnp.arange(S)[None, None, :]
    s = jnp.where(valid, s, -1e30)
    if cs is not None:
        p = jax.nn.softmax(s, axis=1)
        attn = jax.lax.dot_general(
            (p * cs[:, :, 1][:, :, None]).astype(cdt),
            ckv[:, :, dh:].astype(cdt),
            (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # (R, S, dh)
    else:
        p = jax.nn.softmax(s, axis=1).astype(cdt)
        attn = jax.lax.dot_general(
            p, ckv[:, :, dh:], (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # (R, S, dh)
    return attn


def _prefill_full(params, cfg, tokens, total, kv_int8=False):
    """Whole-prompt prefill in ONE causal forward pass (round 4; the
    scan-of-_decode_one prefill cost P sequential decoder steps — a
    single batched pass keeps the MXU busy and is O(P) faster in
    wall-clock for prompt-heavy generation).

    tokens: (B, P) int32.  Returns (last_logits (B, V) f32, caches) with
    per-layer caches sized ``total`` and positions [0, P) filled —
    exactly the state the decode scan expects.  Handles the same weight
    formats as ``_decode_one`` (float or weight-only int8) and the int8
    KV cache layout.
    """
    import jax
    import jax.numpy as jnp

    cdt = jnp.dtype(cfg.dtype)
    B, P = tokens.shape
    D, H = cfg.d_model, cfg.n_heads
    dh = D // H

    x = _embed(params, tokens, cdt)                    # (B, P, D)
    x = x + params["pos_emb"][:P].astype(cdt)[None]
    x = T._layer_norm(x, params["emb_ln"]["g"].astype(cdt),
                      params["emb_ln"]["b"].astype(cdt))

    caches = []
    for layer in params["layers"]:
        def dn(w):
            return w.astype(cdt)
        qkv = _qkv(layer, x, cdt)
        q = qkv[:, :, :D].reshape(B, P, H, dh)
        k = qkv[:, :, D:2 * D].reshape(B, P, H, dh)
        v = qkv[:, :, 2 * D:].reshape(B, P, H, dh)

        # the full-sequence causal attention rides the same path the
        # training forward uses — flash kernel past MXNET_FLASH_MIN_SEQ
        # (no O(P^2) materialization for long prompts), jnp reference
        # below it / off-TPU
        from ..kernels.flash_attention import flash_attention
        attn = flash_attention(q, k, v, causal=True).reshape(B, P, D)
        attn = _wmm(attn, layer["wo"], cdt) + dn(layer["bo"])
        x = T._layer_norm(x + attn, dn(layer["ln1"]["g"]),
                          dn(layer["ln1"]["b"]))
        if "moe" in layer:
            from ..parallel.moe import moe_ffn
            h, _ = moe_ffn(x, layer["moe"], n_experts=cfg.n_experts,
                           top_k=cfg.expert_top_k,
                           capacity_factor=cfg.capacity_factor,
                           dtype=cdt)
        else:
            h = jax.nn.gelu(_wmm(x, layer["w1"], cdt) + dn(layer["b1"]),
                            approximate=True)
            h = _wmm(h, layer["w2"], cdt) + dn(layer["b2"])
        x = T._layer_norm(x + h, dn(layer["ln2"]["g"]),
                          dn(layer["ln2"]["b"]))

        # fill the decode caches: (B*H, L, dh) prefix [0, P)
        kf = k.transpose(0, 2, 1, 3).reshape(B * H, P, dh)
        vf = v.transpose(0, 2, 1, 3).reshape(B * H, P, dh)
        if kv_int8:
            kvq, skv = _kv_quantize(kf, vf)
            ckv = jnp.zeros((B * H, total, 2 * dh), jnp.int8)
            ckv = jax.lax.dynamic_update_slice(ckv, kvq, (0, 0, 0))
            cs = jnp.zeros((B * H, total, 2), jnp.float32)
            cs = jax.lax.dynamic_update_slice(cs, skv, (0, 0, 0))
            caches.append({"kv": ckv, "s": cs})
        else:
            ckv = jnp.zeros((B * H, total, 2 * dh), cdt)
            ckv = jax.lax.dynamic_update_slice(
                ckv, jnp.concatenate([kf, vf], axis=2).astype(cdt),
                (0, 0, 0))
            caches.append({"kv": ckv})

    logits = _lm_head(params, x[:, -1], cdt)           # (B, V) f32
    return logits, caches


def _decode_one(params, cfg, token, pos, caches):
    """One decode step: token (B,) int32 at position pos; caches is a
    list of per-layer dicts {"kv": (B*H, L, 2*dh)} (fused batch·head
    leading dim, k and v halves of one buffer — see the layout notes in
    the attention block), or {"kv": int8, "s": (B*H, L, 2)} for the
    int8 KV path.  Returns (logits (B, V), new caches)."""
    import jax
    import jax.numpy as jnp

    cdt = jnp.dtype(cfg.dtype)
    B = token.shape[0]
    D, H = cfg.d_model, cfg.n_heads
    dh = D // H

    x = _embed(params, token, cdt)                     # (B, D)
    x = x + jax.lax.dynamic_index_in_dim(
        params["pos_emb"], pos, keepdims=False).astype(cdt)
    x = T._layer_norm(x, params["emb_ln"]["g"].astype(cdt),
                      params["emb_ln"]["b"].astype(cdt))

    new_caches = []
    for layer, cache in zip(params["layers"], caches):
        def dn(w):
            return w.astype(cdt)
        qkv = _qkv(layer, x, cdt)
        q, k, v = (qkv[:, :D].reshape(B * H, dh),
                   qkv[:, D:2 * D].reshape(B * H, dh),
                   qkv[:, 2 * D:].reshape(B * H, dh))
        # caches are (B*H, L, dh) and attention is a pair of batched
        # dot_generals over the fused batch dim.  Measured on chip
        # (benchmark/gpt_decode_probe.py, docs/perf.md "GPT decode"):
        # this formulation streams the caches at HBM bandwidth, where
        # the (B, L, H, dh)-layout einsum ran ~3x slower and the
        # per-step attention dominated decode.  bf16 dots with f32
        # accumulation — casting the cache itself to f32 materialized
        # a full copy every step.
        if "s" in cache:
            # int8 KV cache (generate(kv_int8=True)): per-(row, token)
            # symmetric s8 with the dequant folded into the dots
            # (_attend_rows).  Halves the cache stream (docs/perf.md
            # "GPT decode").
            kvq, skv = _kv_quantize(k, v)
            ckv = jax.lax.dynamic_update_index_in_dim(
                cache["kv"], kvq[:, None], pos, 1)
            cs = jax.lax.dynamic_update_index_in_dim(
                cache["s"], skv[:, None], pos, 1)
            new_caches.append({"kv": ckv, "s": cs})
            attn = _attend_rows(q, ckv, cs, pos, dh)  # (B*H, dh)
        else:
            # one fused (k|v) buffer per layer: a single DUS per step
            # and two dots over slices — 24 small DUS ops/step cost
            # ~0.1 ms of fixed overhead vs 12 (measured, docs/perf.md)
            ckv = jax.lax.dynamic_update_index_in_dim(
                cache["kv"], jnp.concatenate([k, v], axis=1)[:, None],
                pos, 1)
            new_caches.append({"kv": ckv})
            attn = _attend_rows(q, ckv, None, pos, dh)  # (B*H, dh)
        attn = attn.astype(cdt)
        attn = _wmm(attn.reshape(B, D), layer["wo"], cdt) + \
            dn(layer["bo"])
        x = T._layer_norm(x + attn, dn(layer["ln1"]["g"]),
                          dn(layer["ln1"]["b"]))
        if "moe" in layer:
            from ..parallel.moe import moe_ffn
            h, _ = moe_ffn(x[:, None, :], layer["moe"],
                           n_experts=cfg.n_experts,
                           top_k=cfg.expert_top_k,
                           capacity_factor=cfg.capacity_factor,
                           dtype=cdt)
            h = h[:, 0, :]
        else:
            h = jax.nn.gelu(_wmm(x, layer["w1"], cdt) + dn(layer["b1"]),
                            approximate=True)
            h = _wmm(h, layer["w2"], cdt) + dn(layer["b2"])
        x = T._layer_norm(x + h, dn(layer["ln2"]["g"]),
                          dn(layer["ln2"]["b"]))

    h = jax.nn.gelu(_wmm(x, params["mlm_dense"], cdt),
                    approximate=True)
    h = T._layer_norm(h, params["mlm_ln"]["g"].astype(cdt),
                      params["mlm_ln"]["b"].astype(cdt))
    emb = params["tok_emb"]
    if isinstance(emb, dict):
        # h @ W.T with W ≈ q * s[:, None]  →  (h @ q.T) * s[None, :];
        # scale applied in f32 on the small (B, V) output
        logits = (h @ emb["q"].T.astype(cdt)).astype(jnp.float32) * \
            emb["s"][None, :]
    else:
        logits = (h @ emb.T.astype(cdt)).astype(jnp.float32)
    logits = logits + params["mlm_bias"].astype(jnp.float32)
    return logits.astype(jnp.float32), new_caches


def _decode_block(params, cfg, tokens, pos, caches):
    """Batched multi-token decode step (the speculative-verify forward):
    ``tokens`` is (B, S) int32 occupying positions [pos, pos+S) — ONE
    causal forward over the block against the KV caches, instead of S
    sequential ``_decode_one`` steps.

    Writes the block's k/v into the caches at [pos, pos+S) FIRST, then
    attends with the per-row causal mask (block row i sees cache slots
    <= pos+i) — so ``_decode_one`` is exactly the S=1 special case.
    ``_decode_one`` deliberately stays a SEPARATE implementation, not
    an S=1 wrapper: its squeezed (B, D) formulation is the compiled
    shape behind the recorded on-chip decode rates, which this round
    cannot re-measure (keep the three copies of the layer block —
    here, ``_decode_one``, ``_prefill_full`` — in sync by hand).
    Returns (logits (B, S, V) f32, new caches).  Handles the same
    weight formats (float / weight-only int8) and both KV-cache layouts
    ({"kv"} float, {"kv","s"} int8) as ``_decode_one``.

    Stale cache slots beyond the committed length need no active
    rollback: the next block write at the committed position overwrites
    them before any mask ever exposes them (the speculative loop's
    rollback-by-pointer contract, tested by
    ``test_spec_rollback_forced_rejections``)."""
    import jax
    import jax.numpy as jnp

    cdt = jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    D, H = cfg.d_model, cfg.n_heads
    dh = D // H

    x = _embed(params, tokens, cdt)                    # (B, S, D)
    x = x + jax.lax.dynamic_slice(
        params["pos_emb"], (pos, 0),
        (S, D)).astype(cdt)[None]
    x = T._layer_norm(x, params["emb_ln"]["g"].astype(cdt),
                      params["emb_ln"]["b"].astype(cdt))

    new_caches = []
    for layer, cache in zip(params["layers"], caches):
        def dn(w):
            return w.astype(cdt)
        qkv = _qkv(layer, x, cdt)                      # (B, S, 3D)
        q = qkv[:, :, :D].reshape(B, S, H, dh) \
            .transpose(0, 2, 1, 3).reshape(B * H, S, dh)
        k = qkv[:, :, D:2 * D].reshape(B, S, H, dh) \
            .transpose(0, 2, 1, 3).reshape(B * H, S, dh)
        v = qkv[:, :, 2 * D:].reshape(B, S, H, dh) \
            .transpose(0, 2, 1, 3).reshape(B * H, S, dh)
        if "s" in cache:
            # int8 KV cache: per-(row, token) symmetric s8, scales
            # folded into the dots exactly as in _decode_one
            kvq, skv = _kv_quantize(k, v)
            ckv = jax.lax.dynamic_update_slice(cache["kv"], kvq,
                                               (0, pos, 0))
            cs = jax.lax.dynamic_update_slice(cache["s"], skv,
                                              (0, pos, 0))
            new_caches.append({"kv": ckv, "s": cs})
            attn = _attend_block(q, ckv, cs, pos, dh)  # (B*H, S, dh)
        else:
            ckv = jax.lax.dynamic_update_slice(
                cache["kv"],
                jnp.concatenate([k, v], axis=2).astype(cdt),
                (0, pos, 0))
            new_caches.append({"kv": ckv})
            attn = _attend_block(q, ckv, None, pos, dh)  # (B*H, S, dh)
        attn = attn.astype(cdt).reshape(B, H, S, dh) \
            .transpose(0, 2, 1, 3).reshape(B, S, D)
        attn = _wmm(attn, layer["wo"], cdt) + dn(layer["bo"])
        x = T._layer_norm(x + attn, dn(layer["ln1"]["g"]),
                          dn(layer["ln1"]["b"]))
        if "moe" in layer:
            from ..parallel.moe import moe_ffn
            h, _ = moe_ffn(x, layer["moe"], n_experts=cfg.n_experts,
                           top_k=cfg.expert_top_k,
                           capacity_factor=cfg.capacity_factor,
                           dtype=cdt)
        else:
            h = jax.nn.gelu(_wmm(x, layer["w1"], cdt) + dn(layer["b1"]),
                            approximate=True)
            h = _wmm(h, layer["w2"], cdt) + dn(layer["b2"])
        x = T._layer_norm(x + h, dn(layer["ln2"]["g"]),
                          dn(layer["ln2"]["b"]))

    return _lm_head(params, x, cdt), new_caches       # (B, S, V) f32


def draft_slice_params(params, cfg, n_layers=2):
    """Self-drafting config (b): the draft model is the target's own
    first ``n_layers`` decoder layers with the shared embedding / LM
    head — zero extra weights to train or store, shares the tokenizer
    and embedding shapes by construction.  Returns (draft_params,
    draft_cfg) for ``generate_speculative(drafter="self")``; combine
    with ``quantize_decode_params`` for a w8 draft."""
    dcfg = dataclasses.replace(cfg, n_layers=n_layers)
    dparams = dict(params)
    dparams["layers"] = list(params["layers"][:n_layers])
    return dparams, dcfg


def _draft_ngram(token_buf, n_next, K, g):
    """Zero-cost prompt-lookup drafter (drafter option (b)): find the
    most recent earlier occurrence of the last ``g`` committed tokens
    in the sequence so far and propose the K tokens that followed it
    (prompt-lookup / n-gram speculation).  Pure vectorized compares —
    no model forward.  token_buf (B, BUF) with positions [0, n_next)
    committed; falls back to repeating the last token when no match.
    Returns (B, K) int32 proposals for positions [n_next, n_next+K).

    This is the IN-XLA twin of ``serving/drafters.py ngram_draft``
    (the host-side drafter the continuous-batching engine uses for
    in-engine speculation, round 11) — semantic parity between the
    two is pinned by ``tests/test_paged_attention.py``, so accept
    rates measured through either path come from one drafting rule."""
    import jax
    import jax.numpy as jnp

    B, BUF = token_buf.shape
    W = BUF - g + 1                       # candidate window starts
    key = jax.lax.dynamic_slice(token_buf, (0, n_next - g), (B, g))
    eq = jnp.ones((B, W), bool)
    for j in range(g):
        eq = eq & (token_buf[:, j:W + j] == key[:, j:j + 1])
    # a usable match must end before the key itself and have its
    # continuation start inside the committed region
    starts = jnp.arange(W)[None, :]
    eq = eq & (starts + g < n_next)
    score = jnp.where(eq, starts, -1)
    s_star = jnp.max(score, axis=1)                    # (B,)
    found = s_star >= 0
    idx = s_star[:, None] + g + jnp.arange(K)[None, :]
    # continuation elements past the committed pointer would read
    # stale-draft slots — fall back to the last committed token there
    # (proposal quality only; the verify step gates correctness)
    ok = found[:, None] & (idx < n_next)
    cand = jnp.take_along_axis(token_buf, jnp.clip(idx, 0, BUF - 1),
                               axis=1)
    last = jax.lax.dynamic_slice(token_buf, (0, n_next - 1), (B, 1))
    return jnp.where(ok, cand,
                     jnp.broadcast_to(last, (B, K))).astype(jnp.int32)


def generate_speculative(params, cfg, prompt, max_new_tokens, *, K=4,
                         drafter="ngram", draft_params=None,
                         draft_cfg=None, ngram=2, temperature=0.0,
                         rng=None, kv_int8=False, return_stats=False):
    """Speculative (multi-token) generation: draft K candidate tokens
    per iteration, verify them in ONE batched causal forward on the
    target model (``_decode_block``), and accept the longest prefix
    that matches what the target itself would have produced — plus the
    target's own token at the first mismatch — so every iteration
    commits 1..K+1 tokens with the OUTPUT DISTRIBUTION OF PLAIN
    ``generate``: greedy speculative decode is token-identical, and
    temperature>0 uses the draft-rejection sampling rule (accept d with
    prob min(1, p(d)/q(d)); on rejection sample the renormalized
    residual max(p-q, 0)) whose marginals equal target sampling.

    Numerics caveat: "token-identical" is bit-exact under float32
    compute (``tests/test_gpt.py`` pins it).  Under bfloat16 compute
    the block-verify and single-step forwards may reduce in different
    orders, and a 1-ulp argmax tie in the target logits can resolve
    differently — rare on trained checkpoints (real logit gaps are
    orders above 1 ulp), common on random-init ones (near-flat
    logits); same caveat class as the w8 decode parity gates.  The
    accepted sequence always follows the target's own block-forward
    argmax exactly.

    Drafters
    --------
    ``drafter="ngram"``: zero-cost prompt-lookup — propose the K tokens
    that followed the most recent earlier occurrence of the last
    ``ngram`` tokens (no draft model; wins on repetitive/structured
    text).  ``drafter="self"``: a small self-drafting GPT
    (``draft_params``/``draft_cfg``, same vocab; e.g.
    ``draft_slice_params`` for a layer-slice draft, optionally w8 via
    ``quantize_decode_params``) runs K+1 sequential cached decode steps
    per iteration.

    Batch semantics: acceptance is synchronized across the batch (the
    committed pointer advances by ``min`` of the per-row accept counts
    +1), which keeps the KV caches and position bookkeeping scalar —
    rows that accepted more simply keep their verified tokens as the
    next iteration's pending/drafts, so per-row outputs are unchanged.
    Rejected positions roll back by POINTER only: their cache slots are
    overwritten by the next block write before any causal mask exposes
    them.

    The whole prefill + draft + verify + accept loop compiles into one
    XLA program per shape (``lax.while_loop``), same as ``generate``.
    Needs ``P + max_new_tokens + K <= cfg.max_len`` (the verify block
    may overshoot the last position by up to K).

    ``return_stats=True`` additionally returns a dict with ``iters``
    (verify steps), ``drafted``/``accepted`` (accept rate =
    accepted/drafted), and ``tokens`` committed — the
    accepted-tokens-per-verify-step numbers the benchmark gates use.
    """
    import jax
    import jax.numpy as jnp

    if not cfg.causal:
        cfg = dataclasses.replace(cfg, causal=True)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if K < 1:
        raise ValueError("generate_speculative: K must be >= 1")
    if drafter == "self":
        if draft_params is None or draft_cfg is None:
            raise ValueError("drafter='self' needs draft_params and "
                             "draft_cfg")
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError("draft model must share the vocab")
        if not draft_cfg.causal:
            draft_cfg = dataclasses.replace(draft_cfg, causal=True)
    elif drafter != "ngram":
        raise ValueError("drafter must be 'ngram' or 'self'")

    B, P = prompt.shape
    if max_new_tokens <= 0:
        return (prompt, {"iters": 0, "drafted": 0, "accepted": 0,
                         "tokens": 0}) if return_stats else prompt
    total = P + max_new_tokens + K      # verify may overshoot by <=K
    if total > cfg.max_len:
        raise ValueError(
            "generate_speculative: %d tokens (incl. K=%d overshoot "
            "headroom) > cfg.max_len=%d" % (total, K, cfg.max_len))
    if drafter == "self" and total > draft_cfg.max_len:
        raise ValueError("draft_cfg.max_len too small: need %d"
                         % total)

    cache_key = (cfg, B, P, max_new_tokens, K, drafter, draft_cfg,
                 ngram, float(temperature), bool(kv_int8),
                 bool(return_stats))
    cached = _generate_cache.get(cache_key)
    if cached is not None:
        return cached(params, draft_params, prompt, rng)

    S = K + 1

    @jax.jit
    def run(params, draft_params, prompt, rng):
        f32 = jnp.float32

        def sample(logits, key):
            if temperature == 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, logits / temperature, axis=-1).astype(jnp.int32)

        logits, caches = _prefill_full(params, cfg, prompt, total,
                                       kv_int8=kv_int8)
        rng, sub = jax.random.split(rng)
        pending = sample(logits, sub)                  # (B,)

        # token_buf holds prompt + committed tokens; slots past the
        # committed pointer hold stale drafts (never read: the ngram
        # drafter masks on the committed length)
        token_buf = jnp.zeros((B, total), jnp.int32)
        token_buf = jax.lax.dynamic_update_slice(token_buf, prompt,
                                                 (0, 0))
        token_buf = jax.lax.dynamic_update_slice(
            token_buf, pending[:, None], (0, P))

        if drafter == "self":
            _, dcaches = _prefill_full(draft_params, draft_cfg, prompt,
                                       total)
        else:
            dcaches = None

        def draft_k(dcaches, token_buf, n, pending, key):
            """Propose drafts (B, K) for positions [n+1, n+K]; returns
            (dcaches, drafts, q) with q (B, K, V) the draft proposal
            distributions (None semantics for ngram: one-hot)."""
            if drafter == "ngram":
                return dcaches, _draft_ngram(token_buf, n + 1, K,
                                             ngram), None

            def dstep(carry, i):
                dc, tok, k2 = carry
                lg, dc = _decode_one(draft_params, draft_cfg, tok,
                                     n + i, dc)
                k2, s2 = jax.random.split(k2)
                nxt = sample(lg, s2)
                return (dc, nxt, k2), (nxt, lg)

            # K+1 steps: step i feeds the token at position n+i, so the
            # draft caches end the iteration filled through n+K (the
            # all-accepted case needs slot n+K next round); the last
            # step's proposal is discarded.
            (dcaches, _, _), (toks, lgs) = jax.lax.scan(
                dstep, (dcaches, pending, key), jnp.arange(S))
            drafts = toks[:K].T.astype(jnp.int32)      # (B, K)
            if temperature == 0.0:
                q = None
            else:
                q = jax.nn.softmax(
                    lgs[:K].astype(f32) / temperature,
                    axis=-1).transpose(1, 0, 2)        # (B, K, V)
            return dcaches, drafts, q

        def body(carry):
            caches, dcaches, token_buf, pending, emitted, key, \
                iters, accepted = carry
            n = P + emitted - 1           # cache position of `pending`
            key, kd, ka, kr = jax.random.split(key, 4)
            dcaches, drafts, q = draft_k(dcaches, token_buf, n,
                                         pending, kd)

            block = jnp.concatenate([pending[:, None], drafts], axis=1)
            logits_blk, caches = _decode_block(params, cfg, block, n,
                                               caches)  # (B, S, V)

            if temperature == 0.0:
                tgt = jnp.argmax(logits_blk, axis=-1) \
                    .astype(jnp.int32)                 # (B, S)
                ok = drafts == tgt[:, :K]              # (B, K)
            else:
                p = jax.nn.softmax(logits_blk.astype(f32) / temperature,
                                   axis=-1)            # (B, S, V)
                p_d = jnp.take_along_axis(
                    p[:, :K], drafts[:, :, None], axis=2)[:, :, 0]
                if q is None:            # deterministic (one-hot) draft
                    ratio = p_d
                else:
                    q_d = jnp.take_along_axis(
                        q, drafts[:, :, None], axis=2)[:, :, 0]
                    ratio = p_d / jnp.maximum(q_d, 1e-30)
                u = jax.random.uniform(ka, (B, K))
                ok = u < ratio
            a_b = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                          axis=1)                      # (B,)
            a = jnp.min(a_b)              # batch-synchronized commit

            if temperature == 0.0:
                cont = jax.lax.dynamic_index_in_dim(
                    tgt, a, axis=1, keepdims=False)    # (B,)
            else:
                # residual sampling at the first rejected position;
                # rows that accepted past `a` keep their verified draft
                p_a = jax.lax.dynamic_index_in_dim(p, a, axis=1,
                                                   keepdims=False)
                if q is None:
                    q_a = jax.nn.one_hot(
                        jax.lax.dynamic_index_in_dim(
                            drafts, jnp.minimum(a, K - 1), axis=1,
                            keepdims=False),
                        cfg.vocab_size, dtype=f32)
                else:
                    q_a = jax.lax.dynamic_index_in_dim(
                        q, jnp.minimum(a, K - 1), axis=1,
                        keepdims=False)
                res = jnp.maximum(p_a - jnp.where(a >= K, 0.0, 1.0)
                                  * q_a, 0.0)
                rs = jnp.sum(res, axis=-1, keepdims=True)
                res = jnp.where(rs > 0, res / jnp.maximum(rs, 1e-30),
                                p_a)
                cont_s = jax.random.categorical(
                    kr, jnp.log(res + 1e-30), axis=-1
                ).astype(jnp.int32)
                d_a = jax.lax.dynamic_index_in_dim(
                    drafts, jnp.minimum(a, K - 1), axis=1,
                    keepdims=False)
                cont = jnp.where(a_b > a, d_a, cont_s)

            token_buf = jax.lax.dynamic_update_slice(token_buf, drafts,
                                                     (0, n + 1))
            token_buf = jax.lax.dynamic_update_slice(
                token_buf, cont[:, None], (0, n + a + 1))
            return (caches, dcaches, token_buf, cont,
                    emitted + a + 1, key, iters + 1,
                    accepted + a)

        def cond(carry):
            return carry[4] < max_new_tokens

        init = (caches, dcaches, token_buf, pending,
                jnp.int32(1), rng, jnp.int32(0), jnp.int32(0))
        (_, _, token_buf, _, emitted, _, iters, accepted) = \
            jax.lax.while_loop(cond, body, init)

        out = token_buf[:, :P + max_new_tokens]
        if return_stats:
            return out, {"iters": iters, "drafted": iters * K,
                         "accepted": accepted, "tokens": emitted}
        return out

    if len(_generate_cache) >= _GENERATE_CACHE_MAX:
        _generate_cache.pop(next(iter(_generate_cache)))
    _generate_cache[cache_key] = run
    return run(params, draft_params, prompt, rng)


def generate(params, cfg, prompt, max_new_tokens, *, temperature=0.0,
             rng=None, kv_int8=False):
    """Autoregressive generation with KV caches.

    prompt: (B, P) int32.  temperature 0 → greedy argmax; otherwise
    softmax sampling.  Returns (B, P + max_new_tokens) int32.  The whole
    loop (prefill + decode scan) jits into one program per
    (P, max_new_tokens) pair.

    ``kv_int8=True`` stores the KV caches as per-token symmetric s8
    (halves decode's dominant HBM stream — docs/perf.md "GPT decode");
    combine with ``quantize_decode_params`` for weight-only int8.
    """
    import jax
    import jax.numpy as jnp

    if not cfg.causal:
        cfg = dataclasses.replace(cfg, causal=True)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    B, P = prompt.shape
    if max_new_tokens <= 0:
        return prompt
    total = P + max_new_tokens
    if total > cfg.max_len:
        raise ValueError("generate: %d tokens > cfg.max_len=%d"
                         % (total, cfg.max_len))
    cache_key = (cfg, B, P, max_new_tokens, float(temperature),
                 bool(kv_int8))
    cached = _generate_cache.get(cache_key)
    if cached is not None:
        return cached(params, prompt, rng)

    @jax.jit
    def run(params, prompt, rng):
        # whole-prompt prefill: ONE causal forward builds the caches and
        # the last position's logits (round 4 — the previous scan of
        # per-token decoder steps cost P sequential passes)
        logits, caches = _prefill_full(params, cfg, prompt, total,
                                       kv_int8=kv_int8)

        def sample(logits, key):
            if temperature == 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, logits / temperature, axis=-1).astype(jnp.int32)

        def decode(carry, i):
            caches, logits, key = carry
            key, sub = jax.random.split(key)
            tok = sample(logits, sub)
            new_logits, caches = _decode_one(params, cfg, tok, P + i,
                                             caches)
            return (caches, new_logits, key), tok

        # N-1 decode steps produce N-1 tokens plus the logits for the
        # last one — sampling it outside the scan avoids a wasted
        # full decoder forward whose logits nothing reads
        (_, last_logits, key), toks = jax.lax.scan(
            decode, (caches, logits, rng),
            jnp.arange(max_new_tokens - 1))
        key, sub = jax.random.split(key)
        last = sample(last_logits, sub)
        toks = jnp.concatenate([toks.T.astype(jnp.int32),
                                last[:, None].astype(jnp.int32)], axis=1)
        return jnp.concatenate([prompt, toks], axis=1)

    # cache the jitted runner so repeated same-shape calls reuse the
    # compiled program (jax.jit's cache is keyed on the fn object);
    # bounded FIFO so shape churn cannot grow memory forever
    if len(_generate_cache) >= _GENERATE_CACHE_MAX:
        _generate_cache.pop(next(iter(_generate_cache)))
    _generate_cache[cache_key] = run
    return run(params, prompt, rng)


_generate_cache: Dict[Any, Any] = {}
_GENERATE_CACHE_MAX = 16
