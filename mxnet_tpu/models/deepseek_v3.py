"""DeepSeek-V3 family (HF ``model_type: deepseek_v3``; GigaChat3.1-702B-A36B
is one): multi-head LATENT attention (MLA) with a low-rank query, YaRN
rotary on a 64-lane part of each head, ``first_k_dense_replace`` leading
SwiGLU layers and then expert layers — sigmoid scores, group-limited
top-k with a selection bias (``noaux_tc``), a shared expert — under
pre-norm residual blocks.

One block function, ``serve_block``, computes the layer.  It takes its
attention backend as an argument: ``attend(q, row)`` is handed each
row's ABSORBED queries and its one new cache row, and returns what the
heads read back in latent space.  ``serving.ServingEngine`` hands it the
paged latent pool; ``forward`` hands it a full causal softmax, and is
the dense full-sequence pass the tests hold against
``chipbench/reference/deepseek_v3.py``.

**The served form.**  The cache holds ``[c_kv | rotated k_pe]`` a token
a layer, ``kv_lora_rank + qk_rope_head_dim`` values shared by every
query head, and nothing per head.  With ``W_kvb`` split per head into
its key part ``K_h`` (rank x nope) and value part ``V_h`` (rank x v)::

    q_lat_h = q_nope_h K_h^T                      (rank)
    score_h = (q_lat_h . c_kv + q_pe_h . k_pe) x s
    o_lat_h = sum_t p_t c_kv_t                    (rank)
    o_h     = o_lat_h V_h                         (v)

so a row's query is ``[q_lat_h | q_pe_h]`` against the cached row, and
``p`` against the same row's first ``rank`` values.  ``s`` is the
model's, ``(nope + rope)^-0.5 x mscale^2``, no ``dh^-0.5``.  Prefill rows
take the same path (a row is a token in the engine's step).

**The expert layer** computes one rank's share of an expert-parallel
deployment (``held_first``, ``held_count``): it routes over all
``n_routed_experts`` as published and sums the terms of the experts held
here (``parallel/moe.py``); the shared expert is computed whole.  It
counts, over the live rows, the row-expert pairs it dispatched, the
held experts hit and the copies of an expert's weights its grouped
products ask for (``StepCounts``): the engine reads them back with the
step's tokens.

Precision: the residual stream and the matmuls' operands are
``cfg.dtype``, accumulated in float32; norms, rotary angles, the router
(its matmul at the highest precision, its weights float32) and the
softmax are float32.
"""
from __future__ import annotations

import dataclasses
import math
import sys

__all__ = ["DeepseekV3Config", "param_shapes", "init_params", "forward",
           "serve_embed", "serve_block", "serve_logits", "StepCounts",
           "STEP_COUNTERS", "counter_stats", "yarn_inv_freq"]


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The published ``config.json`` keys under the engine's names.
    ``n_routed_experts`` is the router's width (all the experts of the
    deployment); ``held_first`` / ``held_count`` say which of them this
    program holds (all of them by default).  ``max_len`` is None:
    positions are rotary, the context is bounded by whoever holds the
    cache."""
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int
    moe_d_ff: int
    n_routed_experts: int
    n_shared_experts: int
    top_k: int
    n_group: int
    topk_group: int
    first_k_dense: int
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    held_first: int = 0
    held_count: int = -1
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    dtype: str = "bfloat16"
    causal: bool = True
    max_len: None = None

    def __post_init__(self):
        if self.held_count < 0:
            object.__setattr__(self, "held_count",
                               self.n_routed_experts - self.held_first)
        if not 0 <= self.held_first <= self.held_first + self.held_count \
                <= self.n_routed_experts:
            raise ValueError(
                "DeepseekV3Config: experts %d..%d are not among the %d "
                "routed" % (self.held_first,
                            self.held_first + self.held_count - 1,
                            self.n_routed_experts))
        if self.n_routed_experts % self.n_group:
            raise ValueError("DeepseekV3Config: %d experts do not divide "
                             "into %d groups"
                             % (self.n_routed_experts, self.n_group))

    @classmethod
    def from_hf(cls, c, **kw):
        """From the keys of a ``deepseek_v3`` ``config.json``.  A file
        that states one rank's share gives the experts held as
        ``n_routed_experts`` and the published count, the router's width,
        as ``router_width`` beside ``ep_rank``."""
        rs = c.get("rope_scaling") or {}
        held = c["n_routed_experts"]
        width = c.get("router_width", held)
        return cls(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"], d_ff=c["intermediate_size"],
            moe_d_ff=c["moe_intermediate_size"], n_routed_experts=width,
            n_shared_experts=c["n_shared_experts"],
            top_k=c["num_experts_per_tok"], n_group=c["n_group"],
            topk_group=c["topk_group"],
            first_k_dense=c["first_k_dense_replace"],
            routed_scaling_factor=c["routed_scaling_factor"],
            norm_topk_prob=c["norm_topk_prob"],
            held_first=c.get("ep_rank", 0) * held, held_count=held,
            rms_eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
            rope_factor=float(rs.get("factor", 1.0)),
            rope_original_max=rs.get("original_max_position_embeddings",
                                     4096),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale=float(rs.get("mscale", 1.0)),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)), **kw)

    @property
    def latent_row(self):
        """``(rank, rope)``: what one cached token of one layer is
        (``serving/paged_kv.py`` shapes the pool from it)."""
        return self.kv_lora_rank, self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        """``(nope + rope)^-0.5 x m^2``, ``m`` YaRN's ``mscale_all_dim``
        correction (1 without scaling)."""
        m = _yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) \
            if self.rope_mscale_all_dim else 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m

    @property
    def serving(self):
        """The module whose ``serve_*`` functions the engine's step
        program is built from."""
        return sys.modules[__name__]


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """YaRN's blend of the rotary frequencies, float64 numpy
    ``(rope / 2,)``, and the factor on cos and sin: pairs that turn more
    than ``beta_fast`` times over the original context keep their
    frequency, those that turn less than ``beta_slow`` times are
    interpolated by ``factor``, a linear ramp between."""
    import numpy as np
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return extra, 1.0

    def correction_dim(turns):
        return dim * math.log(cfg.rope_original_max
                              / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv = extra / cfg.rope_factor * (1.0 - mask) + extra * mask
    attn = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
           if cfg.rope_mscale_all_dim else 1.0)
    return inv, attn


def is_expert_layer(cfg, i):
    return i >= cfg.first_k_dense


def param_shapes(cfg):
    """{path: shape}: matrices are (in, out); ``wq_b``'s columns are a
    head's ``[nope | rope]``, ``wkv_a``'s ``[c_kv | k_pe]``, ``wkv_b``'s a
    head's ``[k_nope | v]``; an expert layer holds the experts it was
    given, (held, in, out), the router over all of them and the bias
    the choice adds (float32 both)."""
    D, V, H = cfg.d_model, cfg.vocab_size, cfg.n_heads
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    attn = {"attn_norm": (D,), "wq_a": (D, cfg.q_lora_rank),
            "q_norm": (cfg.q_lora_rank,),
            "wq_b": (cfg.q_lora_rank, H * dq),
            "wkv_a": (D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_norm": (cfg.kv_lora_rank,),
            "wkv_b": (cfg.kv_lora_rank,
                      H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (H * cfg.v_head_dim, D), "ffn_norm": (D,)}
    F, E = cfg.moe_d_ff, cfg.held_count
    Fs = cfg.moe_d_ff * cfg.n_shared_experts
    dense = {"w_gate": (D, cfg.d_ff), "w_up": (D, cfg.d_ff),
             "w_down": (cfg.d_ff, D)}
    moe = {"router": (D, cfg.n_routed_experts),
           "router_bias": (cfg.n_routed_experts,),
           "ew_gate": (E, D, F), "ew_up": (E, D, F), "ew_down": (E, F, D),
           "sw_gate": (D, Fs), "sw_up": (D, Fs), "sw_down": (Fs, D)}
    return {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V),
            "layers": [dict(attn, **(moe if is_expert_layer(cfg, i)
                                     else dense))
                       for i in range(cfg.n_layers)]}


def init_params(key, cfg, dtype=None):
    """Seeded parameters: matrices N(0, 1/fan_in), norm gains 1, the
    router and its bias float32 (the bias N(0, 0.01))."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype or cfg.dtype)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        if name.endswith("norm"):
            x = jnp.ones(shape, dtype)
        elif name == "router_bias":
            x = 0.01 * jax.random.normal(k, shape, jnp.float32)
        else:
            x = jax.random.normal(k, shape, jnp.float32) \
                / math.sqrt(shape[-2])
            x = x if name == "router" else x.astype(dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------ pieces ---

def _rms(x, w, eps):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)) \
        * w.astype(jnp.float32)


def _mm(x, w, cdt):
    """(rows, in) @ (in, out): operands in the compute dtype, float32 out."""
    import jax.numpy as jnp
    return jnp.dot(x.astype(cdt), w.astype(cdt),
                   preferred_element_type=jnp.float32)


def _rope(x, pos, cfg):
    """YaRN rotary over the last axis of (T, ..., rope) at positions
    ``pos``: lanes 2i and 2i + 1 are a pair, turned by ``pos x
    inv_freq[i]`` and left in place (the ``deepseek_v3`` model code
    de-interleaves both q and k first, which no dot product sees)."""
    import jax.numpy as jnp
    inv, attn = yarn_inv_freq(cfg)
    ang = pos.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None]                 # (T, rope/2)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],)
    cos = (jnp.cos(ang) * attn).reshape(shape)
    sin = (jnp.sin(ang) * attn).reshape(shape)
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(m, w_gate, w_up, w_down, cdt):
    import jax
    h = jax.nn.silu(_mm(m, w_gate, cdt)) * _mm(m, w_up, cdt)
    return _mm(h, w_down, cdt)


STEP_COUNTERS = ("moe_pairs", "moe_experts_hit", "moe_weight_fetches")


class StepCounts:
    """What the expert layers of one call count over its live rows:
    ``live`` (T,) bool in, ``counts`` (one int32 scalar per entry of
    ``names``, summed over the layers) out."""
    names = STEP_COUNTERS

    def __init__(self, live):
        import jax.numpy as jnp
        self.live = live
        self.counts = [jnp.zeros((), jnp.int32) for _ in self.names]

    def add(self, *counts):
        self.counts = [a + b for a, b in zip(self.counts, counts)]


def counter_stats(cfg, params, counts):
    """What one step's ``STEP_COUNTERS`` add to the engine's ``stats``:
    themselves, and the bytes of the expert weights the step had to
    read (``moe_experts_hit`` x one expert's three matrices; what the
    grouped products copied is ``moe_weight_fetches`` matrices, three
    a hit where none is copied twice)."""
    pairs, hit, fetches = (int(c) for c in counts)
    w = next(layer["ew_gate"] for layer in params["layers"]
             if "ew_gate" in layer)
    return {"moe_pairs": pairs, "moe_experts_hit": hit,
            "moe_weight_fetches": fetches,
            "moe_expert_bytes": hit * 3 * w.shape[1] * w.shape[2]
            * w.dtype.itemsize}


def _experts(layer, cfg, m, counts):
    """The expert layer on (T, D) normed rows: this rank's share of the
    routed sum plus the shared expert, float32."""
    import jax
    import jax.numpy as jnp
    from ..parallel.moe import held_experts_ffn, route_group_limited
    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("moe_route"):
        logits = jnp.dot(m.astype(cdt).astype(jnp.float32),
                         layer["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        idx, w = route_group_limited(
            jax.nn.sigmoid(logits), layer["router_bias"],
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor)
    with jax.named_scope("moe_experts"):
        y, pairs, hit, _, fetches = held_experts_ffn(
            m.astype(cdt), layer["ew_gate"].astype(cdt),
            layer["ew_up"].astype(cdt), layer["ew_down"].astype(cdt),
            idx, w, held_first=cfg.held_first, live=counts.live)
        counts.add(pairs, hit, fetches)
    with jax.named_scope("moe_shared"):
        return y + _swiglu(m, layer["sw_gate"], layer["sw_up"],
                           layer["sw_down"], cdt)


def _attn_absorbed(layer, cfg, u, row_pos, attend):
    """The served attention on (T, D) normed rows, before ``wo``:
    (T, H x v) float32 (module docstring)."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    T = u.shape[0]
    H, R = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    wkv_b = layer["wkv_b"].astype(cdt).reshape(R, H, nope + dv)
    with jax.named_scope("mla_q"):
        c_q = _rms(_mm(u, layer["wq_a"], cdt), layer["q_norm"],
                   cfg.rms_eps)
        q = _mm(c_q, layer["wq_b"], cdt).reshape(T, H, nope + rope)
        # absorbed: a head's q_nope against its key part of W_kvb
        q_lat = jnp.einsum("thn,rhn->thr", q[..., :nope].astype(cdt),
                           wkv_b[..., :nope],
                           preferred_element_type=jnp.float32)
    with jax.named_scope("mla_kv"):
        ckv = _mm(u, layer["wkv_a"], cdt)                     # (T, R+rope)
        c_kv = _rms(ckv[:, :R], layer["kv_norm"], cfg.rms_eps)
    with jax.named_scope("rope"):
        q_pe = _rope(q[..., nope:], row_pos, cfg)
        k_pe = _rope(ckv[:, R:], row_pos, cfg)
    q_cat = jnp.concatenate([q_lat, q_pe], axis=-1).astype(cdt)
    row = jnp.concatenate([c_kv, k_pe], axis=-1).astype(cdt)
    o_lat = attend(q_cat, row)                                # (T, H, R)
    with jax.named_scope("mla_out"):
        return jnp.einsum("thr,rhv->thv", o_lat.astype(cdt),
                          wkv_b[..., nope:],
                          preferred_element_type=jnp.float32
                          ).reshape(T, H * dv)


def _attn_expanded(layer, cfg, u, row_pos, B):
    """The attention as published, over B full sequences: per-head keys
    ``[k_nope_h | k_pe]`` and values ``v_h`` expanded from every row's
    ``c_kv``, queries as they come out of ``wq_b``.  For the tests (no
    cache could afford it): same (T, H x v) float32."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    T = u.shape[0] // B
    H, R = cfg.n_heads, cfg.kv_lora_rank
    nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    wkv_b = layer["wkv_b"].astype(cdt).reshape(R, H, nope + dv)
    c_q = _rms(_mm(u, layer["wq_a"], cdt), layer["q_norm"], cfg.rms_eps)
    q = _mm(c_q, layer["wq_b"], cdt).reshape(B * T, H, -1)
    ckv = _mm(u, layer["wkv_a"], cdt)
    c_kv = _rms(ckv[:, :R], layer["kv_norm"], cfg.rms_eps).astype(cdt)
    kv = jnp.einsum("tr,rhn->thn", c_kv, wkv_b,
                    preferred_element_type=jnp.float32
                    ).astype(cdt).reshape(B, T, H, nope + dv)
    q_pe = _rope(q[..., nope:], row_pos, cfg).astype(cdt)
    k_pe = _rope(ckv[:, R:], row_pos, cfg).astype(cdt)
    q = q.astype(cdt)
    s = (jnp.einsum("bqhn,bkhn->bhqk",
                    q[..., :nope].reshape(B, T, H, nope), kv[..., :nope],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhn,bkn->bhqk", q_pe.reshape(B, T, H, -1),
                      k_pe.reshape(B, T, -1),
                      preferred_element_type=jnp.float32)) \
        * cfg.softmax_scale
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), s,
                                 -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhv->bqhv", p.astype(cdt), kv[..., nope:],
                      preferred_element_type=jnp.float32
                      ).reshape(B * T, H * dv)


def _block(layer, cfg, x, attention, counts):
    """``y = h + FFN(norm(h))``, ``h = x + wo(attention(norm(x)))``."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("norm"):
        u = _rms(x, layer["attn_norm"], cfg.rms_eps)
    o = attention(u)
    with jax.named_scope("mla_out"):
        h = (x.astype(jnp.float32) + _mm(o, layer["wo"], cdt)).astype(cdt)
    with jax.named_scope("norm"):
        m = _rms(h, layer["ffn_norm"], cfg.rms_eps)
    if "router" in layer:
        y = _experts(layer, cfg, m, counts)
    else:
        with jax.named_scope("ffn"):
            y = _swiglu(m, layer["w_gate"], layer["w_up"],
                        layer["w_down"], cdt)
    return (h.astype(jnp.float32) + y).astype(cdt)


def serve_block(layer, cfg, x, row_pos, attend, state, counts):
    """One block on (T, D) rows at positions ``row_pos``.
    ``attend(q (T, H, rank + rope), row (T, rank + rope))`` writes each
    row's cache row and returns its heads' read-back in latent space,
    (T, H, rank) float32; ``counts`` is the call's ``StepCounts``.
    ``state`` is the slot state of families that keep one; this one
    keeps pages alone."""
    return _block(layer, cfg, x, lambda u: _attn_absorbed(
        layer, cfg, u, row_pos, attend), counts)


def serve_embed(params, cfg, tokens, row_pos):
    """(T,) ids -> (T, D) rows; positions enter in the blocks (rotary)."""
    import jax.numpy as jnp
    return params["embed"][tokens].astype(jnp.dtype(cfg.dtype))


def serve_logits(params, cfg, x, slot_rows):
    """Float32 logits of the sampling rows alone: (S, n) row indices ->
    (S, n, V)."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("norm"):
        h = _rms(x[slot_rows.reshape(-1)], params["final_norm"],
                 cfg.rms_eps)
    return _mm(h, params["lm_head"], cdt).reshape(
        slot_rows.shape + (cfg.vocab_size,))


def forward(params, cfg, tokens, absorbed=True):
    """Dense full-sequence pass: (B, T) ids -> (B, T, V) float32 logits
    through the same block as the engine's step, a full causal softmax
    standing in for the pages.  ``absorbed=False`` computes the
    attention as published instead, every cached row expanded into
    per-head keys and values (the tests hold the two against each
    other)."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    H, R = cfg.n_heads, cfg.kv_lora_rank
    row_pos = jnp.tile(jnp.arange(T, dtype=jnp.int32), B)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def attend(q, row):
        q = q.reshape(B, T, H, -1)
        row = row.reshape(B, T, -1)
        s = jnp.einsum("bqhw,bkw->bhqk", q, row,
                       preferred_element_type=jnp.float32) \
            * cfg.softmax_scale
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkr->bqhr", p.astype(cdt), row[..., :R],
                       preferred_element_type=jnp.float32)
        return o.reshape(B * T, H, R)

    counts = StepCounts(jnp.ones((B * T,), bool))
    x = serve_embed(params, cfg, tokens.reshape(-1), row_pos)
    for layer in params["layers"]:
        if absorbed:
            x = serve_block(layer, cfg, x, row_pos, attend, None, counts)
        else:
            x = _block(layer, cfg, x, lambda u, layer=layer:
                       _attn_expanded(layer, cfg, u, row_pos, B), counts)
    rows = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    return serve_logits(params, cfg, x, rows)
