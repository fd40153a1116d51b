"""LFM2-MoE (Liquid AI, 2025-10; HF ``model_type: lfm2_moe``;
LFM2-8B-A1B is one): layers of TWO kinds in one model, by
``layer_types`` — a GATED SHORT CONVOLUTION (``"conv"``) or a rotary
grouped-query attention with an RMSNorm on every query and key head
(``"full_attention"``) — each followed by a SwiGLU feed-forward in the
first ``num_dense_layers`` layers and by an expert layer after: sigmoid
scores, a selection bias, top-k, the chosen scores normalised, no shared
expert.  Pre-norm residual blocks, the head tied to the embedding.

One block function, ``serve_block``, computes the layer, whichever its
kind (told from the layer's parameters).  It takes what a layer keeps
between calls as arguments and uses what its kind needs:
``attend(q, k, v)`` (an attention layer: where the keys and values are
kept and how a row reads them), a slot-state backend (a convolution
layer: where a sequence's window lives) and the call's counters (an
expert layer).  ``layer_cache`` says which layer keeps what — pages and
no window, or a window and no pages — and ``serving.ServingEngine``
builds its pools from it; ``forward`` hands the block a full causal
softmax and zero windows, and is the dense full-sequence pass the tests
hold against ``chipbench/reference/lfm2_moe.py``.

**The short convolution** (no bias, no activation)::

    [B | C | h] = u W_in
    g_t   = B_t * h_t
    c_t   = sum_j w[j] g_{t-(K-1)+j}         K taps, the last on row t
    out_t = (C_t * c_t) W_out

Between calls a sequence keeps its last ``K - 1`` rows of ``g``: a
``(K - 1, D)`` window a slot a convolution layer, zeros at the
sequence's first row (``falcon_h1.slot_conv`` computes it over the
call's flat rows and moves every slot's window past them).

**The expert layer** holds every expert: ``parallel/moe.py``'s serving
form with one group and all experts held.  It counts, over the live
rows, the row-expert pairs it dispatched, the experts hit, the copies
of an expert's weights its grouped products ask for and the heaviest
expert's pairs (``STEP_COUNTERS``, summed over the layers):
the engine reads them back with the step's tokens.

Precision: the residual stream, the matmuls' operands and the window
are ``cfg.dtype``, accumulated in float32; norms, the gating products
and the taps, rotary angles, the router (its matmul at the highest
precision, its weights and bias float32) and the softmax are float32.
"""
from __future__ import annotations

import dataclasses
import math
import sys
from typing import Tuple

from .deepseek_v3 import STEP_COUNTERS as _STEP_COUNTERS
from .deepseek_v3 import StepCounts as _StepCounts
from .deepseek_v3 import _swiglu
from .deepseek_v3 import counter_stats as _counter_stats
from .falcon_h1 import SlotState, _mm, _rms, _rope

__all__ = ["Lfm2MoeConfig", "param_shapes", "init_params", "forward",
           "serve_embed", "serve_block", "serve_logits", "layer_cache",
           "SlotState", "StepCounts", "STEP_COUNTERS", "counter_stats"]


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published ``config.json`` keys under the engine's names.
    ``layer_types`` names each layer's operator; ``max_len`` is None:
    positions are rotary, the context is bounded by whoever holds the
    cache."""
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    moe_d_ff: int
    n_experts: int
    top_k: int
    n_dense_layers: int
    layer_types: Tuple[str, ...]
    conv_kernel: int = 3
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    rms_eps: float = 1e-5
    rope_theta: float = 1e6
    dtype: str = "bfloat16"
    causal: bool = True
    max_len: None = None

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers or \
                set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                "Lfm2MoeConfig: layer_types %r do not name %d layers "
                "'conv' or 'full_attention'"
                % (self.layer_types, self.n_layers))
        if self.n_heads % self.n_kv_heads:
            raise ValueError("Lfm2MoeConfig: %d query heads do not divide "
                             "over %d key/value heads"
                             % (self.n_heads, self.n_kv_heads))

    @classmethod
    def from_hf(cls, c, **kw):
        """From the keys of an ``lfm2_moe`` ``config.json`` (``head_dim``
        where the file has it, else ``hidden_size / heads``)."""
        return cls(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim")
            or c["hidden_size"] // c["num_attention_heads"],
            d_ff=c["intermediate_size"],
            moe_d_ff=c["moe_intermediate_size"],
            n_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
            n_dense_layers=c["num_dense_layers"],
            layer_types=tuple(c["layer_types"]),
            conv_kernel=c["conv_L_cache"],
            norm_topk_prob=c["norm_topk_prob"],
            use_expert_bias=c["use_expert_bias"],
            routed_scaling_factor=c["routed_scaling_factor"],
            rms_eps=c["norm_eps"], rope_theta=float(c["rope_theta"]), **kw)

    @property
    def serving(self):
        """The module whose ``serve_*`` functions the engine's step
        program is built from."""
        return sys.modules[__name__]


def layer_cache(cfg):
    """What each layer keeps between calls, ``(pages, slot state)`` a
    layer: an attention layer K/V pages and no window, a convolution
    layer its last ``conv_kernel - 1`` gated rows and no pages."""
    window = {"conv": ((cfg.conv_kernel - 1, cfg.d_model), cfg.dtype)}
    return [(False, window) if kind == "conv" else (True, {})
            for kind in cfg.layer_types]


def param_shapes(cfg):
    """{path: shape}: matrices are (in, out); ``conv_in``'s columns are
    ``[B | C | h]``; ``conv_w`` is (taps, channels), the last tap on the
    current row; an expert layer holds every expert, (E, in, out), the
    router and the bias the choice adds (float32 both).  The head is the
    embedding's transpose."""
    D, V = cfg.d_model, cfg.vocab_size
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    F, E = cfg.moe_d_ff, cfg.n_experts
    conv = {"conv_in": (D, 3 * D), "conv_w": (cfg.conv_kernel, D),
            "conv_out": (D, D)}
    attn = {"wq": (D, H * dh), "wk": (D, Hkv * dh), "wv": (D, Hkv * dh),
            "q_norm": (dh,), "k_norm": (dh,), "wo": (H * dh, D)}
    dense = {"w_gate": (D, cfg.d_ff), "w_up": (D, cfg.d_ff),
             "w_down": (cfg.d_ff, D)}
    moe = {"router": (D, E), "router_bias": (E,), "ew_gate": (E, D, F),
           "ew_up": (E, D, F), "ew_down": (E, F, D)}
    return {"embed": (V, D), "embedding_norm": (D,),
            "layers": [dict({"operator_norm": (D,), "ffn_norm": (D,)},
                            **(conv if kind == "conv" else attn),
                            **(dense if i < cfg.n_dense_layers else moe))
                       for i, kind in enumerate(cfg.layer_types)]}


def init_params(key, cfg, dtype=None):
    """Seeded parameters: matrices N(0, 1/fan_in), norm gains 1, the
    taps N(0, 1/taps), the router and its bias float32 (the bias
    N(0, 0.01))."""
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

STEP_COUNTERS = _STEP_COUNTERS + ("moe_pairs_max",)


class StepCounts(_StepCounts):
    """``deepseek_v3.StepCounts`` with this family's own count after
    them: per expert layer the heaviest expert's pairs."""
    names = STEP_COUNTERS


def counter_stats(cfg, params, counts):
    """What one step's ``STEP_COUNTERS`` add to the engine's ``stats``:
    ``deepseek_v3.counter_stats`` of that family's (themselves and the
    bytes of the expert weights the step had to read) and this one's."""
    return dict(_counter_stats(cfg, params, counts[:-1]),
                moe_pairs_max=int(counts[-1]))


def _short_conv(layer, cfg, u, state):
    """The gated short convolution on (T, D) normed rows (module
    docstring); ``state.conv`` keeps the slots' windows."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    D = cfg.d_model
    with jax.named_scope("conv_in"):
        p = _mm(u, layer["conv_in"], cdt)
        # the window holds g in the compute dtype: the rows of this
        # call are rounded as the rows it will read back were
        g = (p[:, :D] * p[:, 2 * D:]).astype(cdt)
    with jax.named_scope("short_conv"):
        c = state.conv(g, layer["conv_w"], jnp.zeros((D,), jnp.float32))
    with jax.named_scope("conv_out"):
        return _mm(p[:, D:2 * D] * c, layer["conv_out"], cdt)


def _attention(layer, cfg, u, row_pos, attend):
    """Grouped-query attention on (T, D) normed rows: every query and
    key head normalised over its own lanes, THEN rotated."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    T = u.shape[0]
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("qkv"):
        q = _mm(u, layer["wq"], cdt).reshape(T, Hq, dh)
        k = _mm(u, layer["wk"], cdt).reshape(T, Hkv, dh)
        v = _mm(u, layer["wv"], cdt).reshape(T, Hkv, dh)
    with jax.named_scope("qk_norm"):
        q = _rms(q, layer["q_norm"], cfg.rms_eps)
        k = _rms(k, layer["k_norm"], cfg.rms_eps)
    with jax.named_scope("rope"):
        q = _rope(q, row_pos, cfg.rope_theta).astype(cdt)
        k = _rope(k, row_pos, cfg.rope_theta).astype(cdt)
    o = attend(q, k, v.astype(cdt))
    with jax.named_scope("attn_out"):
        return _mm(o.reshape(T, Hq * dh), layer["wo"], cdt)


def _experts(layer, cfg, m, counts):
    """The expert layer on (T, D) normed rows, float32."""
    import jax
    import jax.numpy as jnp
    from ..parallel.moe import held_experts_ffn, route_group_limited
    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("moe_route"):
        logits = jnp.dot(m.astype(cdt).astype(jnp.float32),
                         layer["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        bias = layer["router_bias"] if cfg.use_expert_bias \
            else jnp.zeros_like(layer["router_bias"])
        idx, w = route_group_limited(
            jax.nn.sigmoid(logits), bias, n_group=1, topk_group=1,
            top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, eps=1e-6)
    with jax.named_scope("moe_experts"):
        y, pairs, hit, sizes, fetches = held_experts_ffn(
            m.astype(cdt), layer["ew_gate"].astype(cdt),
            layer["ew_up"].astype(cdt), layer["ew_down"].astype(cdt),
            idx, w, held_first=0, live=counts.live)
        counts.add(pairs, hit, fetches, jnp.max(sizes))
    return y


def serve_block(layer, cfg, x, row_pos, attend, state, counts):
    """One block on (T, D) rows at positions ``row_pos``:
    ``h = x + Operator(norm(x))``, ``y = h + FFN(norm(h))``.  An
    attention layer calls ``attend(q (T, Hq, dh), k, v (T, Hkv, dh))``,
    which returns each row's attention over its own sequence, (T, Hq,
    dh) float32; a convolution layer uses ``state`` (``SlotState``: its
    ``conv``); an expert layer adds to ``counts`` (``StepCounts``)."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("norm"):
        u = _rms(x, layer["operator_norm"], cfg.rms_eps)
    o = _short_conv(layer, cfg, u, state) if "conv_w" in layer \
        else _attention(layer, cfg, u, row_pos, attend)
    h = (x.astype(jnp.float32) + o).astype(cdt)
    with jax.named_scope("norm"):
        m = _rms(h, layer["ffn_norm"], cfg.rms_eps)
    if "router" in layer:
        y = _experts(layer, cfg, m, counts)
    else:
        with jax.named_scope("ffn"):
            y = _swiglu(m, layer["w_gate"], layer["w_up"],
                        layer["w_down"], cdt)
    return (h.astype(jnp.float32) + y).astype(cdt)


def serve_embed(params, cfg, tokens, row_pos):
    """(T,) ids -> (T, D) rows; positions enter in the blocks (rotary)."""
    import jax.numpy as jnp
    return params["embed"][tokens].astype(jnp.dtype(cfg.dtype))


def serve_logits(params, cfg, x, slot_rows):
    """Float32 logits of the sampling rows alone, against the
    embedding (the tied head): (S, n) row indices -> (S, n, V)."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("norm"):
        h = _rms(x[slot_rows.reshape(-1)], params["embedding_norm"],
                 cfg.rms_eps)
    logits = jax.lax.dot_general(
        h.astype(cdt), params["embed"].astype(cdt),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return logits.reshape(slot_rows.shape + (cfg.vocab_size,))


def forward(params, cfg, tokens):
    """Dense full-sequence pass: (B, T) ids -> (B, T, V) float32 logits,
    through the same block as the engine's step.  Each sequence is one
    slot whose rows are all in this call: a full causal softmax stands
    in for the pages and zero windows for the pools."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    row_slot = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
    row_pos = jnp.tile(jnp.arange(T, dtype=jnp.int32), B)
    fresh = jnp.ones((B + 1,), bool)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def attend(q, k, v):
        q = q.reshape(B, T, Hkv, Hq // Hkv, dh)
        k, v = k.reshape(B, T, Hkv, dh), v.reshape(B, T, Hkv, dh)
        s = jnp.einsum("bqhrd,bkhd->bhrqk", q, k,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        o = jnp.einsum("bhrqk,bkhd->bqhrd", p.astype(cdt), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B * T, Hq, dh)

    counts = StepCounts(jnp.ones((B * T,), bool))
    x = serve_embed(params, cfg, tokens.reshape(-1), row_pos)
    for layer, (_, keeps) in zip(params["layers"], layer_cache(cfg)):
        pools = {name: jnp.zeros((B + 1,) + shape, dtype)
                 for name, (shape, dtype) in keeps.items()}
        x = serve_block(layer, cfg, x, row_pos, attend,
                        SlotState(pools, row_slot, fresh, T), counts)
    rows = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    return serve_logits(params, cfg, x, rows)
