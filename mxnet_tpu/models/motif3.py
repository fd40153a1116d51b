"""Motif-3 (Motif-Technologies, 2026-08; HF ``model_type: Motif``;
Motif-3-Beta is one): grouped differential LATENT attention (GDLA) in
layers of two kinds — sliding-window attention over the last
``sliding_window`` positions, or full causal attention, one full layer
in every ``sliding_window_period`` — each followed by a PolyNorm MLP in
the leading dense layers and by an expert layer in the others (sigmoid
scores, a selection bias, top-k, the chosen scores normalised and
scaled, one shared expert), under a residual of ``n`` streams mixed by
manifold-constrained hyper-connections (mHC).

**The residual** (mHC, Xie et al., arXiv:2512.24880).  A token carries
``X`` (n, D), the embedding copied into each stream.  Each branch F (the
attention, then the feed-forward) reads a token-dependent mix of the
streams and writes back into all of them::

    x~     = RMS(vec X)                               (n D; no gain)
    H_pre  = sigmoid(a_pre x~ Phi_pre + b_pre)        (1 x n)
    H_post = 2 sigmoid(a_post x~ Phi_post + b_post)   (1 x n)
    H_res  = Sinkhorn(exp(a_res mat(x~ Phi_res) + b_res))   (n x n)
    X     <- clamp(H_res X + H_post^T F(RMSNorm(H_pre X)), +-hidden_clamp)

``Sinkhorn`` alternates row and column normalisation
``mhc_sinkhorn_iters`` times, in float32, so that ``H_res`` is doubly
stochastic.  The block's input and output are (T, n, D): the engine
hands ``x`` from block to block without looking into it.  The head reads
the sum of the streams, RMS-normed.

**The attention** (both kinds; only the mask differs).  A latent row
``[c_kv | k_pe]`` a token, shared by every head, as in
``deepseek_v3``; ``W_kvb`` expands it to ``n_kv_heads`` GROUPS of keys
``W_uk_g`` and values ``W_uv_g``, five query heads a group at 80 / 16.
Head ``h`` is in group ``g = h // r`` (``r`` heads a group); the group's
last head ``r g + r - 1`` is its NOISE head, the others its signal heads.
Served absorbed: a head's query ``[q_nope_h W_uk_g^T | q_pe_h]`` against
the latent rows reads back ``c^_h = sum_t p_{h,t} c_kv_t``, and the
differential, linear, is taken in latent space::

    lambda_h = sigmoid(u w_lambda_h)                  (per token)
    o_h      = ((c^_h - lambda_h c^_n(g)) W_uv_g) * sigmoid(u W_gate)_h
    out      = concat_h(o_h) W_o                      (signal heads)

so the noise heads need no up-projection.  A full layer's rows go
through ``attend(q, row)`` (the engine's latent pages: the walk); a
sliding layer keeps a RING of its last ``W`` latent rows a slot as slot
state and no pages (``exaone_moe.latent_window``: the window ``(p - W,
p]``, entries before the sequence's first position absent; a call's
prefill rows read in one pass).

**The feed-forward.**  PolyNorm (Zhuo et al., arXiv:2411.03884) in place
of SiLU, in the dense MLP, every expert and the shared expert::

    y     = (PN(x W_gate) * (x W_up)) W_down
    PN(z) = s (w0 N(z^3) + w1 N(z^2) + w2 N(z)) + clamp(b, +-c)
    N(v)  = v / rms(v)      over the MLP's width

each expert with its own ``(w0, w1, w2, b)``.  The expert layer computes
one rank's share of an expert-parallel deployment (``held_first``,
``held_count``; ``parallel/moe.py``): it routes over all
``n_routed_experts`` and sums the held experts' terms; the shared expert
is computed whole.  It counts what ``exaone_moe.StepCounts`` counts, the
latent rings' live rows and reads among them.

Precision: the streams, the matmuls' operands, the pages and the rings
are ``cfg.dtype``, accumulated in float32; norms, rotary angles, the
mHC maps and Sinkhorn (the maps' matmul at the highest precision, their
weights float32), PolyNorm, the router (likewise) and the softmax are
float32.
"""
from __future__ import annotations

import dataclasses
import math
import sys
from typing import Tuple

from .deepseek_v3 import _mm, _rms, _rope
from .exaone_moe import (STEP_COUNTERS, SlotState, StepCounts,
                         counter_stats)

__all__ = ["Motif3Config", "param_shapes", "init_params", "forward",
           "serve_embed", "serve_block", "serve_logits", "layer_cache",
           "SlotState", "StepCounts", "STEP_COUNTERS", "counter_stats",
           "sinkhorn", "polynorm"]


@dataclasses.dataclass(frozen=True)
class Motif3Config:
    """The published ``config.json`` keys under the engine's names.
    ``sliding_windows`` gives each layer's window, 0 for a full layer;
    ``n_kv_heads`` the key/value groups, each with one of the
    ``n_noise_heads``.  ``n_routed_experts`` is the router's width;
    ``held_first`` / ``held_count`` say which experts this program
    holds (all by default).  ``max_len`` is None: the context is bounded by whoever holds the
    cache."""
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_noise_heads: int
    d_ff: int
    moe_d_ff: int
    n_routed_experts: int
    n_shared_experts: int
    top_k: int
    first_k_dense: int
    sliding_windows: Tuple[int, ...]
    mhc_rate: int = 4
    mhc_sinkhorn_iters: int = 20
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    polynorm_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    hidden_clamp: float = 1e6
    held_first: int = 0
    held_count: int = -1
    rms_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    causal: bool = True
    max_len: None = None
    # YaRN is not applied (``apply_yarn_scaling`` false): the rotary of
    # ``deepseek_v3._rope`` at a factor of 1
    rope_factor = 1.0

    def __post_init__(self):
        if self.held_count < 0:
            object.__setattr__(self, "held_count",
                               self.n_routed_experts - self.held_first)
        if not 0 <= self.held_first <= self.held_first + self.held_count \
                <= self.n_routed_experts:
            raise ValueError(
                "Motif3Config: experts %d..%d are not among the %d routed"
                % (self.held_first, self.held_first + self.held_count - 1,
                   self.n_routed_experts))
        if len(self.sliding_windows) != self.n_layers:
            raise ValueError("Motif3Config: sliding_windows %r do not name "
                             "%d layers" % (self.sliding_windows,
                                            self.n_layers))
        if self.n_heads % self.n_kv_heads \
                or self.n_noise_heads != self.n_kv_heads \
                or self.n_heads // self.n_kv_heads < 2:
            raise ValueError(
                "Motif3Config: %d heads over %d groups with %d noise heads:"
                " needs one noise head and at least one signal head a "
                "group" % (self.n_heads, self.n_kv_heads,
                           self.n_noise_heads))

    @classmethod
    def from_hf(cls, c, **kw):
        """From the keys of a ``Motif`` ``config.json``.  Layer ``i``
        slides where ``use_sliding_window`` and ``i mod
        sliding_window_period`` is not the period's last.  A file that
        states one rank's share gives the experts held as
        ``num_experts`` and the published count, the router's width, as
        ``router_width`` beside ``ep_rank``."""
        held = c["num_experts"]
        period = c["sliding_window_period"]
        windows = tuple(
            c["sliding_window"] if c["use_sliding_window"]
            and i % period != period - 1 else 0
            for i in range(c["num_hidden_layers"]))
        return cls(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["head_dim"] - c["qk_rope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"], n_noise_heads=c["num_noise_heads"],
            d_ff=c["intermediate_size"],
            moe_d_ff=c["moe_intermediate_size"],
            n_routed_experts=c.get("router_width", held),
            n_shared_experts=c["num_shared_experts"],
            top_k=c["experts_top_k"],
            first_k_dense=c["n_dense_first_layers"],
            sliding_windows=windows, mhc_rate=c["mhc_expansion_rate"],
            mhc_sinkhorn_iters=c["mhc_sinkhorn_iters"],
            routed_scaling_factor=float(c["route_scale"]),
            norm_topk_prob=c["route_norm"],
            polynorm_scale=c["polynorm_output_scale"],
            polynorm_bias_clamp=c["polynorm_bias_clamp"],
            hidden_clamp=float(c["hidden_clamp"]),
            held_first=c.get("ep_rank", 0) * held, held_count=held,
            rms_eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
            **kw)

    @property
    def group_heads(self):
        """Query heads a key/value group: its signal heads and its
        noise head, last."""
        return self.n_heads // self.n_kv_heads

    @property
    def signal_heads(self):
        return self.n_heads - self.n_noise_heads

    @property
    def latent_row(self):
        """``(rank, rope)``: what one cached token of one layer is."""
        return self.kv_lora_rank, self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        """``(nope + rope)^-0.5``: no YaRN correction."""
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def serving(self):
        """The module whose ``serve_*`` functions the engine's step
        program is built from."""
        return sys.modules[__name__]


def layer_cache(cfg):
    """What each layer keeps between calls, ``(pages, slot state)`` a
    layer: a full layer latent pages and no ring, a sliding layer its
    ring of the last ``W`` positions' latent rows, padded as a page's
    row is (``paged_kv.latent_width``), and no pages."""
    from ..serving.paged_kv import latent_width
    row = latent_width(*cfg.latent_row)
    return [(False, {"win": ((w, row), cfg.dtype)}) if w else (True, {})
            for w in cfg.sliding_windows]


def _mhc_width(cfg):
    """Columns of one branch's mHC map: ``H_pre`` n, ``H_post`` n,
    ``H_res`` n x n."""
    return cfg.mhc_rate * (cfg.mhc_rate + 2)


def param_shapes(cfg):
    """{path: shape}: matrices are (in, out); ``wq_b``'s columns are a
    head's ``[nope | rope]``, ``wkv_a``'s ``[c_kv | k_pe]``, ``wkv_b``'s a
    group's ``[k_nope | v]``; ``w_lambda``, ``w_ogate`` and ``wo`` cover
    the signal heads alone, in head order.  Each branch's mHC map
    ``mhc_<branch>`` (n D, n (n + 2)), its three scales ``_a`` and its
    biases ``_b`` (float32); ``poly`` a PolyNorm's ``(w0, w1, w2, b)``
    (float32; an expert layer's routed experts one row each).  An expert
    layer holds the experts it was given, (held, in, out), the router
    over all of them and the bias the choice adds (float32 both).  An
    untied head."""
    D, V, H = cfg.d_model, cfg.vocab_size, cfg.n_heads
    G, R = cfg.n_kv_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    Hs, n, M = cfg.signal_heads, cfg.mhc_rate, _mhc_width(cfg)
    attn = {"attn_norm": (D,), "wq_a": (D, cfg.q_lora_rank),
            "q_norm": (cfg.q_lora_rank,),
            "wq_b": (cfg.q_lora_rank, H * (nope + rope)),
            "wkv_a": (D, R + rope), "kv_norm": (R,),
            "wkv_b": (R, G * (nope + dv)), "w_lambda": (D, Hs),
            "w_ogate": (D, Hs * dv), "wo": (Hs * dv, D), "ffn_norm": (D,)}
    for branch in ("attn", "ffn"):
        attn.update({"mhc_" + branch: (n * D, M),
                     "mhc_%s_a" % branch: (3,),
                     "mhc_%s_b" % branch: (M,)})
    F, E = cfg.moe_d_ff, cfg.held_count
    Fs = cfg.moe_d_ff * cfg.n_shared_experts
    dense = {"w_gate": (D, cfg.d_ff), "w_up": (D, cfg.d_ff),
             "w_down": (cfg.d_ff, D), "poly": (4,)}
    moe = {"router": (D, cfg.n_routed_experts),
           "router_bias": (cfg.n_routed_experts,),
           "ew_gate": (E, D, F), "ew_up": (E, D, F), "ew_down": (E, F, D),
           "ew_poly": (E, 4), "sw_gate": (D, Fs), "sw_up": (D, Fs),
           "sw_down": (Fs, D), "sw_poly": (4,)}
    return {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V),
            "layers": [dict(attn, **(moe if i >= cfg.first_k_dense
                                     else dense))
                       for i in range(cfg.n_layers)]}


# what stays float32 whatever the weights' dtype
FLOAT32 = ("router", "router_bias", "mhc_attn", "mhc_attn_a", "mhc_attn_b",
           "mhc_ffn", "mhc_ffn_a", "mhc_ffn_b", "poly", "ew_poly",
           "sw_poly")


def init_params(key, cfg, dtype=None):
    """Seeded parameters: matrices N(0, 1/fan_in), norm gains 1, each
    PolyNorm (1/3, 1/3, 1/3, 0), the mHC scales 0.01 and biases 0; the
    router, its bias (N(0, 0.01)), the mHC maps and the PolyNorms
    float32."""
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
        elif name.endswith("poly"):
            x = jnp.broadcast_to(jnp.asarray([1 / 3, 1 / 3, 1 / 3, 0.0],
                                             jnp.float32), shape)
        elif name.startswith("mhc_") and name.endswith("_a"):
            x = jnp.full(shape, 0.01, jnp.float32)
        elif name.startswith("mhc_") and name.endswith("_b"):
            x = jnp.zeros(shape, jnp.float32)
        elif name == "router_bias":
            x = 0.01 * jax.random.normal(k, shape, jnp.float32)
        else:
            x = jax.random.normal(k, shape, jnp.float32) \
                / math.sqrt(shape[-2])
            x = x if name in FLOAT32 else x.astype(dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------ pieces ---

def sinkhorn(m, iters):
    """``iters`` rounds of row then column normalisation of the positive
    (.., n, n) ``m``, float32: doubly stochastic in the limit, its
    columns exactly after the last round."""
    import jax.numpy as jnp
    m = m.astype(jnp.float32)
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def polynorm(z, w, scale, clamp, eps):
    """``PN(z)`` over the last axis of ``z``, float32: ``w`` (.., 4) the
    ``(w0, w1, w2, b)`` of each row, or (4,) for all of them."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("polynorm"):
        z = z.astype(jnp.float32)
        w = w.astype(jnp.float32)

        def n(v):
            return v * jax.lax.rsqrt(
                jnp.mean(jnp.square(v), axis=-1, keepdims=True) + eps)
        return scale * (w[..., 0:1] * n(z * z * z) + w[..., 1:2] * n(z * z)
                        + w[..., 2:3] * n(z)) \
            + jnp.clip(w[..., 3:4], -clamp, clamp)


def _poly_mlp(m, w_gate, w_up, w_down, poly, cfg):
    """``(PN(m W_gate) * (m W_up)) W_down`` on (T, D) rows, float32."""
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    h = polynorm(_mm(m, w_gate, cdt), poly, cfg.polynorm_scale,
                 cfg.polynorm_bias_clamp, cfg.rms_eps) * _mm(m, w_up, cdt)
    return _mm(h, w_down, cdt)


def _mhc_pre(layer, cfg, branch, x):
    """A branch's mix of the (T, n, D) streams: its input, RMS-normed
    with the branch's gain, float32 (T, D), and its ``(H_post (T, n),
    H_res (T, n, n))``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    T, n, D = x.shape
    a, b = layer["mhc_%s_a" % branch], layer["mhc_%s_b" % branch]
    with jax.named_scope("mhc_pre"):
        xs = x.astype(f32)
        flat = xs.reshape(T, n * D)
        flat = flat * jax.lax.rsqrt(
            jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
            + cfg.rms_eps)
        proj = jnp.dot(flat, layer["mhc_" + branch].astype(f32),
                       precision=jax.lax.Precision.HIGHEST)   # (T, n(n+2))
        pre = jax.nn.sigmoid(a[0] * proj[:, :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
        h = jnp.sum(pre[:, :, None] * xs, axis=1)
        u = _rms(h, layer[branch + "_norm"], cfg.rms_eps)
    with jax.named_scope("mhc_res"):
        res = sinkhorn(jnp.exp(a[2] * proj[:, 2 * n:].reshape(T, n, n)
                               + b[2 * n:].reshape(n, n)),
                       cfg.mhc_sinkhorn_iters)
    return u, (post, res)


def _mhc_post(x, f, mix, cfg):
    """``clamp(H_res X + H_post^T f)`` back in the streams' dtype."""
    import jax
    import jax.numpy as jnp
    post, res = mix
    with jax.named_scope("mhc_res"):
        mixed = jnp.sum(res[..., None] * x.astype(jnp.float32)[:, None],
                        axis=2)                                # (T, n, D)
    with jax.named_scope("mhc_post"):
        y = mixed + post[..., None] * f[:, None, :]
        return jnp.clip(y, -cfg.hidden_clamp, cfg.hidden_clamp).astype(
            x.dtype)


def _gdla(layer, cfg, u, row_pos, attend, state, counts):
    """The served attention on (T, D) normed rows: (T, D) float32
    (module docstring).  A full layer calls ``attend``, a sliding layer
    reads its ring (``state``)."""
    import jax
    import jax.numpy as jnp
    from ..serving.paged_kv import latent_width
    cdt = jnp.dtype(cfg.dtype)
    T = u.shape[0]
    H, G, r = cfg.n_heads, cfg.n_kv_heads, cfg.group_heads
    R, nope, rope = cfg.kv_lora_rank, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim
    wkv_b = layer["wkv_b"].astype(cdt).reshape(R, G, nope + cfg.v_head_dim)
    with jax.named_scope("gdla_q"):
        c_q = _rms(_mm(u, layer["wq_a"], cdt), layer["q_norm"],
                   cfg.rms_eps)
        q = _mm(c_q, layer["wq_b"], cdt).reshape(T, H, nope + rope)
        # absorbed: a head's q_nope against its group's key part
        q_lat = jnp.einsum("tgjn,rgn->tgjr",
                           q[..., :nope].astype(cdt).reshape(T, G, r, nope),
                           wkv_b[..., :nope],
                           preferred_element_type=jnp.float32
                           ).reshape(T, H, R)
        q_pe = _rope(q[..., nope:], row_pos, cfg)
        q_cat = jnp.concatenate([q_lat, q_pe], axis=-1).astype(cdt)
    with jax.named_scope("gdla_kv"):
        ckv = _mm(u, layer["wkv_a"], cdt)                     # (T, R+rope)
        c_kv = _rms(ckv[:, :R], layer["kv_norm"], cfg.rms_eps)
        k_pe = _rope(ckv[:, R:], row_pos, cfg)
        row = jnp.concatenate([c_kv, k_pe], axis=-1).astype(cdt)
    if state is not None:
        with jax.named_scope("win_latent"):
            pad = latent_width(R, rope) - R - rope
            c_hat = state.latent_window(
                q_cat, jnp.pad(row, ((0, 0), (0, pad))), row_pos, R,
                cfg.softmax_scale, counts)
    else:
        c_hat = attend(q_cat, row)                            # (T, H, R)
    with jax.named_scope("gdla_diff"):
        lam = jax.nn.sigmoid(_mm(u, layer["w_lambda"], cdt))  # (T, Hs)
        c = c_hat.reshape(T, G, r, R)
        d = c[:, :, :r - 1] \
            - lam.reshape(T, G, r - 1)[..., None] * c[:, :, r - 1:]
        o = jnp.einsum("tgjr,rgv->tgjv", d.astype(cdt),
                       wkv_b[..., nope:],
                       preferred_element_type=jnp.float32
                       ).reshape(T, cfg.signal_heads * cfg.v_head_dim)
    with jax.named_scope("attn_gate"):
        o = o * jax.nn.sigmoid(_mm(u, layer["w_ogate"], cdt))
    with jax.named_scope("attn_out"):
        return _mm(o, layer["wo"], cdt)


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
            jax.nn.sigmoid(logits), layer["router_bias"], n_group=1,
            topk_group=1, top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor)
    with jax.named_scope("moe_experts"):
        # each pair's expert's PolyNorm; the pairs past the groups a row
        # of zeros
        poly = jnp.concatenate([layer["ew_poly"].astype(jnp.float32),
                                jnp.zeros((1, 4), jnp.float32)])

        def act(gate, up, expert):
            return polynorm(gate, poly[expert], cfg.polynorm_scale,
                            cfg.polynorm_bias_clamp, cfg.rms_eps) * up
        y, pairs, hit, _, fetches = held_experts_ffn(
            m.astype(cdt), layer["ew_gate"].astype(cdt),
            layer["ew_up"].astype(cdt), layer["ew_down"].astype(cdt),
            idx, w, held_first=cfg.held_first, live=counts.live, act=act)
        counts.add(pairs, hit, fetches)
    with jax.named_scope("moe_shared"):
        return y + _poly_mlp(m, layer["sw_gate"], layer["sw_up"],
                             layer["sw_down"], layer["sw_poly"], cfg)


def serve_block(layer, cfg, x, row_pos, attend, state, counts):
    """One block on (T, n, D) streams at positions ``row_pos``: the
    attention branch, then the feed-forward branch, each through its
    mHC mix.  A full layer calls ``attend(q (T, H, rank + rope), row (T,
    rank + rope))``, which writes each row's latent row and returns its
    heads' read-back in latent space, (T, H, rank) float32; a sliding
    layer uses ``state`` (``SlotState``: its latent ring); an expert
    layer and a sliding layer add to ``counts`` (``StepCounts``)."""
    import jax
    u, mix = _mhc_pre(layer, cfg, "attn", x)
    x = _mhc_post(x, _gdla(layer, cfg, u, row_pos, attend, state, counts),
                  mix, cfg)
    m, mix = _mhc_pre(layer, cfg, "ffn", x)
    if "router" in layer:
        f = _experts(layer, cfg, m, counts)
    else:
        with jax.named_scope("ffn"):
            f = _poly_mlp(m, layer["w_gate"], layer["w_up"],
                          layer["w_down"], layer["poly"], cfg)
    return _mhc_post(x, f, mix, cfg)


def serve_embed(params, cfg, tokens, row_pos):
    """(T,) ids -> (T, n, D) streams, the embedding in each; positions
    enter in the blocks (rotary)."""
    import jax.numpy as jnp
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    return jnp.broadcast_to(x[:, None], (x.shape[0], cfg.mhc_rate,
                                         x.shape[1]))


def serve_logits(params, cfg, x, slot_rows):
    """Float32 logits of the sampling rows alone over this chip's slice
    of the vocabulary: the streams summed, RMS-normed, through the
    head; (S, n) row indices -> (S, n, V)."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("norm"):
        h = jnp.sum(x[slot_rows.reshape(-1)].astype(jnp.float32), axis=1)
        h = _rms(h, params["final_norm"], cfg.rms_eps)
    return _mm(h, params["lm_head"], cdt).reshape(
        slot_rows.shape + (cfg.vocab_size,))


def forward(params, cfg, tokens):
    """Dense full-sequence pass: (B, T) ids -> (B, T, V) float32 logits,
    through the same block as the engine's step.  Each sequence is one
    slot whose rows are all in this call: a full causal softmax over
    the latent rows stands in for the pages and zero rings for the
    pools."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    H, R = cfg.n_heads, cfg.kv_lora_rank
    row_slot = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
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
    for layer, (_, keeps) in zip(params["layers"], layer_cache(cfg)):
        pools = {name: jnp.zeros((B + 1,) + shape, dtype)
                 for name, (shape, dtype) in keeps.items()}
        state = SlotState(pools, row_slot, None, B * T) if keeps \
            else None
        x = serve_block(layer, cfg, x, row_pos, attend, state, counts)
    rows = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    return serve_logits(params, cfg, x, rows)
