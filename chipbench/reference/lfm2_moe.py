"""Plain reference for the LFM2-MoE family (Liquid AI, 2025-10; HF
``model_type: lfm2_moe``; LFM2-8B-A1B), in float32.

Straightforward ``jax.numpy``: no kernels, no cache, no window carried
from call to call, no batching of the experts, and nothing imported from
the program under test.  Whole sequences in, every position's logits out.

* block: ``h = x + Operator_i(RMSNorm(x))``, ``y = h + FFN_i(RMSNorm(h))``;
  ``Operator_i`` by ``layer_types[i]``: the gated short convolution
  (``"conv"``) or the attention (``"full_attention"``); ``FFN_i`` a SwiGLU of
  ``intermediate_size`` in the first ``num_dense_layers`` layers and the
  expert layer after;
* short convolution (``conv_bias`` false, no activation):
  ``[B | C | h] = u W_in``, ``g = B * h``, ``c_t = sum_j w[j] g_{t-2+j}`` as a
  sum of three shifted products over the sequence padded with two zero
  rows in front, ``out = (C * c) W_out``;
* attention: 32 query heads over 8 key/value heads of 64, each key/value
  head repeated over the query heads it serves; an RMSNorm with one gain
  vector of 64 on every query head and every key head BEFORE the
  rotation; rotary over the whole head, the two halves rotated against
  each other (``rotate_half``), base ``rope_theta``, no scaling; a full
  causal softmax at scale ``64^-0.5``;
* expert layer: ``s = sigmoid(m W_r)``; for choosing only ``s + b``;
  ``jax.lax.top_k`` picks ``num_experts_per_tok``; the weights are the
  chosen ``s`` over their sum plus 1e-6 (``norm_topk_prob``), times
  ``routed_scaling_factor``; then a plain loop over all the experts, each
  over every row and masked; no shared expert;
* head: ``RMSNorm(x) embed^T`` (tied).

The weights are the benchmark's own, made here from ``--seed``
(``make_params``) and handed to the program and to the reference alike;
what is shared with the program is the layout of that tree: matrices are
``(in, out)``, ``conv_in``'s columns ``[B | C | h]``, ``conv_w`` ``(taps,
channels)`` with the last tap on the current row, the experts stacked
``(experts, in, out)``.

On the chip ``decoder_logits`` runs one layer at a time and upcasts each
stored matrix where it is used (an expert layer would be 1.4 GB in
float32 beside the bfloat16 weights).

``precision`` names how the weight matmuls are computed: ``float32``
(the reference: "highest"), ``fp8`` (the control: both operands rounded
to e4m3's four significant bits).
``fault`` plants one departure from the published layer (for the limits'
calibration): ``window_not_carried`` (every row a call of its own that
starts from a zero window: the two earlier taps see nothing),
``b_c_exchanged`` (``g = C * h``, ``out = (B * c) W_out``), ``no_qk_norm``,
``no_expert_bias`` (the choice by ``s`` alone), ``no_norm_topk`` (the chosen
scores as they are).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("window_not_carried", "b_c_exchanged", "no_qk_norm",
          "no_expert_bias", "no_norm_topk")

_DRAW_BLOCK = 8 * 1024 * 1024


# -------------------------------------------------------------- sizes ---

def dims(sizes):
    """The widths the layers are built from, by their published keys."""
    H = sizes["num_attention_heads"]
    return {"D": sizes["hidden_size"], "V": sizes["vocab_size"], "H": H,
            "Hkv": sizes["num_key_value_heads"],
            "dh": sizes.get("head_dim") or sizes["hidden_size"] // H,
            "F": sizes["intermediate_size"],
            "Fe": sizes["moe_intermediate_size"],
            "E": sizes["num_experts"], "K": sizes["conv_L_cache"],
            "L": sizes["num_hidden_layers"],
            "dense": sizes["num_dense_layers"]}


def param_shapes(sizes):
    """The parameter tree as {path: shape}, in the program's layout."""
    d = dims(sizes)
    D, H, Hkv, dh = d["D"], d["H"], d["Hkv"], d["dh"]
    conv = {"conv_in": (D, 3 * D), "conv_w": (d["K"], D),
            "conv_out": (D, D)}
    attn = {"wq": (D, H * dh), "wk": (D, Hkv * dh), "wv": (D, Hkv * dh),
            "q_norm": (dh,), "k_norm": (dh,), "wo": (H * dh, D)}
    dense = {"w_gate": (D, d["F"]), "w_up": (D, d["F"]),
             "w_down": (d["F"], D)}
    moe = {"router": (D, d["E"]), "router_bias": (d["E"],),
           "ew_gate": (d["E"], D, d["Fe"]), "ew_up": (d["E"], D, d["Fe"]),
           "ew_down": (d["E"], d["Fe"], D)}
    return {"embed": (d["V"], D), "embedding_norm": (D,),
            "layers": [dict({"operator_norm": (D,), "ffn_norm": (D,)},
                            **(conv if kind == "conv" else attn),
                            **(dense if i < d["dense"] else moe))
                       for i, kind in enumerate(sizes["layer_types"])]}


def _weight_std(name, sizes):
    """Standard deviation of a seeded matrix (``assumed`` in the
    configuration file): ``a / sqrt(fan_in)``, ``a`` = 1 keeps every
    projection of a unit-variance input at unit variance.  The
    exceptions: the embedding, which is the head too, 2 / sqrt(D)
    (logits of deviation 2; the first norm takes its scale out of the
    stream); ``wq`` and ``wk`` 2 (under the per-head norm their scale
    cancels: away from 1 so that leaving the norm out changes the
    scores, as on a trained checkpoint, whose queries and keys are not
    of unit size); the dense ``w_down`` 2 (the MLP of the residual's
    order); the experts' ``ew_down`` 0.5: with no shared expert and
    independent random experts, one changed choice swaps a whole
    expert's term, and at 2 that moved the stream by a tenth, enough to
    change the choices of every later layer: bfloat16's near-tie flips
    cascaded through the 10 expert layers and the served tokens read
    as far from the float32 reference as chance (configuration file,
    ``assumed``); the taps 1 / sqrt(taps); the router 1.5 (sigmoid
    scores spread over 0.05-0.95)."""
    d = dims(sizes)
    D = d["D"]
    return {
        "embed": 2.0 / math.sqrt(D),
        "conv_in": 1.0 / math.sqrt(D),
        "conv_w": 1.0 / math.sqrt(d["K"]),
        "conv_out": 1.0 / math.sqrt(D),
        "wq": 2.0 / math.sqrt(D), "wk": 2.0 / math.sqrt(D),
        "wv": 1.0 / math.sqrt(D),
        "wo": 1.0 / math.sqrt(d["H"] * d["dh"]),
        "w_gate": 1.0 / math.sqrt(D), "w_up": 1.0 / math.sqrt(D),
        "w_down": 2.0 / math.sqrt(d["F"]),
        "router": 1.5 / math.sqrt(D),
        "ew_gate": 1.0 / math.sqrt(D), "ew_up": 1.0 / math.sqrt(D),
        "ew_down": 0.5 / math.sqrt(d["Fe"]),
    }[name]


def _normal(key, shape, std, dtype):
    """N(0, std^2) of ``shape`` in ``dtype``, a large leaf drawn, scaled
    and cast in blocks of its leading axis, so that no whole-leaf
    float32 temporary is ever live."""
    def draw(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)
    n = int(np.prod(shape))
    rows = shape[0]
    if len(shape) < 2 or n <= _DRAW_BLOCK:
        return draw(key, shape)
    per = max(1, _DRAW_BLOCK // (n // rows))
    while rows % per:
        per -= 1
    keys = jax.random.split(key, rows // per)
    return jax.lax.map(lambda k: draw(k, (per,) + tuple(shape[1:])),
                       keys).reshape(shape)


def make_params(seed, sizes, dtype):
    """Every leaf from the seed in ONE jitted call, on the device, in the
    type it is served in.  Matrices N(0, ``_weight_std``); norm gains
    1 + N(0, 0.02); the router and its bias stay float32 whatever
    ``dtype``, the bias N(0, ``assumed.expert_bias_std``)."""
    shapes = param_shapes(sizes)
    bias_std = sizes["assumed"]["expert_bias_std"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            name = path[-1].key
            k = jax.random.fold_in(key, i)
            if name.endswith("norm"):
                x = (1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
                     ).astype(dtype)
            elif name == "router_bias":
                x = bias_std * jax.random.normal(k, shape, jnp.float32)
            elif name == "router":
                x = _normal(k, shape, _weight_std(name, sizes),
                            jnp.float32)
            else:
                x = _normal(k, shape, _weight_std(name, sizes), dtype)
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    # a seed may be a little over 2**31: fold it into 32 unsigned bits
    key = jax.random.PRNGKey(np.uint32(int(seed) % (2 ** 32)))
    return jax.jit(build)(key)


# ------------------------------------------------------------- blocks ---

def _fake_fp8(x):
    """Round to e4m3's four significant bits (the exponent's range is not
    narrowed: kinder than real fp8)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def _mm(x, w, precision):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif precision != "float32":
        raise ValueError("precision %r" % (precision,))
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w.astype(jnp.float32)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _swiglu(x, w_gate, w_up, w_down, precision):
    return _mm(_silu(_mm(x, w_gate, precision)) * _mm(x, w_up, precision),
               w_down, precision)


def short_conv(u, layer, sizes, precision="float32", fault=None):
    """The gated short convolution on (B, T, D): a padded sum of shifted
    products."""
    K = sizes["conv_L_cache"]
    T = u.shape[1]
    Bg, Cg, h = jnp.split(_mm(u, layer["conv_in"], precision), 3, axis=-1)
    if fault == "b_c_exchanged":
        Bg, Cg = Cg, Bg
    g = Bg * h
    w = layer["conv_w"].astype(jnp.float32)
    if fault == "window_not_carried":
        c = w[K - 1] * g
    else:
        padded = jnp.pad(g, ((0, 0), (K - 1, 0), (0, 0)))
        c = sum(w[j] * padded[:, j:j + T] for j in range(K))
    return _mm(Cg * c, layer["conv_out"], precision)


def _rotary(x, theta):
    """(B, T, H, dh), position t the row's index: ``rotate_half``."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(u, layer, sizes, precision, fault):
    d = dims(sizes)
    B, T, _ = u.shape
    H, Hkv, dh = d["H"], d["Hkv"], d["dh"]
    eps, theta = sizes["norm_eps"], float(sizes["rope_theta"])
    q = _mm(u, layer["wq"], precision).reshape(B, T, H, dh)
    k = _mm(u, layer["wk"], precision).reshape(B, T, Hkv, dh)
    v = _mm(u, layer["wv"], precision).reshape(B, T, Hkv, dh)
    if fault != "no_qk_norm":
        q = _rms(q, layer["q_norm"], eps)
        k = _rms(k, layer["k_norm"], eps)
    q, k = _rotary(q, theta), _rotary(k, theta)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / math.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    return _mm(o.reshape(B, T, H * dh), layer["wo"], precision)


def route(m, layer, sizes, fault=None):
    """(chosen experts (.., k), their weights)."""
    s = 1.0 / (1.0 + jnp.exp(-jnp.matmul(m, layer["router"],
                                         precision=HIGHEST)))
    choice = s + layer["router_bias"] \
        if sizes["use_expert_bias"] and fault != "no_expert_bias" else s
    _, idx = jax.lax.top_k(choice, sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sizes["norm_topk_prob"] and fault != "no_norm_topk":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return idx, w * sizes["routed_scaling_factor"]


def expert_layer(m, layer, sizes, precision="float32", fault=None):
    """The expert layer on (.., D): one expert at a time over every row,
    masked by the row's weight for it."""
    idx, w = route(m, layer, sizes, fault)
    y = jnp.zeros_like(m)
    for e in range(sizes["num_experts"]):
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        out = _swiglu(m, layer["ew_gate"][e], layer["ew_up"][e],
                      layer["ew_down"][e], precision)
        y = y + mine[..., None] * out
    return y


def _block(x, layer, sizes, precision, fault):
    """One block on (B, T, D) float32; the layer's kind by its leaves."""
    eps = sizes["norm_eps"]
    u = _rms(x, layer["operator_norm"], eps)
    h = x + (short_conv(u, layer, sizes, precision, fault)
             if "conv_w" in layer
             else _attention(u, layer, sizes, precision, fault))
    m = _rms(h, layer["ffn_norm"], eps)
    if "router" in layer:
        return h + expert_layer(m, layer, sizes, precision, fault)
    return h + _swiglu(m, layer["w_gate"], layer["w_up"], layer["w_down"],
                       precision)


def _static(sizes):
    """The numbers and flags of the configuration, hashable."""
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float, bool))))


@functools.partial(jax.jit, static_argnames=("sizes_t", "precision",
                                             "fault"),
                   donate_argnums=(0,))
def _layer(x, layer, sizes_t, precision, fault):
    with jax.default_matmul_precision("highest"):
        return _block(x, layer, dict(sizes_t), precision, fault)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, embed, eps, precision):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(x, norm, eps), embed.T, precision)


def decoder_logits(params, tokens, sizes, precision="float32", fault=None):
    """Teacher-forced causal LM: (B, T) ids -> (B, T, V) float32 logits;
    position t's row scores the token at t + 1.  One layer at a time,
    each matrix upcast at its matmul."""
    sizes_t = _static(sizes)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    for layer in params["layers"]:
        x = _layer(x, layer, sizes_t, precision, fault)
    return _head(x, params["embedding_norm"], params["embed"],
                 sizes["norm_eps"], precision)
