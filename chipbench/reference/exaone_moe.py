"""Plain reference for the EXAONE-MoE family (LG AI Research, 2026-01; HF
``model_type: exaone_moe``; K-EXAONE-236B-A23B), in float32.

Straightforward ``jax.numpy``: no kernels, no cache, no ring carried from
call to call, no batching of the experts, and nothing imported from the
program under test.  Whole sequences in, every position's logits out.

* block (EXAONE 4.0's, ``transformers`` ``models/exaone4/modeling_exaone4.py``
  lines 217-228 and 295-313): no norm before either branch;
  ``h = x + RMSNorm(Attention_i(x))``, ``y = h + RMSNorm(FFN_i(h))``;
  ``FFN_i`` a SwiGLU of ``intermediate_size`` where ``mlp_layer_types[i]``
  is ``"dense"``, the expert layer where it is ``"sparse"``;
* attention: 64 query heads over 8 key/value heads of 128, each key/value
  head repeated over the query heads it serves; an RMSNorm with one gain
  vector of 128 on every query head and every key head; rotary
  (``rotate_half``, base ``rope_parameters.rope_theta``, no scaling) where
  ``sliding_windows[i]`` is not 0, NO position encoding on the full layers
  (line 226: "global NoPE"); a softmax at scale ``128^-0.5`` over the keys
  ``k <= q`` and, on a sliding layer of window ``W``, ``k > q - W``
  (``masking_utils.py`` line 88): an explicit mask over the whole sequence;
* expert layer: ``s = sigmoid(h W_r)`` over all ``router_width`` experts;
  for choosing only ``s + b``; ``jax.lax.top_k`` picks
  ``num_experts_per_tok``; the weights are the chosen ``s`` over their sum
  plus 1e-20 (``norm_topk_prob``), times ``routed_scaling_factor``; then a
  plain loop over the experts this rank holds (``ep_rank`` says which
  block of ``num_experts``), each over every row and masked, plus the
  shared expert;
* head: ``RMSNorm(x) lm_head`` (untied), over the vocabulary slice.

The weights are the benchmark's own, made here from ``--seed``
(``make_params``) and handed to the program and to the reference alike;
what is shared with the program is the layout of that tree: matrices are
``(in, out)``, the held experts stacked ``(held, in, out)``.

On the chip ``decoder_logits`` runs one layer at a time and upcasts each
stored matrix where it is used.

``precision`` names how the weight matmuls are computed: ``float32`` (the
reference: "highest"), ``fp8`` (the control: both operands rounded to
e4m3's four significant bits).
``fault`` plants one departure from the published layer (for the limits'
calibration and the tests): ``window_left_out`` (the sliding layers attend
to the whole sequence), ``window_off_by_one`` (``k >= q - W``: one key
more), ``rope_on_global`` (the full layers rotated too), ``pre_norm``
(``h = x + Attention(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, the same
gains), ``no_qk_norm``, ``no_shared_expert``, ``ring_zero_filled`` (a
query at ``q < W - 1`` reads ``W - 1 - q`` zero keys and values before the
sequence's first: a ring that is zeroed, not masked).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("window_left_out", "window_off_by_one", "rope_on_global",
          "pre_norm", "no_qk_norm", "no_shared_expert", "ring_zero_filled")

_DRAW_BLOCK = 8 * 1024 * 1024
# queries a block of the attention: 512 against 5,120 keys and 64 heads
# are 0.67 GB of float32 scores
_QUERY_BLOCK = 512


# -------------------------------------------------------------- sizes ---

def dims(sizes):
    """The widths the layers are built from, by their published keys."""
    held = sizes["num_experts"]
    return {"D": sizes["hidden_size"], "V": sizes["vocab_size"],
            "H": sizes["num_attention_heads"],
            "Hkv": sizes["num_key_value_heads"], "dh": sizes["head_dim"],
            "F": sizes["intermediate_size"],
            "Fe": sizes["moe_intermediate_size"],
            "Fs": sizes["moe_intermediate_size"]
            * sizes["num_shared_experts"],
            "E": sizes.get("router_width", held), "held": held,
            "first": sizes.get("ep_rank", 0) * held,
            "L": sizes["num_hidden_layers"]}


def param_shapes(sizes):
    """The parameter tree as {path: shape}, in the program's layout."""
    d = dims(sizes)
    D, H, Hkv, dh = d["D"], d["H"], d["Hkv"], d["dh"]
    attn = {"wq": (D, H * dh), "wk": (D, Hkv * dh), "wv": (D, Hkv * dh),
            "q_norm": (dh,), "k_norm": (dh,), "wo": (H * dh, D),
            "post_attn_norm": (D,), "post_ffn_norm": (D,)}
    dense = {"w_gate": (D, d["F"]), "w_up": (D, d["F"]),
             "w_down": (d["F"], D)}
    moe = {"router": (D, d["E"]), "router_bias": (d["E"],),
           "ew_gate": (d["held"], D, d["Fe"]),
           "ew_up": (d["held"], D, d["Fe"]),
           "ew_down": (d["held"], d["Fe"], D),
           "sw_gate": (D, d["Fs"]), "sw_up": (D, d["Fs"]),
           "sw_down": (d["Fs"], D)}
    return {"embed": (d["V"], D), "final_norm": (D,),
            "lm_head": (D, d["V"]),
            "layers": [dict(attn, **(dense if kind == "dense" else moe))
                       for kind in sizes["mlp_layer_types"]]}


def _weight_std(name, sizes):
    """Standard deviation of a seeded matrix (``assumed`` in the
    configuration file): ``a / sqrt(fan_in)``, ``a`` = 1 keeps every
    projection of a unit-variance input at unit variance.  Every branch
    is normed after it, so a branch's own scale cancels; what is left to
    choose: the embedding N(0, 1) (a branch's size), the head 2 (logits
    of deviation 2), the router ``router_a`` (sigmoid scores spread over
    about 0.1-0.9 at the stream's size where the expert layers read it),
    and the routed experts' ``ew_down`` against the shared expert's
    ``sw_down`` (0.5 against 2: with a held expert in one of a row's 8
    choices at weight 2.5 / 8, its term is about 8% of the branch, so a
    choice that bfloat16 breaks the other way on a near-tie moves the
    stream by a few percent and the later layers' choices do not follow
    it: the lesson of the LFM2 cell, PERF.md section 4)."""
    d = dims(sizes)
    D = d["D"]
    return {
        "embed": 1.0,
        "lm_head": 2.0 / math.sqrt(D),
        "wq": 1.0 / math.sqrt(D), "wk": 1.0 / math.sqrt(D),
        "wv": 1.0 / math.sqrt(D),
        "wo": 1.0 / math.sqrt(d["H"] * d["dh"]),
        "w_gate": 1.0 / math.sqrt(D), "w_up": 1.0 / math.sqrt(D),
        "w_down": 1.0 / math.sqrt(d["F"]),
        "router": sizes["assumed"]["router_a"] / math.sqrt(D),
        "ew_gate": 1.0 / math.sqrt(D), "ew_up": 1.0 / math.sqrt(D),
        "ew_down": 0.5 / math.sqrt(d["Fe"]),
        "sw_gate": 1.0 / math.sqrt(D), "sw_up": 1.0 / math.sqrt(D),
        "sw_down": 2.0 / math.sqrt(d["Fs"]),
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


def _rotary(x, theta):
    """(B, T, H, dh), position t the row's index: ``rotate_half``."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(u, layer, sizes, window, precision, fault):
    """The attention of a layer of ``window`` (0: a full layer) on
    (B, T, D) rows."""
    d = dims(sizes)
    B, T, _ = u.shape
    H, Hkv, dh = d["H"], d["Hkv"], d["dh"]
    eps = sizes["rms_norm_eps"]
    theta = float(sizes["rope_parameters"]["rope_theta"])
    q = _mm(u, layer["wq"], precision).reshape(B, T, H, dh)
    k = _mm(u, layer["wk"], precision).reshape(B, T, Hkv, dh)
    v = _mm(u, layer["wv"], precision).reshape(B, T, Hkv, dh)
    if fault != "no_qk_norm":
        q = _rms(q, layer["q_norm"], eps)
        k = _rms(k, layer["k_norm"], eps)
    if window or fault == "rope_on_global":
        q, k = _rotary(q, theta), _rotary(k, theta)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    # the queries in blocks of _QUERY_BLOCK: a block's scores against the
    # whole sequence, not the whole (T, T) at once
    o = jnp.concatenate([
        _softmax_av(q[:, lo:lo + _QUERY_BLOCK], k, v, lo, window, fault)
        for lo in range(0, T, _QUERY_BLOCK)], axis=1)
    return _mm(o.reshape(B, T, H * dh), layer["wo"], precision)


def _softmax_av(q, k, v, lo, window, fault):
    """Queries ``lo ..`` (B, n, H, dh) against every key (B, T, H, dh),
    under the causal mask and, on a sliding layer, the window's."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / math.sqrt(q.shape[-1])
    qi = lo + jnp.arange(q.shape[1])[:, None]
    ki = jnp.arange(k.shape[1])[None, :]
    see = ki <= qi
    if window and fault != "window_left_out":
        reach = window + 1 if fault == "window_off_by_one" else window
        see = see & (ki > qi - reach)
    s = jnp.where(see[None, None], s, -1e30)
    if window and fault == "ring_zero_filled":
        # W - 1 zero keys before position 0: each scores 0 and adds
        # nothing to the values, but takes its share of the softmax
        zeros = jnp.maximum(window - 1 - qi[:, 0], 0)
        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), 0.0)
        e = jnp.exp(s - m)
        p = e / (jnp.sum(e, axis=-1, keepdims=True)
                 + zeros[None, None, :, None] * jnp.exp(-m))
    else:
        p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


def route(m, layer, sizes):
    """(chosen experts (.., k) over all ``router_width``, their weights)."""
    s = 1.0 / (1.0 + jnp.exp(-jnp.matmul(m, layer["router"],
                                         precision=HIGHEST)))
    _, idx = jax.lax.top_k(s + layer["router_bias"],
                           sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sizes["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * sizes["routed_scaling_factor"]


def expert_layer(m, layer, sizes, precision="float32", fault=None,
                 shared=True):
    """The share's expert layer on (.., D): the held experts' terms, one
    expert at a time over every row and masked, plus the shared expert."""
    d = dims(sizes)
    idx, w = route(m, layer, sizes)
    y = jnp.zeros_like(m)
    for e in range(d["held"]):
        mine = jnp.sum(jnp.where(idx == d["first"] + e, w, 0.0), axis=-1)
        out = _swiglu(m, layer["ew_gate"][e], layer["ew_up"][e],
                      layer["ew_down"][e], precision)
        y = y + mine[..., None] * out
    if shared and fault != "no_shared_expert":
        y = y + _swiglu(m, layer["sw_gate"], layer["sw_up"],
                        layer["sw_down"], precision)
    return y


def _ffn(h, layer, sizes, precision, fault):
    if "router" in layer:
        return expert_layer(h, layer, sizes, precision, fault)
    return _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                   precision)


def _block(x, layer, sizes, window, precision, fault):
    """One block on (B, T, D) float32."""
    eps = sizes["rms_norm_eps"]
    a, f = layer["post_attn_norm"], layer["post_ffn_norm"]
    if fault == "pre_norm":
        h = x + _attention(_rms(x, a, eps), layer, sizes, window,
                           precision, fault)
        return h + _ffn(_rms(h, f, eps), layer, sizes, precision, fault)
    h = x + _rms(_attention(x, layer, sizes, window, precision, fault),
                 a, eps)
    return h + _rms(_ffn(h, layer, sizes, precision, fault), f, eps)


def _static(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _static(x)) for k, x in v.items()
                            if _static(x) is not None))
    if isinstance(v, list):
        return tuple(v) if all(isinstance(e, (int, float)) for e in v) \
            else None
    return v if isinstance(v, (int, float, bool)) else None


def _sizes(sizes_t):
    return {k: dict(v) if isinstance(v, tuple) and v
            and isinstance(v[0], tuple) else v for k, v in sizes_t}


@functools.partial(jax.jit, static_argnames=("sizes_t", "window",
                                             "precision", "fault"),
                   donate_argnums=(0,))
def _layer(x, layer, sizes_t, window, precision, fault):
    with jax.default_matmul_precision("highest"):
        return _block(x, layer, _sizes(sizes_t), window, precision, fault)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, w, eps, precision):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(x, norm, eps), w, precision)


def decoder_logits(params, tokens, sizes, precision="float32", fault=None):
    """Teacher-forced causal LM: (B, T) ids -> (B, T, V) float32 logits;
    position t's row scores the token at t + 1.  One layer at a time,
    each matrix upcast at its matmul."""
    sizes_t = _static({k: v for k, v in sizes.items()
                       if k not in ("engine", "rehearse", "assumed",
                                    "reduced_why", "sliding_windows",
                                    "layer_types", "mlp_layer_types")})
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    for layer, window in zip(params["layers"], sizes["sliding_windows"]):
        x = _layer(x, layer, sizes_t, int(window), precision, fault)
    return _head(x, params["final_norm"], params["lm_head"],
                 sizes["rms_norm_eps"], precision)
