"""Plain reference for the DeepSeek-V3 family (HF ``model_type:
deepseek_v3``; GigaChat3.1-702B-A36B), in float32.

Straightforward ``jax.numpy``: no kernels, no cache, no batching of the
experts, and nothing imported from the program under test.  The
attention is the PUBLISHED one, not the served one: every token's
``c_kv`` is expanded through ``W_kvb`` into per-head ``k_nope`` and ``v``,
the one rotated ``k_pe`` is shared by all heads, scores are
``(q_nope . k_nope + q_pe . k_pe) x s`` under a full causal softmax.  No
query is absorbed and no latent row is kept.

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
  ``FFN`` is a SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers and the expert layer after;
* rotary: YaRN (``rope_scaling``), lanes ``2i`` and ``2i + 1`` of the
  64 a pair (``assumed`` in the configuration file);
* softmax scale ``s = (nope + rope)^-0.5 x m^2``,
  ``m = 0.1 x mscale_all_dim x ln(factor) + 1``;
* expert layer (``noaux_tc``): ``s = sigmoid(x W_r)`` over
  ``router_width`` experts; for choosing only ``s' = s + b``; a group's
  score is the sum of its two best ``s'``, the ``topk_group`` best groups
  stay, ``jax.lax.top_k`` picks ``num_experts_per_tok`` among them; the
  weights are the chosen ``s`` over their sum, times
  ``routed_scaling_factor``; then a plain loop over the experts;
* **the share**: the file's ``n_routed_experts`` counts the experts HELD
  (``ep_rank`` says which block of the ``router_width``); routing runs
  over all of them and only held experts' terms are summed.  The shared
  expert is whole.

The weights are the benchmark's own, made here from ``--seed``
(``make_params``) and handed to the program and to the reference alike;
what is shared with the program is the layout of that tree: matrices are
``(in, out)``, ``wq_b``'s columns a head's ``[nope | rope]``, ``wkv_a``'s
``[c_kv | k_pe]``, ``wkv_b``'s a head's ``[k_nope | v]``, the held experts
stacked ``(held, in, out)``.

On the chip ``decoder_logits`` runs one layer at a time and upcasts each
stored matrix where it is used (an expert layer's share would be 3.5 GB
in float32 beside the bfloat16 weights).

``precision`` names how the weight matmuls are computed: ``float32``
(the reference: "highest"), ``fp8`` (the control: both operands rounded
to e4m3's four significant bits), ``int8_weights`` (for information).
``fault`` plants one departure from the published layer (for the limits'
calibration): ``no_group_limit`` (top-k over all experts),
``no_shared_expert``, ``k_pe_not_rotated``, ``no_yarn_blend`` (plain
rotary frequencies), ``no_mscale`` (``m^2`` left out of ``s``).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("no_group_limit", "no_shared_expert", "k_pe_not_rotated",
          "no_yarn_blend", "no_mscale")

_DRAW_BLOCK = 8 * 1024 * 1024


# -------------------------------------------------------------- sizes ---

def dims(sizes):
    """The widths the layers are built from, by their published keys."""
    held = sizes["n_routed_experts"]
    return {"D": sizes["hidden_size"], "V": sizes["vocab_size"],
            "H": sizes["num_attention_heads"],
            "Rq": sizes["q_lora_rank"], "R": sizes["kv_lora_rank"],
            "nope": sizes["qk_nope_head_dim"],
            "rope": sizes["qk_rope_head_dim"], "dv": sizes["v_head_dim"],
            "F": sizes["intermediate_size"],
            "Fe": sizes["moe_intermediate_size"],
            "Fs": sizes["moe_intermediate_size"]
            * sizes["n_shared_experts"],
            "E": sizes.get("router_width", held), "held": held,
            "first": sizes.get("ep_rank", 0) * held,
            "L": sizes["num_hidden_layers"],
            "dense": sizes["first_k_dense_replace"]}


def param_shapes(sizes):
    """The parameter tree as {path: shape}, in the program's layout."""
    d = dims(sizes)
    D, H = d["D"], d["H"]
    attn = {"attn_norm": (D,), "wq_a": (D, d["Rq"]), "q_norm": (d["Rq"],),
            "wq_b": (d["Rq"], H * (d["nope"] + d["rope"])),
            "wkv_a": (D, d["R"] + d["rope"]), "kv_norm": (d["R"],),
            "wkv_b": (d["R"], H * (d["nope"] + d["dv"])),
            "wo": (H * d["dv"], D), "ffn_norm": (D,)}
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
            "layers": [dict(attn, **(moe if i >= d["dense"] else dense))
                       for i in range(d["L"])]}


def _weight_std(name, sizes):
    """Standard deviation of a seeded matrix (``assumed`` in the
    configuration file): ``a / sqrt(fan_in)``, ``a`` = 1 keeps every
    projection of a unit-variance input at unit variance.  The
    exceptions keep what follows alive: ``wq_b`` 0.75 (softmax scores of
    deviation about 1.5), the three ``w_down`` 2 (the routed sum, the
    shared expert and the dense MLP of the residual's order), the
    router 1.5 (sigmoid scores spread over 0.05-0.95), the head 2
    (logits of deviation 2)."""
    d = dims(sizes)
    D = d["D"]
    table = {
        "embed": 1.0,
        "lm_head": 2.0 / math.sqrt(D),
        "wq_a": 1.0 / math.sqrt(D),
        "wq_b": 0.75 / math.sqrt(d["Rq"]),
        "wkv_a": 1.0 / math.sqrt(D),
        "wkv_b": 1.0 / math.sqrt(d["R"]),
        "wo": 1.0 / math.sqrt(d["H"] * d["dv"]),
        "w_gate": 1.0 / math.sqrt(D), "w_up": 1.0 / math.sqrt(D),
        "w_down": 2.0 / math.sqrt(d["F"]),
        "router": 1.5 / math.sqrt(D),
        "ew_gate": 1.0 / math.sqrt(D), "ew_up": 1.0 / math.sqrt(D),
        "ew_down": 2.0 / math.sqrt(d["Fe"]),
        "sw_gate": 1.0 / math.sqrt(D), "sw_up": 1.0 / math.sqrt(D),
        "sw_down": 2.0 / math.sqrt(d["Fs"]),
    }
    return table[name]


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
    ``dtype`` (the published modelling code keeps the gate in float32),
    the bias N(0, 0.01): twice the gap between neighbouring scores at
    the top-8's edge, so it decides a good part of the choices, and
    small enough that the experts' loads stay within a quarter of each
    other, as a trained bias (whose purpose is balance) leaves them."""
    shapes = param_shapes(sizes)
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
                x = 0.01 * jax.random.normal(k, shape, jnp.float32)
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


def _fake_int8(x, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0,
                    1e-12)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, precision):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif precision == "int8_weights":
        w = _fake_int8(w, 0)
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


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(sizes, fault=None):
    """``(inv_freq (rope / 2,), factor on cos and sin)`` after the
    ``deepseek_v3`` / YaRN recipe: ``inv_freq = f_inter (1 - mask) +
    f_extra mask``, ``mask = 1 - ramp(low, high)``, the correction range
    from ``beta_fast`` and ``beta_slow`` turns over the original
    context."""
    dim, base = sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    rs = sizes.get("rope_scaling")
    f_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not rs or fault == "no_yarn_blend":
        return f_extra, 1.0
    factor = rs["factor"]
    f_inter = f_extra / factor

    def find_dim(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(find_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(find_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return f_inter * (1.0 - mask) + f_extra * mask, \
        mscale(factor, rs["mscale"]) / mscale(factor, rs["mscale_all_dim"])


def scale(sizes, fault=None):
    rs = sizes.get("rope_scaling")
    s = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5
    if rs and rs.get("mscale_all_dim") and fault != "no_mscale":
        s *= mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _rotary(x, sizes, fault):
    """(B, T, ..., rope), position t the row's index: lanes 2i, 2i + 1
    turned by ``t x inv_freq[i]``."""
    inv, factor = yarn(sizes, fault)
    T = x.shape[1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None]
    shape = (1, T) + (1,) * (x.ndim - 3) + (ang.shape[1],)
    cos = (jnp.cos(ang) * factor).reshape(shape)
    sin = (jnp.sin(ang) * factor).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(u, layer, sizes, precision, fault):
    d = dims(sizes)
    B, T, _ = u.shape
    H, R, nope, rope, dv = d["H"], d["R"], d["nope"], d["rope"], d["dv"]
    eps = sizes["rms_norm_eps"]
    c_q = _rms(_mm(u, layer["wq_a"], precision), layer["q_norm"], eps)
    q = _mm(c_q, layer["wq_b"], precision).reshape(B, T, H, nope + rope)
    ckv = _mm(u, layer["wkv_a"], precision)
    c_kv = _rms(ckv[..., :R], layer["kv_norm"], eps)
    kv = _mm(c_kv, layer["wkv_b"], precision).reshape(B, T, H, nope + dv)
    q_pe = _rotary(q[..., nope:], sizes, fault)
    k_pe = ckv[..., R:]
    if fault != "k_pe_not_rotated":
        k_pe = _rotary(k_pe, sizes, fault)
    s = (jnp.einsum("bqhn,bkhn->bhqk", q[..., :nope], kv[..., :nope],
                    precision=HIGHEST)
         + jnp.einsum("bqhn,bkn->bhqk", q_pe, k_pe, precision=HIGHEST)) \
        * scale(sizes, fault)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhv->bqhv", p, kv[..., nope:], precision=HIGHEST)
    return _mm(o.reshape(B, T, H * dv), layer["wo"], precision)


def route(m, layer, sizes, fault=None):
    """(chosen experts (.., k) over all ``router_width``, their weights)."""
    d = dims(sizes)
    k, G = sizes["num_experts_per_tok"], sizes["n_group"]
    s = 1.0 / (1.0 + jnp.exp(-jnp.matmul(m, layer["router"],
                                         precision=HIGHEST)))
    choice = s + layer["router_bias"]
    if fault != "no_group_limit":
        groups = choice.reshape(choice.shape[:-1] + (G, d["E"] // G))
        score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        _, best = jax.lax.top_k(score, sizes["topk_group"])
        kept = jnp.any(best[..., None] == jnp.arange(G), axis=-2)
        choice = jnp.where(jnp.repeat(kept, d["E"] // G, axis=-1), choice,
                           -jnp.inf)
    _, idx = jax.lax.top_k(choice, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sizes["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * sizes["routed_scaling_factor"]


def expert_layer(m, layer, sizes, precision="float32", fault=None,
                 shared=True):
    """The share's expert layer on (.., D): the held experts' terms, one
    expert at a time over every row and masked, plus the shared expert."""
    d = dims(sizes)
    idx, w = route(m, layer, sizes, fault)
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


def _block(x, layer, sizes, precision, fault):
    """One block on (B, T, D) float32."""
    eps = sizes["rms_norm_eps"]
    h = x + _attention(_rms(x, layer["attn_norm"], eps), layer, sizes,
                       precision, fault)
    m = _rms(h, layer["ffn_norm"], eps)
    if "router" in layer:
        return h + expert_layer(m, layer, sizes, precision, fault)
    return h + _swiglu(m, layer["w_gate"], layer["w_up"], layer["w_down"],
                       precision)


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


@functools.partial(jax.jit, static_argnames=("sizes_t", "precision",
                                             "fault"),
                   donate_argnums=(0,))
def _layer(x, layer, sizes_t, precision, fault):
    with jax.default_matmul_precision("highest"):
        return _block(x, layer, _sizes(sizes_t), precision, fault)


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
                                    "reduced_why")})
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    for layer in params["layers"]:
        x = _layer(x, layer, sizes_t, precision, fault)
    return _head(x, params["final_norm"], params["lm_head"],
                 sizes["rms_norm_eps"], precision)
