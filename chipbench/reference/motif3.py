"""Plain reference for the Motif-3 family (Motif-Technologies, 2026-08; HF
``model_type: Motif``; Motif-3-Beta), in float32.

Straightforward ``jax.numpy``: no kernels, no cache, no ring carried from
call to call, no batching of the experts, and nothing imported from the
program under test.  Whole sequences in, every position's logits out.
The attention is computed in its EXPANDED published form, not absorbed.

* residual (mHC, arXiv:2512.24880): ``n = mhc_expansion_rate`` streams a
  token, the embedding in each.  A branch F reads ``x~ = RMS(vec X)`` (no
  gain), ``H_pre = sigmoid(a_pre x~ Phi_pre + b_pre)``, ``H_post = 2
  sigmoid(a_post x~ Phi_post + b_post)``, ``H_res = Sinkhorn(exp(a_res
  mat(x~ Phi_res) + b_res))`` (row then column normalisation,
  ``mhc_sinkhorn_iters`` rounds), and ``X <- clamp(H_res X + H_post^T
  F(RMSNorm(H_pre X)), +-hidden_clamp)``; the attention branch, then the
  feed-forward branch; the head reads ``RMSNorm(sum of the streams)``;
* attention (GDLA): ``c_q = RMSNorm(u W_qa)``, ``q = c_q W_qb`` (heads of
  ``[nope | rope]``); ``[c_kv | k_r] = u W_kva``, ``c_kv =
  RMSNorm(c_kv)``; rotary on ``q_pe`` and ``k_r`` (lanes 2i and 2i + 1 a
  pair, base ``rope_theta``, no YaRN: ``apply_yarn_scaling`` false); per
  group ``g`` keys ``[c_kv W_uk_g | k_pe]`` and values ``c_kv W_uv_g``
  (``W_kvb`` a group's ``[W_uk | W_uv]``), head ``h`` in group ``h //
  r``; a softmax at ``head_dim^-0.5`` over the keys ``k <= q`` and, in a
  sliding layer of window ``W``, ``k > q - W``: an explicit mask over the
  whole sequence.  Head ``r g + r - 1`` is group g's noise head; a signal
  head's output is ``(a_h - sigmoid(u w_lambda_h) a_noise) *
  sigmoid(u W_gate)_h``, and ``out = concat(signal heads) W_o``;
* layer ``i`` slides where ``i mod sliding_window_period`` is not the
  period's last;
* feed-forward: ``(PN(x W_gate) * (x W_up)) W_down``, ``PN(z) = s (w0
  N(z^3) + w1 N(z^2) + w2 N(z)) + clamp(b, +-c)``, ``N(v) = v / rms(v)``
  over the width; a dense MLP in the first ``n_dense_first_layers``
  layers, the expert layer after them: ``s = sigmoid(m W_r)`` over all
  ``router_width`` experts; for choosing only ``s + b``;
  ``jax.lax.top_k`` picks ``experts_top_k``; the weights are the chosen
  ``s`` over their sum plus 1e-20 (``route_norm``), times
  ``route_scale``; then a plain loop over the experts this rank holds
  (``ep_rank`` says which block of ``num_experts``), each over every row
  and masked, plus the shared expert;
* head: ``RMSNorm(sum of the streams) lm_head`` (untied), over the
  vocabulary slice.

The weights are the benchmark's own, made here from ``--seed``
(``make_params``) and handed to the program and to the reference alike;
what is shared with the program is the layout of that tree: matrices are
``(in, out)``, the held experts stacked ``(held, in, out)``.

On the chip ``decoder_logits`` runs one layer at a time, upcasts each
stored matrix where it is used and takes the queries in blocks.

``precision`` names how the weight matmuls are computed: ``float32`` (the
reference: "highest"), ``fp8`` (the control: both operands rounded to
e4m3's four significant bits, and the streams between blocks rounded
alike).
``fault`` plants one departure from the published layer (for the limits'
calibration and the tests): ``window_left_out`` (the sliding layers
attend to the whole sequence), ``window_off_by_one`` (``k >= q - W``: one
key more), ``noise_not_subtracted`` (lambda 0), ``noise_head_first``
(head ``r g`` is the noise head), ``no_output_gate``, ``res_rows_only``
(``H_res`` row-normalised once, no Sinkhorn), ``silu_for_polynorm``
(SwiGLU's activation in every MLP), ``no_shared_expert``,
``ring_zero_filled`` (a query at ``q < W - 1`` reads ``W - 1 - q`` zero
keys and values before the sequence's first: a ring that is zeroed, not
masked).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("window_left_out", "window_off_by_one", "noise_not_subtracted",
          "noise_head_first", "no_output_gate", "res_rows_only",
          "silu_for_polynorm", "no_shared_expert", "ring_zero_filled")

_DRAW_BLOCK = 8 * 1024 * 1024
# queries a block of the attention: 256 against 5,120 keys and 80 heads
# are 0.42 GB of float32 scores
_QUERY_BLOCK = 256


# -------------------------------------------------------------- sizes ---

def dims(sizes):
    """The widths the layers are built from, by their published keys."""
    held = sizes["num_experts"]
    H, G = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    n = sizes["mhc_expansion_rate"]
    return {"D": sizes["hidden_size"], "V": sizes["vocab_size"], "H": H,
            "G": G, "r": H // G, "Hs": H - sizes["num_noise_heads"],
            "qr": sizes["q_lora_rank"], "R": sizes["kv_lora_rank"],
            "rope": sizes["qk_rope_head_dim"],
            "nope": sizes["head_dim"] - sizes["qk_rope_head_dim"],
            "dv": sizes["v_head_dim"], "F": sizes["intermediate_size"],
            "Fe": sizes["moe_intermediate_size"],
            "Fs": sizes["moe_intermediate_size"]
            * sizes["num_shared_experts"],
            "E": sizes.get("router_width", held), "held": held,
            "first": sizes.get("ep_rank", 0) * held,
            "L": sizes["num_hidden_layers"], "n": n, "M": n * (n + 2)}


def windows(sizes):
    """Each layer's window, 0 for a full layer."""
    period = sizes["sliding_window_period"]
    return [sizes["sliding_window"] if sizes["use_sliding_window"]
            and i % period != period - 1 else 0
            for i in range(sizes["num_hidden_layers"])]


def param_shapes(sizes):
    """The parameter tree as {path: shape}, in the program's layout."""
    d = dims(sizes)
    D, H, G, R = d["D"], d["H"], d["G"], d["R"]
    attn = {"attn_norm": (D,), "wq_a": (D, d["qr"]), "q_norm": (d["qr"],),
            "wq_b": (d["qr"], H * (d["nope"] + d["rope"])),
            "wkv_a": (D, R + d["rope"]), "kv_norm": (R,),
            "wkv_b": (R, G * (d["nope"] + d["dv"])),
            "w_lambda": (D, d["Hs"]), "w_ogate": (D, d["Hs"] * d["dv"]),
            "wo": (d["Hs"] * d["dv"], D), "ffn_norm": (D,)}
    for branch in ("attn", "ffn"):
        attn.update({"mhc_" + branch: (d["n"] * D, d["M"]),
                     "mhc_%s_a" % branch: (3,),
                     "mhc_%s_b" % branch: (d["M"],)})
    dense = {"w_gate": (D, d["F"]), "w_up": (D, d["F"]),
             "w_down": (d["F"], D), "poly": (4,)}
    moe = {"router": (D, d["E"]), "router_bias": (d["E"],),
           "ew_gate": (d["held"], D, d["Fe"]),
           "ew_up": (d["held"], D, d["Fe"]),
           "ew_down": (d["held"], d["Fe"], D), "ew_poly": (d["held"], 4),
           "sw_gate": (D, d["Fs"]), "sw_up": (D, d["Fs"]),
           "sw_down": (d["Fs"], D), "sw_poly": (4,)}
    dense_layers = sizes["n_dense_first_layers"]
    return {"embed": (d["V"], D), "final_norm": (D,),
            "lm_head": (D, d["V"]),
            "layers": [dict(attn, **(dense if i < dense_layers else moe))
                       for i in range(d["L"])]}


def _weight_std(name, sizes):
    """Standard deviation of a seeded matrix (``assumed`` in the
    configuration file): ``a / sqrt(fan_in)``, ``a`` = 1 keeps every
    projection of a unit-variance input at unit variance.  What is left
    to choose: the embedding N(0, 1), the head 2 (logits of deviation 2),
    the router ``router_a``, the routed experts' ``ew_down`` against the
    shared expert's ``sw_down`` (0.5 against 2: a held expert's term a
    few percent of the branch, so that a choice bfloat16 breaks the other
    way on a near-tie does not cascade through the later layers'
    choices), and the mHC maps (``mhc_phi_a``: ``x~ Phi`` of deviation
    ``mhc_phi_a``)."""
    d = dims(sizes)
    D = d["D"]
    a = sizes["assumed"]
    return {
        "embed": 1.0, "lm_head": 2.0 / math.sqrt(D),
        "wq_a": 1.0 / math.sqrt(D), "wq_b": 1.0 / math.sqrt(d["qr"]),
        "wkv_a": 1.0 / math.sqrt(D), "wkv_b": 1.0 / math.sqrt(d["R"]),
        "w_lambda": 1.0 / math.sqrt(D), "w_ogate": 1.0 / math.sqrt(D),
        "wo": 1.0 / math.sqrt(d["Hs"] * d["dv"]),
        "mhc_attn": a["mhc_phi_a"] / math.sqrt(d["n"] * D),
        "mhc_ffn": a["mhc_phi_a"] / math.sqrt(d["n"] * D),
        "w_gate": 1.0 / math.sqrt(D), "w_up": 1.0 / math.sqrt(D),
        "w_down": 1.0 / math.sqrt(d["F"]),
        "router": a["router_a"] / math.sqrt(D),
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
    1 + N(0, 0.02); float32 whatever ``dtype``: the router and its bias
    (N(0, ``expert_bias_std``)), the mHC maps, their scales (each
    ``mhc_alpha``) and biases (``b_pre``, ``b_post`` N(0,
    ``mhc_bias_std``^2), ``b_res`` N(0, ``mhc_res_bias_std``^2)), and the
    PolyNorms (``w_i`` 1/3 + N(0, ``poly_w_std``^2), ``b`` N(0,
    ``poly_b_std``^2))."""
    shapes = param_shapes(sizes)
    a = sizes["assumed"]
    n = sizes["mhc_expansion_rate"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            name = path[-1].key
            k = jax.random.fold_in(key, i)
            normal = functools.partial(jax.random.normal, k, shape,
                                       jnp.float32)
            if name.endswith("norm"):
                x = (1.0 + 0.02 * normal()).astype(dtype)
            elif name == "router_bias":
                x = a["expert_bias_std"] * normal()
            elif name.endswith("poly"):
                z = normal()
                x = jnp.concatenate([1.0 / 3 + a["poly_w_std"] * z[..., :3],
                                     a["poly_b_std"] * z[..., 3:]], -1)
            elif name.startswith("mhc_") and name.endswith("_a"):
                x = jnp.full(shape, a["mhc_alpha"], jnp.float32)
            elif name.startswith("mhc_") and name.endswith("_b"):
                z = normal()
                x = jnp.concatenate([a["mhc_bias_std"] * z[:2 * n],
                                     a["mhc_res_bias_std"] * z[2 * n:]])
            elif name == "router" or name.startswith("mhc_"):
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


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def sinkhorn(m, iters):
    """Row then column normalisation of the positive (.., n, n) ``m``,
    ``iters`` rounds."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def polynorm(z, w, sizes):
    """``PN(z)`` over the last axis, ``w`` (.., 4) ``(w0, w1, w2, b)``."""
    eps = sizes["rms_norm_eps"]

    def n(v):
        return v / jnp.sqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True)
                            + eps)
    c = sizes["polynorm_bias_clamp"]
    return sizes["polynorm_output_scale"] * (
        w[..., 0:1] * n(z ** 3) + w[..., 1:2] * n(z ** 2)
        + w[..., 2:3] * n(z)) + jnp.clip(w[..., 3:4], -c, c)


def _mlp(x, w_gate, w_up, w_down, poly, sizes, precision, fault):
    g = _mm(x, w_gate, precision)
    act = g * _sigmoid(g) if fault == "silu_for_polynorm" \
        else polynorm(g, poly.astype(jnp.float32), sizes)
    return _mm(act * _mm(x, w_up, precision), w_down, precision)


def _rotary(x, lo, theta):
    """(B, n, H?, d): positions ``lo ..`` along axis 1, lanes 2i and
    2i + 1 a pair turned by ``pos / theta^(2i / d)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pos = lo + jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * inv[None]                          # (n, d/2)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(u, layer, sizes, window, precision, fault):
    """GDLA, expanded, on (B, T, D) normed rows: (B, T, D)."""
    d = dims(sizes)
    B, T, _ = u.shape
    H, G, r, R = d["H"], d["G"], d["r"], d["R"]
    nope, rope, dv = d["nope"], d["rope"], d["dv"]
    eps = sizes["rms_norm_eps"]
    theta = float(sizes["rope_theta"])
    c_q = _rms(_mm(u, layer["wq_a"], precision), layer["q_norm"], eps)
    q = _mm(c_q, layer["wq_b"], precision).reshape(B, T, H, nope + rope)
    ckv = _mm(u, layer["wkv_a"], precision)
    c_kv = _rms(ckv[..., :R], layer["kv_norm"], eps)
    k_pe = _rotary(ckv[..., R:], 0, theta)                   # (B, T, rope)
    kv = _mm(c_kv, layer["wkv_b"], precision).reshape(B, T, G, nope + dv)
    # each group's keys and values, repeated over its r heads
    k_nope = jnp.repeat(kv[..., :nope], r, axis=2)           # (B, T, H, .)
    v = jnp.repeat(kv[..., nope:], r, axis=2)
    scale = (nope + rope) ** -0.5
    block = min(_QUERY_BLOCK, T)
    nb = -(-T // block)
    qp = jnp.pad(q, ((0, 0), (0, nb * block - T), (0, 0), (0, 0)))
    qp = qp.reshape(B, nb, block, H, nope + rope).transpose(1, 0, 2, 3, 4)

    def one(args):
        i, qb = args
        lo = i * block
        s = (jnp.einsum("bqhn,bkhn->bhqk", qb[..., :nope], k_nope,
                        precision=HIGHEST)
             + jnp.einsum("bqhn,bkn->bhqk",
                          _rotary(qb[..., nope:], lo, theta), k_pe,
                          precision=HIGHEST)) * scale
        return _softmax_av(s, v, lo, window, fault)
    a = jax.lax.map(one, (jnp.arange(nb), qp))          # (nb, B, blk, H, dv)
    a = a.transpose(1, 0, 2, 3, 4).reshape(B, nb * block, H, dv)[:, :T]
    a = a.reshape(B, T, G, r, dv)
    if fault == "noise_head_first":
        a = jnp.concatenate([a[:, :, :, 1:], a[:, :, :, :1]], axis=3)
    lam = _sigmoid(_mm(u, layer["w_lambda"], precision)).reshape(
        B, T, G, r - 1)
    if fault == "noise_not_subtracted":
        lam = jnp.zeros_like(lam)
    o = (a[:, :, :, :r - 1] - lam[..., None] * a[:, :, :, r - 1:]
         ).reshape(B, T, d["Hs"] * dv)
    if fault != "no_output_gate":
        o = o * _sigmoid(_mm(u, layer["w_ogate"], precision))
    return _mm(o, layer["wo"], precision)


def _softmax_av(s, v, lo, window, fault):
    """Scores (B, H, n, T) of queries ``lo ..`` against every key, under
    the causal mask and, on a sliding layer, the window's; the values'
    weighted sum (B, n, H, dv)."""
    qi = lo + jnp.arange(s.shape[2])[:, None]
    ki = jnp.arange(s.shape[3])[None, :]
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
    return jnp.einsum("bhqk,bkhv->bqhv", p, v, precision=HIGHEST)


def route(m, layer, sizes):
    """(chosen experts (.., k) over all ``router_width``, their weights)."""
    s = _sigmoid(jnp.matmul(m, layer["router"], precision=HIGHEST))
    _, idx = jax.lax.top_k(s + layer["router_bias"],
                           sizes["experts_top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sizes["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * sizes["route_scale"]


def expert_layer(m, layer, sizes, precision="float32", fault=None,
                 shared=True):
    """The share's expert layer on (.., D): the held experts' terms, one
    expert at a time over every row and masked, plus the shared expert."""
    d = dims(sizes)
    idx, w = route(m, layer, sizes)

    def one(e, y):
        mine = jnp.sum(jnp.where(idx == d["first"] + e, w, 0.0), axis=-1)
        out = _mlp(m, layer["ew_gate"][e], layer["ew_up"][e],
                   layer["ew_down"][e], layer["ew_poly"][e], sizes,
                   precision, fault)
        return y + mine[..., None] * out
    y = jax.lax.fori_loop(0, d["held"], one, jnp.zeros_like(m))
    if shared and fault != "no_shared_expert":
        y = y + _mlp(m, layer["sw_gate"], layer["sw_up"], layer["sw_down"],
                     layer["sw_poly"], sizes, precision, fault)
    return y


def mhc_maps(x, layer, branch, sizes, fault=None):
    """A branch's ``(H_pre, H_post, H_res)`` from the streams (.., n, D)."""
    n = sizes["mhc_expansion_rate"]
    flat = x.reshape(x.shape[:-2] + (-1,))
    flat = flat / jnp.sqrt(jnp.mean(jnp.square(flat), axis=-1,
                                    keepdims=True) + sizes["rms_norm_eps"])
    proj = jnp.matmul(flat, layer["mhc_" + branch], precision=HIGHEST)
    a, b = layer["mhc_%s_a" % branch], layer["mhc_%s_b" % branch]
    pre = _sigmoid(a[0] * proj[..., :n] + b[:n])
    post = 2.0 * _sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
    e = jnp.exp(a[2] * proj[..., 2 * n:] + b[2 * n:]).reshape(
        proj.shape[:-1] + (n, n))
    res = e / jnp.sum(e, axis=-1, keepdims=True) \
        if fault == "res_rows_only" \
        else sinkhorn(e, sizes["mhc_sinkhorn_iters"])
    return pre, post, res


def _branch(x, layer, branch, f, sizes, fault):
    """``clamp(H_res X + H_post^T F(RMSNorm(H_pre X)))`` on (B, T, n, D)."""
    pre, post, res = mhc_maps(x, layer, branch, sizes, fault)
    u = _rms(jnp.einsum("btn,btnd->btd", pre, x, precision=HIGHEST),
             layer[branch + "_norm"], sizes["rms_norm_eps"])
    y = jnp.einsum("btij,btjd->btid", res, x, precision=HIGHEST) \
        + post[..., None] * f(u)[:, :, None]
    c = sizes["hidden_clamp"]
    return jnp.clip(y, -c, c)


def _block(x, layer, sizes, window, precision, fault):
    """One block on (B, T, n, D) float32 streams."""
    x = _branch(x, layer, "attn", lambda u: _attention(
        u, layer, sizes, window, precision, fault), sizes, fault)
    if "router" in layer:
        def ffn(u):
            return expert_layer(u, layer, sizes, precision, fault)
    else:
        def ffn(u):
            return _mlp(u, layer["w_gate"], layer["w_up"], layer["w_down"],
                        layer["poly"], sizes, precision, fault)
    x = _branch(x, layer, "ffn", ffn, sizes, fault)
    return _fake_fp8(x) if precision == "fp8" else x


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
        return _mm(_rms(jnp.sum(x, axis=-2), norm, eps), w, precision)


def decoder_logits(params, tokens, sizes, precision="float32", fault=None):
    """Teacher-forced causal LM: (B, T) ids -> (B, T, V) float32 logits;
    position t's row scores the token at t + 1.  One layer at a time,
    each matrix upcast at its matmul."""
    sizes_t = _static({k: v for k, v in sizes.items()
                       if k not in ("engine", "rehearse", "assumed",
                                    "reduced_why", "rope_scaling",
                                    "polynorm_output_scale_per_layer")})
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    x = jnp.broadcast_to(x[:, :, None], x.shape[:2]
                         + (sizes["mhc_expansion_rate"], x.shape[-1]))
    if precision == "fp8":
        x = _fake_fp8(x)
    for layer, window in zip(params["layers"], windows(sizes)):
        x = _layer(x, layer, sizes_t, int(window), precision, fault)
    return _head(x, params["final_norm"], params["lm_head"],
                 sizes["rms_norm_eps"], precision)
