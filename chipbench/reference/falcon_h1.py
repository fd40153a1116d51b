"""Plain reference for the Falcon-H1 family (TII, 2025-05; ``model_type:
falcon_h1``), in float32.

Straightforward ``jax.numpy``: no kernels, no cache, no chunking of the
recurrence, and nothing imported from the program under test.  Every
layer is the same block: one RMSNorm, then a Mamba-2 mixer and a rotary
grouped-query attention on the SAME normed input, summed into the
residual, then a SwiGLU feed-forward; muP multipliers where the
published ``falcon_h1`` model code applies them.

* the recurrence is written literally, a ``lax.scan`` over time:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``;
* the causal depthwise convolution is an explicit sum over its 4 taps;
* attention is a full causal softmax, each key/value head repeated over
  the query heads it serves; rotary over the whole head, the two halves
  rotated against each other (``rotate_half``), no scaling;
* the gated norm multiplies by ``silu(z)`` first
  (``mamba_norm_before_gate`` false) and normalises each of the
  ``mamba_n_groups`` groups of channels by itself.

The weights are the benchmark's own, made here from ``--seed``
(``make_params``) and handed to the program and to the reference alike;
what is shared with the program is the layout of that tree, the
program's input format: matrices are ``(in, out)``, ``in_proj``'s
columns are ``[z | x | B | C | dt]``, ``conv_w`` is ``(taps, channels)``
with the last tap on the current input.

On the chip the float32 weights of six layers do not fit beside the
bfloat16 ones: ``decoder_logits`` upcasts and runs one layer at a time,
and the head in blocks of the vocabulary, into one preallocated array.

``precision`` names how the weight matmuls are computed, as in
``bert.py``: ``float32`` (the reference: "highest"), ``fp8`` (the control,
both operands rounded to e4m3's four significant bits), ``int8_weights``
(the weights alone, a scale per column; read for information).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# elements of one block when a large leaf is drawn, and columns of the
# vocabulary the head computes at a time
_DRAW_BLOCK = 8 * 1024 * 1024
_HEAD_BLOCK = 16 * 1024


# -------------------------------------------------------------- sizes ---

def dims(sizes):
    """The widths the layers are built from, by their published keys."""
    d_ssm = sizes["mamba_d_ssm"]
    G, N = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    H = sizes["mamba_n_heads"]
    return {"D": sizes["hidden_size"], "F": sizes["intermediate_size"],
            "V": sizes["vocab_size"], "d_ssm": d_ssm, "G": G, "N": N,
            "H": H, "P": sizes["mamba_d_head"], "K": sizes["mamba_d_conv"],
            "conv_dim": d_ssm + 2 * G * N,
            "in_proj": 2 * d_ssm + 2 * G * N + H,
            "Hq": sizes["num_attention_heads"],
            "Hkv": sizes["num_key_value_heads"], "dh": sizes["head_dim"]}


def param_shapes(sizes):
    """The parameter tree as {path: shape}, in the program's layout."""
    d = dims(sizes)
    D, F, V = d["D"], d["F"], d["V"]
    layer = {"in_norm": (D,), "in_proj": (D, d["in_proj"]),
             "conv_w": (d["K"], d["conv_dim"]), "conv_b": (d["conv_dim"],),
             "dt_bias": (d["H"],), "A_log": (d["H"],), "D": (d["H"],),
             "ssm_norm": (d["d_ssm"],), "out_proj": (d["d_ssm"], D),
             "wq": (D, d["Hq"] * d["dh"]), "wk": (D, d["Hkv"] * d["dh"]),
             "wv": (D, d["Hkv"] * d["dh"]), "wo": (d["Hq"] * d["dh"], D),
             "ff_norm": (D,), "w_gate": (D, F), "w_up": (D, F),
             "w_down": (F, D)}
    return {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V),
            "layers": [dict(layer)
                       for _ in range(sizes["num_hidden_layers"])]}


def _weight_std(name, sizes):
    """Standard deviation of a seeded matrix: 1 / (sqrt(fan_in) x the
    published multiplier on its path), so that under those multipliers
    every projection of a unit-variance input has about unit variance
    and the logits are alive (a departure: the configuration file lists
    it).  ``in_proj`` takes the geometric middle of its five
    ``ssm_multipliers``; the head aims at logits of deviation 2."""
    d = dims(sizes)
    D = d["D"]
    gate_mult, down_mult = sizes["mlp_multipliers"]
    ssm_mid = math.exp(sum(math.log(m) for m in sizes["ssm_multipliers"])
                       / len(sizes["ssm_multipliers"]))
    table = {
        "embed": 1.0 / sizes["embedding_multiplier"],
        "lm_head": 2.0 / (math.sqrt(D) * sizes["lm_head_multiplier"]),
        "in_proj": 1.0 / (math.sqrt(D) * sizes["ssm_in_multiplier"]
                          * ssm_mid),
        "out_proj": 1.0 / (math.sqrt(d["d_ssm"])
                           * sizes["ssm_out_multiplier"]),
        "wq": 1.0 / (math.sqrt(D) * sizes["attention_in_multiplier"]),
        "wk": 1.5 / (math.sqrt(D) * sizes["attention_in_multiplier"]
                     * sizes["key_multiplier"]),
        "wv": 1.0 / (math.sqrt(D) * sizes["attention_in_multiplier"]),
        "wo": 1.0 / (math.sqrt(d["Hq"] * d["dh"])
                     * sizes["attention_out_multiplier"]),
        "w_gate": 1.0 / (math.sqrt(D) * gate_mult),
        "w_up": 1.0 / math.sqrt(D),
        "w_down": 1.0 / (math.sqrt(d["F"]) * down_mult),
        "conv_w": 0.5, "conv_b": 0.1,
    }
    return table[name]


def _normal(key, shape, std, dtype):
    """N(0, std^2) of ``shape`` in ``dtype``, a large leaf drawn, scaled
    and cast in blocks of rows, so that no whole-leaf float32 temporary
    is ever live (the embedding's would be 5.3 GB)."""
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
    1 + N(0, 0.02); and Mamba-2's own initialisation of the recurrence
    (``assumed`` in the configuration file): ``A ~ U(1, 16)`` held as
    ``A_log``, ``dt ~ logU(1e-3, 1e-1)`` held as the inverse softplus
    ``dt_bias``, ``D = 1``; those three stay float32 whatever ``dtype``
    (32 numbers each, exponentiated)."""
    shapes = param_shapes(sizes)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            name = path[-1].key
            k = jax.random.fold_in(key, i)
            if name == "A_log":
                x = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                x = dt + jnp.log(-jnp.expm1(-dt))
            elif name == "D":
                x = jnp.ones(shape, jnp.float32)
            elif name.endswith("norm"):
                x = (1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
                     ).astype(dtype)
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
    if precision == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif precision == "int8_weights":
        w = _fake_int8(w, 0)
    elif precision != "float32":
        raise ValueError("precision %r" % (precision,))
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _mixer(u, layer, sizes, precision):
    """The Mamba-2 mixer on (B, T, D): the recurrence step by step."""
    d = dims(sizes)
    B, T, _ = u.shape
    H, P, G, N, K = d["H"], d["P"], d["G"], d["N"], d["K"]
    d_ssm, GN = d["d_ssm"], d["G"] * d["N"]
    p = _mm(sizes["ssm_in_multiplier"] * u, layer["in_proj"], precision)
    widths = [d_ssm, d_ssm, GN, GN, H]           # z | x | B | C | dt
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in
                           zip(widths, sizes["ssm_multipliers"])])
    p = p * mup
    z, xBC, dt = p[..., :d_ssm], p[..., d_ssm:d_ssm + d["conv_dim"]], \
        p[..., d_ssm + d["conv_dim"]:]
    # causal depthwise convolution, tap K-1 on the current input
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = layer["conv_b"] + sum(layer["conv_w"][j] * padded[:, j:j + T]
                                 for j in range(K))
    xBC = _silu(conv)
    x = xBC[..., :d_ssm].reshape(B, T, H, P)
    Bm = xBC[..., d_ssm:d_ssm + GN].reshape(B, T, G, N)
    Cm = xBC[..., d_ssm + GN:].reshape(B, T, G, N)
    # head h reads group h // (H / G)
    Bh = jnp.repeat(Bm, H // G, axis=2)          # (B, T, H, N)
    Ch = jnp.repeat(Cm, H // G, axis=2)
    dt = _softplus(dt + layer["dt_bias"])        # (B, T, H)
    A = -jnp.exp(layer["A_log"])                 # (H,)

    def step(S, inp):
        x_t, B_t, C_t, dt_t = inp                # (B,H,P) (B,H,N) x2 (B,H)
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :]
        return S, jnp.sum(S * C_t[..., None, :], axis=-1)

    S0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, y = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, Bh, Ch, dt)))
    y = jnp.moveaxis(y, 0, 1) + layer["D"][:, None] * x      # (B,T,H,P)
    y = y.reshape(B, T, d_ssm) * _silu(z)
    # the gated norm: each group's d_ssm / G channels by themselves
    yg = y.reshape(B, T, G, d_ssm // G)
    yg = yg / jnp.sqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                       + sizes["rms_norm_eps"])
    y = yg.reshape(B, T, d_ssm) * layer["ssm_norm"]
    return _mm(y, layer["out_proj"], precision)


def _rotary(x, theta):
    """(B, T, H, dh), position t = the row's index: HF's ``rotate_half``
    convention, ``inv_freq = theta ** (-2i / dh)`` on both halves."""
    T, dh = x.shape[1], x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _attention(a, layer, sizes, precision):
    d = dims(sizes)
    B, T, _ = a.shape
    Hq, Hkv, dh = d["Hq"], d["Hkv"], d["dh"]
    q = _mm(a, layer["wq"], precision).reshape(B, T, Hq, dh)
    k = (sizes["key_multiplier"] * _mm(a, layer["wk"], precision)
         ).reshape(B, T, Hkv, dh)
    v = _mm(a, layer["wv"], precision).reshape(B, T, Hkv, dh)
    q, k = _rotary(q, sizes["rope_theta"]), _rotary(k, sizes["rope_theta"])
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / math.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    return _mm(o.reshape(B, T, Hq * dh), layer["wo"], precision)


def _block(x, layer, sizes, precision):
    """One parallel block on (B, T, D) float32."""
    eps = sizes["rms_norm_eps"]
    gate_mult, down_mult = sizes["mlp_multipliers"]
    u = _rms(x, layer["in_norm"], eps)
    x = x + sizes["ssm_out_multiplier"] * _mixer(u, layer, sizes, precision) \
        + sizes["attention_out_multiplier"] * _attention(
            sizes["attention_in_multiplier"] * u, layer, sizes, precision)
    m = _rms(x, layer["ff_norm"], eps)
    h = _mm(m, layer["w_up"], precision) \
        * _silu(gate_mult * _mm(m, layer["w_gate"], precision))
    return x + down_mult * _mm(h, layer["w_down"], precision)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _static(sizes):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in sizes.items()
        if isinstance(v, (int, float))
        or (isinstance(v, list) and all(isinstance(e, (int, float))
                                        for e in v))))


@functools.partial(jax.jit, static_argnames=("sizes_t",))
def _embed(embed, tokens, sizes_t):
    return embed[tokens].astype(jnp.float32) \
        * dict(sizes_t)["embedding_multiplier"]


@functools.partial(jax.jit, static_argnames=("sizes_t", "precision"),
                   donate_argnums=(0,))
def _layer(x, layer, sizes_t, precision):
    with jax.default_matmul_precision("highest"):
        return _block(x, _f32(layer), dict(sizes_t), precision)


@functools.partial(jax.jit, static_argnames=("sizes_t",))
def _final_norm(x, w, sizes_t):
    return _rms(x, w.astype(jnp.float32), dict(sizes_t)["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("sizes_t", "precision"),
                   donate_argnums=(0,))
def _head_block(logits, h, w, start, sizes_t, precision):
    with jax.default_matmul_precision("highest"):
        part = dict(sizes_t)["lm_head_multiplier"] * _mm(
            h, w.astype(jnp.float32), precision)
    return jax.lax.dynamic_update_slice(logits, part, (0, 0, start))


def decoder_logits(params, tokens, sizes, precision="float32"):
    """Teacher-forced causal LM: (B, T) ids -> (B, T, V) float32 logits;
    position t's row scores the token at t + 1.  One layer at a time,
    its weights upcast inside the call, and the head in blocks of the
    vocabulary written into one array."""
    sizes_t = _static(sizes)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed(params["embed"], tokens, sizes_t)
    for layer in params["layers"]:
        x = _layer(x, layer, sizes_t, precision)
    h = _final_norm(x, params["final_norm"], sizes_t)
    V = sizes["vocab_size"]
    logits = jnp.zeros(tokens.shape + (V,), jnp.float32)
    for v0 in range(0, V, _HEAD_BLOCK):
        w = params["lm_head"][:, v0:v0 + _HEAD_BLOCK]
        logits = _head_block(logits, h, w, v0, sizes_t, precision)
    return logits
