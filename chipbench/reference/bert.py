"""Plain reference for the BERT family (Devlin et al. 2018), in float32.

Straightforward ``jax.numpy``: no kernels, no cache, no batching tricks, and
nothing imported from the program under test.  Two uses of one block:

* the bidirectional encoder with the masked-LM head, its loss, gradients
  and the AdamW update (``train_reference``) -- the `bert_base` cells;
* the same block under a causal mask with the LM head, teacher-forced over
  whole sequences (``decoder_logits``) -- the `bert_large_decoder` cells
  (HF ``BertLMHeadModel(is_decoder=True)``, Rothe et al. arXiv:1907.12461).

The weights are the benchmark's own, made here from ``--seed``
(``make_params``) and handed to the program and to the reference alike; the
only thing shared with the program is the layout of that tree, which is
the program's input format.

Departures from the published model, all of them the program's and copied
so that the two compute the same function:
* GELU is the tanh form (Google's original BERT code; HF's "gelu" is erf);
* the head's transform ``mlm_dense`` has no bias;
* the decoder adds no token-type embedding.

``precision`` names how the weight matmuls are computed: ``float32`` (the
reference: "highest", true f32 on the MXU); ``fp8`` (the control, the
nearest precision below the configurations' bfloat16: both operands
rounded to e4m3's four significant bits, the gradient passing straight
through -- int8 with a scale per row keeps about as many bits as bfloat16
and separates nothing); ``int8_weights`` (the weights alone rounded to
int8 with a scale per column: the program's own weight-only option, read
for information).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ weights ---

def param_shapes(sizes):
    """The parameter tree as {path: shape}, in the program's layout."""
    D, F, V = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["vocab_size"]
    ln = {"g": (D,), "b": (D,)}
    layer = {"wq": (D, D), "wk": (D, D), "wv": (D, D), "wo": (D, D),
             "bq": (D,), "bk": (D,), "bv": (D,), "bo": (D,),
             "ln1": dict(ln), "ln2": dict(ln),
             "w1": (D, F), "b1": (F,), "w2": (F, D), "b2": (D,)}
    return {"tok_emb": (V, D),
            "pos_emb": (sizes["max_position_embeddings"], D),
            "type_emb": (max(1, sizes["type_vocab_size"]), D),
            "emb_ln": dict(ln), "mlm_dense": (D, D), "mlm_ln": dict(ln),
            "mlm_bias": (V,),
            "layers": [dict(layer, ln1=dict(ln), ln2=dict(ln))
                       for _ in range(sizes["num_hidden_layers"])]}


def _is_shape(x):
    return isinstance(x, tuple)


def make_params(seed, sizes, dtype):
    """Every leaf from the seed in ONE jitted call, on the device, in the
    type it is served or trained in.  Matrices and biases are N(0, 0.02),
    LayerNorm gains 1 + N(0, 0.02): nothing is exactly 0 or 1, so a bias
    or a gain that the program dropped would show."""
    shapes = param_shapes(sizes)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            x = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
            if getattr(path[-1], "key", None) == "g":
                x = 1.0 + x
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # a seed may be a little over 2**31: fold it into 32 unsigned bits
    key = jax.random.PRNGKey(np.uint32(int(seed) % (2 ** 32)))
    return jax.jit(build)(key)


# ------------------------------------------------------------- blocks ---

def _straight_through(x, q):
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x):
    """Round to e4m3's four significant bits (the exponent's range is not
    narrowed: kinder than real fp8); the gradient passes straight through."""
    m, e = jnp.frexp(x)
    return _straight_through(x, jnp.ldexp(jnp.round(m * 16.0) / 16.0, e))


def _fake_int8(x, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0,
                    1e-12)
    return _straight_through(x, jnp.clip(jnp.round(x / s), -127, 127) * s)


def _mm(x, w, precision):
    if precision == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif precision == "int8_weights":
        w = _fake_int8(w, 0)
    elif precision != "float32":
        raise ValueError("precision %r" % (precision,))
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer_norm(x, ln, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * ln["g"] + ln["b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, layer, sizes, causal, precision):
    """One post-LN transformer block on (B, T, D) float32."""
    B, T, D = x.shape
    H = sizes["num_attention_heads"]
    dh = D // H
    eps = sizes["layer_norm_eps"]
    q = (_mm(x, layer["wq"], precision) + layer["bq"]).reshape(B, T, H, dh)
    k = (_mm(x, layer["wk"], precision) + layer["bk"]).reshape(B, T, H, dh)
    v = (_mm(x, layer["wv"], precision) + layer["bv"]).reshape(B, T, H, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / math.sqrt(dh)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s,
                      -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    a = _mm(a.reshape(B, T, D), layer["wo"], precision) + layer["bo"]
    x = _layer_norm(x + a, layer["ln1"], eps)
    h = _gelu(_mm(x, layer["w1"], precision) + layer["b1"])
    h = _mm(h, layer["w2"], precision) + layer["b2"]
    return _layer_norm(x + h, layer["ln2"], eps)


def _head(params, x, sizes, precision):
    h = _gelu(_mm(x, params["mlm_dense"], precision))
    h = _layer_norm(h, params["mlm_ln"], sizes["layer_norm_eps"])
    return _mm(h, params["tok_emb"].T, precision) + params["mlm_bias"]


def _f32(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def encoder_logits(params, tokens, type_ids, sizes, precision="float32"):
    """(B, T) ids -> (B, T, V) masked-LM logits."""
    T = tokens.shape[1]
    x = params["tok_emb"][tokens] + params["pos_emb"][:T][None] \
        + params["type_emb"][type_ids]
    x = _layer_norm(x, params["emb_ln"], sizes["layer_norm_eps"])
    for layer in params["layers"]:
        x = _block(x, layer, sizes, False, precision)
    return _head(params, x, sizes, precision)


@functools.partial(jax.jit, static_argnames=("sizes_t", "precision"))
def _decoder_logits(params, tokens, sizes_t, precision):
    sizes = dict(sizes_t)
    params = _f32(params)
    T = tokens.shape[1]
    x = params["tok_emb"][tokens] + params["pos_emb"][:T][None]
    x = _layer_norm(x, params["emb_ln"], sizes["layer_norm_eps"])
    for layer in params["layers"]:
        x = _block(x, layer, sizes, True, precision)
    return _head(params, x, sizes, precision)


def _static(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float))))


def decoder_logits(params, tokens, sizes, precision="float32"):
    """Teacher-forced causal LM: (B, T) ids -> (B, T, V) float32 logits;
    position t's row scores the token at t + 1."""
    return _decoder_logits(params, jnp.asarray(tokens, jnp.int32),
                           _static(sizes), precision)


# ----------------------------------------------------------- training ---

def _nll_sum(params, batch, sizes, precision):
    logits = encoder_logits(params, batch["tokens"], batch["type_ids"],
                            sizes, precision)
    labels = batch["labels"]
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0))


@functools.partial(jax.jit, static_argnames=("sizes_t", "precision"))
def _block_grads(params, batch, sizes_t, precision):
    return jax.value_and_grad(_nll_sum)(params, batch, dict(sizes_t),
                                        precision)


@functools.partial(jax.jit, static_argnames=("hyper_t",))
def _adamw(params, grads, mu, nu, t, hyper_t):
    h = dict(hyper_t)
    b1, b2 = h["beta1"], h["beta2"]

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                      + h["eps"])
        return p - h["learning_rate"] * (step + h["weight_decay"] * p), m, v

    out = jax.tree_util.tree_map(one, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(        # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def leaf_norms(tree):
    """l2 norm of every leaf, as one float32 vector in leaf order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree_util.tree_leaves(tree)])


@jax.jit
def leaf_change_norms(new, old):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(new),
                        jax.tree_util.tree_leaves(old))])


def loss_and_grads(params, batch, sizes, precision="float32", row_block=4,
                   rows=None):
    """Mean masked NLL and its gradient, accumulated over blocks of rows so
    that float32 activations fit beside the parameters.  ``rows`` (a fault
    for the tests) keeps only those rows, the mean taken over the rest."""
    batch = {k: np.asarray(v) for k, v in batch.items()}
    if rows is not None:
        batch = {k: v[rows] for k, v in batch.items()}
    n = batch["tokens"].shape[0]
    count = float(np.sum(batch["labels"] >= 0))
    total, grads = 0.0, None
    for r0 in range(0, n, row_block):
        blk = {k: jnp.asarray(v[r0:r0 + row_block]) for k, v in batch.items()}
        part, g = _block_grads(params, blk, _static(sizes), precision)
        total = total + part
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    grads = jax.tree_util.tree_map(lambda g: g / count, grads)
    return total / count, grads


def train_reference(params0, batches, sizes, hyper, precision="float32",
                    row_block=4, rows=None, frozen=False):
    """Follow the first ``len(batches)`` AdamW steps from ``params0``.

    Returns the losses, the first gradient with its per-leaf norms, and
    the per-leaf norms of the parameters' change after the last step.
    ``rows`` and ``frozen`` plant the faults the tests need: half a batch
    left out; a step that returns its state unchanged."""
    params = _f32(params0)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu = zeros, zeros
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        loss, grads = loss_and_grads(params, batch, sizes, precision,
                                     row_block, rows)
        losses.append(float(loss))
        if t == 1:
            grad1, grad_norms = grads, np.asarray(leaf_norms(grads))
        if not frozen:
            params, mu, nu = _adamw(params, grads, mu, nu, float(t),
                                    _static(hyper))
    change = np.asarray(leaf_change_norms(params, _f32(params0)))
    return {"losses": losses, "grad1": grad1, "grad_norms": grad_norms,
            "change_norms": change}
