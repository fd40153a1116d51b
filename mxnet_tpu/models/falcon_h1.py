"""Falcon-H1 (TII, 2025-05; HF ``model_type: falcon_h1``): every layer a
PARALLEL block — one RMSNorm, then a Mamba-2 mixer and a rotary
grouped-query attention on the same normed input, summed into the
residual — followed by a SwiGLU feed-forward, with muP multipliers on
the embedding, the projections and the logits.

One block function, ``serve_block``, computes the layer.  It takes its
two stateful parts as arguments: ``attend(q, k, v)`` (where the keys and
values are kept and how a row reads them) and a slot-state backend
(``conv`` / ``scan``: where a sequence's convolution window and SSM state
live between calls).  ``serving.ServingEngine`` hands it the paged K/V
pools and the per-slot state pools; ``forward`` hands it a full causal
softmax and zero states, and is the dense full-sequence pass the tests
hold against ``chipbench/reference/falcon_h1.py``.

**The scan inside a step** (``slot_scan``).  A call carries ``T`` flat
rows, each with a slot; rows of one slot are consecutive and in order (a
decode row is a segment of one, a prefill chunk a segment of up to
``chunk`` rows, dead rows point at the scratch slot, the last).  With
``a_t = dt_t A`` the log-decay, ``cum`` its running sum within the
slot's rows of this call and ``same(t, s)`` = same slot and ``s <= t``::

    y_t = sum_s same(t,s) exp(cum_t - cum_s) (C_t . B_s) dt_s x_s
          + exp(cum_t) (S_prev[slot] C_t) + D x_t
    S_new[slot] = exp(cum_last) S_prev[slot]
                  + sum_s exp(cum_last - cum_s) dt_s x_s (x) B_s

the chunked ("dual") form with a segment mask, ``T x T`` per head.  The
first line's sum is one formula over all rows.  The two terms that touch
the state are computed slot-major, so that no per-row copy of a
``(heads, head, state)`` state is ever made: every slot with ONE row in
the call in one vectorised pass over the pool (read once, written once,
in place under donation), every slot with more rows in a loop that runs
as many times as there are such slots (a chunk's ``S_prev C`` and its
update are small matmuls against one slot's state).  A slot whose
``fresh`` flag is set starts from a zero state and a zero window: the
pool's old content is masked as it is read, so a new request costs no
dispatch of its own.

Precision: the residual stream, the projections' operands and the
convolution window are ``cfg.dtype``; the matmuls accumulate in float32;
everything between ``in_proj`` and ``out_proj`` (the multipliers, the
convolution, ``dt``, the decays, the scan, the gated norm), the rotary
angles, the softmax and the norms are float32; the SSM state is kept
in float32 (what the Mamba kernels keep: a request's state is rounded
once a token on the way to its last).
"""
from __future__ import annotations

import dataclasses
import math
import sys
from typing import Tuple

__all__ = ["FalconH1Config", "init_params", "forward", "serve_embed",
           "serve_block", "serve_logits", "slot_state_shapes",
           "layer_cache", "SlotState", "slot_scan", "slot_conv"]


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The published ``config.json`` keys under the engine's names.
    ``max_len`` is None: positions are rotary, so the context is bounded
    by whoever holds the cache (the engine's ``max_seq``)."""
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    d_ssm: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    conv_kernel: int = 4
    rms_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    dtype: str = "bfloat16"
    causal: bool = True
    max_len: None = None

    def __post_init__(self):
        if self.d_ssm != self.ssm_heads * self.ssm_head_dim:
            raise ValueError("FalconH1Config: d_ssm %d != %d heads of %d"
                             % (self.d_ssm, self.ssm_heads,
                                self.ssm_head_dim))
        if self.n_heads % self.n_kv_heads \
                or self.ssm_heads % self.ssm_groups:
            raise ValueError("FalconH1Config: query heads must divide "
                             "over key/value heads, SSM heads over "
                             "groups")

    @classmethod
    def from_hf(cls, c, **kw):
        """From the keys of a published ``falcon_h1`` ``config.json``."""
        return cls(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], d_ssm=c["mamba_d_ssm"],
            ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
            ssm_groups=c["mamba_n_groups"], ssm_state=c["mamba_d_state"],
            conv_kernel=c["mamba_d_conv"], rms_eps=c["rms_norm_eps"],
            rope_theta=float(c["rope_theta"]),
            embedding_multiplier=c["embedding_multiplier"],
            lm_head_multiplier=c["lm_head_multiplier"],
            attention_in_multiplier=c["attention_in_multiplier"],
            attention_out_multiplier=c["attention_out_multiplier"],
            key_multiplier=c["key_multiplier"],
            ssm_in_multiplier=c["ssm_in_multiplier"],
            ssm_out_multiplier=c["ssm_out_multiplier"],
            ssm_multipliers=tuple(c["ssm_multipliers"]),
            mlp_multipliers=tuple(c["mlp_multipliers"]), **kw)

    @property
    def conv_dim(self):
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def serving(self):
        """The module whose ``serve_*`` functions the engine's step
        program is built from."""
        return sys.modules[__name__]


def param_shapes(cfg):
    """{path: shape}: matrices are (in, out); ``in_proj``'s columns are
    ``[z | x | B | C | dt]``; ``conv_w`` is (taps, channels), the last
    tap on the current input."""
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    GN = cfg.ssm_groups * cfg.ssm_state
    layer = {"in_norm": (D,),
             "in_proj": (D, 2 * cfg.d_ssm + 2 * GN + cfg.ssm_heads),
             "conv_w": (cfg.conv_kernel, cfg.conv_dim),
             "conv_b": (cfg.conv_dim,), "dt_bias": (cfg.ssm_heads,),
             "A_log": (cfg.ssm_heads,), "D": (cfg.ssm_heads,),
             "ssm_norm": (cfg.d_ssm,), "out_proj": (cfg.d_ssm, D),
             "wq": (D, cfg.n_heads * cfg.head_dim),
             "wk": (D, cfg.n_kv_heads * cfg.head_dim),
             "wv": (D, cfg.n_kv_heads * cfg.head_dim),
             "wo": (cfg.n_heads * cfg.head_dim, D), "ff_norm": (D,),
             "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    return {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V),
            "layers": [dict(layer) for _ in range(cfg.n_layers)]}


def init_params(key, cfg, dtype=None):
    """Seeded parameters: matrices N(0, 1/fan_in), norm gains 1, and
    Mamba-2's initialisation of the recurrence (``A ~ U(1, 16)`` as
    ``A_log``, ``dt ~ logU(1e-3, 1e-1)`` as the inverse-softplus
    ``dt_bias``, ``D = 1``; float32 whatever ``dtype``)."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype or cfg.dtype)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        if name == "A_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1., 16.))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "D":
            x = jnp.ones(shape, jnp.float32)
        elif name.endswith("norm"):
            x = jnp.ones(shape, dtype)
        elif len(shape) == 1:
            x = jnp.zeros(shape, dtype)
        else:
            x = (jax.random.normal(k, shape, jnp.float32)
                 / math.sqrt(shape[0])).astype(dtype)
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


def _rope(x, pos, theta):
    """Rotary over the whole head of (T, H, dh) at positions ``pos``:
    the two halves rotated against each other, angles in float32."""
    import jax.numpy as jnp
    dh = x.shape[-1]
    inv = jnp.exp(jnp.arange(0, dh, 2, dtype=jnp.float32)
                  * (-math.log(theta) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]          # (T, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _segments(row_slot):
    """(T, T) bool: row s is of row t's slot and not after it."""
    import jax.numpy as jnp
    t = jnp.arange(row_slot.shape[0])
    return (row_slot[:, None] == row_slot[None, :]) & (t[None] <= t[:, None])


def _slot_rows(row_slot, S1):
    """Per slot: how many rows it has in the call, its first and last."""
    import jax.numpy as jnp
    T = row_slot.shape[0]
    onehot = row_slot[:, None] == jnp.arange(S1)[None, :]        # (T, S1)
    cnt = jnp.sum(onehot, axis=0)
    first = jnp.argmax(onehot, axis=0)
    last = T - 1 - jnp.argmax(onehot[::-1], axis=0)
    return cnt, first, last


def slot_conv(xBC, w, b, row_slot, fresh, pool):
    """Causal depthwise convolution over the call's flat rows.

    xBC (T, C): the rows' inputs; w (K, C), the last tap on the current
    input; b (C,); pool (S + 1, K - 1, C): each slot's last K - 1 inputs,
    the newest last.  A row's earlier inputs are the rows before it in
    its segment and, before those, the slot's window (zeros where
    ``fresh``).  Returns the (T, C) float32 pre-activations and the
    pool with every slot's window moved past its rows."""
    import jax.numpy as jnp
    f32 = jnp.float32
    T, K = xBC.shape[0], w.shape[0]
    S1 = pool.shape[0]
    w, b = w.astype(f32), b.astype(f32)
    old = jnp.where(fresh[:, None, None], 0, pool)               # (S1,K-1,C)
    rank = jnp.sum(_segments(row_slot), axis=1) - 1              # (T,)
    win = old[row_slot]                                          # (T,K-1,C)
    out = b + w[K - 1] * xBC.astype(f32)
    for d in range(1, K):
        # the input d rows back: a row of this call, or window entry
        # K - 1 - d + rank
        j = jnp.clip(K - 1 - d + rank, 0, K - 2)
        back = jnp.take_along_axis(win, j[:, None, None], axis=1)[:, 0]
        prev = jnp.where((rank >= d)[:, None], jnp.roll(xBC, d, axis=0),
                         back)
        out = out + w[K - 1 - d] * prev.astype(f32)
    cnt, first, _ = _slot_rows(row_slot, S1)
    new = []
    for j in range(K - 1):
        # entry j of the new window is entry cnt + j of [window ++ rows]
        src = cnt + j
        kept = jnp.take_along_axis(
            old, jnp.clip(src, 0, K - 2)[:, None, None], axis=1)[:, 0]
        row = xBC[jnp.clip(first + src - (K - 1), 0, T - 1)]
        new.append(jnp.where((src < K - 1)[:, None], kept,
                             row.astype(pool.dtype)))
    return out, jnp.stack(new, axis=1)


def slot_scan(x, Bm, Cm, dt, A, Dp, row_slot, fresh, pool, chunk):
    """The SSM recurrence over the call's flat rows (module docstring).

    x (T, H, P), Bm / Cm (T, G, N), dt (T, H) after its softplus, A (H,)
    negative, Dp (H,): float32.  row_slot (T,) in [0, S], S the scratch
    slot; fresh (S + 1,) bool; pool (S + 1, H, P, N) the slots' states;
    ``chunk`` the most rows one slot may have in a call (static).
    Returns y (T, H, P) float32 and the updated pool."""
    import jax
    import jax.numpy as jnp
    f32, HI = jnp.float32, jax.lax.Precision.HIGHEST
    T, H, P = x.shape
    G = Bm.shape[1]
    S1 = pool.shape[0]
    rep = H // G
    a = dt * A                                                   # (T, H)
    same = _segments(row_slot)
    cum = jnp.einsum("ts,sh->th", same.astype(f32), a, precision=HI)
    # ---- within the call: one formula over all rows
    CB = jnp.einsum("tgn,sgn->tsg", Cm, Bm, precision=HI)        # (T,T,G)
    decay = jnp.exp(jnp.where(same[..., None],
                              cum[:, None, :] - cum[None, :, :], -jnp.inf))
    W = decay * jnp.repeat(CB, rep, axis=-1) * dt[None]          # (T,T,H)
    y = jnp.einsum("tsh,shp->thp", W, x, precision=HI) \
        + Dp[None, :, None] * x
    # ---- against the state, slot-major
    cnt, first, last = _slot_rows(row_slot, S1)
    real = jnp.arange(S1) < S1 - 1                 # not the scratch slot
    single = (cnt == 1) & real
    multi = (cnt >= 2) & real
    Bh = jnp.repeat(Bm, rep, axis=1)                             # (T,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1)
    with jax.named_scope("ssm_state_write"):
        # slots with one row: the whole pool read once and written once
        prev = jnp.where(fresh[:, None, None, None], 0,
                         pool).astype(f32)                       # (S1,H,P,N)
        z1 = jnp.sum(prev * Ch[first][:, :, None, :], axis=-1)   # (S1,H,P)
        dA = jnp.exp(jnp.where(single[:, None], a[first], 0.0))
        dtx = jnp.where(single[:, None, None],
                        dt[first][:, :, None] * x[first], 0.0)
        new = dA[:, :, None, None] * prev \
            + dtx[..., None] * Bh[first][:, :, None, :]
        new = new.astype(pool.dtype)
    z = jnp.where(single[row_slot][:, None, None], z1[row_slot], 0.0)

    # slots with several rows (prefill chunks): one at a time, a window
    # of ``chunk`` rows that holds the slot's segment
    R = min(chunk, T)
    ids = jnp.nonzero(multi, size=max(1, T // 2), fill_value=S1 - 1)[0]

    def one(i, carry):
        new, z = carry
        s = ids[i]
        r0 = jnp.clip(first[s], 0, T - R)
        cut = lambda v: jax.lax.dynamic_slice_in_dim(v, r0, R)   # noqa: E731
        mine = cut(row_slot) == s                                # (R,)
        Sp = new[s].astype(f32)                                  # (H,P,N)
        zs = jnp.einsum("hpn,rhn->rhp", Sp, cut(Ch), precision=HI)
        z = jax.lax.dynamic_update_slice_in_dim(
            z, jnp.where(mine[:, None, None], zs, cut(z)), r0, 0)
        end = cum[last[s]]                                       # (H,)
        wgt = jnp.where(mine[:, None],
                        jnp.exp(jnp.where(mine[:, None],
                                          end[None] - cut(cum), 0.0))
                        * cut(dt), 0.0)                          # (R,H)
        U = jnp.einsum("rhp,rhn->hpn", wgt[:, :, None] * cut(x), cut(Bh),
                       precision=HI)
        Sn = jnp.exp(end)[:, None, None] * Sp + U
        return new.at[s].set(Sn.astype(new.dtype)), z

    with jax.named_scope("ssm_state_write"):
        new, z = jax.lax.fori_loop(0, jnp.sum(multi), one, (new, z))
    return y + jnp.exp(cum)[:, :, None] * z, new


def slot_state_shapes(cfg):
    """What one slot keeps per layer beside its K/V pages:
    {name: (shape, dtype)}."""
    return {"conv": ((cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype),
            "ssm": ((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    "float32")}


def layer_cache(cfg):
    """What each layer keeps between calls, ``(pages, slot state)`` a
    layer: K/V pages and both states in every one."""
    return [(True, slot_state_shapes(cfg))] * cfg.n_layers


class SlotState:
    """The slot-state backend of one layer in one call: the layer's
    state pools, which slot each row belongs to and which slots start
    from zero.  ``conv`` and ``scan`` compute over the rows and replace
    ``pools`` by the updated ones."""

    def __init__(self, pools, row_slot, fresh, chunk):
        self.pools = dict(pools)
        self.row_slot, self.fresh, self.chunk = row_slot, fresh, chunk

    def conv(self, xBC, w, b):
        out, self.pools["conv"] = slot_conv(
            xBC, w, b, self.row_slot, self.fresh, self.pools["conv"])
        return out

    def scan(self, x, Bm, Cm, dt, A, Dp):
        y, self.pools["ssm"] = slot_scan(
            x, Bm, Cm, dt, A, Dp, self.row_slot, self.fresh,
            self.pools["ssm"], self.chunk)
        return y


# ------------------------------------------------------------- block ---

def _mixer(layer, cfg, u, state):
    """The Mamba-2 mixer on (T, D) normed rows."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    cdt = jnp.dtype(cfg.dtype)
    T = u.shape[0]
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, \
        cfg.ssm_state
    d_ssm, GN = cfg.d_ssm, cfg.ssm_groups * cfg.ssm_state
    with jax.named_scope("ssm_in"):
        p = _mm(cfg.ssm_in_multiplier * u, layer["in_proj"], cdt)
        mup = jnp.concatenate([
            jnp.full((w,), m, f32) for w, m in
            zip((d_ssm, d_ssm, GN, GN, H), cfg.ssm_multipliers)])
        p = p * mup
        z = p[:, :d_ssm]
        xBC = p[:, d_ssm:d_ssm + cfg.conv_dim].astype(cdt)
        dt = jax.nn.softplus(p[:, d_ssm + cfg.conv_dim:]
                             + layer["dt_bias"].astype(f32))     # (T, H)
    with jax.named_scope("ssm_conv"):
        xBC = jax.nn.silu(state.conv(xBC, layer["conv_w"],
                                     layer["conv_b"]))
    with jax.named_scope("ssm_scan"):
        y = state.scan(xBC[:, :d_ssm].reshape(T, H, P),
                       xBC[:, d_ssm:d_ssm + GN].reshape(T, G, N),
                       xBC[:, d_ssm + GN:].reshape(T, G, N), dt,
                       -jnp.exp(layer["A_log"].astype(f32)),
                       layer["D"].astype(f32))
    with jax.named_scope("ssm_out"):
        y = y.reshape(T, d_ssm) * jax.nn.silu(z)
        # the gated norm: multiplied by silu(z) first, each group of
        # d_ssm / G channels normalised by itself
        yg = y.reshape(T, G, d_ssm // G)
        yg = yg * jnp.reciprocal(jnp.sqrt(
            jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
            + cfg.rms_eps))
        y = yg.reshape(T, d_ssm) * layer["ssm_norm"].astype(f32)
        return _mm(y, layer["out_proj"], cdt)


def serve_block(layer, cfg, x, row_pos, attend, state, counts=None):
    """One parallel block on (T, D) rows at positions ``row_pos``.
    ``attend(q (T, Hq, dh), k, v (T, Hkv, dh))`` returns each row's
    attention over its own sequence, (T, Hq, dh) float32; ``state`` is
    the slot-state backend (``SlotState``).  ``counts`` is the step
    counters of families that count; this one counts nothing."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    T = x.shape[0]
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("norm"):
        u = _rms(x, layer["in_norm"], cfg.rms_eps)
    mixed = _mixer(layer, cfg, u, state)
    with jax.named_scope("qkv"):
        a = cfg.attention_in_multiplier * u
        q = _mm(a, layer["wq"], cdt).reshape(T, Hq, dh)
        k = (cfg.key_multiplier * _mm(a, layer["wk"], cdt)
             ).reshape(T, Hkv, dh)
        v = _mm(a, layer["wv"], cdt).reshape(T, Hkv, dh)
    with jax.named_scope("rope"):
        q = _rope(q, row_pos, cfg.rope_theta).astype(cdt)
        k = _rope(k, row_pos, cfg.rope_theta).astype(cdt)
    attn = attend(q, k, v.astype(cdt))
    with jax.named_scope("attn_out"):
        attn = _mm(attn.reshape(T, Hq * dh), layer["wo"], cdt)
        x = (x.astype(jnp.float32) + cfg.ssm_out_multiplier * mixed
             + cfg.attention_out_multiplier * attn).astype(cdt)
    with jax.named_scope("ffn"):
        gate_mult, down_mult = cfg.mlp_multipliers
        m = _rms(x, layer["ff_norm"], cfg.rms_eps)
        h = _mm(m, layer["w_up"], cdt) \
            * jax.nn.silu(gate_mult * _mm(m, layer["w_gate"], cdt))
        return (x.astype(jnp.float32)
                + down_mult * _mm(h, layer["w_down"], cdt)).astype(cdt)


def serve_embed(params, cfg, tokens, row_pos):
    """(T,) ids -> (T, D) rows; positions enter in the blocks (rotary)."""
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    return (params["embed"][tokens].astype(jnp.float32)
            * cfg.embedding_multiplier).astype(cdt)


def serve_logits(params, cfg, x, slot_rows):
    """Float32 logits of the sampling rows alone: (S, n) row indices ->
    (S, n, V).  The head's weights are most of what a step reads; its
    matmul runs over the rows that sample."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("norm"):
        h = _rms(x[slot_rows.reshape(-1)], params["final_norm"],
                 cfg.rms_eps)
    logits = cfg.lm_head_multiplier * _mm(h, params["lm_head"], cdt)
    return logits.reshape(slot_rows.shape + (cfg.vocab_size,))


def forward(params, cfg, tokens):
    """Dense full-sequence pass: (B, T) ids -> (B, T, V) float32 logits,
    through the same block as the engine's step.  Each sequence is one
    slot whose rows are all in this call: a full causal softmax stands
    in for the pages and zero states for the pools."""
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

    x = serve_embed(params, cfg, tokens.reshape(-1), row_pos)
    for layer in params["layers"]:
        pools = {name: jnp.zeros((B + 1,) + shape, dtype) for
                 name, (shape, dtype) in slot_state_shapes(cfg).items()}
        x = serve_block(layer, cfg, x, row_pos, attend,
                        SlotState(pools, row_slot, fresh, T))
    rows = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    return serve_logits(params, cfg, x, rows)
