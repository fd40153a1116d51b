"""EXAONE-MoE (LG AI Research, 2026-01; HF ``model_type: exaone_moe``;
K-EXAONE-236B-A23B is one): grouped-query attention layers of TWO kinds
in one model, by ``layer_types`` — SLIDING-WINDOW attention over the last
``sliding_window`` positions, rotary, or FULL attention with no position
encoding at all (NoPE) — each followed by a SwiGLU feed-forward in the
layers ``mlp_layer_types`` calls ``"dense"`` and by an expert layer in
the others: sigmoid scores, a selection bias, top-k, the chosen scores
normalised and scaled, one shared expert.  The block is EXAONE 4.0's:
NO norm before either branch, an RMSNorm AFTER each (on the branch's
output, before the residual sum), an RMSNorm on every query and key
head::

    q, k, v = x Wq, x Wk, x Wv;  q, k = RMSNorm_head(q), RMSNorm_head(k)
    q, k = rope(q), rope(k)                     sliding layers only
    h = x + RMSNorm(attn(q, k, v) Wo)
    y = h + RMSNorm(FFN(h))

One block function, ``serve_block``, computes the layer, whichever its
kind.  It takes what a layer keeps between calls as arguments and uses
what its kind needs: ``attend(q, k, v)`` (a full layer: the paged K/V
and how a row reads them), a slot-state backend (a sliding layer: where
a sequence's last positions live) and the call's counters.
``layer_cache`` says which layer keeps what — pages and no ring, or a
ring and no pages — and ``serving.ServingEngine`` builds its pools from
it; ``forward`` hands the block a full causal softmax and zero rings,
and is the dense full-sequence pass the tests hold against
``chipbench/reference/exaone_moe.py``.

**The ring** (``slot_window``).  A sliding layer's query at position
``p`` reads the keys at positions ``(p - W, p]``, ``W`` the window,
itself among them.  Between calls a slot keeps its last ``W`` positions'
``[k | v]`` rows, the flat layout of a grouped-query page
(``serving/paged_kv.py write_rows``), in a ring indexed by ``position
mod W``: ``(W, Hkv * 2 * dh)`` a slot a layer.  A token writes ONE row
of it (a window shifted along, as ``falcon_h1.slot_conv`` keeps its
few taps, would rewrite the whole ``W`` rows every step).  Entry ``j``
then holds the newest position ``<= p0 - 1`` that is ``j`` mod ``W``,
``p0`` the slot's first position in the call: the ring needs no reset,
and a position below 0 — the ring's old content when a slot starts a
sequence — is ABSENT from the softmax, masked by position, not a zero
key (a zero key would take a share of every softmax).  A call's rows of
one slot are consecutive and in order (a decode row, or a prefill chunk
of up to ``chunk`` rows): every slot with ONE row in the call in one
vectorised pass over the pool (its row written, then its window read
from the ring), every slot with more in a loop that runs as many times
as there are such slots, each reading its ring ONCE for all its rows
(the ring's entries before ``p0`` and the call's own rows, causal,
inside the window) and then writing its last ``W`` rows.

**The expert layer** computes one rank's share of an expert-parallel
deployment (``held_first``, ``held_count``; ``parallel/moe.py``): it
routes over all ``n_routed_experts`` and sums the held experts' terms;
the shared expert is computed whole.  It counts what
``deepseek_v3.StepCounts`` counts and, per sliding layer, the live rows
through it and the ring entries and own rows the call's attention had
to read (``STEP_COUNTERS``, summed over the layers): the engine reads
them back with the step's tokens.

Precision: the residual stream, the matmuls' operands and the ring are
``cfg.dtype``, accumulated in float32; norms, rotary angles, the router
(its matmul at the highest precision, its weights and bias float32) and
the softmax are float32.
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
from .falcon_h1 import _mm, _rms, _rope, _slot_rows

__all__ = ["ExaoneMoeConfig", "param_shapes", "init_params", "forward",
           "serve_embed", "serve_block", "serve_logits", "layer_cache",
           "SlotState", "slot_window", "latent_window", "StepCounts",
           "STEP_COUNTERS",
           "counter_stats"]


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """The published ``config.json`` keys under the engine's names.
    ``sliding_windows`` gives each layer's window, 0 for a full layer;
    ``mlp_layer_types`` each layer's feed-forward.  ``n_routed_experts``
    is the router's width (all the experts of the deployment);
    ``held_first`` / ``held_count`` say which of them this program holds
    (all by default).  ``max_len`` is None: the context is bounded by
    whoever holds the cache."""
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    moe_d_ff: int
    n_routed_experts: int
    n_shared_experts: int
    top_k: int
    sliding_windows: Tuple[int, ...]
    mlp_layer_types: Tuple[str, ...]
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    held_first: int = 0
    held_count: int = -1
    rms_eps: float = 1e-5
    rope_theta: float = 1e6
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
                "ExaoneMoeConfig: experts %d..%d are not among the %d "
                "routed" % (self.held_first,
                            self.held_first + self.held_count - 1,
                            self.n_routed_experts))
        if len(self.sliding_windows) != self.n_layers \
                or len(self.mlp_layer_types) != self.n_layers \
                or set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(
                "ExaoneMoeConfig: sliding_windows %r and mlp_layer_types %r "
                "do not name %d layers" % (self.sliding_windows,
                                           self.mlp_layer_types,
                                           self.n_layers))
        if self.n_heads % self.n_kv_heads:
            raise ValueError("ExaoneMoeConfig: %d query heads do not divide "
                             "over %d key/value heads"
                             % (self.n_heads, self.n_kv_heads))

    @classmethod
    def from_hf(cls, c, **kw):
        """From the keys of an ``exaone_moe`` ``config.json``.  A file
        that states one rank's share gives the experts held as
        ``num_experts`` and the published count, the router's width, as
        ``router_width`` beside ``ep_rank``."""
        held = c["num_experts"]
        return cls(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"],
            moe_d_ff=c["moe_intermediate_size"],
            n_routed_experts=c.get("router_width", held),
            n_shared_experts=c["num_shared_experts"],
            top_k=c["num_experts_per_tok"],
            sliding_windows=tuple(c["sliding_windows"]),
            mlp_layer_types=tuple(c["mlp_layer_types"]),
            routed_scaling_factor=c["routed_scaling_factor"],
            norm_topk_prob=c["norm_topk_prob"],
            held_first=c.get("ep_rank", 0) * held, held_count=held,
            rms_eps=c["rms_norm_eps"],
            rope_theta=float(c["rope_parameters"]["rope_theta"]), **kw)

    @property
    def serving(self):
        """The module whose ``serve_*`` functions the engine's step
        program is built from."""
        return sys.modules[__name__]


def layer_cache(cfg):
    """What each layer keeps between calls, ``(pages, slot state)`` a
    layer: a full layer K/V pages and no ring, a sliding layer its ring
    of the last ``W`` positions' ``[k | v]`` rows and no pages."""
    row = cfg.n_kv_heads * 2 * cfg.head_dim
    return [(False, {"win": ((w, row), cfg.dtype)}) if w else (True, {})
            for w in cfg.sliding_windows]


def param_shapes(cfg):
    """{path: shape}: matrices are (in, out); an expert layer holds the
    experts it was given, (held, in, out), the router over all of them
    and the bias the choice adds (float32 both), and the shared expert.
    An untied head."""
    D, V = cfg.d_model, cfg.vocab_size
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    F, E = cfg.moe_d_ff, cfg.held_count
    Fs = cfg.moe_d_ff * cfg.n_shared_experts
    attn = {"wq": (D, H * dh), "wk": (D, Hkv * dh), "wv": (D, Hkv * dh),
            "q_norm": (dh,), "k_norm": (dh,), "wo": (H * dh, D),
            "post_attn_norm": (D,), "post_ffn_norm": (D,)}
    dense = {"w_gate": (D, cfg.d_ff), "w_up": (D, cfg.d_ff),
             "w_down": (cfg.d_ff, D)}
    moe = {"router": (D, cfg.n_routed_experts),
           "router_bias": (cfg.n_routed_experts,),
           "ew_gate": (E, D, F), "ew_up": (E, D, F), "ew_down": (E, F, D),
           "sw_gate": (D, Fs), "sw_up": (D, Fs), "sw_down": (Fs, D)}
    return {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V),
            "layers": [dict(attn, **(dense if kind == "dense" else moe))
                       for kind in cfg.mlp_layer_types]}


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

STEP_COUNTERS = _STEP_COUNTERS + ("win_rows", "win_positions")


class StepCounts(_StepCounts):
    """``deepseek_v3.StepCounts`` with this family's own counts after
    them: per sliding layer the live rows through it (``win_rows``) and
    the ring entries and own rows its attention had to read
    (``win_positions``)."""
    names = STEP_COUNTERS

    def add(self, *counts):
        """The expert layer's counts, the first of ``names``."""
        n = len(counts)
        self.counts = [a + b for a, b in zip(self.counts, counts)] \
            + self.counts[n:]

    def add_window(self, rows, positions):
        self.counts[-2] = self.counts[-2] + rows
        self.counts[-1] = self.counts[-1] + positions


def counter_stats(cfg, params, counts):
    """What one step's ``STEP_COUNTERS`` add to the engine's ``stats``:
    ``deepseek_v3.counter_stats`` of that family's and this one's."""
    return dict(_counter_stats(cfg, params, counts[:-2]),
                win_rows=int(counts[-2]), win_positions=int(counts[-1]))


def slot_window(q, k, v, row_pos, row_slot, pool, chunk):
    """Sliding-window attention over the call's flat rows and the slots'
    rings (module docstring).

    q (T, Hq, dh), k / v (T, Hkv, dh): the rows' queries, keys and
    values; row_pos / row_slot (T,) int32, dead rows in the scratch slot
    S; pool (S + 1, W, Hkv * 2 * dh): each slot's ring, entry ``j`` the
    ``[k | v]`` row of its newest position that is ``j`` mod ``W``;
    ``chunk`` the most rows one slot may have in a call (static).
    Returns (out (T, Hq, dh) float32, the pool with every slot's newest
    rows written, the live rows, the ring entries and own rows the
    attention had to read: ``min(p0, W - 1)`` and the rows a slot)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    T, Hq, dh = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    S1, W, _ = pool.shape
    cdt = pool.dtype
    kv = jnp.concatenate([k, v], axis=-1).astype(cdt).reshape(T, -1)
    cnt, first, last = _slot_rows(row_slot, S1)
    real = jnp.arange(S1) < S1 - 1                 # not the scratch slot
    single = (cnt == 1) & real
    multi = (cnt >= 2) & real
    p0 = row_pos[first]                                          # (S1,)
    scale = 1.0 / math.sqrt(dh)

    def softmax_av(s, see, vals, eq):
        s = jnp.where(see, s * scale, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(eq, p.astype(cdt), vals,
                          preferred_element_type=f32)

    # slots with one row: its row written at p mod W, then its window
    # (p - W, p] read from the ring, one pass over the pool
    ent = jnp.where(single, p0 % W, W)                   # W: dropped
    pool = pool.at[jnp.arange(S1), ent].set(kv[first], mode="drop")
    ring = pool.reshape(S1, W, Hkv, 2 * dh)
    held = p0[:, None] - (p0[:, None] - jnp.arange(W)[None]) % W
    s1 = jnp.einsum("sgrd,swgd->sgrw",
                    q[first].astype(cdt).reshape(S1, Hkv, rep, dh),
                    ring[..., :dh], preferred_element_type=f32)
    o1 = softmax_av(s1, (held >= 0)[:, None, None, :], ring[..., dh:],
                    "sgrw,swgd->sgrd").reshape(S1, Hq, dh)
    out = jnp.where(single[row_slot][:, None, None], o1[row_slot], 0.0)

    # slots with several rows (prefill chunks): one at a time, a window
    # of ``chunk`` rows that holds the slot's rows, its ring read once
    R = min(chunk, T)
    ids = jnp.nonzero(multi, size=max(1, T // 2), fill_value=S1 - 1)[0]

    def one(i, carry):
        pool, out = carry
        s = ids[i]
        r0 = jnp.clip(first[s], 0, T - R)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, r0, R)  # noqa: E731
        mine = cut(row_slot) == s                                # (R,)
        pos = cut(row_pos)
        old = pool[s]                                            # (W, L)
        before = p0[s] - 1 - (p0[s] - 1 - jnp.arange(W)) % W    # (W,)
        keys = jnp.concatenate([old, cut(kv)]).reshape(W + R, Hkv, 2 * dh)
        kpos = jnp.concatenate([before, pos])
        kok = jnp.concatenate([before >= 0, mine])
        see = kok[None] & (kpos[None] <= pos[:, None]) \
            & (kpos[None] > pos[:, None] - W)                    # (R, W+R)
        sc = jnp.einsum("rgqd,kgd->rgqk",
                        cut(q).astype(cdt).reshape(R, Hkv, rep, dh),
                        keys[..., :dh], preferred_element_type=f32)
        o = softmax_av(sc, see[:, None, None, :], keys[..., dh:],
                       "rgqk,kgd->rgqd").reshape(R, Hq, dh)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(mine[:, None, None], o, cut(out)), r0, 0)
        # the slot's last W rows at their place in the ring
        keep = mine & (pos > row_pos[last[s]] - W)
        new = old.at[jnp.where(keep, pos % W, W)].set(cut(kv), mode="drop")
        return pool.at[s].set(new), out

    pool, out = jax.lax.fori_loop(0, jnp.sum(multi), one, (pool, out))
    live = cnt * real
    read = jnp.where(live > 0, jnp.minimum(p0, W - 1) + live, 0)
    return out, pool, jnp.sum(live), jnp.sum(read)


def latent_window(q, row, row_pos, row_slot, pool, chunk, *, rank, scale):
    """``slot_window`` over a ring of LATENT rows: one shared row a
    token, every head reading it (``models/motif3.py``).

    q (T, H, dk): each head's absorbed query, scored against the first
    ``dk`` lanes of a row; row (T, L): each row's ``[c_kv | k_pe]`` as
    the ring holds it (padded); the values are a row's first ``rank``
    lanes; ``scale`` the softmax's.  pool (S + 1, W, L) and the rows'
    slots and positions as ``slot_window``'s.  ``chunk`` (static) bounds
    the rows of ALL slots with several rows in the call together, which
    lie within ``chunk`` consecutive rows (the engine's prefill rows:
    one budget, after the decode rows).  Slots with one row take
    ``slot_window``'s pass over the pool; the window of ``chunk`` rows
    is read in ONE pass — each row against its slot's ring as it was
    before the call and the window's own rows of its slot, causal,
    inside ``W`` — and its slots' last ``W`` rows are written into their
    rings in one scatter after it; no loop over slots, and no pass at
    all in a call that has no such slot.  Returns (out (T, H, rank)
    float32, the pool, the live rows, the ring entries and own rows the
    attention had to read)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    T, H, dk = q.shape
    S1, W, L = pool.shape
    cdt = pool.dtype
    kv = row.astype(cdt)
    q = q.astype(cdt)
    cnt, first, last = _slot_rows(row_slot, S1)
    real = jnp.arange(S1) < S1 - 1                 # not the scratch slot
    single = (cnt == 1) & real
    multi = (cnt >= 2) & real
    p0 = row_pos[first]                                          # (S1,)

    def softmax(s, see):
        return jax.nn.softmax(jnp.where(see, s * scale, -1e30),
                              axis=-1).astype(cdt)

    # slots with one row: its row written at p mod W, then its window
    # (p - W, p] read from the ring, one pass over the pool
    ent = jnp.where(single, p0 % W, W)                   # W: dropped
    pool = pool.at[jnp.arange(S1), ent].set(kv[first], mode="drop")
    held = p0[:, None] - (p0[:, None] - jnp.arange(W)[None]) % W
    s1 = jnp.einsum("shd,swd->shw", q[first], pool[..., :dk],
                    preferred_element_type=f32)
    o1 = jnp.einsum("shw,swr->shr", softmax(s1, (held >= 0)[:, None]),
                    pool[..., :rank], preferred_element_type=f32)
    out = jnp.where(single[row_slot][:, None, None], o1[row_slot], 0.0)

    R = min(chunk, T)

    def several(args):
        """The window of R rows that holds every slot with several rows:
        their outputs placed, their rings written."""
        out, pool = args
        r0 = jnp.clip(jnp.min(jnp.where(multi, first, T)), 0, T - R)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, r0, R)  # noqa: E731
        slot, pos, rows = cut(row_slot), cut(row_pos), cut(kv)
        mine = multi[slot]                                       # (R,)
        ring = pool[slot]                                        # (R, W, L)
        before = p0[slot][:, None] - 1 \
            - (p0[slot][:, None] - 1 - jnp.arange(W)[None]) % W  # (R, W)
        see_ring = (before >= 0) & (before > pos[:, None] - W)
        see_own = (slot[None] == slot[:, None]) & mine[None] \
            & (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None] - W)
        qw = cut(q)
        sc = jnp.concatenate([
            jnp.einsum("rhd,rwd->rhw", qw, ring[..., :dk],
                       preferred_element_type=f32),
            jnp.einsum("rhd,kd->rhk", qw, rows[:, :dk],
                       preferred_element_type=f32)], axis=-1)
        p = softmax(sc, jnp.concatenate([see_ring, see_own], -1)[:, None])
        o = jnp.einsum("rhw,rwv->rhv", p[..., :W], ring[..., :rank],
                       preferred_element_type=f32) \
            + jnp.einsum("rhk,kv->rhv", p[..., W:], rows[:, :rank],
                         preferred_element_type=f32)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(mine[:, None, None], o, cut(out)), r0, 0)
        # each such slot's last W rows at their place in its ring
        keep = mine & (pos > row_pos[last[slot]] - W)
        pool = pool.at[slot, jnp.where(keep, pos % W, W)].set(
            rows, mode="drop")
        return out, pool

    out, pool = jax.lax.cond(jnp.any(multi), several, lambda a: a,
                             (out, pool))
    live = cnt * real
    read = jnp.where(live > 0, jnp.minimum(p0, W - 1) + live, 0)
    return out, pool, jnp.sum(live), jnp.sum(read)


class SlotState:
    """The slot-state backend of one layer in one call: the layer's
    ring pool and which slot each row belongs to.  The engine's
    ``fresh`` mask is not needed: a ring is masked by position, and a
    slot's first position makes every older entry absent.  ``window``
    computes over the rows and replaces ``pools`` by the updated ones."""

    def __init__(self, pools, row_slot, fresh, chunk):
        self.pools = dict(pools)
        self.row_slot, self.chunk = row_slot, chunk

    def window(self, q, k, v, row_pos, counts=None):
        out, self.pools["win"], rows, read = slot_window(
            q, k, v, row_pos, self.row_slot, self.pools["win"], self.chunk)
        if counts is not None:
            counts.add_window(rows, read)
        return out

    def latent_window(self, q, row, row_pos, rank, scale, counts=None):
        """The window over a ring of latent rows (``latent_window``):
        (T, H, rank) float32."""
        out, self.pools["win"], rows, read = latent_window(
            q, row, row_pos, self.row_slot, self.pools["win"], self.chunk,
            rank=rank, scale=scale)
        if counts is not None:
            counts.add_window(rows, read)
        return out


def _attention(layer, cfg, x, row_pos, attend, state, counts):
    """Grouped-query attention on (T, D) rows as they come (no norm
    before it): every query and key head normalised over its own lanes,
    then rotated where the layer slides; its window from the slot's ring
    or its whole sequence from the pages."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    T = x.shape[0]
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("qkv"):
        q = _mm(x, layer["wq"], cdt).reshape(T, Hq, dh)
        k = _mm(x, layer["wk"], cdt).reshape(T, Hkv, dh)
        v = _mm(x, layer["wv"], cdt).reshape(T, Hkv, dh).astype(cdt)
    with jax.named_scope("qk_norm"):
        q = _rms(q, layer["q_norm"], cfg.rms_eps)
        k = _rms(k, layer["k_norm"], cfg.rms_eps)
    if state is not None:
        with jax.named_scope("rope"):
            q = _rope(q, row_pos, cfg.rope_theta)
            k = _rope(k, row_pos, cfg.rope_theta)
        with jax.named_scope("win_attn"):
            o = state.window(q.astype(cdt), k.astype(cdt), v, row_pos,
                             counts)
    else:
        o = attend(q.astype(cdt), k.astype(cdt), v)
    with jax.named_scope("attn_out"):
        return _mm(o.reshape(T, Hq * dh), layer["wo"], cdt)


def _experts(layer, cfg, m, counts):
    """The expert layer on (T, D) rows: this rank's share of the routed
    sum plus the shared expert, float32."""
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
        y, pairs, hit, _, fetches = held_experts_ffn(
            m.astype(cdt), layer["ew_gate"].astype(cdt),
            layer["ew_up"].astype(cdt), layer["ew_down"].astype(cdt),
            idx, w, held_first=cfg.held_first, live=counts.live)
        counts.add(pairs, hit, fetches)
    with jax.named_scope("moe_shared"):
        return y + _swiglu(m, layer["sw_gate"], layer["sw_up"],
                           layer["sw_down"], cdt)


def serve_block(layer, cfg, x, row_pos, attend, state, counts):
    """One block on (T, D) rows at positions ``row_pos``:
    ``h = x + norm(Attention(x))``, ``y = h + norm(FFN(h))``.  A full
    layer calls ``attend(q (T, Hq, dh), k, v (T, Hkv, dh))``, which
    returns each row's attention over its own sequence, (T, Hq, dh)
    float32; a sliding layer uses ``state`` (``SlotState``: its ring);
    an expert layer and a sliding layer add to ``counts``
    (``StepCounts``)."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    o = _attention(layer, cfg, x, row_pos, attend, state, counts)
    with jax.named_scope("post_norm"):
        h = (x.astype(jnp.float32)
             + _rms(o, layer["post_attn_norm"], cfg.rms_eps)).astype(cdt)
    if "router" in layer:
        f = _experts(layer, cfg, h, counts)
    else:
        with jax.named_scope("ffn"):
            f = _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                        cdt)
    with jax.named_scope("post_norm"):
        return (h.astype(jnp.float32)
                + _rms(f, layer["post_ffn_norm"], cfg.rms_eps)).astype(cdt)


def serve_embed(params, cfg, tokens, row_pos):
    """(T,) ids -> (T, D) rows; positions enter in the sliding blocks."""
    import jax.numpy as jnp
    return params["embed"][tokens].astype(jnp.dtype(cfg.dtype))


def serve_logits(params, cfg, x, slot_rows):
    """Float32 logits of the sampling rows alone over this chip's slice
    of the vocabulary: (S, n) row indices -> (S, n, V)."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    with jax.named_scope("norm"):
        h = _rms(x[slot_rows.reshape(-1)], params["final_norm"],
                 cfg.rms_eps)
    return _mm(h, params["lm_head"], cdt).reshape(
        slot_rows.shape + (cfg.vocab_size,))


def forward(params, cfg, tokens):
    """Dense full-sequence pass: (B, T) ids -> (B, T, V) float32 logits,
    through the same block as the engine's step.  Each sequence is one
    slot whose rows are all in this call: a full causal softmax stands
    in for the pages and zero rings for the pools."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    row_slot = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
    row_pos = jnp.tile(jnp.arange(T, dtype=jnp.int32), B)
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
        state = SlotState(pools, row_slot, None, T) if keeps else None
        x = serve_block(layer, cfg, x, row_pos, attend, state, counts)
    rows = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    return serve_logits(params, cfg, x, rows)
