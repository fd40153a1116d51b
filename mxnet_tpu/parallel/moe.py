"""Mixture-of-Experts with expert parallelism over an ``ep`` mesh axis.

No reference counterpart — MXNet 1.x predates MoE (SURVEY.md §2.4 marks
expert parallel ABSENT); this is a TPU-build extension following the
GShard/Switch recipe: a learned router picks top-k experts per token,
tokens are packed into per-expert capacity buffers with dense one-hot
dispatch/combine einsums (XLA-friendly — no gather/scatter, the MXU does
the packing), and the expert dimension of both the parameter tensors and
the dispatched activations is sharded over ``ep`` so GSPMD inserts the
all-to-alls over ICI.

Gradients flow through the gate probabilities in the combine tensor
(standard straight-through routing); an auxiliary load-balancing loss
(Switch eq. 4) keeps the router from collapsing onto few experts.

**The serving form** (``route_group_limited`` + ``held_experts_ffn``,
PR 32) is the other recipe, DeepSeek-V3's: sigmoid scores, a bias that
takes part in the choice alone, group-limited top-k, and DROPLESS
dispatch with no capacity buffer.  The expert layer is told which
experts it holds (``held_first``, ``held_count``: one rank's share of an
expert-parallel deployment), routes over ALL of them and computes its
own experts' part of the result: the row-expert pairs are sorted by
expert, the held ones first, and three grouped matmuls run over a
static ``rows x top_k`` pairs (``_grouped_dot``: on a TPU the megablox
kernel, which visits only the row tiles that hold a group and reads
each hit expert's weights about once; ``jax.lax.ragged_dot`` elsewhere).  What
the absent experts would add is left out; on one chip the layer runs
without its exchange.
"""
from __future__ import annotations

import math
from typing import Optional

from ..base import MXNetError

__all__ = ["init_moe_ffn", "moe_ffn", "moe_param_specs",
           "moe_param_shardings", "route_group_limited",
           "held_experts_ffn"]


def init_moe_ffn(key, d_model, d_ff, n_experts, param_dtype="float32"):
    """Router + per-expert FFN params: leaves carry a leading E axis."""
    import jax
    import jax.numpy as jnp
    k = jax.random.split(key, 3)
    scale = 0.02
    return {
        "router": (jax.random.normal(k[0], (d_model, n_experts))
                   * scale).astype(param_dtype),
        "w1": (jax.random.normal(k[1], (n_experts, d_model, d_ff))
               * scale).astype(param_dtype),
        "b1": jnp.zeros((n_experts, d_ff), param_dtype),
        "w2": (jax.random.normal(k[2], (n_experts, d_ff, d_model))
               * scale).astype(param_dtype),
        "b2": jnp.zeros((n_experts, d_model), param_dtype),
    }


def moe_param_specs(tp="tp", ep="ep"):
    """Mesh-free ``PartitionSpec`` pytree matching init_moe_ffn:
    experts over ``ep``, FFN hidden dim over ``tp`` (pass ``None`` to
    drop an axis) — the spec twin ``moe_param_shardings`` binds."""
    from jax.sharding import PartitionSpec as P
    return {
        "router": P(),
        "w1": P(ep, None, tp),
        "b1": P(ep, tp),
        "w2": P(ep, tp, None),
        "b2": P(ep, None),
    }


def moe_param_shardings(mesh):
    """NamedSharding pytree matching init_moe_ffn: experts over ``ep``,
    FFN hidden dim over ``tp`` when present."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    specs = moe_param_specs(
        tp="tp" if "tp" in mesh.axis_names else None,
        ep="ep" if "ep" in mesh.axis_names else None)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def _top_k_gating(gates, k):
    """gates (G, S, E) softmax probs → per-slot expert index + gate value,
    shapes (G, S, k), slot 0 = highest gate."""
    import jax
    val, idx = jax.lax.top_k(gates, k)
    return idx, val


def moe_ffn(x, params, *, n_experts, top_k=2, capacity_factor=1.25,
            mesh=None, activation="gelu", dtype=None):
    """MoE FFN: x (G, S, D) → (y (G, S, D), aux_loss scalar).

    G = token groups (the batch dim), S = tokens per group.  Each group
    routes independently with expert capacity
    ``C = ceil(top_k * S * capacity_factor / E)``; overflow tokens fall
    through the residual (their y contribution is 0).
    """
    import jax
    import jax.numpy as jnp

    G, S, D = x.shape
    E = n_experts
    if top_k > E:
        raise MXNetError("moe_ffn: top_k=%d > n_experts=%d (lower "
                         "expert_top_k or add experts)" % (top_k, E))
    C = max(1, math.ceil(top_k * S * capacity_factor / E))
    cdt = dtype or x.dtype

    router_logits = (x.astype(jnp.float32)
                     @ params["router"].astype(jnp.float32))
    gates = jax.nn.softmax(router_logits, axis=-1)        # (G, S, E)

    # Switch aux loss: E * Σ_e (token-fraction_e · mean-prob_e)
    top1 = jnp.argmax(gates, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32),
                    axis=(0, 1))
    prob = jnp.mean(gates, axis=(0, 1))
    aux_loss = E * jnp.sum(frac * prob)

    idx, val = _top_k_gating(gates, top_k)                # (G, S, k)
    # renormalize selected gate values per token
    val = val / jnp.maximum(jnp.sum(val, -1, keepdims=True), 1e-9)

    # capacity assignment: position of each (token, slot) in its expert's
    # buffer, counted in slot-major order so slot-0 picks win capacity.
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)      # (G, S, k, E)
    flat = onehot.transpose(0, 2, 1, 3).reshape(G, top_k * S, E)
    pos_flat = jnp.cumsum(flat, axis=1) - flat            # (G, kS, E)
    pos = pos_flat.reshape(G, top_k, S, E).transpose(0, 2, 1, 3)
    pos = jnp.sum(pos * onehot, axis=-1)                  # (G, S, k)
    keep = pos < C

    # (G, S, k, E, C) slot one-hot; overflow slots map to the dropped
    # C-th class.  dispatch sums slots; combine weights them by gate.
    slot_oh = (jax.nn.one_hot(idx, E, dtype=jnp.float32)[..., None]
               * jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                                dtype=jnp.float32)[..., None, :-1])
    disp = jnp.sum(slot_oh, axis=2)                       # (G, S, E, C)
    combine = jnp.sum(
        slot_oh * val[..., None, None].astype(jnp.float32),
        axis=2)                                           # (G, S, E, C)

    xin = jnp.einsum("gsec,gsd->egcd", disp.astype(cdt), x.astype(cdt))
    # constraints only along axes that actually partition — a trivial
    # (size-1) constraint is not free on every backend (docs/perf.md);
    # gate per-axis so dp stays constrained even when ep is trivial
    from .mesh import live_axis
    ep = live_axis(mesh, "ep")
    dp = live_axis(mesh, "dp")
    if ep or dp:
        from jax.sharding import NamedSharding, PartitionSpec as P
        # keep the token-group dim dp-sharded — pinning it replicated
        # would all-gather over dp and fold-duplicate the expert FLOPs
        xin = jax.lax.with_sharding_constraint(
            xin, NamedSharding(mesh, P(ep, dp, None, None)))

    h = jnp.einsum("egcd,edf->egcf", xin, params["w1"].astype(cdt))
    h = h + params["b1"][:, None, None, :].astype(cdt)
    if activation == "gelu":
        h = jax.nn.gelu(h, approximate=True)
    elif activation == "relu":
        h = jax.nn.relu(h)
    else:
        raise MXNetError("unknown activation %r" % activation)
    y = jnp.einsum("egcf,efd->egcd", h, params["w2"].astype(cdt))
    y = y + params["b2"][:, None, None, :].astype(cdt)
    if ep or dp:
        y = jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P(ep, dp, None, None)))

    out = jnp.einsum("gsec,egcd->gsd", combine.astype(cdt), y)
    return out.astype(x.dtype), aux_loss


# ------------------------------------------------- the serving form ---

def route_group_limited(scores, bias, *, n_group, topk_group, top_k,
                        norm_topk_prob=True, scale=1.0, eps=1e-20):
    """DeepSeek-V3's ``noaux_tc`` choice over ``scores`` (T, E) float32
    (the sigmoid of the router's logits): for choosing only,
    ``s' = scores + bias``; a group's score is the sum of its two best
    ``s'`` (``n_group`` groups of ``E / n_group`` consecutive experts),
    the best ``topk_group`` groups stay, and the ``top_k`` best ``s'``
    among them are chosen (``n_group`` 1: the ``top_k`` best of all).
    The weights are the chosen SCORES (not ``s'``), divided by their sum
    plus ``eps`` (the family's constant) where ``norm_topk_prob``, times
    ``scale``.  Returns ``(idx (T, top_k) int32, w (T, top_k) float32)``
    over all E experts, whoever holds them."""
    import jax
    import jax.numpy as jnp
    T, E = scores.shape
    choice = scores + bias.astype(scores.dtype)
    if n_group > 1:
        grouped = choice.reshape(T, n_group, E // n_group)
        best2, _ = jax.lax.top_k(grouped, 2)
        _, keep = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
        kept = jnp.any(keep[:, :, None] == jnp.arange(n_group), axis=1)
        choice = jnp.where(jnp.repeat(kept, E // n_group, axis=1), choice,
                           -jnp.inf)
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), w * scale


def _tile(n, prefer):
    """The largest of ``prefer`` that divides ``n``, else ``n`` whole."""
    return next((t for t in prefer if n % t == 0), n)


# the largest weight tile ``_gmm_tiling`` asks for whole-K: two buffers
# of it, the (128, K) rows' two, the float32 output tile's two and the
# accumulator stay under 10.5 MiB of the 16 MiB of VMEM a call gets
_WEIGHT_TILE_BYTES = 4 << 20


def _gmm_tiling(M, K, N, itemsize):
    """``(tm, tk, tn)`` of the grouped product (M, K) x (G, K, N), from
    the shapes and the operands' item size alone.  Row tiles of 128.
    The weight tile spans ALL of K where some ``tn`` of 512 to 1,024
    lanes (the largest multiple of 128 that divides N) keeps ``(K, tn)``
    within ``_WEIGHT_TILE_BYTES``: the grid then asks for the same
    weight block from an expert's consecutive row tiles, and the copy is
    made once (``_grouped_dot``).  Elsewhere each side is the largest
    multiple of 128 lanes up to 1,024 that divides it (1,024 of 2,048
    or 7,168; 896 of 1,792 = 7 x 256, where powers of two alone gave
    256-wide tiles at 365 GB/s against 484: my chip runs, PR 36), else
    the side whole."""
    tm = _tile(M, (128, 64, 32, 16, 8))
    tn = next((t for t in range(1024, 511, -128)
               if N % t == 0 and K * t * itemsize <= _WEIGHT_TILE_BYTES),
              None)
    if tn is not None:
        return tm, K, tn
    lanes = range(1024, 0, -128)
    return tm, _tile(K, lanes), _tile(N, lanes)


def _weight_fetches(sizes, tm, tiles_k):
    """The copies of a group's weights that megablox's grid makes in
    one grouped product over sorted groups of ``sizes`` (G,) int32, in
    units of one group's whole matrix.  A grid step's weight block is
    ``(group of the visit, k_i, n_i)``, a visit being one row tile of
    ``tm`` that holds some of a group's rows, and a block is copied
    unless the step before asked for the same one: with one K tile a
    group is copied once however many row tiles its rows straddle (the
    groups hit); with more, ``k_i`` has moved on between two visits and
    every visit copies the matrix again."""
    import jax.numpy as jnp
    if tiles_k == 1:
        return jnp.sum(sizes > 0, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    return jnp.sum(jnp.where(sizes > 0, -(-ends // tm) - starts // tm, 0),
                   dtype=jnp.int32)


def _grouped_dot(x, w, sizes):
    """``x[rows of group g] @ w[g]`` for every group: x (M, K) sorted by
    group, w (G, K, N), sizes (G,) int32 whose sum may fall short of M
    (the rows after the last group are whatever the kernel leaves
    there).  Float32 out.  On a TPU ``megablox.gmm`` under
    ``_gmm_tiling``: a grid (N tiles, ACTIVE row tiles, K tiles) whose
    weight block follows the row tile's group.  The weights' copy binds
    a step with a handful of rows an expert, and a group whose rows
    straddle a row tile's edge is visited from both tiles: where the
    weight tiles split K the second visit copies the matrix again
    (``k_i`` has moved on in between), where one spans K it finds its
    block in VMEM and costs its product alone (``_weight_fetches``
    counts which).  The XLA ``ragged_dot`` on the CPU."""
    import jax
    import jax.numpy as jnp
    from ..kernels.platform import run_kernel
    tiling = _gmm_tiling(*x.shape, w.shape[2], w.dtype.itemsize)

    def build(on_cpu):
        if on_cpu:
            return lambda x, w, sizes: jax.lax.ragged_dot(
                x, w, sizes, preferred_element_type=jnp.float32)
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return lambda x, w, sizes: gmm(x, w, sizes, jnp.float32, tiling)

    return run_kernel(build, x, w, sizes)


def held_experts_ffn(x, w_gate, w_up, w_down, idx, w, *, held_first,
                     live=None, act=None):
    """The held experts' part of a dropless expert layer.

    ``x`` (T, D) rows in the compute dtype; ``w_gate`` / ``w_up``
    (E_held, D, F) and ``w_down`` (E_held, F, D): the SwiGLU experts
    ``held_first .. held_first + E_held - 1`` of the layer; ``idx`` /
    ``w`` (T, k) each row's chosen experts and weights over ALL experts
    (``route_group_limited``); ``live`` (T,) bool: rows whose pairs are
    dispatched (a dead row of a fixed-shape step is none of the
    traffic); ``act(gate, up, expert)``: the experts' activation of the
    sorted pairs' two products ((T*k, F) float32 each; ``expert``
    (T*k,) int32 each pair's held expert, ``E_held`` past the groups),
    SwiGLU's ``silu(gate) * up`` where None.  Returns ``(y (T, D) float32, pairs, hit, sizes,
    fetches)``: ``y = sum over a row's HELD choices of w_k E_k(x)``,
    the number of row-expert pairs dispatched, of held experts with at
    least one, each held expert's pairs, (E_held,) int32, and the
    copies of an expert's matrix that the three products' grids ask
    for (``_weight_fetches`` under each product's ``_gmm_tiling``;
    3 x ``hit`` where every hit expert is copied once a product).

    The ``T x k`` pairs are a static bound, so nothing is dropped and
    no capacity is set: the pairs are sorted by held expert (those of
    absent experts and dead rows last, outside every group), each
    matmul is one grouped product over the sorted rows, and the result
    goes back by the inverse permutation."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    T, D = x.shape
    K = idx.shape[1]
    E = w_gate.shape[0]
    local = idx - held_first
    held = (local >= 0) & (local < E)
    if live is not None:
        held = held & live[:, None]
    key = jnp.where(held, local, E).reshape(-1)              # (T*K,)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(E)[None, :], axis=0,
                    dtype=jnp.int32)                          # (E,)
    xs = x[order // K]                                        # (T*K, D)
    if act is None:
        h = jax.nn.silu(_grouped_dot(xs, w_gate, sizes)) \
            * _grouped_dot(xs, w_up, sizes)
    else:
        h = act(_grouped_dot(xs, w_gate, sizes),
                _grouped_dot(xs, w_up, sizes), key[order])
    h = h.astype(x.dtype)
    out = _grouped_dot(h, w_down, sizes)                      # (T*K, D)
    # rows past the groups are whatever the grouped product leaves
    # there: selected away, not multiplied away
    back = jnp.argsort(order)
    out = jnp.where(held.reshape(-1, 1), out[back], 0.0)
    y = jnp.sum(out.reshape(T, K, D)
                * jnp.where(held, w, 0.0).astype(f32)[..., None], axis=1)

    def fetches(k, n):
        tm, tk, _ = _gmm_tiling(T * K, k, n, w_gate.dtype.itemsize)
        return _weight_fetches(sizes, tm, -(-k // tk))

    F = w_gate.shape[2]
    return (y, jnp.sum(sizes), jnp.sum(sizes > 0, dtype=jnp.int32), sizes,
            2 * fetches(D, F) + fetches(F, D))
