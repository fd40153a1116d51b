"""Pipeline parallelism: GPipe microbatch schedule over a ``pp`` mesh axis.

No reference counterpart — MXNet 1.x has only manual model parallelism
(``group2ctx`` + the nnvm ``place_device`` pass, SURVEY.md §2.4); pipeline
parallelism is a TPU-build extension.  Design is the collective-pipelining
recipe: each ``pp`` shard holds a contiguous block of layers ("stage"),
activations hop one stage per step with ``lax.ppermute`` over ICI, and a
``lax.scan`` runs the ``n_microbatches + n_stages - 1`` step GPipe
schedule.  Everything is scan + ppermute + where, so reverse-mode AD
yields the mirrored backward pipeline for free.

The ``pp`` axis is the ONLY manual axis (``shard_map(axis_names={axis})``);
``dp``/``tp`` stay auto, so GSPMD still lays out the in-stage matmuls and
inserts the gradient psum over ``dp``.
"""
from __future__ import annotations

import functools

from ..base import MXNetError

__all__ = ["pipeline_apply", "stack_layer_params"]


def stack_layer_params(layers):
    """List of per-layer param pytrees (same structure) → one pytree whose
    leaves gain a leading ``n_layers`` axis.  This is the layout pipeline
    stages index into; shard the leading axis over ``pp``."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *layers)


def _tree_index(tree, i):
    import jax
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def pipeline_apply(stage_fn, stacked_params, x, aux=None, *, mesh,
                   axis="pp", n_microbatches, has_aux=False):
    """Run ``x`` through a layer stack pipelined over ``mesh`` axis ``axis``.

    stage_fn(stage_params, x_mub, aux_mub, stage_idx, mub_idx) -> x_out
        applies ONE stage's layers to one microbatch.  ``stage_params``
        leaves have leading dim ``n_layers // n_stages``; ``stage_idx`` /
        ``mub_idx`` are traced int32 scalars (use ``jax.random.fold_in``
        for per-site dropout keys).  With ``has_aux=True`` it instead
        returns ``(x_out, aux_scalar)`` (e.g. a MoE load-balancing loss).
    stacked_params : pytree with leading ``n_layers`` axis
        (see :func:`stack_layer_params`).
    x : (B, ...) global batch; B must divide by ``n_microbatches``.
    aux : optional pytree of (B, ...) per-example tensors that travel with
        their microbatch unchanged (attention masks, per-row keys, ...).

    Returns (B, ...) output of the final stage — or, with ``has_aux``,
    ``(output, aux_total)`` where ``aux_total`` is the microbatch-mean of
    the per-stage aux scalars summed over stages (matching what a
    sequential full-batch pass would report).  Differentiable.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if axis not in mesh.axis_names:
        raise MXNetError("mesh has no axis %r" % axis)
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    if B % n_microbatches:
        raise MXNetError("batch %d %% n_microbatches %d != 0"
                         % (B, n_microbatches))
    mub = B // n_microbatches
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_layers % n_stages:
        raise MXNetError("n_layers %d %% pp %d != 0" % (n_layers, n_stages))
    per_stage = n_layers // n_stages

    # (n_layers, ...) -> (n_stages, per_stage, ...); P(axis) on dim 0 gives
    # each pp shard exactly its stage block.
    staged = jax.tree_util.tree_map(
        lambda a: a.reshape((n_stages, per_stage) + a.shape[1:]),
        stacked_params)
    xm = x.reshape((n_microbatches, mub) + x.shape[1:])
    auxm = jax.tree_util.tree_map(
        lambda a: a.reshape((n_microbatches, mub) + a.shape[1:]), aux)

    n_iter = n_microbatches + n_stages - 1
    fwd_perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    # XLA:CPU workaround: AllReducePromotion crashes ("Invalid binary
    # instruction opcode copy") cloning the bf16 gradient all-reduce
    # this partial-manual shard_map produces — reduced repro committed
    # at docs/xla_cpu_bf16_pp_repro.py.  Keep bf16 PARAM leaves f32
    # across the shard_map boundary on CPU (their grad psum then runs
    # f32, which the pass leaves alone) and cast back inside the manual
    # region; activations and compute stay bf16.  TPU takes the direct
    # path.
    cpu_bf16_fix = mesh.devices.flat[0].platform == "cpu"
    p_dtypes = jax.tree_util.tree_map(lambda a: a.dtype, staged)
    x_dtype = xm.dtype
    aux_dtypes = jax.tree_util.tree_map(lambda a: a.dtype, auxm)

    def _widen(t):
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32)
            if a.dtype == jnp.bfloat16 else a, t)

    def _narrow(t, dtypes):
        return jax.tree_util.tree_map(lambda a, d: a.astype(d), t,
                                      dtypes)

    if cpu_bf16_fix:
        # every input replicated over the manual axis whose grad needs
        # a pp all-reduce must cross the boundary as f32 (params AND
        # activations/aux) — see the repro note above
        staged, xm, auxm = _widen(staged), _widen(xm), _widen(auxm)

    def per_shard(staged_p, xm, auxm):
        if cpu_bf16_fix:
            staged_p = _narrow(staged_p, p_dtypes)
            xm = xm.astype(x_dtype)
            auxm = _narrow(auxm, aux_dtypes)
        stage_p = _tree_index(staged_p, 0)      # squeeze P(axis) block
        s = jax.lax.axis_index(axis)

        def body(carry, t):
            state, out_acc, aux_acc = carry
            m = jnp.clip(t - s, 0, n_microbatches - 1)
            # stage 0 injects microbatch t; others take the ppermuted
            # activation handed over from stage s-1 last step.
            inject = jax.lax.dynamic_index_in_dim(
                xm, jnp.clip(t, 0, n_microbatches - 1), keepdims=False)
            cur = jnp.where(s == 0, inject, state)
            aux_mub = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, m, keepdims=False),
                auxm)
            res = stage_fn(stage_p, cur, aux_mub, s, m)
            y, aux_s = res if has_aux else (res, 0.0)
            y = y.astype(xm.dtype)
            active = (t - s >= 0) & (t - s < n_microbatches)
            is_last = s == n_stages - 1
            out_acc = jnp.where(
                active & is_last,
                jax.lax.dynamic_update_index_in_dim(out_acc, y, m, 0),
                out_acc)
            aux_acc = aux_acc + jnp.where(active, aux_s, 0.0)
            state = jax.lax.ppermute(y, axis, fwd_perm)
            return (state, out_acc, aux_acc), ()

        state0 = jnp.zeros_like(xm[0])
        out0 = jnp.zeros_like(xm)
        aux0 = jnp.zeros((), jnp.float32)
        (_, out_acc, aux_acc), _ = jax.lax.scan(
            body, (state0, out0, aux0), jnp.arange(n_iter))
        # emit per-stage accumulators; only the last stage's out is real,
        # aux sums across stages.
        return out_acc[None], aux_acc[None]

    sharded = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=(P(axis), P(axis)),
        axis_names={axis}, check_vma=False,
    )
    # Partial-manual shard_map (axis_names ⊂ mesh axes) only lowers
    # correctly under jit in jax 0.9 — the eager impl path re-enters
    # shard_map with full-mesh manual axes and rejects the specs.  Under
    # an outer jit this inner jit is inlined.
    out, aux_out = jax.jit(sharded)(staged, xm, auxm)
    # (n_stages, n_microbatches, mub, ...) — last stage holds the output.
    result = out[-1].reshape((B,) + out.shape[3:])
    if has_aux:
        return result, aux_out.sum() / n_microbatches
    return result
