"""Sequence/context parallelism: ring attention and Ulysses (all-to-all).

No reference counterpart — MXNet 1.x predates sequence parallelism
(SURVEY.md §5.7 marks it ABSENT; the task brief makes it first-class for
the TPU build).  Design follows the public ring-attention recipe: shard the
sequence over the ``sp`` mesh axis, keep Q resident, rotate K/V blocks
around the ring with ``lax.ppermute`` while accumulating online softmax in
float32 — the collective rides ICI and overlaps with the block matmuls.
Ulysses instead swaps sequence-sharding for head-sharding with two
``all_to_all``s and runs dense local attention.

Both are reverse-mode differentiable (scan + ppermute / all_to_all have
transposes), so they drop straight into training steps under ``jit``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

from ..base import MXNetError

__all__ = ["ring_attention", "ulysses_attention",
           "sequence_parallel_attention"]


def _ring_shard(q, k, v, kmask, *, axis_name, causal, sm_scale):
    """Per-shard ring attention.  q/k/v: (B, Ts, H, dh) local blocks;
    kmask: (B, Ts) 1=valid.  Runs n_shards steps of blockwise online
    softmax, rotating (k, v, kmask) one hop per step."""
    import jax
    import jax.numpy as jnp

    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Tq, H, dh = q.shape
    Tk = k.shape[1]

    qf = q.astype(jnp.float32)
    q_pos = my * Tq + jnp.arange(Tq)

    perm = [(j, (j + 1) % n) for j in range(n)]

    m0 = jnp.full((B, H, Tq, 1), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, Tq, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((B, Tq, H, dh), dtype=jnp.float32)

    def step(carry, i):
        k_c, v_c, km_c, m, l, acc = carry
        # block currently held originated on shard (my - i) mod n
        src = (my - i) % n
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_c.astype(jnp.float32))
        s = s * sm_scale
        valid = km_c[:, None, None, :] != 0
        if causal:
            k_pos = src * Tk + jnp.arange(Tk)
            valid = valid & (k_pos[None, None, None, :] <=
                             q_pos[None, None, :, None])
        s = jnp.where(valid, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_c.astype(jnp.float32))
        acc_new = acc * jnp.moveaxis(alpha, 1, 2) + pv
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        km_c = jax.lax.ppermute(km_c, axis_name, perm)
        return (k_c, v_c, km_c, m_new, l_new, acc_new), ()

    (k_c, v_c, km_c, m, l, acc), _ = jax.lax.scan(
        step, (k, v, kmask, m0, l0, acc0), jnp.arange(n))
    out = acc / jnp.maximum(jnp.moveaxis(l, 1, 2), 1e-30)
    return out.astype(q.dtype)


def _ulysses_shard(q, k, v, kmask, *, axis_name, causal, sm_scale):
    """Per-shard Ulysses: all-to-all seq-shard → head-shard, dense local
    attention over the full sequence, all-to-all back."""
    import jax
    import jax.numpy as jnp

    n = jax.lax.psum(1, axis_name)
    B, Ts, H, dh = q.shape
    # (B, Ts, H, dh) -> (B, T, H/n, dh)
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=2, concat_axis=1, tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    maskg = jax.lax.all_gather(kmask, axis_name, axis=1, tiled=True)

    T = qg.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", qg.astype(jnp.float32),
                   kg.astype(jnp.float32)) * sm_scale
    valid = maskg[:, None, None, :] != 0
    if causal:
        pos = jnp.arange(T)
        valid = valid & (pos[None, None, None, :] <=
                         pos[None, None, :, None])
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vg.astype(jnp.float32))
    out = out.astype(q.dtype)
    # (B, T, H/n, dh) -> (B, Ts, H, dh)
    return jax.lax.all_to_all(out, axis_name=axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


def _wrap(fn_shard, q, k, v, mask, mesh, seq_axis, causal):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if seq_axis not in mesh.axis_names:
        raise MXNetError("mesh has no axis %r" % seq_axis)
    batch_axis = "dp" if "dp" in mesh.axis_names else None
    if mask is None:
        mask = jnp.ones(q.shape[:2], dtype=jnp.int8)

    sm_scale = 1.0 / math.sqrt(q.shape[-1])
    qspec = P(batch_axis, seq_axis, None, None)
    mspec = P(batch_axis, seq_axis)
    fn = functools.partial(fn_shard, axis_name=seq_axis, causal=causal,
                           sm_scale=sm_scale)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(qspec, qspec, qspec, mspec),
                         out_specs=qspec,
                         check_vma=False)(q, k, v, mask)


def ring_attention(q, k, v, mask=None, *, mesh, seq_axis="sp",
                   causal=False):
    """Ring attention over the ``seq_axis`` mesh axis.

    q/k/v: (B, T, H, dh) GLOBAL arrays (sharded or to-be-sharded on T);
    mask: (B, T) key-validity.  Returns (B, T, H, dh)."""
    return _wrap(_ring_shard, q, k, v, mask, mesh, seq_axis, causal)


def ulysses_attention(q, k, v, mask=None, *, mesh, seq_axis="sp",
                      causal=False):
    """Ulysses (all-to-all head-scatter) attention over ``seq_axis``.
    Requires n_heads % mesh.shape[seq_axis] == 0."""
    if q.shape[2] % mesh.shape[seq_axis]:
        raise MXNetError(
            "ulysses: n_heads=%d not divisible by %s=%d"
            % (q.shape[2], seq_axis, mesh.shape[seq_axis]))
    return _wrap(_ulysses_shard, q, k, v, mask, mesh, seq_axis, causal)


def sequence_parallel_attention(q, k, v, mask=None, *, mesh,
                                seq_axis="sp", causal=False,
                                method="ring"):
    """Dispatch helper: ``method`` in {'ring', 'ulysses'}."""
    if method == "ring":
        return ring_attention(q, k, v, mask, mesh=mesh, seq_axis=seq_axis,
                              causal=causal)
    if method == "ulysses":
        return ulysses_attention(q, k, v, mask, mesh=mesh,
                                 seq_axis=seq_axis, causal=causal)
    raise MXNetError("unknown sequence-parallel method %r" % method)
