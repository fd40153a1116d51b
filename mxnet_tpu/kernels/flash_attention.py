"""Flash attention — Pallas TPU kernels (forward AND backward).

No reference counterpart (MXNet 1.x predates flash attention; SURVEY.md
§5.7 marks sequence-scale attention as a TPU-build extension).  Design per
/opt/skills/guides/pallas_guide.md: grid over (batch·heads, q-blocks),
online-softmax accumulation over k-blocks held in VMEM, fp32 accumulators,
MXU matmuls with ``preferred_element_type``.

Backward is the FlashAttention-2 recipe as two Pallas kernels — the
forward saves per-row logsumexp; ``delta = rowsum(dO·O)`` is a cheap jnp
reduction; a dq kernel (grid over q blocks, scanning kv) and a dk/dv
kernel (grid over kv blocks, scanning q) recompute probabilities
blockwise so nothing quadratic is ever materialized.

Sequence-length limit: every program holds a WHOLE ``(1, T, dh)`` K and
V block in VMEM (the dk/dv kernel: Q and dO), double-buffered, with dh
padded to the 128-lane tile.  AOT compiles against v5e (PR 21) put the
ceiling at a block of about 4 MiB — T = 16,384 in bf16, 8,192 in f32:
up to 2 MiB (T = 8,192 bf16, the longest ever run on a chip) every
program tried compiles; between 2 and 4 MiB it depends on how much
VMEM XLA takes for the surrounding program (``RESOURCE_EXHAUSTED …
vmem`` when it does not fit); above 4 MiB every 12-head program was
refused, so ``flash_attention`` raises ``ValueError`` there.  Longer
sequences need K/V streamed in blocks (ROADMAP B7) or
``parallel/ring_attention.py``.
"""
from __future__ import annotations

import collections as _collections
import functools
import math

from jax.experimental import pallas as pl

__all__ = ["flash_attention"]

# test hook: run the Pallas kernels in interpreter mode (exact f32 math,
# works on CPU) so kernel correctness is checkable against the jnp
# reference to tight tolerances without MXU rounding in the way; also
# forces the kernel path regardless of sequence length
_INTERPRET = False

# largest whole-sequence VMEM block (T x 128-padded dh x itemsize) the
# kernels accept — module docstring, "Sequence-length limit"
MAX_KV_BLOCK_BYTES = 4 << 20

# below this sequence length the XLA-fused attention wins on this
# hardware (measured fwd+bwd crossover — docs/perf.md "Long context"):
# the blockwise backward pays two extra S recomputes that XLA's fused
# short-sequence backward avoids, while above it the O(T^2)
# materialization dominates (and OOMs).  Override via
# MXNET_FLASH_MIN_SEQ (e.g. lower it when activation memory, not step
# time, is the binding constraint).
import os as _os


def _min_seq():
    # read at call time: docs/perf.md documents MXNET_FLASH_MIN_SEQ as a
    # user-tunable knob, so setting it after import must take effect
    return int(_os.environ.get("MXNET_FLASH_MIN_SEQ", "4096"))


def _dropout_keep(bh, q_pos, k_pos, seed, rate):
    """Deterministic per-position keep mask for fused attention dropout.

    Counter-based: a murmur-style uint32 mix of (batch·head, absolute q
    position, absolute k position, seed) — every kernel (fwd, dq, dkv)
    regenerates the SAME mask for a tile from positions alone, so
    nothing is stored and no cross-kernel PRNG-state bookkeeping
    exists.  Runs in interpreter mode too (plain jnp integer ops, no
    ``pltpu.prng_*``), which is what makes the CPU parity oracle
    possible (tests/test_flash_dropout.py)."""
    import jax.numpy as jnp
    u = jnp.uint32
    x = (q_pos.astype(u)[:, None] * u(2654435761)) ^ \
        (k_pos.astype(u)[None, :] * u(97780813)) ^ \
        (bh.astype(u) * u(2246822519)) ^ seed.astype(u)
    x = (x ^ (x >> u(16))) * u(2246822519)
    x = (x ^ (x >> u(13))) * u(3266489917)
    x = x ^ (x >> u(16))
    return x >= u(min(int(rate * 4294967296.0), 4294967295))


def dense_keep_mask(B, H, T, seed, rate):
    """Dense (B, H, T, T) positional-hash keep mask — the SAME stream
    the fused kernels regenerate blockwise from positions.  Single
    construction point for every dense consumer (the jnp fallback
    below, the transformer's non-flash path, the parity oracle), so
    the 'one dropout semantics across all paths' invariant cannot
    drift (round-5 review).  ``seed``: int32 scalar."""
    import jax
    import jax.numpy as jnp
    pos = jnp.arange(T, dtype=jnp.int32)
    bh = (jnp.arange(B, dtype=jnp.uint32)[:, None] * jnp.uint32(H)
          + jnp.arange(H, dtype=jnp.uint32)[None, :]).reshape(-1)
    keep = jax.vmap(lambda b: _dropout_keep(b, pos, pos, seed,
                                            float(rate)))(bh)
    return keep.reshape(B, H, T, T)


def _kernel(q_ref, k_ref, v_ref, mask_ref, seed_ref, o_ref, lse_ref, *,
            block_k, sm_scale, causal, dropout):
    import jax
    import jax.numpy as jnp

    q = q_ref[0]                      # (BQ, dh)
    bq, dh = q.shape
    T = k_ref.shape[1]
    nk = T // block_k
    bh = pl.program_id(0)
    q_pos = pl.program_id(1) * bq + jnp.arange(bq)

    m0 = jnp.full((bq, 1), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((bq, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((bq, dh), dtype=jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (BQ, BK)
        msk = mask_ref[0, 0, pl.dslice(i * block_k, block_k)]
        k_pos = i * block_k + jnp.arange(block_k)
        valid = msk[None, :] != 0
        if causal:
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
        s = jnp.where(valid, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        # the softmax denominator accumulates the UNDROPPED p — dropout
        # applies to the normalized probabilities, not the logits
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout > 0.0:
            keep = _dropout_keep(bh, q_pos, k_pos, seed_ref[0],
                                 dropout)
            p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout))
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # blocks fully above the diagonal contribute nothing — stop at
        # the diagonal block (the standard FlashAttention-2 bound)
        nk_eff = (pl.program_id(1) * bq + bq + block_k - 1) // block_k
        nk_eff = jnp.minimum(nk, nk_eff)
    else:
        nk_eff = nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # per-row logsumexp, consumed by the backward kernels
    lse_ref[0, 0] = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, 0]


def _flash_fwd_tpu(q, k, v, mask, seed, causal=False, dropout=0.0,
                   block_q=128, block_k=128):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, dh = q.shape
    sm_scale = 1.0 / math.sqrt(dh)
    # layout: (B*H, T, dh)
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    if mask is None:
        mask_arr = jnp.ones((B, T), dtype=jnp.int8)
    else:
        mask_arr = mask.astype(jnp.int8)

    block_q = min(block_q, T)
    block_k = min(block_k, T)
    grid = (B * H, T // block_q)

    # mask as (B, 1, T): the (1, 1, T) block satisfies the (8, 128)
    # tiling rule (second-to-last block dim equals the array dim) with
    # static in-kernel indices — a (1, T) block of a (B, T) array does
    # not, and a dynamic batch index into packed int8 rows is
    # unprovable for Mosaic.
    out, lse = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, sm_scale=sm_scale,
                          causal=causal, dropout=dropout),
        interpret=_INTERPRET,
        out_shape=[jax.ShapeDtypeStruct((B * H, T, dh), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, T, dh), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, T, dh), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, 1, T), lambda bh, qi, H=H: (bh // H, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),
        ],
    )(qt, kt, vt, mask_arr[:, None, :], seed)
    return (out.reshape(B, H, T, dh).transpose(0, 2, 1, 3),
            lse.reshape(B, H, T))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   mask_ref, seed_ref, dq_ref, *, block_k, sm_scale,
                   causal, dropout):
    import jax
    import jax.numpy as jnp

    q = q_ref[0]                      # (BQ, dh)
    do = do_ref[0]                    # (BQ, dh)
    lse = lse_ref[0, 0]               # (BQ,)
    delta = delta_ref[0, 0]           # (BQ,)
    bq, dh = q.shape
    T = k_ref.shape[1]
    nk = T // block_k
    bh = pl.program_id(0)
    q_pos = pl.program_id(1) * bq + jnp.arange(bq)

    def body(i, acc):
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (BQ, BK)
        msk = mask_ref[0, 0, pl.dslice(i * block_k, block_k)]
        k_pos = i * block_k + jnp.arange(block_k)
        valid = msk[None, :] != 0
        if causal:
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)  # (BQ, BK)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (BQ, BK)
        if dropout > 0.0:
            # dS = P ∘ (D∘dP̃ − delta): the same positional keep mask
            # the forward used, regenerated — never stored
            keep = _dropout_keep(bh, q_pos, k_pos, seed_ref[0],
                                 dropout)
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout))
        ds = p * (dp - delta[:, None]) * sm_scale
        return acc + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        nk_eff = (pl.program_id(1) * bq + bq + block_k - 1) // block_k
        nk_eff = jnp.minimum(nk, nk_eff)
    else:
        nk_eff = nk
    acc0 = jnp.zeros((bq, dh), jnp.float32)
    acc = jax.lax.fori_loop(0, nk_eff, body, acc0)
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    mask_ref, seed_ref, dk_ref, dv_ref, *, block_q,
                    sm_scale, causal, dropout):
    import jax
    import jax.numpy as jnp

    k = k_ref[0]                      # (BK, dh)
    v = v_ref[0]
    bk, dh = k.shape
    T = q_ref.shape[1]
    nq = T // block_q
    bh = pl.program_id(0)
    k_pos = pl.program_id(1) * bk + jnp.arange(bk)
    msk = mask_ref[0, 0, pl.dslice(pl.program_id(1) * bk, bk)]

    def body(j, carry):
        dk_acc, dv_acc = carry
        q = q_ref[0, pl.dslice(j * block_q, block_q), :]
        do = do_ref[0, pl.dslice(j * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.dslice(j * block_q, block_q)]
        delta = delta_ref[0, 0, pl.dslice(j * block_q, block_q)]
        q_pos = j * block_q + jnp.arange(block_q)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (BQ, BK)
        valid = msk[None, :] != 0
        if causal:
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
        if dropout > 0.0:
            keep = _dropout_keep(bh, q_pos, k_pos, seed_ref[0],
                                 dropout)
            inv = 1.0 / (1.0 - dropout)
            p_drop = jnp.where(keep, p, 0.0) * inv
        else:
            keep = None
            p_drop = p
        # dV += P̃^T dO (the DROPPED probabilities feed V's gradient)
        dv_acc = dv_acc + jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (BK, dh)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (BQ, BK)
        if keep is not None:
            dp = jnp.where(keep, dp, 0.0) * inv
        ds = p * (dp - delta[:, None]) * sm_scale
        # dK += dS^T Q
        dk_acc = dk_acc + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    if causal:
        # q blocks strictly above this kv block's diagonal see none of
        # these keys — start at the diagonal block
        j0 = (pl.program_id(1) * bk) // block_q
    else:
        j0 = 0
    z = jnp.zeros((bk, dh), jnp.float32)
    dk_acc, dv_acc = jax.lax.fori_loop(j0, nq, body, (z, z))
    dk_ref[0] = dk_acc.astype(dk_ref.dtype)
    dv_ref[0] = dv_acc.astype(dv_ref.dtype)


def _flash_bwd_tpu(q, k, v, mask, seed, out, lse, g, causal=False,
                   dropout=0.0, block_q=128, block_k=128):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, dh = q.shape
    sm_scale = 1.0 / math.sqrt(dh)
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    dot = g.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    ot = out.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    lse_f = lse.reshape(B * H, 1, T)
    if mask is None:
        mask_arr = jnp.ones((B, T), dtype=jnp.int8)
    else:
        mask_arr = mask.astype(jnp.int8)
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    # delta_i = sum_d dO_id * O_id — one cheap fused reduction
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)[:, None, :]                      # (B*H, 1, T)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k,
                          sm_scale=sm_scale, causal=causal,
                          dropout=dropout),
        interpret=_INTERPRET,
        out_shape=jax.ShapeDtypeStruct((B * H, T, dh), q.dtype),
        grid=(B * H, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, T, dh), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, T, dh), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, block_q, dh), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),
            pl.BlockSpec((1, 1, T), lambda bh, qi, H=H: (bh // H, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh),
                               lambda bh, qi: (bh, qi, 0)),
    )(qt, kt, vt, dot, lse_f, delta, mask_arr[:, None, :], seed)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q,
                          sm_scale=sm_scale, causal=causal,
                          dropout=dropout),
        interpret=_INTERPRET,
        out_shape=[jax.ShapeDtypeStruct((B * H, T, dh), k.dtype),
                   jax.ShapeDtypeStruct((B * H, T, dh), v.dtype)],
        grid=(B * H, T // block_k),
        in_specs=[
            pl.BlockSpec((1, T, dh), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, T, dh), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, 1, T), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, 1, T), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, 1, T), lambda bh, ki, H=H: (bh // H, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, dh), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, ki: (bh, ki, 0)),
        ],
    )(qt, kt, vt, dot, lse_f, delta, mask_arr[:, None, :], seed)

    unpack = lambda x: x.reshape(B, H, T, dh).transpose(0, 2, 1, 3)
    return unpack(dq), unpack(dk), unpack(dv)


def _reference_attention(q, k, v, mask, causal=False, dropout=0.0,
                         seed=None):
    import jax
    import jax.numpy as jnp
    dh = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    if causal:
        T = q.shape[1]
        tri = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(tri[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
        q.dtype)
    if dropout > 0.0:
        # the SAME positional hash mask the Pallas kernels use, built
        # dense — the fallback and the kernel paths drop identical
        # entries for a given seed (and this is the parity oracle)
        B, T, H, _ = q.shape
        keep = dense_keep_mask(B, H, T, seed[0], dropout)
        probs = jnp.where(keep, probs, 0).astype(q.dtype) \
            * (1.0 / (1.0 - dropout))
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def _make_flash(causal, dropout):
    import jax

    @jax.custom_vjp
    def _flash(q, k, v, mask, seed):
        out, _ = _flash_fwd_tpu(q, k, v, mask, seed, causal=causal,
                                dropout=dropout)
        return out

    def fwd(q, k, v, mask, seed):
        out, lse = _flash_fwd_tpu(q, k, v, mask, seed, causal=causal,
                                  dropout=dropout)
        return out, (q, k, v, mask, seed, out, lse)

    def bwd(res, g):
        q, k, v, mask, seed, out, lse = res
        dq, dk, dv = _flash_bwd_tpu(q, k, v, mask, seed, out, lse, g,
                                    causal=causal, dropout=dropout)
        return dq, dk, dv, None, None

    _flash.defvjp(fwd, bwd)
    return _flash


# LRU-bounded: keyed by (causal, dropout-rate); a dropout-rate schedule
# sweeping many distinct rates would otherwise grow this dict (and each
# entry's compiled custom_vjp closures) without bound (round-4 advisor).
_flash_cached = _collections.OrderedDict()
_FLASH_CACHE_MAX = 16


def flash_attention(q, k, v, mask=None, causal=False, dropout=0.0,
                    dropout_seed=None):
    """(B, T, H, dh) attention with a fused online-softmax TPU kernel;
    ``causal=True`` adds the autoregressive lower-triangular mask.

    ``dropout`` > 0 applies attention-probability dropout INSIDE the
    kernels (fwd + both bwd) via a positional counter hash keyed by
    ``dropout_seed`` (int32 scalar; required when dropout > 0) — no
    (T, T) mask is ever materialized, and the backward regenerates the
    identical mask from positions (SURVEY.md §5.7; round-4 item #7).

    Routing: the jnp reference where the operands live on the CPU
    backend (under tracing: where ``jit`` will place the program),
    below ``MXNET_FLASH_MIN_SEQ``, or when shapes don't tile (T not
    divisible by the 128 block, dh not lane-aligned); the reference
    applies the same hash dropout.  Above ``MAX_KV_BLOCK_BYTES`` (module
    docstring) the kernels cannot hold K/V in VMEM: ``ValueError``.

    Memory note: the fallback materializes the (B, H, T, T) keep mask
    densely on top of the probs tensor, so dropout training roughly
    doubles attention peak memory versus dropout=0 on that path.  If
    that OOMs at a T below ``MXNET_FLASH_MIN_SEQ`` (default 4096),
    lower the env var to route those lengths to the fused kernels,
    which never build the mask.
    """
    import jax
    import jax.numpy as jnp
    dropout = float(dropout)
    if not 0.0 <= dropout < 1.0:
        raise ValueError("flash_attention: dropout must be in [0, 1), "
                         "got %r" % dropout)
    if dropout > 0.0:
        if dropout_seed is None:
            raise ValueError("flash_attention: dropout > 0 requires "
                             "dropout_seed")
        seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
    else:
        seed = jnp.zeros(1, jnp.int32)
    from .platform import default_platform, platform_of
    # decided here at trace time, not staged per platform: training
    # differentiates through this call (platform.run_kernel docstring)
    platform = platform_of(q, k, v) or default_platform()
    B, T, H, dh = q.shape
    if not _INTERPRET and (platform == "cpu" or T < _min_seq()):
        return _reference_attention(q, k, v, mask, causal=causal,
                                    dropout=dropout, seed=seed)
    if T % 128 != 0 or dh not in (64, 128, 256):
        return _reference_attention(q, k, v, mask, causal=causal,
                                    dropout=dropout, seed=seed)
    block_bytes = T * max(dh, 128) * jnp.dtype(q.dtype).itemsize
    if not _INTERPRET and block_bytes > MAX_KV_BLOCK_BYTES:
        raise ValueError(
            "flash_attention: T=%d, dh=%d, %s needs a %.1f MiB whole-"
            "sequence K/V block in VMEM; the kernels hold at most %d MiB "
            "(T=16384 bf16 / 8192 f32) — shard the sequence "
            "(parallel/ring_attention.py)"
            % (T, dh, jnp.dtype(q.dtype).name, block_bytes / 2**20,
               MAX_KV_BLOCK_BYTES >> 20))
    key = (causal, dropout)
    fn = _flash_cached.get(key)
    if fn is None:
        fn = _make_flash(causal, dropout)
        _flash_cached[key] = fn
        if len(_flash_cached) > _FLASH_CACHE_MAX:
            _flash_cached.popitem(last=False)
    else:
        _flash_cached.move_to_end(key)
    return fn(q, k, v, mask, seed)
