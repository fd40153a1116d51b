"""Fused paged-attention decode kernel — Pallas TPU (round 11).

The serving engine's decode attention (``serving/engine.py _make_step``)
previously materialized a block-table gather in HBM every step:
``pool[row_pages]`` builds a dense (T*H, L, 2*dh) view — T·H·L·2·dh
elements copied through HBM per layer per step — and only then runs the
two attention dots (``models/gpt.py _attend_rows``).  For decode that
gather IS the step cost: the dots read each element once, so the copy
doubles the dominant HBM stream and adds a full intermediate buffer.

This kernel walks each row's block table directly: grid (T, PP) with
the block table scalar-prefetched (``pltpu.PrefetchScalarGridSpec``),
so the BlockSpec index map streams page ``bt[t, j]`` HBM→VMEM per grid
step (Pallas double-buffers consecutive pages automatically), and the
kernel body folds that page into an **online-softmax accumulation**
(running max / denominator / weighted-V accumulator in VMEM scratch,
the FlashAttention recurrence over pages instead of k-blocks).  The
ragged last page is masked by absolute position (``k_pos <= pos`` —
the same per-row mask ``_attend_rows`` applies), pages past the row's
length are skipped (``pl.when``), and int8-KV pages dequantize inside
the loop — the k scale multiplies the scores, the v scale folds into
the softmax weights, exactly where ``_attend_rows`` folds them —
reading the round-22 TILE-SHAPED scale pages: ``(pages, 2, ps, H)``
f32 planes (k plane 0, v plane 1), so a page's scales stream as
``(ps, H)`` blocks with heads on the lane axis instead of the old
per-column ``(ps, H, 2)`` stripes (``serving/paged_kv.py`` owns the
layout; the engine's quant/dequant and the wire frames moved with it).

Round 22 — the mesh lowering (``mesh=``): ``paged_attention(...,
mesh=serving_mesh(tp))`` wraps the same kernel in ``shard_map`` over
the serving mesh, each device walking its H/tp heads slice of the
heads-sharded pool (``P(None, None, 'tp', None)``; scale planes shard
their trailing heads axis) with q sharded on heads and the block
table/positions REPLICATED into scalar prefetch.  Attention is
head-local, so the body is reused verbatim with H→H/tp and zero
collectives inside — the output-projection psum stays the engine's
(GSPMD inserts it outside the kernel, same as the XLA path).  The
engine passes its mesh whenever ``kernel="pallas", tp>1``
(``serving/engine.py``); tp∈{2,4} greedy token identity vs tp=1 and
``generate`` is pinned in ``tests/test_serving_tp.py`` and the
mesh-vs-reference parity in ``tests/test_paged_attention.py``.

Numerics: online softmax normalizes ONCE at the end (acc / l) where
the jnp reference normalizes the probabilities before the V dot, and
the page-sequential accumulation orders the L-length reductions
differently from one batched dot — both are 1–2 ulp effects in f32
(measured max |diff| ~2e-7 on randn inputs; same caveat class as the
paged-vs-contiguous reduction-order note in ``tests/test_serving.py``).
``tests/test_paged_attention.py`` pins the kernel against the
``_attend_rows`` reference at a few-ulp tolerance across page-boundary
cases in interpreter mode, and the serving tests pin full greedy
TOKEN-identity of the pallas engine against ``generate`` — the
exactness bar the serving stack actually guarantees.

Chip status (PR 21): the two head-batched ``dot_general``s this kernel
was written with (batch dim not leading, no free rhs dim) were refused
by Mosaic — ``failed to parse TPU_DotDimensionNumbersAttr`` — so before
PR 21 the kernel had only ever run in the interpreter.  Both
contractions are now multiply-and-reduce on the VPU (one query row per
head: the walk is bound by the page stream, not by flops), which
Mosaic compiles for v5e at every shape ``tests/test_kernels_mosaic.py``
tries (bf16/f32/int8 pools, the ``full`` preset's 12 heads and its
H/tp slices 6 and 3, the head-blocked 16/32-head walk).  On the chip
``chip_smoke.py`` pins it against ``paged_attention_reference`` and
runs the ``full`` preset engine through it; CHANGES.md (PR 21) has
what that run found.  Its speed is NOT measured — ROADMAP A3.
"""
from __future__ import annotations

import functools

__all__ = ["paged_attention", "paged_attention_reference"]

def _kernel(bt_ref, pos_ref, q_ref, kv_ref, *rest, page_size, dh,
            int8):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if int8:
        s_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        s_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest

    # grid (T, NH, PP): rows, head BLOCKS, pages — the page walk is
    # innermost so the online-softmax scratch accumulates over j for a
    # fixed (row, head-block) and every ref below sees one HB-sized
    # heads slice
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages whose first slot is past the row's position hold nothing
    # this row may attend to — skip the whole page (the scalar-prefetch
    # index map still aims their prefetch at whatever bt says, which
    # for unallocated tail entries is the scratch page 0)
    @pl.when(j * page_size <= pos)
    def _page():
        kv = kv_ref[0]                       # (ps, HB, 2*dh) cdt|int8
        q = q_ref[0]                         # (HB, dh) cdt
        cdt = q.dtype
        f32 = jnp.float32
        k = kv[:, :, :dh].astype(f32)
        v = kv[:, :, dh:].astype(f32)
        # One query row per head: both contractions are multiply-and-
        # reduce on the VPU.  Mosaic has no matmul form for a batch dim
        # that is not leading, and a (1, dh) x (dh, ps) product per
        # head would leave the MXU idle anyway — the walk is bound by
        # the page stream, not by these flops.  Products of cdt (or
        # int8) values are exact in f32, so this matches an MXU dot
        # with f32 accumulation up to summation order.
        s = jnp.sum(k * q.astype(f32)[None], axis=-1)   # (ps, HB)
        if int8:
            # k scale multiplies the scores (the same fold point as
            # _attend_rows).  s_ref[0] is the page's scale block
            # (2, ps, HB): plane 0 = k scales, plane 1 = v
            s = s * s_ref[0, 0]
        s = s / jnp.sqrt(f32(dh))
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(k_pos <= pos, s, -1e30)

        m_prev = m_ref[...]                  # (1, HB)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)               # (ps, HB) f32
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + \
            jnp.sum(p, axis=0, keepdims=True)
        if int8:
            # v scale folds into the softmax weights before the V sum
            p = p * s_ref[0, 1]
        p = p.astype(cdt).astype(f32)
        acc_ref[...] = acc_ref[...] * alpha.T + \
            jnp.sum(p[:, :, None] * v, axis=0)          # (HB, dh)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _out():
        o_ref[0] = (acc_ref[...] / l_ref[...].T).astype(o_ref.dtype)


# bounded cache of built pallas_call closures, keyed on every
# shape/dtype the call specializes on (jit would re-trace through a
# fresh closure each step otherwise — the gpt.py cache idiom)
_call_cache = {}
_CALL_CACHE_MAX = 32


def _build(T, H, dh, PP, page_size, num_pages, kv_dtype, q_dtype,
           int8, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    key = (T, H, dh, PP, page_size, num_pages, str(kv_dtype),
           str(q_dtype), int8, interpret)
    fn = _call_cache.get(key)
    if fn is not None:
        return fn

    # head blocking: walk the heads axis in blocks of 8 (the f32
    # sublane count) when H divides, the whole axis otherwise
    # (small-model/test shapes, and the H/tp slices 6 and 3 of the
    # `full` preset).  Bounds per-step VMEM at HB·(ps·2dh + dh)
    # instead of H·(ps·2dh + dh) however many heads this shard holds.
    # An int8 pool walks whole heads: its scale planes carry heads on
    # the LANE axis, and Mosaic takes a lane block only if it is
    # 128-divisible or the whole axis — an 8-head slice of 16 is
    # neither.
    HB = 8 if H % 8 == 0 and not int8 else H
    NH = H // HB

    def page_map(t, h, j, bt, pos):
        return (bt[t * PP + j], 0, h, 0)

    in_specs = [
        pl.BlockSpec((1, HB, dh), lambda t, h, j, bt, pos: (t, h, 0)),
        pl.BlockSpec((1, page_size, HB, 2 * dh), page_map),
    ]
    scratch = [pltpu.VMEM((1, HB), jnp.float32),
               pltpu.VMEM((1, HB), jnp.float32),
               pltpu.VMEM((HB, dh), jnp.float32)]
    if int8:
        # scale block: (2, ps, H) — two (ps, heads) planes indexed by
        # the SAME page map, the whole heads axis last (HB == H here)
        in_specs.append(pl.BlockSpec(
            (1, 2, page_size, HB),
            lambda t, h, j, bt, pos: (bt[t * PP + j], 0, 0, 0)))
    body = functools.partial(_kernel, page_size=page_size, dh=dh,
                             int8=int8)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, NH, PP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, HB, dh),
                               lambda t, h, j, bt, pos: (t, h, 0)),
        scratch_shapes=scratch,
    )
    fn = pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((T, H, dh), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )
    if len(_call_cache) >= _CALL_CACHE_MAX:
        _call_cache.pop(next(iter(_call_cache)))
    _call_cache[key] = fn
    return fn


def paged_attention(q, pool_kv, pool_s, block_tables, row_pos, *,
                    page_size, interpret=None, mesh=None):
    """Single-token attention over paged K/V via block-table walk.

    Parameters
    ----------
    q : (T, H, dh) compute-dtype queries, one per decode row.
    pool_kv : (num_pages, page_size, H, 2*dh) page pool — the
        ``PagedKVCache`` layout (k and v halves fused on the last
        axis); cfg dtype, or int8 when ``pool_s`` is given.
    pool_s : (num_pages, 2, page_size, H) f32 dequant scales for the
        int8-KV pool (``models/gpt.py _kv_quantize`` values in the
        round-22 tile-shaped plane layout — plane 0 k, plane 1 v),
        or None.
    block_tables : (T, PP) int32 per-ROW page ids; entry j covers
        positions [j*page_size, (j+1)*page_size).  Unused tail entries
        should point at the scratch page 0.
    row_pos : (T,) int32 per-row absolute positions — each row attends
        to positions <= its own (the continuous-batching mask).
    mesh : optional serving mesh with a live ``tp`` axis (round 22).
        The call is then lowered through ``shard_map``: each device
        walks only its H/tp heads slice of the heads-sharded pools
        (``P(None, None, 'tp', None)`` kv / ``P(None, None, None,
        'tp')`` scales), with the block table and positions
        replicated.  Attention is collective-free per head — the
        kernel body is REUSED with H → H/tp and the wo psum stays
        outside — so the lowering adds no communication.  ``None``
        (or a trivial tp=1 mesh) is the single-device path.

    Returns (T, H, dh) f32.  ``interpret=None`` interprets where the
    call runs on the CPU backend (the tier-1 path) and compiles with
    Mosaic on the chip.
    """
    import jax
    import jax.numpy as jnp

    from .platform import run_kernel

    T, H, dh = q.shape
    num_pages = pool_kv.shape[0]
    PP = block_tables.shape[1]
    if pool_kv.shape[1] != page_size:
        raise ValueError("paged_attention: pool page_size %d != %d"
                         % (pool_kv.shape[1], page_size))
    int8 = pool_s is not None
    args = [block_tables.reshape(-1).astype(jnp.int32),
            row_pos.astype(jnp.int32), q, pool_kv]
    if int8:
        args.append(pool_s)

    tp_axis = None
    if mesh is not None:
        from ..parallel.mesh import live_axis
        tp_axis = live_axis(mesh, "tp")
    tp = int(mesh.shape["tp"]) if tp_axis else 1
    if H % tp:
        raise ValueError("paged_attention: H=%d not divisible by "
                         "tp=%d" % (H, tp))

    def call(interp):
        fn = _build(T, H // tp, dh, PP, page_size, num_pages,
                    pool_kv.dtype, q.dtype, int8, interp)
        if tp_axis is None:
            return fn
        from jax.sharding import PartitionSpec as P
        in_specs = [P(), P(), P(None, "tp", None),
                    P(None, None, "tp", None)]
        if int8:
            in_specs.append(P(None, None, None, "tp"))
        # check_vma off: the pallas_call's output carries no
        # replication info for the checker to verify — the out spec is
        # the contract
        return jax.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                             out_specs=P(None, "tp", None),
                             check_vma=False)

    return run_kernel(call, *args, interpret=interpret)


def paged_attention_reference(q, pool_kv, pool_s, block_tables,
                              row_pos, *, page_size):
    """The jnp path: block-table gather + ``_attend_rows``.  This IS
    the serving engine's ``kernel="xla"`` attention (the step program
    calls it directly — one copy, so the engine path and the tests'
    oracle cannot drift), and the reference the Pallas kernel is
    pinned against at a few-ulp f32 tolerance (the online-softmax
    normalization-order caveat in the module docstring)."""
    import jax.numpy as jnp

    from ..models.gpt import _attend_rows

    T, H, dh = q.shape
    PP = block_tables.shape[1]
    L = PP * page_size
    ckv = pool_kv[block_tables].transpose(0, 3, 1, 2, 4) \
        .reshape(T * H, L, 2 * dh)
    cs = None
    if pool_s is not None:
        # retiled plane layout (num_pages, 2, ps, H): gather gives
        # (T, PP, 2, ps, H) — reorder back to _attend_rows' per-token
        # (.., L, 2) scale pairs
        cs = pool_s[block_tables].transpose(0, 4, 1, 3, 2) \
            .reshape(T * H, L, 2)
    pos_r = jnp.repeat(row_pos, H)
    out = _attend_rows(q.reshape(T * H, dh), ckv, cs, pos_r, dh)
    return out.reshape(T, H, dh)
