"""Fused paged-attention decode kernel — Pallas TPU.

The serving engine's attention (``serving/engine.py _make_step``) has
two lowerings of one algorithm.  The jnp one,
``paged_attention_reference``, materializes a block-table gather in
HBM every step — ``pool[row_pages]`` builds a dense (T*H, L, 2*dh)
view of every row's WHOLE table, XLA copies it into another layout,
and only then ``models/gpt.py _attend_rows`` reads it: on the chip
98% of a decode-heavy step (PERF.md).  It is the CPU path and the
tests' oracle.  ``paged_attention`` here reads each row's live pages
straight from the pool, once, and folds them into an
**online-softmax accumulation** (running max / denominator /
weighted-V sum, the FlashAttention recurrence over pages instead of
k-blocks): it is what the engine runs on a TPU.

**The walk** (``_walk_kernel``, PR 27, 34).  Grid over blocks of R
rows — 5 grid steps a layer for the benchmark's 160-row step, not one
per page.  The block table and the positions are scalar-prefetched
(``pltpu.PrefetchScalarGridSpec``); the pool stays in HBM
(``memory_space=pl.ANY``).  A grid step's work is the flat sequence
of its rows' live groups of G pages, the (row, group) items, bounded
by ``row_pos // page_size + 1`` and by nothing else: pages past a
row's position, and every page of a dead row but its scratch page,
are neither copied nor folded.  A cursor steps through the items
twice, K - 1 items apart: ``make_async_copy`` brings item i + K - 1
into slot (i - 1) mod K of a ring of K VMEM buffers while item i is
folded out of slot i mod K — a whole page ``(ps, H, 2*dh)`` is one
contiguous copy, and a row's first copy hides behind the rows before
it.  The loop takes K items a trip, so every slot index is static and
the compiler, which sees each slot as a buffer of its own, runs the
scalar code that issues the copies under the chain of the fold beside
it; each item is folded FROM A FRESH (m, l, acc), a whole group at
once, and merged into its row's running state in VMEM (the two-way
softmax merge), so a chain waits for nothing an earlier one leaves;
the rows are normalised and written when the grid step's items are
in.  The slots that a block's last trip has no item for fold what
they hold, masked, into a spare row of the state (a guard a slot
would cost every item a basic block's boundary, a guarded extra trip
a second copy of the loop's body to trace and lower at every
start-up).  G, F, R and K follow from the shapes
(``walk_geometry``): for the cell's bf16 pool of 16 heads of 64,
pages are 64 KiB, G = 8 (four 512 KiB slots), F = G, R = 32, K = 4.
The flat column fold keeps the older loop: per row a ``fori_loop`` over
its groups, one group copied ahead into the other of two slots, the
state carried from group to group.

**The dense fold** (``_fold_dense``, PR 31) is what the walk folds a
``(ps, H, 2*dh)`` pool with, a whole group a turn.  A page's rows,
(token, head) by (k | v), go to the MXU as they lie in VMEM: the
row's zero-extended query times their transpose is ONE tile of scores
(H, tokens*H), live on its block diagonal; mask, max, exp and sums
run once on that tile, and p, zero off the diagonal, is the left
operand of the second product over the same rows.  The column fold
(``_fold``: a lane reduction a token and head on the XLU, the scores
one live lane in 128, both products on the VPU) was bound by the
XLU's reductions; it stays for the per-page grid, whose pools it fits
(int8 scale planes lie tokens-on-sublanes) and whose pages are too
small a tile to pay for the MXU's latency.

**Grouped-query pools** (PR 28).  A model with fewer key/value heads
than query heads keeps FLAT pages, ``(ps, Hkv*2*dh)``: each key/value
head's k then v side by side on the lanes, tokens on the sublanes
(``serving/paged_kv.py`` chooses the layout and writes it,
``write_rows``; ``pool_kv.ndim == 3`` tells it here).  With 4 heads the ``(ps, H, 2*dh)`` page's two minor
dims would be no whole tiles; the flat page is, so the same walk cuts
it out of HBM with the same copies.  ``_fold_flat`` is the same
recurrence with the roles of the axes turned: a head's scores are a
column over the page's tokens, its k and v whole lane tiles read by
each of the ``Hq / Hkv`` query heads that share them.  Where a head's
``[k | v]`` pair is ONE lane tile (heads of 64, PR 36: there the
column fold read 80 GB/s, a lane reduction a query head a turn of two
pages under 32 query heads) the flat pool goes through the ring with
the dense form, ``_fold_flat_dense``: a pair the MXU's weights as it
lies in VMEM, every query head against it, the rows of the heads that
share it kept; the scores one tile, heads by tokens.

**Latent pools** (PR 32).  Multi-head latent attention caches ONE row a
token, ``[c_kv (rank) | rotated k_pe (rope)]``, shared by every query
head (``latent=(rank, rope)``; pages ``(ps, W)``, the row padded with
zero lanes to ``W`` whole tiles, 512 + 64 -> 640).  ``_fold_latent``
is the shape the MXU wants: a row's absorbed queries (H, W) against a
group's rows (n, W) are the scores (H, n) in one product, and ``p``
against the same rows' first ``rank`` lanes is the read-back (H, rank):
the page is read once, as the key and again as the value.  The softmax
scale is the model's (``scale=``) and multiplies the float32 scores;
there is no head size to take a root of.  Only the walk folds it.

**Which pool takes which feeder** (``walk_geometry`` decides, from
shapes alone).  The walk: every 32-bit pool; 16-bit ``(ps, H, 2*dh)``
pools whose head count is a multiple of 8 (the benchmark's BERT
cells, 16 heads); flat 16-bit pools whose pages hold a multiple of 16
tokens and of 128 lanes (the Falcon-H1 cell, 4 x 2 x 128 lanes at 16
tokens).  **The per-page grid** (``_page_kernel``), the older feeder
of the same folds — grid (T, PP), the BlockSpec index map streams
page ``bt[t, j]`` per grid step, pages past the position skipped by
``pl.when`` after their copy was paid — serves what is left, because
this Mosaic refuses a ``memref_slice`` whose two minor dims are not
whole tiles even where it takes them whole: 16-bit pools whose head
count is no multiple of 8 (the ``full`` preset's 12 and its H/tp
slices 6 and 3), flat 16-bit pools with 8-token pages, and int8
pools, whose ``(ps, H)`` scale planes are never whole tiles (ROADMAP
C records the debt).  int8-KV pages dequantize inside the fold — the
k scale multiplies the scores, the v scale folds into the softmax
weights, exactly where ``_attend_rows`` folds them — reading the
round-22 TILE-SHAPED scale pages: ``(pages, 2, ps, H)`` f32 planes (k
plane 0, v plane 1; ``serving/paged_kv.py`` owns the layout).  A flat
pool has no int8 form and no mesh lowering yet.

The mesh lowering (``mesh=``, round 22): ``paged_attention(...,
mesh=serving_mesh(tp))`` wraps the same call in ``shard_map`` over
the serving mesh, each device walking its H/tp heads slice of the
heads-sharded pool (``P(None, None, 'tp', None)``; scale planes shard
their trailing heads axis) with q sharded on heads and the block
table/positions REPLICATED into scalar prefetch.  Attention is
head-local, so the body is reused verbatim with H→H/tp and zero
collectives inside — the output-projection psum stays the engine's
(GSPMD inserts it outside the kernel, same as the XLA path).  The
engine passes its mesh whenever it runs this kernel with tp>1;
tp∈{2,4} greedy token identity vs tp=1 and ``generate`` is pinned in
``tests/test_serving_tp.py`` and the mesh-vs-reference parity in
``tests/test_paged_attention.py``.

Numerics (every fold): pages in the pool's dtype, scores and the
running max / denominator / accumulator in f32, probabilities rounded
to the compute dtype before the V sum, ONE normalization at the end
(acc / l) where the jnp reference normalizes the probabilities before
the V dot; the page-sequential accumulation orders the L-length
reductions differently from one batched dot — both are 1–2 ulp
effects in f32 (measured max |diff| ~2e-7 on randn inputs; same
caveat class as the paged-vs-contiguous reduction-order note in
``tests/test_serving.py``).  The dense fold's products are the MXU's:
exact for 16-bit pages (f32 accumulation, as the VPU's), six bf16
passes (``Precision.HIGHEST``) for a 32-bit pool, 2–4e-7 of the
reference on the chip.  ``tests/test_paged_attention.py`` pins
both feeders against the ``_attend_rows`` reference at a few-ulp
tolerance across page- and group-boundary cases in interpreter mode,
and the serving tests pin full greedy TOKEN-identity of the pallas
engine against ``generate`` — the exactness bar the serving stack
actually guarantees.

On the chip: ``tests/test_kernels_mosaic.py`` compiles every pool
kind for v5e without one; ``chip_smoke.py`` pins both feeders against
``paged_attention_reference`` there.  Measured (PERF.md, PR 27, 31
and 34): in ``bert_large_decoder.decode_heavy`` the gather path was 68
ms of device time a step, the per-page grid 43, the walk under the
column fold 7.3 (bound by the XLU: 64 lane reductions a turn of two
pages), under the dense fold 5.3, with the ring 3.9: 554 GB/s, 68% of
the HBM peak on the cell's ragged rows, 90% on rows 31 pages deep.
The core runs at 0.94 GHz and what binds the walk is its instruction
stream, which the compiled loop's bundles show without a chip: a
group was 657 bundles under two slots, 240 of them the serial scalar
code that issues eight copies, and is about 500 in the ring — 384 the MXU's
weight pushes (a transposed push is 8 cycles an MXU, a plain one 4;
128 of each a group over four MXUs), the rest waits and the tail of
the issue.  Built and lost on the way: a tree of rolls and selects in
place of the lane reductions (x0.42: every roll is an XLU operation
too); two chains in one basic block (no shorter than two blocks: they
contend for the push slots); a ring of three or of six.
"""
from __future__ import annotations

import functools

__all__ = ["paged_attention", "paged_attention_reference"]

# VMEM one DMA slot of the walk may hold: a group is as many whole
# pages as fit (the ring's _RING slots are live, so the walk's buffers
# are that many times this, beside the f32 temporaries of one fold)
_GROUP_BYTES = 512 * 1024
# rows of the step a grid step walks: the first copy of a grid step
# has nothing to hide behind, so its latency is paid once per _ROWS
# rows; q and the output block over it and stay small
_ROWS = 16


# rows a grid step of the LATENT walk takes: its q and output blocks
# are 64 heads wide (R x H x (W + rank) values, double-buffered)
_ROWS_LATENT = 8
# rows a grid step of the ring takes on a ``(ps, H, 2*dh)`` pool: what
# a grid step costs beside its items (its first copies exposed, the
# rows' state normalised and written) is paid once per _ROWS_RING rows
_ROWS_RING = 32
# items a trip of the ring's loop takes, each from a slot of its own:
# while one is folded, _RING - 1 groups of copies are on their way
_RING = 4


def walk_geometry(H, dh, page_size, PP, kv_dtype, flat=False,
                  latent=False):
    """``(G, F, R, K)`` of the walk for one pool geometry — ``G`` pages
    are copied per DMA group (as many whole pages as ``_GROUP_BYTES``
    holds, at least one, at most a row's table), ``F`` of them are
    folded per turn of the inner loop (the whole group under the dense
    fold, whose fixed cost, the MXU's latency twice over, is then paid
    once a group; two a turn under the flat fold where G is even: a
    row's last turn folds at most one page it did not need), ``R``
    rows are walked per grid step, ``K`` groups are taken per trip of
    the walk's loop, each folded by a chain of its own out of its own
    slot of a ring of K copies (``_RING`` where the fold is one MXU
    turn a group, F == G: while one group is folded, K - 1 are on
    their way; 1 under the flat fold, whose turns are bound by the
    XLU's lane reductions and keep their loop of one group a trip
    over two slots) — or ``None`` where Mosaic cannot cut whole pages
    out of the pool and the per-page grid serves instead: a
    ``memref_slice`` of an HBM ref must be whole tiles in its two
    minor dims even where it takes them whole.

    ``H`` is the pool's head count (the key/value heads), ``flat``
    which of the two page layouts it has:

    * ``(page_size, H, 2*dh)`` (as many key/value heads as query
      heads): a 16-bit page is whole tiles only when H is a multiple
      of 8 (12, 6 and 3 are refused: "Slice shape along dimension 2
      must be aligned to tiling (8)"), and an int8 pool's ``(ps, H)``
      scale planes never are;
    * ``(page_size, H*2*dh)`` (``flat``: grouped-query pools, few
      key/value heads): whole tiles when the tokens fill the sublanes
      (8 rows of 32 bits, 16 of 16) and the heads' lanes are a
      multiple of 128 — 4 heads of 128 in bf16 at 16-token pages walk.
      Where a head's ``[k | v]`` pair is ONE lane tile (heads of 64:
      PR 36) the flat pool takes the ring and the dense form of its
      fold (``_fold_flat_dense``); at heads of 128 the column fold and
      its loop stay until a PR measures the switch in the cell that
      runs it (ROADMAP A12 (b)).

    ``latent``: a flat pool of one shared row a token (``H`` 1,
    ``2*dh`` the padded row): the flat page's rule; the whole group is
    one turn (``F = G``, a multiple of 8 pages where that many fit, so
    that a turn's tokens fill the scores' lanes), ``_ROWS_LATENT`` rows a
    grid step, the ring.

    Chosen from shapes alone; the tests read it to aim at the group
    boundaries."""
    import numpy as np
    kv_dtype = np.dtype(kv_dtype)
    if kv_dtype == np.int8:
        return None
    if flat or latent:
        if page_size % (32 // kv_dtype.itemsize) or (H * 2 * dh) % 128:
            return None
    elif kv_dtype.itemsize < 4 and H % 8:
        return None
    page_bytes = page_size * H * 2 * dh * kv_dtype.itemsize
    G = max(1, min(PP, _GROUP_BYTES // page_bytes))
    if latent:
        G -= G % 8 if G > 8 else 0
        return G, G, _ROWS_LATENT, _RING
    if flat and 2 * dh != 128:
        return G, 2 - G % 2, _ROWS, 1
    return G, G, _ROWS_RING, _RING


def _ring(geometry):
    """Whether the walk of this geometry folds whole groups out of the
    ring (its folds take the query zero-extended over the v lanes)."""
    return geometry is not None and geometry[3] > 1


def _scale_folds(dh):
    """Whether 1/sqrt(dh) is a power of two.  It then multiplies into
    q ahead of the page loop (``_scaled``): scaling by a power of two
    commutes with every rounding, so the scores are bit for bit those
    of the division the reference makes after its dot.  Any other dh
    keeps that division, per page."""
    import math
    return math.frexp(float(dh) ** -0.5)[0] == 0.5


def _scaled(q, dh):
    """A row's query in f32, carrying 1/sqrt(dh) where that is exact."""
    import jax.numpy as jnp
    q = q.astype(jnp.float32)
    return q * (float(dh) ** -0.5) if _scale_folds(dh) else q


def _fold(kv, sc, q, m, l, acc, k0, pos, dh, cdt):
    """Fold one page into a row's online softmax: the FlashAttention
    recurrence over pages.  ``kv`` (ps, H, 2*dh) is the page as the
    pool holds it (cdt, or int8 with ``sc`` its (2, ps, H) scale
    block), ``q`` (H, 2*dh) the row's ``_scaled`` query ZERO-EXTENDED
    over the v half of the lanes, ``m`` / ``l`` (H, 1) and ``acc``
    (H, 2*dh) the running max, denominator and weighted sum in f32,
    ``k0`` the page's first position (``kv`` may be several
    consecutive pages, ``ps`` then their tokens together).  Returns
    the three updated.

    The per-page grid's fold (the walk's is ``_fold_dense``).  One
    query row per head: both contractions are multiply-and-reduce on
    the VPU (Mosaic has no matmul form for a batch dim that is not
    leading).
    Products of cdt (or int8) values are exact in f32, so this matches
    an MXU dot with f32 accumulation up to summation order.  Scores
    keep heads on the sublanes — (ps, H, 1), the layout the lane
    reduction leaves them in — and the v sum runs over the page's full
    width, so nothing is relaid between the two contractions: the k
    half of ``acc`` gathers p·k, finite and never read."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    kv = kv.astype(f32)
    s = jnp.sum(kv * q[None], axis=-1, keepdims=True)   # (ps, H, 1)
    if sc is not None:
        # k scale multiplies the scores, v scale folds into the
        # softmax weights (the same fold points as _attend_rows);
        # plane 0 = k scales, plane 1 = v
        s = s * sc[0][:, :, None]
    if not _scale_folds(dh):
        s = s / jnp.sqrt(f32(dh))
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    s = jnp.where(k_pos <= pos, s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=0))          # (H, 1)
    p = jnp.exp(s - m_new[None])                        # (ps, H, 1)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=0)
    if sc is not None:
        p = p * sc[1][:, :, None]
    p = p.astype(cdt).astype(f32)
    acc = acc * alpha + jnp.sum(p * kv, axis=0)         # (H, 2*dh)
    return m_new, l, acc


def _fold_dense(kv, q, m, l, acc, k0, pos, dh, cdt):
    """``_fold`` for the walk, both contractions on the MXU and the
    scores in ONE tile: ``kv`` (n, H, 2*dh) a group's pages as the pool
    holds them, ``q`` (H, 2*dh) the row's ``_scaled`` query in the
    pool's dtype, zero-extended over the v half of the lanes; ``m`` /
    ``l`` (H, 1) and ``acc`` (H, 2*dh) as ``_fold`` carries them.

    The pages' rows, (token, head) by (k | v), are the MXU's weights
    as they lie in VMEM: ``q @ rows^T`` is a tile (H, n*H) whose
    column t*H + h' holds query head h against token t's key head h',
    and the scores are its block diagonal h' == h, heads on the
    sublanes, tokens along the lanes.  Mask (the diagonal and the
    position at once), max, exp, sums and the round of p run once on
    that tile; p is zero off the diagonal, which makes it the
    block-diagonal left operand of the second product: ``p @ rows`` is
    sum_t p[t, h] * kv[t, h, :], the v sum (and p.k on the k half,
    finite and never read).  H times the products the scores need,
    on a unit that one query row per head leaves idle otherwise; no
    lane reduction a token, no page cast to f32 on the VPU.

    Products of cdt values are exact in f32 and the MXU accumulates
    in f32, so this matches ``_fold`` up to the order of the sums; a
    32-bit pool multiplies at the highest precision."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    n, H, W = kv.shape
    rows = kv.reshape(n * H, W)
    prec = jax.lax.Precision.HIGHEST if kv.dtype.itemsize == 4 else None
    s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32, precision=prec)
    if not _scale_folds(dh):
        s = s / jnp.sqrt(f32(dh))
    head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # column t*H + h': exact in f32 for any H (no vector integer divide)
    tok = ((col.astype(f32) + 0.5) * (1.0 / H)).astype(jnp.int32)
    live = (col - tok * H == head) & (k0 + tok <= pos)
    s = jnp.where(live, s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))   # (H, 1)
    p = jnp.exp(s - m_new)                                      # (H, n*H)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(cdt), rows, (((1,), (0,)), ((), ())),
                             preferred_element_type=f32, precision=prec)
    return m_new, l, acc * alpha + pv


def _fold_flat(kv, q, m, l, acc, k0, pos, dh, cdt):
    """``_fold`` for a grouped-query page in the flat layout: ``kv``
    (n, Hkv*2*dh) holds n tokens, each key/value head's k then v on the
    lanes; ``q`` (Hq, dh) the row's ``_scaled`` queries, query head i
    reading key/value head ``i // (Hq / Hkv)``; ``m`` / ``l`` / ``acc``
    tuples of one (1, 1), (1, 1) and (1, dh) float32 per query head.
    Tokens lie on the sublanes, so a head's scores are an (n, 1)
    column, its k and v whole (n, dh) lane tiles cut out once and read
    by each of the heads that share them.  The same recurrence, the
    same roundings, the same order of operations as ``_fold``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    kv = kv.astype(f32)
    Hq = len(m)
    rep = Hq // (kv.shape[1] // (2 * dh))
    live = k0 + jax.lax.broadcasted_iota(
        jnp.int32, (kv.shape[0], 1), 0) <= pos
    m2, l2, acc2 = [], [], []
    for i in range(Hq):
        c0 = (i // rep) * 2 * dh
        k, v = kv[:, c0:c0 + dh], kv[:, c0 + dh:c0 + 2 * dh]
        s = jnp.sum(k * q[i:i + 1, :], axis=-1, keepdims=True)   # (n, 1)
        if not _scale_folds(dh):
            s = s / jnp.sqrt(f32(dh))
        s = jnp.where(live, s, -1e30)
        m_new = jnp.maximum(m[i], jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m[i] - m_new)
        l2.append(l[i] * alpha + jnp.sum(p, axis=0, keepdims=True))
        p = p.astype(cdt).astype(f32)
        acc2.append(acc[i] * alpha
                    + jnp.sum(p * v, axis=0, keepdims=True))     # (1, dh)
        m2.append(m_new)
    return tuple(m2), tuple(l2), tuple(acc2)


def _fold_flat_dense(kv, q, m, l, acc, k0, pos, dh, cdt):
    """``_fold_dense`` for a grouped-query page in the flat layout whose
    heads' ``[k | v]`` pairs are whole lane tiles: ``kv`` (n, Hkv*2*dh)
    a group's pages as the pool holds them, ``q`` (Hq, 2*dh) the row's
    ``_scaled`` queries in the pool's dtype, zero-extended over the v
    half; ``m`` / ``l`` (Hq, 1) and ``acc`` (Hq, 2*dh) float32.

    A key/value head's pair ``kv[:, j]`` (n, 2*dh) is the MXU's weights
    as it lies in VMEM, cut out on tile boundaries: EVERY query head
    against it is one product (Hq, n), of which the rows of the heads
    that share pair j are kept; the scores are one tile, query heads on
    the sublanes, tokens along the lanes, and mask, max, exp, sums and
    the round of p run once on it; p, zeroed outside group j, times the
    pair is that group's v sum (and p.k on the k half, finite and never
    read).  Hkv times the products the scores need, on a unit that a
    handful of query rows leaves idle otherwise; no lane reduction a
    token and head, no slice inside a tile, no page cast to f32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    n, W = kv.shape
    Hq = q.shape[0]
    Hkv = W // (2 * dh)
    prec = jax.lax.Precision.HIGHEST if kv.dtype.itemsize == 4 else None
    group = jax.lax.broadcasted_iota(jnp.int32, (Hq, 1), 0) // (Hq // Hkv)
    pairs = [kv[:, j * 2 * dh:(j + 1) * 2 * dh] for j in range(Hkv)]
    s = jnp.zeros((Hq, n), f32)
    for j, pair in enumerate(pairs):
        s = jnp.where(group == j, jax.lax.dot_general(
            q, pair, (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=prec), s)
    if not _scale_folds(dh):
        s = s / jnp.sqrt(f32(dh))
    tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k0 + tok <= pos, s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))   # (Hq, 1)
    p = jnp.exp(s - m_new)                                      # (Hq, n)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    p = p.astype(cdt)
    pv = jnp.zeros(acc.shape, f32)
    for j, pair in enumerate(pairs):
        pv = pv + jax.lax.dot_general(
            jnp.where(group == j, p, 0), pair, (((1,), (0,)), ((), ())),
            preferred_element_type=f32, precision=prec)
    return m_new, l, acc * alpha + pv


def _fold_latent(kv, q, m, l, acc, k0, pos, dh, cdt, *, rank, scale):
    """``_fold`` for a latent page: ``kv`` (n, W) holds n tokens' rows
    ``[c_kv | k_pe | 0]``, ``q`` (H, W) the row's absorbed queries
    ``[q_lat_h | q_pe_h | 0]`` in the pool's dtype; ``m`` / ``l`` (H, 1)
    and ``acc`` (H, rank) float32.  Both contractions are plain MXU
    products, every head against the one shared row: ``q @ kv^T`` the
    scores (H, n), times ``scale`` in float32; ``p @ kv[:, :rank]`` the
    read-back.  ``dh`` is not used (the scale is the model's)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if kv.dtype.itemsize == 4 else None
    s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32,
                            precision=prec) * scale
    tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k0 + tok <= pos, s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))   # (H, 1)
    p = jnp.exp(s - m_new)                                      # (H, n)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(cdt), kv[:, :rank],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=f32, precision=prec)
    return m_new, l, acc * alpha + pv


def _walk_kernel(bt_ref, pos_ref, q_ref, kv_hbm, o_ref, *scratch,
                 page_size, dh, T, PP, G, F, R, K, flat, latent=None,
                 scale=None):
    """Grid over blocks of R rows; the pool stays in HBM.  A grid step's
    work is the flat sequence of its rows' live groups of G pages, the
    (row, group) ITEMS, bounded by each row's own position.

    The ring (``K`` > 1: ``_fold_dense``; ``latent`` pools, ``latent``
    the rank and ``scale`` the softmax's, ``_fold_latent``): item i
    lives in VMEM slot i mod K, a buffer of its own.  A cursor copies
    K - 1 items ahead of the fold; a trip of the loop takes K items,
    slot by static slot: the item's copies waited for, the item K - 1 on
    copied into the slot the item before left, the whole group folded at
    once (F = G) FROM A FRESH (m, l, acc), and that partial merged into
    its row's running ``state`` (m, l, acc: VMEM, R rows and a spare
    one for the slots a block's last trip has no item for); the rows
    are normalised and written when the grid step's items are in.

    One item a trip (``flat`` pools): per row a loop over its groups,
    group g+1 (or the next row's first) copied into one of two slots
    while group g is folded out of the other, two pages a turn with
    ``_fold_flat``, (m, l, acc) carried from group to group."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    ps = page_size
    H = q_ref.shape[1]
    row0 = pl.program_id(0) * R
    # rows of this grid step that exist (the last block may be short)
    n_rows = jnp.minimum(R, T - row0)
    if K == 1:
        buf, sem = scratch
        slots = [buf]
    else:
        # a buffer a slot: the compiler then sees that a copy into one
        # slot and a fold out of another touch nothing in common, and
        # runs the scalar code of the one under the other
        slots, sem, state = scratch[:K], scratch[K], scratch[K + 1:]

    def last_page(t):
        # the page that holds the row's own position: the walk's one
        # bound.  A dead row (pos 0, all-zero table row) walks the
        # scratch page and nothing else
        return jnp.minimum(pos_ref[t] // ps, PP - 1)

    def copy(page, slot, i):
        dst = buf.at[slot, i] if K == 1 else slots[slot].at[i]
        return pltpu.make_async_copy(kv_hbm.at[page], dst, sem.at[slot, i])

    def start(t, g, slot):
        # row t's group g: whole pages, HBM to VMEM slot ``slot``;
        # pages past the row's position are not copied
        last = last_page(t)
        for i in range(G):
            j = g * G + i

            @pl.when(j <= last)
            def _():
                copy(bt_ref[t * PP + j], slot, i).start()

    def row(r, slot):
        # ``slot`` holds (or is receiving) this row's first group
        t = row0 + r
        pos = pos_ref[t]
        last = last_page(t)
        n_groups = last // G + 1
        q = _scaled(q_ref[r], dh)                      # (H, dh)

        def group(g, carry):
            m, l, acc, slot = carry
            more = g + 1 < n_groups
            t_nxt = jnp.where(more, t, jnp.minimum(t + 1, T - 1))
            g_nxt = jnp.where(more, g + 1, 0)

            @pl.when(more | (r + 1 < n_rows))
            def _():
                start(t_nxt, g_nxt, 1 - slot)

            def turn(c, carry):
                # F pages a turn; those past the row's last were not
                # copied and hold older pages (or the zeros below):
                # finite, and masked by position like any tail
                for f in range(F):
                    @pl.when(g * G + c * F + f <= last)
                    def _():
                        # a wait goes by the copy's slot and size, not
                        # by its source
                        copy(0, slot, c * F + f).wait()
                kv = buf[slot, pl.ds(c * F, F)]
                return _fold_flat(kv.reshape((F * ps,) + kv.shape[2:]), q,
                                  *carry, (g * G + c * F) * ps, pos, dh,
                                  q_ref.dtype)

            n_pages = jnp.minimum(G, last + 1 - g * G)
            m, l, acc = jax.lax.fori_loop(0, (n_pages + F - 1) // F,
                                          turn, (m, l, acc))
            return m, l, acc, 1 - slot

        init = ((jnp.full((1, 1), -jnp.inf, f32),) * H,
                (jnp.zeros((1, 1), f32),) * H,
                (jnp.zeros((1, dh), f32),) * H, slot)
        _, l, acc, slot = jax.lax.fori_loop(0, n_groups, group, init)
        for i in range(H):
            o_ref[r, pl.ds(i, 1), :] = (acc[i] / l[i]).astype(o_ref.dtype)
        return slot

    # -- the ring's cursor: an item is (r, g, last): row r of the grid
    # step, its group g, and the row's last page, -1 (no page) for the
    # rows from n_rows on, which are past the last item.  Scalars by
    # ``lax``: a trip holds the cursor's step 2 K times over, and what
    # ``jnp`` wraps around a floor division is most of what lowering
    # the kernel would cost --
    lax = jax.lax
    i32 = jnp.int32

    def row_last(r):
        # (the step's last row stands in for the read past the rows)
        pos = pos_ref[lax.min(row0 + r, i32(T - 1))]
        return lax.select(r < n_rows,
                          lax.min(lax.div(pos, i32(ps)), i32(PP - 1)),
                          i32(-1))

    def after(item):
        # a row's next group, or the next row's first
        r, g, last = item
        more = (g + 1) * G <= last
        return (lax.select(more, r, r + 1), lax.select(more, g + 1, i32(0)),
                lax.select(more, last, row_last(r + 1)))

    def pages(item, each, under_chain=False):
        # ``each(i, j)`` for the pages of the item's group that its row's
        # position reaches: i in the group, j in the row's table.  A
        # loop; ``under_chain`` straight-line code instead, a guard a
        # page, which the compiler can run under the chain of the fold
        # beside it (G times the code to lower at every start-up, so
        # only where that pays: the copies a trip issues)
        _, g, last = item
        if under_chain:
            def page(i, carry):
                pl.when(g * G + i <= last)(lambda: each(i, g * G + i))
                return carry
            lax.fori_loop(0, G, page, 0, unroll=True)
            return

        def page(i, carry):
            each(i, g * G + i)
            return carry
        lax.fori_loop(
            i32(0), lax.max(lax.min(last + 1 - g * G, i32(G)), i32(0)),
            page, 0)

    def issue(item, slot, under_chain=False):
        t = lax.min(row0 + item[0], i32(T - 1))
        pages(item, lambda i, j: copy(bt_ref[t * PP + j], slot, i).start(),
              under_chain)

    def fold(item, slot):
        r, g, _ = item
        # (a wait goes by the copy's slot and size, not by its source)
        pages(item, lambda i, j: copy(0, slot, i).wait())
        # pages past the row's last were not copied and hold older
        # pages (or the zeros below): finite, and masked by position;
        # the items a block's last trip lacks fold what their slots hold
        # (past the last item: any row's query, the state's spare row)
        q = q_ref[lax.min(r, i32(R - 1))]
        dst = lax.select(r < n_rows, r, i32(R))
        if latent:
            chain = functools.partial(_fold_latent, rank=latent,
                                      scale=scale)
        else:
            # the MXU's operand: a power of two keeps q exact
            chain = _fold_flat_dense if flat else _fold_dense
            q = _scaled(q, dh).astype(kv_hbm.dtype)
        m_ref, l_ref, acc_ref = state
        kv = slots[slot][...]
        # from a fresh state: the chain waits for no other's result
        m_p, l_p, acc_p = chain(
            kv.reshape((G * ps,) + kv.shape[2:]), q,
            jnp.full((H, 1), -jnp.inf, f32), jnp.zeros((H, 1), f32),
            jnp.zeros(acc_ref.shape[1:], f32), g * G * ps,
            pos_ref[lax.min(row0 + r, i32(T - 1))], dh, q_ref.dtype)
        # the two-way softmax merge into the row's running state; a
        # row's first group finds none
        m_o = jnp.where(g == 0, -jnp.inf, m_ref[dst])
        l_o = jnp.where(g == 0, 0.0, l_ref[dst])
        acc_o = jnp.where(g == 0, 0.0, acc_ref[dst])
        m = jnp.maximum(m_o, m_p)
        a_o, a_p = jnp.exp(m_o - m), jnp.exp(m_p - m)
        m_ref[dst] = m
        l_ref[dst] = l_o * a_o + l_p * a_p
        acc_ref[dst] = acc_o * a_o + acc_p * a_p

    def trip(_, cursors):
        # K items, slot by slot.  ``head`` is the item to fold,
        # ``tail`` the one K - 1 on, whose copies go where the item
        # before ``head`` was
        head, tail = cursors
        for slot in range(K):
            issue(tail, (slot - 1) % K, under_chain=True)
            fold(head, slot)
            head, tail = after(head), after(tail)
        return head, tail

    if F > 1:
        @pl.when(pl.program_id(0) == 0)
        def _():
            # what a turn may fold without having copied it must not
            # be whatever VMEM held before this call
            for b in slots:
                b[...] = jnp.zeros_like(b)

    start(row0, 0, 0)
    if K == 1:
        jax.lax.fori_loop(0, n_rows, row, 0)
        return
    n_items = lax.fori_loop(
        0, n_rows, lambda r, n: n + lax.div(row_last(r), i32(G)) + 1, i32(0))
    head = (i32(0), i32(0), row_last(i32(0)))
    tail = after(head)
    for slot in range(1, K - 1):
        issue(tail, slot)
        tail = after(tail)
    lax.fori_loop(0, lax.div(n_items + i32(K - 1), i32(K)), trip,
                  (head, tail))
    m_ref, l_ref, acc_ref = state
    acc = acc_ref[pl.ds(0, R)]
    o_ref[...] = ((acc if latent else acc[:, :, dh:])
                  / l_ref[pl.ds(0, R)]).astype(o_ref.dtype)


def _page_kernel(bt_ref, pos_ref, q_ref, kv_ref, *rest, page_size, dh,
                 int8, flat):
    """Grid (T, PP), the page walk innermost: the BlockSpec index map
    streams page ``bt[t, j]`` per grid step and the online-softmax
    state lives in VMEM scratch across a row's steps.  Only for the
    pools ``walk_geometry`` turns away."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if int8:
        s_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        s_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    j = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a page whose first slot is past the row's position holds nothing
    # the row may attend to (its copy has been paid all the same)
    @pl.when(j * page_size <= pos)
    def _page():
        if flat:
            # the state's rows, one query head each, as _fold_flat
            # carries them
            rows = range(q_ref.shape[1])
            m, l, acc = _fold_flat(
                kv_ref[0], _scaled(q_ref[0], dh),
                tuple(m_ref[pl.ds(i, 1), :] for i in rows),
                tuple(l_ref[pl.ds(i, 1), :] for i in rows),
                tuple(acc_ref[pl.ds(i, 1), :] for i in rows),
                j * page_size, pos, dh, q_ref.dtype)
            for i in rows:
                m_ref[pl.ds(i, 1), :] = m[i]
                l_ref[pl.ds(i, 1), :] = l[i]
                acc_ref[pl.ds(i, 1), :] = acc[i]
        else:
            m_ref[...], l_ref[...], acc_ref[...] = _fold(
                kv_ref[0], s_ref[0] if int8 else None,
                _scaled(q_ref[0], dh), m_ref[...], l_ref[...],
                acc_ref[...], j * page_size, pos, dh, q_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _out():
        acc = acc_ref[...] if flat else acc_ref[:, dh:]
        o_ref[0] = (acc / l_ref[...]).astype(o_ref.dtype)


# bounded cache of built pallas_call closures, keyed on every
# shape/dtype the call specializes on (jit would re-trace through a
# fresh closure each step otherwise — the gpt.py cache idiom)
_call_cache = {}
_CALL_CACHE_MAX = 32


def _build(T, H, dh, PP, page_size, num_pages, kv_dtype, q_dtype,
           int8, interpret, Hkv=None, latent=None, scale=None):
    """The ``pallas_call`` for one geometry.  ``Hkv`` given: a flat
    grouped-query pool ``(pages, page_size, Hkv*2*dh)`` under ``H``
    query heads; None: ``(pages, page_size, H, 2*dh)``.  ``latent``
    (the rank; ``Hkv`` 1, ``2*dh`` the padded row): queries ``(T, H,
    2*dh)`` against the shared row, out ``(T, H, rank)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    key = (T, H, dh, PP, page_size, num_pages, str(kv_dtype),
           str(q_dtype), int8, interpret, Hkv, latent, scale)
    fn = _call_cache.get(key)
    if fn is not None:
        return fn

    flat = Hkv is not None
    # a page as the pool holds it, and a row's queries: zero-extended
    # over the v lanes for the (H, 2*dh) fold, bare for the flat one
    page = (page_size, Hkv * 2 * dh) if flat else (page_size, H, 2 * dh)
    ow = latent or dh
    zeros = (0,) * len(page)
    geometry = walk_geometry(Hkv if flat else H, dh, page_size, PP,
                             kv_dtype, flat=flat, latent=bool(latent))
    qw = 2 * dh if latent or not flat or _ring(geometry) else dh
    if latent and geometry is None:
        raise ValueError(
            "paged_attention: a latent pool is folded by the page walk "
            "alone, which cannot cut %d-token %s pages of %d lanes out of "
            "the pool (whole tiles: 16 tokens of 16 bits or 8 of 32, "
            "lanes a multiple of 128)"
            % (page_size, kv_dtype, 2 * dh))
    if geometry is not None:
        G, F, R, K = geometry
        R = min(R, T)
        grid = (-(-T // R),)
        in_specs = [
            pl.BlockSpec((R, H, qw), lambda b, bt, pos: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        out_specs = pl.BlockSpec((R, H, ow),
                                 lambda b, bt, pos: (b, 0, 0))
        if K == 1:
            scratch = [pltpu.VMEM((2, G) + page, kv_dtype),
                       pltpu.SemaphoreType.DMA((2, G))]
        else:
            # the ring's slots, a buffer each; the rows' running
            # (m, l, acc) beside them
            scratch = [pltpu.VMEM((G,) + page, kv_dtype)] * K + [
                pltpu.SemaphoreType.DMA((K, G)),
                pltpu.VMEM((R + 1, H, 1), jnp.float32),
                pltpu.VMEM((R + 1, H, 1), jnp.float32),
                pltpu.VMEM((R + 1, H, latent or 2 * dh), jnp.float32)]
        body = functools.partial(_walk_kernel, page_size=page_size,
                                 dh=dh, T=T, PP=PP, G=G, F=F, R=R, K=K,
                                 flat=flat, latent=latent, scale=scale)
    else:
        grid = (T, PP)
        in_specs = [
            pl.BlockSpec((1, H, qw), lambda t, j, bt, pos: (t, 0, 0)),
            pl.BlockSpec((1,) + page,
                         lambda t, j, bt, pos: (bt[t * PP + j],) + zeros),
        ]
        if int8:
            # scale block: (2, ps, H) — two (ps, heads) planes indexed
            # by the SAME page map
            in_specs.append(pl.BlockSpec(
                (1, 2, page_size, H),
                lambda t, j, bt, pos: (bt[t * PP + j], 0, 0, 0)))
        out_specs = pl.BlockSpec((1, H, dh),
                                 lambda t, j, bt, pos: (t, 0, 0))
        scratch = [pltpu.VMEM((H, 1), jnp.float32),
                   pltpu.VMEM((H, 1), jnp.float32),
                   pltpu.VMEM((H, qw), jnp.float32)]
        body = functools.partial(_page_kernel, page_size=page_size,
                                 dh=dh, int8=int8, flat=flat)
    fn = pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((T, H, ow), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        interpret=interpret,
    )
    if len(_call_cache) >= _CALL_CACHE_MAX:
        _call_cache.pop(next(iter(_call_cache)))
    _call_cache[key] = fn
    return fn


def paged_attention(q, pool_kv, pool_s, block_tables, row_pos, *,
                    page_size, interpret=None, mesh=None, latent=None,
                    scale=None):
    """Single-token attention over paged K/V via block-table walk.

    Parameters
    ----------
    q : (T, H, dh) compute-dtype queries, one per decode row.
    pool_kv : (num_pages, page_size, H, 2*dh) page pool — the
        ``PagedKVCache`` layout (k and v halves fused on the last
        axis); cfg dtype, or int8 when ``pool_s`` is given.  Or the
        flat grouped-query layout (num_pages, page_size, Hkv*2*dh),
        Hkv dividing H: query head i reads key/value head
        ``i // (H / Hkv)``.
    pool_s : (num_pages, 2, page_size, H) f32 dequant scales for the
        int8-KV pool (``models/gpt.py _kv_quantize`` values in the
        round-22 tile-shaped plane layout — plane 0 k, plane 1 v),
        or None.
    block_tables : (T, PP) int32 per-ROW page ids; entry j covers
        positions [j*page_size, (j+1)*page_size).  Unused tail entries
        should point at the scratch page 0.
    row_pos : (T,) int32 per-row absolute positions — each row attends
        to positions <= its own (the continuous-batching mask).
    latent : ``(rank, rope)`` of a LATENT pool ``(num_pages, page_size,
        W)``, ``W = latent_width(rank, rope)``: ``q`` is then ``(T, H,
        rank + rope)``, each head's absorbed query against the one row
        a token that all heads share, ``scale`` the softmax scale (the
        model's; required), and the result ``(T, H, rank)``: ``p``
        against the rows' first ``rank`` lanes.
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
    Hkv = None
    if latent:
        rank, rope = latent
        W = pool_kv.shape[-1]
        if pool_kv.ndim != 3 or W % 2 or W < rank + rope \
                or dh != rank + rope or scale is None or int8 \
                or mesh is not None:
            raise ValueError(
                "paged_attention: a latent pool is (pages, page_size, W "
                ">= rank + rope) under (T, H, rank + rope) queries with "
                "the model's scale=, no int8 and no mesh; got %r, q %r, "
                "latent %r" % (tuple(pool_kv.shape), tuple(q.shape),
                               latent))
        Hkv, dh = 1, W // 2
        q = jnp.pad(q, ((0, 0), (0, 0), (0, W - rank - rope)))
    elif pool_kv.ndim == 3:
        Hkv = pool_kv.shape[2] // (2 * dh)
        if Hkv * 2 * dh != pool_kv.shape[2] or H % max(Hkv, 1):
            raise ValueError(
                "paged_attention: a flat pool's pages are (page_size, "
                "Hkv*2*dh) with Hkv dividing the %d query heads; got "
                "%r at dh=%d" % (H, tuple(pool_kv.shape), dh))
        if int8 or mesh is not None:
            raise ValueError("paged_attention: a flat (grouped-query) "
                             "pool has no int8 scale planes and no "
                             "mesh lowering")
    elif pool_kv.shape[2] != H:
        raise ValueError("paged_attention: pool of %d heads under %d "
                         "query heads (grouped-query pools are flat: "
                         "(pages, page_size, Hkv*2*dh))"
                         % (pool_kv.shape[2], H))
    # q zero-extended over the v half of a page's lanes (the kernel's
    # one full-width product then contracts q with k alone); the flat
    # column fold cuts k out of the page and takes q as it is (the
    # latent query was padded to the row above)
    bare = latent or (Hkv and not _ring(walk_geometry(
        Hkv, dh, page_size, PP, pool_kv.dtype, flat=True)))
    args = [block_tables.reshape(-1).astype(jnp.int32),
            row_pos.astype(jnp.int32),
            q if bare else jnp.concatenate([q, jnp.zeros_like(q)],
                                           axis=-1), pool_kv]
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
                    pool_kv.dtype, q.dtype, int8, interp, Hkv,
                    latent and latent[0], scale and float(scale))
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

    with jax.named_scope("paged_attn"):
        return run_kernel(call, *args, interpret=interpret)


def paged_attention_reference(q, pool_kv, pool_s, block_tables,
                              row_pos, *, page_size, latent=None,
                              scale=None):
    """The jnp path: block-table gather + ``_attend_rows``.  This IS
    the serving engine's ``kernel="xla"`` attention (the step program
    calls it directly — one copy, so the engine path and the tests'
    oracle cannot drift), and the reference the Pallas kernel is
    pinned against at a few-ulp f32 tolerance (the online-softmax
    normalization-order caveat in the module docstring)."""
    import jax
    import jax.numpy as jnp

    from ..models.gpt import _attend_rows

    T, H, dh = q.shape
    PP = block_tables.shape[1]
    L = PP * page_size
    if latent:
        # every head against the one gathered row a token; the same
        # roundings as ``_fold_latent`` (operands in the pool's dtype,
        # float32 scores times the scale, p rounded before its product)
        rank, rope = latent
        prec = jax.lax.Precision.HIGHEST \
            if pool_kv.dtype.itemsize == 4 else None
        with jax.named_scope("paged_attn"):
            with jax.named_scope("gather"):
                rows = pool_kv[block_tables].reshape(T, L, -1)
            with jax.named_scope("attend"):
                s = jnp.einsum("thw,tlw->thl", q, rows[..., :rank + rope],
                               preferred_element_type=jnp.float32,
                               precision=prec) * scale
                live = jnp.arange(L)[None, None, :] \
                    <= row_pos[:, None, None]
                p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
                return jnp.einsum("thl,tlr->thr", p.astype(q.dtype),
                                  rows[..., :rank],
                                  preferred_element_type=jnp.float32,
                                  precision=prec)
    with jax.named_scope("paged_attn"):
        with jax.named_scope("gather"):
            if pool_kv.ndim == 3:
                # flat grouped-query pool: each key/value head's view
                # repeated for the query heads that share it
                Hkv = pool_kv.shape[2] // (2 * dh)
                ckv = pool_kv[block_tables].reshape(T, L, Hkv, 2 * dh)
                ckv = jnp.repeat(ckv.transpose(0, 2, 1, 3), H // Hkv,
                                 axis=1).reshape(T * H, L, 2 * dh)
            else:
                ckv = pool_kv[block_tables].transpose(0, 3, 1, 2, 4) \
                    .reshape(T * H, L, 2 * dh)
            cs = None
            if pool_s is not None:
                # retiled plane layout (num_pages, 2, ps, H): gather
                # gives (T, PP, 2, ps, H) — reorder back to
                # _attend_rows' per-token (.., L, 2) scale pairs
                cs = pool_s[block_tables].transpose(0, 4, 1, 3, 2) \
                    .reshape(T * H, L, 2)
        with jax.named_scope("attend"):
            pos_r = jnp.repeat(row_pos, H)
            out = _attend_rows(q.reshape(T * H, dh), ckv, cs, pos_r, dh)
            return out.reshape(T, H, dh)
