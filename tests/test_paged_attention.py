"""Fused Pallas paged-attention kernel (kernels/paged_attention.py):
interpreter-mode exactness pins against the ``_attend_rows`` reference
across page-boundary cases, int8-KV agreement, and the ngram-drafter
parity pin (serving/drafters.py host twin vs models/gpt.py _draft_ngram).

FAST tier deliberately (no slow marker): the kernel is the serving
step's inner loop, and these pins are the tier-1 acceptance oracle the
round-11 issue names.  Shapes are tiny — interpreter-mode pallas on
CPU compiles the grid as a loop, so each case costs milliseconds.

Tolerance note (the kernel module docstring, same caveat class as the
paged-int8 note in tests/test_serving.py): online-softmax normalizes
once at the end where the reference normalizes the probabilities
before the V dot, so f32 outputs agree to 1–2 ulps, not bitwise; the
BIT-exact pin the serving stack guarantees is greedy TOKEN identity of
the pallas-kernel engine vs ``generate`` (tests/test_serving.py).
"""
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (conftest device setup)

# a few f32 ulps at unit scale; also the documented int8-path bound
# (the dequant scales enter both sides identically, so the same
# normalization-order ulps dominate there too)
_RTOL, _ATOL = 3e-6, 3e-6


def _mk(T=6, H=2, dh=8, ps=4, PP=3, NP=11, int8=False, seed=0,
        dtype="float32", latent=None, flat=None):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(T, H, dh), jnp.dtype(dtype))
    if latent:
        # one shared row a token, [c_kv | k_pe] padded with zero lanes
        # to whole tiles (serving/paged_kv.py latent_width); dh is
        # rank + rope
        from mxnet_tpu.serving.paged_kv import latent_width
        rows = np.zeros((NP, ps, latent_width(*latent)), np.float32)
        rows[..., :dh] = rng.randn(NP, ps, dh)
        pool, scale = jnp.asarray(rows, jnp.dtype(dtype)), None
    elif flat:
        # a grouped-query pool: ``flat`` key/value heads' [k | v] pairs
        # side by side on a flat page's lanes
        pool = jnp.asarray(rng.randn(NP, ps, flat * 2 * dh),
                           jnp.dtype(dtype))
        scale = None
    elif int8:
        pool = jnp.asarray(rng.randint(-127, 128, (NP, ps, H, 2 * dh)),
                           jnp.int8)
        # round-22 tile-shaped scale layout: (NP, 2, ps, H) planes
        # (k plane 0, v plane 1) — see serving/paged_kv.py
        scale = jnp.asarray(
            np.abs(rng.randn(NP, 2, ps, H)) * 0.02 + 1e-4, jnp.float32)
    else:
        pool = jnp.asarray(rng.randn(NP, ps, H, 2 * dh),
                           jnp.dtype(dtype))
        scale = None
    bt = jnp.asarray(rng.randint(1, NP, (T, PP)), jnp.int32)
    return q, pool, scale, bt


def _both(q, pool, scale, bt, pos, ps, latent=None, n_live=None):
    import jax.numpy as jnp
    from mxnet_tpu.kernels import paged_attention as PA
    pos = jnp.asarray(pos, jnp.int32)
    kw = dict(latent=latent, scale=0.17) if latent else {}
    out = PA.paged_attention(
        q, pool, scale, bt, pos, page_size=ps, interpret=True,
        n_live=None if n_live is None else jnp.int32(n_live), **kw)
    ref = PA.paged_attention_reference(q, pool, scale, bt, pos,
                                       page_size=ps, **kw)
    return np.asarray(out), np.asarray(ref)


def test_kernel_page_boundaries_f32():
    """The page-walk masking pin: positions exactly AT page_size
    multiples (the last valid slot is a page's final slot / a page's
    first slot), ragged last pages, and full tables — every row in one
    call, each against the gathered jnp reference."""
    ps, PP = 4, 3
    q, pool, scale, bt = _mk(T=6, ps=ps, PP=PP)
    # pos semantics: row attends to slots <= pos.  Cases: pos=3 (page
    # 0 exactly full), pos=4 (first slot of page 1), pos=7 (page 1
    # exactly full), pos=8 (first slot of page 2), pos=5 (ragged mid
    # page), pos=11 (every slot of every page)
    pos = [3, 4, 7, 8, 5, 11]
    out, ref = _both(q, pool, scale, bt, pos, ps)
    np.testing.assert_allclose(out, ref, rtol=_RTOL, atol=_ATOL)


def test_kernel_single_token_rows():
    """pos=0 rows (a request's very first decode position): only slot
    0 of page 0 is live — softmax over one element must be exact, and
    the untouched later pages must contribute nothing."""
    ps = 4
    q, pool, scale, bt = _mk(T=3, ps=ps, PP=3)
    out, ref = _both(q, pool, scale, bt, [0, 0, 1], ps)
    np.testing.assert_allclose(out, ref, rtol=_RTOL, atol=_ATOL)
    # pos=0: the output IS v[page, slot 0] (softmax of one logit is
    # exactly 1.0) — pin it against the pool directly
    dh = q.shape[-1]
    v0 = np.asarray(pool)[np.asarray(bt)[0, 0], 0, :, dh:]
    np.testing.assert_allclose(out[0], v0.astype(np.float32),
                               rtol=1e-6)


def test_kernel_shared_and_repeated_pages():
    """Block tables may alias (shared-prefix reuse maps one page into
    many rows' tables) and tail entries point at the scratch page —
    the walk must read whatever the table says, masked by pos."""
    import jax.numpy as jnp
    ps, PP = 4, 3
    q, pool, scale, bt = _mk(T=4, ps=ps, PP=PP)
    bt = np.asarray(bt).copy()
    bt[1] = bt[0]                    # full aliasing (prefix reuse)
    bt[2, 1:] = 0                    # unallocated tail -> scratch page
    bt[3] = bt[3, 0]                 # one page repeated (legal table)
    bt = jnp.asarray(bt)
    out, ref = _both(q, pool, scale, bt, [9, 9, 2, 10], ps)
    np.testing.assert_allclose(out, ref, rtol=_RTOL, atol=_ATOL)


def test_kernel_int8_kv_agreement():
    """int8-KV pages (round-4 scale layout) dequantized INSIDE the
    walk: k scale on the scores, v scale folded into the weights —
    against the reference that folds them at the same points through
    the gathered view."""
    q, pool, scale, bt = _mk(T=5, int8=True)
    out, ref = _both(q, pool, scale, bt, [0, 3, 4, 8, 11], 4)
    np.testing.assert_allclose(out, ref, rtol=_RTOL, atol=_ATOL)


def test_kernel_bf16_compute():
    """bf16 compute dtype (the full-preset serving dtype): dots run in
    bf16 with f32 accumulation on both sides; outputs are f32 and the
    two paths stay within a couple of bf16-accumulation ulps."""
    q, pool, scale, bt = _mk(T=4, dtype="bfloat16", dh=16)
    out, ref = _both(q, pool, scale, bt, [2, 5, 7, 11], 4)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


def test_kernel_larger_head_geometry():
    """A second geometry (more heads, lane-width head dim, deeper
    tables) so the pins don't overfit one shape."""
    q, pool, scale, bt = _mk(T=4, H=4, dh=32, ps=8, PP=4, NP=17,
                             seed=3)
    out, ref = _both(q, pool, scale, bt, [7, 8, 15, 31], 8)
    np.testing.assert_allclose(out, ref, rtol=_RTOL, atol=_ATOL)


def test_kernel_rejects_bad_pool_geometry():
    import jax.numpy as jnp
    from mxnet_tpu.kernels import paged_attention as PA
    q, pool, scale, bt = _mk()
    with pytest.raises(ValueError):
        PA.paged_attention(q, pool, None, bt,
                           jnp.zeros(q.shape[0], jnp.int32),
                           page_size=8, interpret=True)  # pool is ps=4


def test_kernel_mesh_tp_parity():
    """Round 22: the shard_map lowering (``mesh=``) — each device
    walking its 1/tp heads slice of the heads-sharded pool — matches
    the single-device reference at the same page-boundary positions,
    f32 and int8 (the retiled scale planes shard their trailing heads
    axis), and with a dead tail (``n_live``, replicated to every
    device) zeros past it.  The lowering is the unit under test; the
    kernel body is pinned above."""
    from mxnet_tpu.kernels import paged_attention as PA
    from mxnet_tpu.parallel.mesh import serving_mesh
    import jax.numpy as jnp

    mesh = serving_mesh(2)
    pos = jnp.asarray([0, 3, 4, 8, 5, 11], jnp.int32)
    for int8, n in ((False, 6), (True, 6), (False, 4)):
        q, pool, scale, bt = _mk(T=6, int8=int8)
        out = PA.paged_attention(q, pool, scale, bt, pos, page_size=4,
                                 interpret=True, mesh=mesh,
                                 n_live=jnp.int32(n))
        ref = PA.paged_attention_reference(q, pool, scale, bt, pos,
                                           page_size=4)
        np.testing.assert_allclose(np.asarray(out)[:n],
                                   np.asarray(ref)[:n],
                                   rtol=_RTOL, atol=_ATOL)
        np.testing.assert_array_equal(np.asarray(out)[n:], 0.0)
        # heads really shard: 2 devices, half the heads each
        assert len(out.addressable_shards) == 2


# ---------------------------------------------------------------------------
# the walk: rows x groups of pages, bounded by each row's own position
# ---------------------------------------------------------------------------

def _dead_tail_case(name):
    """``_walk_case`` for ``dead_tail_<pool>_<where>``: a step whose
    rows from ``n_live`` on are dead (position 0 on the all-zero table
    row, as the serving engine packs them), ``n_live`` inside the last
    block of R rows, on a block's boundary, 0 or T, over a dense, flat
    or latent pool."""
    pool, where = name[len("dead_tail_"):].split("_", 1)
    mk, R = {
        "dense": (dict(T=38, H=2, dh=8, ps=4, PP=5, NP=23), 32),
        "flat": (dict(T=38, H=4, dh=64, ps=8, PP=5, NP=23, flat=2), 32),
        "latent": (dict(T=12, H=4, dh=80, ps=8, PP=5, NP=23,
                        latent=(64, 16)), 8)}[pool]
    T = mk["T"]
    n = {"inside_block": R + 3, "block_boundary": R, "none_live": 0,
         "all_live": T}[where]
    span = mk["PP"] * mk["ps"]
    return (dict(mk, n_live=n), 2, {r: 0 for r in range(n, T)},
            lambda G, F: [(7 * r + 3) % span if r < n else 0
                          for r in range(T)])


def _walk_case(name):
    """(kwargs of ``_mk``, ``n_live`` among them where the call is
    given one; pages a group or None, table rows to alter or None, the
    positions as a function of the walk's G and F) for one named case.
    Tiny pages in groups of two put several group boundaries inside a
    five-page table; ``None`` keeps the module's own group size."""
    if name.startswith("dead_tail_"):
        return _dead_tail_case(name)
    two_pages = 2
    small = dict(T=6, H=2, dh=8, ps=4, PP=5, NP=23)
    return {
        # contexts of exactly G*ps and G*ps + 1 tokens, then 2G*ps and
        # 2G*ps + 1: a row ends ON a group's last slot / starts a group
        "group_boundary": (small, two_pages, None,
                           lambda G, F: [G * 4 - 1, G * 4, 2 * G * 4 - 1,
                                      2 * G * 4, 4 * G + 1, 19]),
        # the module's own group size (8 pages of 64 KiB), 20-page rows
        "group_boundary_real_pages": (
            dict(T=4, H=8, dh=64, ps=16, PP=20, NP=47), None, None,
            lambda G, F: [G * 16 - 1, G * 16, 2 * G * 16, 319]),
        # a request's first position, and dead rows: position 0 on the
        # all-zero table row (what the engine points dead rows at)
        "pos0_and_dead_rows": (small, two_pages,
                               {1: 0, 3: 0, 5: 0},
                               lambda G, F: [0, 0, 9, 0, 1, 0]),
        # one prefill chunk: consecutive positions of ONE slot's pages
        "prefill_chunk_shares_pages": (small, two_pages,
                                       {1: "row0", 2: "row0", 3: "row0",
                                        4: "row0"},
                                       lambda G, F: [6, 7, 8, 9, 10, 3]),
        # ragged rows over tables whose tails point at the scratch page
        "ragged_scratch_tails": (small, two_pages,
                                 {0: "tail1", 2: "tail2", 4: "tail3"},
                                 lambda G, F: [3, 17, 7, 12, 9, 19]),
        # more rows than one grid step walks, the last block short
        "short_last_row_block": (dict(small, T=35), two_pages, None,
                                 lambda G, F: [i % 20 for i in range(35)]),
        # the edges of the fold's own tile of scores, one turn of F
        # pages wide (the whole group under the dense fold): a row
        # that ends on the tile's last token and one that starts the
        # next tile (a turn with ONE live token), first group and past
        "turn_boundary": (dict(small, PP=6), two_pages, None,
                          lambda G, F: [F * 4 - 1, F * 4, 2 * F * 4 - 1,
                                        2 * F * 4, G * 4 + F * 4 - 1,
                                        G * 4 + F * 4]),
        # groups of four pages: rows whose last group holds one live
        # token, one live page or all but one leave the rest of the
        # folded tile dead (pages never copied, or an earlier row's)
        "dead_group_tail": (dict(small, PP=8, NP=37), 4, None,
                            lambda G, F: [0, 3, G * 4, G * 4 + 3,
                                          2 * G * 4 - 5, 2 * G * 4 - 1]),
        # the benchmark cell's page (16 heads of 64, 16 tokens, bf16)
        # at the module's own group size, rows ending on every edge
        "cell_page_bf16": (
            dict(T=8, H=16, dh=64, ps=16, PP=20, NP=47,
                 dtype="bfloat16"), None, None,
            lambda G, F: [0, F * 16 - 1, F * 16, G * 16 - 1, G * 16,
                          G * 16 + F * 16, 2 * G * 16 + 1, 319]),
        # -- the trips of the ring's loop: a grid step's (row, group)
        # items taken four a trip, the slots the block's last trip has
        # no item for folded into a spare row of the state (groups a
        # row in the comments) --
        # seven items: one whole trip, three items and an empty slot
        "odd_item_count": (small, two_pages, None,          # 1 2 1 1 1 1
                           lambda G, F: [0, 9, 3, 5, 7, 2]),
        # ten: trips that take two groups of ONE row beside groups of
        # two others, and one that ends a row and starts the next
        "turns_within_and_across_rows": (
            small, two_pages, None,                         # 2 1 1 3 1 2
            lambda G, F: [12, 3, 5, 19, 2, 9]),
        # twelve, three whole trips: a row of four groups, begun in
        # a trip's second slot, between rows of one
        "four_groups_between_ones": (
            dict(small, PP=8, NP=37), two_pages, None,      # 1 4 1 1 1 4
            lambda G, F: [3, 31, 2, 0, 7, 30]),
        # dead rows in a trip's second and fourth slot and as the
        # block's last item
        "dead_row_second_item": (small, two_pages,          # 1 1 2 1 1 1
                                 {1: 0, 3: 0, 5: 0},
                                 lambda G, F: [5, 0, 9, 0, 6, 0]),
        # one row: three groups, fewer than a trip; and one group,
        # fewer than the cursor runs ahead
        "one_row": (dict(small, T=1), two_pages, None,
                    lambda G, F: [19]),
        "one_item": (dict(small, T=1), two_pages, None,
                     lambda G, F: [2]),
        # the last row block short, with an odd count of items (58 in
        # the first block of 32 rows, 9 in the last six)
        "short_last_block_odd_items": (
            dict(small, T=38), two_pages, None,
            lambda G, F: [(7 * i + 3) % 20 for i in range(38)]),
        # the cell's page at the module's own G: 15 items, dead rows
        # in neighbouring slots                     # 3 1 1 2 1 3 2 1 1
        "cell_page_bf16_odd_items": (
            dict(T=9, H=16, dh=64, ps=16, PP=20, NP=47,
                 dtype="bfloat16"), None, {1: 0, 2: 0, 8: 0},
            lambda G, F: [319, 0, 0, G * 16 + 2, 5, 2 * G * 16 + 1,
                          G * 16, 100, 0]),
        # a latent pool at the module's own G (128 pages of 4 KiB):
        # seven items                                       # 1 1 2 2 1
        "latent_bf16_own_group": (
            dict(T=5, H=4, dh=80, ps=16, PP=150, NP=311,
                 dtype="bfloat16", latent=(64, 16)), None, None,
            lambda G, F: [0, G * 16 - 1, G * 16, 150 * 16 - 1, 2000]),
        "f32_h6": (dict(small, H=6), two_pages, None,
                   lambda G, F: [0, 5, 8, 9, 16, 19]),
        "f32_h3": (dict(small, H=3), two_pages, None,
                   lambda G, F: [0, 5, 8, 9, 16, 19]),
        "bf16_h8": (dict(small, H=8, dh=16, dtype="bfloat16"),
                    two_pages, None,
                    lambda G, F: [0, 5, 8, 9, 16, 19]),
        # what walk_geometry turns away runs the per-page grid
        "bf16_h6_per_page": (dict(small, H=6, dh=16, dtype="bfloat16"),
                             None, None,
                             lambda G, F: [0, 5, 8, 9, 16, 19]),
        "bf16_h3_per_page": (dict(small, H=3, dh=16, dtype="bfloat16"),
                             None, None,
                             lambda G, F: [0, 5, 8, 9, 16, 19]),
        "int8_per_page": (dict(small, int8=True), None, None,
                          lambda G, F: [0, 5, 8, 9, 16, 19]),
        "int8_h6_per_page": (dict(small, H=6, int8=True), None, None,
                             lambda G, F: [0, 5, 8, 9, 16, 19]),
        # the per-page grid writes the dead tail zeros too
        "bf16_h6_dead_tail_per_page": (
            dict(small, H=6, dh=16, dtype="bfloat16", n_live=4), None,
            {4: 0, 5: 0}, lambda G, F: [0, 5, 8, 9, 0, 0]),
    }[name]


@pytest.mark.parametrize("name", [
    "group_boundary", "group_boundary_real_pages", "pos0_and_dead_rows",
    "prefill_chunk_shares_pages", "ragged_scratch_tails",
    "short_last_row_block", "turn_boundary", "dead_group_tail",
    "cell_page_bf16", "odd_item_count", "turns_within_and_across_rows",
    "four_groups_between_ones", "dead_row_second_item", "one_row",
    "one_item",
    "short_last_block_odd_items", "cell_page_bf16_odd_items",
    "latent_bf16_own_group", "f32_h6", "f32_h3", "bf16_h8",
    "bf16_h6_per_page", "bf16_h3_per_page", "int8_per_page",
    "int8_h6_per_page", "bf16_h6_dead_tail_per_page"] + [
    "dead_tail_%s_%s" % (pool, where)
    for pool in ("dense", "flat", "latent")
    for where in ("inside_block", "block_boundary", "none_live",
                  "all_live")])
def test_walk_cases(name, monkeypatch):
    """The walk against the gathered reference where its loops turn:
    group boundaries, rows of one page, rows sharing pages, short row
    blocks, every pool kind — and the per-page grid on the pools the
    walk cannot cut pages out of.  Where the call is given ``n_live``,
    the rows before it match the reference and the rows from it on
    (dead) are exactly zero."""
    import jax.numpy as jnp
    from mxnet_tpu.kernels import paged_attention as PA

    mk, group_pages, rows, positions = _walk_case(name)
    mk = dict(mk)
    n_live = mk.pop("n_live", None)
    q, pool, scale, bt = _mk(**mk)
    ps, PP = mk["ps"], mk["PP"]
    if group_pages is not None:
        monkeypatch.setattr(PA, "_GROUP_BYTES",
                            group_pages * pool[0].nbytes)
    # built calls are cached by shape, not by the group size
    monkeypatch.setattr(PA, "_call_cache", {})
    latent = mk.get("latent")
    if latent:
        geometry = PA.walk_geometry(1, pool.shape[-1] // 2, ps, PP,
                                    pool.dtype, flat=True, latent=True)
    elif mk.get("flat"):
        geometry = PA.walk_geometry(mk["flat"], mk["dh"], ps, PP,
                                    pool.dtype, flat=True)
    else:
        geometry = PA.walk_geometry(mk["H"], mk["dh"], ps, PP, pool.dtype)
    assert (geometry is None) == name.endswith("_per_page")
    G, F = geometry[:2] if geometry else (1, 1)
    if geometry:
        # every pool here is folded a whole group at once, out of a
        # ring of four slots
        assert F == G and geometry[3] == 4
    if group_pages is not None:
        assert G == group_pages  # several groups inside the table
    elif geometry:
        assert 1 < G < PP
    bt = np.asarray(bt).copy()
    for r, how in (rows or {}).items():
        if how == 0:
            bt[r] = 0
        elif how == "row0":
            bt[r] = bt[0]
        else:
            bt[r, int(how[4:]):] = 0
    pos = positions(G, F)
    assert len(pos) == mk["T"] and max(pos) < PP * ps
    out, ref = _both(q, pool, scale, jnp.asarray(bt), pos, ps, latent,
                     n_live)
    tol = dict(rtol=2e-2, atol=2e-2) if mk.get("dtype") == "bfloat16" \
        else dict(rtol=_RTOL, atol=_ATOL)
    n = mk["T"] if n_live is None else n_live
    np.testing.assert_allclose(out[:n], ref[:n], **tol)
    np.testing.assert_array_equal(out[n:], 0.0)


@pytest.mark.parametrize("PP,G,pos", [
    (5, 5, [0, 3, 4, 13]), (6, 6, [0, 3, 4, 13]), (9, 9, [0, 3, 4, 13]),
    # groups of two pages: 11 items go round the ring of four slots
    # nearly three times, every slot refilled under a row that ends
    # inside its group                                    # 1 3 1 4 1 1
    (9, 2, [0, 17, 4, 26, 7, 1]),
    # groups of three: 9 items, an odd count            # 2 1 3 1 1 1
    (9, 3, [13, 3, 35, 0, 9, 8]),
])
def test_walk_reads_no_page_past_the_position(PP, G, pos, monkeypatch):
    """Pages past a row's position are never copied: with NaN in every
    page a row must not see, the walk's output is finite and unchanged
    (the gather reference, which reads the whole window, is the one
    that would carry them through a 0 x NaN) — though the dense fold
    runs over the whole group, pages it did not copy among them, and
    the ring's four slots hold what earlier rows left there."""
    import jax.numpy as jnp
    from mxnet_tpu.kernels import paged_attention as PA
    ps, T = 4, len(pos)
    q, pool, scale, bt = _mk(T=T, ps=ps, PP=PP, NP=1 + T * PP)
    monkeypatch.setattr(PA, "_GROUP_BYTES",
                        min(G * pool[0].nbytes, PA._GROUP_BYTES))
    monkeypatch.setattr(PA, "_call_cache", {})
    # the whole group at once, out of a ring of four slots
    assert PA.walk_geometry(2, 8, ps, PP, pool.dtype) \
        == (G, G, PA._ROWS_RING, 4)
    bt = np.arange(1, 1 + T * PP, dtype=np.int32).reshape(T, PP)
    pos = np.asarray(pos, np.int32)
    clean = PA.paged_attention(q, pool, None, jnp.asarray(bt),
                               jnp.asarray(pos), page_size=ps,
                               interpret=True)
    dirty = np.asarray(pool).copy()
    for r in range(T):
        dirty[bt[r, pos[r] // ps + 1:]] = np.nan
    out = PA.paged_attention(q, jnp.asarray(dirty), None,
                             jnp.asarray(bt), jnp.asarray(pos),
                             page_size=ps, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


# ---------------------------------------------------------------------------
# drafter parity (serving/drafters.py host twin vs gpt._draft_ngram)
# ---------------------------------------------------------------------------

def test_ngram_draft_parity():
    """ONE drafting rule across the stack: the engine's host-side
    ``ngram_draft`` must propose exactly what ``generate_speculative``'s
    in-XLA ``_draft_ngram`` proposes for the same committed row — for
    matching, non-matching, short-row, and continuation-past-committed
    cases."""
    import jax.numpy as jnp
    from mxnet_tpu.models.gpt import _draft_ngram
    from mxnet_tpu.serving.drafters import ngram_draft

    rng = np.random.RandomState(0)
    cases = [
        np.array([5, 7, 9, 5, 7], np.int32),          # match, cont. inside
        np.array([1, 2, 3, 4, 5], np.int32),          # no match
        np.array([3, 3, 3, 3], np.int32),             # everything matches
        np.array([8, 1, 2, 8, 1, 2, 8, 1, 2], np.int32),  # loop
        np.array([4], np.int32),                      # shorter than g
        np.array([6, 6], np.int32),                   # exactly g
        rng.randint(0, 16, 24).astype(np.int32),      # random collisions
    ]
    for g in (1, 2, 3):
        for K in (1, 3, 5):
            for row in cases:
                n = row.size
                host = ngram_draft(row, K, g)
                # _draft_ngram wants a buffer with headroom past the
                # committed pointer (stale-draft slots) — pad with a
                # sentinel the committed mask must hide
                buf = np.concatenate(
                    [row, np.full(K + 2, 99, np.int32)])[None]
                if n <= g:
                    # the jnp drafter indexes buf[n-g:n] unconditionally;
                    # generate_speculative never calls it with fewer
                    # committed tokens than g+1 (prompt >= 1 + pending).
                    # The host twin defines the short-row fallback.
                    np.testing.assert_array_equal(
                        host, np.full(K, row[-1], np.int32))
                    continue
                ref = np.asarray(_draft_ngram(
                    jnp.asarray(buf), n, K, g))[0]
                np.testing.assert_array_equal(host, ref,
                                              err_msg="g=%d K=%d row=%s"
                                              % (g, K, row))


def test_ngram_draft_validation():
    from mxnet_tpu.serving.drafters import ngram_draft
    with pytest.raises(ValueError):
        ngram_draft(np.zeros(0, np.int32), 2)
    with pytest.raises(ValueError):
        ngram_draft(np.ones(4, np.int32), 0)
