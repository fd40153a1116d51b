"""The grouped expert products' tiles (``parallel/moe.py``
``_gmm_tiling``) and the count of weight copies that follows from them
(``_weight_fetches``, the program's ``moe_weight_fetches``), the latter
against the schedule megablox itself makes (``make_group_metadata`` runs
on the CPU).  Nothing here runs a kernel."""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.parallel import moe

MIB = 1 << 20
# (pairs M, K, N): 256 rows x top-k, bfloat16
LFM2_UP = (1024, 2048, 1792)
LFM2_DOWN = (1024, 1792, 2048)
GIGA_UP = (2048, 7168, 2048)
GIGA_DOWN = (2048, 2048, 7168)
PRODUCTS = {
    "lfm2-gate": (LFM2_UP, (128, 2048, 896)),
    "lfm2-up": (LFM2_UP, (128, 2048, 896)),
    "lfm2-down": (LFM2_DOWN, (128, 1792, 1024)),
    # whole K would fit the budget at 256 lanes only: today's tiles
    "gigachat-gate": (GIGA_UP, (128, 1024, 1024)),
    "gigachat-up": (GIGA_UP, (128, 1024, 1024)),
    "gigachat-down": (GIGA_DOWN, (128, 2048, 1024)),
}


def _vmem(tiling, itemsize):
    """What a grid step of ``gmm`` keeps in VMEM: two buffers each of
    the weight tile, the rows and the float32 output tile, and the
    accumulator."""
    tm, tk, tn = tiling
    return 2 * tk * tn * itemsize + 2 * tm * tk * itemsize \
        + 3 * tm * tn * 4


@pytest.mark.parametrize("name", list(PRODUCTS))
def test_the_cells_products_get_their_tiles(name):
    (M, K, N), want = PRODUCTS[name]
    tiling = moe._gmm_tiling(M, K, N, 2)
    assert tiling == want
    tm, tk, tn = tiling
    assert M % tm == 0 and K % tk == 0 and N % tn == 0
    assert tk % 128 == 0 and tn % 128 == 0
    assert _vmem(tiling, 2) <= 10.5 * MIB < 16 * MIB
    if tk == K:
        assert tn >= 512 and K * tn * 2 <= moe._WEIGHT_TILE_BYTES


@pytest.mark.parametrize("shape,itemsize,want", [
    # over the budget at 512 lanes (7 MiB): split as before
    ((2048, 7168, 2048), 2, (128, 1024, 1024)),
    # float32 operands halve what fits: 2,048 x 512 x 4 is the budget
    ((2048, 2048, 7168), 4, (128, 2048, 512)),
    ((1024, 2048, 1792), 4, (128, 1024, 896)),
    # one byte more than the budget at every width from 512 up
    ((1024, 4096 + 128, 1024), 2, (128, 384, 1024)),
    # N has no divisor of 512 lanes or more: 384 = 3 x 128
    ((1024, 2048, 384), 2, (128, 1024, 384)),
    # sides that are no multiple of 128 stay whole, as before
    ((24, 48, 40), 4, (8, 48, 40)),
    # small K: whole, at the widest tile up to 1,024 lanes
    ((1024, 512, 4096), 2, (128, 512, 1024)),
], ids=["k7168", "f32-giga-down", "f32-lfm2-up", "past-budget", "n384",
        "toy", "k512"])
def test_tiles_outside_the_budget_stay_as_they_were(shape, itemsize, want):
    M, K, N = shape
    tiling = moe._gmm_tiling(M, K, N, itemsize)
    assert tiling == want
    tm, tk, tn = tiling
    assert M % tm == 0 and K % tk == 0 and N % tn == 0
    assert _vmem(tiling, itemsize) < 16 * MIB
    lanes = range(1024, 0, -128)
    if tk < K:          # the parent's rule, side by side
        assert (tk, tn) == (moe._tile(K, lanes), moe._tile(N, lanes))


def _schedule_fetches(sizes, m, tm, tiles_k):
    """Megablox's own schedule: over one N tile the grid runs (visit,
    k_i) with the weight block ``(group_ids[visit], k_i)``; a step whose
    block is the step before's copies nothing.  Each copy is one K tile
    of ``tiles_k``: the count in whole matrices."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata
    (_, group_ids, _), num_tiles = make_group_metadata(
        group_sizes=jnp.asarray(sizes, jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=len(sizes),
        visit_empty_groups=False)
    blocks = [(int(g), k) for g in np.asarray(group_ids)[:int(num_tiles)]
              for k in range(tiles_k)]
    copies = sum(a != b for a, b in zip([None] + blocks, blocks))
    assert copies % tiles_k == 0
    return copies // tiles_k


def _sizes(kind, rs, groups, m):
    if kind == "multinomial":
        return rs.multinomial(m, rs.dirichlet(np.ones(groups) * 8))
    if kind == "short":                 # dead rows: the sum short of m
        return rs.multinomial(m - rs.randint(1, m // 2),
                              rs.dirichlet(np.ones(groups) * 8))
    if kind == "sparse":                # most groups empty
        sizes = np.zeros(groups, np.int64)
        hit = rs.choice(groups, 3, replace=False)
        sizes[hit] = rs.multinomial(m - 7, np.ones(3) / 3)
        return sizes
    if kind == "one":                   # one group holds everything
        sizes = np.zeros(groups, np.int64)
        sizes[rs.randint(groups)] = m
        return sizes
    if kind == "aligned":               # every group a whole row tile
        return np.full(groups, m // groups)
    assert kind == "none"
    return np.zeros(groups, np.int64)


@pytest.mark.parametrize("tiles_k", [1, 2])
@pytest.mark.parametrize("kind", ["multinomial", "short", "sparse", "one",
                                  "aligned", "none"])
def test_weight_fetches_are_megabloxs_schedule(kind, tiles_k):
    rs = np.random.RandomState(len(kind) * 7 + tiles_k)
    for groups, m in ((32, 1024), (16, 2048), (8, 1024)):
        for _ in range(3):
            sizes = _sizes(kind, rs, groups, m)
            got = int(moe._weight_fetches(jnp.asarray(sizes, jnp.int32),
                                          128, tiles_k))
            assert got == _schedule_fetches(sizes, m, 128, tiles_k), sizes
            hit = int((sizes > 0).sum())
            assert got == hit if tiles_k == 1 else got >= hit
    if kind == "one" and tiles_k == 2:
        assert got == m // 128          # a copy at every row tile


def test_lfm2s_step_copied_a_fifth_of_its_experts_twice():
    """What the change removes, at the cell's own numbers: 893 live
    pairs over 32 experts and 128-row tiles visit an expert about 1.18
    times with split K, once with K whole."""
    rs = np.random.RandomState(0)
    split = whole = hit = 0
    for _ in range(40):
        sizes = jnp.asarray(rs.multinomial(893, np.ones(32) / 32),
                            jnp.int32)
        split += int(moe._weight_fetches(sizes, 128, 2))
        whole += int(moe._weight_fetches(sizes, 128, 1))
        hit += int((sizes > 0).sum())
    assert whole == hit
    assert 1.12 < split / hit < 1.25
