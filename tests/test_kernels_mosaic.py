"""The three Pallas kernels must COMPILE for the real chip, not only
run in the interpreter.

libtpu ships a compile-only v5e client that needs no chip:
``jax.experimental.topologies.get_topology_desc("tpu", "v5e:2x2")``
gives TPU devices a CPU-only process can ``jit(...).lower(...).compile()``
against, which runs the real Pallas → Mosaic → TPU compile.  Each case
asserts the compiled program holds a ``tpu_custom_call`` — i.e. the
kernel was lowered by Mosaic, not routed to a jnp fallback or the
interpreter.  Numerics are the interpreter tests' and the chip's job
(``tests/test_paged_attention.py``, ``chip_smoke.py``).
"""
import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    # libtpu is part of the installation: no skip if this fails
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _compile(fn, device, *shapes):
    s = jax.sharding.SingleDeviceSharding(device)
    text = jax.jit(fn, in_shardings=s, out_shardings=s) \
        .lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


# the serve_bench `full` preset's decode geometry: 32 step rows, 12
# heads of 64, 16-token pages, 32 pages a row, a (353, 16, 12, 128) pool
_FULL = dict(T=32, H=12, dh=64, ps=16, PP=32, NP=353)


def _paged_shapes(T, H, dh, ps, PP, NP, kv_dtype, q_dtype):
    int8 = jnp.dtype(kv_dtype) == jnp.int8
    return (_sds((T, H, dh), q_dtype),
            _sds((NP, ps, H, 2 * dh), kv_dtype),
            _sds((NP, 2, ps, H), "float32") if int8 else None,
            _sds((T, PP), "int32"), _sds((T,), "int32"))


@pytest.mark.parametrize("geom,kv_dtype,q_dtype", [
    (_FULL, "bfloat16", "bfloat16"),
    (_FULL, "int8", "bfloat16"),
    (_FULL, "float32", "float32"),
    # H/tp head slices of the full preset at tp=2 / tp=4 (what each
    # device of the mesh lowering walks)
    (dict(_FULL, H=6), "bfloat16", "bfloat16"),
    (dict(_FULL, H=3), "int8", "bfloat16"),
    # more heads and lane-width head dims, whole heads a page
    (dict(_FULL, H=16, dh=128), "bfloat16", "bfloat16"),
    (dict(_FULL, H=32, dh=128), "int8", "bfloat16"),
    # the benchmark's serving cell (bert_large_decoder): 160 step
    # rows, 16 heads of 64, a (3073, 16, 16, 128) pool — the walk
    (dict(T=160, H=16, dh=64, ps=16, PP=32, NP=3073), "bfloat16",
     "bfloat16"),
    # a short last row block, and a row table that is one odd group
    (dict(T=37, H=8, dh=64, ps=16, PP=7, NP=353), "float32", "float32"),
    # the cell's H/tp slice at tp=2: 8 heads, half a packed tile a token
    (dict(T=160, H=8, dh=64, ps=16, PP=32, NP=3073), "bfloat16",
     "bfloat16"),
], ids=["full-bf16", "full-int8", "full-f32", "tp2-bf16", "tp4-int8",
        "h16-bf16", "h32-int8", "cell-bf16", "odd-f32", "cell-tp2-bf16"])
def test_paged_attention_compiles(v5e, geom, kv_dtype, q_dtype):
    from mxnet_tpu.kernels.paged_attention import (_GROUP_BYTES,
                                                   paged_attention,
                                                   walk_geometry)
    q, kv, sc, bt, pos = _paged_shapes(kv_dtype=kv_dtype,
                                       q_dtype=q_dtype, **geom)
    ps = geom["ps"]
    # the walk where Mosaic can cut whole pages out of the pool, the
    # per-page grid elsewhere: both must compile
    geometry = walk_geometry(geom["H"], geom["dh"], ps, geom["PP"],
                             kv_dtype)
    walks = geometry is not None
    assert walks == (kv_dtype == "float32" or
                     (kv_dtype == "bfloat16" and geom["H"] % 8 == 0))
    if walks:
        # the dense fold: a whole group of pages is one turn, its
        # scores one tile (the per-page grid keeps the column fold);
        # four chains a trip of the loop, out of a ring of four slots
        G, F, _, K = geometry
        assert F == G == min(geom["PP"], _GROUP_BYTES // (
            ps * geom["H"] * 2 * geom["dh"]
            * jnp.dtype(kv_dtype).itemsize))
        assert K == 4
    if sc is None:
        _compile(lambda q, kv, bt, pos: paged_attention(
            q, kv, None, bt, pos, page_size=ps),
            v5e[0], q, kv, bt, pos)
    else:
        _compile(lambda q, kv, sc, bt, pos: paged_attention(
            q, kv, sc, bt, pos, page_size=ps),
            v5e[0], q, kv, sc, bt, pos)


# grouped-query pools are flat, (pages, ps, Hkv*2*dh): the benchmark's
# falcon_h1_34b_l6 cell is 128 step rows, 20 query heads over 4
# key/value heads of 128, 48 16-token pages a row, a (3073, 16, 1024)
# bf16 pool
_GQA = dict(T=128, Hq=20, Hkv=4, dh=128, ps=16, PP=48, NP=3073)


# ... and the lfm2_8b_a1b_l12 cell 256 step rows, 32 query heads over 8
# key/value heads of 64 (a head's keys are HALF a lane tile of the flat
# 1,024-lane page), 128 pages a row, a (16385, 16, 1024) bf16 pool
_GQA64 = dict(T=256, Hq=32, Hkv=8, dh=64, ps=16, PP=128, NP=16385)


# ... and the k_exaone_236b_l5_ep8 cell's one full layer 256 step rows, 64
# query heads over 8 key/value heads of 128 (a 2,048-lane page, 64 KiB in
# bf16, 8 query heads a group), 320 pages a row, a (40961, 16, 2048) pool
_GQA128X8 = dict(T=256, Hq=64, Hkv=8, dh=128, ps=16, PP=320, NP=40961)


@pytest.mark.parametrize("geom,dtype,walks", [
    (_GQA, "bfloat16", True),
    (dict(_GQA, T=37, PP=7, NP=353), "float32", True),
    # 8-token bf16 pages are half a tile: the per-page grid
    (dict(_GQA, T=32, ps=8, NP=353), "bfloat16", False),
    (_GQA64, "bfloat16", True),
    # heads of 32: a head's [k | v] pair is half a lane tile, which the
    # walk's fold cannot take whole: the per-page grid's column fold
    (dict(_GQA, T=32, dh=32, NP=353), "float32", False),
    (_GQA128X8, "bfloat16", True),
], ids=["cell-bf16", "odd-f32", "half-tile-bf16", "heads-of-64-bf16",
        "heads-of-32-f32", "2048-lanes-bf16"])
def test_grouped_paged_attention_compiles(v5e, geom, dtype, walks):
    from mxnet_tpu.kernels.paged_attention import (_GROUP_BYTES,
                                                   paged_attention,
                                                   walk_geometry)
    g = geom
    geometry = walk_geometry(g["Hkv"], g["dh"], g["ps"], g["PP"], dtype,
                             flat=True)
    assert (geometry is not None) == walks
    if walks:
        # a head's [k | v] pair whole lane tiles: the ring, a whole group
        # a turn under the dense form of the flat fold (16 bf16 pages of
        # 32 KiB, 8 of 64 KiB, or the 7-page table of 64 KiB f32 pages)
        page = g["ps"] * g["Hkv"] * 2 * g["dh"] * jnp.dtype(dtype).itemsize
        G = min(g["PP"], _GROUP_BYTES // page)
        assert G == (8 if g is _GQA128X8 else
                     min(g["PP"], 16 if dtype == "bfloat16" else 8))
        assert geometry == (G, G, 32, 4)
    _compile(lambda q, kv, bt, pos: paged_attention(
        q, kv, None, bt, pos, page_size=g["ps"]), v5e[0],
        _sds((g["T"], g["Hq"], g["dh"]), dtype),
        _sds((g["NP"], g["ps"], g["Hkv"] * 2 * g["dh"]), dtype),
        _sds((g["T"], g["PP"]), "int32"), _sds((g["T"],), "int32"))


# a latent (MLA) pool is one 512 + 64 row a token padded to 640 lanes:
# the gigachat3_702b_l5_ep16 cell is 256 step rows, 64 query heads, 128
# 16-token pages a row, a (16385, 16, 640) bf16 pool
_MLA = dict(T=256, H=64, rank=512, rope=64, ps=16, PP=128, NP=16385)


@pytest.mark.parametrize("geom,dtype", [
    (_MLA, "bfloat16"),
    (dict(_MLA, T=37, PP=7, NP=353), "float32"),
], ids=["cell-bf16", "odd-f32"])
def test_latent_paged_attention_compiles(v5e, geom, dtype):
    from mxnet_tpu.kernels.paged_attention import (paged_attention,
                                                   walk_geometry)
    from mxnet_tpu.serving.paged_kv import latent_width
    g = geom
    W = latent_width(g["rank"], g["rope"])
    assert W == 640
    G, F, R, K = walk_geometry(1, W // 2, g["ps"], g["PP"], dtype, flat=True,
                               latent=True)
    # the whole group a turn, its tokens whole lane tiles of scores;
    # four chains a trip, out of a ring of four slots
    assert F == G == (24 if dtype == "bfloat16" else g["PP"]) and K == 4
    _compile(lambda q, kv, bt, pos: paged_attention(
        q, kv, None, bt, pos, page_size=g["ps"],
        latent=(g["rank"], g["rope"]), scale=0.14468), v5e[0],
        _sds((g["T"], g["H"], g["rank"] + g["rope"]), dtype),
        _sds((g["NP"], g["ps"], W), dtype),
        _sds((g["T"], g["PP"]), "int32"), _sds((g["T"],), "int32"))


@pytest.mark.parametrize("T,K,D,F,E", [
    (256, 8, 7168, 2048, 16),
    # lfm2_8b_a1b_l12: top-4 over all 32 experts of width 1,792 = 7 x
    # 256: the first width that is no multiple of 512 (896-wide tiles),
    # and both its weight tiles span K (3.5 MiB)
    (256, 4, 2048, 1792, 32),
    # k_exaone_236b_l5_ep8: top-8 over 128, 16 held, K = 6,144: gate / up
    # split K into six 1,024 tiles (6,144 x 512 is past the weight tile's
    # budget), down spans K as GigaChat's does
    (256, 8, 6144, 2048, 16),
], ids=["gigachat", "lfm2", "exaone"])
def test_held_experts_ffn_compiles(v5e, T, K, D, F, E):
    """A cell's expert layer: 256 rows x top-k static pairs over the
    held experts: three grouped products, each one Mosaic call
    (megablox)."""
    from mxnet_tpu.parallel.moe import _gmm_tiling, held_experts_ffn
    # gate / up: whole K at 896 lanes of 1,792; 7,168 x 512 is past the
    # weight tile's budget.  down: whole K both (4 MiB at 2,048 x 1,024)
    assert _gmm_tiling(T * K, D, F, 2) == (
        (128, 1024, 1024) if F == 2048 else (128, 2048, 896))
    assert _gmm_tiling(T * K, F, D, 2) == (128, F, 1024)
    text = _compile(
        lambda x, wg, wu, wd, idx, w, live: held_experts_ffn(
            x, wg, wu, wd, idx, w, held_first=0, live=live), v5e[0],
        _sds((T, D), "bfloat16"), _sds((E, D, F), "bfloat16"),
        _sds((E, D, F), "bfloat16"), _sds((E, F, D), "bfloat16"),
        _sds((T, K), "int32"), _sds((T, K), "float32"), _sds((T,), "bool"))
    assert text.count("tpu_custom_call") >= 3


def test_flash_fwd_bwd_compiles(v5e):
    from mxnet_tpu.kernels import flash_attention as fa
    qkv = _sds((1, 4096, 12, 64), "bfloat16")

    def loss(q, k, v, seed):
        out = fa.flash_attention(q, k, v, causal=True, dropout=0.1,
                                 dropout_seed=seed)
        return jnp.sum(out.astype(jnp.float32))

    # flash_attention routes at trace time by where jit will run
    with jax.default_device(v5e[0]):
        text = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e[0],
                        qkv, qkv, qkv, _sds((), "int32"))
    # forward, dq and dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


def test_flash_rejects_sequences_past_vmem_limit(v5e):
    from mxnet_tpu.kernels import flash_attention as fa
    T = fa.MAX_KV_BLOCK_BYTES // (128 * 2) * 2
    q = _sds((1, T, 1, 128), "bfloat16")
    with jax.default_device(v5e[0]), \
            pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(lambda q: fa.flash_attention(q, q, q), q)


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_fused_multi_sgd_compiles(v5e, momentum):
    from mxnet_tpu.kernels.fused_optimizer import fused_multi_sgd
    shapes = [(64, 3, 7, 7), (64,), (256, 64, 1, 1), (1000, 2048)]
    ws = [_sds(s, "float32") for s in shapes]
    n = len(ws)

    def step(ws, gs, ms):
        return fused_multi_sgd(ws, gs, ms if momentum else None,
                               lrs=[0.1] * n, wds=[1e-4] * n,
                               momentum=momentum)

    _compile(step, v5e[0], ws, ws, ws)
