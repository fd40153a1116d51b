"""Paged KV cache: fixed-size pages in one preallocated pool per layer.

Layout (per decoder layer):

    kv pool : (num_pages, page_size, H, 2*dh)   cfg.dtype | int8
    s pool  : (num_pages, 2, page_size, H)      f32        (kv_int8)

``H`` and ``dh`` are the model's key/value heads and head size
(``kv_geometry``: ``cfg.n_kv_heads`` / ``cfg.head_dim`` where the config
has them, else the ``n_heads`` heads of ``d_model // n_heads`` every
query head has).  A grouped-query pool — fewer key/value heads than
query heads — is FLAT, ``(num_pages, page_size, H*2*dh)``, each head's
k then v side by side on the last axis: with few heads the 4-d page's
two minor dims are no whole tiles and the page walk could not cut
pages out of it (``kernels/paged_attention.py walk_geometry``).

A LATENT pool (multi-head latent attention, ``latent_row``) is flat
too, ``(num_pages, page_size, W)``: one row ``[c_kv | rotated k_pe]`` a
token shared by every query head, read once as the key and its first
``rank`` lanes again as the value, padded with zero lanes to whole
tiles (``latent_width``; ``write_latent`` writes it).

A model whose sequences keep state that is no page (a recurrent state,
a convolution window) gets a second, NON-paged pool per layer and name,
``(num_slots + 1, ...)``: row ``s`` is slot ``s``'s, the last row the
scratch slot that dead rows point at, as they point at the scratch page.
These pools ride in the layer's dict beside ``kv`` and are donated and
updated in place by the step program with it; no allocator: a slot's
row is its own.

What a layer keeps is the model's to say, layer by layer
(``layer_cache``: the ``layer_cache(cfg)`` of the serving module a config
names): pages or none, and which slot-state pools.  A layer that keeps
no pages has no ``kv`` leaf and costs no page bytes; page ids stay
model-wide (one block table: a page id means "this page of every layer
that has pages").  A module that says nothing keeps pages in every
layer and no state.

Each page holds ``page_size`` consecutive token positions of ONE
sequence, all heads, k and v halves fused in the last axis — the same
fused k|v layout the contiguous decode caches use ((B*H, L, 2*dh), see
``models/gpt.py _decode_one``), just chopped along the token axis so
pages from many sequences share one pool.  A request's cache is its
**block table**: a (pages_per_slot,) int32 vector of page ids, entry j
covering positions [j*page_size, (j+1)*page_size).  Attention gathers
``pool[block_table]`` into exactly the (R, L, 2*dh) view
``_attend_rows`` already consumes, so the paged and contiguous paths
share attention code.

Page 0 is the SCRATCH page: unallocated block-table entries and
padding rows point at it, its contents are written by dead rows and
never read under the position mask.  The allocator is a host-side
free list — page ids are plain ints, allocation never touches the
device; the pools themselves are donated through the engine's step
program so the buffers update in place.

No zero-fill on recycle: a freed page re-enters the pool with stale
contents, but a sequence only ever attends to positions <= its own
written length, and every one of those positions is written by that
sequence before any mask exposes it (the same pointer-only argument
as speculative rollback; pinned by the forced-retire test in
``tests/test_serving.py``).

int8-KV uses the per-(row, token) symmetric-s8 scales that
``models/gpt.py _kv_quantize`` emits (round 4), but paged in a
TILE-SHAPED arrangement (round 22): the s pool is (num_pages, **2**,
page_size, H) — a page's scales are two (page_size, H) planes (k
scales, then v scales) instead of per-column (.., H, 2) rows.  On the
8×128 VREG the trailing two axes of every pool block are what Mosaic
tiles; the old layout put a length-2 axis on the lanes (one useful
column per 128-wide register row), the plane layout streams a page's
scales as the same aligned (sublane=tokens, lane=heads) tiles as the
kv block.  The transpose in/out of ``_kv_quantize``'s (T, H, 2) order
happens once at the engine's scatter and in the reference gather —
the wire/export layout follows the pool layout, so disagg transfer
stays exact pool bytes.

Tensor parallelism (round 14): with ``mesh=`` (a ``parallel/mesh.py``
mesh carrying a ``tp`` axis) every pool is laid out heads-sharded —
``P(None, None, 'tp', None)`` on the (num_pages, page_size, **H**,
2*dh) layout — so each device holds ``1/tp`` of every page's bytes
(``bytes_held_per_device``).  Everything HOST-side is untouched and
replicated by construction: the free list, block tables, page ids,
and the prefix-cache trie are plain Python ints/dicts; a page id
means "this slice of every device's pool shard", so allocation,
COW, and prefix reuse are tp-oblivious.

Disaggregated serving (round 15) makes a page the **unit of
transfer**: :meth:`PagedKVCache.export_pages` gathers N pages of every
layer pool to host numpy (one device gather + one device→host copy per
pool key), and :meth:`PagedKVCache.install_pages` scatters received
page content into freshly-allocated local pages through a jitted,
pool-donating program (``_make_install`` — same in-place-update
contract as the engine's step, audited by graphlint as
``serving_page_install``).  Page counts are padded to power-of-two
buckets so the compiled gather/install programs stay O(log pool)
per config; padding rows target scratch page 0, whose contents are
never read.  The wire layout is exactly the pool layout — int8 pages
+ f32 scale pages under int8-KV — so a page moves as the compact,
quantized, self-describing unit the round-7/round-4 design already
made it.
"""
from __future__ import annotations

import math
from collections import deque
from typing import Any, Dict

__all__ = ["PagedKVCache", "contiguous_kv_bytes", "kv_geometry",
           "latent_row", "latent_width", "layer_cache", "write_latent",
           "write_rows"]

# the leaves of a layer's pool dict that are PAGES (indexed by page id:
# what page transfer moves); every other leaf is per-slot state
PAGE_LEAVES = ("kv", "s")


def latent_row(cfg):
    """``(rank, rope)`` of a config whose cache is LATENT (multi-head
    latent attention: ``cfg.latent_row``), else None.  A latent pool
    holds one row ``[c_kv (rank) | rotated k_pe (rope)]`` a token,
    shared by every query head, and nothing per head."""
    return getattr(cfg, "latent_row", None)


def latent_width(rank, rope):
    """Lanes of a latent page's row: ``rank + rope`` padded with zeros
    to whole 128-lane tiles (512 + 64 -> 640), so that the page walk
    can cut whole pages out of the pool."""
    return -(-(rank + rope) // 128) * 128


def kv_geometry(cfg):
    """``(heads, head size, flat)`` of a config's K/V pages: its
    key/value heads and explicit head size where it names them, else
    one per query head of ``d_model // n_heads``; ``flat`` where the
    key/value heads are shared by groups of query heads (the page is
    then ``(page_size, heads*2*head_size)``).  A latent pool
    (``latent_row``) is one flat "head" whose k | v are the two halves
    of its padded row: the page is ``(page_size, latent_width)``."""
    if latent_row(cfg):
        return 1, latent_width(*latent_row(cfg)) // 2, True
    H = getattr(cfg, "n_kv_heads", None) or cfg.n_heads
    dh = getattr(cfg, "head_dim", None) or cfg.d_model // cfg.n_heads
    return H, dh, H != cfg.n_heads


def write_rows(pool_kv, page, off, k, v):
    """The float K/V pool with row r's ``k[r]`` / ``v[r]`` ((T, H, dh)
    each) written at position ``off[r]`` of page ``page[r]``: k then v
    side by side on the last axis, per head in the 4-d page, all heads
    in a row in the flat one.  The page's layout is known here and in
    the kernel that reads it (``kernels/paged_attention.py``)."""
    import jax.numpy as jnp
    new = jnp.concatenate([k, v], axis=-1).astype(pool_kv.dtype)
    if pool_kv.ndim == 3:
        new = new.reshape(new.shape[0], -1)
    return pool_kv.at[page, off].set(new)


def write_latent(pool_kv, page, off, row):
    """The latent pool with row r's ``row[r]`` ((T, rank + rope)) written
    at position ``off[r]`` of page ``page[r]``, zero lanes after it up
    to the page's width."""
    import jax.numpy as jnp
    pad = pool_kv.shape[-1] - row.shape[-1]
    new = jnp.pad(row.astype(pool_kv.dtype), ((0, 0), (0, pad)))
    return pool_kv.at[page, off].set(new)


def _dtype_size(dtype):
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


def _bucket(n):
    """Smallest power of two >= n (compile-count bound for the
    export/install programs)."""
    b = 1
    while b < n:
        b <<= 1
    return b


# jitted page gather/scatter programs, keyed by pool config + bucket —
# module-level like the engine's _step_cache/_copy_cache so the
# interleaving explorer's many short-lived engines share compilations
_xfer_cache: Dict[Any, Any] = {}
_XFER_CACHE_MAX = 32


def _make_install(cfg, kv_int8, bucket, mesh=None):
    """Jitted whole-page scatter: install ``bucket`` pages of received
    content into the donated pools at ``ids`` (padding ids point at
    scratch page 0 — written, never read).  Donation keeps the pools
    updating in place exactly like the step program; graphlint's
    ``serving_page_install`` registry entry gates it."""
    import jax

    key = ("install", cfg, bool(kv_int8), bucket, mesh)
    fn = _xfer_cache.get(key)
    if fn is not None:
        return fn

    def install(pools, ids, content):
        return [dict(pool, **{k: pool[k].at[ids].set(new[k])
                              for k in PAGE_LEAVES if k in pool})
                for pool, new in zip(pools, content)]

    fn = jax.jit(install, donate_argnums=(0,))
    if len(_xfer_cache) >= _XFER_CACHE_MAX:
        _xfer_cache.pop(next(iter(_xfer_cache)))
    _xfer_cache[key] = fn
    return fn


def _make_export(cfg, kv_int8, bucket, mesh=None):
    """Jitted whole-page gather: ``bucket`` pages of every layer pool
    as one stacked array per pool key (the host slices off padding
    after the one device→host copy)."""
    import jax

    key = ("export", cfg, bool(kv_int8), bucket, mesh)
    fn = _xfer_cache.get(key)
    if fn is not None:
        return fn

    def export(pools, ids):
        return [{k: pool[k][ids] for k in PAGE_LEAVES if k in pool}
                for pool in pools]

    fn = jax.jit(export)
    if len(_xfer_cache) >= _XFER_CACHE_MAX:
        _xfer_cache.pop(next(iter(_xfer_cache)))
    _xfer_cache[key] = fn
    return fn


def layer_cache(cfg):
    """What each layer keeps between steps, one ``(pages, state)`` a
    layer: whether it keeps K/V pages, and what one slot keeps there
    that is no page, ``{name: (shape, dtype)}``.  The ``layer_cache`` of
    the model module a config names (``cfg.serving``); a module that
    says nothing keeps pages in every layer and no state."""
    per_layer = getattr(getattr(cfg, "serving", None), "layer_cache", None)
    return list(per_layer(cfg)) if per_layer \
        else [(True, {})] * cfg.n_layers


def contiguous_kv_bytes(cfg, batch, total, kv_int8=False):
    """HBM the contiguous allocator holds for a (batch, total)-shaped
    decode: B*H*total*2*dh elements per layer that keeps K/V (+ the f32
    scale pair per (row, token) when int8) — the baseline for the
    paged-vs-contiguous comparison in benchmark/serve_bench.py."""
    H, dh, _ = kv_geometry(cfg)
    rows = batch * H * total
    per_row = 2 * dh * (1 if kv_int8 else _dtype_size(cfg.dtype))
    if kv_int8:
        per_row += 2 * 4                      # f32 scale pair
    return rows * per_row * sum(pages for pages, _ in layer_cache(cfg))


class PagedKVCache:
    """Preallocated per-layer page pools + the host-side page
    allocator.  ``pools`` is a list (one dict per layer) shaped for
    the engine's step program; reassign it after every donated call."""

    # heads-sharded pool placement: the one genuinely tp-sharded
    # tensor in the serving step program (docs/sharding_readiness.md).
    # The f32 scale pool shards the SAME heads axis, which after the
    # round-22 tile-shaped retile is its LAST axis (num_pages, 2,
    # page_size, H) — hence a separate spec.
    POOL_SPEC = (None, None, "tp", None)
    S_POOL_SPEC = (None, None, None, "tp")

    def __init__(self, cfg, num_pages, page_size, kv_int8=False,
                 mesh=None, device=None, num_slots=None):
        import jax
        import jax.numpy as jnp

        if num_pages < 2:
            raise ValueError("PagedKVCache: need >= 2 pages (page 0 "
                             "is scratch)")
        if page_size < 1:
            raise ValueError("PagedKVCache: page_size must be >= 1")
        self.cfg = cfg
        self.num_pages = num_pages
        self.page_size = page_size
        self.kv_int8 = kv_int8
        self.mesh = mesh
        self.tp = 1
        H, dh, flat = kv_geometry(cfg)
        if flat and (kv_int8 or mesh is not None):
            raise ValueError(
                "PagedKVCache: a flat pool (grouped-query or latent: %d "
                "key/value heads under %d query heads) has no int8 scale "
                "planes and no heads-sharded placement"
                % (H, cfg.n_heads))
        page = (page_size, H * 2 * dh) if flat \
            else (page_size, H, 2 * dh)
        cdt = jnp.dtype(cfg.dtype)
        place = lambda x, spec=None: x       # noqa: E731
        if device is not None:
            # commit the pools to one chip (a cluster replica's): every
            # step output and every eager page write then stays there
            place = lambda x, spec=None: jax.device_put(  # noqa: E731
                x, device)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            if "tp" not in mesh.axis_names:
                raise ValueError("PagedKVCache: mesh has no 'tp' axis")
            self.tp = int(mesh.shape["tp"])
            if H % self.tp:
                raise ValueError(
                    "PagedKVCache: n_heads=%d not divisible by tp=%d "
                    "(pages shard the heads axis)" % (H, self.tp))

            def place(x, spec=self.POOL_SPEC):
                return jax.device_put(
                    x, NamedSharding(mesh, P(*spec)))
        # what each layer keeps (module docstring): pages or none, and
        # one (num_slots + 1, ...) pool per name of what a slot keeps
        # beside them, zeros
        self.layers = layer_cache(cfg)
        self.page_dtype = jnp.dtype(jnp.int8) if kv_int8 else cdt
        names = sorted({n for _, state in self.layers for n in state})
        if names and (num_slots is None or mesh is not None):
            raise ValueError(
                "PagedKVCache: per-slot state %s needs num_slots and has "
                "no sharded placement" % names)
        # (the planner books it every step: summed once)
        self._slot_state_bytes = sum(
            math.prod(shape) * _dtype_size(dtype)
            for _, state in self.layers for shape, dtype in state.values())
        self.pools = []
        for pages, state in self.layers:
            pool = {}
            if pages:
                pool["kv"] = place(jnp.zeros((num_pages,) + page,
                                             self.page_dtype))
                if kv_int8:
                    pool["s"] = place(jnp.zeros(
                        (num_pages, 2, page_size, H), jnp.float32),
                        self.S_POOL_SPEC)
            for name, (shape, dtype) in state.items():
                pool[name] = place(jnp.zeros(
                    (num_slots + 1,) + tuple(shape), dtype))
            self.pools.append(pool)
        # page 0 is scratch — never allocated
        self._free = deque(range(1, num_pages))
        self._in_use = 0
        # optional pool-pressure callback (round 10): when alloc()
        # would fail, the callback is asked to surrender pages first —
        # the PrefixCache frees LRU refcount-0 shared chains here, so
        # cached-but-unreferenced prefixes never starve live requests
        self.pressure_cb = None
        # allocator telemetry (round 8): plain ints bumped on the
        # host-side alloc/free path — the serving engine exports them
        # through its MetricsRegistry.  alloc_failures counts returns
        # of None (the caller then stalls admission or preempts).
        self.alloc_calls = 0
        self.alloc_pages_total = 0
        self.freed_pages_total = 0
        self.alloc_failures = 0

    # ---------------------------------------------------- allocator --
    @property
    def free_pages(self):
        return len(self._free)

    @property
    def pages_in_use(self):
        return self._in_use

    def alloc(self, n):
        """Allocate n pages; returns a list of page ids or None if the
        pool cannot satisfy the request (caller decides to stall or
        preempt — the allocator never partially allocates)."""
        if n < 0:
            raise ValueError("alloc: n must be >= 0")
        self.alloc_calls += 1
        if n > len(self._free) and self.pressure_cb is not None:
            self.pressure_cb(n - len(self._free))
        if n > len(self._free):
            self.alloc_failures += 1
            return None
        out = [self._free.popleft() for _ in range(n)]
        self._in_use += n
        self.alloc_pages_total += n
        return out

    def reset_telemetry(self):
        """Zero the allocator counters (warmup exclusion in benches;
        the free list and in-use accounting are untouched)."""
        self.alloc_calls = 0
        self.alloc_pages_total = 0
        self.freed_pages_total = 0
        self.alloc_failures = 0

    def free(self, pages):
        """Recycle pages (no zero-fill — see the module docstring)."""
        for p in pages:
            if not 1 <= p < self.num_pages:
                raise ValueError("free: bad page id %r" % (p,))
        self._free.extend(pages)
        self._in_use -= len(pages)
        self.freed_pages_total += len(pages)

    # ---------------------------------------------- page transfer ----
    def export_pages(self, page_ids):
        """Gather ``page_ids``' content across every layer pool to
        host numpy: a list (per layer) of ``{"kv": (n, ps, H, 2dh)}``
        (+ ``"s"`` under int8-KV) arrays in ``page_ids`` order — the
        disaggregated wire payload, byte-identical to the pool layout.
        One jitted gather + one device→host copy per call (bucketed
        page count, so compilations stay bounded)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        n = len(page_ids)
        if n == 0:
            return []
        b = _bucket(n)
        ids = np.zeros(b, np.int32)       # padding gathers scratch
        ids[:n] = page_ids
        fn = _make_export(self.cfg, self.kv_int8, b, mesh=self.mesh)
        out = jax.device_get(fn(self.pools, jnp.asarray(ids)))
        return [{k: v[:n] for k, v in layer.items()} for layer in out]

    def install_pages(self, page_ids, content):
        """Scatter received page ``content`` (the ``export_pages``
        layout, host arrays or buffer-backed views) into this pool's
        ``page_ids`` (already allocated by the caller).  Runs the
        jitted donating install program — the pools update in place
        and ``self.pools`` is reassigned, exactly like a step."""
        import jax.numpy as jnp
        import numpy as np

        n = len(page_ids)
        if n == 0:
            return
        if len(content) != self.cfg.n_layers:
            raise ValueError(
                "install_pages: %d layers of content for a %d-layer "
                "pool" % (len(content), self.cfg.n_layers))
        b = _bucket(n)
        ids = np.zeros(b, np.int32)       # padding scatters to scratch
        ids[:n] = page_ids
        padded = []
        for layer, pool in zip(content, self.pools):
            lay = {}
            for k in PAGE_LEAVES:
                if k not in pool:
                    continue
                ref, a = pool[k], np.asarray(layer[k])
                want = (n,) + tuple(ref.shape[1:])
                if a.shape != want or a.dtype != ref.dtype:
                    raise ValueError(
                        "install_pages: content %s %r/%s does not "
                        "match pool page shape %r/%s"
                        % (k, a.shape, a.dtype, want, ref.dtype))
                if b != n:
                    pad = np.zeros((b - n,) + want[1:], a.dtype)
                    a = np.concatenate([a, pad], axis=0)
                lay[k] = jnp.asarray(a)
            padded.append(lay)
        fn = _make_install(self.cfg, self.kv_int8, b, mesh=self.mesh)
        self.pools = fn(self.pools, jnp.asarray(ids), padded)

    # -------------------------------------------------- accounting ---
    @property
    def bytes_per_page(self):
        """Device bytes one page costs across the layers that keep
        pages."""
        H, dh, _ = kv_geometry(self.cfg)
        per_tok = H * 2 * dh * (1 if self.kv_int8
                                else _dtype_size(self.cfg.dtype))
        if self.kv_int8:
            per_tok += H * 2 * 4
        return per_tok * self.page_size \
            * sum(pages for pages, _ in self.layers)

    @property
    def bytes_per_slot_state(self):
        """Device bytes of ONE slot's state that is no page, every name
        of every layer that keeps some together (0 for a model that
        keeps pages alone)."""
        return self._slot_state_bytes

    @property
    def bytes_held(self):
        """HBM held by allocated (non-scratch, non-free) pages — the
        number the serving benchmark reports against
        ``contiguous_kv_bytes``."""
        return self._in_use * self.bytes_per_page

    @property
    def bytes_pool(self):
        """HBM the whole preallocated pool occupies (the capacity
        budget the engine was configured with)."""
        return self.num_pages * self.bytes_per_page

    @property
    def bytes_held_per_device(self):
        """Per-device share of ``bytes_held``: pages shard the heads
        axis over ``tp``, so each device holds exactly 1/tp of every
        allocated page (H % tp == 0 is enforced at construction)."""
        return self.bytes_held // self.tp

    @property
    def bytes_pool_per_device(self):
        """Per-device share of the preallocated pool capacity."""
        return self.bytes_pool // self.tp
