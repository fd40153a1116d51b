"""Continuous-batching (Orca-style, iteration-level) GPT decode engine.

One **step program** per engine config — compiled exactly once — takes
a fixed-shape batch of T token rows, where each row is (token, slot,
position): running requests contribute ONE decode row each, freshly
admitted requests contribute up to ``prefill_chunk`` prompt rows
(chunked prefill), and leftover rows, after every live one, are dead
padding aimed at the scratch page.  The program embeds all rows,
scatters every row's k/v into the paged pools at
(block_table[slot][pos//page_size], pos%page_size), gathers each
row's block-table view, and attends via
the SAME ``_attend_rows`` code the contiguous decode step uses (with
per-row positions instead of one scalar — that is the whole
continuous-batching trick at the model level).  Greedy argmax logits
are read at each slot's last live row.

Scheduling (host side, plain Python — the device never sees dynamic
shapes):

1. retire finished sequences, recycle their pages;
2. admit queued requests into free slots while the pool can cover
   their prompt (+1 decode) pages;
3. top up pages on demand as running sequences cross a page boundary —
   if the pool is exhausted, preempt the YOUNGEST running request
   (free its pages, requeue it at the front; it re-prefills its
   committed tokens on re-admission, which under greedy decode is
   recompute-exact);
4. build the row batch, run the step program, commit sampled tokens,
   check stop conditions.

Round 11 adds the two raw-decode-speed levers from ROADMAP item 2:

* ``kernel="pallas"`` routes the step program's attention through the
  fused block-table-walk kernel (``kernels/paged_attention.py``):
  online-softmax over each row's live pages, copied HBM→VMEM once,
  int8 dequant in the inner loop, no materialized gather.  ``"xla"``
  keeps the gather + ``_attend_rows`` path; both are cross-checked by
  tests.  Left unset, the engine picks by the platform its pools live
  on: the walk on a TPU (PR 27: 8 ms of a decode-heavy step against
  the gather's 68), the gather everywhere else.
* ``spec_K=K`` folds speculative decode INTO the step program: each
  running decode slot feeds its pending token plus K host-drafted
  rows (``serving/drafters.py`` ngram by default), the ONE program
  verifies every row's drafts against its own per-position argmaxes
  (the batched-verify amortization that flips round-6's stand-alone
  negative result), accepted tokens commit by advancing ``n_cached``
  over k/v already written this step, and rejections roll back by
  POINTER only — stale slots are overwritten at the committed
  position before any mask exposes them (the ``_decode_block``
  argument, serving edition).

Round 14 scales the engine UP, not just out: ``tp=N`` lowers the one
step program through a ``parallel/mesh.py`` tensor-parallel mesh.
Params shard by the megatron rules the training side already uses
(``models/transformer.py param_specs``; int8 ``{"q","s"}`` specs
derived — ``models/gpt.py decode_param_specs``), the paged KV pools
shard their HEADS axis (``P(None, None, 'tp', None)``) so each device
holds 1/tp of every page, and every host-built row/table input
replicates.  The scheduler above is untouched: page ids, block
tables, free lists, and the prefix trie are host state meaning "this
slice of every device's shard".  Attention needs no cross-head
collective (softmax and int8-KV quant stats reduce over head_dim,
which stays whole); the output projection's ``P('tp', None)``
contraction is the one GSPMD-inserted reduce per layer.  Declared
shardings live in :func:`step_input_specs`, which graphlint's
sharding-readiness audit verifies against the megatron rule table
(``docs/sharding_readiness.md``, UNCOVERED = 0) and whose pool
donation stays pinned by ``graph-donation``.

Round 18 adds the tier under the pool (ROADMAP item 4): with
``tier_bytes=N`` the engine owns a ``serving/tier_store.py
HostTierStore`` — a byte-budgeted host-DRAM LRU of exact pool-layout
page bytes.  Pressure eviction of refcount-0 prefix chains SPILLS
them there instead of dropping (``PrefixCache`` warm hits re-install
on the next match), and preemption SWAPS the victim's written pages
out (``_preempt_victim``) so resume is install-exact
(``_admit``/``_swap_in``) instead of recompute-exact — preemption
cost becomes O(transfer) instead of O(prefill).  Every tier path
degrades to the pre-tier behavior when the tier refuses or the entry
was LRU-aged: exactness NEVER depends on the tier, only latency does.

Round 21 hides the host scheduler behind device execution (ROADMAP
item 4).  There is one step loop and its pipeline depth is 0 or 1, read
in ``__init__`` from where the pools live, as ``kernel`` is: depth 1
(``eng.overlap``) on a TPU without speculation, depth 0 anywhere else —
on XLA:CPU the "device" is the host's own cores, nothing to hide
behind, and the drafters of a ``spec_K > 0`` engine read committed host
tokens.  No argument and no environment variable takes part.  At depth
1 the step program grows a per-row ``tok_src`` selector so a decode
row's input token can come from the PREVIOUS step's device-resident
argmax matrix instead of a host-fed value: ``step()`` builds step N+1's
admission / prefix match / page allocation / row batch into a second
preallocated buffer set and dispatches it against step N's device
output before the host has read step N back, all on the caller's
thread (the engine starts none: PR 29 measured a planner thread's build
0.15 ms dearer than the inline one, the interpreter's lock being the
caller's).  The host consumes tokens one step behind (stop conditions,
commits, metrics); a committed stop/eos/cancel/preemption that
invalidates the speculatively dispatched step reconciles EXACTLY: the
stale row's writes land at positions beyond every committed read range
(the same argument that makes preemption recompute-exact), so per-row
skip suffices.  Depth 0 is bit-for-bit the round-20 engine: same
compiled program, same host schedule, same commit order.

The step program is built from a MODEL MODULE's three functions,
``serve_embed`` / ``serve_block`` / ``serve_logits``: ``models/gpt.py``
for a ``TransformerConfig``, or the module a config object names itself
(``cfg.serving``; ``models/falcon_h1.py``).  The engine reads the family
from the config it is given — no option selects it — and supplies what a
block keeps between steps: ``attend(q, k, v)``, which writes the rows'
keys and values into their pages and reads each row's sequence back
through the block table, and, for a family whose sequences keep state
that is no page (a convolution window, an SSM state), a SLOT-STATE
backend over one ``(num_slots + 1, ...)`` pool per layer and name
(``serving/paged_kv.py``).  What a layer keeps is the module's to say,
layer by layer (``layer_cache``): pages, slot state, both (a parallel
block) or one of them (an operator that attends beside one that
convolves).  Every block is handed the attention backend, its layer's
slot-state backend (None where the layer keeps none) and the step's
counters together and uses what it needs; the layer's new pools are
collected from whichever ran.  A slot's
state starts from zero at the slot's first prefill chunk — the planner
fills a per-slot ``fresh`` mask, staged with the rows, and the program
masks the pool's old content as it reads it: no dispatch of its own —
carries across chunks and decode steps, and is rebuilt by recomputation
after a preemption (``resume_input`` re-prefills from position 0).
Dead rows point at the scratch slot ``num_slots`` as they point at the
scratch page.  Such a family's context is bounded by the pool
(``max_seq``), not by a position table, and the engine refuses for it,
by name, what would need snapshots or rollback of that state:
``prefix_cache``, ``spec_K``, the KV tier, ``admit_prefilled``,
``kv_int8``, ``tp > 1`` (ROADMAP B-m6).

A family whose cache is LATENT (``cfg.latent_row``: multi-head latent
attention, ``models/deepseek_v3.py``) hands ``attend`` each row's
absorbed queries and its ONE cache row instead of per-head keys and
values: the pool holds that row a token a layer (``paged_kv.py``), the
walk folds every head against it with the model's own softmax scale.
Such a family's module may count on the device over the step's live
rows (``STEP_COUNTERS``: its expert layers' dispatched pairs, experts
hit and weight copies): the counts ride behind the sampled tokens in
the step's one read-back and ``counter_stats`` books them in ``stats``
at the commit.
Refused for it by name: ``prefix_cache``, ``spec_K``, the KV tier,
``admit_prefilled``, ``kv_int8``, ``tp > 1`` (no test shows them on
latent pages yet).

Exactness: under f32 greedy, engine outputs are token-identical to
``models/gpt.py generate`` per request, whatever the batch mix,
admission order, page reuse, preemptions, swap-outs, kernel choice,
drafter quality, or tp degree — pinned by ``tests/test_serving.py``,
``tests/test_serving_tier.py`` and ``tests/test_serving_tp.py``.

Telemetry (round 8, ``mxnet_tpu/obs``): with ``metrics=True`` (or
``MXNET_SERVING_METRICS=1``) the engine feeds a per-engine
``MetricsRegistry`` — request/step/row counters, queue-depth and
page-pool gauges, TTFT / TBT / admission-wait / step-time histograms —
and, while the profiler is recording, emits per-request lifecycle
spans (admission_wait / prefill / decode / preempt / retire) into the
profiler's chrome-trace stream on the shared ``perf_counter`` clock.
All request timestamps (``Request.submit_t`` / ``token_times``) are on
that clock.  Metrics are OFF by default; the disabled path is one
``is None`` test per call site — no instruments exist, nothing
allocates.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import profiler
from ..models import gpt as G
from . import drafters
from .paged_kv import (PAGE_LEAVES, PagedKVCache, kv_geometry, latent_row,
                       layer_cache, write_latent, write_rows)
from .prefix_cache import PrefixCache
from .tier_store import HostTierStore

__all__ = ["Request", "ServingEngine", "step_input_specs",
           "step_output_specs"]


def step_input_specs(params, cfg, kv_int8, tp="tp", overlap=False):
    """The ENGINE'S DECLARED shardings: a mesh-free ``PartitionSpec``
    pytree for every input of the step program, positionally matching
    ``_make_step``'s ``(params, pools, tokens, row_slot, row_pos,
    row_live, bt, slot_rows)`` signature — plus, for the ``overlap``
    variant, the trailing ``(prev_tok, tok_src)`` pair (the previous
    step's device-resident argmax matrix and the per-row selector
    into it), both replicated like every other host-shaped input.

    * params — the megatron rules via ``models/gpt.py
      decode_param_specs`` (int8 q/s specs derived from the float
      rules);
    * pools — heads-sharded pages, ``PagedKVCache.POOL_SPEC``
      (= P(None, None, 'tp', None) on the (pages, page_size, H, 2*dh)
      layout; the f32 scale pool shards the same heads axis, which
      the round-22 tile-shaped retile moved last —
      ``PagedKVCache.S_POOL_SPEC`` = P(None, None, None, 'tp') on
      (pages, 2, page_size, H));
    * everything host-built (token rows, slot/pos/live vectors, block
      tables, sampling-row matrix) — replicated.

    graphlint's sharding-readiness audit verifies THIS table against
    the megatron rules and pins ``docs/sharding_readiness.md`` to it
    (UNCOVERED count 0); the engine binds it to its mesh.  Mesh-free
    so the FAST-tier spec test needs no devices."""
    from jax.sharding import PartitionSpec as P

    from ..models import gpt as G

    rep = P()
    out = (G.decode_param_specs(params, cfg, tp=tp),
           _pool_specs(cfg, kv_int8, tp), rep, rep, rep, rep, rep, rep)
    if overlap:
        out = out + (rep, rep)
    return out


def step_output_specs(cfg, kv_int8, tp="tp"):
    """Output twin of ``step_input_specs``: the (S, n_sample) argmax
    matrix replicates (the host reads it every step — the one
    intended sync), the returned pools keep the input pool sharding
    (shape/dtype AND sharding match is what keeps donation aliasing
    the buffers in place — the ``graph-donation`` gate)."""
    from jax.sharding import PartitionSpec as P

    return (P(), _pool_specs(cfg, kv_int8, tp))


def _pool_specs(cfg, kv_int8, tp):
    """The pools' spec tree: the page leaves of every layer that keeps
    pages (slot state has no sharded placement)."""
    from jax.sharding import PartitionSpec as P

    pool = {"kv": P(*[tp if a == "tp" else a
                      for a in PagedKVCache.POOL_SPEC])}
    if kv_int8:
        pool["s"] = P(*[tp if a == "tp" else a
                        for a in PagedKVCache.S_POOL_SPEC])
    return [dict(pool) if pages else {} for pages, _ in layer_cache(cfg)]


def _bind(mesh, tree):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


@dataclasses.dataclass
class Request:
    """One generation request and its in-flight bookkeeping."""
    rid: int
    prompt: np.ndarray                    # (P,) int32, immutable
    max_new_tokens: int
    eos_id: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    state: str = "queued"                 # queued|running|done|cancelled
    # runtime (engine-owned)
    slot: Optional[int] = None
    pages: List[int] = dataclasses.field(default_factory=list)
    n_prefilled: int = 0                  # input rows already fed
    n_cached: int = 0                     # positions written to cache
    pending: Optional[int] = None         # sampled, not yet in cache
    # admissions so far: a plan records the one it was built in, so a
    # step still in flight across a preemption AND the re-admission is
    # told from the request's new life (neither carried nor committed)
    admit_seq: int = 0
    # shared-prefix bookkeeping (round 10; empty when the engine runs
    # without a prefix cache)
    prefix_entries: List[Any] = dataclasses.field(default_factory=list)
    shared_pages: set = dataclasses.field(default_factory=set)
    chain_upto: int = 0                   # leading pages known to cache
    prefix_hit_tokens: int = 0            # prefill rows skipped via hits
    # timestamps are time.perf_counter() seconds — the profiler's trace
    # clock (profiler.now_us() / 1e6), so lifecycle spans and op events
    # interleave in one dump
    submit_t: float = 0.0
    wait_start: float = 0.0               # submit or last preemption
    token_times: List[float] = dataclasses.field(default_factory=list)
    # edge-minted trace context (round 23): the HTTP front door's
    # X-Request-Id, stamped into lifecycle trace instants so the edge
    # access log and the engine swimlane correlate by one string
    trace_id: Optional[str] = None

    @property
    def resume_input(self):
        """Prefill source: prompt + committed tokens (after a
        preemption the whole committed sequence re-prefills)."""
        if not self.generated:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])

    @property
    def output(self):
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])


# one compiled step program per (cfg, shape) — shared across engines
_step_cache: Dict[Any, Any] = {}
_STEP_CACHE_MAX = 8

# one compiled COW page-copy per pool config — shared across engines
# (round 12: the interleaving explorer builds hundreds of short-lived
# clusters; a per-engine jit here recompiled the same trivial program
# for every replica of every schedule)
_copy_cache: Dict[Any, Any] = {}


def _make_copy(cfg, kv_int8, mesh=None):
    """Jitted whole-page pool copy (COW at a shared-prefix
    divergence).  Page ids are traced scalars, so one compilation per
    pool config covers every (src, dst) pair and every engine whose
    pools share that config.  With ``mesh`` the copy rides the same
    heads-sharded pool placement as the step program (donation
    preserved — the pools stay in place per device, no reshard)."""
    import jax

    key = (cfg, bool(kv_int8), mesh)
    fn = _copy_cache.get(key)
    if fn is not None:
        return fn

    def copy(pools, s, d):
        return [dict(pool, **{k: pool[k].at[d].set(pool[k][s])
                              for k in PAGE_LEAVES if k in pool})
                for pool in pools]

    kw = {}
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        _, pool_shardings = step_output_specs(cfg, kv_int8)
        pool_shardings = _bind(mesh, pool_shardings)
        rep = _bind(mesh, P())
        kw = {"in_shardings": (pool_shardings, rep, rep),
              "out_shardings": pool_shardings}
    fn = jax.jit(copy, donate_argnums=(0,), **kw)
    if len(_copy_cache) >= _STEP_CACHE_MAX:
        _copy_cache.pop(next(iter(_copy_cache)))
    _copy_cache[key] = fn
    return fn


def _make_step(cfg, num_slots, n_rows, pages_per_slot, page_size,
               kv_int8, kernel="xla", n_sample=1, mesh=None,
               params=None, overlap=False):
    """Build (and cache) the jitted unified prefill+decode step.

    ``kernel`` selects the decode-attention lowering: ``"xla"`` is
    the block-table gather + ``_attend_rows`` path (materializes the
    gathered (T*H, L, 2*dh) view of every row's whole table),
    ``"pallas"`` the fused ``kernels/paged_attention.py`` walk (each
    row's live pages copied once and folded into an online softmax,
    no gather materialization; interpreter mode off-TPU).  The
    engine resolves its own default before it calls this.

    ``n_sample`` is how many argmax rows each slot reads back per step
    (1 + spec_K): with in-engine speculation every decode slot feeds
    its pending token plus K draft rows and the host verifies the
    drafts against the returned per-row argmaxes.

    With ``mesh`` (round 14, tensor-parallel serving) the ONE step is
    lowered through the mesh: ``in_shardings``/``out_shardings`` from
    the engine's declared spec table (``step_input_specs`` — megatron
    rules for params, heads-sharded pools, replicated host rows), and
    donation of the sharded pools survives because every donated pool
    leaf has a shape/dtype/sharding-matched output (``params`` is
    needed for the spec tree's structure only — float vs weight-only
    int8).

    For a family with per-slot state (in any layer: ``layer_cache``)
    the program takes one more input after ``slot_rows``:
    ``slot_fresh``, (num_slots + 1,) bool, the slots whose state starts
    from zero in this step; the state pools ride in ``pools`` and are
    donated with the pages.

    With ``overlap`` (round 21, latency-hiding scheduling) the
    program takes two extra inputs: ``prev_tok``, the PREVIOUS step's
    device-resident ``(S, n_sample)`` argmax matrix, and ``tok_src``,
    a per-row int32 selector — row r's effective input token is
    ``prev_tok[tok_src[r], 0]`` when ``tok_src[r] >= 0`` and
    ``tokens[r]`` otherwise.  That one gather is what takes the host
    readback off the dispatch critical path: step N+1 launches
    against step N's output buffer without the host ever seeing it.
    ``overlap=False`` compiles the EXACT round-20 program (the flag
    is part of the cache key; no ``where`` enters the graph).

    The compiled program is audited by graphlint
    (``tools/analysis/graphlint.py``, tier-1): pool donation is
    verified against the lowering (dropping ``donate_argnums=(1,)``
    here fails ``tests/test_static_analysis.py``), peak live bytes are
    gated by ``tools/analysis/hbm_budgets.json``, and bf16/int8→f32
    upcasts must be declared accumulation points."""
    import jax
    import jax.numpy as jnp

    key = (cfg, num_slots, n_rows, pages_per_slot, page_size,
           bool(kv_int8), kernel, n_sample, mesh, bool(overlap),
           None if mesh is None
           else jax.tree_util.tree_structure(params))
    fn = _step_cache.get(key)
    if fn is not None:
        return fn

    # the model code behind the config: its embedding, its block and
    # its sampling rows' logits (``serve_*`` of models/gpt.py, or of
    # the module a config names itself); the engine supplies what a
    # block keeps between steps — the paged K/V behind ``attend`` and,
    # in a layer that keeps some (``layer_cache``), the per-slot state
    model = getattr(cfg, "serving", G)
    latent = latent_row(cfg)
    counter_names = tuple(getattr(model, "STEP_COUNTERS", ()))

    def _body(params, pools, tokens, row_slot, row_pos, row_live, bt,
              slot_rows, slot_fresh=None):
        # the named scopes are metadata of the compiled operations (a
        # device trace groups by them; no layer index, so the layers
        # add up under one name): the program computes what it did
        # without them
        with jax.named_scope("embed"):
            x = model.serve_embed(params, cfg, tokens, row_pos)

        # dead rows write to the scratch page and read garbage the
        # host never looks at; bt carries one extra all-zero row
        # (index num_slots) that dead rows point at, so their gathers
        # touch only the scratch page instead of streaming slot 0's
        # real pages
        page_idx = row_pos // page_size                # (T,)
        page = jnp.where(row_live,
                         bt[row_slot, page_idx], 0)    # (T,)
        off = row_pos % page_size
        row_pages = bt[row_slot]                       # (T, PP)
        # one past the last live row: ``_build_plan`` packs the live
        # rows first, so the walk stops here and skips the dead tail
        n_live = jnp.max(jnp.where(
            row_live, jnp.arange(1, n_rows + 1, dtype=jnp.int32), 0))

        new_pools = []
        # what the model counts over the step's live rows, all layers
        # together (``STEP_COUNTERS``)
        counts = model.StepCounts(row_live) if counter_names else None
        for layer, pool, (_, keeps) in zip(params["layers"], pools,
                                           layer_cache(cfg)):
            # the layer's updated pools, from whichever backend ran
            new = {}

            def attend_latent(q, row, pool=pool, new=new):
                """Write the rows' one latent row each into their
                pages, then every head of each row against its own
                block table's rows: (T, H, rank) float32."""
                from ..kernels import paged_attention as PA
                with jax.named_scope("kv_write"):
                    pool_kv = write_latent(pool["kv"], page, off, row)
                    new["kv"] = pool_kv
                if kernel == "pallas":
                    return PA.paged_attention(
                        q, pool_kv, None, row_pages, row_pos,
                        page_size=page_size, latent=latent,
                        scale=cfg.softmax_scale, n_live=n_live)
                return PA.paged_attention_reference(
                    q, pool_kv, None, row_pages, row_pos,
                    page_size=page_size, latent=latent,
                    scale=cfg.softmax_scale)

            def attend(q, k, v, pool=pool, new=new):
                """Write the rows' k/v into their pages, then each
                row's attention over its own block table: (T, H, dh)
                float32.  Leaves the layer's updated pages in
                ``new``."""
                with jax.named_scope("kv_write"):
                    if kv_int8:
                        kvq, skv = G._kv_quantize(k, v)  # (T, H, 2dh/2)
                        pool_kv = pool["kv"].at[page, off].set(kvq)
                        # retiled scale planes (paged_kv.py): the (N,
                        # 2, ps, H) pool takes row r's scales at
                        # [page_r, :, off_r] — a (T, 2, H) update, so
                        # _kv_quantize's (T, H, 2) transposes once here
                        pool_s = pool["s"].at[page, :, off].set(
                            skv.transpose(0, 2, 1))
                        new.update(kv=pool_kv, s=pool_s)
                    else:
                        pool_kv = write_rows(pool["kv"], page, off, k, v)
                        pool_s = None
                        new["kv"] = pool_kv
                if kernel == "pallas":
                    # fused block-table walk
                    # (kernels/paged_attention.py): each row's live
                    # pages are copied HBM->VMEM once, online-softmax
                    # accumulation, int8 dequant in the inner loop —
                    # no gathered view is ever materialized.  With a
                    # mesh the call shard_maps over tp: each device
                    # walks its own H/tp heads slice of the pools
                    # (round 22)
                    from ..kernels.paged_attention import \
                        paged_attention
                    return paged_attention(q, pool_kv, pool_s,
                                           row_pages, row_pos,
                                           page_size=page_size,
                                           mesh=mesh, n_live=n_live)
                # block-table gather + _attend_rows — ONE copy of the
                # gather lives in kernels/paged_attention.py, shared
                # with the tests' oracle, so the engine path and the
                # kernel's comparison reference cannot drift apart
                # (scatter-before-gather so every row sees its own
                # k/v, same as the contiguous DUS order)
                from ..kernels.paged_attention import \
                    paged_attention_reference
                return paged_attention_reference(
                    q, pool_kv, pool_s, row_pages, row_pos,
                    page_size=page_size)

            # a slot's state that is no page, where the layer keeps
            # some: its (num_slots + 1, ...) pools, the rows' slots
            # (dead rows the scratch slot) and which slots start from
            # zero this step
            state = model.SlotState(
                {name: pool[name] for name in keeps}, row_slot,
                slot_fresh, n_rows - num_slots * n_sample) \
                if keeps else None
            x = model.serve_block(layer, cfg, x, row_pos,
                                  attend_latent if latent else attend,
                                  state, counts)
            if keeps:
                new.update(state.pools)
            new_pools.append(new)

        with jax.named_scope("head"):
            # (S, n_sample, V) f32: column 0 is the slot's sampling
            # row (the old slot_last_row), columns 1.. are its
            # draft-verify rows; dead columns point at row 0 and the
            # host never reads them
            slot_logits = model.serve_logits(params, cfg, x, slot_rows)
        with jax.named_scope("sample"):
            next_tok = jnp.argmax(slot_logits,
                                  axis=-1).astype(jnp.int32)
        if counter_names:
            # the step's counts ride behind the tokens, one row each:
            # the host's one read-back brings them
            next_tok = jnp.concatenate([next_tok, jnp.broadcast_to(
                jnp.stack(counts.counts)[:, None],
                (len(counter_names), n_sample))])
        return next_tok, new_pools

    if overlap:
        def step(params, pools, tokens, row_slot, row_pos, row_live,
                 bt, slot_rows, *rest):
            # device-carried inputs: rows with tok_src >= 0 read the
            # previous step's argmax for that slot straight off the
            # device (column 0 = the slot's sampling row); everything
            # else — prefill rows, post-fence decode rows, dead
            # padding — keeps its host-fed token.  An exact int32
            # select: carried steps compute bit-identically to the
            # serial schedule that would have fed the same token.
            # (``rest``: a stateful family's ``slot_fresh`` first.)
            prev_tok, tok_src = rest[-2:]
            eff = jnp.where(
                tok_src >= 0,
                prev_tok[jnp.clip(tok_src, 0, num_slots - 1), 0],
                tokens)
            return _body(params, pools, eff, row_slot, row_pos,
                         row_live, bt, slot_rows, *rest[:-2])
    else:
        step = _body

    kw = {}
    if mesh is not None:
        kw = {"in_shardings": _bind(
                  mesh, step_input_specs(params, cfg, kv_int8,
                                         overlap=overlap)),
              "out_shardings": _bind(
                  mesh, step_output_specs(cfg, kv_int8))}
    fn = jax.jit(step, donate_argnums=(1,), **kw)
    if len(_step_cache) >= _STEP_CACHE_MAX:
        _step_cache.pop(next(iter(_step_cache)))
    _step_cache[key] = fn
    return fn


class _StepBuffers:
    """One preallocated set of host-side step inputs.  The engine
    owns TWO and rotates: while step N (built into set A) executes on
    device, ``_build_plan`` builds step N+1 into set B — and depth 0
    rotates too, so no step's host buffers are ever mutated
    while a dispatch that snapshot them could still be staging
    (round-21 satellite: no fresh numpy allocations per step)."""

    __slots__ = ("tokens", "row_slot", "row_pos", "row_live",
                 "tok_src", "slot_rows", "bt", "fresh")

    def __init__(self, n_rows, num_slots, spec_K, pages_per_slot):
        T, S = n_rows, num_slots
        self.tokens = np.zeros(T, np.int32)
        self.row_slot = np.full(T, S, np.int32)
        self.row_pos = np.zeros(T, np.int32)
        self.row_live = np.zeros(T, bool)
        self.tok_src = np.full(T, -1, np.int32)
        self.slot_rows = np.zeros((S, 1 + spec_K), np.int32)
        self.bt = np.zeros((S + 1, pages_per_slot), np.int32)
        # slots whose state that is no page starts from zero this step
        # (staged only for a family that keeps such state)
        self.fresh = np.zeros(S + 1, bool)

    def reset(self, num_slots):
        self.tokens.fill(0)
        self.row_slot.fill(num_slots)
        self.row_pos.fill(0)
        self.row_live.fill(False)
        self.tok_src.fill(-1)
        self.slot_rows.fill(0)
        self.fresh.fill(False)


class _Plan:
    """One fully-built step: the row batch plus everything the commit
    needs recorded AT BUILD TIME.  At depth 1 the commit runs one
    step later than the build, after the next plan's build has already
    advanced ``n_prefilled`` — so commits must never read live
    scheduler positions; they read these records."""

    __slots__ = ("buf", "samplers", "spec_plan", "decode_pos",
                 "was_decode", "prefill_mid", "n_dec_rows",
                 "n_pre_rows", "n_rows_used", "decode_rids",
                 "prefill_spans", "carried", "empty",
                 "pipelined", "kv_pages", "state_slots", "resets",
                 "admit")

    def __init__(self):
        self.buf = None
        self.samplers = []          # requests sampling a token
        self.spec_plan = {}         # rid -> drafts (depth 0 only)
        self.decode_pos = {}        # rid -> its sampling row's pos
        self.was_decode = {}        # rid -> fed a decode row?
        self.prefill_mid = []       # (req, n_prefilled) mid-prefill
        self.n_dec_rows = 0
        self.n_pre_rows = 0
        self.n_rows_used = 0
        self.decode_rids = []       # trace
        self.prefill_spans = []     # trace: (rid, row_lo, row_hi)
        self.carried = 0            # rows fed from device prev_tok
        self.empty = True           # no live rows
        self.pipelined = False      # built by a depth-1 engine
        self.kv_pages = 0           # K/V pages the step's attention reads
        self.state_slots = 0        # slots whose state the step updates
        self.resets = 0             # of them, started from zero
        self.admit = {}             # rid -> its admit_seq at build

    def current(self, req):
        """Whether ``req`` still lives the admission this plan's rows
        were built in (and holds a slot)."""
        return req.slot is not None and req.state == "running" \
            and self.admit.get(req.rid) == req.admit_seq


_engine_seq = itertools.count()
_NO_SPAN = contextlib.nullcontext()


class _EngineObs:
    """Per-engine observability bundle: a labeled ``MetricsRegistry``
    (instrument handles bound once at construction — the step path
    does attribute increments, never name lookups) plus the
    request-span trace emitter.  Constructed only when metrics are
    enabled; the engine otherwise carries ``_obs = None`` and every
    call site is a single ``is None`` branch."""

    def __init__(self, registry=None):
        from .. import obs as O
        if registry is None:
            registry = O.MetricsRegistry(
                labels={"engine": str(next(_engine_seq))})
            # self-created registries join the process-wide Prometheus
            # scrape; an explicitly passed registry stays caller-scoped
            O.register_engine_registry(registry)
        self.registry = registry
        c, g, h = registry.counter, registry.gauge, registry.histogram
        self.submitted = c("serving_requests_submitted_total",
                           "requests accepted by submit()")
        self.admitted = c("serving_requests_admitted_total",
                          "admissions into a decode slot (resumes "
                          "after preemption count again)")
        self.finished = c("serving_requests_finished_total",
                          "requests retired done")
        self.cancelled = c("serving_requests_cancelled_total",
                           "requests retired by cancel()")
        self.preemptions = c("serving_preemptions_total",
                             "youngest-victim preemptions")
        self.steps = c("serving_steps_total", "engine iterations")
        self.tokens = c("serving_tokens_total",
                        "tokens committed to requests")
        self.decode_rows = c("serving_decode_rows_total",
                             "decode rows fed to the step program")
        self.prefill_rows = c("serving_prefill_rows_total",
                              "chunked-prefill rows fed")
        self.dead_rows = c("serving_dead_rows_total",
                           "padding rows aimed at the scratch page")
        self.alloc_calls = c("serving_page_alloc_calls_total",
                             "page-allocator calls")
        self.pages_allocated = c("serving_pages_allocated_total",
                                 "pages handed out")
        self.pages_freed = c("serving_pages_freed_total",
                             "pages recycled")
        self.alloc_failures = c("serving_page_alloc_failures_total",
                                "allocations refused by a dry pool "
                                "(caller stalls or preempts)")
        # in-engine speculative decode (round 11; all-zero at spec_K=0)
        self.spec_drafted = c("serving_spec_drafted_tokens_total",
                              "draft tokens fed to the batched "
                              "verify forward")
        self.spec_accepted = c("serving_spec_accepted_tokens_total",
                               "draft tokens committed (matched the "
                               "verify argmax)")
        self.spec_rejected = c("serving_spec_rejected_tokens_total",
                               "draft tokens rolled back by pointer "
                               "(drafted - accepted)")
        self.g_spec_accept_rate = g(
            "serving_spec_accept_rate",
            "cumulative accepted / drafted draft tokens")
        # host-DRAM KV tier (round 18; all-zero when disabled)
        self.tier_spills = c("serving_tier_spills_total",
                             "KV pages spilled HBM -> host tier "
                             "(pressure-evicted prefix chains + "
                             "preemption swap-outs)")
        self.tier_installs = c("serving_tier_installs_total",
                               "KV pages installed host tier -> HBM "
                               "(warm prefix hits + swap-in resumes)")
        self.tier_bytes = c("serving_tier_bytes_total",
                            "bytes moved through the host tier, both "
                            "directions (spill + install + peer "
                            "fetches served from the tier)")
        self.tier_evicted = c("serving_tier_evicted_pages_total",
                              "pages LRU-dropped from the host tier "
                              "(its byte budget, not pool pressure)")
        self.g_tier_pages = g("serving_tier_pages",
                              "KV pages currently held by the host "
                              "tier")
        self.g_tier_bytes = g("serving_tier_bytes_held",
                              "host-DRAM bytes currently held by the "
                              "tier (vs its byte budget)")
        self.g_tier_budget = g("serving_tier_budget_bytes",
                               "the host tier's configured byte "
                               "budget")
        self.warm_hit_tokens = c(
            "serving_prefix_warm_hit_tokens_total",
            "prefill tokens served by re-installing SPILLED chain "
            "pages (the warm-hit outcome between hot-hit and miss)")
        self.swap_outs = c("serving_swap_outs_total",
                           "preemptions whose victim pages were "
                           "swapped to the host tier instead of "
                           "discarded")
        self.swap_ins = c("serving_swap_ins_total",
                          "preemption resumes served install-exact "
                          "from the host tier instead of recomputed")
        # shared-prefix cache (round 10; all-zero when disabled)
        self.prefix_hit_tokens = c("serving_prefix_hit_tokens_total",
                                   "prefill tokens skipped via "
                                   "prefix-cache hits")
        self.prefix_lookup_tokens = c(
            "serving_prefix_lookup_tokens_total",
            "prefill tokens eligible for prefix reuse (admissions)")
        self.prefix_pages_hit = c("serving_prefix_pages_hit_total",
                                  "cached pages mapped read-only into "
                                  "block tables")
        self.prefix_pages_inserted = c(
            "serving_prefix_pages_inserted_total",
            "prompt pages donated to the prefix cache")
        self.prefix_pages_evicted = c(
            "serving_prefix_pages_evicted_total",
            "refcount-0 chains evicted under pool pressure")
        self.prefix_cows = c("serving_prefix_cow_total",
                             "copy-on-write page copies at divergence")
        self.g_prefix_cached = g("serving_prefix_cached_pages",
                                 "pages owned by the prefix cache")
        self.g_prefix_hit_ratio = g(
            "serving_prefix_hit_ratio",
            "cumulative hit tokens / lookup tokens")
        self.g_running = g("serving_running", "requests holding a slot")
        self.g_queued = g("serving_queued", "requests waiting for a "
                          "slot (incl. preempted)")
        self.g_page_free = g("serving_page_free",
                             "free-list length (pages)")
        self.g_pages_in_use = g("serving_pages_in_use",
                                "allocated non-scratch pages")
        self.g_hbm_held = g("serving_hbm_held_bytes",
                            "device bytes held by allocated pages")
        self.g_step_decode = g("serving_step_decode_rows",
                               "decode rows in the latest step")
        self.g_step_prefill = g("serving_step_prefill_rows",
                                "prefill rows in the latest step")
        self.g_step_dead = g("serving_step_dead_rows",
                             "dead rows in the latest step")
        self.h_admission = h("serving_admission_wait_ms",
                             help="submit (or preemption) -> slot "
                                  "admission")
        self.h_ttft = h("serving_ttft_ms",
                        help="submit -> first committed token")
        self.h_tbt = h("serving_tbt_ms",
                       help="interval between committed tokens "
                            "(preemption gaps included)")
        self.h_step = h("serving_step_ms", help="engine step duration")
        from ..obs import RequestTraceEmitter
        self.trace = RequestTraceEmitter()
        # last-seen allocator totals, so sync_cache feeds DELTAS: with
        # a caller-shared registry two engines would otherwise assign
        # competing cumulative values and the counters would go
        # backwards (a Prometheus rate() reads that as a reset)
        self._cache_seen = [0, 0, 0, 0]
        self._prefix_seen = [0, 0, 0, 0, 0, 0]
        self._tier_seen = [0, 0, 0, 0]
        self._warm_seen = [0]             # sync_prefix: warm tokens
        self._swap_seen = [0, 0]          # sync_tier: outs, ins

    def sync_cache(self, cache):
        """Fold the allocator's plain-int telemetry into the registry
        by increment (cache totals only grow between resets).  v <
        last-seen means ``reset_telemetry()`` re-baselined the cache:
        v IS the activity since the reset, so count it rather than
        dropping everything until totals pass the stale baseline."""
        vals = (cache.alloc_calls, cache.alloc_pages_total,
                cache.freed_pages_total, cache.alloc_failures)
        seen = self._cache_seen
        for i, (ctr, v) in enumerate(zip(
                (self.alloc_calls, self.pages_allocated,
                 self.pages_freed, self.alloc_failures), vals)):
            d = v - seen[i]
            if d < 0:              # cache reset: restart from zero
                d = v
            if d > 0:
                ctr.inc(d)
            seen[i] = v
        self.g_page_free.set(cache.free_pages)
        self.g_pages_in_use.set(cache.pages_in_use)
        self.g_hbm_held.set(cache.bytes_held)

    def sync_prefix(self, prefix):
        """Fold the prefix cache's host ints in, delta-wise like
        sync_cache (same shared-registry aggregation argument)."""
        vals = (prefix.hit_tokens_total, prefix.lookup_tokens_total,
                prefix.pages_hit_total, prefix.pages_inserted_total,
                prefix.pages_evicted_total, prefix.cow_total)
        ctrs = (self.prefix_hit_tokens, self.prefix_lookup_tokens,
                self.prefix_pages_hit, self.prefix_pages_inserted,
                self.prefix_pages_evicted, self.prefix_cows)
        seen = self._prefix_seen
        for i, (ctr, v) in enumerate(zip(ctrs, vals)):
            d = v - seen[i]
            if d < 0:
                d = v
            if d > 0:
                ctr.inc(d)
            seen[i] = v
        self.g_prefix_cached.set(prefix.cached_pages)
        self.g_prefix_hit_ratio.set(
            prefix.hit_tokens_total
            / max(1, prefix.lookup_tokens_total))
        # warm-hit token delta rides the prefix sync (the counter
        # lives on the prefix cache, tier or not)
        seen = self._warm_seen
        d = prefix.warm_hit_tokens_total - seen[0]
        if d < 0:
            d = prefix.warm_hit_tokens_total
        if d > 0:
            self.warm_hit_tokens.inc(d)
        seen[0] = prefix.warm_hit_tokens_total

    def sync_tier(self, tier, swap_outs, swap_ins):
        """Fold the host tier's plain-int telemetry in, delta-wise
        like sync_cache (same shared-registry aggregation argument);
        occupancy gauges carry the current tier state."""
        vals = (tier.spilled_pages_total, tier.installed_pages_total,
                tier.bytes_moved_total, tier.evicted_pages_total)
        ctrs = (self.tier_spills, self.tier_installs, self.tier_bytes,
                self.tier_evicted)
        seen = self._tier_seen
        for i, (ctr, v) in enumerate(zip(ctrs, vals)):
            d = v - seen[i]
            if d < 0:                     # tier reset: restart from 0
                d = v
            if d > 0:
                ctr.inc(d)
            seen[i] = v
        self.g_tier_pages.set(tier.pages_held)
        self.g_tier_bytes.set(tier.bytes_held)
        self.g_tier_budget.set(tier.budget_bytes)
        seen = self._swap_seen
        for i, (ctr, v) in enumerate(zip(
                (self.swap_outs, self.swap_ins),
                (swap_outs, swap_ins))):
            d = v - seen[i]
            if d < 0:
                d = v
            if d > 0:
                ctr.inc(d)
            seen[i] = v


class ServingEngine:
    """Continuous-batching greedy decode over a ``PagedKVCache``.

    Parameters
    ----------
    params, cfg : the GPT decode params/config (float or
        ``quantize_decode_params`` weight-only int8 — same formats as
        ``generate``), or another family's: a config object that names
        its model module (``models/falcon_h1.py FalconH1Config``) with
        that module's parameter tree.
    num_slots : concurrent sequences per iteration (the decode batch).
    page_size : tokens per KV page.
    num_pages : pool capacity; default fully provisions every slot
        (``num_slots * pages_per_slot + 1``) — pass less to serve more
        slots than contiguous HBM would allow (page reuse + preemption
        absorb the tail).
    pages_per_slot : per-request length cap in pages; default covers
        ``cfg.max_len``.
    prefill_chunk : prompt tokens fed per iteration (chunked prefill
        rides the same step program; bigger chunks prefill faster but
        make every iteration's compiled batch wider).
    kv_int8 : paged int8-KV cache (the round-4 scale layout).
    prefix_cache : enable refcounted shared-prefix page reuse
        (``serving/prefix_cache.py``): prompts matching cached chains
        map those pages read-only and skip their prefill rows;
        completed prompt pages are donated back; refcount-0 chains are
        LRU-evicted under pool pressure.  Off by default — the
        ``ServingCluster`` turns it on per replica.
    kernel : ``"xla"`` attends via the block-table gather +
        ``_attend_rows``; ``"pallas"`` runs the fused
        ``kernels/paged_attention.py`` block-table walk (interpreter
        mode off-TPU, so tier-1 CPU tests cover the kernel path).
        None (the default) picks by the platform the engine's pools
        were placed on: ``"pallas"`` on a TPU, where the walk reads
        each row's live pages once and the gather path copies every
        row's whole table three times over; ``"xla"`` on anything
        else, where the kernel would be interpreted.  Outputs differ
        by 1–2 f32 ulps (online-softmax normalization order — the
        kernel module docstring); greedy token-identity vs
        ``generate`` is pinned for both by ``tests/test_serving``.
        ``stats["kv_pages_read"]`` over ``stats["kv_pages_window"]``
        says how much of the attention window a step read, and over
        ``stats["kv_pages_folded"]`` how much of what the walk folded
        (whole turns of pages) was live; ``stats["kv_groups_live"]``
        over ``stats["kv_chain_slots"]`` how full the trips of the
        walk's loop ran (K groups each).
    spec_K : in-engine speculative decode — each running decode slot
        drafts K tokens per step, the step program verifies all rows'
        drafts in ONE batched forward over the paged cache, accepted
        tokens commit by pointer-only page advances and rejections
        roll back exactly (stale slots are overwritten before any
        mask exposes them — the ``_decode_block`` argument).  0 (the
        default) disables speculation; the step program then has the
        round-7 shape.  Greedy output stays token-identical to plain
        decode whatever the drafter proposes.
    spec_drafter : ``"ngram"`` (prompt-lookup over the row's committed
        tokens, zero cost — ``serving/drafters.py``) or a callable
        ``f(tokens (n,), K) -> (K,)`` proposing the next K tokens
        (tests use adversarial/oracle callables).
    spec_ngram : n-gram length for the ngram drafter.
    tp : tensor-parallel degree (round 14).  ``tp > 1`` builds (or
        accepts via ``mesh=``) a ``parallel/mesh.py`` serving mesh and
        lowers the ONE compiled step through it: params shard by the
        megatron rules (int8 q/s specs derived), the paged KV pools
        shard the HEADS axis (``P(None, None, 'tp', None)`` — each
        device holds 1/tp of every page), host state (block tables,
        free lists, the prefix-cache trie, row batches) stays
        replicated, and pool donation survives the shardings.  Per-
        device weight and KV-pool bytes drop ~1/tp, so a model ~tp×
        too big for one chip serves; f32-greedy outputs stay
        token-identical to ``tp=1`` and to ``generate`` (pinned by
        ``tests/test_serving_tp.py``).  Requires ``cfg.n_heads % tp
        == 0``.  Both kernels serve tp>1: the XLA gather shards
        through GSPMD, and (round 22) the Pallas block-table walk is
        shard_map-lowered so each device walks its own H/tp heads
        slice — speculation (``spec_K``) composes with both.
    mesh : optional pre-built mesh with a ``tp`` axis (e.g.
        ``parallel.serving_mesh(tp)``); overrides ``tp``.
    device : the one chip a ``tp=1`` engine lives on — params and KV
        pools are committed to it, so the step program runs there.
        None (the default) leaves placement to JAX: the default
        device.  ``ServingCluster`` passes replica i device i mod n.
    tier_bytes : host-DRAM KV tier budget in bytes (round 18).  > 0
        attaches a ``HostTierStore``: pressure-evicted refcount-0
        prefix chains spill to it (and re-install as warm hits),
        preemption victims swap out (and resume install-exact).
        None reads ``MXNET_SERVE_TIER_BYTES`` (off unless set); 0
        disables — the engine then behaves bit-identically to round
        17 (drop on pressure, recompute on resume).
    rid_start : first request id this engine assigns (a cluster gives
        each replica a disjoint block so rids — and their trace
        swimlanes — are unique cluster-wide).
    metrics : True/False enables/disables the obs layer; None (the
        default) reads ``MXNET_SERVING_METRICS`` (off unless "1").
        Disabled means NO instruments exist — the hot path pays one
        ``is None`` branch.
    registry : optional ``obs.MetricsRegistry`` to feed (tests /
        callers wanting isolation); by default the engine creates its
        own, labeled ``{engine="<n>"}``, and registers it with the
        process-wide Prometheus scrape.
    """

    def __init__(self, params, cfg, *, num_slots, page_size=16,
                 num_pages=None, pages_per_slot=None, prefill_chunk=8,
                 kv_int8=False, prefix_cache=False, metrics=None,
                 registry=None, rid_start=0, kernel=None, spec_K=0,
                 spec_drafter="ngram", spec_ngram=2, tp=1, mesh=None,
                 tier_bytes=None, device=None):
        if not cfg.causal:
            cfg = dataclasses.replace(cfg, causal=True)
        # a family whose sequences keep state that is no page (in any
        # layer, by its serving module's ``layer_cache``): one state
        # per slot beside or instead of that layer's pages, see
        # ``_build_plan``
        self._stateful = any(state for _, state in layer_cache(cfg))
        if num_slots < 1:
            raise ValueError("ServingEngine: num_slots must be >= 1")
        if prefill_chunk < 1:
            raise ValueError("ServingEngine: prefill_chunk must be "
                             ">= 1")
        if kernel not in (None, "xla", "pallas"):
            raise ValueError("ServingEngine: kernel must be 'xla', "
                             "'pallas' or None, got %r" % (kernel,))
        if spec_K < 0:
            raise ValueError("ServingEngine: spec_K must be >= 0")
        if spec_drafter != "ngram" and not callable(spec_drafter):
            raise ValueError("ServingEngine: spec_drafter must be "
                             "'ngram' or a callable")
        if mesh is not None:
            if "tp" not in mesh.axis_names:
                raise ValueError("ServingEngine: mesh has no 'tp' "
                                 "axis (build one with "
                                 "parallel.serving_mesh)")
            if tp not in (1, int(mesh.shape["tp"])):
                raise ValueError(
                    "ServingEngine: tp=%d disagrees with the mesh's "
                    "tp axis (%d)" % (tp, mesh.shape["tp"]))
            tp = int(mesh.shape["tp"])
        if tp < 1:
            raise ValueError("ServingEngine: tp must be >= 1")
        if tier_bytes is None:
            env = os.environ.get("MXNET_SERVE_TIER_BYTES", "")
            try:
                tier_bytes = int(env) if env else 0
            except ValueError:
                raise ValueError(
                    "MXNET_SERVE_TIER_BYTES=%r: expected int" % env)
        # what a family is refused, by name.  Per-slot recurrent state:
        # each of these moves, shares or rolls back a sequence's cache
        # as PAGES; a slot's state is none, and has no snapshot,
        # rollback or sharded layout yet.  A latent (MLA) row per
        # token: no test shows these on latent pages yet (ROADMAP B-m6)
        for keeps, refused in (
                (self._stateful and "per-slot recurrent state", (
                    (prefix_cache, "prefix_cache=True: a shared prefix "
                     "is K/V pages; the state after it has no snapshot "
                     "to restore"),
                    (spec_K > 0, "spec_K > 0: rejected drafts roll "
                     "back by page pointer; the state has no rollback"),
                    (tier_bytes, "tier_bytes > 0 (KV tier / swap): a "
                     "victim's pages swap out and in; its state has no "
                     "snapshot, resume is by recomputation"),
                    (kv_int8, "kv_int8=True: no int8 layout for a "
                     "grouped-query pool"),
                    (tp > 1, "tp > 1: no sharded layout for the "
                     "key/value heads and the per-slot state"))),
                (latent_row(cfg) and "a latent (MLA) row per token", (
                    (prefix_cache, "prefix_cache=True"),
                    (spec_K > 0, "spec_K > 0"),
                    (tier_bytes, "tier_bytes > 0 (KV tier / swap)"),
                    (kv_int8, "kv_int8=True: no int8 layout for a "
                     "latent row"),
                    (tp > 1, "tp > 1: a latent row has no heads to "
                     "shard")))):
            for on, what in refused if keeps else ():
                if on:
                    raise ValueError(
                        "ServingEngine: %s keeps %s; refused with %s"
                        % (type(cfg).__name__, keeps, what))
        if device is not None and tp > 1:
            raise ValueError("ServingEngine: device= places a tp=1 "
                             "engine; a tp>1 engine is placed by its "
                             "mesh")
        if tp > 1:
            # capability check (round 22): the Pallas walk is mesh-
            # lowered — any kernel serves tp>1 provided the heads
            # axis divides (each device walks H/tp heads of the
            # heads-sharded pools; shard_map needs a whole number of
            # heads per device).  The old blanket pallas×tp>1 error
            # is gone; n_heads % tp is the one genuine requirement
            # either kernel has.
            if cfg.n_heads % tp:
                raise ValueError(
                    "ServingEngine: n_heads=%d not divisible by "
                    "tp=%d — the KV pools shard the heads axis"
                    % (cfg.n_heads, tp))
            if isinstance(params, dict) and any(
                    "moe" in layer for layer in params.get("layers",
                                                           ())):
                raise ValueError(
                    "ServingEngine: MoE decode params are tp=1-only "
                    "this round (expert dispatch is not validated "
                    "under the serving mesh; experts would replicate "
                    "with only the FFN hidden dim sharded)")
            if mesh is None:
                from ..parallel.mesh import serving_mesh
                mesh = serving_mesh(tp)
        self.tp = tp
        # a trivial tp=1 mesh takes the unsharded single-device path
        # (sharding constraints over trivial axes are not free on
        # every backend — the live_axis argument in parallel/mesh.py)
        self.mesh = mesh if tp > 1 else None
        if pages_per_slot is None:
            if cfg.max_len is None:
                raise ValueError(
                    "ServingEngine: %s has no position table; give "
                    "pages_per_slot (the context bound is the pool's)"
                    % type(cfg).__name__)
            pages_per_slot = -(-cfg.max_len // page_size)
        # the attention view may be wider than cfg.max_len (its tail
        # is masked scratch); positions are bounded by submit()'s
        # max_len check, which keeps pos_emb indexing in range
        if num_pages is None:
            num_pages = num_slots * pages_per_slot + 1
        if num_pages < pages_per_slot + 1:
            raise ValueError(
                "ServingEngine: num_pages (%d) cannot hold one "
                "max-length request (%d pages + scratch)"
                % (num_pages, pages_per_slot))
        if self.mesh is not None:
            # commit the params into their megatron shards NOW: per-
            # device weight bytes drop ~1/tp from this point on (the
            # "model ~tp× too big for one chip" half of the claim —
            # the pools are the other half)
            import jax
            params = jax.device_put(
                params, _bind(self.mesh,
                              G.decode_param_specs(params, cfg)))
        else:
            # one placement, here: host leaves (a disaggregated
            # worker's params come off the wire as numpy) would
            # otherwise ride to the device again on every step.
            # Leaves already on a device stay where they are unless
            # ``device`` names another.
            import jax
            params = jax.device_put(params, device)
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self.prefill_chunk = prefill_chunk
        self.kv_int8 = bool(kv_int8)
        self.spec_K = int(spec_K)
        self.spec_drafter = spec_drafter
        self.spec_ngram = int(spec_ngram)
        self.max_seq = pages_per_slot * page_size
        # with speculation every decode slot may feed 1 + K rows
        # (pending + drafts); the program shape stays fixed, unused
        # draft rows are dead padding like everything else
        self.n_rows = num_slots * (1 + self.spec_K) + prefill_chunk
        self.cache = PagedKVCache(cfg, num_pages, page_size,
                                  kv_int8=self.kv_int8,
                                  mesh=self.mesh, device=device,
                                  num_slots=num_slots)
        # what the engine is not told it reads from the platform its
        # pools were just placed on (the step program runs where its
        # pools live).  One attention, two lowerings: the walk is the
        # fast one on a TPU, the gather everywhere else
        from ..kernels.platform import platform_of
        on_tpu = platform_of(self.cache.pools) == "tpu"
        if kernel is None:
            kernel = "pallas" if on_tpu else "xla"
        self.kernel = kernel
        # whether attention walks each row's own pages (the Pallas
        # walk, on a pool it can cut pages out of) or reads the whole
        # (rows x pages_per_slot) window: what kv_pages_read books.
        # The walk folds whole turns of F pages: what kv_pages_folded
        # books (0: no walk); it copies groups of G pages and takes K
        # of them a trip of its loop within each block of R rows: what
        # kv_groups_live and kv_chain_slots book
        from ..kernels.paged_attention import walk_geometry
        kv_heads, head_dim, flat_kv = kv_geometry(cfg)
        geometry = kernel == "pallas" and walk_geometry(
            kv_heads // tp, head_dim, page_size, pages_per_slot,
            self.cache.page_dtype, flat=flat_kv,
            latent=bool(latent_row(cfg)))
        self._walk_group, self._walk_turn, self._walk_rows, \
            self._walk_chains = geometry or (0, 0, 0, 0)
        # host-DRAM KV tier (round 18): explicit argument >
        # MXNET_SERVE_TIER_BYTES env > off.  0/None disables — every
        # pre-tier behavior (drop on pressure, recompute on resume)
        # is preserved bit for bit with the tier off.
        self.tier = HostTierStore(tier_bytes) if tier_bytes else None
        # shared-prefix page reuse (round 10): content-keyed trie over
        # the pool; the allocator's pressure callback evicts (round
        # 18: spills) refcount-0 chains before ever refusing a live
        # request
        self.prefix = PrefixCache(self.cache, tier=self.tier) \
            if prefix_cache else None
        if self.prefix is not None:
            self.cache.pressure_cb = self.prefix.evict
        # the step loop's pipeline depth (round 21; the module
        # docstring), a fact the engine reads and no argument: 1, the
        # host plans, stages and launches step N+1 while step N
        # computes, where there is a device to hide behind (PR 29: the
        # chip waited 4 ms of a 12 ms step for the host); 0, it
        # alternates with the device, on XLA:CPU, where the "device" is
        # the host's own cores, and with speculation, whose drafters
        # read committed host tokens
        self.overlap = on_tpu and self.spec_K == 0
        self._copy_fn = None              # jitted COW page copy
        if self.prefix is not None:
            # pre-compile the COW program now (scratch-onto-scratch is
            # a no-op copy): the first real divergence must not stall
            # the serving loop for a compile — page ids are traced
            # scalars, so this one compilation covers every (src, dst)
            self._cow_page(0, 0)
        self._step_fn = _make_step(cfg, num_slots, self.n_rows,
                                   pages_per_slot, page_size,
                                   self.kv_int8, kernel=self.kernel,
                                   n_sample=1 + self.spec_K,
                                   mesh=self.mesh, params=self.params,
                                   overlap=self.overlap)
        self._queue: List[Request] = []
        self._slots: List[Optional[Request]] = [None] * num_slots
        # rid_start: a ServingCluster gives each replica a disjoint
        # rid block so request ids (and their trace swimlanes) stay
        # unique across the whole cluster
        self._next_rid = int(rid_start)
        self.requests: Dict[int, Request] = {}
        self.stats = {"steps": 0, "preemptions": 0, "admitted": 0,
                      "decode_rows": 0, "prefill_rows": 0,
                      "dead_rows": 0, "peak_pages": 0,
                      "prefix_hit_tokens": 0, "cow_copies": 0,
                      "spec_drafted": 0, "spec_accepted": 0,
                      "swap_outs": 0, "swap_ins": 0,
                      "slot_occupancy_sum": 0.0, "overlap_steps": 0,
                      "kv_pages_window": 0, "kv_pages_read": 0,
                      "kv_pages_folded": 0, "kv_groups_live": 0,
                      "kv_chain_slots": 0}
        if self._stateful:
            # slot-states read and written (the live slots of each
            # dispatched step), those started from zero, and the bytes
            # the recurrence REQUIRES: one read and one write of a live
            # slot's state per layer that keeps one.  What the program
            # moves is no
            # less: ``slot_scan``'s single-row pass reads and rewrites
            # the whole (num_slots + 1) pool every step and a chunk's
            # slot is touched once more in its loop, so the two agree
            # only while every slot is live with one row
            self.stats.update(ssm_state_updates=0, ssm_state_resets=0,
                              ssm_state_bytes=0)
        # what the model's module counts on the device a step
        # (``STEP_COUNTERS``, read back behind the tokens) and what
        # ``counter_stats`` makes of it: absent for a family that
        # counts nothing
        self._model = getattr(cfg, "serving", G)
        self._n_counters = len(getattr(self._model, "STEP_COUNTERS", ()))
        if self._n_counters:
            self.stats.update(self._model.counter_stats(
                cfg, self.params, (0,) * self._n_counters))
        # One lock (_mu) guards what the caller of step() shares with
        # the threads that submit, cancel, preempt and admit_prefilled:
        # queue/slots/pages/prefix/stats and the request fields they
        # mutate.  The in-flight step (_inflight) is step()'s own.
        self._mu = threading.Lock()
        self._bufs = (
            _StepBuffers(self.n_rows, num_slots, self.spec_K,
                         pages_per_slot),
            _StepBuffers(self.n_rows, num_slots, self.spec_K,
                         pages_per_slot))
        self._buf_idx = 0
        # canonical block table, patched incrementally at page
        # alloc/free time (satellite: no full rebuild per step); row
        # num_slots stays all-scratch for dead rows
        self._bt = np.zeros((num_slots + 1, pages_per_slot), np.int32)
        # the step on the device at depth 1: (its _Plan, its
        # device-resident next_tok)
        self._inflight = None
        self._tok0 = None            # lazy zeros for the first prev_tok
        if metrics is None:
            # an explicitly supplied registry is a request for
            # telemetry; otherwise the env var decides
            metrics = registry is not None or \
                os.environ.get("MXNET_SERVING_METRICS", "0") == "1"
        elif not metrics and registry is not None:
            raise ValueError(
                "ServingEngine: registry= given but metrics=False — "
                "the registry would be silently ignored")
        self._obs = _EngineObs(registry) if metrics else None
        # optional retire hook (round 15, disaggregated serving): step
        # frees a finished request's pages before returning, but the
        # prefill worker must export them for the handoff stream —
        # the callback runs at retire time, pages still assigned.
        # (Freed page CONTENT stays intact until the NEXT step's
        # allocations, so a post-step export of the snapshotted ids
        # is race-free on the single engine thread.)
        self.retire_cb = None

    # ------------------------------------------------------- intake --
    def submit(self, prompt, max_new_tokens, eos_id=None,
               trace_id=None):
        """Queue a request; returns its id.  prompt: (P,) ints."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("submit: empty prompt")
        if max_new_tokens < 1:
            raise ValueError("submit: max_new_tokens must be >= 1")
        total = prompt.size + max_new_tokens
        if total > self.max_seq:
            raise ValueError(
                "submit: %d tokens > engine max_seq %d (pages_per_slot"
                " * page_size)" % (total, self.max_seq))
        # the final sampled token never enters the cache, so cache
        # positions top out at total - 1 <= max_len (same contract as
        # generate: P + max_new <= cfg.max_len)
        # (a model with no position table is bound by the pool alone)
        if self.cfg.max_len is not None and total > self.cfg.max_len:
            raise ValueError("submit: %d tokens > cfg.max_len=%d"
                             % (total, self.cfg.max_len))
        now = time.perf_counter()
        with self._mu:
            req = Request(rid=self._next_rid, prompt=prompt,
                          max_new_tokens=int(max_new_tokens),
                          eos_id=eos_id, submit_t=now, wait_start=now,
                          trace_id=trace_id)
            self._next_rid += 1
            self.requests[req.rid] = req
            self._queue.append(req)
            if self._obs is not None:
                self._obs.submitted.inc()
                self._obs.g_queued.set(len(self._queue))
            return req.rid

    @property
    def free_slots(self):
        """Decode slots currently unoccupied (the disaggregated decode
        worker admits handed-off requests only when one is free)."""
        return sum(r is None for r in self._slots)

    def admit_prefilled(self, prompt, generated, pages, *,
                        max_new_tokens, eos_id=None, rid=None):
        """Adopt an externally-prefilled request (disaggregated
        serving, round 15): ``pages`` were already allocated from THIS
        engine's cache and installed with the k/v content of positions
        ``[0, P + len(generated) - 1)`` (P = prompt length) — the
        prefill replica's exact pool bytes.  ``generated`` must carry
        at least the prefill side's first sampled token; the request
        resumes mid-decode exactly where a single engine would be
        after committing those tokens (``pending`` = the last one,
        ``n_cached`` = P + len(generated) - 1), so under f32 greedy
        the continuation is bit-identical to an undisturbed run.

        Raises if no slot is free — the caller (the decode worker
        loop) checks ``free_slots`` first and re-tries later rather
        than queueing device pages behind a full engine."""
        if self._stateful:
            raise ValueError(
                "admit_prefilled: %s keeps per-slot recurrent state; "
                "the disaggregated hand-off moves K/V pages and has no "
                "snapshot of that state to install"
                % type(self.cfg).__name__)
        if latent_row(self.cfg):
            raise ValueError(
                "admit_prefilled: %s keeps a latent (MLA) row per token; "
                "the disaggregated hand-off is not shown on latent pages "
                "yet" % type(self.cfg).__name__)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        generated = [int(t) for t in generated]
        if not generated:
            raise ValueError("admit_prefilled: needs >= 1 committed "
                             "token (the prefill side samples the "
                             "first before handoff)")
        if prompt.size < 1:
            raise ValueError("admit_prefilled: empty prompt")
        total = prompt.size + max_new_tokens
        if total > self.max_seq or (self.cfg.max_len is not None
                                    and total > self.cfg.max_len):
            raise ValueError(
                "admit_prefilled: %d tokens > max_seq %d / max_len %r"
                % (total, self.max_seq, self.cfg.max_len))
        with self._mu:
            free = [i for i, r in enumerate(self._slots)
                    if r is None]
            if not free:
                raise RuntimeError("admit_prefilled: no free slot")
            n_cached = prompt.size + len(generated) - 1
            need = -(-n_cached // self.page_size) if n_cached else 0
            if len(pages) < need:
                raise ValueError(
                    "admit_prefilled: %d pages cannot cover %d cached"
                    " positions" % (len(pages), n_cached))
            now = time.perf_counter()
            if rid is None:
                rid = self._next_rid
                self._next_rid += 1
            req = Request(rid=rid, prompt=prompt,
                          max_new_tokens=int(max_new_tokens),
                          eos_id=eos_id, submit_t=now, wait_start=now)
            req.generated = generated
            req.pending = generated[-1]
            req.n_cached = n_cached
            req.n_prefilled = n_cached
            req.pages = list(pages)
            req.slot = free[0]
            req.state = "running"
            req.admit_seq += 1
            self.requests[rid] = req
            self._slots[req.slot] = req
            self._bt_set(req.slot, req.pages)
            self.stats["admitted"] += 1
            if self._obs is not None:
                self._obs.submitted.inc()
                self._obs.admitted.inc()
                self._obs.g_running.set(
                    sum(r is not None for r in self._slots))
            return rid

    def cancel(self, rid):
        """Force-retire a request (frees its slot and pages
        immediately; queued requests are simply dropped).  A cancel
        landing after completion — the inherent client race — is a
        no-op: the finished output stays retrievable."""
        with self._mu:
            req = self.requests[rid]
            if req.state in ("done", "cancelled"):
                return
            if req.state == "queued":
                self._queue.remove(req)
                if self.tier is not None:
                    # a swapped-out victim cancelled while queued must
                    # not squat in the host tier until LRU age-out
                    self.tier.drop(("swap", rid))
            elif req.state == "running":
                self._release(req)
            req.state = "cancelled"
            if self._obs is not None:
                self._obs.cancelled.inc()
                self._obs.g_queued.set(len(self._queue))
                self._obs.g_running.set(
                    sum(r is not None for r in self._slots))
                if profiler.is_recording():
                    cargs = {"state": "cancelled"}
                    if req.trace_id:
                        cargs["trace_id"] = req.trace_id
                    self._obs.trace.add_instant(
                        rid, "retire", time.perf_counter(),
                        args=cargs)
                    self._obs.trace.flush()

    # ----------------------------------------------------- plumbing --
    # (Every helper below mutates scheduler state that submit, cancel
    # and preempt reach from other threads — callers hold the engine
    # lock.)

    def _bt_set(self, slot, pages):
        """Patch the canonical block table's row for ``slot`` to
        ``pages`` (satellite: incremental patching, no per-step
        rebuild).  A local row view keeps the slice stores cheap."""
        # mxlint: requires(ServingEngine._mu)
        row = self._bt[slot]
        n = min(len(pages), row.size)
        row[:n] = pages[:n]
        row[n:] = 0

    def _bt_clear(self, slot):
        # mxlint: requires(ServingEngine._mu)
        self._bt[slot, :] = 0

    # mxlint: requires(ServingEngine._mu)
    def _release(self, req):
        if req.slot is not None:
            # clear the block-table row BEFORE nulling the slot: no
            # window where a freed page id sits in a live-looking row
            self._bt_clear(req.slot)
        if req.pages:
            if req.shared_pages:
                # cache-owned pages stay cached (their refs drop
                # below); only privately-owned pages return to the pool
                self.cache.free([p for p in req.pages
                                 if p not in req.shared_pages])
            else:
                self.cache.free(req.pages)
            req.pages = []
        if req.prefix_entries:
            self.prefix.release(req.prefix_entries)
            req.prefix_entries = []
        req.shared_pages = set()
        req.chain_upto = 0
        if req.slot is not None:
            self._slots[req.slot] = None
            req.slot = None

    # mxlint: requires(ServingEngine._mu)
    def _preempt_for(self, req):
        """Free one+ pages by preempting the youngest running request
        other than ``req``; returns True if anything was preempted."""
        victims = [r for r in self._slots
                   if r is not None and r is not req]
        if not victims:
            return False
        self._preempt_victim(max(victims, key=lambda r: r.rid))
        return True

    # mxlint: requires(ServingEngine._mu)
    def _preempt_victim(self, victim):
        """Evict ``victim`` from its slot and requeue it at the front.
        With a host tier the victim's written pages are SWAPPED OUT
        first — the exact pool bytes of positions ``[0, n_cached)``
        move to host DRAM and resume becomes install-exact instead of
        recompute-exact (O(transfer), not O(prefill)).  The export
        must precede ``_release`` so the pages are captured before
        the free list reclaims them for the very allocation that
        forced this preemption.  A refused swap (tier full/absent)
        falls back to the round-7 recompute path; either resume is
        bit-identical to an undisturbed run under f32 greedy."""
        swapped = False
        if self.tier is not None and victim.n_cached > 0:
            n = -(-victim.n_cached // self.page_size)
            if n * self.cache.bytes_per_page <= self.tier.budget_bytes:
                # budget pre-check BEFORE the device gather: a victim
                # the tier must refuse would otherwise pay a full
                # export round trip per preemption just to throw the
                # bytes away (the export layout is exactly the pool
                # layout, so bytes_per_page predicts the refusal)
                content = self.cache.export_pages(victim.pages[:n])
                swapped = self.tier.put(
                    ("swap", victim.rid), content, n,
                    meta={"n_cached": victim.n_cached,
                          "pending": victim.pending})
            if swapped:
                self.stats["swap_outs"] += 1
        self._release(victim)
        victim.state = "queued"
        victim.n_prefilled = 0
        victim.n_cached = 0
        victim.pending = None
        self._queue.insert(0, victim)
        self.stats["preemptions"] += 1
        if self._obs is not None:
            now = time.perf_counter()
            victim.wait_start = now
            self._obs.preemptions.inc()
            if profiler.is_recording():
                self._obs.trace.add_instant(
                    victim.rid, "preempt", now,
                    args={"committed": len(victim.generated),
                          "swapped": swapped})
        return swapped

    def preempt(self, rid):
        """Force-preempt one RUNNING request through the standard
        victim path (swap-out when a tier is attached) — the chaos /
        benchmark / ops lever behind the swap-vs-recompute resume
        measurement.  Returns True if the pages were swapped out,
        False for a recompute-resume preemption."""
        with self._mu:
            req = self.requests[rid]
            if req.state != "running":
                raise ValueError(
                    "preempt(%d): request is %s, not running"
                    % (rid, req.state))
            return self._preempt_victim(req)

    def _cow_page(self, src, dst):
        """Device-copy page ``src`` into ``dst`` across every layer
        pool (copy-on-write at a shared-prefix divergence) via the
        module-level keyed-cache program (``_make_copy``); pools are
        donated and update in place like the step program's."""
        if self._copy_fn is None:
            self._copy_fn = _make_copy(self.cfg, self.kv_int8,
                                       mesh=self.mesh)
        self.cache.pools = self._copy_fn(self.cache.pools, src, dst)

    # mxlint: requires(ServingEngine._mu)
    def _insert_prefix(self, req):
        """Donate req's freshly-completed, fully-prompt-covered pages
        to the prefix cache (so later requests sharing the prefix skip
        their prefill).  Pages past ``chain_upto`` whose every position
        is both written (n_cached) and prompt-derived qualify."""
        upto = min(req.prompt.size, req.n_cached) // self.page_size
        if upto <= req.chain_upto:
            return
        new = self.prefix.insert_chain(req.prompt, req.pages, upto,
                                       from_page=req.chain_upto)
        for j, entry in new:
            req.shared_pages.add(req.pages[j])
            req.prefix_entries.append(entry)
        req.chain_upto = upto

    # mxlint: requires(ServingEngine._mu)
    def _ensure_page(self, req, pos):
        """Make req's block table cover position pos (allocating, or
        preempting another request when the pool is dry)."""
        idx = pos // self.page_size
        grew = idx >= len(req.pages)
        while idx >= len(req.pages):
            got = self.cache.alloc(1)
            if got is None:
                if not self._preempt_for(req):
                    raise RuntimeError(
                        "ServingEngine: page pool exhausted by a "
                        "single request — grow num_pages")
                continue
            req.pages.extend(got)
        if grew and req.slot is not None:
            self._bt_set(req.slot, req.pages)
        return True

    # mxlint: requires(ServingEngine._mu)
    def _admit(self):
        while self._queue:
            free_slots = [i for i, r in enumerate(self._slots)
                          if r is None]
            if not free_slots:
                return
            req = self._queue[0]
            inp = req.resume_input
            if self.tier is not None:
                swapped = self._swap_in(req, inp, free_slots[0])
                if swapped == "admitted":
                    continue
                if swapped == "stall":
                    return
            total = -(-min(inp.size + 1, self.max_seq)
                      // self.page_size)
            # shared-prefix match: map cached pages read-only, skip
            # their prefill rows.  Always re-feed at least the final
            # input token — the step program needs one live row at the
            # end of the input to produce this request's logits.
            entries, hit_pages, m_tok = ([], [], 0) \
                if self.prefix is None else self.prefix.match(inp)
            skip = min(m_tok, inp.size - 1)
            cow_idx = skip // self.page_size
            cow = cow_idx < len(hit_pages)
            try:
                got = self.cache.alloc(total - len(hit_pages)
                                       + (1 if cow else 0))
            except BaseException:
                # pylocklint py-ref-leak (round 12): alloc can raise
                # through the pressure callback — the refs match()
                # just took must not leak on that edge, or the chain
                # stays pinned unevictable for the engine's lifetime
                if entries:
                    self.prefix.release(entries)
                raise
            if got is None:
                if entries:
                    self.prefix.release(entries)
                return                     # stall admission, not decode
            self._queue.pop(0)
            req.pages = list(hit_pages)
            req.shared_pages = set(hit_pages)
            req.prefix_entries = entries
            if cow:
                # the first position this request writes falls inside
                # the last mapped page (partial-page match, or a
                # whole-input match re-feeding its final token):
                # copy-on-write it into a private page before any row
                # targets it — the shared page is never written
                assert cow_idx == len(hit_pages) - 1
                priv = got.pop()
                self._cow_page(hit_pages[cow_idx], priv)
                req.pages[cow_idx] = priv
                req.shared_pages.discard(hit_pages[cow_idx])
                self.prefix.release([req.prefix_entries.pop()])
                self.prefix.note_cow()
                self.stats["cow_copies"] += 1
            req.chain_upto = len(req.prefix_entries)
            req.pages.extend(got)
            if self.prefix is not None:
                self.prefix.note_admit(skip, inp.size,
                                       len(req.shared_pages))
                self.stats["prefix_hit_tokens"] += skip
                req.prefix_hit_tokens = skip
            req.slot = free_slots[0]
            req.state = "running"
            req.admit_seq += 1
            req.n_prefilled = skip
            req.n_cached = skip
            req.pending = None
            self._slots[req.slot] = req
            self._bt_set(req.slot, req.pages)
            self.stats["admitted"] += 1
            if self._obs is not None:
                now = time.perf_counter()
                self._obs.admitted.inc()
                self._obs.h_admission.observe(
                    (now - req.wait_start) * 1e3)
                if profiler.is_recording():
                    self._obs.trace.add_span(
                        req.rid, "admission_wait", req.wait_start, now)
                    if req.generated:
                        self._obs.trace.add_instant(req.rid, "resume",
                                                    now)

    # mxlint: requires(ServingEngine._mu)
    def _swap_in(self, req, inp, slot):
        """Install-exact resume (round 18): if ``req`` was preempted
        with its pages swapped to the host tier, re-install the exact
        pool bytes and resume at the saved ``(n_cached, pending)``
        state — no re-prefill, O(transfer).  Returns ``"admitted"``
        (slot taken, caller continues), ``"stall"`` (the pool cannot
        hold the swap right now — admission stalls exactly like the
        round-7 alloc-refused path, the tier entry is kept for the
        next try), or ``"none"`` (no swap entry: the caller runs the
        normal match/alloc admission — a swap LRU-evicted from the
        tier degrades to recompute-exact, never to wrong)."""
        entry = self.tier.peek(("swap", req.rid))
        if entry is None:
            return "none"
        # same page coverage as the normal admission path: the
        # chunked-prefill plan (a mid-prefill victim resumes its
        # remaining prompt rows) assumes every input position's page
        # already exists — the swapped pages are a PREFIX of that set
        total = max(entry.n_pages,
                    -(-min(inp.size + 1, self.max_seq)
                      // self.page_size))
        got = self.cache.alloc(total)
        if got is None:
            return "stall"
        entry = self.tier.pop(("swap", req.rid))
        if entry is None:
            # evicted between peek and pop (the alloc's own pressure
            # spills insert ahead of it; peek pinned recency, so this
            # is a can't-fit-both corner): give the pages back and
            # recompute
            self.cache.free(got)
            return "none"
        self.cache.install_pages(got[:entry.n_pages], entry.content)
        self._queue.pop(0)
        req.pages = got
        req.shared_pages = set()
        req.prefix_entries = []
        req.chain_upto = 0
        req.slot = slot
        req.state = "running"
        req.admit_seq += 1
        req.n_cached = entry.meta["n_cached"]
        req.pending = entry.meta["pending"]
        # a decode-phase victim resumes fully prefilled; a victim
        # caught mid-prefill (pending is None) continues its chunked
        # prefill from the first unwritten position
        req.n_prefilled = inp.size if req.pending is not None \
            else req.n_cached
        self._slots[slot] = req
        self._bt_set(slot, req.pages)
        self.stats["admitted"] += 1
        self.stats["swap_ins"] += 1
        if self._obs is not None:
            now = time.perf_counter()
            self._obs.admitted.inc()
            self._obs.h_admission.observe((now - req.wait_start) * 1e3)
            if profiler.is_recording():
                self._obs.trace.add_span(
                    req.rid, "admission_wait", req.wait_start, now)
                self._obs.trace.add_instant(
                    req.rid, "resume", now,
                    args={"swap_in": True,
                          "pages": len(req.pages)})
        return "admitted"

    # mxlint: requires(ServingEngine._mu)
    def _plan_speculation(self):
        """Phase-A speculation planning: for every running decode row
        propose K_eff draft tokens (host-side — the drafters are
        vectorized so this prices like the rest of the per-step host
        scheduling) and secure pages through the deepest draft write
        position.  K_eff = min(spec_K, tokens this request may still
        commit) keeps every draft's cache position within the
        request's admitted budget (positions top out at
        prompt+max_new-1, the same bound submit() enforced), so no
        extra headroom is ever needed.  Returns {rid: drafts (K_eff,)
        np.int32}.  MUST run before any row is built — _ensure_page
        may preempt (the phase-A contract in step())."""
        plan = {}
        if self.spec_K < 1:
            return plan
        vmax = self.cfg.vocab_size - 1
        for req in list(self._slots):
            if req is None or req.pending is None:
                continue
            k_eff = min(self.spec_K,
                        req.max_new_tokens - len(req.generated))
            if k_eff < 1:
                continue
            buf = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)])
            if callable(self.spec_drafter):
                d = np.asarray(self.spec_drafter(buf, k_eff),
                               np.int32).reshape(-1)
                if d.size != k_eff:
                    raise ValueError(
                        "spec_drafter returned %d proposals, wanted "
                        "%d" % (d.size, k_eff))
                # clamp into the vocab: an out-of-range proposal would
                # index-clamp inside the program and silently verify
                # as a different token
                d = np.clip(d, 0, vmax)
            else:
                d = drafters.ngram_draft(buf, k_eff, self.spec_ngram)
            self._ensure_page(req, req.n_cached + k_eff)
            # _ensure_page never preempts req itself, but it may have
            # preempted a LATER slot this loop already planned — the
            # build phase skips slot-less requests, so a stale plan
            # entry is never fed
            plan[req.rid] = d
        return plan

    # --------------------------------------------------------- step --
    def step(self):
        """One engine iteration.  Returns the list of request ids
        whose COMMIT landed during this call (possibly empty); False
        when there is nothing left to do.  One body, whose pipeline
        depth the engine read from its platform (``self.overlap``):
        build the next plan, stage and launch it; then, at depth 0,
        read that step back and commit it; at depth 1, read back and
        commit the step that WAS in flight — launched by the call
        before, against whose device-resident tokens this call's was
        dispatched — and leave this call's in flight, so a request's
        finish is reported one call after the step that produced its
        last token.  Tokens are the same at either depth.  All of it
        runs on the caller's thread.

        Every call that finds work is one ``engine.step``
        ``profiler.span`` whose children are its phases —
        ``engine.plan``, ``engine.stage``, ``engine.launch``,
        ``engine.wait``, ``engine.commit`` — whether or not metrics
        are on (docs/observability.md, "Spans on the device's
        clock")."""
        with self._mu:
            # made before the span opens: a call that finds no work is
            # no step
            if self._inflight is None and self._idle():
                return False
        with profiler.span("engine.step", os=True,
                           step=self.stats["steps"]) as sp:
            with profiler.span("engine.plan"), self._mu:
                plan = self._build_plan()
            self._span_args(sp, plan)
            launched = None
            if not (plan.empty and self.overlap):
                # (depth 1 dispatches no empty plan: every live request
                # rides the in-flight step, which is all there is to
                # drain)
                with self._operator_span():
                    launched = plan, self._dispatch(plan)
            if self.overlap:
                # this call's step stays in flight; the one to read
                # back is the step the call before left there
                launched, self._inflight = self._inflight, launched
            return [] if launched is None \
                else self._drain(*launched, sp.t0)

    def _idle(self):
        return not self._queue and all(r is None for r in self._slots)

    def _operator_span(self):
        """The step program is the serving layer's "operator": with
        metrics on, a recording profiler logs its dispatch as a
        cat-"operator" ``serving_step`` event interleaved with the
        request spans of ``_commit``."""
        if self._obs is None:
            return _NO_SPAN
        return profiler.span("serving_step", cat="operator")

    def _span_args(self, sp, plan):
        """What the step's ``engine.step`` span says of its plan."""
        args = {"decode": plan.n_dec_rows, "prefill": plan.n_pre_rows,
                "dead": self.n_rows - plan.n_rows_used,
                "pages": plan.kv_pages}
        if self._stateful:
            args["resets"] = plan.resets
        sp.set(**args)

    def _drain(self, plan, tok, t0):
        """Block on a dispatched step's sampled tokens and commit it.
        At depth 1 this runs AFTER the next step was dispatched — the
        readback waits out step N's tail while N+1 executes."""
        with profiler.span("engine.wait", cpu=True) as wait:
            # mxlint: allow(host-sync) -- intentional: the ONE device
            # sync per step — at depth 1 one step BEHIND dispatch (the
            # latency-hiding point); the host branches on step N's
            # tokens (stop conditions, commits) while step N+1
            # executes
            next_tok = np.asarray(tok)
        with profiler.span("engine.commit"), self._mu:
            return self._commit(plan, next_tok, wait.t1, t0)

    def close(self):
        """Let go of the in-flight step (idempotent).  The engine
        starts no thread, so there is nothing to stop or join; callers
        that own engines (clusters, the benchmark's driver) call this
        when they are done with one."""
        self._inflight = None

    # mxlint: requires(ServingEngine._mu)
    def _build_plan(self):
        """Phases A+B of the engine step — admission, page
        allocation, speculation planning, and the fixed-shape row
        batch — built into the next rotated buffer set and recorded
        as a ``_Plan``.  Where a step is in flight (depth 1) it
        additionally plans CARRIED decode rows for that step's
        samplers: their input token is the in-flight step's
        device-resident argmax (``tok_src``), their position the
        in-flight sampling position + 1 — the dispatch never waits for
        the readback.  Everything the later commit needs is recorded
        here at build time (plan k+1 is built before k's commit
        runs).

        The rows are PACKED: carried, decode (each with its draft
        rows), then prefill rows fill rows ``0 .. n_rows_used - 1``,
        and every row after them is dead.  The step program walks
        pages up to the last live row only (``_body``'s ``n_live``), so
        a dead row between live ones would be walked, correctly and
        for nothing."""
        plan = _Plan()
        plan.pipelined = self.overlap
        inflight = self._inflight[0] if self._inflight else None
        self._admit()

        # ---- phase A: secure pages.  _ensure_page may PREEMPT the
        # youngest running request, so all allocation happens before
        # any row is built — a victim preempted here simply has no
        # rows this step (build skips slot-less requests); allocating
        # mid-build could free pages a built row already targets.
        carried = {}                   # rid -> device-carried position
        if inflight is not None:
            for req in inflight.samplers:
                if not inflight.current(req):
                    continue           # preempted/cancelled mid-flight
                if len(req.generated) + 1 >= req.max_new_tokens:
                    # the in-flight token predictably finishes this
                    # request — its slot idles one step and retires
                    # at the drain (never decode past the budget)
                    continue
                pos = inflight.decode_pos[req.rid] + 1
                self._ensure_page(req, pos)
                carried[req.rid] = pos
        # (a sampler preempted and admitted again since is no longer
        # the in-flight step's: it prefills from its first token)
        inflight_rids = set() if inflight is None else \
            {req.rid for req in inflight.samplers
             if inflight.current(req)}
        for req in list(self._slots):
            if req is not None and req.pending is not None \
                    and req.rid not in inflight_rids:
                self._ensure_page(req, req.n_cached)
        # speculation planning (drafting + draft-depth pages) is part
        # of phase A for the same reason (a speculating engine is at
        # depth 0: the drafters read committed host tokens)
        spec_plan = self._plan_speculation()
        plan.spec_plan = spec_plan
        budget = self.prefill_chunk
        pre = {}                           # rid -> prefill rows planned
        for req in list(self._slots):
            if req is None or req.pending is not None or budget <= 0:
                continue
            n = min(budget, req.resume_input.size - req.n_prefilled)
            # _admit allocated ceil((input+1)/page_size) pages, so
            # every prefill position is already covered — only the
            # decode-row loop and _plan_speculation above can allocate
            # (and thus preempt); keep BOTH before this point
            assert (req.n_prefilled + n - 1) // self.page_size \
                < len(req.pages)
            pre[req.rid] = n
            budget -= n

        # ---- phase B: build the fixed-shape row batch into the next
        # rotated buffer set (satellite: persistent buffers — the set
        # the in-flight step was staged from is never touched) ----
        obs = self._obs
        tracing = obs is not None and profiler.is_recording()
        buf = self._bufs[self._buf_idx]
        self._buf_idx ^= 1
        buf.reset(self.num_slots)
        np.copyto(buf.bt, self._bt)        # canonical, patched at
        plan.buf = buf                     # alloc/free — no rebuild
        T, S = self.n_rows, self.num_slots
        tokens, row_slot = buf.tokens, buf.row_slot
        row_pos, row_live = buf.row_pos, buf.row_live
        slot_rows, tok_src = buf.slot_rows, buf.tok_src
        samplers = plan.samplers
        r = 0
        # carried decode rows (depth 1 only): input = the in-flight
        # step's argmax for this slot, read on device via tok_src
        if inflight is not None:
            for req in inflight.samplers:
                if req.rid not in carried:
                    continue
                pos = carried[req.rid]
                row_slot[r] = req.slot
                row_pos[r] = pos
                row_live[r] = True
                tok_src[r] = req.slot
                slot_rows[req.slot, 0] = r
                samplers.append(req)
                plan.admit[req.rid] = req.admit_seq
                plan.decode_pos[req.rid] = pos
                plan.was_decode[req.rid] = True
                plan.carried += 1
                self.stats["decode_rows"] += 1
                plan.n_dec_rows += 1
                if tracing:
                    plan.decode_rids.append(req.rid)
                r += 1
        for req in list(self._slots):      # decode (+ draft) rows
            if req is None or req.pending is None \
                    or req.rid in inflight_rids:
                continue
            tokens[r] = req.pending
            row_slot[r] = req.slot
            row_pos[r] = req.n_cached
            row_live[r] = True
            slot_rows[req.slot, 0] = r
            samplers.append(req)
            plan.admit[req.rid] = req.admit_seq
            plan.decode_pos[req.rid] = req.n_cached
            plan.was_decode[req.rid] = True
            self.stats["decode_rows"] += 1
            plan.n_dec_rows += 1
            if tracing:
                plan.decode_rids.append(req.rid)
            r += 1
            # draft rows: positions n_cached+1 .. n_cached+K_eff, one
            # verify argmax read back per row.  Their k/v lands in the
            # cache like any row's; rejected tails are overwritten at
            # the committed position before any mask exposes them
            # (pointer-only rollback, the _decode_block argument).
            for i, d in enumerate(spec_plan.get(req.rid, ())):
                tokens[r] = d
                row_slot[r] = req.slot
                row_pos[r] = req.n_cached + 1 + i
                row_live[r] = True
                slot_rows[req.slot, 1 + i] = r
                r += 1
        for req in list(self._slots):      # chunked prefill rows
            if req is None or req.pending is not None \
                    or req.rid in inflight_rids:
                continue
            inp = req.resume_input
            p0 = req.n_prefilled
            sampled = False
            if pre.get(req.rid, 0):
                plan.state_slots += 1
                if p0 == 0:
                    # the slot's first chunk (a new request, or one
                    # resumed after a preemption, recomputed from its
                    # first token): its state starts from zero, masked
                    # inside the step program
                    buf.fresh[req.slot] = True
                    plan.resets += 1
            for _ in range(pre.get(req.rid, 0)):
                p = req.n_prefilled
                tokens[r] = inp[p]
                row_slot[r] = req.slot
                row_pos[r] = p
                row_live[r] = True
                req.n_prefilled += 1
                self.stats["prefill_rows"] += 1
                if req.n_prefilled == inp.size:
                    slot_rows[req.slot, 0] = r
                    samplers.append(req)
                    plan.admit[req.rid] = req.admit_seq
                    plan.decode_pos[req.rid] = p
                    plan.was_decode[req.rid] = False
                    sampled = True
                r += 1
            if not sampled:
                # still mid-prefill: the commit advances n_cached to
                # the rows THIS plan wrote (recorded now — by commit
                # time the next build may have pushed n_prefilled on)
                plan.prefill_mid.append((req, req.n_prefilled))
                plan.admit[req.rid] = req.admit_seq
            if tracing and req.n_prefilled > p0:
                plan.prefill_spans.append((req.rid, p0,
                                           req.n_prefilled))

        plan.n_rows_used = r
        plan.n_pre_rows = sum(pre.values())
        plan.state_slots += plan.n_dec_rows
        plan.empty = r == 0
        if r or not self.overlap:
            # an empty plan is never dispatched at depth 1 — don't book
            # a phantom batch (depth 0 dispatches dead batches only
            # when the idle check already found work)
            self.stats["dead_rows"] += T - r
            # how far the attention's reads follow the rows: the
            # window is every row's whole table; the walk reads up to
            # each live row's own position, and nothing of the dead
            # rows after them
            window = T * self.pages_per_slot
            plan.kv_pages = window
            if self._walk_turn:
                # a row's live pages, and those rounded up to the
                # whole turns the walk's two contractions run over
                live = row_pos // self.page_size + 1
                live[r:] = 0
                F = self._walk_turn
                plan.kv_pages = int(live.sum())
                self.stats["kv_pages_folded"] += int(
                    ((live + F - 1) // F * F).sum())
                # the groups that hold a live page, and the chain
                # slots of the trips that fold them: each block of R
                # rows rounds its groups up to whole trips of K
                G, K = self._walk_group, self._walk_chains
                groups = (live + G - 1) // G
                trips = -(-np.add.reduceat(
                    groups, np.arange(0, T, self._walk_rows)) // K)
                self.stats["kv_groups_live"] += int(groups.sum())
                self.stats["kv_chain_slots"] += int(trips.sum()) * K
            self.stats["kv_pages_window"] += window
            self.stats["kv_pages_read"] += plan.kv_pages
            if self._stateful:
                self.stats["ssm_state_updates"] += plan.state_slots
                self.stats["ssm_state_resets"] += plan.resets
                self.stats["ssm_state_bytes"] += 2 * plan.state_slots \
                    * self.cache.bytes_per_slot_state
            self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                           self.cache.pages_in_use)
            self.stats["slot_occupancy_sum"] += \
                sum(r_ is not None for r_ in self._slots) / float(S)
        return plan

    def _dispatch(self, plan):
        """Stage a plan's host buffers and launch the step program
        (asynchronous — the device array returns immediately).  No
        lock: the buffers are plan-owned and the pool handoff happens
        only on the engine thread."""
        import jax.numpy as jnp

        buf = plan.buf
        with profiler.span("engine.stage"):
            staged = [jnp.asarray(buf.tokens), jnp.asarray(buf.row_slot),
                      jnp.asarray(buf.row_pos), jnp.asarray(buf.row_live),
                      jnp.asarray(buf.bt), jnp.asarray(buf.slot_rows)]
            if self._stateful:
                staged.append(jnp.asarray(buf.fresh))
            if self.overlap:
                if self._inflight is not None:
                    prev = self._inflight[1]
                else:
                    if self._tok0 is None:
                        # committed where the pools are (``device=``),
                        # as the steps' own output will then be: a
                        # first step fed an uncommitted array is
                        # lowered, and its executable fetched, a second
                        # time when the second step brings the
                        # committed one
                        import jax
                        tok0 = jnp.zeros(
                            (self.num_slots + self._n_counters,
                             1 + self.spec_K), jnp.int32)
                        pool = jax.tree_util.tree_leaves(
                            self.cache.pools)[0]
                        if self.mesh is None and pool.committed:
                            tok0 = jax.device_put(tok0, pool.sharding)
                        self._tok0 = tok0
                    prev = self._tok0
                staged += [prev, jnp.asarray(buf.tok_src)]
        with profiler.span("engine.launch"):
            next_tok, self.cache.pools = self._step_fn(
                self.params, self.cache.pools, *staged)
        return next_tok

    # mxlint: requires(ServingEngine._mu)
    def _commit(self, plan, next_tok, now, t_step0):
        """Phase C: consume a completed step's sampled tokens — stop
        conditions, retirement, metrics.  At depth 1 this runs one
        step after the plan was built (and after the NEXT plan was
        already built), so it reads no live scheduler position: every
        one it needs was recorded on the plan at build time."""
        obs = self._obs
        tracing = obs is not None and profiler.is_recording()
        self.stats["steps"] += 1
        if plan.pipelined:
            self.stats["overlap_steps"] += 1
        finished = []
        spec_spans = []                    # trace: (rid, drafted, accepted)
        for req in plan.samplers:
            if not plan.current(req):
                continue                   # preempted/cancelled
            was_decode = plan.was_decode[req.rid]
            # rows written this step are now cached (the recorded
            # sampling position, NOT live scheduler state)
            req.n_cached = plan.decode_pos[req.rid] + 1
            if self.prefix is not None:
                # donate completed prompt pages BEFORE a possible
                # same-step retire releases them
                self._insert_prefix(req)
            row = next_tok[req.slot]       # (1 + spec_K,) argmaxes
            drafts = plan.spec_plan.get(req.rid) if was_decode \
                else None
            if drafts is not None and drafts.size:
                # greedy verify: row[i] is the target's own argmax
                # after pending + drafts[:i]; accept the longest
                # matching draft prefix plus the target token at the
                # first mismatch — exactly generate_speculative's
                # greedy accept rule, per row instead of batch-min
                k_eff = drafts.size
                a = 0
                while a < k_eff and int(drafts[a]) == int(row[a]):
                    a += 1
                commit = [int(row[i]) for i in range(a + 1)]
                # accepted drafts are ALREADY in the cache at
                # n_cached..n_cached+a-1 (their rows wrote this step)
                req.n_cached += a
                self.stats["spec_drafted"] += k_eff
                self.stats["spec_accepted"] += a
                if obs is not None:
                    obs.spec_drafted.inc(k_eff)
                    obs.spec_accepted.inc(a)
                    obs.spec_rejected.inc(k_eff - a)
                if tracing:
                    spec_spans.append((req.rid, k_eff, a))
            else:
                commit = [int(row[0])]
            if obs is not None:
                if req.token_times:
                    obs.h_tbt.observe(
                        (now - req.token_times[-1]) * 1e3)
                elif not req.generated:
                    obs.h_ttft.observe((now - req.submit_t) * 1e3)
                    if tracing:
                        obs.trace.add_instant(
                            req.rid, "first_token", now,
                            args={"trace_id": req.trace_id}
                            if req.trace_id else None)
            done = False
            for tok in commit:
                req.generated.append(tok)
                req.token_times.append(now)
                req.pending = tok
                if obs is not None:
                    obs.tokens.inc()
                if (len(req.generated) >= req.max_new_tokens
                        or (req.eos_id is not None
                            and tok == req.eos_id)):
                    done = True
                    break
            if done:
                req.state = "done"
                if self.retire_cb is not None:
                    self.retire_cb(req)
                self._release(req)
                finished.append(req.rid)
                if obs is not None:
                    obs.finished.inc()
                    if tracing:
                        rargs = {"tokens": len(req.generated)}
                        if req.trace_id:
                            rargs["trace_id"] = req.trace_id
                        obs.trace.add_instant(req.rid, "retire", now,
                                              args=rargs)
        # slots that fed prefill rows but did not finish their input
        # this step just advance n_cached — to the position recorded
        # at build time (by now the next build may have pushed
        # n_prefilled past what THIS step's rows actually wrote)
        for req, p1 in plan.prefill_mid:
            if not plan.current(req):
                continue
            req.n_cached = max(req.n_cached, p1)
            if self.prefix is not None:
                self._insert_prefix(req)
        if self._n_counters:
            for name, n in self._model.counter_stats(
                    self.cfg, self.params,
                    next_tok[self.num_slots:, 0]).items():
                self.stats[name] += n

        if obs is not None:
            dead = self.n_rows - plan.n_rows_used
            obs.steps.inc()
            obs.h_step.observe((now - t_step0) * 1e3)
            # row-mix counters increment by THIS step's amounts (never
            # assigned wholesale: engines sharing a caller-supplied
            # registry must aggregate, not clobber); gauges carry the
            # step's prefill-vs-decode mix (plan rows were all fed —
            # the phase-A assert guarantees page coverage)
            obs.decode_rows.inc(plan.n_dec_rows)
            obs.prefill_rows.inc(plan.n_pre_rows)
            obs.dead_rows.inc(dead)
            obs.g_step_decode.set(plan.n_dec_rows)
            obs.g_step_prefill.set(plan.n_pre_rows)
            obs.g_step_dead.set(dead)
            obs.g_running.set(sum(r_ is not None
                                  for r_ in self._slots))
            obs.g_queued.set(len(self._queue))
            if self.stats["spec_drafted"]:
                obs.g_spec_accept_rate.set(
                    self.stats["spec_accepted"]
                    / self.stats["spec_drafted"])
            obs.sync_cache(self.cache)
            if self.prefix is not None:
                obs.sync_prefix(self.prefix)
            if self.tier is not None:
                obs.sync_tier(self.tier, self.stats["swap_outs"],
                              self.stats["swap_ins"])
            if tracing:
                for rid in plan.decode_rids:
                    obs.trace.add_span(rid, "decode", t_step0, now)
                for rid, k_eff, a in spec_spans:
                    obs.trace.add_span(rid, "spec_verify", t_step0,
                                       now, args={"drafted": k_eff,
                                                  "accepted": a})
                for rid, p0, p1 in plan.prefill_spans:
                    obs.trace.add_span(rid, "prefill[%d:%d)"
                                       % (p0, p1), t_step0, now,
                                       args={"rows": p1 - p0})
                obs.trace.flush()
        return finished

    def run(self):
        """Drain: step until every submitted request is done (or
        cancelled).  Returns {rid: (P + generated,) int32}."""
        while True:
            out = self.step()
            if out is False:
                break
        return {rid: req.output for rid, req in self.requests.items()
                if req.state == "done"}

    # --------------------------------------------------- accounting --
    @property
    def metrics_enabled(self):
        return self._obs is not None

    @property
    def registry(self):
        """The engine's ``obs.MetricsRegistry`` (None when metrics are
        disabled)."""
        return self._obs.registry if self._obs is not None else None

    def reset_metrics(self):
        """Zero this engine's telemetry in place (warmup exclusion in
        benches): registry values, the allocator's cumulative ints,
        AND the delta tracker that folds the latter into the former —
        resetting the first two but not the third would silently
        swallow the warmup's worth of post-reset allocations."""
        if self._obs is None:
            return
        self._obs.registry.reset_values()
        self.cache.reset_telemetry()
        self._obs._cache_seen = [0, 0, 0, 0]
        if self.prefix is not None:
            self.prefix.lookups_total = 0
            self.prefix.lookup_tokens_total = 0
            self.prefix.hit_tokens_total = 0
            self.prefix.pages_hit_total = 0
            self.prefix.pages_inserted_total = 0
            self.prefix.pages_evicted_total = 0
            self.prefix.cow_total = 0
            self.prefix.pages_spilled_total = 0
            self.prefix.pages_restored_total = 0
            self.prefix.warm_hits_total = 0
            self.prefix.warm_hit_tokens_total = 0
            self._obs._prefix_seen = [0, 0, 0, 0, 0, 0]
        if self.tier is not None:
            self.tier.reset_telemetry()
            with self._mu:
                self.stats["swap_outs"] = 0
                self.stats["swap_ins"] = 0
            self._obs._tier_seen = [0, 0, 0, 0]
            self._obs._swap_seen = [0, 0]
        self._obs._warm_seen = [0]

    def metrics(self):
        """JSON-able telemetry snapshot: this engine's counters/gauges,
        histogram summaries (count/sum/p50/p95/p99 ms), and — when the
        native runtime is loaded — the dependency engine's
        ``MXEngineStats``.  ``{"enabled": False}`` when metrics are
        off."""
        if self._obs is None:
            return {"enabled": False}
        snap = self._obs.registry.snapshot()
        snap["enabled"] = True
        try:
            from .. import native
            if native.available():
                snap["native_engine"] = native.engine_stats()
        except Exception:
            pass
        return snap

    @property
    def hbm_held(self):
        return self.cache.bytes_held

    @property
    def hbm_pool(self):
        return self.cache.bytes_pool

    @property
    def hbm_held_per_device(self):
        """Per-device share of the allocated page bytes (= hbm_held /
        tp — pages shard the heads axis, so the split is exact)."""
        return self.cache.bytes_held_per_device

    @property
    def hbm_pool_per_device(self):
        return self.cache.bytes_pool_per_device
