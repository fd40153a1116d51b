"""Continuous-batching GPT serving engine over a paged KV cache.

The fixed-batch decode entry points (``models/gpt.py generate`` /
``generate_speculative``) assume a batch of requests that start and
finish together, with one contiguous max-seq KV allocation per slot —
real mixed-length traffic pays padding in both HBM and tokens/sec.
This package is the Orca-style fix: in-flight (iteration-level)
batching with a vLLM-style paged KV cache.

- ``paged_kv.PagedKVCache`` — fixed-size pages in one preallocated
  pool per layer, per-request block tables, host-side free-list
  allocator, int8-KV supported via the existing per-(row, token)
  scale layout.
- ``engine.ServingEngine`` — admits new requests into free decode
  slots each iteration, runs (chunked) prefill for admitted requests
  and one decode step for running requests in a SINGLE compiled XLA
  program (padded to static slot/page shapes: exactly one compilation
  per config), retires finished sequences, and recycles their pages.

Round 10 adds the cluster layer above the engine:

- ``prefix_cache.PrefixCache`` — refcounted shared-prefix page reuse
  inside the paged pool: prompt pages are content-keyed per prefix
  chain, matching requests map them read-only (copy-on-write at the
  first divergent token), refcount-0 chains are LRU-evicted under
  pool pressure.  ``ServingEngine(prefix_cache=True)``.
- ``cluster.ServingCluster`` — N engine replicas (threads
  in-process) behind one async ``submit()/result()`` API:
  least-loaded routing with prefix affinity, bounded admission queue
  with backpressure + per-request TTL, health checks, watchdog
  failover with recompute-exact resubmission, graceful
  drain/scale-down.

Round 11 adds the raw-decode-speed levers (ROADMAP item 2):

- ``ServingEngine(kernel="pallas")`` — the step program attends via
  the fused block-table-walk Pallas kernel
  (``kernels/paged_attention.py``: online-softmax over pages, int8
  dequant in the inner loop, no materialized gather); ``"xla"`` keeps
  the gather + ``_attend_rows`` path, cross-checked by tests.  Left
  unset, an engine on a TPU takes the walk and any other the gather.
- ``ServingEngine(spec_K=K)`` — in-engine speculative decode:
  host-side drafting (``drafters.ngram_draft``) feeds K extra rows
  per decode slot into the SAME step program, which verifies every
  row's drafts in one batched forward; accepts commit by pointer
  advance, rejections roll back exactly.

Benchmark: ``benchmark/serve_bench.py`` (Poisson arrivals over a mixed
prompt/output-length distribution; ``--replicas N
--shared-prefix-frac F`` for the cluster section; ``--kernel`` /
``--spec-K`` / ``--kernel-ablation`` / ``--spec-sweep`` for the
round-11 levers); gates ``gpt_serve_mixed_tok_s`` /
``gpt_serve_prefix_hit_ttft_ms`` / ``gpt_serve_decode_step_ms``.
Exactness: paged greedy decode is token-identical to ``generate``
under f32, through the cluster as well — prefix hits, COW divergence,
mid-flight replica failure, either attention kernel, and speculation
with arbitrary drafters included (``tests/test_serving.py``,
``tests/test_serving_cluster.py``).

Round 15 disaggregates the cluster across OS processes
(ROADMAP item 3):

- ``transport.py`` — framed zero-copy messaging over the
  ``parallel/dist.py`` raw-frame wire (tensor bytes never pickle).
- ``page_streamer.py`` — prefill→decode KV-page streaming pipelined
  with prefill chunks; decode-side staging installer.
- ``cluster.DisaggServingCluster`` — router + spawned prefill/decode
  worker PROCESSES: chunked prefill on one process streams int8/f32
  KV pages to a decode process that picks the request up at
  ``n_cached = prompt_len``; the prefix trie's knowledge lives in a
  router-owned ``ClusterPrefixIndex`` so a hot prefix is prefilled
  once per CLUSTER and fetched (raw page bytes) by whoever needs it;
  SIGKILL of any worker fails over recompute-exact from the token
  stream.  ``serve_bench --disagg``;
  ``gpt_serve_disagg_remote_hit_ttft_ms`` gate;
  ``tests/test_serving_disagg.py`` (slow group j).

Round 16 adds the traffic-realism layer (ROADMAP item 2):

- ``autoscaler.Autoscaler`` — a metrics-driven control loop over the
  ``cluster_*`` gauges/histograms that drives the clusters' scaling
  actuation paths (``add_replica``/``remove_replica`` thread
  replicas; role-aware ``add_worker``/``drain_worker`` disagg worker
  processes) with hysteresis, cooldowns, and a replica budget;
  scale-down drains gracefully under a CHECKED zero-leak contract.
- ``chaos.ChaosDriver`` — seeded, trace-relative fault injection
  (injected replica death/stall in-process; real SIGKILL/SIGSTOP/
  connection-reset for disagg worker processes), so "replica death
  during the burst" is a reproducible scenario.
- ``ClusterOverloaded.retry_after_s`` — a structured Retry-After
  hint from queue excess / recent drain rate (the future HTTP 429).
  Workload side: ``benchmark/traffic_trace.py`` (seeded diurnal +
  burst + heavy-tail traces, goodput SLO classification) and
  ``serve_bench --trace`` (open-loop replay + ``gpt_serve_goodput``
  gate; ``tests/test_serving_traffic.py``, slow group k).

Round 18 adds hierarchical KV tiering (ROADMAP item 4):

- ``tier_store.HostTierStore`` — a byte-budgeted host-DRAM LRU of
  exact pool-layout page bytes under every engine's pool
  (``ServingEngine(tier_bytes=N)`` / ``MXNET_SERVE_TIER_BYTES``):
  pressure-evicted refcount-0 prefix chains SPILL instead of drop
  and re-install as **warm hits** (the outcome between hot-hit and
  miss); preemption victims SWAP OUT and resume install-exact
  instead of recompute-exact — O(transfer), not O(prefill).  In the
  disaggregated cluster the router's ``ClusterPrefixIndex`` carries
  a per-key tier tag (``hbm``/``host``) and spilled chains stay
  peer-fetchable, served straight from the owner's host tier.
  ``serve_bench --tier-sweep``; ``gpt_serve_tier_hit_ttft_ms`` gate;
  ``tests/test_serving_tier.py`` (slow group l).
"""
from .paged_kv import PagedKVCache
from .prefix_cache import PrefixCache, ClusterPrefixIndex
from .drafters import ngram_draft
from .engine import Request, ServingEngine
from .tier_store import HostTierStore
from .cluster import (ServingCluster, ClusterRequest, ClusterOverloaded,
                      RequestExpired, RequestCancelled, ClusterClosed,
                      ClusterFailed, DisaggServingCluster, run_worker)
from .autoscaler import Autoscaler, HistogramWindow
from .chaos import ChaosDriver, ChaosEvent, chaos_schedule
from .http_frontend import HttpFrontend, ApiKeyTable

__all__ = ["PagedKVCache", "PrefixCache", "ClusterPrefixIndex",
           "HostTierStore", "Request", "ServingEngine",
           "ServingCluster", "ClusterRequest", "ClusterOverloaded",
           "RequestExpired", "RequestCancelled", "ClusterClosed",
           "ClusterFailed", "DisaggServingCluster", "run_worker",
           "ngram_draft", "Autoscaler", "HistogramWindow",
           "ChaosDriver", "ChaosEvent", "chaos_schedule",
           "HttpFrontend", "ApiKeyTable"]
