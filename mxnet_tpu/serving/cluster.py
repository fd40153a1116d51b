"""Multi-replica serving cluster: SLO-aware router over N engines.

After round 7 the serving stack topped out at ONE ``ServingEngine``
fed directly by a benchmark loop.  This module is the cluster/front-end
layer the Orca/vLLM lineage assumes above the engine: it owns N
replicas (threads in-process, one engine + one prefix cache each) and
gives clients a single async ``submit()/result()`` API.

* **Routing** — least-loaded, with **prefix affinity**: the router
  keys each prompt's full-page prefix chains
  (``prefix_cache.chain_keys``) and sends a request whose prefix was
  recently routed somewhere back to that replica, as long as that
  replica's load is within ``affinity_slack`` of the minimum — so a
  shared system prompt is prefetched once per replica it actually
  lands on, not once per request.  Affinity never overrides health or
  a drained replica.
* **Admission** — the waiting set (router inboxes + engine queues) is
  bounded by ``max_queue``; ``submit()`` raises
  :class:`ClusterOverloaded` past it (backpressure, not buffering).
  A per-request ``ttl_s`` expires requests still WAITING past their
  deadline (:class:`RequestExpired` from ``result()``); requests that
  started decoding are never expired mid-flight.
* **Failover** — a replica whose worker raises fails itself over; a
  replica that stalls past ``watchdog_s`` while holding work is
  failed over by the monitor thread.  Either way its waiting and
  in-flight requests are resubmitted to survivors with their
  committed tokens as prompt extension — the engine's
  recompute-exact resume path, so under f32 greedy the final output
  is token-identical to an undisturbed run (pinned by
  ``tests/test_serving_cluster.py``).  The zombie worker of a stalled
  replica is fenced: completions are matched against the request's
  current (replica, engine-rid) assignment under the cluster lock,
  so a late step can never deliver into a resubmitted request.
* **Drain / scale-down** — ``drain_replica(i)`` stops routing to a
  replica, reroutes its waiting requests, lets in-flight requests
  finish, and parks the worker; ``close()`` drains everything.

Clock: ``time.perf_counter`` throughout — the serving trace clock
(mxlint ``clock-mix`` enforces this for the whole package).

Round 15 promotes replicas to **processes** and splits roles:
:class:`DisaggServingCluster` (bottom of this module) runs a router in
THIS process and N prefill + M decode workers as spawned OS processes,
wired by ``serving/transport.py`` over the ``parallel/dist.py`` raw
frames.  A prefill worker runs chunked prefill only and streams
finished int8/f32 KV pages to its request's decode worker
(``serving/page_streamer.py`` — pipelined with the prefill chunks);
the decode worker installs them and picks the request up at
``n_cached = prompt_len``.  The prefix-cache trie's knowledge lives in
the router's :class:`prefix_cache.ClusterPrefixIndex`; a replica
matching another replica's chain fetches the page bytes peer-to-peer
instead of re-prefilling — once per cluster, not once per replica.
SIGKILL of any worker process triggers the router's watchdog: its
requests resubmit to survivors with their streamed committed tokens
as prompt extension — the same recompute-exact resume contract as the
in-process cluster, now across a process boundary.
"""
from __future__ import annotations

import collections
import itertools
import os
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .. import profiler
from .engine import ServingEngine
from .prefix_cache import chain_keys, ClusterPrefixIndex

__all__ = ["ServingCluster", "ClusterRequest", "ClusterOverloaded",
           "RequestExpired", "RequestCancelled", "ClusterClosed",
           "ClusterFailed", "DisaggServingCluster", "run_worker"]

# rid blocks: replica i assigns engine rids in [i*RID_BLOCK, ...), so
# request ids and trace swimlanes stay unique across the cluster
RID_BLOCK = 1 << 20


def _env_default(name, fallback, cast=float):
    """Operational limits default from ``MXNET_SERVE_*`` env vars
    (round 16): the watchdog/TTL/admission bounds were hard-coded
    construction defaults, but the autoscaler and chaos tests need
    tighter timeouts than production wants, and ops wants to retune
    a deployment without editing call sites (docs/env_vars.md).  An
    explicit constructor argument always wins; the env var only
    replaces the built-in default."""
    v = os.environ.get(name)
    if v is None or v == "":
        return fallback
    try:
        return cast(v)
    except ValueError:
        raise ValueError("%s=%r: expected %s"
                         % (name, v, cast.__name__))


class ClusterOverloaded(RuntimeError):
    """submit() refused: the bounded admission queue is full.

    Carries a structured ``retry_after_s`` hint — the estimated time
    until the queue drains below the admission bound at the cluster's
    recent completion rate (groundwork for the HTTP front door's
    429 + Retry-After, ROADMAP item 6).  Also surfaced on the
    ``cluster_retry_after_s`` gauge at each rejection."""

    def __init__(self, msg, retry_after_s=None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class RequestExpired(RuntimeError):
    """The request's TTL elapsed before it started decoding."""


class RequestCancelled(RuntimeError):
    """The request was cancelled via ``cancel(rid)`` (round 20: the
    HTTP front door's client-disconnect propagation) before it
    finished; its slot and pages were released immediately."""


class ClusterClosed(RuntimeError):
    """The cluster is closed (or lost every replica)."""


class ClusterFailed(RuntimeError):
    """No healthy replica remained to finish the request."""


class ClusterRequest:
    """Front-end request record.  ``committed`` accumulates tokens
    from failed-over incarnations; the live incarnation's engine
    request holds the rest."""
    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_id",
                 "deadline", "state", "replica", "engine_rid",
                 "committed", "output", "error", "done_evt",
                 "submit_t", "first_token_t", "token_times",
                 "affinity_keys", "failovers", "delivered",
                 "stream", "listeners", "cancel_req", "trace_id")

    def __init__(self, rid, prompt, max_new_tokens, eos_id, deadline,
                 affinity_keys, trace_id=None):
        self.rid = rid
        # edge-minted trace context (round 23): defaults to a
        # rid-derived id so direct submit() callers trace too
        self.trace_id = trace_id if trace_id is not None \
            else "rid%d" % rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.deadline = deadline
        self.state = "queued"   # queued|running|done|expired|failed
        self.replica: Optional[int] = None
        self.engine_rid: Optional[int] = None
        self.committed: List[int] = []
        self.output: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.done_evt = threading.Event()
        self.submit_t = time.perf_counter()
        self.first_token_t: Optional[float] = None
        # per-token commit timestamps across ALL incarnations — the
        # goodput classifier's input (worst inter-token gap = the
        # stall a streaming client saw, failovers included)
        self.token_times: List[float] = []
        self.affinity_keys = affinity_keys
        self.failovers = 0
        self.delivered = False
        # the canonical PUBLISHED token stream (round 20): what every
        # attach_stream listener has been handed so far, across
        # incarnations — always a prefix of committed + the live
        # engine request's generated tokens, so a failover resumes
        # the stream without a gap or a repeat
        self.stream: List[int] = []
        self.listeners: List = []
        self.cancel_req = False


class _Replica:
    __slots__ = ("idx", "engine", "thread", "inbox", "wake", "lock",
                 "in_flight", "heartbeat", "alive", "draining", "dead",
                 "error", "drained_evt")

    def __init__(self, idx, engine):
        self.idx = idx
        self.engine = engine
        self.thread: Optional[threading.Thread] = None
        self.inbox: "collections.deque[ClusterRequest]" = \
            collections.deque()
        self.wake = threading.Event()
        self.in_flight: Dict[int, ClusterRequest] = {}
        self.heartbeat = time.perf_counter()
        self.alive = True
        self.draining = False
        self.dead = False
        self.error: Optional[BaseException] = None
        self.drained_evt = threading.Event()

    @property
    def load(self):
        return len(self.inbox) + len(self.in_flight)

    @property
    def waiting(self):
        # inbox + engine-queued (len() reads are GIL-atomic; the value
        # is advisory — admission control, not correctness).  A dead
        # replica's abandoned engine queue must not count against the
        # cluster's admission budget.
        if self.dead:
            return 0
        return len(self.inbox) + len(self.engine._queue)


class _ClusterObs:
    """Router-level instrument bundle (mirrors ``_EngineObs``)."""

    _seq = [0]

    def __init__(self, registry=None):
        from .. import obs as O
        if registry is None:
            registry = O.MetricsRegistry(
                labels={"cluster": str(self._seq[0])})
            self._seq[0] += 1
            O.register_engine_registry(registry)
        self.registry = registry
        c, g, h = registry.counter, registry.gauge, registry.histogram
        self.submitted = c("cluster_requests_submitted_total",
                           "requests accepted by cluster submit()")
        self.rejected = c("cluster_requests_rejected_total",
                          "submissions refused by backpressure")
        self.expired = c("cluster_requests_expired_total",
                         "requests whose TTL elapsed while waiting")
        self.cancelled = c("cluster_cancelled_total",
                           "requests cancelled via cancel(rid) — "
                           "client disconnects propagated by the "
                           "HTTP front door, plus chaos 'cancel' "
                           "actions")
        self.completed = c("cluster_requests_completed_total",
                           "requests finished across all replicas")
        self.failovers = c("cluster_failovers_total",
                           "replica failures (raise or watchdog "
                           "stall) drained to survivors")
        self.resubmitted = c("cluster_requests_resubmitted_total",
                             "requests resubmitted after a replica "
                             "failure (recompute-exact resume)")
        self.routed_affinity = c("cluster_routed_affinity_total",
                                 "routing decisions won by prefix "
                                 "affinity")
        self.routed_least = c("cluster_routed_least_loaded_total",
                              "routing decisions by least-loaded")
        self.g_healthy = g("cluster_replicas_healthy",
                           "replicas accepting traffic")
        self.g_waiting = g("cluster_queue_depth",
                           "waiting requests (inboxes + engine "
                           "queues)")
        self.g_in_flight = g("cluster_in_flight",
                             "requests holding an engine slot or "
                             "engine queue entry")
        self.g_retry_after = g("cluster_retry_after_s",
                               "last Retry-After hint handed to a "
                               "rejected submit() (queue excess / "
                               "recent drain rate)")
        self.scale_ups = c("cluster_scale_ups_total",
                           "replicas added (add_replica)")
        self.scale_downs = c("cluster_scale_downs_total",
                             "replicas drained and released "
                             "(remove_replica)")
        self.h_ttft = h("cluster_ttft_ms",
                        help="cluster submit() -> first committed "
                             "token (any incarnation)")
        from ..obs import RequestTraceEmitter
        self.trace = RequestTraceEmitter()


class ServingCluster:
    """N in-process ``ServingEngine`` replicas behind one router.

    Engine sizing kwargs (``num_slots``, ``page_size`` …) apply to
    EVERY replica.  ``prefix_cache`` defaults ON here (it is what
    prefix-affinity routing exists for); each replica has its own
    cache, so shared-prefix prefill is paid once per replica.  The
    round-11 decode levers pass straight through: ``kernel`` selects
    each replica's attention path (xla gather vs fused pallas walk;
    None leaves it to each engine, which picks by its device)
    and ``spec_K``/``spec_drafter``/``spec_ngram`` arm in-engine
    speculative decode per replica — failover/resubmit semantics are
    unchanged because committed tokens are committed tokens however
    many a step produced (recompute-exact resume replays them as
    prompt extension, pinned by ``tests/test_serving_cluster.py``).
    ``tp=N``/``mesh=`` (round 14) likewise: every replica lowers its
    step through the same tensor-parallel mesh, and the whole engine
    config is captured ONCE (``_engine_kwargs``) so a failover
    resubmission always lands on a survivor with identical tp/mesh
    setup (``tests/test_serving_tp.py`` pins failover-under-tp).
    On one host tp>1 replicas time-share the same tp devices.  tp=1
    replicas spread over the host's chips: replica i's params and KV
    pools are committed to local device i mod n (``_replica_device``),
    so one process drives n chips with n replicas instead of stacking
    them all on the default device.
    """

    def __init__(self, params, cfg, *, replicas=2, num_slots,
                 page_size=16, num_pages=None, pages_per_slot=None,
                 prefill_chunk=8, kv_int8=False, prefix_cache=True,
                 metrics=None, registry=None, max_queue=None,
                 watchdog_s=None, default_ttl_s=None,
                 affinity_slack=None,
                 affinity_capacity=4096, retain_results=4096,
                 kernel=None, spec_K=0, spec_drafter="ngram",
                 spec_ngram=2, tp=1, mesh=None, tier_bytes=None):
        if replicas < 1:
            raise ValueError("ServingCluster: replicas must be >= 1")
        self.num_slots = num_slots
        self.page_size = page_size
        # operational limits: explicit argument > MXNET_SERVE_* env >
        # built-in default (docs/env_vars.md "Serving cluster limits")
        if max_queue is None:
            max_queue = _env_default("MXNET_SERVE_MAX_QUEUE", 256,
                                     int)
        if watchdog_s is None:
            watchdog_s = _env_default("MXNET_SERVE_WATCHDOG_S", 30.0)
        if default_ttl_s is None:
            default_ttl_s = _env_default("MXNET_SERVE_TTL_S", None)
        self.max_queue = int(max_queue)
        self.watchdog_s = float(watchdog_s)
        self.default_ttl_s = default_ttl_s
        self.prefix_enabled = bool(prefix_cache)
        # affinity may leave the favored replica at most this many
        # WAITING requests deeper than the shallowest queue: the cache
        # hit saves prefill steps, but letting a hot prefix build an
        # unbounded queue behind one replica while others idle trades
        # TTFT SLO for hit ratio — exactly the wrong direction
        self.affinity_slack = (max(1, num_slots // 4)
                               if affinity_slack is None
                               else int(affinity_slack))
        self._lock = threading.RLock()
        self._closed = False
        self._next_rid = 0
        self.requests: Dict[int, ClusterRequest] = {}
        # terminal requests are retained (rid order) up to this many,
        # then dropped — a long-running cluster must not grow its
        # request table with total traffic served
        self._retain = int(retain_results)
        self._terminal: "collections.deque[int]" = collections.deque()
        # prefix-chain key -> replica idx (LRU-capped)
        self._affinity: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._affinity_cap = int(affinity_capacity)
        if metrics is None:
            import os
            metrics = registry is not None or \
                os.environ.get("MXNET_SERVING_METRICS", "0") == "1"
        self._obs = _ClusterObs(registry) if metrics else None
        # ONE captured engine config (round 14): every replica — and
        # any future re-admission target — is built from this dict, so
        # a request resubmitted to a survivor after failover lands on
        # an engine with the SAME tp/mesh/kernel/spec setup as the one
        # that died.  Previously the kwargs were splatted ad hoc at
        # the construction site only; adding an engine knob meant
        # remembering to thread it here by hand.
        if tp > 1 or mesh is not None:
            # build the mesh and commit the params into their megatron
            # shards ONCE, cluster-wide: every replica's engine then
            # sees already-correctly-placed arrays and its device_put
            # is a no-op — without this, R replicas would each retain
            # an independent sharded copy of the weights on the same
            # tp devices (R× the per-device weight bytes the tp story
            # exists to divide)
            import jax
            from ..models import gpt as G
            from ..parallel.mesh import serving_mesh
            from .engine import _bind
            if mesh is None:
                mesh = serving_mesh(tp)
            if int(mesh.shape.get("tp", 1)) > 1:
                params = jax.device_put(
                    params, _bind(mesh,
                                  G.decode_param_specs(params, cfg)))
        self._engine_kwargs = dict(
            num_slots=num_slots, page_size=page_size,
            num_pages=num_pages, pages_per_slot=pages_per_slot,
            prefill_chunk=prefill_chunk, kv_int8=kv_int8,
            prefix_cache=prefix_cache, metrics=bool(metrics),
            kernel=kernel, spec_K=spec_K, spec_drafter=spec_drafter,
            spec_ngram=spec_ngram, tp=tp, mesh=mesh,
            tier_bytes=tier_bytes)
        # kept for add_replica (autoscaler scale-up): a replica added
        # mid-run must be built from the SAME params/config as the
        # originals (references only — params are already placed)
        self._params, self._cfg = params, cfg
        self._rid_blocks = replicas       # next replica's rid block
        # recent completion timestamps — the drain-rate estimate
        # behind ClusterOverloaded.retry_after_s
        self._completions: "collections.deque[float]" = \
            collections.deque(maxlen=256)
        # set True by an attaching Autoscaler: the zero-replica state
        # is then RECOVERABLE (tick self-heals below min_size), so
        # requests stranded by the last replica's death PARK here
        # instead of failing; add_replica reroutes them.  Without a
        # scaler the round-10 fail-fast contract stands.
        self.scaler_attached = False
        self._orphans: "collections.deque[ClusterRequest]" = \
            collections.deque()
        self.replicas: List[_Replica] = []
        for i in range(replicas):
            self.replicas.append(_Replica(i, self._build_engine(i)))
        # submit()-side validation limits, captured once (replica 0's
        # engine may be released by a later scale-down)
        self._max_seq = self.replicas[0].engine.max_seq
        # pre-warm the step program BEFORE workers and the watchdog
        # start: a first-step compile longer than watchdog_s would
        # otherwise read as a stall and cascade failovers across
        # equally-cold survivors.  The jitted step is shared (its cache
        # keys on config, not engine) but XLA compiles it once per
        # device, so one warm-up per distinct replica device.
        warmed = set()
        for rep in self.replicas:
            dev = self._replica_device(rep.idx)
            if dev not in warmed:
                warmed.add(dev)
                self._warm_engine(rep.engine)
        for rep in self.replicas:
            rep.thread = threading.Thread(
                target=self._worker, args=(rep,), daemon=True,
                name="serving-replica-%d" % rep.idx)
            rep.thread.start()
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True,
            name="serving-cluster-monitor")
        self._monitor.start()
        # publish the healthy count NOW: the gauges are otherwise
        # first written on traffic, and an autoscaler attached to an
        # idle fresh cluster would read healthy=0 and fire a spurious
        # self-heal scale-up
        if self._obs is not None:
            with self._lock:
                self._sync_gauges_locked()

    def _replica_device(self, idx):
        """Replica ``idx``'s chip: local device ``idx mod n`` for tp=1
        replicas on a host with several devices; None (JAX's default
        placement) for tp>1 replicas, which their mesh places, and on
        a one-device host."""
        import jax
        kw = self._engine_kwargs
        if kw["tp"] > 1 or kw["mesh"] is not None:
            return None
        devs = jax.local_devices()
        return devs[idx % len(devs)] if len(devs) > 1 else None

    def _build_engine(self, block):
        """Engine for replica/rid-block ``block`` from the captured
        config, on that replica's device."""
        return ServingEngine(self._params, self._cfg,
                             rid_start=block * RID_BLOCK,
                             device=self._replica_device(block),
                             **self._engine_kwargs)

    @staticmethod
    def _warm_engine(eng):
        """Compile + first-dispatch an engine outside the serving
        clock, then zero the warmup's footprint from its stats."""
        wid = eng.submit(np.ones(1, np.int32), 1)
        eng.run()
        del eng.requests[wid]
        for k in eng.stats:
            eng.stats[k] = type(eng.stats[k])()
        if eng.metrics_enabled:
            eng.reset_metrics()

    # ------------------------------------------------------- intake --
    def submit(self, prompt, max_new_tokens, eos_id=None, ttl_s=None,
               trace_id=None):
        """Queue a request; returns its cluster rid immediately.
        Raises :class:`ClusterOverloaded` when the bounded admission
        queue is full and :class:`ClusterClosed` after close()."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        # validate NOW, in the caller's thread, with the engine's own
        # rules: a request the engines would reject must fail the
        # submit() call, not poison a replica worker later.  Limits
        # are read from the captured spec, not replicas[0] — replica
        # 0 may have been scale-downed (engine released) by now.
        if prompt.size < 1:
            raise ValueError("submit: empty prompt")
        if max_new_tokens < 1:
            raise ValueError("submit: max_new_tokens must be >= 1")
        total = prompt.size + int(max_new_tokens)
        if total > self._max_seq:
            raise ValueError(
                "submit: %d tokens > replica max_seq %d"
                % (total, self._max_seq))
        if total > self._cfg.max_len:
            raise ValueError("submit: %d tokens > cfg.max_len=%d"
                             % (total, self._cfg.max_len))
        keys = chain_keys(prompt, self.page_size) \
            if self.prefix_enabled else []
        with self._lock:
            if self._closed:
                raise ClusterClosed("submit() after close()")
            if not self._healthy():
                if self.scaler_attached:
                    # the autoscaler will restore min capacity —
                    # refuse RETRYABLY, not terminally.  The hint is
                    # RECOVERY-based (the queue-drain formula reads
                    # ~1 ms whenever the queue is shallow, which
                    # would tell clients to hammer a cluster whose
                    # self-heal takes seconds)
                    hint = max(0.05, self.watchdog_s / 4.0)
                    if self._obs is not None:
                        self._obs.rejected.inc()
                        self._obs.g_retry_after.set(hint)
                    raise ClusterOverloaded(
                        "no healthy replicas (self-heal pending); "
                        "retry after %.3fs" % hint,
                        retry_after_s=hint)
                raise ClusterClosed("no healthy replicas")
            waiting = sum(r.waiting for r in self.replicas)
            if waiting >= self.max_queue:
                hint = self._retry_after_locked(waiting)
                if self._obs is not None:
                    self._obs.rejected.inc()
                    self._obs.g_retry_after.set(hint)
                raise ClusterOverloaded(
                    "admission queue full (%d waiting >= max_queue "
                    "%d); retry after %.3fs"
                    % (waiting, self.max_queue, hint),
                    retry_after_s=hint)
            if ttl_s is None:
                ttl_s = self.default_ttl_s
            deadline = None if ttl_s is None \
                else time.perf_counter() + float(ttl_s)
            cr = ClusterRequest(self._next_rid, prompt,
                                int(max_new_tokens), eos_id, deadline,
                                keys, trace_id=trace_id)
            self._next_rid += 1
            self.requests[cr.rid] = cr
            rep = self._route_locked(cr)
            rep.inbox.append(cr)
            cr.replica = rep.idx
            if self._obs is not None:
                self._obs.submitted.inc()
                self._sync_gauges_locked()
            rep.wake.set()
        return cr.rid

    def result(self, rid, timeout=None):
        """Block until the request finishes; returns the full token
        array (prompt + generated).  Raises :class:`RequestExpired` /
        :class:`ClusterFailed` per the terminal state, TimeoutError
        on timeout."""
        cr = self.requests.get(rid)
        if cr is None:
            raise KeyError(
                "result(%d): unknown rid (already collected and "
                "purged past retain_results?)" % rid)
        if not cr.done_evt.wait(timeout):
            raise TimeoutError("result(%d): still running" % rid)
        with self._lock:
            cr.delivered = True
            self._purge_locked()
        if cr.state == "done":
            return cr.output
        if cr.state == "expired":
            raise RequestExpired("request %d expired before "
                                 "admission" % rid)
        if cr.state == "cancelled":
            raise RequestCancelled("request %d was cancelled" % rid)
        raise ClusterFailed("request %d: %r" % (rid, cr.error))

    def drain(self, timeout=None):
        """Wait until every submitted request reaches a terminal
        state.  Returns True if fully drained."""
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        for cr in list(self.requests.values()):
            left = None if deadline is None \
                else max(0.0, deadline - time.perf_counter())
            if not cr.done_evt.wait(left):
                return False
        return True

    # -------------------------------------------- streaming (rnd 20) --
    def attach_stream(self, rid, cb):
        """Register a per-request token-stream listener (the HTTP
        front door's SSE feed).  ``cb`` receives, in order:
        ``("tokens", [int, ...])`` for each batch of newly committed
        tokens (the backlog is delivered immediately on attach, so a
        late attach never misses tokens), then exactly one terminal
        event — ``("done", output_array)`` or ``("error", exc)``.
        Callbacks run on cluster worker threads under the cluster
        lock: they must be quick and non-blocking (the HTTP bridge
        is one ``call_soon_threadsafe`` enqueue)."""
        with self._lock:
            cr = self.requests.get(rid)
            if cr is None:
                raise KeyError("attach_stream(%d): unknown rid" % rid)
            if cr.stream:
                cb(("tokens", list(cr.stream)))
            if cr.state in ("queued", "running"):
                cr.listeners.append(cb)
            else:
                cr.delivered = True        # terminal event handed out
                cb(self._terminal_event(cr))

    @staticmethod
    def _terminal_event(cr):
        if cr.state == "done":
            return ("done", cr.output)
        if cr.state == "expired":
            return ("error", RequestExpired(
                "request %d expired before admission" % cr.rid))
        if cr.state == "cancelled":
            return ("error", RequestCancelled(
                "request %d was cancelled" % cr.rid))
        return ("error", cr.error if cr.error is not None else
                ClusterFailed("request %d failed" % cr.rid))

    def _publish_tokens_locked(self, cr, ereq=None):
        """Hand listeners every not-yet-published token.  The full
        stream so far is ``committed`` (tokens snapshotted across
        failovers) plus the LIVE incarnation's ``generated`` — the
        published prefix is tracked in ``cr.stream``, so failover
        snapshots (which fold generated into committed) never repeat
        or drop a token."""
        full = list(cr.committed)
        if ereq is not None:
            full.extend(int(t) for t in ereq.generated)
        new = full[len(cr.stream):]
        if new:
            cr.stream.extend(new)
            for cb in cr.listeners:
                cb(("tokens", new))

    def _finish_locked(self, cr):
        """Terminal transition tail shared by every path that ends a
        request: flush any unpublished committed tokens, deliver the
        one terminal stream event, wake ``result()`` waiters.  A
        stream listener receiving the terminal event IS the delivery
        — mark the request delivered so ``_purge_locked`` can bound
        the table (the HTTP path never calls ``result()``; without
        this a long-running front door would grow ``requests`` with
        total traffic served)."""
        self._publish_tokens_locked(cr)
        if cr.listeners:
            cr.delivered = True
        for cb in cr.listeners:
            cb(self._terminal_event(cr))
        cr.listeners = []
        cr.done_evt.set()

    def _publish_running(self, rep):
        """Per-step token publication for this replica's in-flight
        requests (the SSE hot path) — a no-op when nobody listens."""
        with self._lock:
            for erid, cr in rep.in_flight.items():
                if not cr.listeners or cr.state != "running":
                    continue
                self._publish_tokens_locked(
                    cr, rep.engine.requests.get(erid))

    # ---------------------------------------------- cancel (rnd 20) --
    def cancel(self, rid):
        """Cancel a request end-to-end (the HTTP front door's client-
        disconnect propagation; also a chaos action).  A WAITING
        request is dropped immediately; a RUNNING one is flagged and
        its replica worker releases the slot and pages on its own
        thread BEFORE its next engine step (the engine is single-
        threaded state — freeing from here would race the step).
        Returns True if the cancel took (or will take) effect, False
        if the request already reached a terminal state — the
        inherent client race; the finished output stays retrievable."""
        with self._lock:
            cr = self.requests.get(rid)
            if cr is None:
                raise KeyError("cancel(%d): unknown rid" % rid)
            if cr.state not in ("queued", "running"):
                return False
            if cr.state == "queued":
                for rep in self.replicas:
                    try:
                        rep.inbox.remove(cr)
                        break
                    except ValueError:
                        pass
                try:
                    self._orphans.remove(cr)
                except ValueError:
                    pass
                self._cancel_now_locked(cr)
                return True
            cr.cancel_req = True
            rep = self.replicas[cr.replica]
            rep.wake.set()
            return True

    def _cancel_now_locked(self, cr):
        cr.state = "cancelled"
        self._retire_locked(cr)
        if self._obs is not None:
            self._obs.cancelled.inc()
            self._sync_gauges_locked()
        self._finish_locked(cr)

    def _sweep_cancels(self, rep):
        """Apply pending cancels on THIS replica's worker thread,
        between steps: ``engine.cancel`` frees the slot and recycles
        the pages immediately, so a disconnected client's pages are
        back in the pool before the engine's next step completes
        (the round-20 acceptance criterion, asserted via pool gauges
        in ``tests/test_http_frontend.py``)."""
        with self._lock:
            pend = [(erid, cr) for erid, cr in rep.in_flight.items()
                    if cr.cancel_req and cr.state == "running"]
            for erid, cr in pend:
                del rep.in_flight[erid]
                ereq = rep.engine.requests.get(erid)
                if ereq is not None:
                    # fold the live incarnation's tokens into the
                    # committed log (the failover snapshot fold) so
                    # the cancelled request's partial output is
                    # checkable against the oracle as a strict
                    # prefix, not an empty list
                    cr.committed.extend(int(t)
                                        for t in list(ereq.generated))
                    cr.token_times.extend(ereq.token_times)
                    rep.engine.cancel(erid)
                    del rep.engine.requests[erid]
                self._cancel_now_locked(cr)

    # ------------------------------------------------------ routing --
    def _healthy(self):
        return [r for r in self.replicas
                if r.alive and not r.draining]

    def _route_locked(self, cr):
        healthy = self._healthy()
        if not healthy:
            raise ClusterClosed("no healthy replicas")
        min_wait = min(r.waiting for r in healthy)
        target = None
        # longest registered prefix wins (iterate deepest-first)
        for key in reversed(cr.affinity_keys):
            idx = self._affinity.get(key)
            if idx is None:
                continue
            rep = self.replicas[idx]
            if rep.alive and not rep.draining \
                    and rep.waiting <= min_wait + self.affinity_slack:
                target = rep
                self._affinity.move_to_end(key)
                if self._obs is not None:
                    self._obs.routed_affinity.inc()
                break
        if target is None:
            target = min(healthy, key=lambda r: (r.load, r.idx))
            if self._obs is not None:
                self._obs.routed_least.inc()
        for key in cr.affinity_keys:
            self._affinity[key] = target.idx
            self._affinity.move_to_end(key)
        while len(self._affinity) > self._affinity_cap:
            self._affinity.popitem(last=False)
        return target

    def _retry_after_locked(self, waiting):
        """Retry-After hint for a rejected submit(): the time for the
        queue excess over the admission bound (plus one average
        request) to drain at the cluster's recent completion rate.
        With no completions observed yet the hint falls back to one
        watchdog quarter — short enough to retry soon, long enough to
        not hammer a cluster that is still compiling."""
        now = time.perf_counter()
        comp = self._completions
        # age out stale samples: a rate computed across an idle gap
        # would hand a busy-again cluster an hours-long hint
        horizon = now - max(5.0, self.watchdog_s)
        while comp and comp[0] < horizon:
            comp.popleft()
        if len(comp) >= 2 and now > comp[0]:
            # len-1 completion INTERVALS over the observed span —
            # conservatively low rate, conservatively long hint.
            # Clamped ABOVE by the watchdog (round-20 small fix): a
            # stalled or barely-completing cluster must not advertise
            # a multi-hour hint — within one watchdog the cluster has
            # either failed over and drained or the client should
            # probe again regardless
            rate = (len(comp) - 1) / (now - comp[0])
            excess = waiting - self.max_queue + 1
            return min(self.watchdog_s,
                       max(0.001, excess / max(rate, 1e-6)))
        return max(0.001, self.watchdog_s / 4.0)

    def _retire_locked(self, cr):
        """Bound the request table: remember terminal rids in order
        and drop the oldest DELIVERED ones past ``retain_results`` — a
        long-running cluster must not grow memory with total traffic
        served, but a finished result the client has not yet collected
        is never purged out from under its pending result() call."""
        self._terminal.append(cr.rid)
        self._purge_locked()

    def _purge_locked(self):
        excess = len(self._terminal) - self._retain
        if excess <= 0:
            return
        kept: "collections.deque[int]" = collections.deque()
        for rid in self._terminal:
            req = self.requests.get(rid)
            if excess > 0 and (req is None or req.delivered):
                excess -= 1
                if req is not None:
                    del self.requests[rid]
            else:
                kept.append(rid)
        self._terminal = kept

    def _sync_gauges_locked(self):
        obs = self._obs
        if obs is None:
            return
        obs.g_healthy.set(len(self._healthy()))
        obs.g_waiting.set(sum(r.waiting for r in self.replicas))
        obs.g_in_flight.set(
            sum(len(r.in_flight) for r in self.replicas))

    # ------------------------------------------------------- worker --
    def _worker(self, rep):
        eng = rep.engine
        while True:
            rep.heartbeat = time.perf_counter()
            if rep.dead:
                return
            try:
                self._pump_inbox(rep)
                self._sweep_cancels(rep)
                finished = eng.step()
            except Exception as e:                  # replica death
                self._fail_replica(rep, e)
                return
            rep.heartbeat = time.perf_counter()
            if finished is not False:
                self._publish_running(rep)
            if finished is False:
                with self._lock:
                    idle = not rep.inbox and not rep.in_flight
                    if idle and (rep.draining or self._closed):
                        rep.alive = False
                        rep.drained_evt.set()
                        self._sync_gauges_locked()
                        return
                rep.wake.wait(timeout=0.02)
                rep.wake.clear()
            elif finished:
                for erid in finished:
                    self._complete(rep, erid)

    def _pump_inbox(self, rep):
        """Move waiting requests into the engine, bounded to one
        engine-queue's worth of backlog so TTL expiry keeps meaning
        (a request buried in an unbounded engine queue could never be
        expired — the engine queue is this thread's, the inbox is the
        cluster's)."""
        eng = rep.engine
        while True:
            with self._lock:
                if not rep.inbox or rep.dead:
                    return
                if len(eng._queue) >= self.num_slots:
                    return
                cr = rep.inbox.popleft()
                if cr.cancel_req:
                    # cancelled while queued on a failover/drain
                    # reroute path (a directly-queued cancel leaves
                    # the inbox inside cancel() itself)
                    self._cancel_now_locked(cr)
                    continue
                now = time.perf_counter()
                if cr.deadline is not None and now > cr.deadline \
                        and not cr.committed:
                    cr.state = "expired"
                    self._retire_locked(cr)
                    if self._obs is not None:
                        self._obs.expired.inc()
                        self._sync_gauges_locked()
                    self._finish_locked(cr)
                    continue
                prompt = cr.prompt if not cr.committed else \
                    np.concatenate([cr.prompt,
                                    np.asarray(cr.committed,
                                               np.int32)])
                try:
                    erid = eng.submit(
                        prompt, cr.max_new_tokens - len(cr.committed),
                        eos_id=cr.eos_id, trace_id=cr.trace_id)
                except Exception as e:
                    # a request THIS engine rejects (submit() already
                    # pre-validated, so this is belt-and-braces) fails
                    # alone — it must not take the worker down
                    cr.state = "failed"
                    cr.error = e
                    self._retire_locked(cr)
                    self._finish_locked(cr)
                    continue
                cr.state = "running"
                cr.replica = rep.idx
                cr.engine_rid = erid
                rep.in_flight[erid] = cr
                if self._obs is not None:
                    self._sync_gauges_locked()

    def _complete(self, rep, erid):
        with self._lock:
            cr = rep.in_flight.pop(erid, None)
            if cr is None or rep.dead:
                return                      # fenced zombie completion
            if cr.state != "running" or cr.replica != rep.idx \
                    or cr.engine_rid != erid:
                return
            ereq = rep.engine.requests[erid]
            if cr.cancel_req:
                # a cancel raced the finishing step: cancel() already
                # returned True, so cancel WINS (the same rule the
                # failover path applies — the client is gone and the
                # finished output has no collector).  Fold the
                # generated tokens so the oracle prefix checks and a
                # late stream attach see the truth, then retire as
                # cancelled — cluster_cancelled_total must agree with
                # every True cancel() or the bench reconciliation
                # breaks
                cr.committed.extend(int(t)
                                    for t in list(ereq.generated))
                cr.token_times.extend(ereq.token_times)
                del rep.engine.requests[erid]
                self._cancel_now_locked(cr)
                return
            self._publish_tokens_locked(cr, ereq)
            cr.output = ereq.output
            cr.state = "done"
            cr.token_times.extend(ereq.token_times)
            self._completions.append(time.perf_counter())
            if cr.first_token_t is None and ereq.token_times:
                cr.first_token_t = ereq.token_times[0]
            # the engine-side record (prompt/generated/output arrays)
            # is fully copied out — drop it so a long-running replica
            # does not accumulate one Request per request ever served
            del rep.engine.requests[erid]
            self._retire_locked(cr)
            if self._obs is not None:
                self._obs.completed.inc()
                if cr.first_token_t is not None:
                    self._obs.h_ttft.observe(
                        (cr.first_token_t - cr.submit_t) * 1e3)
                self._sync_gauges_locked()
            self._finish_locked(cr)

    # ----------------------------------------------------- failover --
    def _fail_replica(self, rep, error):
        """Drain a dead/stalled replica: mark it out of rotation and
        resubmit its waiting + in-flight requests to survivors via the
        recompute-exact resume path.  Idempotent under the lock (the
        worker's own exception path and the monitor's watchdog can
        race here)."""
        with self._lock:
            if rep.dead:
                return
            rep.dead = True
            rep.alive = False
            rep.error = error
            strays = list(rep.inbox)
            rep.inbox.clear()
            in_flight = list(rep.in_flight.items())
            rep.in_flight.clear()
            obs = self._obs
            if obs is not None:
                obs.failovers.inc()
            tracing = obs is not None and profiler.is_recording()
            now = time.perf_counter()
            survivors = self._healthy()
            for erid, cr in in_flight:
                # snapshot committed tokens (greedy determinism makes
                # any snapshot point exact: the resumed run regenerates
                # the continuation identically)
                ereq = rep.engine.requests.get(erid)
                if ereq is not None:
                    cr.committed.extend(int(t)
                                        for t in list(ereq.generated))
                    cr.token_times.extend(ereq.token_times)
                    if cr.first_token_t is None and ereq.token_times:
                        cr.first_token_t = ereq.token_times[0]
                cr.failovers += 1
                if tracing:
                    obs.trace.add_instant(
                        cr.rid, "failover", now,
                        args={"replica": rep.idx,
                              "committed": len(cr.committed)})
            for cr in strays + [cr for _, cr in in_flight]:
                if cr.state not in ("queued", "running"):
                    continue
                if cr.cancel_req:
                    # a cancel raced the failover: the client is gone
                    # — cancel beats resubmission (recomputing a
                    # disconnected request's tokens on a survivor
                    # would be pure waste)
                    self._cancel_now_locked(cr)
                    continue
                done = (cr.eos_id is not None
                        and cr.eos_id in cr.committed) or \
                    len(cr.committed) >= cr.max_new_tokens
                if done:
                    cr.output = np.concatenate(
                        [cr.prompt,
                         np.asarray(cr.committed, np.int32)])
                    cr.state = "done"
                    self._completions.append(now)
                    self._retire_locked(cr)
                    if obs is not None:
                        obs.completed.inc()
                    self._finish_locked(cr)
                    continue
                cr.state = "queued"
                cr.engine_rid = None
                if not survivors:
                    if self.scaler_attached and not self._closed:
                        # round 16: the zero-replica state is
                        # recoverable (the autoscaler self-heals
                        # below min_size) — PARK the request;
                        # add_replica reroutes it when capacity
                        # returns, close() fails it if none ever does
                        self._orphans.append(cr)
                        continue
                    cr.state = "failed"
                    cr.error = error
                    self._retire_locked(cr)
                    self._finish_locked(cr)
                    continue
                target = self._route_locked(cr)
                target.inbox.append(cr)
                cr.replica = target.idx
                target.wake.set()
                if obs is not None:
                    obs.resubmitted.inc()
                    if tracing:
                        obs.trace.add_instant(
                            cr.rid, "resubmit", now,
                            args={"replica": target.idx})
            if tracing:
                obs.trace.flush()
            if obs is not None:
                self._sync_gauges_locked()

    def _monitor_loop(self):
        period = max(0.01, min(0.25, self.watchdog_s / 4.0))
        while True:
            time.sleep(period)
            with self._lock:
                if self._closed and all(not r.alive
                                        for r in self.replicas):
                    return
                now = time.perf_counter()
                stalled = [
                    r for r in self.replicas
                    if r.alive and not r.dead
                    and (r.in_flight or r.inbox)
                    and now - r.heartbeat > self.watchdog_s]
            for rep in stalled:
                self._fail_replica(
                    rep, RuntimeError(
                        "replica %d stalled past watchdog %.3fs"
                        % (rep.idx, self.watchdog_s)))

    # ---------------------------------------------- drain/scale-down --
    def drain_replica(self, idx, timeout=None):
        """Graceful scale-down of one replica: stop routing to it,
        reroute its waiting requests, let in-flight requests finish,
        park the worker.  Returns True once drained."""
        rep = self.replicas[idx]
        with self._lock:
            rep.draining = True
            strays = list(rep.inbox)
            rep.inbox.clear()
            for cr in strays:
                if cr.state != "queued":
                    continue
                target = self._route_locked(cr)
                target.inbox.append(cr)
                cr.replica = target.idx
                target.wake.set()
            if self._obs is not None:
                self._sync_gauges_locked()
        rep.wake.set()
        return rep.drained_evt.wait(timeout)

    # ------------------------------------------------- scale-up/down --
    def add_replica(self):
        """Scale-up actuation (round 16, driven by
        ``serving/autoscaler.py``): build ONE more engine replica from
        the captured ``_engine_kwargs`` and put it in rotation.
        Engine construction + pre-warm run OUTSIDE the cluster lock
        (the step program is already compiled — the cost is params
        placement and one cached-program dispatch); only the rid-block
        reservation and the rotation append hold it.  Returns the new
        replica index."""
        with self._lock:
            if self._closed:
                raise ClusterClosed("add_replica() after close()")
            block = self._rid_blocks
            self._rid_blocks += 1
        eng = self._build_engine(block)
        self._warm_engine(eng)
        with self._lock:
            idx = None if self._closed else len(self.replicas)
            if idx is not None:
                rep = _Replica(idx, eng)
                self.replicas.append(rep)
                rep.thread = threading.Thread(
                    target=self._worker, args=(rep,), daemon=True,
                    name="serving-replica-%d" % idx)
                rep.thread.start()
                # requests stranded by a total-loss failover ride the
                # new capacity (recompute-exact resume, committed
                # tokens already snapshotted by _fail_replica)
                while self._orphans:
                    cr = self._orphans.popleft()
                    if cr.state != "queued":
                        continue
                    target = self._route_locked(cr)
                    target.inbox.append(cr)
                    cr.replica = target.idx
                    target.wake.set()
                    if self._obs is not None:
                        self._obs.resubmitted.inc()
                if self._obs is not None:
                    self._obs.scale_ups.inc()
                    self._sync_gauges_locked()
        if idx is None:
            # lost the race with close(): release the freshly built,
            # never-published engine's cache-owned state before
            # abandoning it to GC (the rid block is just a counter)
            if eng.prefix is not None:
                eng.prefix.clear()
            raise ClusterClosed("add_replica() after close()")
        return idx

    def remove_replica(self, idx=None, timeout=None):
        """Scale-down actuation: gracefully drain one replica (the
        least-loaded healthy one unless ``idx`` names it), verify it
        leaked nothing, and release its KV pool.  Never removes the
        last healthy replica.  Returns the removed index, or None if
        no replica was eligible / the drain timed out.

        The zero-leak contract is CHECKED, not assumed: after the
        drain the replica's prefix cache must hold zero refs, and
        clearing it must return the pool to zero pages in use —
        anything else raises RuntimeError (a page leak found at
        scale-down is a bug, not an operational event)."""
        with self._lock:
            healthy = self._healthy()
            if len(healthy) <= 1:
                return None
            if idx is None:
                idx = min(healthy, key=lambda r: (r.load, -r.idx)).idx
            elif not any(r.idx == idx for r in healthy):
                return None
        if not self.drain_replica(idx, timeout) \
                and not self.replicas[idx].drained_evt.is_set():
            # timed out with work still in flight: back in rotation
            # (mirrors drain_worker) — leaving draining set would
            # silently shrink capacity without ever releasing the
            # replica
            with self._lock:
                rep = self.replicas[idx]
                if rep.alive and not rep.dead:
                    rep.draining = False
                    rep.wake.set()
            return None
        rep = self.replicas[idx]
        eng = rep.engine
        leaked_refs = 0 if eng.prefix is None else eng.prefix.refs_total
        if eng.prefix is not None:
            eng.prefix.clear()
        in_use = eng.cache.pages_in_use
        if leaked_refs or in_use:
            raise RuntimeError(
                "remove_replica(%d): %d prefix refs / %d pages still "
                "held after drain — scale-down would leak" %
                (idx, leaked_refs, in_use))
        eng.close()                       # retire any planner thread
        with self._lock:
            rep.dead = True               # waiting -> 0, never routed
            rep.engine = None             # release pools/params refs
            if self._obs is not None:
                self._obs.scale_downs.inc()
                self._sync_gauges_locked()
        return idx

    def detach_scaler(self):
        """The attached autoscaler is going away: requests parked for
        a self-heal that will now never come must fail loudly instead
        of hanging their result() waiters forever."""
        with self._lock:
            self.scaler_attached = False
            while self._orphans:
                cr = self._orphans.popleft()
                if cr.state != "queued":
                    continue
                cr.state = "failed"
                cr.error = ClusterFailed(
                    "request %d: parked for scale-up but the "
                    "autoscaler detached" % cr.rid)
                self._retire_locked(cr)
                self._finish_locked(cr)

    # the autoscaler's actuation protocol (shared with
    # DisaggServingCluster): scale_up() -> bool, scale_down() -> bool
    def scale_up(self):
        self.add_replica()
        return True

    def scale_down(self, timeout=60.0):
        return self.remove_replica(timeout=timeout) is not None

    @property
    def slots_per_replica(self):
        return self.num_slots

    def close(self, timeout=None):
        """Drain every replica and stop the monitor.  In-flight work
        finishes first (the watchdog still covers a replica that
        stalls during shutdown)."""
        with self._lock:
            self._closed = True
            # parked orphans will never see new capacity now
            while self._orphans:
                cr = self._orphans.popleft()
                if cr.state != "queued":
                    continue
                cr.state = "failed"
                cr.error = ClusterClosed(
                    "cluster closed with the request parked for "
                    "scale-up")
                self._retire_locked(cr)
                self._finish_locked(cr)
        for rep in self.replicas:
            rep.wake.set()
        for rep in self.replicas:
            if rep.thread is not None:
                rep.thread.join(timeout)
        for rep in self.replicas:
            if rep.engine is not None:
                rep.engine.close()
        self._monitor.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()

    # --------------------------------------------------- accounting --
    def health(self):
        """Per-replica health snapshot (the health-check surface)."""
        now = time.perf_counter()
        with self._lock:
            return [{"replica": r.idx, "alive": r.alive,
                     "draining": r.draining, "dead": r.dead,
                     "load": r.load, "waiting": r.waiting,
                     "in_flight": len(r.in_flight),
                     "heartbeat_age_s": now - r.heartbeat,
                     "error": repr(r.error) if r.error else None}
                    for r in self.replicas]

    def debug_status(self):
        """Ops introspection snapshot for ``GET /debug/statusz``
        (round 23) — the in-process flavor's counterpart of
        :meth:`DisaggServingCluster.debug_status`: live topology plus
        in-flight request states.  JSON-able, read-only."""
        now = time.perf_counter()
        with self._lock:
            reqs = []
            for cr in self.requests.values():
                if cr.state not in ("queued", "running"):
                    continue
                reqs.append({
                    "rid": cr.rid, "trace_id": cr.trace_id,
                    "state": cr.state, "replica": cr.replica,
                    # the canonical PUBLISHED stream length — survives
                    # failover (committed snapshots + live tokens)
                    "tokens": len(cr.stream),
                    "failovers": cr.failovers,
                    "ttft_ms": None if cr.first_token_t is None
                    else (cr.first_token_t - cr.submit_t) * 1e3,
                    "age_s": now - cr.submit_t})
            return {"kind": "inproc", "closed": self._closed,
                    "replicas": self.health(), "requests": reqs}

    @property
    def registry(self):
        return self._obs.registry if self._obs is not None else None

    def metrics(self):
        """JSON-able snapshot: router counters + per-replica engine
        snapshots."""
        if self._obs is None:
            return {"enabled": False}
        snap = self._obs.registry.snapshot()
        snap["enabled"] = True
        snap["replicas"] = [r.engine.metrics() for r in self.replicas
                            if r.engine is not None]
        return snap


# ===========================================================================
# Disaggregated prefill/decode serving (round 15): cross-PROCESS
# replicas streaming int8 KV pages, with a cluster-level prefix index.
# ===========================================================================

class _DisaggObs:
    """Router-side instrument bundle for the disaggregated cluster."""

    _seq = [0]

    def __init__(self, registry=None):
        from .. import obs as O
        if registry is None:
            registry = O.MetricsRegistry(
                labels={"disagg": str(self._seq[0])})
            self._seq[0] += 1
            O.register_engine_registry(registry)
        self.registry = registry
        c, g, h = registry.counter, registry.gauge, registry.histogram
        self.submitted = c("cluster_requests_submitted_total",
                           "requests accepted by cluster submit()")
        self.cancelled = c("cluster_cancelled_total",
                           "requests cancelled via cancel(rid) — "
                           "client disconnects propagated by the "
                           "HTTP front door, plus chaos 'cancel' "
                           "actions")
        self.completed = c("cluster_requests_completed_total",
                           "requests finished across all workers")
        self.failovers = c("cluster_failovers_total",
                           "worker-process failures (SIGKILL, crash, "
                           "or watchdog stall) failed over")
        self.resubmitted = c("cluster_requests_resubmitted_total",
                             "requests resubmitted after a worker "
                             "death (recompute-exact resume)")
        self.page_bytes = c("cluster_page_bytes_streamed_total",
                            "KV page bytes moved between worker "
                            "processes (prefill->decode streams + "
                            "peer prefix fetches)")
        self.pages_streamed = c("cluster_pages_streamed_total",
                                "KV pages moved between worker "
                                "processes")
        self.remote_hits = c("serving_prefix_remote_hits_total",
                             "prefix chains fetched from another "
                             "replica instead of re-prefilled")
        self.remote_hit_tokens = c(
            "serving_prefix_remote_hit_tokens_total",
            "prompt tokens whose prefill was skipped via a REMOTE "
            "prefix fetch")
        self.g_workers = g("cluster_workers_healthy",
                           "worker processes accepting traffic")
        self.g_in_flight = g("cluster_in_flight",
                             "requests not yet terminal")
        self.h_ttft = h("cluster_ttft_ms",
                        help="cluster submit() -> first committed "
                             "token seen at the router")
        self.h_transfer = h("cluster_page_transfer_ms",
                            help="page-frame send -> installed in the "
                                 "decode pool (same-host monotonic "
                                 "clock)")
        # round 23: router-lane request spans (submit instant, TTFT
        # span) in the same merged chrome trace the worker spans land
        # in — the router process IS the recording process
        from ..obs.trace import RequestTraceEmitter
        self.trace = RequestTraceEmitter()


def _why_not_ready(got):
    """What the READY wait got instead: a worker that could not build
    its engine says why in an ``error`` frame (e.g. a chip it could
    not claim); one that died without a word leaves None."""
    if got not in (None, "timeout") and got[0] == "error":
        return got[1].get("msg")
    return repr(got)


class _WorkerHandle:
    """Router-side record of one worker process."""
    __slots__ = ("name", "role", "proc", "conn", "data_host",
                 "data_port", "last_seen", "dead", "draining",
                 "outstanding", "stats", "stats_evt", "stats_sid",
                 "error", "recv_thread", "pid", "clock_offset",
                 "clock_rtt", "flight_tail", "chip")

    def __init__(self, name, role):
        self.name = name
        self.role = role
        self.proc = None
        self.chip = None                  # TPU chip a spawned worker owns
        self.pid = None                   # from hello (put-segment sweep)
        self.conn = None
        self.data_host = None
        self.data_port = None
        self.last_seen = time.perf_counter()
        self.dead = False
        self.draining = False
        self.outstanding = set()          # rids currently assigned
        self.stats: Dict = {}
        self.stats_evt = threading.Event()
        self.stats_sid = None             # awaited stats_req id
        self.error = None
        self.recv_thread = None
        # round 23: ping-pong clock model (worker perf_counter minus
        # router perf_counter, min-RTT sample) — corrects this
        # worker's shipped span times onto the router timeline
        self.clock_offset = 0.0
        self.clock_rtt = None             # best (lowest) RTT seen, s
        # round 23: recovered flight-recorder tail after this worker
        # died (the post-mortem evidence _fail_worker pulled from its
        # crash-durable ring)
        self.flight_tail = None

    @property
    def alive(self):
        return not self.dead and self.conn is not None


class DisaggRequest:
    """Router-side request record for the disaggregated cluster.
    ``committed`` is fed by the token stream from whichever worker is
    running the request — it is the failover snapshot (a SIGKILLed
    worker's memory is gone; only streamed tokens survive)."""
    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_id", "state",
                 "phase", "prefill", "decode", "gen", "committed",
                 "output", "error", "done_evt", "submit_t",
                 "first_token_t", "token_times", "failovers",
                 "delivered", "listeners", "trace_id")

    def __init__(self, rid, prompt, max_new_tokens, eos_id,
                 trace_id=None):
        self.rid = rid
        # round 23 trace context: minted at the HTTP edge (the
        # X-Request-Id) or defaulted here; carried in the meta of
        # every request-bearing wire kind and stamped on every span
        self.trace_id = trace_id if trace_id is not None \
            else "rid%d" % rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.state = "running"            # running|done|failed
        self.phase = "prefill"            # prefill|decode
        self.prefill: Optional[str] = None
        self.decode: Optional[str] = None
        self.gen = 0                      # incarnation fence
        self.committed: List[int] = []
        self.output: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.done_evt = threading.Event()
        self.submit_t = time.perf_counter()
        self.first_token_t: Optional[float] = None
        # router-side arrival time of each streamed token (tokens in
        # one frame share a timestamp) — the goodput classifier's view
        self.token_times: List[float] = []
        self.failovers = 0
        self.delivered = False
        # round 20: attach_stream listeners.  ``committed`` IS the
        # canonical stream here (it only grows, at the router, under
        # the router lock) — no separate published-prefix bookkeeping
        self.listeners: List = []


class DisaggServingCluster:
    """Disaggregated prefill/decode serving across OS processes.

    The router (this object, in the calling process) spawns
    ``prefill`` + ``decode`` worker processes (``multiprocessing``
    spawn — real pids, SIGKILL-able), ships each the model params and
    engine config over the transport at handshake, and then routes:
    every request runs chunked prefill on a prefill worker (its
    engine capped at one sampled token), whose finished KV pages
    stream to the request's decode worker pipelined with the prefill
    chunks; the decode worker installs the pages, admits the request
    at ``n_cached = prompt_len`` via ``engine.admit_prefilled``, and
    streams committed tokens back to the router.

    * **Cluster-level prefix reuse** — the router owns a
      :class:`prefix_cache.ClusterPrefixIndex`; submit() attaches a
      hint naming the replica holding the longest cached chain, and
      the prefill worker fetches those pages peer-to-peer (raw int8
      page bytes) instead of recomputing them.  A hot prefix is
      prefilled once per CLUSTER; ``serving_prefix_remote_hits_total``
      / ``cluster_page_bytes_streamed_total`` measure it.
    * **Failover** — a worker that dies (SIGKILL, crash, socket loss)
      or stalls past ``watchdog_s`` is failed over: its requests
      resubmit to survivors with the router's streamed ``committed``
      tokens as prompt extension (recompute-exact; f32-greedy output
      is token-identical to an undisturbed run), fenced by per-request
      incarnation numbers so a zombie's late frames never land.
    * **Exactness** — prefill and decode run the SAME compiled step
      program config; pages transfer as exact pool bytes.  Under f32
      greedy the cluster output is bit-identical to single-engine
      ``generate`` (pinned by ``tests/test_serving_disagg.py``).

    Off-host scale-out uses the same protocol: pass ``spawn=False``
    and start workers via ``tools/launch.py --launcher serve`` (or
    ``run_worker()`` with ``MXNET_SERVE_*`` env) on any reachable
    host.
    """

    def __init__(self, params, cfg, *, prefill=1, decode=1,
                 num_slots, page_size=16, num_pages=None,
                 pages_per_slot=None, prefill_chunk=8, kv_int8=False,
                 kernel=None, spec_K=0, metrics=None, registry=None,
                 watchdog_s=None, spawn=True, host="127.0.0.1",
                 port=0, ready_timeout=None, tier_bytes=None):
        if prefill < 1 or decode < 1:
            raise ValueError("DisaggServingCluster: needs >= 1 "
                             "prefill and >= 1 decode worker")
        if watchdog_s is None:
            watchdog_s = _env_default("MXNET_SERVE_WATCHDOG_S", 30.0)
        if ready_timeout is None:
            ready_timeout = _env_default(
                "MXNET_SERVE_READY_TIMEOUT_S", 120.0)
        self.cfg = cfg
        self.page_size = page_size
        self.watchdog_s = float(watchdog_s)
        self._spawn = bool(spawn)
        self._engine_kwargs = dict(
            num_slots=num_slots, page_size=page_size,
            num_pages=num_pages, pages_per_slot=pages_per_slot,
            prefill_chunk=prefill_chunk, kv_int8=kv_int8,
            kernel=kernel, spec_K=spec_K, tier_bytes=tier_bytes)
        # mirror of the workers' engine limits, so an invalid request
        # fails the submit() call instead of poisoning a worker
        pps = pages_per_slot if pages_per_slot is not None \
            else -(-cfg.max_len // page_size)
        self._max_seq = min(pps * page_size, cfg.max_len)
        if metrics is None:
            metrics = registry is not None or \
                os.environ.get("MXNET_SERVING_METRICS", "0") == "1"
        self._obs = _DisaggObs(registry) if metrics else None
        self._lock = threading.RLock()
        self._closed = False
        self._next_rid = 0
        self.requests: Dict[int, DisaggRequest] = {}
        # terminal requests are retained up to this many, then the
        # oldest DELIVERED ones drop — a long-running router must not
        # grow its request table with total traffic served (the same
        # contract as ServingCluster.retain_results)
        self._retain = 4096
        self._terminal: "collections.deque[int]" = collections.deque()
        self.index = ClusterPrefixIndex()
        # hellos from workers that connected while another worker's
        # add_worker handshake was draining the accept queue
        self._early_hellos: Dict[str, object] = {}
        self._rr = [0, 0]                 # round-robin cursors
        # worker-reported cumulative stats, delta-folded into the
        # router registry (same idiom as _EngineObs.sync_cache)
        self._stat_seen: Dict[str, Dict[str, float]] = {}
        # -- round 23 observability state ---------------------------
        # router-side crash-durable flight ring (workers get their
        # own in-process), merged cross-process trace emitter, the
        # per-rid span store behind GET /debug/trace/<rid>, and the
        # TTFT sliding window behind the statusz SLO burn gauges
        from ..obs.flight import FlightRecorder
        from ..obs.trace import MergedTraceEmitter
        self._flight = FlightRecorder()
        self._merged = MergedTraceEmitter()   # internally locked
        self._span_store: "collections.OrderedDict[int, list]" = \
            collections.OrderedDict()
        self._span_store_cap = 512
        self._flight_tails: Dict[str, list] = {}
        self._clock_seq = itertools.count(1)
        self._ttft_window: "collections.deque" = collections.deque()
        self._slo_ttft_ms = _env_default(
            "MXNET_SERVE_SLO_TTFT_MS", 1000.0)
        self.workers: Dict[str, _WorkerHandle] = {}
        # pre-provisioned standby workers (round 18): fully handshaken
        # (engine built + pre-warmed) but held out of routing AND out
        # of the healthy-capacity gauge until scale_up() adopts them —
        # burst capacity priced at a peer-map flip, not at
        # process-spawn + jax import + compile
        self._standby: set = set()
        for i in range(prefill):
            self.workers["prefill%d" % i] = _WorkerHandle(
                "prefill%d" % i, "prefill")
        for i in range(decode):
            self.workers["decode%d" % i] = _WorkerHandle(
                "decode%d" % i, "decode")

        from .transport import Listener, tree_to_frames
        import jax
        # port: 0 lets the OS pick (the spawned-worker path); an
        # external launcher (tools/launch.py --launcher serve) picks
        # the port up front and hands it to both sides via env
        self._listener = Listener(host=host, port=port)
        self._pending_conns: "queue.Queue" = queue.Queue()
        self._listener.start(self._pending_conns.put)
        host_params = jax.device_get(params)
        self._params_frames = tree_to_frames(host_params)
        try:
            if spawn:
                for wh in self.workers.values():
                    self._spawn_worker(wh)
            self._handshake_all(ready_timeout)
        except BaseException:
            # a failed construction must not strand live worker
            # processes (each holding an engine) or the bound
            # listener — the caller never gets an object to close()
            for wh in self.workers.values():
                if wh.proc is not None and wh.proc.is_alive():
                    wh.proc.terminate()
                    # reap: a SIGTERMed child stays a zombie pid
                    # until joined (py-resource-lifecycle)
                    wh.proc.join(timeout=5)
                if wh.conn is not None:
                    wh.conn.close()
            self._listener.close()
            raise
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True,
            name="disagg-monitor")
        self._monitor.start()

    # ------------------------------------------------------- spawn ---
    def _spawn_worker(self, wh):
        """Start ``wh``'s worker process.  Where the workers will run
        on TPU chips (this host has some and ``JAX_PLATFORMS`` lets the
        children use them) each gets exactly one — the lowest not owned
        by a live spawned worker — through its environment
        (``context.one_chip_env``), and this process must hold none: it
        only ships frames, so its params can stay host-side."""
        import multiprocessing as mp
        from ..context import (held_accelerator, host_tpu_chips,
                               one_chip_env)
        plats = os.environ.get("JAX_PLATFORMS", "")
        chips = host_tpu_chips() \
            if not plats or "tpu" in plats.split(",") else 0
        env = {}
        if chips:
            held = held_accelerator()
            if held is not None:
                raise RuntimeError(
                    "DisaggServingCluster(spawn=True): this process "
                    "has initialised the %r backend and holds the "
                    "host's chips, so a spawned worker cannot claim "
                    "one (it would die with 'The TPU is already in use "
                    "by process with pid %d').  Keep the router on the "
                    "CPU backend — jax.config.update('jax_platforms', "
                    "'cpu') before anything touches JAX; it only ships "
                    "host-side params — or start the workers yourself "
                    "(spawn=False, tools/launch.py --launcher serve)."
                    % (held, os.getpid()))
            owned = {w.chip for w in self.workers.values()
                     if w.chip is not None and w.proc is not None
                     and w.proc.is_alive()}
            free = [c for c in range(chips) if c not in owned]
            if not free:
                raise RuntimeError(
                    "DisaggServingCluster(spawn=True): worker %s needs "
                    "a chip of its own and all %d on this host are "
                    "owned by live workers" % (wh.name, chips))
            wh.chip = free[0]
            env = one_chip_env(wh.chip)
        wh.proc = mp.get_context("spawn").Process(
            target=_disagg_worker_entry,
            args=(wh.name, wh.role, self._listener.host,
                  self._listener.port),
            daemon=True, name="serving-" + wh.name)
        # a spawned child inherits os.environ as it is at start()
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            wh.proc.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # --------------------------------------------------- handshake ---
    def _handshake_all(self, timeout):
        deadline = time.perf_counter() + timeout
        need = {n for n in self.workers}
        while need:
            left = deadline - time.perf_counter()
            if left <= 0:
                raise RuntimeError(
                    "DisaggServingCluster: workers %s never connected"
                    % sorted(need))
            try:
                conn = self._pending_conns.get(timeout=min(left, 1.0))
            except queue.Empty:
                continue
            got = conn.recv(timeout=left)
            if got in (None, "timeout"):
                conn.close()
                continue
            kind, meta, _ = got
            if kind != "hello" or meta.get("name") not in need:
                conn.close()
                continue
            name = meta["name"]
            wh = self.workers[name]
            wh.conn = conn
            wh.pid = meta.get("pid")
            pm, pb = self._params_frames
            conn.send("config",
                      {"cfg": self.cfg, "role": wh.role,
                       "engine_kwargs": self._engine_kwargs,
                       "params_meta": pm,
                       "watchdog_s": self.watchdog_s}, pb)
            need.discard(name)
        # collect READY (with data ports) from everyone
        for name, wh in self.workers.items():
            got = wh.conn.recv(timeout=max(
                1.0, deadline - time.perf_counter()))
            if got in (None, "timeout") or got[0] != "ready":
                raise RuntimeError(
                    "DisaggServingCluster: worker %s failed to build "
                    "its engine: %s" % (name, _why_not_ready(got)))
            _, meta, _ = got
            wh.data_host = meta["data_host"]
            wh.data_port = meta["data_port"]
            wh.last_seen = time.perf_counter()
        peers = {n: {"role": w.role, "host": w.data_host,
                     "port": w.data_port}
                 for n, w in self.workers.items()}
        for wh in self.workers.values():
            wh.conn.send("peers", {"peers": peers})
            wh.recv_thread = threading.Thread(
                target=self._recv_loop, args=(wh,), daemon=True,
                name="disagg-recv-" + wh.name)
            wh.recv_thread.start()
        # clock-offset ping burst AFTER recv threads start: the
        # worker is in its run() loop by now, so replies ride the
        # normal inbox->_handle->send path and land in _recv_loop
        for wh in self.workers.values():
            self._clock_ping(wh)
        if self._obs is not None:
            self._obs.g_workers.set(self._serving_count())

    def _clock_ping(self, wh, n=5):
        """Ping-pong clock-offset burst (round 23): each ``clock_req``
        echoes back with the worker's ``perf_counter`` read; the
        min-RTT sample (``_on_clock``) estimates this worker's clock
        offset from the router.  Same-host workers share
        CLOCK_MONOTONIC, so the estimate validates at ~0 there and
        becomes load-bearing for off-host workers."""
        for _ in range(n):
            try:
                wh.conn.send("clock_req",
                             {"seq": next(self._clock_seq),
                              "t0": time.perf_counter()})
            except OSError:
                return                    # monitor will fail it over

    def _serving_count(self):
        """Workers counted as serving capacity: alive and not parked
        as standby (a standby worker is warm but deliberately invisible
        to the autoscaler's healthy gauge — counting it would tell the
        scaler the capacity is already deployed)."""
        return sum(w.alive and w.name not in self._standby
                   for w in self.workers.values())

    # ------------------------------------------------- router recv ---
    def _recv_loop(self, wh):
        while True:
            got = wh.conn.recv()
            if got is None:
                with self._lock:
                    closed = self._closed or wh.dead
                if not closed:
                    self._fail_worker(wh, RuntimeError(
                        "worker %s: connection lost (process died?)"
                        % wh.name))
                return
            kind, meta, bufs = got
            wh.last_seen = time.perf_counter()
            if kind == "tokens":
                self._on_tokens(wh, meta)
            elif kind == "handed":
                self._on_handed(wh, meta)
            elif kind == "done":
                self._on_done(wh, meta)
            elif kind == "lost":
                self._on_lost(wh, meta)
            elif kind == "insert":
                self.index.report_insert(wh.name, meta["keys"])
            elif kind == "evict":
                self.index.report_evict(wh.name, meta["keys"])
            elif kind == "tier":
                # round 18: chains moved between the worker's tiers
                # (spill hbm->host / warm restore host->hbm) — re-tag,
                # never forget: a spilled chain is still fetchable
                self.index.report_tier(wh.name, meta["keys"],
                                       meta["tier"])
            elif kind == "stats":
                self._on_stats(wh, meta)
            elif kind == "spans":
                self._on_spans(wh, meta)
            elif kind == "clock":
                self._on_clock(wh, meta)
            elif kind == "reqfail":
                with self._lock:
                    cr = self.requests.get(meta["rid"])
                    if cr is not None and cr.gen == meta["gen"] \
                            and cr.state == "running":
                        cr.state = "failed"
                        cr.error = RuntimeError(meta.get("msg", ""))
                        for side in (cr.prefill, cr.decode):
                            w = self.workers.get(side)
                            if w is not None:
                                w.outstanding.discard(cr.rid)
                        self._terminal.append(cr.rid)
                        self._finish_locked(cr)
            elif kind == "error":
                self._fail_worker(wh, RuntimeError(
                    "worker %s: %s" % (wh.name, meta.get("msg"))))
                return

    def _commit_tokens_locked(self, cr, toks, now):
        """Append newly streamed tokens (router lock held)."""
        if toks and cr.first_token_t is None:
            cr.first_token_t = now
            ttft_ms = (now - cr.submit_t) * 1e3
            if self._obs is not None:
                self._obs.h_ttft.observe(ttft_ms)
                if profiler.is_recording():
                    # router-lane TTFT span: the worker/transport
                    # spans shipped for this rid nest inside it in
                    # the merged dump (flushed by the caller outside
                    # the router lock)
                    self._obs.trace.add_span(
                        cr.rid, "ttft", cr.submit_t, now,
                        args={"trace_id": cr.trace_id,
                              "prefill": cr.prefill,
                              "decode": cr.decode})
            # SLO burn window (round 23 statusz): (arrival, ttft_ms)
            # samples pruned to the longest burn window
            self._ttft_window.append((now, ttft_ms))
            while self._ttft_window and \
                    now - self._ttft_window[0][0] > 300.0:
                self._ttft_window.popleft()
        new = [int(t) for t in toks]
        cr.committed.extend(new)
        cr.token_times.extend(now for _ in toks)
        if new:
            # round 20: the per-token failover log IS the SSE feed —
            # every listener sees exactly the tokens a resubmission
            # would replay, so streams survive worker death
            for cb in cr.listeners:
                cb(("tokens", new))

    def _terminal_event(self, cr):
        if cr.state == "done":
            return ("done", cr.output)
        if cr.state == "cancelled":
            return ("error", RequestCancelled(
                "request %d was cancelled" % cr.rid))
        return ("error", cr.error if cr.error is not None else
                ClusterFailed("request %d failed" % cr.rid))

    def _finish_locked(self, cr):
        """Terminal transition tail (router lock held): one terminal
        stream event per request, then wake ``result()`` waiters.  A
        listener receiving the terminal event IS the delivery — mark
        delivered so ``_purge_locked`` bounds the table under pure
        HTTP traffic (same contract as ``ServingCluster``)."""
        if cr.listeners:
            cr.delivered = True
        for cb in cr.listeners:
            cb(self._terminal_event(cr))
        cr.listeners = []
        cr.done_evt.set()

    def attach_stream(self, rid, cb):
        """Register a per-request token-stream listener — the same
        contract as ``ServingCluster.attach_stream`` (backlog
        delivered on attach, then ``("tokens", [...])`` batches and
        one terminal ``("done", output)`` / ``("error", exc)``).
        Callbacks run on the router's receive threads under the
        router lock: keep them to an enqueue."""
        with self._lock:
            cr = self.requests.get(rid)
            if cr is None:
                raise KeyError("attach_stream(%d): unknown rid" % rid)
            if cr.committed:
                cb(("tokens", list(cr.committed)))
            if cr.state == "running":
                cr.listeners.append(cb)
            else:
                cr.delivered = True        # terminal event handed out
                cb(self._terminal_event(cr))

    def cancel(self, rid):
        """Cancel a running request end-to-end (round 20): bump the
        incarnation gen (fencing every late frame of the old one) and
        send the gen-fenced ``cancel`` wire kind to BOTH assigned
        workers, which drop staged pages and force-retire the engine
        request — pages and slot are recycled without waiting for the
        generation to finish.  A cancel landing after completion is a
        no-op returning False (the inherent client race); a repeat
        cancel, or one for a gen that already died, is likewise
        harmless — the worker-side fence makes it a no-op."""
        sends = []
        with self._lock:
            cr = self.requests.get(rid)
            if cr is None:
                raise KeyError("cancel(%d): unknown rid" % rid)
            if cr.state != "running":
                return False
            cr.gen += 1
            cr.state = "cancelled"
            for side in set((cr.prefill, cr.decode)):
                w = self.workers.get(side)
                if w is not None:
                    w.outstanding.discard(cr.rid)
                    if w.alive:
                        sends.append((w.conn, (
                            "cancel", {"rid": cr.rid,
                                       "below_gen": cr.gen,
                                       "trace_id": cr.trace_id},
                            [])))
            if self._obs is not None:
                self._obs.cancelled.inc()
                self._obs.g_in_flight.set(
                    sum(r.state == "running"
                        for r in self.requests.values()))
            self._terminal.append(cr.rid)
            self._purge_locked()
            self._finish_locked(cr)
        self._do_sends(sends)
        self._flight.record("cancel", rid=rid, trace_id=cr.trace_id)
        return True

    def _on_tokens(self, wh, meta):
        with self._lock:
            cr = self.requests.get(meta["rid"])
            if cr is None or cr.gen != meta["gen"] \
                    or cr.state != "running":
                return
            self._commit_tokens_locked(cr, meta["toks"], time.perf_counter())
        if self._obs is not None:
            self._obs.trace.flush()       # outside the router lock

    def _on_handed(self, wh, meta):
        """Prefill finished and handed off to the decode worker.
        Carries NO tokens: the decode worker reports the whole
        committed stream (handoff tokens included) on its own FIFO
        connection — splitting the stream across the two workers'
        independent router connections would race, and a decode
        'done' overtaking the prefill 'handed' would silently drop
        (or reorder) the prefill-sampled token."""
        with self._lock:
            cr = self.requests.get(meta["rid"])
            if cr is None or cr.gen != meta["gen"] \
                    or cr.state != "running":
                return
            cr.phase = "decode"
            wh.outstanding.discard(cr.rid)

    def _on_done(self, wh, meta):
        sends = []
        with self._lock:
            cr = self.requests.get(meta["rid"])
            if cr is None or cr.gen != meta["gen"] \
                    or cr.state != "running":
                return
            self._commit_tokens_locked(cr, meta.get("toks", ()),
                                       time.perf_counter())
            cr.output = np.concatenate(
                [cr.prompt, np.asarray(cr.committed, np.int32)])
            cr.state = "done"
            for side in (cr.prefill, cr.decode):
                w = self.workers.get(side)
                if w is not None:
                    w.outstanding.discard(cr.rid)
            if cr.phase == "prefill" and cr.decode != wh.name:
                # the request completed AT PREFILL: the decode side
                # may hold staged pages from the stream — fence it
                # authoritatively from here (the prefill worker's
                # courtesy 'drop' is best-effort; a failed send would
                # leak decode pool pages forever)
                w = self.workers.get(cr.decode)
                if w is not None and w.alive:
                    sends.append((w.conn, (
                        "abort", {"rid": cr.rid,
                                  "below_gen": cr.gen + 1}, [])))
            if self._obs is not None:
                self._obs.completed.inc()
                self._obs.g_in_flight.set(
                    sum(r.state == "running"
                        for r in self.requests.values()))
            self._terminal.append(cr.rid)
            self._purge_locked()
            self._finish_locked(cr)
        self._do_sends(sends)
        self._flight.record("done", rid=meta.get("rid"),
                            worker=wh.name)
        if self._obs is not None:
            self._obs.trace.flush()       # outside the router lock

    def _purge_locked(self):
        excess = len(self._terminal) - self._retain
        if excess <= 0:
            return
        kept: "collections.deque[int]" = collections.deque()
        for rid in self._terminal:
            req = self.requests.get(rid)
            if excess > 0 and (req is None or req.delivered):
                excess -= 1
                self.requests.pop(rid, None)
            else:
                kept.append(rid)
        self._terminal = kept

    def _on_lost(self, wh, meta):
        """A prefill worker abandoned a request because its decode
        peer was unreachable (peer data-plane failure with the peer
        PROCESS possibly still alive — the watchdog cannot see it):
        reassign, with any streamed state fenced out."""
        sends = []
        with self._lock:
            cr = self.requests.get(meta["rid"])
            if cr is None or cr.gen != meta["gen"] \
                    or cr.state != "running":
                return
            cr.gen += 1
            cr.failovers += 1
            for side in (cr.prefill, cr.decode):
                w = self.workers.get(side)
                if w is not None:
                    w.outstanding.discard(cr.rid)
                    if w.alive:
                        sends.append((w.conn, (
                            "abort", {"rid": cr.rid,
                                      "below_gen": cr.gen}, [])))
            if cr.failovers > 5:
                # a persistently broken data plane must not ping-pong
                # the request between worker pairs forever
                cr.state = "failed"
                cr.error = ClusterFailed(
                    "request %d: abandoned %d times (worker data "
                    "plane unreachable)" % (cr.rid, cr.failovers))
                self._terminal.append(cr.rid)
                self._finish_locked(cr)
            else:
                sends.extend(self._dispatch_locked(cr))
                if cr.state == "running" and self._obs is not None:
                    self._obs.resubmitted.inc()
        self._do_sends(sends)

    def _on_stats(self, wh, meta):
        wh.stats = meta["stats"]
        obs = self._obs
        if obs is not None:
            seen = self._stat_seen.setdefault(wh.name, {})
            for key, ctr in (("bytes_streamed", obs.page_bytes),
                             ("pages_streamed", obs.pages_streamed),
                             ("remote_hits", obs.remote_hits),
                             ("remote_hit_tokens",
                              obs.remote_hit_tokens)):
                v = wh.stats.get(key, 0)
                d = v - seen.get(key, 0)
                if d > 0:
                    ctr.inc(d)
                seen[key] = v
            for ms in wh.stats.get("transfer_ms", ()):
                obs.h_transfer.observe(ms)
        # set LAST, and only for the awaited stats_req reply: an
        # unsolicited periodic frame serialized before the request
        # must not satisfy the wait with a stale snapshot (a
        # cluster_stats() caller reading the registry right after the
        # event must see the REQUESTED message's deltas folded in)
        if meta.get("sid") is not None \
                and meta["sid"] == wh.stats_sid:
            wh.stats_evt.set()

    def _on_clock(self, wh, meta):
        """One ``clock_req`` -> ``clock`` ping-pong sample (round 23):
        ``offset = t_worker - (t0 + rtt/2)`` — the worker's clock
        read, centered on the round trip.  Min-RTT filtering keeps
        the sample least contaminated by queueing delay; correction
        is ``t_router = t_worker - offset``."""
        now = time.perf_counter()
        try:
            t0 = float(meta["t0"])
            tw = float(meta["t_worker"])
        except (KeyError, TypeError, ValueError):
            return
        rtt = max(0.0, now - t0)
        if wh.clock_rtt is None or rtt < wh.clock_rtt:
            wh.clock_rtt = rtt
            wh.clock_offset = tw - (t0 + rtt / 2.0)

    def _on_spans(self, wh, meta):
        """Fold a worker's shipped span batch (the ``spans`` wire
        kind, riding its stats tick) onto the router timeline: times
        corrected by the worker's clock offset, stored per-rid for
        ``GET /debug/trace/<rid>``, and — while a profiler session is
        recording — emitted into the ONE merged chrome trace under
        the worker's (or the shared ``transport``) swimlane."""
        spans = meta.get("spans") or ()
        if not spans:
            return
        off = wh.clock_offset
        with self._lock:
            for s in spans:
                if not isinstance(s, dict):
                    continue
                rec = dict(s, worker=wh.name, offset_s=off)
                lst = self._span_store.get(rec.get("rid"))
                if lst is None:
                    self._span_store[rec.get("rid")] = lst = []
                    while len(self._span_store) > \
                            self._span_store_cap:
                        self._span_store.popitem(last=False)
                lst.append(rec)
        if profiler.is_recording():
            # outside the router lock — the merged emitter carries
            # its own lock, and a profiler flush must never extend a
            # critical section every recv thread contends on
            for s in spans:
                if not isinstance(s, dict):
                    continue
                lane = "transport" \
                    if s.get("cat") == "transport" else wh.name
                self._merged.add(lane, s, off)
            self._merged.flush()

    # ------------------------------------------------------ intake ---
    def _pick(self, role, exclude=()):
        """Least-outstanding over healthy workers of ``role``, ties
        broken round-robin — back-to-back submits spread across
        replicas (the cluster prefix index, not affinity stickiness,
        is what makes spreading cheap here: the second replica fetches
        the pages instead of recomputing them)."""
        cands = sorted((w for w in self.workers.values()
                        if w.role == role and w.alive
                        and not w.draining
                        and w.name not in exclude),
                       key=lambda w: w.name)
        if not cands:
            return None
        i = 0 if role == "prefill" else 1
        cur = self._rr[i]
        self._rr[i] = cur + 1
        lo = min(len(w.outstanding) for w in cands)
        tied = [w for w in cands if len(w.outstanding) == lo]
        return tied[cur % len(tied)]

    def submit(self, prompt, max_new_tokens, eos_id=None,
               trace_id=None):
        """Queue a request; returns its rid immediately.
        ``trace_id`` (round 23) is the cross-process trace context —
        the HTTP front door passes its ``X-Request-Id`` so edge,
        router, worker, and transport spans correlate; unset, the
        request traces under a ``rid<N>`` default."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("submit: empty prompt")
        if max_new_tokens < 1:
            raise ValueError("submit: max_new_tokens must be >= 1")
        if prompt.size + int(max_new_tokens) > self._max_seq:
            raise ValueError(
                "submit: %d tokens > worker max_seq/max_len %d"
                % (prompt.size + int(max_new_tokens), self._max_seq))
        with self._lock:
            if self._closed:
                raise ClusterClosed("submit() after close()")
            cr = DisaggRequest(self._next_rid, prompt,
                               int(max_new_tokens), eos_id,
                               trace_id=trace_id)
            self._next_rid += 1
            self.requests[cr.rid] = cr
            if self._obs is not None:
                self._obs.submitted.inc()
                self._obs.g_in_flight.set(
                    sum(r.state == "running"
                        for r in self.requests.values()))
            sends = self._dispatch_locked(cr)
        self._do_sends(sends)
        self._flight.record("submit", rid=cr.rid,
                            trace_id=cr.trace_id, prefill=cr.prefill,
                            decode=cr.decode)
        if self._obs is not None and profiler.is_recording():
            self._obs.trace.add_instant(
                cr.rid, "submit", cr.submit_t,
                args={"trace_id": cr.trace_id})
            self._obs.trace.flush()
        return cr.rid

    def _dispatch_locked(self, cr):
        """Assign (or reassign) a request; returns the (conn, frame)
        sends to perform OUTSIDE the lock."""
        pre = self._pick("prefill")
        dec = self._pick("decode")
        if pre is None or dec is None:
            cr.state = "failed"
            cr.error = ClusterFailed(
                "no healthy %s worker" %
                ("prefill" if pre is None else "decode"))
            self._terminal.append(cr.rid)
            self._finish_locked(cr)
            return []
        cr.prefill, cr.decode = pre.name, dec.name
        cr.phase = "prefill"
        pre.outstanding.add(cr.rid)
        dec.outstanding.add(cr.rid)
        inp = cr.prompt if not cr.committed else np.concatenate(
            [cr.prompt, np.asarray(cr.committed, np.int32)])
        owner, depth, tier = self.index.match(
            chain_keys(inp, self.page_size))
        hint = None
        if owner is not None and owner != pre.name:
            wo = self.workers.get(owner)
            if wo is not None and wo.alive:
                hint = owner
        # hint_tier (round 18): where the owner's copy lives —
        # "hbm" (device pool, a gather away) or "host" (spilled to
        # the owner's host tier, served without any device work).
        # The prefill worker weighs the peer fetch against its OWN
        # hot + warm local depth (probe_depth), so a peer copy only
        # wins when it covers strictly more than local HBM + local
        # host DRAM together — transfer must beat transfer, not just
        # prefill.
        meta = {"rid": cr.rid, "gen": cr.gen,
                "max_new": cr.max_new_tokens - len(cr.committed),
                "eos": cr.eos_id, "decode": dec.name,
                "hint": hint, "hint_depth": depth,
                "hint_tier": tier if hint is not None else None,
                "trace_id": cr.trace_id}
        return [(pre.conn, ("submit", meta,
                            [np.ascontiguousarray(inp).data]))]

    def _do_sends(self, sends):
        for conn, (kind, meta, bufs) in sends:
            try:
                conn.send(kind, meta, bufs)
            except OSError:
                pass                      # the monitor will fail it over

    def drain(self, timeout=None):
        """Wait until every submitted request reaches a terminal
        state.  Returns True if fully drained (the same contract as
        ``ServingCluster.drain`` — the trace-replay harness drives
        both flavors through it)."""
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        for cr in list(self.requests.values()):
            left = None if deadline is None \
                else max(0.0, deadline - time.perf_counter())
            if not cr.done_evt.wait(left):
                return False
        return True

    def result(self, rid, timeout=None):
        """Block until the request finishes; returns prompt +
        generated tokens.  Raises :class:`ClusterFailed` if no healthy
        worker could finish it."""
        cr = self.requests.get(rid)
        if cr is None:
            raise KeyError("result(%d): unknown rid (already "
                           "collected and purged?)" % rid)
        if not cr.done_evt.wait(timeout):
            raise TimeoutError("result(%d): still running" % rid)
        with self._lock:
            cr.delivered = True
            self._purge_locked()
        if cr.state == "done":
            return cr.output
        if cr.state == "cancelled":
            raise RequestCancelled("request %d was cancelled" % rid)
        raise ClusterFailed("request %d: %r" % (rid, cr.error))

    # ---------------------------------------------------- failover ---
    def _fail_worker(self, wh, error):
        """A worker process died or stalled: fence it, drop its index
        entries, resubmit its requests to survivors with the streamed
        committed tokens as prompt extension (recompute-exact)."""
        sends = []
        with self._lock:
            if wh.dead:
                return
            wh.dead = True
            wh.error = error
            self._standby.discard(wh.name)
            self.index.drop_owner(wh.name)
            # a SIGKILLed worker cannot sweep its own unreceived put
            # segments (its orderly-exit sweep never ran) — reclaim
            # them by its pid; a receiver mid-open just sees ENOENT,
            # which reads as the sender's death (it IS dead)
            pid = wh.pid or (wh.proc.pid if wh.proc is not None
                             else None)
            if pid is not None:
                from .transport import put_sweep
                put_sweep(pid)
                # round 23 forensics: the victim's span buffer died
                # with it, but its flight-recorder ring is
                # crash-durable (mmap, page cache) — recover the tail
                # by the same pid key the put sweep uses
                from ..obs.flight import flight_recover
                tail = flight_recover(pid, unlink=True)
                if tail:
                    wh.flight_tail = tail
                    self._flight_tails[wh.name] = tail
            if self._obs is not None:
                self._obs.failovers.inc()
                self._obs.g_workers.set(self._serving_count())
            # a request in the prefill phase dies with either of its
            # assigned workers (pages may already be streaming to the
            # decode side); one that completed handoff only dies with
            # its DECODE worker — the prefill side is out of the loop
            victims = [
                cr for cr in self.requests.values()
                if cr.state == "running"
                and ((cr.phase == "prefill"
                      and wh.name in (cr.prefill, cr.decode))
                     or (cr.phase == "decode"
                         and wh.name == cr.decode))]
            for cr in victims:
                cr.gen += 1
                cr.failovers += 1
                for side in (cr.prefill, cr.decode):
                    w = self.workers.get(side)
                    if w is not None:
                        w.outstanding.discard(cr.rid)
                # fence + free whatever the surviving side holds
                for side in set((cr.prefill, cr.decode)):
                    w = self.workers.get(side)
                    if w is not None and w.alive:
                        sends.append((w.conn, ("abort",
                                               {"rid": cr.rid,
                                                "below_gen":
                                                cr.gen}, [])))
                # already satisfiable from streamed tokens?
                done = (len(cr.committed) >= cr.max_new_tokens
                        or (cr.eos_id is not None
                            and cr.eos_id in cr.committed))
                if done:
                    cr.output = np.concatenate(
                        [cr.prompt,
                         np.asarray(cr.committed, np.int32)])
                    cr.state = "done"
                    if self._obs is not None:
                        self._obs.completed.inc()
                    self._terminal.append(cr.rid)
                    self._finish_locked(cr)
                    continue
                sends.extend(self._dispatch_locked(cr))
                if cr.state == "running" and self._obs is not None:
                    self._obs.resubmitted.inc()
        try:
            wh.conn.close()
        except Exception:
            pass
        self._do_sends(sends)
        self._flight.record("worker_dead", worker=wh.name,
                            error=repr(error))
        tail = wh.flight_tail
        if tail and profiler.is_recording():
            # fold the victim's final events into the live merged
            # trace as instants on its swimlane — the chaos test's
            # checked artifact
            for ev in tail:
                self._merged.add_flight(wh.name, ev,
                                        wh.clock_offset)
            self._merged.flush()

    def flight_tail(self, name):
        """The recovered flight-recorder tail of a dead worker
        (seq-ordered event dicts), or ``None`` — post-mortem
        debugging surface, also summarized in ``debug_status()``."""
        with self._lock:
            return self._flight_tails.get(name)

    def _monitor_loop(self):
        period = max(0.05, min(0.5, self.watchdog_s / 4.0))
        while True:
            time.sleep(period)
            with self._lock:
                if self._closed:
                    return
                suspects = []
                now = time.perf_counter()
                for wh in self.workers.values():
                    if wh.dead:
                        continue
                    if wh.proc is not None and not wh.proc.is_alive():
                        suspects.append((wh, "process exited"))
                    elif wh.outstanding and \
                            now - wh.last_seen > self.watchdog_s:
                        suspects.append((wh, "stalled past watchdog "
                                         "%.1fs" % self.watchdog_s))
            for wh, why in suspects:
                self._fail_worker(wh, RuntimeError(
                    "worker %s: %s" % (wh.name, why)))

    # --------------------------------------------------- accounting --
    _stats_seq = itertools.count(1)

    def cluster_stats(self, timeout=5.0):
        """Fresh per-worker stats snapshot (stats-request round
        trip, correlated by sequence id): {name: {..engine/prefix/
        streamer counters..}} for LIVE workers."""
        sid = next(self._stats_seq)
        live = [w for w in self.workers.values() if w.alive]
        for wh in live:
            wh.stats_sid = sid
            wh.stats_evt.clear()
        for wh in live:
            try:
                wh.conn.send("stats_req", {"sid": sid})
            except OSError:
                pass
        deadline = time.perf_counter() + timeout
        for wh in live:
            wh.stats_evt.wait(max(0.0,
                                  deadline - time.perf_counter()))
        return {wh.name: dict(wh.stats) for wh in live}

    def health(self):
        now = time.perf_counter()
        with self._lock:
            return [{"worker": w.name, "role": w.role,
                     "alive": w.alive, "dead": w.dead,
                     "standby": w.name in self._standby,
                     "draining": w.draining,
                     "outstanding": len(w.outstanding),
                     "heartbeat_age_s": now - w.last_seen,
                     "pid": None if w.proc is None else w.proc.pid,
                     "error": repr(w.error) if w.error else None}
                    for w in self.workers.values()]

    # --------------------------------------- ops introspection (23) --
    def _slo_locked(self, now):
        """SLO burn-rate gauges from the router's TTFT window: the
        fraction of recent requests over the
        ``MXNET_SERVE_SLO_TTFT_MS`` budget, expressed as a burn rate
        against the 1% error budget of a 99% objective (>1.0 means
        the window is eating budget faster than it refills)."""
        budget_ms = self._slo_ttft_ms
        windows = {}
        # zip, not ((label, win), …): a 2-tuple whose second element
        # is a ("str", …) tuple reads as a queued wire send to
        # protolint's model — keep ops plumbing out of the protocol
        for label, win_s in zip(("1m", "5m"), (60.0, 300.0)):
            n = bad = 0
            for t, ms in self._ttft_window:
                if now - t <= win_s:
                    n += 1
                    bad += ms > budget_ms
            frac = bad / n if n else 0.0
            windows[label] = {"requests": n, "over_budget": bad,
                              "bad_fraction": frac,
                              "burn_rate": frac / 0.01}
        return {"ttft_budget_ms": budget_ms, "windows": windows}

    def debug_status(self):
        """One-call ops snapshot behind ``GET /debug/statusz``: live
        topology, per-worker health + clock offsets + cached stats
        (tier occupancy included), in-flight request states, SLO burn
        gauges, and the flight-recorder state."""
        now = time.perf_counter()
        with self._lock:
            workers = []
            for w in self.workers.values():
                st = w.stats or {}
                tail = self._flight_tails.get(w.name)
                workers.append({
                    "worker": w.name, "role": w.role,
                    "alive": w.alive, "dead": w.dead,
                    "standby": w.name in self._standby,
                    "draining": w.draining,
                    "outstanding": len(w.outstanding),
                    "heartbeat_age_s": now - w.last_seen,
                    "pid": w.pid or (w.proc.pid
                                     if w.proc is not None else None),
                    "clock_offset_us": None if w.clock_rtt is None
                    else w.clock_offset * 1e6,
                    "clock_rtt_us": None if w.clock_rtt is None
                    else w.clock_rtt * 1e6,
                    "active_requests": st.get("active_requests"),
                    "pages_in_use": st.get("pages_in_use"),
                    "free_pages": st.get("free_pages"),
                    "tier": st.get("tier"),
                    "flight_tail_events": None if tail is None
                    else len(tail),
                    "error": repr(w.error) if w.error else None})
            reqs = [{"rid": r.rid, "trace_id": r.trace_id,
                     "state": r.state, "phase": r.phase,
                     "prefill": r.prefill, "decode": r.decode,
                     "gen": r.gen, "committed": len(r.committed),
                     "failovers": r.failovers,
                     "age_s": now - r.submit_t,
                     "ttft_ms": None if r.first_token_t is None
                     else (r.first_token_t - r.submit_t) * 1e3}
                    for r in self.requests.values()
                    if r.state == "running"]
            slo = self._slo_locked(now)
            recovered = sorted(self._flight_tails)
        return {"kind": "disagg", "closed": self._closed,
                "workers": workers, "in_flight": reqs, "slo": slo,
                "flight": {"path": self._flight.path,
                           "recovered": recovered}}

    def request_trace(self, rid):
        """Everything the router knows about one request's timeline:
        its record (state/assignment/timing) plus every span workers
        shipped for it (clock-corrected store behind
        ``GET /debug/trace/<rid>``).  KeyError on a rid the router
        has never seen."""
        with self._lock:
            cr = self.requests.get(rid)
            router = None if cr is None else {
                "rid": cr.rid, "trace_id": cr.trace_id,
                "state": cr.state, "phase": cr.phase,
                "prefill": cr.prefill, "decode": cr.decode,
                "gen": cr.gen, "committed": len(cr.committed),
                "failovers": cr.failovers, "submit_t": cr.submit_t,
                "first_token_t": cr.first_token_t}
            spans = [dict(s) for s in self._span_store.get(rid, ())]
        if router is None and not spans:
            raise KeyError("request_trace(%r): unknown rid" % (rid,))
        return {"rid": rid, "router": router, "spans": spans}

    @property
    def registry(self):
        return self._obs.registry if self._obs is not None else None

    def kill_worker(self, name, sig=None):
        """Test/ops helper: SIGKILL a spawned worker process."""
        import signal as _signal
        wh = self.workers[name]
        if wh.proc is None:
            raise ValueError("worker %s was not spawned locally"
                             % name)
        os.kill(wh.proc.pid, sig or _signal.SIGKILL)

    # ------------------------------------------------- scale-up/down --
    def _handshake_one(self, wh, timeout):
        """Handshake ONE late worker on the live listener (the
        add_worker path — same protocol as the construction-time
        ``_handshake_all``).  Hellos from OTHER concurrently-joining
        workers are stashed, not closed — closing them would kill a
        sibling's join (the multi-worker ``--workers-only`` flow
        starts several workers at once; _handshake_all's any-name
        acceptance has the same property at construction)."""
        deadline = time.perf_counter() + timeout
        with self._lock:
            conn = self._early_hellos.pop(wh.name, None)
        while conn is None:
            left = deadline - time.perf_counter()
            if left <= 0:
                raise RuntimeError(
                    "add_worker: %s never connected" % wh.name)
            try:
                cand = self._pending_conns.get(timeout=min(left, 1.0))
            except queue.Empty:
                continue
            got = cand.recv(timeout=left)
            if got in (None, "timeout"):
                cand.close()
                continue
            kind, meta, _ = got
            name = meta.get("name") if kind == "hello" else None
            if name == wh.name:
                conn = cand
                wh.pid = meta.get("pid")
            elif name:
                # a sibling joiner beat us to the accept queue: park
                # its hello'd connection for ITS add_worker call
                with self._lock:
                    old = self._early_hellos.pop(name, None)
                    self._early_hellos[name] = cand
                if old is not None:
                    old.close()
            else:
                cand.close()
        wh.conn = conn
        pm, pb = self._params_frames
        wh.conn.send("config",
                     {"cfg": self.cfg, "role": wh.role,
                      "engine_kwargs": self._engine_kwargs,
                      "params_meta": pm,
                      "watchdog_s": self.watchdog_s}, pb)
        got = wh.conn.recv(timeout=max(
            1.0, deadline - time.perf_counter()))
        if got in (None, "timeout") or got[0] != "ready":
            raise RuntimeError(
                "add_worker: worker %s failed to build its engine: "
                "%s" % (wh.name, _why_not_ready(got)))
        _, meta, _ = got
        wh.data_host = meta["data_host"]
        wh.data_port = meta["data_port"]
        wh.last_seen = time.perf_counter()

    def add_worker(self, role, spawn=None, ready_timeout=None,
                   standby=False):
        """Scale-up actuation (round 16): add one more ``role``
        worker PROCESS to the live cluster.  ``spawn=True`` forks it
        here (multiprocessing spawn, like construction);
        ``spawn=False`` waits for an externally-launched worker —
        ``tools/launch.py --launcher serve --workers-only`` (or bare
        ``run_worker()`` with ``MXNET_SERVE_*`` env) started against
        this router's port, which is how an autoscaler adds capacity
        on ANOTHER host.  Blocks through handshake + engine pre-warm;
        every live worker receives the refreshed peer map.  Returns
        the new worker's name.

        ``standby=True`` (round 18, the pre-provisioned-join path):
        the worker is brought ALL the way up — handshake, params
        ship, engine build, step-program pre-warm, peer map — but
        parked out of routing and out of the healthy-capacity gauge.
        ``scale_up()`` adopts the warmest standby of the needed role
        in O(peer-map flip) instead of paying process-spawn + jax
        import + compile (~15 s on CPU — longer than the whole burst
        the round-16 goodput row measured; the honest caveat this
        path exists to close)."""
        if role not in ("prefill", "decode"):
            raise ValueError("add_worker: role must be 'prefill' or "
                             "'decode', got %r" % (role,))
        if ready_timeout is None:
            ready_timeout = _env_default(
                "MXNET_SERVE_READY_TIMEOUT_S", 120.0)
        with self._lock:
            if self._closed:
                raise ClusterClosed("add_worker() after close()")
            i = 0
            while "%s%d" % (role, i) in self.workers:
                i += 1
            name = "%s%d" % (role, i)
            wh = _WorkerHandle(name, role)
            # hidden from _pick until FULLY ready: the handshake sets
            # wh.conn (making it "alive") several messages before the
            # worker has its peer map — a submit dispatched into that
            # window would hit a worker still in __init__, which
            # treats the unexpected frame as a broken handshake and
            # dies
            wh.draining = True
            self.workers[name] = wh
        if spawn is None:
            spawn = self._spawn
        try:
            if spawn:
                self._spawn_worker(wh)
            self._handshake_one(wh, ready_timeout)
        except BaseException:
            with self._lock:
                wh.dead = True
                self._standby.discard(name)
                self.workers.pop(name, None)
            if wh.proc is not None and wh.proc.is_alive():
                wh.proc.terminate()
                wh.proc.join(timeout=5)   # reap the zombie pid
            if wh.conn is not None:
                wh.conn.close()
            raise
        with self._lock:
            peers = {n: {"role": w.role, "host": w.data_host,
                         "port": w.data_port}
                     for n, w in self.workers.items() if w.alive}
            targets = [w for w in self.workers.values() if w.alive]
        for w in targets:
            try:
                w.conn.send("peers", {"peers": peers})
            except OSError:
                pass                      # the monitor will fail it over
        wh.recv_thread = threading.Thread(
            target=self._recv_loop, args=(wh,), daemon=True,
            name="disagg-recv-" + wh.name)
        wh.recv_thread.start()
        self._clock_ping(wh)
        with self._lock:
            if standby:
                # fully warm, deliberately invisible: stays draining
                # (never routed, never chaos-targeted) until adopted
                self._standby.add(name)
            else:
                wh.draining = False       # ready: now routable
            if self._obs is not None:
                self._obs.g_workers.set(self._serving_count())
        return name

    def adopt_standby(self, role):
        """Put one pre-provisioned standby ``role`` worker into
        rotation (round 18).  O(flag flip): the worker is already
        handshaken, pre-warmed, and in every peer map.  Returns its
        name, or None when no standby of that role is parked."""
        with self._lock:
            for name in sorted(self._standby):
                wh = self.workers.get(name)
                if wh is not None and wh.role == role and wh.alive:
                    self._standby.discard(name)
                    wh.draining = False
                    if self._obs is not None:
                        self._obs.g_workers.set(self._serving_count())
                    return name
        return None

    def drain_worker(self, name, timeout=60.0):
        """Graceful scale-down of one worker process: stop routing to
        it, wait for its outstanding requests to finish, then shut it
        down (clean exit, not SIGKILL — its engine drains with zero
        in-flight loss).  Refuses to drain the last live worker of a
        role.  Returns True once drained and stopped; False (and back
        in rotation) on timeout."""
        with self._lock:
            wh = self.workers[name]
            if wh.dead:
                return False
            siblings = [w for w in self.workers.values()
                        if w.role == wh.role and w.alive
                        and not w.draining and w is not wh]
            if not siblings:
                return False
            wh.draining = True
        deadline = time.perf_counter() + float(timeout)
        drained = False
        while time.perf_counter() < deadline:
            with self._lock:
                drained = not wh.outstanding
            if drained:
                break
            time.sleep(0.02)
        if not drained:
            with self._lock:
                wh.draining = False       # back in rotation
            return False
        with self._lock:
            wh.dead = True                # recv EOF won't fail over
            self._standby.discard(name)   # a drained spare is gone
            self.index.drop_owner(name)
            if self._obs is not None:
                self._obs.g_workers.set(self._serving_count())
        try:
            wh.conn.send("shutdown", {})
        except OSError:
            pass
        if wh.proc is not None:
            wh.proc.join(timeout=10)
            if wh.proc.is_alive():
                wh.proc.terminate()
                wh.proc.join(timeout=5)   # reap the zombie pid
        try:
            wh.conn.close()
        except Exception:
            pass
        return True

    # the autoscaler's actuation protocol (shared with
    # ServingCluster) — role-aware here: scale_up grows the role with
    # the higher mean outstanding load, scale_down drains the
    # least-loaded worker of any role that keeps >= 1 worker
    def scale_up(self):
        with self._lock:
            load = {}
            for role in ("prefill", "decode"):
                ws = [w for w in self.workers.values()
                      if w.role == role and w.alive
                      and not w.draining]
                load[role] = (float("inf") if not ws else
                              sum(len(w.outstanding) for w in ws)
                              / len(ws))
        role = max(sorted(load), key=lambda r: load[r])
        # a pre-provisioned standby of the needed role is adopted in
        # O(peer-map flip); only a cold cluster pays spawn + compile
        if self.adopt_standby(role) is not None:
            return True
        self.add_worker(role)
        return True

    def scale_down(self, timeout=60.0):
        with self._lock:
            cands = []
            for role in ("prefill", "decode"):
                ws = [w for w in self.workers.values()
                      if w.role == role and w.alive
                      and not w.draining]
                if len(ws) > 1:
                    cands.extend(ws)
            if not cands:
                return False
            name = min(cands, key=lambda w: (len(w.outstanding),
                                             w.name)).name
        return self.drain_worker(name, timeout=timeout)

    @property
    def slots_per_replica(self):
        return self._engine_kwargs["num_slots"]

    def close(self, timeout=30.0):
        with self._lock:
            self._closed = True
            workers = list(self.workers.values())
            # a result() waiter on another thread must not block
            # forever on a request the shutdown abandons — fail every
            # non-terminal request loudly (the in-process cluster
            # DRAINS instead; this transport has no graceful drain
            # yet, so honesty beats a silent hang)
            for cr in self.requests.values():
                if cr.state == "running":
                    cr.state = "failed"
                    cr.error = ClusterClosed(
                        "cluster closed with the request in flight")
                    self._terminal.append(cr.rid)
                    self._finish_locked(cr)
        for wh in workers:
            if wh.conn is not None:
                try:
                    wh.conn.send("shutdown", {})
                except OSError:
                    pass
        from .transport import put_sweep
        from ..obs.flight import flight_sweep
        for wh in workers:
            if wh.proc is not None:
                wh.proc.join(timeout=timeout)
                if wh.proc.is_alive():
                    wh.proc.terminate()
                    wh.proc.join(timeout=5)
            # drain the recv thread BEFORE closing the conn: the
            # worker's last-gasp frames (its final span ship) are
            # still in the socket buffer, and the thread exits on the
            # EOF the dead worker left only after folding them —
            # closing first would drop the trace tail of every
            # sub-tick run
            if wh.recv_thread is not None and \
                    wh.recv_thread is not threading.current_thread():
                wh.recv_thread.join(timeout=5)
            if wh.conn is not None:
                wh.conn.close()
            # belt over the workers' own exit sweeps: a worker that
            # died uncleanly leaves pid-prefixed segments (and its
            # flight ring) behind
            pid = wh.pid or (wh.proc.pid if wh.proc is not None
                             else None)
            if pid is not None:
                put_sweep(pid)
                flight_sweep(pid)
        self._flight.close(unlink=True)
        with self._lock:
            early = list(self._early_hellos.values())
            self._early_hellos.clear()
        for conn in early:
            try:
                conn.close()
            except Exception:
                pass
        self._listener.close()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


# --------------------------------------------------------------------------
# disaggregated worker-process side
# --------------------------------------------------------------------------

class _DisaggWorker:
    """One prefill or decode worker process: a single main loop owns
    the engine (all device work stays on one thread — receive threads
    only enqueue host bytes), a data listener serves peer page
    fetches / the prefill→decode stream, and a control connection
    carries submits/tokens/stats to the router."""

    def __init__(self, name, role, router_host, router_port):
        from .transport import connect, frames_to_tree, Listener
        self.name = name
        self.role = role
        self.inbox: "queue.Queue" = queue.Queue()
        self.fetch_inbox: "queue.Queue" = queue.Queue()
        self.router = connect(router_host, router_port, timeout=60.0,
                              retry_until=60.0)
        self.router.send("hello", {"name": name, "role": role,
                                   "pid": os.getpid()})
        got = self.router.recv(timeout=120.0)
        if got in (None, "timeout") or got[0] != "config":
            raise RuntimeError("worker %s: bad config handshake: %r"
                               % (name, got))
        _, meta, bufs = got
        self.cfg = meta["cfg"]
        self.watchdog_s = meta.get("watchdog_s", 30.0)
        params = frames_to_tree(meta["params_meta"], bufs)
        kw = dict(meta["engine_kwargs"])
        if role == "prefill":
            # the prefill replica's trie is the cluster's page source;
            # speculation never pays on a 1-token budget
            kw.update(prefix_cache=True, spec_K=0)
        else:
            kw.update(prefix_cache=False)
        try:
            self.eng = ServingEngine(params, self.cfg, **kw)
        except Exception as e:
            # the first touch of the device: tell the router why (a
            # chip this process could not claim, a pool that does not
            # fit) — it is in its READY wait and fails construction
            # with this reason instead of a bare EOF
            try:
                self.router.send("error", {"msg": repr(e)})
            except OSError:
                pass
            raise
        # pre-warm the compiled step BEFORE reporting ready: the
        # handshake timeout covers the compile, so the router's
        # watchdog never mistakes a first-request compile for a stall
        wid = self.eng.submit(np.ones(1, np.int32), 1)
        self.eng.run()
        del self.eng.requests[wid]
        # pre-warm the bucketed page-transfer programs too (round
        # 18): the first peer fetch, prefill->decode stream, or
        # pressure spill after handshake must pay a TRANSFER, not a
        # compile — a bucket-4 install compile inside a fetch reply
        # is most of a cold prefill on CPU.  One allocated page
        # repeated per bucket exercises every gather/scatter shape
        # the small-run paths use; the page is scratch-grade warmup
        # state and goes straight back to the free list.
        ids = self.eng.cache.alloc(1)
        if ids is not None:
            for b in (1, 2, 4, 8):
                content = self.eng.cache.export_pages(ids * b)
                self.eng.cache.install_pages(ids * b, content)
            self.eng.cache.free(ids)
        if self.eng.prefix is not None:
            self.eng.prefix.clear()
        for k in self.eng.stats:
            self.eng.stats[k] = type(self.eng.stats[k])()
        if self.eng.prefix is not None:
            self.eng.prefix.evict_cb = self._on_evict
            if self.eng.tier is not None:
                self.eng.prefix.tier_cb = self._on_tier_move
        if role == "prefill":
            self.eng.retire_cb = self._on_retire
        self._evicted_keys: List[bytes] = []
        # chain key -> last tier seen ("host"/"hbm"), flushed with the
        # stats tick as `tier` frames: absolute per-key state, so only
        # the LAST transition per key travels (a spill+restore inside
        # one tick cancels out to a no-op re-tag)
        self._tier_moves: Dict[bytes, str] = {}
        from .page_streamer import PageStreamer, PageReceiver
        self.streamer = PageStreamer(self.eng)
        self.receiver = PageReceiver(self.eng)
        # data plane: loopback for spawned local workers; an
        # externally-placed worker (another host) sets
        # MXNET_SERVE_DATA_HOST to ITS reachable address — we then
        # bind all interfaces and advertise that address to peers
        data_host = os.environ.get("MXNET_SERVE_DATA_HOST")
        self.listener = Listener(
            host="0.0.0.0" if data_host else "127.0.0.1")
        self.listener.start(self._peer_handler)
        self.router.send("ready",
                         {"data_host": data_host or "127.0.0.1",
                          "data_port": self.listener.port})
        got = self.router.recv(timeout=120.0)
        if got in (None, "timeout") or got[0] != "peers":
            raise RuntimeError("worker %s: no peer map" % name)
        self.peers = got[1]["peers"]
        self._peer_conns: Dict[str, object] = {}
        # request state: engine rid -> {rid, gen, meta, inp}
        self.by_erid: Dict[int, dict] = {}
        self.by_rid: Dict[int, int] = {}  # cluster rid -> engine rid
        self._reported: Dict[int, int] = {}   # rid -> tokens reported
        self.remote_hits = 0
        self.remote_hit_tokens = 0
        self.remote_hits_host_tier = 0
        self.fetch_bytes = 0
        self.pages_put_total = 0          # pages sent via put segments
        self.put_bytes_total = 0
        self._fetch_seq = 0               # fetch/reply correlation
        # rid -> lowest still-valid gen (per-request fence): a
        # fenced-out zombie prefill's late frames must be DROPPED —
        # letting them recreate staging would read as an out-of-order
        # stream and a protocol error must not kill a healthy worker
        self._fenced: Dict[int, int] = {}
        self.transfer_ms: List[float] = []
        self._last_stats = 0.0
        # round 23 observability: the crash-durable flight ring
        # (recovered by the router if we are SIGKILLed) and the span
        # staging buffer shipped to the router on the stats tick
        from ..obs.flight import FlightRecorder
        from ..obs.trace import SpanBuffer
        self._flight = FlightRecorder()
        self._spans = SpanBuffer()
        self._decode_t0: Dict[int, float] = {}   # rid -> admit time
        self._flight.record("ready", worker=name, role=role)
        self._running = True
        threading.Thread(target=self._router_recv, daemon=True,
                         name="disagg-router-recv").start()

    # -- feeder threads -> inbox ------------------------------------
    def _router_recv(self):
        while True:
            got = self.router.recv()
            if got is None:
                self.inbox.put(("_lost", None, None, None))
                return
            kind, meta, bufs = got
            self.inbox.put((kind, meta, bufs, None))

    def _peer_handler(self, conn):
        """One accepted peer connection: prefill→decode page streams
        and sibling FETCH requests; frames are enqueued with the conn
        so the main loop can reply in order.  The FIRST frame out is
        our transport caps (round 22) — the connector's ``wait_caps``
        relies on it preceding any reply."""
        try:
            conn.send_caps()
        except OSError:
            return
        while True:
            got = conn.recv()
            if got is None:
                return
            kind, meta, bufs = got
            if kind == "caps":
                continue                  # recorded on conn by recv
            if kind == "fetch":
                self.fetch_inbox.put((meta, bufs, conn))
                # wake token: an idle main loop is parked on the
                # general inbox — without it a fetch waits out the
                # full idle poll (20 ms) before being served, which
                # would dominate the remote-hit TTFT
                self.inbox.put(("_wake", None, None, None))
            else:
                self.inbox.put((kind, meta, bufs, conn))

    def _on_evict(self, key):
        self._evicted_keys.append(key)
        self._tier_moves.pop(key, None)   # gone beats any re-tag

    def _on_tier_move(self, key, tier):
        self._tier_moves[key] = tier

    def _on_retire(self, req):
        """Engine retire hook (prefill role): snapshot the finishing
        request's page ids + cache depth before ``_release`` clears
        them — the post-step handoff export streams from this
        snapshot (freed pages stay byte-intact until the next step's
        allocations)."""
        st = self.by_erid.get(req.rid)
        if st is not None:
            st["final_pages"] = list(req.pages)
            st["final_n_cached"] = req.n_cached
            st["final_chain_upto"] = req.chain_upto

    # -- remote prefix fetch (prefill role) -------------------------
    def _peer_conn(self, owner):
        from .transport import connect
        conn = self._peer_conns.get(owner)
        if conn is None or conn.closed:
            p = self.peers[owner]
            conn = connect(p["host"], p["port"], timeout=10.0)
            # caps handshake (round 22): advertise ours, learn theirs
            # (the acceptor's caps frame is its first) — a timeout
            # just means a socket-only peer, never a failure
            try:
                conn.send_caps()
                conn.wait_caps(timeout=5.0)
            except OSError:
                conn.close()              # died mid-handshake
                raise
            self._peer_conns[owner] = conn
        return conn

    def _send_pages_frame(self, conn, kind, meta, bufs):
        """Send a page-carrying frame (``pages`` stream or
        ``fetch_reply``) over the negotiated transport: a /dev/shm
        put when both ends advertised same-host ``put_pages``, else
        inline socket bytes — the segment holds EXACTLY the bytes the
        socket body would, so the two paths are bit-identical on
        install.  Raises OSError like ``conn.send`` (callers' peer
        failover paths apply unchanged)."""
        from .transport import put_capability, put_eligible, put_write
        if bufs and put_eligible(put_capability(), conn.peer_put):
            path, sizes = put_write(bufs)
            try:
                conn.send(kind, dict(
                    meta, put={"path": path, "sizes": sizes}), ())
            except BaseException:
                # the peer never got the frame: the segment has no
                # unlinker left — reclaim it before re-raising into
                # the caller's drop/abandon path
                try:
                    os.unlink(path)
                except OSError:
                    pass
                raise
            # receipt is invisible to the sender (the receiver
            # unlinks at open); a receiver that dies between our send
            # and its open strands the segment — our pid-prefixed
            # name makes it sweepable (put_sweep at our exit, or the
            # router's by-pid sweep if WE are the one killed)
            self.pages_put_total += int(meta.get("n", len(bufs)))
            self.put_bytes_total += sum(sizes)
        else:
            conn.send(kind, meta, bufs)

    def _serve_fetches(self):
        """Answer queued sibling FETCH requests (also called while
        WAITING on our own fetch — two replicas fetching from each
        other must not deadlock).  The reply goes out on EVERY exit
        edge: if serving the fetch raises, the requester gets an n=0
        miss NOW instead of waiting out its full fetch timeout on a
        reply that will never come — and one bad fetch must not take
        down the whole worker (proto-reply-pairing's checked
        invariant)."""
        while True:
            try:
                meta, bufs, conn = self.fetch_inbox.get_nowait()
            except queue.Empty:
                return
            reply_bufs = []
            n_full = 0
            try:
                tokens = np.frombuffer(bytes(bufs[0]), np.int32)
                if self.eng.prefix is not None:
                    # restore=False: serving a sibling must not spend
                    # OUR pool pages re-installing spilled chains —
                    # the spilled tail ships straight from host DRAM
                    entries, pages, m = self.eng.prefix.match(
                        tokens, restore=False)
                    try:
                        n_hot = min(len(pages),
                                    m // self.eng.page_size)
                        parts = []
                        if n_hot:
                            parts.append(self.eng.cache.export_pages(
                                pages[:n_hot]))
                        # round 18: spilled continuation off the host
                        # tier — a spilled chain stays P2P-fetchable,
                        # and CHEAPER to serve (no device gather)
                        tail = self.eng.prefix.spilled_content(
                            tokens, n_hot)
                        n_full = n_hot + len(tail)
                        parts.extend(tail)
                        if parts:
                            from .page_streamer import (
                                merge_page_content, pages_to_bufs)
                            reply_bufs = pages_to_bufs(
                                merge_page_content(parts))
                    finally:
                        self.eng.prefix.release(entries)
            except Exception:
                # degrade to a miss: the requester falls back to a
                # cold prefill instead of eating its fetch timeout
                n_full, reply_bufs = 0, []
            try:
                self._send_pages_frame(
                    conn, "fetch_reply",
                    {"n": n_full, "fid": meta.get("fid"),
                     "trace_id": meta.get("trace_id"),
                     "t_send": time.perf_counter()},
                    reply_bufs)
                self.fetch_bytes += sum(
                    memoryview(b).nbytes for b in reply_bufs)
            except OSError:
                pass                      # requester died: their loss

    def _fetch_remote(self, owner, tokens, timeout=15.0,
                      peer_tier=None, trace_id=None):
        """Fetch the longest cached chain for ``tokens`` from a
        sibling replica and graft it into the local trie.  A miss (or
        a dead/slow peer) degrades to a cold local prefill — the
        exactness contract never depends on the fetch.  ``peer_tier``
        is the router's tag for the owner's copy (``hbm``/``host``) —
        accounting only: a spilled peer chain serves from its host
        tier without a device gather, and the per-tier hit counters
        are how the tier-sweep benchmark prices that difference."""
        from .page_streamer import bufs_to_pages, _release
        self._fetch_seq += 1
        fid = self._fetch_seq
        try:
            conn = self._peer_conn(owner)
            conn.send("fetch", {"fid": fid, "trace_id": trace_id},
                      [np.ascontiguousarray(tokens).data])
        except (OSError, KeyError):
            return 0
        deadline = time.perf_counter() + timeout
        while True:
            left = deadline - time.perf_counter()
            if left <= 0:
                # a reply may still be in flight on this cached conn;
                # drop the conn so a LATER fetch cannot mistake the
                # stale reply (old tokens' page bytes!) for its own
                self._peer_conns.pop(owner, None)
                conn.close()
                return 0
            got = conn.recv(timeout=min(left, 0.05))
            if got == "timeout":
                self._serve_fetches()     # break fetch-fetch deadlock
                continue
            if got is None:
                self._peer_conns.pop(owner, None)
                return 0
            kind, meta, bufs = got
            if kind != "fetch_reply" or meta.get("fid") != fid:
                _release(bufs)            # stale put reply: unmap it
                continue                  # stale/uncorrelated frame
            break
        n = meta["n"]
        if not n:
            return 0
        ps = self.eng.page_size
        ids = self.eng.cache.alloc(n)
        if ids is None:
            _release(bufs)                # put segment: unmap now
            return 0                      # pool too tight: stay cold
        self.eng.cache.install_pages(
            ids, bufs_to_pages(self.eng.cache, n, bufs))
        _release(bufs)
        created = self.eng.prefix.insert_chain(
            tokens[:n * ps], ids, upto_page=n)
        created_idx = {j for j, _ in created}
        # pages whose chain position was already cached locally stay
        # unowned — free them instead of leaking
        extra = [ids[j] for j in range(n) if j not in created_idx]
        if extra:
            self.eng.cache.free(extra)
        # the fetched entries are cache-owned (refcount 0 until a
        # request maps them); drop the donor refs insert_chain took
        self.eng.prefix.release([e for _, e in created])
        self.remote_hits += 1
        self.remote_hit_tokens += n * ps
        if peer_tier == "host":
            self.remote_hits_host_tier += 1
        self.transfer_ms.append(
            (time.perf_counter() - meta["t_send"]) * 1e3)
        # bytes are counted SENDER-side only (the owner's
        # _serve_fetches), matching the prefill→decode stream
        # convention — counting here too would double every fetch in
        # cluster_page_bytes_streamed_total
        return n * ps

    # -- message handling -------------------------------------------
    def _handle(self, kind, meta, bufs, conn):
        if kind == "submit":
            inp = np.frombuffer(bytes(bufs[0]), np.int32)
            if meta["gen"] < self._fenced.get(meta["rid"], -1):
                # a late dispatch racing an abort for a NEWER
                # incarnation of the same rid: the router no longer
                # wants this gen — admitting it would resurrect a
                # fenced zombie (proto-gen-fence checked invariant)
                return
            t_recv = time.perf_counter()
            tid = meta.get("trace_id")
            self._flight.record("submit_recv", rid=meta["rid"],
                                gen=meta["gen"], trace_id=tid)
            self._spans.instant(meta["rid"], "submit_recv", t_recv,
                                trace_id=tid)
            if meta.get("hint") and self.eng.prefix is not None:
                # round 18: the local depth a fetch must beat counts
                # BOTH tiers — hot trie pages and spilled (host-tier)
                # pages, which restore for one install.  A peer copy
                # wins only on strictly deeper coverage: transfer
                # competes with transfer, not with prefill
                # (probe_depth takes no refs and restores nothing).
                hot, warm = self.eng.prefix.probe_depth(inp)
                if meta["hint_depth"] > hot + warm:
                    t0f = time.perf_counter()
                    got = self._fetch_remote(
                        meta["hint"], inp,
                        peer_tier=meta.get("hint_tier"),
                        trace_id=tid)
                    # the remote-hit transfer, visible INSIDE this
                    # request's TTFT span in the merged dump
                    self._spans.span(
                        meta["rid"], "fetch", t0f,
                        time.perf_counter(), trace_id=tid,
                        cat="transport",
                        args={"owner": meta["hint"],
                              "hit_tokens": got})
            try:
                erid = self.eng.submit(
                    inp, 1 if self.role == "prefill"
                    else meta["max_new"], eos_id=meta["eos"],
                    trace_id=tid)
            except Exception as e:
                # a request THIS engine rejects fails alone — it must
                # not take the worker (and every other request on it)
                # down with it
                self.router.send("reqfail", {"rid": meta["rid"],
                                             "gen": meta["gen"],
                                             "msg": repr(e)})
                return
            self.by_erid[erid] = {"rid": meta["rid"],
                                  "gen": meta["gen"],
                                  "meta": meta, "inp": inp,
                                  "t0": t_recv}
            self.by_rid[meta["rid"]] = erid
            self._reported[meta["rid"]] = 0
        elif kind == "pages":
            key = tuple(meta["srid"])
            if key[1] < self._fenced.get(key[0], -1):
                return                    # zombie incarnation's frame
            try:
                self.receiver.on_pages(key, meta["start"],
                                       meta["n"], bufs)
            except RuntimeError:
                # a gapped stream cannot be resumed; drop ITS staging
                # and let the router's reassignment recover — one bad
                # stream must not take down the whole worker
                self.receiver.abort(key)
                return
            now = time.perf_counter()
            self.transfer_ms.append((now - meta["t_send"]) * 1e3)
            self._flight.record("pages_recv", rid=key[0],
                                start=meta["start"], n=meta["n"])
            # the prefill->decode page transfer as a transport-lane
            # span: t0 is the SENDER's t_send on the same-host
            # monotonic clock (the h_transfer convention)
            self._spans.span(key[0], "transfer", meta["t_send"], now,
                             trace_id=meta.get("trace_id"),
                             cat="transport",
                             args={"start": meta["start"],
                                   "pages": meta["n"]})
        elif kind == "handoff":
            key = tuple(meta["srid"])
            if key[1] < self._fenced.get(key[0], -1):
                return
            self.receiver.on_handoff(
                key, meta["total"],
                dict(meta, prompt=np.frombuffer(bytes(bufs[0]),
                                                np.int32)))
            self._flight.record("handoff_recv", rid=key[0],
                                total=meta["total"])
            self._spans.instant(key[0], "handoff_recv",
                                time.perf_counter(),
                                trace_id=meta.get("trace_id"))
        elif kind == "abort":
            # flight record AFTER the fenced abort: protolint's
            # gen-fence rule wants no state touched before the fence
            self._abort(meta["rid"], meta["below_gen"])
            self._flight.record("abort", rid=meta["rid"],
                                below_gen=meta["below_gen"])
        elif kind == "cancel":
            # round 20: client-disconnect propagation.  Same fencing
            # and cleanup as a failover abort — drop staged pages,
            # force-retire the engine request (pages + slot recycle
            # NOW, not at generation end) — but nothing resubmits
            # afterwards: the router already retired the request.  A
            # late cancel for a gen that already died is a no-op by
            # the same fence.
            self._abort(meta["rid"], meta["below_gen"])
            self._flight.record("cancel", rid=meta["rid"],
                                trace_id=meta.get("trace_id"))
        elif kind == "drop":
            key = tuple(meta["srid"])
            if key[1] < self._fenced.get(key[0], -1):
                return                    # zombie incarnation's frame
            # the prefill side completed this request itself: free
            # any staged pages of its stream
            self.receiver.abort(key)
        elif kind == "peers":
            # live peer-map refresh (router add_worker/scale-up):
            # only ever grows or re-addresses — cached conns to
            # still-present peers stay valid
            self.peers = meta["peers"]
        elif kind == "stats_req":
            self._send_stats(sid=meta.get("sid"))
        elif kind == "clock_req":
            # ping-pong clock-offset probe (round 23): echo the
            # router's t0 with OUR clock read, immediately — any
            # extra queueing here inflates the RTT estimate, and the
            # router's min-RTT filter discards the sample
            try:
                self.router.send("clock",
                                 {"seq": meta["seq"],
                                  "t0": meta["t0"],
                                  "t_worker": time.perf_counter()})
            except OSError:
                self._running = False
        elif kind == "caps":
            pass                          # recorded on the conn by recv
        elif kind == "_wake":
            pass                          # fetch_inbox wake token
        elif kind in ("shutdown", "_lost"):
            self._running = False

    def _abort(self, rid, below_gen):
        """Fence a resubmitted incarnation: drop staged pages and any
        running engine request with an older gen; remember the fence
        so the zombie's LATE frames drop instead of recreating
        staging."""
        if below_gen > self._fenced.get(rid, -1):
            self._fenced[rid] = below_gen
            if len(self._fenced) > 4096:  # bound: oldest rids first
                for k in sorted(self._fenced)[:1024]:
                    del self._fenced[k]
        for key in [k for k in self.receiver.staged_rids
                    if k[0] == rid and k[1] < below_gen]:
            self.receiver.abort(key)
        erid = self.by_rid.get(rid)
        if erid is not None and self.by_erid[erid]["gen"] < below_gen:
            self.by_erid.pop(erid)
            self.by_rid.pop(rid, None)
            self._reported.pop(rid, None)
            self._decode_t0.pop(rid, None)
            self.streamer.drop(erid)
            if erid in self.eng.requests:
                self.eng.cancel(erid)
                del self.eng.requests[erid]

    # -- per-step work ----------------------------------------------
    def _admit_ready(self):
        """Decode role: admit handed-off requests whose pages are all
        installed, as slots free up.  Installs themselves run AFTER
        the step (round 21 — off the dispatch critical path, hidden
        behind the launched step's device time at depth 1); when
        the engine is idle there is nothing to hide behind, so
        install eagerly here."""
        if self.eng._inflight is None and not any(
                s is not None for s in self.eng._slots):
            self.receiver.retry_installs()
        for key in list(self.receiver.staged_rids):
            if not self.receiver.ready(key):
                continue
            if self.eng.free_slots == 0:
                return
            pages, meta = self.receiver.take(key)
            rid, gen = key
            erid = self.eng.admit_prefilled(
                meta["prompt"], meta["toks"], pages,
                max_new_tokens=meta["max_new"], eos_id=meta["eos"])
            self.by_erid[erid] = {"rid": rid, "gen": gen,
                                  "meta": meta}
            self.by_rid[rid] = erid
            t_admit = time.perf_counter()
            self._decode_t0[rid] = t_admit
            self._flight.record("admit", rid=rid, gen=gen)
            self._spans.instant(rid, "admit_prefilled", t_admit,
                                trace_id=meta.get("trace_id"))
            # report from zero: the handoff tokens travel to the
            # router in OUR stream (single FIFO connection), not the
            # prefill worker's — cross-connection ordering is the
            # race _on_handed documents
            self._reported[rid] = 0

    def _abandon(self, erid, st):
        """The decode peer is unreachable (connect refused, or a send
        died mid-stream — which also means the decode side's in-order
        page stream now has a gap): abandon this incarnation and hand
        the request BACK to the router for reassignment.  Merely
        relying on decode-death failover is not enough — the peer
        PROCESS may be alive with only the data-plane link broken,
        and its heartbeats would keep the watchdog quiet forever."""
        try:
            self.router.send("lost", {"rid": st["rid"],
                                      "gen": st["gen"]})
        except OSError:
            pass                          # router gone: shutting down
        self.streamer.drop(erid)
        self.by_erid.pop(erid, None)
        self.by_rid.pop(st["rid"], None)
        self._reported.pop(st["rid"], None)
        if erid in self.eng.requests:
            if self.eng.requests[erid].state in ("queued", "running"):
                self.eng.cancel(erid)
            del self.eng.requests[erid]

    def _stream_pages(self, finished):
        """Prefill role: after a step, stream newly-completed pages of
        every in-flight handoff; finish the stream + hand off for
        requests that sampled their token this step."""
        fin = set(finished or ())
        for erid, st in list(self.by_erid.items()):
            req = self.eng.requests.get(erid)
            if req is None:
                continue
            final = erid in fin
            dec = self._conn_or_none(st["meta"]["decode"])
            if final:
                out = self.streamer.pump(
                    erid, st.get("final_n_cached", req.n_cached),
                    st.get("final_pages", req.pages), final=True)
            else:
                out = self.streamer.pump(erid, req.n_cached,
                                         req.pages)
            if out is not None and dec is not None:
                start, n, bufs = out
                try:
                    self._send_pages_frame(
                        dec, "pages",
                        {"srid": (st["rid"], st["gen"]),
                         "start": start, "n": n,
                         "trace_id": st["meta"].get("trace_id"),
                         "t_send": time.perf_counter()}, bufs)
                    self._flight.record("pages_sent", rid=st["rid"],
                                        start=start, n=n)
                except OSError:
                    self._drop_peer(st["meta"]["decode"])
                    dec = None            # gap in the stream: abandon
            if dec is None and st["meta"]["max_new"] > 1:
                self._abandon(erid, st)
                continue
            if final:
                toks = [int(t) for t in req.generated]
                total = self.streamer.pending(erid)
                remaining = st["meta"]["max_new"] - len(toks)
                eos = st["meta"]["eos"]
                if eos is not None and toks and toks[-1] == eos:
                    remaining = 0         # eos at prefill: complete
                if remaining > 0:
                    try:
                        dec.send(
                            "handoff",
                            {"srid": (st["rid"], st["gen"]),
                             "total": total, "toks": toks,
                             "max_new": st["meta"]["max_new"],
                             "eos": st["meta"]["eos"],
                             "trace_id":
                                 st["meta"].get("trace_id")},
                            [np.ascontiguousarray(st["inp"]).data])
                    except OSError:
                        # the decode side never got the handoff:
                        # reporting "handed" anyway would strand the
                        # request on a worker that keeps heartbeating
                        self._drop_peer(st["meta"]["decode"])
                        self._report_inserts(
                            req, st.get("final_chain_upto", 0))
                        self._abandon(erid, st)
                        continue
                    # phase flip only — the decode worker reports the
                    # tokens (see _on_handed)
                    self.router.send("handed", {"rid": st["rid"],
                                                "gen": st["gen"]})
                else:
                    # 1-token budget / eos at prefill: prefill was
                    # the whole request — tell the decode side to
                    # drop any pages already streamed to it, or they
                    # leak in its staging
                    self.router.send("done", {"rid": st["rid"],
                                              "gen": st["gen"],
                                              "toks": toks})
                    if dec is not None:
                        try:
                            dec.send("drop",
                                     {"srid": (st["rid"],
                                               st["gen"])})
                        except OSError:
                            pass
                t1 = time.perf_counter()
                tid = st["meta"].get("trace_id")
                self._spans.span(st["rid"], "prefill",
                                 st.get("t0", t1), t1, trace_id=tid,
                                 args={"toks": len(toks),
                                       "pages": total,
                                       "handed": remaining > 0})
                self._flight.record(
                    "handoff_sent" if remaining > 0 else "done",
                    rid=st["rid"], total=total)
                self._report_inserts(req,
                                     st.get("final_chain_upto", 0))
                self.streamer.drop(erid)
                self.by_erid.pop(erid, None)
                self.by_rid.pop(st["rid"], None)
                self._reported.pop(st["rid"], None)
                del self.eng.requests[erid]

    def _report_inserts(self, req, chain_upto):
        """Tell the router which chains this replica now holds
        (``chain_upto`` from the retire-time snapshot — ``_release``
        zeroes the live field before this runs)."""
        if self.eng.prefix is None or chain_upto == 0:
            return
        keys = chain_keys(req.prompt,
                          self.eng.page_size)[:chain_upto]
        if keys:
            try:
                self.router.send("insert", {"keys": keys})
            except OSError:
                pass

    def _flush_tokens(self, finished):
        """Decode role: stream each request's newly committed tokens;
        DONE when finished."""
        fin = set(finished or ())
        for erid, st in list(self.by_erid.items()):
            req = self.eng.requests.get(erid)
            if req is None:
                continue
            rid = st["rid"]
            new = [int(t) for t in
                   req.generated[self._reported.get(rid, 0):]]
            if erid in fin:
                self.router.send("done", {"rid": rid,
                                          "gen": st["gen"],
                                          "toks": new})
                # decode span closes with the request: its token
                # count equals the committed stream the router saw
                # for this incarnation (decode reports from zero) —
                # the trace-merge reconciliation the slow tier pins
                t1 = time.perf_counter()
                self._spans.span(
                    rid, "decode", self._decode_t0.pop(rid, t1), t1,
                    trace_id=st["meta"].get("trace_id"),
                    args={"toks": len(req.generated)})
                self._flight.record("done", rid=rid,
                                    toks=len(req.generated))
                self.by_erid.pop(erid, None)
                self.by_rid.pop(rid, None)
                self._reported.pop(rid, None)
                del self.eng.requests[erid]
            elif new:
                self.router.send("tokens", {"rid": rid,
                                            "gen": st["gen"],
                                            "toks": new})
                self._reported[rid] = len(req.generated)

    def _conn_or_none(self, name):
        try:
            return self._peer_conn(name)
        except (OSError, KeyError):
            return None

    def _drop_peer(self, name):
        """Evict a cached peer connection after a send failure — the
        Connection object never learns its socket died, so leaving it
        cached would poison every later send to that peer even after
        the peer recovers (the next ``_peer_conn`` reconnects)."""
        conn = self._peer_conns.pop(name, None)
        if conn is not None:
            conn.close()

    def _maybe_send_stats(self):
        """Rate-limited periodic stats tick (the main loop's path);
        the `stats_req` reply rides :meth:`_send_stats` directly — a
        rate limit on the reply path would DROP solicited replies
        and stall the router's cluster_stats() round trip."""
        if time.perf_counter() - self._last_stats < 0.25:
            return
        self._send_stats()
        # span shipping rides the same tick but NOT _send_stats
        # itself: that is the stats_req reply path and must stay
        # call-free (proto-reply-pairing)
        spans = self._spans.drain()
        if spans:
            try:
                self.router.send("spans", {"spans": spans})
            except OSError:
                self._running = False

    def _send_stats(self, sid=None):
        """Send one stats frame NOW.  This is the `stats_req` →
        `stats` reply path, so it must reach the send on every exit
        edge (proto-reply-pairing): no early returns; the only
        excused failure is the router connection itself dying."""
        self._last_stats = time.perf_counter()
        eng = self.eng
        prefix = eng.prefix
        stats = {
            "role": self.role,
            "steps": eng.stats["steps"],
            "prefill_rows": eng.stats["prefill_rows"],
            "decode_rows": eng.stats["decode_rows"],
            "preemptions": eng.stats["preemptions"],
            "prefix_hit_tokens": eng.stats["prefix_hit_tokens"],
            "pages_in_use": eng.cache.pages_in_use,
            "free_pages": eng.cache.free_pages,
            "prefix_cached_pages":
                0 if prefix is None else prefix.cached_pages,
            "prefix_refs": 0 if prefix is None else prefix.refs_total,
            "active_requests": len(self.by_erid),
            "staged_rids": len(self.receiver.staged_rids),
            "remote_hits": self.remote_hits,
            "remote_hit_tokens": self.remote_hit_tokens,
            "remote_hits_host_tier": self.remote_hits_host_tier,
            "prefix_spilled_pages":
                0 if prefix is None else prefix.spilled_pages,
            "warm_hits": 0 if prefix is None
                else prefix.warm_hits_total,
            "warm_hit_tokens": 0 if prefix is None
                else prefix.warm_hit_tokens_total,
            "swap_outs": eng.stats["swap_outs"],
            "swap_ins": eng.stats["swap_ins"],
            "overlap_steps": eng.stats["overlap_steps"],
            # inlined (not eng.tier.stats()): this fn is the
            # stats_req reply path, so the dict build must be
            # call-free — proto-reply-pairing's exception-edge rule
            "tier": None if eng.tier is None else {
                "pages_held": eng.tier.pages_held,
                "bytes_held": eng.tier.bytes_held,
                "budget_bytes": eng.tier.budget_bytes,
                "spilled_pages_total": eng.tier.spilled_pages_total,
                "installed_pages_total":
                    eng.tier.installed_pages_total,
                "bytes_moved_total": eng.tier.bytes_moved_total,
                "evicted_pages_total": eng.tier.evicted_pages_total,
                "evictions_total": eng.tier.evictions_total},
            "bytes_streamed": self.streamer.bytes_streamed_total
            + self.fetch_bytes,
            "pages_streamed": self.streamer.pages_streamed_total,
            "pages_installed": self.receiver.pages_installed_total,
            # round 22 put-transport accounting: logical page bytes
            # above count IDENTICALLY on both transports (the perf
            # counters measure pages moved, not socket bytes); these
            # say how many rode /dev/shm puts instead of the socket
            "pages_put": self.pages_put_total,
            "put_bytes": self.put_bytes_total,
            # send-then-clear: the router OBSERVES every sample it
            # receives into the transfer histogram, so samples must
            # travel exactly once (re-sending a sliding window would
            # re-observe lingering samples every 0.25 s tick)
            "transfer_ms": self.transfer_ms,
        }
        self.transfer_ms = []
        if self._tier_moves:
            moves, self._tier_moves = self._tier_moves, {}
            by_tier: Dict[str, List[bytes]] = {}
            for k, t in moves.items():
                by_tier.setdefault(t, []).append(k)
            for t, keys in by_tier.items():
                try:
                    self.router.send("tier", {"keys": keys,
                                              "tier": t})
                except OSError:
                    pass
        if self._evicted_keys:
            keys, self._evicted_keys = self._evicted_keys, []
            try:
                self.router.send("evict", {"keys": keys})
            except OSError:
                pass
        try:
            self.router.send("stats", {"stats": stats, "sid": sid})
        except OSError:
            self._running = False

    # -- main loop ---------------------------------------------------
    def run(self):
        try:
            while self._running:
                drained = False
                while True:
                    try:
                        item = self.inbox.get_nowait()
                    except queue.Empty:
                        break
                    drained = True
                    self._handle(*item)
                self._serve_fetches()
                if not self._running:
                    break
                if self.role == "decode":
                    self._admit_ready()
                busy = bool(self.eng._queue) or any(
                    s is not None for s in self.eng._slots)
                if busy:
                    finished = self.eng.step()
                    self._flight.record(
                        "step", active=len(self.by_erid),
                        finished=len(finished or ()))
                    if self.role == "prefill":
                        self._stream_pages(finished)
                    else:
                        # staged-page installs land here, AFTER the
                        # step — overlapped with the dispatched
                        # step's device time, not serialized between
                        # admission and dispatch (round 21)
                        self.receiver.retry_installs()
                        self._flush_tokens(finished)
                elif not drained:
                    try:
                        item = self.inbox.get(timeout=0.02)
                        self._handle(*item)
                    except queue.Empty:
                        pass
                self._maybe_send_stats()
        except Exception as e:
            try:
                self.router.send("error", {"msg": repr(e)})
            except OSError:
                pass
            raise
        finally:
            # last-gasp span ship: a worker shut down between 0.25 s
            # stats ticks (every sub-second run) still delivers its
            # staged spans — without this a short-lived cluster's
            # merged trace shows the router talking to silence
            spans = self._spans.drain()
            if spans:
                try:
                    self.router.send("spans", {"spans": spans})
                except OSError:
                    pass
            self.listener.close()
            self.router.close()
            for c in self._peer_conns.values():
                c.close()
            # orderly exit needs no forensics: unlink our flight
            # ring (a SIGKILL skips this finally — that file IS the
            # evidence the router recovers)
            self._flight.record("exit")
            self._flight.close(unlink=True)
            # reclaim any put segment we wrote whose receiver never
            # opened it (peer died mid-flight): our pid prefixes
            # every segment name
            from .transport import put_sweep
            put_sweep()


def _disagg_worker_entry(name, role, router_host, router_port):
    """Spawned-process entry point (multiprocessing spawn target).

    Exits via ``os._exit``: a worker that ran its engine has live
    PJRT/XLA thread pools whose C++ static destructors abort
    (``std::terminate``) under normal interpreter teardown; the
    router tracks liveness by connection EOF, so skipping teardown
    loses nothing."""
    try:
        _DisaggWorker(name, role, router_host, router_port).run()
    except BaseException:
        import traceback
        traceback.print_exc()
        os._exit(1)
    os._exit(0)


def run_worker():
    """Externally-launched worker entry (``tools/launch.py --launcher
    serve`` or bare env): connects to the router named by
    ``MXNET_SERVE_ROUTER_HOST``/``MXNET_SERVE_ROUTER_PORT`` as
    ``MXNET_SERVE_WORKER`` with role ``MXNET_SERVE_ROLE``."""
    _disagg_worker_entry(
        os.environ["MXNET_SERVE_WORKER"],
        os.environ.get("MXNET_SERVE_ROLE", "prefill"),
        os.environ.get("MXNET_SERVE_ROUTER_HOST", "127.0.0.1"),
        int(os.environ["MXNET_SERVE_ROUTER_PORT"]))
