"""Continuous-batching serving benchmark (round 7): Poisson arrivals
over a mixed prompt/output-length distribution, the paged-KV
``ServingEngine`` vs the fixed-batch ``generate`` baseline at EQUAL
HBM budget.

    python benchmark/serve_bench.py                 # mid preset (CPU-able)
    python benchmark/serve_bench.py --preset full   # chip gate config
    python benchmark/serve_bench.py --quick         # CI smoke
    python benchmark/serve_bench.py --sweep         # + occupancy/page-size
    python benchmark/serve_bench.py --replicas 2 --shared-prefix-frac 0.8
                                    # + round-10 cluster + prefix rows

Sections (rows carry {"section": ...} in the JSON):

* ``e2e``     — the headline: R requests arrive Poisson(rate); the
  engine admits them into ``num_slots`` slots as they arrive; the
  baseline groups them into fixed batches of B = the slot count whose
  CONTIGUOUS max-shape KV allocation equals the engine's page pool
  (equal HBM), pads every batch to the workload max prompt/output
  shape (one compiled program, standard static serving), and waits
  for each batch to fully arrive before launching.  Reported:
  useful tok/s (= requested generated tokens / wall clock from first
  arrival to last completion), per-request normalized per-token
  latency (completion - arrival) / tokens at p50/p99, and HBM held.
* ``occupancy`` — closed-loop load of k in-flight requests for
  k = slots/4, slots/2, slots (the batch-occupancy ablation).
* ``pagesize`` — the e2e engine run swept over page_size (the sweep
  that picked the default of 16).
* ``telemetry`` (round 8) — the e2e engine run repeated with
  ``metrics=True``: latency percentiles now come from the ENGINE'S OWN
  histograms (``serving_ttft_ms`` / ``serving_tbt_ms``,
  ``mxnet_tpu/obs``) — the source of truth — with one external
  wall-clock cross-check retained: the harness measures its own
  per-token intervals around ``step()``, pushes them through an
  identical histogram, and FAILS (RuntimeError) if the two p99s
  diverge >10% (a silently skewed trace clock would fail here, not in
  a dashboard weeks later).  The row also reports
  ``overhead_incl_harness_pct`` vs the metrics-off e2e run — that
  number includes the harness's own cross-check loop; the clean
  metrics-only budget is gated at 3% by
  ``gpt_serve_metrics_overhead_pct`` (closed loop, cross_check off).

Both sides pre-warm their compiled programs before the clock; tok/s
counts only requested tokens (baseline padding tokens are waste by
construction — that is the point being measured).  All timestamps are
``time.perf_counter()`` — the engine's telemetry clock — so internal
and external measurements subtract cleanly.

* ``prefix`` / ``cluster`` (round 10, ``--replicas N
  --shared-prefix-frac F``) — the ``ServingCluster`` front end over N
  replicas on a workload where fraction F of requests share one
  system-prompt prefix: a prefix-cache on/off pair (cluster-side TTFT,
  hit tokens, affinity routing), the single-engine prefix-hit-vs-cold
  TTFT measurement behind the ``gpt_serve_prefix_hit_ttft_ms`` gate,
  and a forced mid-run replica failover in which every request must
  still complete (recompute-exact resubmission).
* ``kernel`` (round 11, ``--kernel-ablation``) — the fused Pallas
  paged-attention kernel vs the XLA block-table-gather path: one
  closed-loop decode-heavy run per kernel, step time from the
  engine's ``serving_step_ms`` histogram.  Off-TPU the kernel runs in
  interpreter mode (correctness path, not a perf claim — the printout
  says so); the chip number is the ``gpt_serve_decode_step_ms``
  gate's to pin.  ``--kernel pallas`` additionally routes the
  headline e2e engine runs through the kernel.  Round 22: combined
  with ``--tp N`` the ablation runs BOTH kernels at tp=N on the
  virtual mesh (the mesh-lowered shard_map kernel vs the sharded XLA
  gather) — it rides the ``--tp`` invocation-topology rule below.
* ``spec`` (round 11, ``--spec-sweep``) — in-engine speculative
  decode accept×K sweep on the mixed Poisson workload (spec_K =
  0/2/4, tok/s + accept rate + tokens/step per row); ``--spec-K N``
  arms speculation on the headline e2e engine run instead.
* ``tp`` (round 14, ``--tp N``) — tensor-parallel serving on the
  8-device VIRTUAL CPU mesh (the same ``jax_num_cpu_devices``
  mechanism the MULTICHIP dry-runs use; requested before a backend
  initializes, so ``--tp`` runs as
  its own invocation — ENFORCED: the other sections are skipped, as
  their recorded numbers assume the single-device host topology the
  virtual mesh replaces): the closed-loop engine run at tp=1 and
  tp=N on the identical workload, reporting tok/s, per-device
  KV-pool bytes held/pooled (the ~1/tp claim), and a full f32-greedy
  TOKEN-IDENTITY cross-check between the two (raises on the first
  divergent request).  Off-chip the tok/s pair prices XLA:CPU's
  sharded-collective overhead, not ICI — the per-device-bytes and
  identity columns are the claims; the chip prices the speed.

* ``transport`` (round 22, ``--transport-ablation``) — the
  disaggregated page transport pair: the SAME cross-process
  remote-hit measurement as the ``disagg`` gate, once with the
  zero-copy put transport forced (``MXNET_SERVE_TRANSPORT=put``) and
  once with socket frames (``=socket``), reporting per-mode
  remote-hit TTFT, pages/bytes streamed, pages/bytes put, and the
  per-frame transfer latency — with a cross-mode token-identity
  check and a counter reconciliation (the put run must move EVERY
  streamed page through segments; the socket run must put none).
  Runs ALONE (cross-process clusters own the host).  NOTE the CPU
  measurement prices a same-host /dev/shm handoff, not ICI — the
  chip-side number is ``gpt_serve_put_remote_hit_ttft_ms``'s to pin.
* ``trace`` (round 16, ``--trace burst10x`` or a
  ``traffic_trace.py`` JSON file) — OPEN-LOOP replay of a seeded
  workload trace (diurnal ramp + 10× burst + heavy-tailed lengths)
  against ``ServingCluster`` (or ``DisaggServingCluster`` with
  ``--disagg``), with the metrics-driven autoscaler live and a
  seeded chaos schedule (one replica death mid-burst; real SIGKILL
  for disagg).  Reports GOODPUT (completions meeting per-request
  TTFT + worst-token-gap SLO) and hard-fails unless every request
  completes bit-identical to the ``generate`` oracle with zero
  leaked pages/refs after the scaler returns to min size.  Runs
  ALONE (it owns the replica topology); the row carries the trace
  seed + sha256 so ``MULTICHIP_r08.json`` reproduces from the
  checked-in seed (docs/perf.md "Traffic realism").
* ``trace_overhead`` (round 23, ``--trace-overhead``) — the
  observability-tax pair: the SAME seeded closed-loop disagg
  measurement run with the flight recorder + span shipping at their
  defaults ("on") and with ``MXNET_SERVE_FLIGHT_SLOTS=0`` +
  ``MXNET_SERVE_SPANS=0`` exported before the cluster spawns
  ("off"), cross-mode token identity hard-enforced (the tracing-off
  serving path must be BIT-identical — tracing may cost time, never
  tokens) plus a both-ways toggle reconciliation (the on run must
  actually ship spans; the off run must ship none).  The on row's
  ``trace_overhead_pct`` is the ``gpt_serve_trace_overhead_pct``
  gate.  Runs ALONE (cross-process clusters own the host).
  ``--chrome-trace FILE --disagg`` additionally profiles the disagg
  section's Poisson run and dumps the ONE merged chrome trace —
  router (real pid) + per-worker + transport swimlanes on the
  handshake-reconciled clock — with a lane-coverage smoke check.

The ``gpt_serve_mixed_tok_s`` / ``gpt_serve_p99_ms`` /
``gpt_serve_metrics_overhead_pct`` / ``gpt_serve_prefix_hit_ttft_ms``
/ ``gpt_serve_decode_step_ms`` / ``gpt_serve_goodput`` /
``gpt_serve_trace_overhead_pct`` gates
(benchmark/perf_regression.py) run ``run_gate()`` /
``run_gate_telemetry()`` / ``run_gate_prefix()`` /
``run_gate_decode_step()`` / ``run_gate_goodput()`` /
``run_gate_trace_overhead()`` below on the full-size preset.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# --------------------------------------------------------------- presets ---

@dataclasses.dataclass
class Preset:
    name: str
    # model
    vocab: int = 32000
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_len: int = 512
    w8: bool = True
    dtype: str = "bfloat16"
    # engine
    num_slots: int = 16
    page_size: int = 16
    prefill_chunk: int = 16
    # workload
    n_requests: int = 64
    rate: float = 100.0                   # arrivals/sec
    prompt_lens: tuple = (16, 32, 64, 128, 192)
    out_lens: tuple = (16, 32, 64, 128, 160)
    # per-request SLO budgets for the round-16 trace-replay goodput
    # section (docs/perf.md "Traffic realism"): TTFT covers admission
    # queueing + chunked prefill at burst depth; the worst inter-token
    # gap covers a preemption re-prefill or one replica failover —
    # sized so steady-state traffic passes with margin and sustained
    # overload / unabsorbed faults do not
    slo_ttft_ms: float = 1000.0
    slo_tbt_ms: float = 350.0


PRESETS = {
    "full": Preset("full"),
    # mid: small enough to measure end-to-end on the XLA:CPU host
    "mid": Preset("mid", vocab=4096, d_model=256, n_heads=4,
                  n_layers=4, d_ff=1024, max_len=256, w8=False,
                  dtype="float32", num_slots=8, page_size=16,
                  prefill_chunk=16, n_requests=32, rate=64.0,
                  prompt_lens=(8, 16, 32, 64), out_lens=(8, 16, 32, 64),
                  slo_ttft_ms=750.0, slo_tbt_ms=250.0),
    "quick": Preset("quick", vocab=256, d_model=64, n_heads=4,
                    n_layers=2, d_ff=128, max_len=64, w8=False,
                    dtype="float32", num_slots=4, page_size=4,
                    prefill_chunk=8, n_requests=8, rate=50.0,
                    prompt_lens=(4, 8, 12), out_lens=(4, 8, 12),
                    slo_ttft_ms=500.0, slo_tbt_ms=200.0),
}


def _model(p):
    import jax
    from mxnet_tpu.models import gpt
    cfg = gpt.gpt_config(vocab_size=p.vocab, max_len=p.max_len,
                         d_model=p.d_model, n_heads=p.n_heads,
                         n_layers=p.n_layers, d_ff=p.d_ff,
                         dropout=0.0, use_flash=False, remat=False,
                         dtype=p.dtype)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    if p.w8:
        params = gpt.quantize_decode_params(params)
    return params, cfg


def _workload(p, seed=0, shared_prefix_frac=0.0, page_size=None):
    """[(arrival_s, prompt (P,) int32, n_new)] sorted by arrival.

    ``shared_prefix_frac`` F makes a fraction F of requests open with
    one fixed prefix (a "system prompt" of full pages, half the max
    prompt length rounded down to the page grid) followed by a random
    tail — the traffic shape the round-10 prefix cache exists for."""
    rng = np.random.RandomState(seed)
    ps = page_size or p.page_size
    pre_len = (max(p.prompt_lens) // 2 // ps) * ps
    shared_pre = rng.randint(1, p.vocab, max(pre_len, 1)) \
        .astype(np.int32)
    t = 0.0
    out = []
    for _ in range(p.n_requests):
        t += rng.exponential(1.0 / p.rate)
        P = int(rng.choice(p.prompt_lens))
        N = int(rng.choice(p.out_lens))
        if shared_prefix_frac > 0.0 and rng.rand() < shared_prefix_frac:
            head = shared_pre[:min(P - 1, pre_len)]
            tail = rng.randint(1, p.vocab, P - head.size) \
                .astype(np.int32)
            prompt = np.concatenate([head, tail])
        else:
            prompt = rng.randint(1, p.vocab, P).astype(np.int32)
        out.append((t, prompt, N))
    return out


def _lat_stats(per_req):
    a = np.asarray(sorted(per_req))
    return (float(np.percentile(a, 50)), float(np.percentile(a, 99)))


# ------------------------------------------------------- engine config ---

# ONE construction path for the engine sizing kwargs (round 15,
# perf_opt satellite): every section — single engine, cluster, tp,
# disagg — derives (num_slots, page_size, pages_per_slot,
# prefill_chunk) here.  Previously each section rebuilt the kwargs ad
# hoc; a drifted default in one rebuild would silently compare unlike
# configs.  Sharing the constructor makes the workload-derived parts
# identical BY CONSTRUCTION; the registry below additionally asserts
# the preset-carried parts (slots, chunk) stay identical across
# sections — the one drift the constructor cannot see is a section
# passing a locally-modified preset copy.
_geometry_seen = {}


def _engine_geometry(p, workload, page_size=None, num_pages=None,
                     section="?"):
    page_size = page_size or p.page_size
    max_total = max(len(pr) + n for _, pr, n in workload)
    pps = -(-max_total // page_size)
    if num_pages is not None:
        num_pages = max(num_pages, pps + 1)
    fixed = (p.num_slots, p.prefill_chunk)
    prev = _geometry_seen.get(p.name)
    if prev is None:
        _geometry_seen[p.name] = (fixed, section)
    elif prev[0] != fixed:
        raise RuntimeError(
            "serve_bench: section %r runs preset %r with (num_slots, "
            "prefill_chunk)=%r but section %r ran it with %r — the "
            "sections would compare unlike engine configs"
            % (section, p.name, fixed, prev[1], prev[0]))
    return dict(num_slots=p.num_slots, page_size=page_size,
                pages_per_slot=pps, prefill_chunk=p.prefill_chunk,
                num_pages=num_pages)


# ------------------------------------------------------------------ runs ---

def _hist_percentiles(samples_ms):
    """Push wall-clock samples through the SAME fixed-bucket histogram
    the engine uses, so the external cross-check compares estimator
    against estimator (clock skew shows up; bucket quantization — up
    to one bucket width — cancels)."""
    from mxnet_tpu.obs import Histogram
    h = Histogram("ext")
    for s in samples_ms:
        h.observe(s)
    return h


def _bucket_width_at(v, bounds):
    """Width of the bucket containing v in the given histogram bounds
    — the resolution floor of any percentile comparison at that
    magnitude."""
    from bisect import bisect_left
    i = bisect_left(bounds, v)
    if i >= len(bounds):
        return bounds[-1]
    return bounds[i] - (bounds[i - 1] if i > 0 else 0.0)


def run_engine(params, cfg, p, workload, num_pages=None,
               page_size=None, closed_loop_k=None, metrics=False,
               cross_check=True, kernel=None, spec_K=0,
               spec_drafter="ngram", tp=1):
    """Open-loop (Poisson ``workload``) or closed-loop (``k`` always in
    flight, workload gives the request shapes) engine run.

    ``metrics=True`` enables the engine's obs layer, reports TTFT/TBT
    percentiles from the engine-internal histograms, and cross-checks
    the TBT p99 against this harness's own external wall-clock
    measurement — >10% divergence raises.  ``cross_check=False`` skips
    the external measurement entirely: the overhead gate compares
    metrics-off vs metrics-on ENGINE cost, so the harness's own
    per-step observation work must not ride along on one side.

    ``kernel``/``spec_K`` (round 11) select the engine's attention
    path (None: the engine's own choice by its device — the Pallas
    walk on a TPU, the XLA gather on CPU) and arm in-engine
    speculation; spec rows report the accept
    rate and tokens/step alongside tok/s (the benchmark-definition
    note from round 6 applies: committed tokens per wall second moves
    with the accept rate as well as the step time)."""
    from mxnet_tpu.serving import ServingEngine
    # per-slot cap sized to the workload, not cfg.max_len — the
    # equal-HBM pool budget is derived from the workload max shape
    geo = _engine_geometry(p, workload, page_size=page_size,
                           num_pages=num_pages, section="engine")
    eng = ServingEngine(params, cfg, metrics=bool(metrics),
                        kernel=kernel, spec_K=spec_K,
                        spec_drafter=spec_drafter, tp=tp, **geo)
    # pre-warm the step program outside the clock (and drop the
    # warmup's footprint from the reported stats/registry — the
    # compile time would otherwise own the TTFT tail)
    widp, widn = workload[0][1], workload[0][2]
    wid = eng.submit(widp, widn)
    eng.run()
    del eng.requests[wid]
    for k in eng.stats:
        eng.stats[k] = type(eng.stats[k])()
    if metrics:
        eng.reset_metrics()

    useful = sum(n for _, _, n in workload)
    arrivals = {}
    t0 = time.perf_counter()
    peak_held = 0
    # external wall-clock per-token observation (the cross-check):
    # rid -> [tokens seen, timestamp of the last seen token / submit]
    ext_seen = {}
    ext_ttft_ms = []
    ext_tbt_ms = []
    observe_ext = metrics and cross_check

    def _ext_collect():
        """The external wall-clock measurement point: called after each
        step() return.  The engine commits ONE burst per request per
        step — a single token, or up to spec_K+1 under speculation —
        and the engine-internal TBT histogram likewise records once
        per burst, so both sides of the cross-check measure the same
        per-burst intervals.  Finished requests drop out of the scan
        so the per-step cost tracks in-flight count, not total
        submissions."""
        now_pc = time.perf_counter()
        retired = []
        for rid, st in ext_seen.items():
            req = eng.requests[rid]
            ng = len(req.generated)
            if ng > st[0]:
                dt_ms = (now_pc - st[1]) * 1e3
                (ext_ttft_ms if st[0] == 0 else ext_tbt_ms).append(
                    dt_ms)
                st[0] = ng
                st[1] = now_pc
            if req.state in ("done", "cancelled"):
                retired.append(rid)
        for rid in retired:
            del ext_seen[rid]

    if closed_loop_k is None:
        pending = list(workload)
        submitted = {}
        while True:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                at, prompt, n = pending.pop(0)
                rid = eng.submit(prompt, n)
                submitted[rid] = n
                arrivals[rid] = at
                if observe_ext:
                    ext_seen[rid] = [0, time.perf_counter()]
            r = eng.step()
            peak_held = max(peak_held, eng.hbm_held)
            if observe_ext:
                _ext_collect()
            if r is False:
                if not pending:
                    break
                time.sleep(max(0.0, pending[0][0]
                               - (time.perf_counter() - t0)))
    else:
        pending = list(workload)
        submitted = {}
        in_flight = 0
        while pending or in_flight:
            while pending and in_flight < closed_loop_k:
                at, prompt, n = pending.pop(0)
                rid = eng.submit(prompt, n)
                submitted[rid] = n
                arrivals[rid] = time.perf_counter() - t0
                in_flight += 1
                if observe_ext:
                    ext_seen[rid] = [0, time.perf_counter()]
            done = eng.step()
            peak_held = max(peak_held, eng.hbm_held)
            if observe_ext:
                _ext_collect()
            if done:
                in_flight -= len(done)
    wall = time.perf_counter() - t0

    lat = []
    for rid, n in submitted.items():
        req = eng.requests[rid]
        lat.append((req.token_times[-1] - t0 - arrivals[rid])
                   / max(1, len(req.generated)))
    p50, p99 = _lat_stats(lat)
    out = {"tok_s": useful / wall, "wall_s": wall, "lat_p50_s": p50,
           "lat_p99_s": p99, "hbm_peak_held": peak_held,
           "hbm_pool": eng.hbm_pool,
           "occupancy": eng.stats["slot_occupancy_sum"]
           / max(1, eng.stats["steps"]),
           "preemptions": eng.stats["preemptions"],
           "steps": eng.stats["steps"], "kernel": eng.kernel}
    if spec_K:
        out.update({
            "spec_K": spec_K,
            "spec_drafted": eng.stats["spec_drafted"],
            "spec_accept_rate": eng.stats["spec_accepted"]
            / max(1, eng.stats["spec_drafted"]),
            "tokens_per_step": useful / max(1, eng.stats["steps"])})
    if metrics:
        reg = eng.registry
        h_ttft = reg.histogram("serving_ttft_ms")
        h_tbt = reg.histogram("serving_tbt_ms")
        h_step = reg.histogram("serving_step_ms")
        out.update({
            "ttft_p50_ms": h_ttft.percentile(50),
            "ttft_p95_ms": h_ttft.percentile(95),
            "ttft_p99_ms": h_ttft.percentile(99),
            "tbt_p50_ms": h_tbt.percentile(50),
            "tbt_p95_ms": h_tbt.percentile(95),
            "tbt_p99_ms": h_tbt.percentile(99),
            "step_p50_ms": h_step.percentile(50),
        })
        if not observe_ext:
            return out
        # the cross-check, two guards (both fail the BENCH, loudly):
        #
        # 1. MEAN — exact arithmetic on both sides (histogram sum/count
        #    vs the raw external samples), so NO quantization noise: a
        #    skewed trace clock (wrong clock source, unit confusion)
        #    shifts every sample proportionally and is caught at 10%.
        #    The 0.2 ms absolute floor covers the real measurement-
        #    point separation (internal records at token commit inside
        #    step(); external after step() returns + harness loop).
        # 2. p99 — reported side by side as the operator-facing number;
        #    gated at max(10%, one bucket width at that magnitude):
        #    percentiles from a fixed-bucket estimator cannot be
        #    compared finer than the containing bucket, and a handful
        #    of tail samples landing across an edge under host load is
        #    quantization, not skew.
        ext_tbt = _hist_percentiles(ext_tbt_ms)
        out["ext_ttft_p99_ms"] = \
            _hist_percentiles(ext_ttft_ms).percentile(99)
        out["ext_tbt_p99_ms"] = ext_tbt.percentile(99)
        int_mean = h_tbt.sum / max(1, h_tbt.count)
        ext_mean = sum(ext_tbt_ms) / max(1, len(ext_tbt_ms))
        out["tbt_mean_ms"] = int_mean
        out["ext_tbt_mean_ms"] = ext_mean
        mean_diff = abs(int_mean - ext_mean)
        if mean_diff > max(0.10 * ext_mean, 0.2):
            raise RuntimeError(
                "serve_bench: engine-internal TBT mean (%.3f ms) vs "
                "external wall-clock mean (%.3f ms) diverge %.1f%% "
                "(>10%%) — trace clock is skewed"
                % (int_mean, ext_mean,
                   100 * mean_diff / max(ext_mean, 1e-9)))
        p99_diff = abs(out["tbt_p99_ms"] - out["ext_tbt_p99_ms"])
        div = p99_diff / max(out["ext_tbt_p99_ms"], 1e-9)
        out["tbt_p99_divergence"] = div
        # the p99 hard-gate needs a real tail population: below ~100
        # samples the p99 is the last order statistic and one
        # host-scheduler spike between the two measurement points
        # flips it a bucket (observed on the quick preset under
        # parallel test load).  The mean gate above stays always-on —
        # it is the actual clock-skew detector.
        if len(ext_tbt_ms) >= 100 and \
                p99_diff > max(0.10 * out["ext_tbt_p99_ms"],
                               _bucket_width_at(out["ext_tbt_p99_ms"],
                                                ext_tbt.bounds)):
            raise RuntimeError(
                "serve_bench: engine-internal TBT p99 (%.3f ms) vs "
                "external wall-clock p99 (%.3f ms) diverge %.1f%% "
                "(>10%% and more than one histogram bucket) — trace "
                "clock or histogram is skewed"
                % (out["tbt_p99_ms"], out["ext_tbt_p99_ms"],
                   100 * div))
    return out


def run_fixed_batch(params, cfg, p, workload, batch):
    """Static-batch baseline: batches of ``batch`` in arrival order,
    every batch padded to the WORKLOAD max prompt/output shape (one
    compiled program — standard static serving), launch waits for the
    whole batch to have arrived."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import gpt
    Pg = max(len(pr) for _, pr, _ in workload)
    Ng = max(n for _, _, n in workload)

    def pad(prompts):
        out = np.ones((batch, Pg), np.int32)
        for i, pr in enumerate(prompts):
            out[i, :len(pr)] = pr
        return jnp.asarray(out)

    # pre-warm the compiled shape
    o = gpt.generate(params, cfg, pad([workload[0][1]]), Ng)
    jax.device_get(o.ravel()[:1])

    useful = sum(n for _, _, n in workload)
    t0 = time.perf_counter()
    lat = []
    for i in range(0, len(workload), batch):
        grp = workload[i:i + batch]
        wait_until = max(at for at, _, _ in grp)
        now = time.perf_counter() - t0
        if now < wait_until:
            time.sleep(wait_until - now)
        o = gpt.generate(params, cfg, pad([pr for _, pr, _ in grp]), Ng)
        jax.device_get(o.ravel()[:1])
        t_done = time.perf_counter() - t0
        for at, _, n in grp:
            lat.append((t_done - at) / max(1, n))
    wall = time.perf_counter() - t0
    from mxnet_tpu.serving.paged_kv import contiguous_kv_bytes
    p50, p99 = _lat_stats(lat)
    return {"tok_s": useful / wall, "wall_s": wall, "lat_p50_s": p50,
            "lat_p99_s": p99,
            "hbm_held": contiguous_kv_bytes(cfg, batch, Pg + Ng)}


def _equal_hbm_pages(cfg, p, workload, batch):
    """Engine page budget whose pool bytes match the baseline's
    contiguous (batch, Pmax+Nmax) allocation."""
    from mxnet_tpu.serving.paged_kv import contiguous_kv_bytes, \
        PagedKVCache
    Pg = max(len(pr) for _, pr, _ in workload)
    Ng = max(n for _, _, n in workload)
    budget = contiguous_kv_bytes(cfg, batch, Pg + Ng)
    probe = PagedKVCache(cfg, 2, p.page_size)
    return max(2, budget // probe.bytes_per_page)


# --------------------------------------------------------------- cluster ---

def run_cluster(params, cfg, p, workload, replicas, prefix=True,
                fail_after_steps=None):
    """Round-10 cluster section: the ``ServingCluster`` front end over
    ``replicas`` engine replicas on the (optionally shared-prefix)
    Poisson workload.  ``fail_after_steps=k`` kills replica 0's engine
    after k steps mid-run — the failover row asserts every request
    still completes (recompute-exact resubmission to survivors).

    TTFT here is CLUSTER-side (submit() → first committed token on
    whichever replica ran it, failovers included) — the number a
    client sees, admission queueing and routing included."""
    from mxnet_tpu.serving import ServingCluster
    geo = _engine_geometry(p, workload, section="cluster")
    cl = ServingCluster(params, cfg, replicas=replicas,
                        prefix_cache=prefix, metrics=True,
                        max_queue=10 ** 6, watchdog_s=60.0, **geo)
    try:
        # pre-warm the (shared) step program outside the clock; the
        # warm prefix-cache state it leaves is the steady-state a
        # long-running cluster serves from
        wid = cl.submit(workload[0][1], workload[0][2])
        cl.result(wid, timeout=600)
        if fail_after_steps is not None:
            eng0 = cl.replicas[0].engine
            orig_step = eng0.step
            calls = [0]

            def bomb():
                # count only steps with real work: the idle worker
                # loop polls step() ~50x/s, and counting those would
                # fire the bomb before any request reaches this
                # replica — a failover row that never exercises the
                # in-flight resume path it exists to measure
                busy = eng0._queue or \
                    any(s is not None for s in eng0._slots)
                if busy:
                    calls[0] += 1
                    if calls[0] == fail_after_steps:
                        raise RuntimeError(
                            "serve_bench injected failure")
                return orig_step()

            eng0.step = bomb

        useful = sum(n for _, _, n in workload)
        rids = []
        t0 = time.perf_counter()
        for at, prompt, n in workload:
            now = time.perf_counter() - t0
            if now < at:
                time.sleep(at - now)
            rids.append((cl.submit(prompt, n), at))
        for rid, _ in rids:
            cl.result(rid, timeout=600)
        wall = time.perf_counter() - t0

        ttft = []
        for rid, at in rids:
            cr = cl.requests[rid]
            if cr.first_token_t is not None:
                ttft.append((cr.first_token_t - t0 - at) * 1e3)
        ttft_p50, ttft_p99 = _lat_stats(ttft)
        c = cl.metrics()["counters"]
        hit_tokens = sum(r.engine.stats["prefix_hit_tokens"]
                         for r in cl.replicas)
        out = {"tok_s": useful / wall, "wall_s": wall,
               "replicas": replicas, "prefix_cache": bool(prefix),
               "ttft_p50_ms": ttft_p50, "ttft_p99_ms": ttft_p99,
               "completed": int(c["cluster_requests_completed_total"])
               - 1,                      # minus the warmup request
               "failovers": int(c["cluster_failovers_total"]),
               "resubmitted": int(
                   c["cluster_requests_resubmitted_total"]),
               "routed_affinity": int(
                   c["cluster_routed_affinity_total"]),
               "prefix_hit_tokens": int(hit_tokens),
               "cow_copies": sum(r.engine.stats["cow_copies"]
                                 for r in cl.replicas)}
        if out["completed"] != len(workload):
            raise RuntimeError(
                "serve_bench cluster: %d/%d requests completed"
                % (out["completed"], len(workload)))
        return out
    finally:
        cl.close(timeout=120)


_prefix_gate_cache = {}


def run_gate_prefix(preset="full"):
    """The ``gpt_serve_prefix_hit_ttft_ms`` gate: TTFT of a request
    whose whole prompt sits in the prefix cache (hit, COW re-feed of
    the final token) vs a cold same-length prompt, measured on one
    engine so the number is scheduling-deterministic.  Gate value =
    hit TTFT in ms (direction "lower"); the cold TTFT and speedup
    ride along for the docs."""
    if preset in _prefix_gate_cache:
        return _prefix_gate_cache[preset]
    from mxnet_tpu.serving import ServingEngine
    p = PRESETS[preset]
    params, cfg = _model(p)
    rng = np.random.RandomState(0)
    P = max(p.prompt_lens)
    N = 4
    eng = ServingEngine(params, cfg, num_slots=p.num_slots,
                        page_size=p.page_size,
                        prefill_chunk=p.prefill_chunk,
                        prefix_cache=True)
    # compile outside the clock
    wid = eng.submit(rng.randint(1, p.vocab, P).astype(np.int32), N)
    eng.run()
    del eng.requests[wid]

    def ttft_ms(prompt):
        t0 = time.perf_counter()
        rid = eng.submit(prompt, N)
        req = eng.requests[rid]
        while not req.generated:
            eng.step()
        dt = (time.perf_counter() - t0) * 1e3
        eng.run()                        # drain the rest
        return dt

    shared = rng.randint(1, p.vocab, P).astype(np.int32)
    # cold reps use FRESH prompts (same shape) so nothing is cached;
    # hit reps replay the shared prompt — best-of-3 each side, the
    # same jitter-stripping the other serving gates use
    cold = min(ttft_ms(rng.randint(1, p.vocab, P).astype(np.int32))
               for _ in range(3))
    ttft_ms(shared)                      # populate the cache
    hit = min(ttft_ms(shared) for _ in range(3))
    out = {"ttft_cold_ms": cold, "ttft_hit_ms": hit,
           "speedup": cold / max(hit, 1e-9),
           "hit_tokens": int(eng.stats["prefix_hit_tokens"]),
           "prompt_len": P}
    _prefix_gate_cache[preset] = out
    return out


# ------------------------------------- round-15 disaggregated serving ---

def _shared_pre(p, seed, page_size=None):
    """Reconstruct the workload's shared system-prompt prefix (the
    FIRST draw of the seeded generator in ``_workload``) so the
    disagg section can reconcile prefilled-once without changing the
    workload contract."""
    rng = np.random.RandomState(seed)
    ps = page_size or p.page_size
    pre_len = (max(p.prompt_lens) // 2 // ps) * ps
    return rng.randint(1, p.vocab, max(pre_len, 1)).astype(np.int32)


def run_disagg(params, cfg, p, workload, prefill=2, decode=1,
               seed=0):
    """Round-15 section: the cross-PROCESS ``DisaggServingCluster``
    (``prefill`` prefill + ``decode`` decode worker processes behind
    the in-process router) on the shared-prefix Poisson workload.

    Reports tok/s, router-side TTFT percentiles, page bytes/pages
    streamed between processes, remote prefix hits, and transfer
    latency — and CROSS-CHECKS the prefilled-once claim: the shared
    prefix must be cold-prefilled at most once cluster-wide, every
    other occurrence served by a local or remote prefix hit
    (RuntimeError otherwise — the claim is reconciled, not asserted).
    """
    from mxnet_tpu.serving import DisaggServingCluster
    geo = _engine_geometry(p, workload, section="disagg")
    cl = DisaggServingCluster(params, cfg, prefill=prefill,
                              decode=decode, metrics=True,
                              watchdog_s=60.0, **geo)
    try:
        # engine pre-warm is per worker process (inside the
        # handshake).  One extra warm request carrying the shared
        # prefix runs BEFORE the clock so the cluster index knows its
        # owner when the Poisson flood arrives — without it the first
        # few concurrent sharers race the first insert report and
        # each cold-prefills (an inherent property of concurrent
        # arrival, not a bug), which would turn the strict
        # prefilled-once reconciliation below into a coin flip
        pre = _shared_pre(p, seed)
        wid = cl.submit(np.concatenate(
            [pre, np.ones(1, np.int32)]), 1)
        cl.result(wid, timeout=600)
        useful = sum(n for _, _, n in workload)
        rids = []
        t0 = time.perf_counter()
        for at, prompt, n in workload:
            now = time.perf_counter() - t0
            if now < at:
                time.sleep(at - now)
            rids.append((cl.submit(prompt, n), at))
        for rid, _ in rids:
            cl.result(rid, timeout=600)
        wall = time.perf_counter() - t0

        ttft = []
        for rid, at in rids:
            cr = cl.requests[rid]
            if cr.first_token_t is not None:
                ttft.append((cr.first_token_t - t0 - at) * 1e3)
        ttft_p50, ttft_p99 = _lat_stats(ttft)
        st = cl.cluster_stats()
        snap = cl.registry.snapshot()["counters"]

        # prefilled-once reconciliation: per-request shared full-page
        # depth; the warm request above paid the ONE cold prefill, so
        # every sharer's full-page depth must have been served by a
        # (local or remote) prefix hit
        ps = p.page_size
        depths = []
        for _, prompt, _ in workload:
            head = min(prompt.size - 1, pre.size)
            d = 0
            if head >= ps and np.array_equal(prompt[:ps], pre[:ps]):
                d = (np.asarray(
                    prompt[:head] == pre[:head]).cumprod().sum()
                    // ps)
            depths.append(int(d))
        must_skip = sum(depths) * ps
        # engine-side prefix_hit_tokens ALONE counts tokens not
        # recomputed: a remote fetch grafts pages into the local trie
        # and the engine's admission hit then counts them — adding
        # remote_hit_tokens on top would double-count every fetched
        # sharer and let genuine cold re-prefills slip through
        skipped = sum(v.get("prefix_hit_tokens", 0)
                      for v in st.values())
        if skipped < must_skip:
            raise RuntimeError(
                "serve_bench --disagg: prefilled-once violated — the "
                "shared prefix accounts for %d skippable tokens but "
                "only %d were served from the (local+remote) prefix "
                "caches" % (must_skip, skipped))
        out = {"tok_s": useful / wall, "wall_s": wall,
               "prefill_workers": prefill, "decode_workers": decode,
               "ttft_p50_ms": ttft_p50, "ttft_p99_ms": ttft_p99,
               "completed": int(
                   snap["cluster_requests_completed_total"]),
               "failovers": int(snap["cluster_failovers_total"]),
               "page_bytes_streamed": int(
                   snap["cluster_page_bytes_streamed_total"]),
               "pages_streamed": int(
                   snap["cluster_pages_streamed_total"]),
               "prefix_remote_hits": int(
                   snap["serving_prefix_remote_hits_total"]),
               "prefix_remote_hit_tokens": int(
                   snap["serving_prefix_remote_hit_tokens_total"]),
               "prefix_local_hit_tokens": int(skipped),
               "prefilled_once_margin_tokens": int(
                   skipped - must_skip)}
        if out["completed"] != len(workload) + 1:   # + the warm req
            raise RuntimeError(
                "serve_bench --disagg: %d/%d requests completed"
                % (out["completed"] - 1, len(workload)))
        out["completed"] -= 1
        return out
    finally:
        cl.close()


_disagg_gate_cache = {}


def run_gate_disagg(preset="full"):
    """The ``gpt_serve_disagg_remote_hit_ttft_ms`` gate: TTFT of a
    request whose whole-page prompt prefix sits in ANOTHER prefill
    process's cache — the requester fetches the int8/f32 pages over
    the transport instead of recomputing them — vs a cold same-length
    prompt on the same cluster.  Gate value = remote-hit TTFT in ms
    (direction "lower"); cold TTFT and the cold/remote speedup ride
    along for the docs.

    Best-of-3 on three distinct prompts inside ONE cluster: submits
    are sequential, so least-outstanding routing degenerates to
    round-robin and each prompt's second submission deterministically
    lands on the OTHER prefill worker (validated via the remote-hit
    counter, not assumed)."""
    if preset in _disagg_gate_cache:
        return _disagg_gate_cache[preset]
    from mxnet_tpu.serving import DisaggServingCluster
    p = PRESETS[preset]
    params, cfg = _model(p)
    rng = np.random.RandomState(0)
    P = (max(p.prompt_lens) // p.page_size) * p.page_size
    N = 4
    wl_probe = [(0.0, np.ones(P, np.int32), N)]
    geo = _engine_geometry(p, wl_probe, section="disagg-gate")
    cl = DisaggServingCluster(params, cfg, prefill=2, decode=1,
                              metrics=True, watchdog_s=60.0, **geo)
    try:
        def ttft_ms(prompt):
            rid = cl.submit(prompt, N)
            cl.result(rid, timeout=600)
            cr = cl.requests[rid]
            return (cr.first_token_t - cr.submit_t) * 1e3

        cold, remote = [], []
        for _ in range(3):
            shared = rng.randint(1, p.vocab, P).astype(np.int32)
            cold.append(ttft_ms(shared))      # cold on worker A
            remote.append(ttft_ms(shared))    # remote fetch on B
        st = cl.cluster_stats()
        hits = sum(v.get("remote_hits", 0) for v in st.values())
        if hits < 3:
            raise RuntimeError(
                "run_gate_disagg: expected 3 remote prefix hits, "
                "counters saw %d — the measurement did not exercise "
                "the cross-process fetch path" % hits)
        out = {"ttft_cold_ms": min(cold),
               "ttft_remote_hit_ms": min(remote),
               "speedup": min(cold) / max(min(remote), 1e-9),
               "prompt_len": P,
               "remote_hits": hits,
               "page_bytes_streamed": int(sum(
                   v.get("bytes_streamed", 0) for v in st.values()))}
    finally:
        cl.close()
    _disagg_gate_cache[preset] = out
    return out


# ------------------------------------- round-22 page-put transport ---

def run_transport_ablation(p, seed=0):
    """The ``--transport-ablation`` pair: the run_gate_disagg
    remote-hit measurement (2 prefill + 1 decode processes, 3
    cold+remote prompt pairs) executed once per transport —
    ``MXNET_SERVE_TRANSPORT=socket`` (raw frames) and ``=put``
    (zero-copy /dev/shm segments) — on the SAME seeded prompts.

    Per-mode rows report remote-hit/cold TTFT, pages/bytes streamed,
    pages/bytes moved through put segments, and per-frame transfer
    latency p50.  Three reconciliations hard-fail the section
    (RuntimeError): the put run must move EVERY streamed page through
    segments (pages_put == pages_streamed > 0), the socket run must
    put NONE, and every request's tokens must be bit-identical across
    the two modes.  NOTE on CPU both modes price a same-host handoff
    (loopback socket vs shm mmap), not ICI — the chip-side number is
    the ``gpt_serve_put_remote_hit_ttft_ms`` gate's to pin."""
    import hashlib
    from mxnet_tpu.serving import DisaggServingCluster
    params, cfg = _model(p)
    rng = np.random.RandomState(seed)
    P = (max(p.prompt_lens) // p.page_size) * p.page_size
    N = 4
    prompts = [rng.randint(1, p.vocab, P).astype(np.int32)
               for _ in range(3)]
    sha = hashlib.sha256()
    for pr in prompts:
        sha.update(pr.tobytes())
    geo = _engine_geometry(p, [(0.0, prompts[0], N)],
                           section="transport")
    prev = os.environ.get("MXNET_SERVE_TRANSPORT")
    rows, outs = [], {}
    try:
        for mode in ("socket", "put"):
            os.environ["MXNET_SERVE_TRANSPORT"] = mode
            cl = DisaggServingCluster(params, cfg, prefill=2,
                                      decode=1, metrics=True,
                                      watchdog_s=60.0, **geo)
            try:
                cold, remote, toks = [], [], []
                for pr in prompts:
                    for leg in (cold, remote):
                        rid = cl.submit(pr, N)
                        toks.append(np.asarray(
                            cl.result(rid, timeout=600)))
                        cr = cl.requests[rid]
                        leg.append(
                            (cr.first_token_t - cr.submit_t) * 1e3)
                st = cl.cluster_stats()
            finally:
                cl.close()
            outs[mode] = toks
            hits = sum(v.get("remote_hits", 0) for v in st.values())
            pages = sum(v.get("pages_streamed", 0)
                        for v in st.values())
            put_pages = sum(v.get("pages_put", 0)
                            for v in st.values())
            xfer = [ms for v in st.values()
                    for ms in v.get("transfer_ms", ())]
            xfer_p50, _ = _lat_stats(xfer)
            # bytes reconcile EXACTLY: bytes_streamed counts logical
            # page bytes on the stream AND the fetch-reply path
            # (identically on both transports), and put_bytes counts
            # segment bytes for the same two frame kinds — so a put
            # run that really moved every page frame through
            # segments shows equality.  pages_streamed alone counts
            # only the prefill→decode stream (fetch replies ride
            # fetch_bytes), hence >= on the page counters.
            bytes_streamed = int(sum(
                v.get("bytes_streamed", 0) for v in st.values()))
            put_bytes = int(sum(
                v.get("put_bytes", 0) for v in st.values()))
            if mode == "put" and not (
                    put_pages >= pages > 0
                    and put_bytes == bytes_streamed):
                raise RuntimeError(
                    "serve_bench --transport-ablation: the put run "
                    "streamed %d page(s) / %d B but the put "
                    "segments carried %d frame-page(s) / %d B — the "
                    "zero-copy path did not cover every page frame "
                    "(same-host eligibility broken?)"
                    % (pages, bytes_streamed, put_pages, put_bytes))
            if mode == "socket" and put_pages:
                raise RuntimeError(
                    "serve_bench --transport-ablation: the socket "
                    "run put %d page(s) — MXNET_SERVE_TRANSPORT="
                    "socket must kill the capability" % put_pages)
            rows.append({
                "section": "transport",
                "config": "transport_%s" % mode,
                "preset": p.name,
                "transport": mode, "seed": seed,
                "prompts_sha": sha.hexdigest()[:16],
                "prompt_len": P, "remote_hits": hits,
                "ttft_cold_ms": min(cold),
                "ttft_remote_hit_ms": min(remote),
                "pages_streamed": pages,
                "page_bytes_streamed": bytes_streamed,
                "pages_put": put_pages,
                "put_bytes": put_bytes,
                "transfer_p50_ms": xfer_p50})
    finally:
        if prev is None:
            os.environ.pop("MXNET_SERVE_TRANSPORT", None)
        else:
            os.environ["MXNET_SERVE_TRANSPORT"] = prev
    mismatches = sum(not np.array_equal(a, b)
                     for a, b in zip(outs["socket"], outs["put"]))
    if mismatches:
        raise RuntimeError(
            "serve_bench --transport-ablation: %d/%d requests "
            "diverge between the socket and put transports — the "
            "bit-identity contract is broken"
            % (mismatches, len(outs["socket"])))
    for r in rows:
        r["identity_checked"] = len(outs["socket"])
        r["identity_mismatches"] = 0
    return rows


_put_gate_cache = {}


def run_gate_put_transport(preset="full", seed=0):
    """The ``gpt_serve_put_remote_hit_ttft_ms`` gate: remote-hit TTFT
    (ms) of the run_gate_disagg measurement with the zero-copy put
    transport FORCED — the one number that prices the
    device-to-device page path end to end (segment write, handoff,
    mmap install) against its socket twin
    ``gpt_serve_disagg_remote_hit_ttft_ms``.  Direction "lower":
    v <= hi.  Hard-fails unless every streamed page actually rode a
    put segment and the tokens match the socket transport bitwise
    (the full --transport-ablation reconciliation runs underneath).
    The row carries seed + prompts sha for MULTICHIP provenance."""
    key = (preset, seed)
    if key in _put_gate_cache:
        return _put_gate_cache[key]
    rows = run_transport_ablation(PRESETS[preset], seed=seed)
    row = next(r for r in rows if r["transport"] == "put")
    _put_gate_cache[key] = row
    return row


# ----------------------------------- round-23 observability overhead ---


def run_trace_overhead(p, seed=0):
    """The ``--trace-overhead`` pair (round 23): one seeded
    closed-loop measurement on the cross-process cluster (2 prefill +
    1 decode workers, sequential submits — every request's full
    lifecycle prices the span/flight emit paths), run twice:

    * ``on``  — observability at its defaults: every worker records
      into its flight ring and ships span batches on the stats tick;
      the router folds them into the span store.
    * ``off`` — ``MXNET_SERVE_FLIGHT_SLOTS=0`` and
      ``MXNET_SERVE_SPANS=0`` exported BEFORE the cluster constructs,
      so the spawned worker processes inherit the kill switch.

    Two reconciliations hard-fail the section (RuntimeError): the
    toggle must demonstrably TAKE on both sides (the on run ships >0
    spans and exposes a live flight path via debug_status; the off
    run ships none and exposes no path), and every request's tokens
    must be bit-identical across the modes — tracing may cost time,
    never tokens.  ``trace_overhead_pct`` = wall-clock tax of the on
    run vs the off run; the gated budget is
    ``gpt_serve_trace_overhead_pct`` (direction "lower")."""
    import hashlib
    from mxnet_tpu.serving import DisaggServingCluster
    params, cfg = _model(p)
    rng = np.random.RandomState(seed)
    P = (max(p.prompt_lens) // p.page_size) * p.page_size
    N = 8
    prompts = [rng.randint(1, p.vocab, P).astype(np.int32)
               for _ in range(3)]
    sha = hashlib.sha256()
    for pr in prompts:
        sha.update(pr.tobytes())
    geo = _engine_geometry(p, [(0.0, prompts[0], N)],
                           section="trace-overhead")
    env_keys = ("MXNET_SERVE_FLIGHT_SLOTS", "MXNET_SERVE_SPANS")
    prev = {k: os.environ.get(k) for k in env_keys}
    rows, outs = [], {}
    try:
        for mode in ("on", "off"):
            for k in env_keys:
                if mode == "off":
                    os.environ[k] = "0"
                else:
                    os.environ.pop(k, None)   # library defaults
            cl = DisaggServingCluster(params, cfg, prefill=2,
                                      decode=1, metrics=True,
                                      watchdog_s=60.0, **geo)
            try:
                toks, rids = [], []
                t0 = time.perf_counter()
                for _ in range(2):            # each prompt cold+hit
                    for pr in prompts:
                        rid = cl.submit(pr, N)
                        rids.append(rid)
                        toks.append(np.asarray(
                            cl.result(rid, timeout=600)))
                wall = time.perf_counter() - t0
                ttft = [(cl.requests[rid].first_token_t
                         - cl.requests[rid].submit_t) * 1e3
                        for rid in rids]
                # toggle reconciliation: spans ride the 0.25 s stats
                # tick, so poll past one tick before concluding
                deadline = time.perf_counter() + 10.0
                while True:
                    n_spans = sum(
                        len(cl.request_trace(rid)["spans"])
                        for rid in rids)
                    if n_spans or time.perf_counter() > deadline:
                        break
                    time.sleep(0.05)
                flight_path = cl.debug_status()["flight"]["path"]
            finally:
                cl.close()
            outs[mode] = toks
            if mode == "on" and not (n_spans and flight_path):
                raise RuntimeError(
                    "serve_bench --trace-overhead: the on run shipped "
                    "%d span(s), flight path %r — observability was "
                    "not actually live on the measured path"
                    % (n_spans, flight_path))
            if mode == "off" and (n_spans or flight_path):
                raise RuntimeError(
                    "serve_bench --trace-overhead: the off run "
                    "shipped %d span(s), flight path %r — the env "
                    "kill switch did not reach the workers"
                    % (n_spans, flight_path))
            p50, p99 = _lat_stats(ttft)
            rows.append({
                "section": "trace_overhead",
                "config": "trace_%s" % mode,
                "preset": p.name, "obs": mode, "seed": seed,
                "prompts_sha": sha.hexdigest()[:16],
                "prompt_len": P, "requests": len(rids),
                "tok_s": len(rids) * N / wall, "wall_s": wall,
                "ttft_p50_ms": p50, "ttft_p99_ms": p99,
                "spans_shipped": int(n_spans),
                "flight_live": flight_path is not None})
    finally:
        for k in env_keys:
            if prev[k] is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = prev[k]
    mismatches = sum(not np.array_equal(a, b)
                     for a, b in zip(outs["on"], outs["off"]))
    if mismatches:
        raise RuntimeError(
            "serve_bench --trace-overhead: %d/%d requests diverge "
            "between observability on and off — the tracing-off "
            "serving path must be bit-identical"
            % (mismatches, len(outs["on"])))
    by = {r["obs"]: r for r in rows}
    pct = 100.0 * (by["off"]["tok_s"] / by["on"]["tok_s"] - 1.0)
    for r in rows:
        r["trace_overhead_pct"] = pct
        r["identity_checked"] = len(outs["on"])
        r["identity_mismatches"] = 0
    return rows


_trace_overhead_gate_cache = {}


def run_gate_trace_overhead(preset="full", seed=0):
    """The ``gpt_serve_trace_overhead_pct`` gate: tok/s tax of
    default-on observability (flight ring + span shipping + router
    span store) on the seeded closed-loop disagg pair, in percent.
    Direction "lower": v <= hi.  Hard-fails unless the toggle took on
    both sides and the two runs were token-bit-identical (the full
    --trace-overhead reconciliation runs underneath).  The row
    carries seed + prompts sha for MULTICHIP provenance."""
    key = (preset, seed)
    if key in _trace_overhead_gate_cache:
        return _trace_overhead_gate_cache[key]
    rows = run_trace_overhead(PRESETS[preset], seed=seed)
    row = next(r for r in rows if r["obs"] == "on")
    _trace_overhead_gate_cache[key] = row
    return row


_pallas_tp_gate_cache = {}


def run_gate_pallas_tp_step(preset="full", tp=2, seed=0):
    """The ``gpt_serve_pallas_tp2_step_ms`` gate: engine-internal
    step-time p50 of the SAME closed-loop decode-heavy pallas run as
    ``gpt_serve_decode_step_ms``, mesh-lowered at tp=2 (each device
    walks its heads slice of the heads-sharded pool through the
    shard_map kernel) — the pair pins the tp lowering from both
    sides: this number regressing while the tp=1 one holds means the
    shard_map walk / replicated-table prefetch got expensive; both
    regressing means the kernel did.  Best-of-3, seed + workload sha
    carried.  Needs >= tp visible devices (RuntimeError otherwise —
    off-chip the tests' 8-device virtual mesh provides them).
    Direction "lower": v <= hi.  Only meaningful on chip — off-TPU
    the kernel interprets and the mesh shares one host."""
    import hashlib
    import jax
    key = (preset, tp, seed)
    if key in _pallas_tp_gate_cache:
        return _pallas_tp_gate_cache[key]
    if tp > len(jax.devices()):
        raise RuntimeError(
            "run_gate_pallas_tp_step: tp=%d but only %d device(s) "
            "visible — the gate needs the tp-way mesh" %
            (tp, len(jax.devices())))
    p = PRESETS[preset]
    params, cfg = _model(p)
    wl = _decode_heavy_workload(p, seed=seed)
    sha = hashlib.sha256()
    for _, prompt, n in wl:
        sha.update(prompt.tobytes())
        sha.update(np.int64(n).tobytes())
    best = min(
        (run_engine(params, cfg, p, wl, closed_loop_k=p.num_slots,
                    metrics=True, cross_check=False, kernel="pallas",
                    tp=tp)
         for _ in range(3)),
        key=lambda r: r["step_p50_ms"])
    row = {"step_p50_ms": best["step_p50_ms"], "tp": tp,
           "seed": seed, "workload_sha": sha.hexdigest()[:16]}
    _pallas_tp_gate_cache[key] = row
    return row


# ---------------------------------------------- round-18 KV tiering ---

_tier_gate_cache = {}

_TIER_BYTES = 1 << 26                    # 64 MB host tier for the sweep


def run_gate_tier(preset="full", seed=0):
    """The ``gpt_serve_tier_hit_ttft_ms`` gate + the single-engine
    half of ``--tier-sweep``: TTFT of one whole-page prompt measured
    at every local tier of the round-18 hierarchy on ONE engine
    (scheduling-deterministic, same protocol as the round-10 prefix
    gate):

    * **cold** — nothing cached, the full chunked prefill;
    * **hot** (hbm) — the chain lives in the prefix trie, pages map
      read-only + COW re-feed of the final token;
    * **warm** (host) — the chain was SPILLED to the host tier
      (``prefix.spill()``, the deterministic stand-in for pool
      pressure) and ``match`` re-installs it through the bucketed
      donated scatter before the COW re-feed.

    Plus the preemption-resume pair: wall time from ``preempt()`` to
    the request's next committed token with the tier ON (swap-out →
    install-exact resume) vs OFF (recompute-exact re-prefill).

    Hard checks (RuntimeError, the round's acceptance criteria):
    hot < warm < cold strictly; swap-resume < recompute-resume on the
    mid/full presets; every completion in the sweep bit-identical to
    the ``generate`` oracle; zero leaked pages/refs after the drain.
    The row carries ``seed`` + ``sweep_sha`` (sha256 over every
    prompt fed, in order) — ``perf_regression.py`` refuses the gate
    without them, the same reproducibility contract as the goodput
    gate."""
    import hashlib
    key = (preset, seed)
    if key in _tier_gate_cache:
        return _tier_gate_cache[key]
    from mxnet_tpu.serving import ServingEngine
    p = PRESETS[preset]
    params, cfg = _model(p)
    rng = np.random.RandomState(seed)
    sha = hashlib.sha256()
    P = (max(p.prompt_lens) // p.page_size) * p.page_size
    chain = P // p.page_size
    N = 4
    eng = ServingEngine(params, cfg, num_slots=p.num_slots,
                        page_size=p.page_size,
                        prefill_chunk=p.prefill_chunk,
                        prefix_cache=True, metrics=True,
                        tier_bytes=_TIER_BYTES)
    wid = eng.submit(np.ones(1, np.int32), 1)
    eng.run()
    del eng.requests[wid]
    checks = []                          # (prompt, n, output) for the oracle

    def ttft_ms(prompt, n=N):
        t0 = time.perf_counter()
        rid = eng.submit(prompt, n)
        req = eng.requests[rid]
        while not req.generated:
            eng.step()
        dt = (time.perf_counter() - t0) * 1e3
        eng.run()                        # drain the rest
        checks.append((prompt, n, req.output))
        return dt

    def draw(n_tok):
        a = rng.randint(1, p.vocab, n_tok).astype(np.int32)
        sha.update(a.tobytes())
        return a

    shared = draw(P)
    cold = min(ttft_ms(draw(P)) for _ in range(3))
    ttft_ms(shared)                      # populate the trie
    hot = min(ttft_ms(shared) for _ in range(3))
    warms = []
    for _ in range(3):
        eng.prefix.spill()               # whole refcount-0 set -> host
        h, w = eng.prefix.probe_depth(shared)
        if h != 0 or w < chain - 1:
            raise RuntimeError(
                "run_gate_tier: spill() left the shared chain "
                "hot=%d/warm=%d of %d pages — the warm measurement "
                "would not exercise the host tier" % (h, w, chain))
        warms.append(ttft_ms(shared))    # match restores = warm hit
    warm = min(warms)
    # the economic claim — warm saves the prefill — must hold on
    # every preset; the full sandwich (hot < warm: warm pays the
    # install) is additionally enforced where it is MEASURABLE: on
    # quick/mid the ~0.4-0.9 ms install dwarfs host jitter, on the
    # full preset (bf16 768-d model, ~600 ms step on CPU) ±100 ms
    # host jitter swamps a ~2 ms install and min-of-3 can land warm
    # under hot — a measurement artifact, not a tier property (the
    # checked-in mid-preset MULTICHIP row pins the strict ordering)
    ordered = hot < warm < cold if preset in ("quick", "mid") \
        else warm < cold
    if not ordered:
        raise RuntimeError(
            "run_gate_tier: TTFT ordering violated — hot %.2f / warm "
            "%.2f / cold %.2f ms (warm must sit strictly between: "
            "above hot by the install cost, below cold by the saved "
            "prefill)" % (hot, warm, cold))
    snap = eng.registry.snapshot()["counters"]
    if eng.prefix.refs_total or \
            eng.cache.pages_in_use != eng.prefix.cached_pages:
        raise RuntimeError(
            "run_gate_tier: leak after the TTFT sweep (refs=%d, "
            "in_use=%d, cached=%d)" % (eng.prefix.refs_total,
                                       eng.cache.pages_in_use,
                                       eng.prefix.cached_pages))

    # ---- swap-resume vs recompute-resume ----------------------------
    def resume_ms(tier_on):
        n_new = 8
        e2 = ServingEngine(params, cfg, num_slots=2,
                           page_size=p.page_size,
                           prefill_chunk=p.prefill_chunk,
                           prefix_cache=False,
                           tier_bytes=_TIER_BYTES if tier_on else 0)
        w2 = e2.submit(np.ones(1, np.int32), 1)
        e2.run()
        del e2.requests[w2]
        best = None
        for _ in range(3):
            pr = draw(P)
            rid = e2.submit(pr, n_new)
            req = e2.requests[rid]
            while len(req.generated) < n_new // 2:
                e2.step()
            k = len(req.generated)
            t0 = time.perf_counter()
            swapped = e2.preempt(rid)
            if swapped != tier_on:
                raise RuntimeError(
                    "run_gate_tier: preempt() swap=%r with tier_on="
                    "%r — the resume pair is not measuring what it "
                    "claims" % (swapped, tier_on))
            while len(req.generated) <= k:
                e2.step()
            dt = (time.perf_counter() - t0) * 1e3
            e2.run()
            checks.append((pr, n_new, req.output))
            best = dt if best is None else min(best, dt)
        if e2.cache.pages_in_use:
            raise RuntimeError(
                "run_gate_tier: %d pages leaked after the %s resume "
                "runs" % (e2.cache.pages_in_use,
                          "swap" if tier_on else "recompute"))
        return best

    swap = resume_ms(True)
    recompute = resume_ms(False)
    if preset in ("mid", "full") and not (swap < recompute):
        raise RuntimeError(
            "run_gate_tier: swap-resume %.2f ms >= recompute-resume "
            "%.2f ms at the %s preset — install-exact resume is not "
            "paying for itself" % (swap, recompute, preset))

    # every completion in the sweep must be the generate oracle's
    oracle = _oracle_outputs(params, cfg,
                             [(pr, n) for pr, n, _ in checks])
    bad = sum(not np.array_equal(out, o)
              for (_, _, out), o in zip(checks, oracle))
    if bad:
        raise RuntimeError(
            "run_gate_tier: %d/%d completions diverge from the "
            "generate oracle across the tier sweep" % (bad,
                                                       len(checks)))
    out = {"ttft_cold_ms": cold, "ttft_hot_ms": hot,
           "ttft_warm_ms": warm,
           "warm_vs_cold_speedup": cold / max(warm, 1e-9),
           "hot_vs_warm_install_ms": warm - hot,
           "swap_resume_ms": swap, "recompute_resume_ms": recompute,
           "swap_vs_recompute_speedup": recompute / max(swap, 1e-9),
           "prompt_len": P, "chain_pages": chain,
           "tier_budget_bytes": _TIER_BYTES,
           "tier_spills": int(snap["serving_tier_spills_total"]),
           "tier_installs": int(snap["serving_tier_installs_total"]),
           "tier_bytes_moved": int(snap["serving_tier_bytes_total"]),
           "warm_hit_tokens": int(
               snap["serving_prefix_warm_hit_tokens_total"]),
           "oracle_checked": len(checks), "oracle_mismatches": 0,
           "seed": seed, "sweep_sha": sha.hexdigest()[:16]}
    _tier_gate_cache[key] = out
    return out


def run_tier_peer(p, seed=0):
    """The cross-process half of ``--tier-sweep``: TTFT of a request
    whose prefix chain lives in a PEER prefill process's **host
    tier** — the owner spilled it under pool pressure, the router's
    index re-tagged it ``host`` (the round-18 ``tier`` wire kind),
    and the requester's fetch is served straight from the owner's
    host DRAM with no device gather on the owner's side.

    Scenario (sequential submits alternate workers by round-robin):
    the shared prompt cold-prefills on worker A (pool sized to hold
    two chains + slack); filler prompts then accumulate cached chains
    on A until pressure spills the LRU — the shared chain's tail — to
    A's host tier; once the router index shows the ``host`` tag the
    prompt is submitted again, landing on worker B, which fetches the
    chain peer-to-peer (hot head exported, spilled tail served from
    host DRAM).  ``remote_hits_host_tier`` must move or the run
    aborts — the measurement proves the spilled-chain fetch path, it
    does not assume it."""
    from mxnet_tpu.serving import DisaggServingCluster
    params, cfg = _model(p)
    rng = np.random.RandomState(seed)
    ps = p.page_size
    P = (max(p.prompt_lens) // ps) * ps
    chain = P // ps
    N = 4
    cl = DisaggServingCluster(
        params, cfg, prefill=2, decode=1, metrics=True,
        watchdog_s=60.0, num_slots=2, page_size=ps,
        num_pages=2 * chain + 3, pages_per_slot=chain + 1,
        prefill_chunk=p.prefill_chunk, tier_bytes=_TIER_BYTES)
    try:
        def ttft(prompt, n=N):
            rid = cl.submit(prompt, n)
            cl.result(rid, timeout=600)
            cr = cl.requests[rid]
            return (cr.first_token_t - cr.submit_t) * 1e3

        from mxnet_tpu.serving import prefix_cache as PC
        shared = rng.randint(1, p.vocab, P).astype(np.int32)
        keys = PC.chain_keys(shared, ps)
        cold = ttft(shared)              # submit 1 -> worker A: owns

        def chain_spilled():
            with cl.index._mu:
                return any(cl.index._tier.get(k) == "host"
                           for k in keys)

        # filler pairs (one lands A by round-robin alternation) —
        # retired filler prompts DONATE their chains, so A's pool
        # fills with cached pages until a filler's allocation forces
        # the pressure spill of the LRU chain = the shared one; the
        # `tier` frame rides the 0.25 s stats tick, so poll the
        # router index between pairs (submit parity stays even)
        for _ in range(4):
            for _ in range(2):
                ttft(rng.randint(1, p.vocab, P).astype(np.int32))
            deadline = time.perf_counter() + 2.0
            while time.perf_counter() < deadline \
                    and not chain_spilled():
                time.sleep(0.05)
            if chain_spilled():
                break
        if not chain_spilled():
            raise RuntimeError(
                "run_tier_peer: the shared chain never re-tagged "
                "'host' in the router index — the owner never "
                "spilled it (or the tier frame never arrived); the "
                "peer-host measurement cannot run")
        peer_host = ttft(shared)         # even parity -> worker B: fetch
        st = cl.cluster_stats()
        host_hits = sum(v.get("remote_hits_host_tier", 0)
                        for v in st.values())
        if host_hits < 1:
            raise RuntimeError(
                "run_tier_peer: remote_hits_host_tier=0 — the final "
                "submission did not fetch from the peer's host tier "
                "(routing drifted?); measurement aborted")
        return {"ttft_cold_ms": cold,
                "ttft_peer_host_ms": peer_host,
                "speedup": cold / max(peer_host, 1e-9),
                "prompt_len": P, "chain_pages": chain,
                "remote_hits_host_tier": host_hits,
                "page_bytes_streamed": int(sum(
                    v.get("bytes_streamed", 0) for v in st.values())),
                "seed": seed}
    finally:
        cl.close()


# ------------------------------------------ round-16 traffic realism ---

def _trace_spec(p, seed, duration_s=None):
    """The scripted burst10x trace spec for a preset: one diurnal
    cycle, a 10× burst window in its rising half, heavy-tailed
    lengths clamped to the preset's shapes (prompt lengths snapped to
    a geometric grid so the exactness oracle compiles a handful of
    ``generate`` programs, not one per length)."""
    import traffic_trace as TT
    if duration_s is None:
        duration_s = 1.5 if p.name == "quick" else 4.0
    return TT.burst10x_spec(
        seed=seed, vocab=p.vocab,
        max_total=max(p.prompt_lens) + max(p.out_lens),
        base_rate=p.rate / 4.0, duration_s=duration_s,
        prompt_max=max(p.prompt_lens), out_max=max(p.out_lens))


def _oracle_outputs(params, cfg, reqs):
    """Single-engine ``generate`` oracle for a list of (prompt, n)
    requests, grouped by prompt length (one compile per distinct
    length) and chunked to bound the contiguous KV allocation.
    Returns the full continuation per request index."""
    import jax.numpy as jnp
    from mxnet_tpu.models import gpt
    by_len = {}
    for i, (prompt, n) in enumerate(reqs):
        by_len.setdefault(len(prompt), []).append((i, prompt, n))
    out = [None] * len(reqs)
    for P, group in sorted(by_len.items()):
        n_max = max(n for _, _, n in group)
        for k in range(0, len(group), 32):
            chunk = group[k:k + 32]
            batch = jnp.asarray(np.stack([pr for _, pr, _ in chunk]))
            o = np.asarray(gpt.generate(params, cfg, batch, n_max))
            for (i, _, n), row in zip(chunk, o):
                out[i] = row[:P + n].astype(np.int32)
    return out


def run_trace_replay(params, cfg, p, trace, *, disagg=False,
                     autoscale=True, min_replicas=2, max_replicas=4,
                     chaos_events=None, chaos_seed=0, chaos_kinds=None,
                     slo=None, verify_oracle=True, standby_prefill=0):
    """Round-16 headline section: OPEN-LOOP replay of a seeded
    workload trace (diurnal ramp + 10× burst + heavy-tailed lengths,
    ``benchmark/traffic_trace.py``) against the serving cluster, with
    the metrics-driven autoscaler live and a seeded chaos schedule
    firing at trace-relative times.

    Reports GOODPUT — completions that met their per-request SLO
    (TTFT and worst inter-token gap budgets), as a fraction of all
    arrivals and as SLO-good tokens per wall second — alongside the
    raw tok/s the earlier sections report.  Open loop means arrivals
    never wait for the cluster: a queue the autoscaler fails to drain
    shows up as TTFT-violating (or rejected) requests, exactly as a
    real front door would see it.

    Hard checks, each a RuntimeError (the acceptance criteria of the
    round, reconciled rather than asserted in prose): every submitted
    request completes; every completed output is BIT-IDENTICAL to the
    single-engine ``generate`` oracle (f32 greedy); after the drain
    the autoscaler has returned to ``min_replicas`` and no replica
    holds a page or a prefix ref beyond its cache-owned set.

    The result row carries ``seed`` and ``trace_sha`` so the run is
    reproducible from the checked-in JSON alone
    (``perf_regression.py`` refuses a goodput gate without the hash).
    """
    import traffic_trace as TT
    from mxnet_tpu.serving import (Autoscaler, ChaosDriver,
                                   ChaosEvent, ClusterOverloaded,
                                   DisaggServingCluster,
                                   ServingCluster)
    wl = TT.workload(trace)
    spec = trace["spec"]
    slo = slo or TT.SLO(p.slo_ttft_ms, p.slo_tbt_ms)
    geo = _engine_geometry(p, wl, section="trace")
    if chaos_events is None:
        # the scripted scenario: one fault per kind, spread through
        # the burst window.  Default ("kill",) = one replica death
        # mid-burst (a real SIGKILL for the disagg cluster's worker
        # processes, the injected-raise failover path for in-process
        # replicas — prefill-targeted there so the single decode role
        # survives).  Round 20 adds "cancel" — a seeded live request
        # cancelled end-to-end, the client-disconnect fault the HTTP
        # front door propagates.
        kinds = tuple(chaos_kinds) if chaos_kinds else ("kill",)
        step = spec["burst_dur_s"] / (len(kinds) + 1.0)
        chaos_events = [
            ChaosEvent(spec["burst_at_s"] + (i + 1) * step, k,
                       "prefill" if (disagg and k == "kill") else None)
            for i, k in enumerate(kinds)]
    if disagg:
        cl = DisaggServingCluster(params, cfg, prefill=2, decode=1,
                                  metrics=True, watchdog_s=60.0,
                                  **geo)
        size0 = 3
    else:
        cl = ServingCluster(params, cfg, replicas=min_replicas,
                            metrics=True, watchdog_s=60.0,
                            max_queue=10 ** 6, **geo)
        size0 = min_replicas
    scaler = None
    drv = ChaosDriver(cl, chaos_events, seed=chaos_seed)
    try:
        # pre-warm outside the clock (each disagg worker pre-warms in
        # its own handshake; this covers the router paths)
        wid = cl.submit(wl[0][1], wl[0][2])
        cl.result(wid, timeout=600)
        if standby_prefill:
            if not disagg:
                raise ValueError("standby is a disagg-only knob "
                                 "(pre-provisioned worker processes)")
            # round 18 (ROADMAP item-2 remainder): pre-provisioned
            # workers — spawned, handshaken, engine-warm BEFORE the
            # clock starts, adopted by scale_up() in O(peer-map
            # flip).  One warm spare PER ROLE, because the
            # role-aware scale_up grows whichever role's outstanding
            # load is higher at the firing tick (usually decode —
            # it holds every in-flight rid to completion); a spare
            # for only one role would leave the other's scale-up
            # spawn-priced.  This is the deployment the spawn-priced
            # row's caveat said was missing: burst capacity no
            # longer pays process-spawn + jax import + compile
            # INSIDE a 4 s burst.
            for role in ("prefill", "decode"):
                for _ in range(standby_prefill):
                    cl.add_worker(role, standby=True)
        if autoscale:
            # the TTFT trigger is the load signal that works for BOTH
            # flavors: the disagg cluster has no admission queue (its
            # backlog is worker-side), so queue depth alone would
            # never fire there — a windowed TTFT p95 past the SLO is
            # the operator-visible symptom either way
            scaler = Autoscaler(
                cl, min_size=size0,
                max_size=max(max_replicas, size0),
                interval_s=0.05, cooldown_s=0.5,
                up_queue_factor=1.0, down_queue_factor=0.25,
                ttft_p95_slo_ms=slo.ttft_ms,
                up_ticks=2, down_ticks=20,
                drain_timeout_s=120.0).start()
        submitted = {}
        rejected = []
        t0 = time.perf_counter()
        for at, prompt, n in wl:
            while True:
                now = time.perf_counter() - t0
                drv.poll(now)
                if now >= at:
                    break
                time.sleep(min(at - now, 0.01))
            try:
                submitted[cl.submit(prompt, n)] = (at, prompt, n)
            except ClusterOverloaded as e:
                rejected.append({"at": at, "n": n,
                                 "retry_after_s": e.retry_after_s})
        while True:
            drv.poll(time.perf_counter() - t0)
            if cl.drain(timeout=0.25) and drv.done():
                break
            if time.perf_counter() - t0 > 600:
                raise RuntimeError("serve_bench --trace: replay did "
                                   "not drain within 600s")
        wall = time.perf_counter() - t0

        good, ttfts, worst_tbts = [], [], []
        completed = cancelled = failed = 0
        for rid, (at, prompt, n) in submitted.items():
            cr = cl.requests[rid]
            if cr.state == "done":
                completed += 1
            elif cr.state == "cancelled":
                cancelled += 1            # chaos "cancel" victims
            else:
                failed += 1
            ok, ttft_ms, tbt_ms = TT.classify_request(
                cr.submit_t, cr.token_times, n, slo)
            good.append((ok, n))
            if np.isfinite(ttft_ms):
                ttfts.append(ttft_ms)
            if np.isfinite(tbt_ms):
                worst_tbts.append(tbt_ms)
        arrivals = len(submitted) + len(rejected)
        goodput_frac = sum(ok for ok, _ in good) / max(1, arrivals)
        goodput_tok = sum(n for ok, n in good if ok)
        useful = sum(n for _, _, n in wl)
        if failed or completed + cancelled != len(submitted):
            raise RuntimeError(
                "serve_bench --trace: %d/%d submitted requests "
                "completed (%d failed) — the chaos/scale scenario "
                "lost requests" % (completed, len(submitted), failed))
        # cancel reconciliation: every chaos "cancel" that named a
        # victim ended exactly one request in state "cancelled", and
        # the metrics counter agrees — no cancel may be lost or
        # double-fired
        cancels_applied = sum(1 for e in drv.applied
                              if e["kind"] == "cancel"
                              and e["victim"] is not None)
        n_counter = int(cl.registry.snapshot()["counters"].get(
            "cluster_cancelled_total", 0))
        if cancelled != cancels_applied or n_counter != cancelled:
            raise RuntimeError(
                "serve_bench --trace: cancel arithmetic broken — "
                "%d requests cancelled, %d chaos cancels applied, "
                "cluster_cancelled_total=%d"
                % (cancelled, cancels_applied, n_counter))

        mismatches = 0
        if verify_oracle:
            reqs = [(pr, n) for _, pr, n in
                    (submitted[rid] for rid in submitted)]
            oracle = _oracle_outputs(params, cfg, reqs)
            for (rid, (at, prompt, n)), o in zip(submitted.items(),
                                                 oracle):
                cr = cl.requests[rid]
                if cr.state == "cancelled":
                    # a cancelled request never finished — but every
                    # token it DID commit must be a strict prefix of
                    # the oracle continuation (it must never have
                    # produced a wrong token, even one that was
                    # cut off)
                    got = [int(t) for t in cr.committed]
                    o_gen = [int(t) for t in o[len(prompt):]]
                    if got != o_gen[:len(got)]:
                        mismatches += 1
                elif not np.array_equal(cr.output, o):
                    mismatches += 1
            if mismatches:
                raise RuntimeError(
                    "serve_bench --trace: %d/%d completions diverge "
                    "from the generate oracle — exactness broken "
                    "under chaos/scaling" % (mismatches,
                                             len(submitted)))

        # the autoscaler must come back down, and nothing may leak
        scale_ups = scale_downs = 0
        up_act = []
        if scaler is not None:
            deadline = time.perf_counter() + 60.0
            while time.perf_counter() < deadline:
                if scaler.error is not None:
                    # the loop died on a real actuation failure (e.g.
                    # the zero-leak RuntimeError): that diagnosis,
                    # not a generic convergence message, is the
                    # result
                    raise scaler.error
                if scaler._healthy() <= size0:
                    break
                time.sleep(0.1)
            else:
                raise RuntimeError(
                    "serve_bench --trace: autoscaler never returned "
                    "to min size %d after the drain" % size0)
            scale_ups = sum(e["action"] == "up" for e in scaler.events)
            scale_downs = sum(e["action"] == "down"
                              for e in scaler.events)
            # the spawn-vs-standby economics, MEASURED per scale-up:
            # how long the actuation blocked before capacity existed
            # (process spawn + jax import + compile ≈ 15 s on this
            # host; standby adoption ≈ milliseconds)
            up_act = [e["actuation_s"] for e in scaler.events
                      if e["action"] == "up" and "actuation_s" in e]
        if disagg:
            st = cl.cluster_stats()
            for name, s in st.items():
                if (s.get("prefix_refs", 0)
                        or s.get("staged_rids", 0)
                        or s.get("active_requests", 0)
                        or s.get("pages_in_use", 0)
                        != s.get("prefix_cached_pages", 0)):
                    raise RuntimeError(
                        "serve_bench --trace: worker %s leaks after "
                        "drain: %r" % (name, s))
        else:
            for rep in cl.replicas:
                eng = rep.engine
                if eng is None or rep.dead:
                    continue              # removed: checked at drain
                refs = 0 if eng.prefix is None else \
                    eng.prefix.refs_total
                cached = 0 if eng.prefix is None else \
                    eng.prefix.cached_pages
                if refs or eng.cache.pages_in_use != cached:
                    raise RuntimeError(
                        "serve_bench --trace: replica %d leaks after "
                        "drain (refs=%d, in_use=%d, cached=%d)"
                        % (rep.idx, refs, eng.cache.pages_in_use,
                           cached))

        snap = cl.registry.snapshot()["counters"]
        ttft_p50, ttft_p99 = _lat_stats(ttfts)
        tbt_p50, tbt_p99 = _lat_stats(worst_tbts)
        return {
            "section": "trace",
            "config": "trace_%s_%s%s" % (
                spec["name"],
                "disagg_p2_d1" if disagg else
                "r%d-%d" % (min_replicas, max_replicas),
                "_standby%d" % standby_prefill if standby_prefill
                else ""),
            "standby_prefill": standby_prefill,
            "seed": spec["seed"], "trace_sha": TT.trace_hash(trace),
            "events": len(wl), "arrivals": arrivals,
            "submitted": len(submitted), "rejected": len(rejected),
            "completed": completed, "cancelled": cancelled,
            "goodput_frac": goodput_frac,
            "goodput_tok_s": goodput_tok / wall,
            "tok_s": useful / wall, "wall_s": wall,
            "slo_ttft_ms": slo.ttft_ms, "slo_tbt_ms": slo.tbt_ms,
            "ttft_p50_ms": ttft_p50, "ttft_p99_ms": ttft_p99,
            "worst_tbt_p50_ms": tbt_p50, "worst_tbt_p99_ms": tbt_p99,
            "failovers": int(snap.get("cluster_failovers_total", 0)),
            "resubmitted": int(snap.get(
                "cluster_requests_resubmitted_total", 0)),
            "scale_ups": scale_ups, "scale_downs": scale_downs,
            "scale_up_actuation_s": [round(a, 4) for a in up_act],
            "chaos": drv.applied,
            "oracle_checked": len(submitted) if verify_oracle else 0,
            "oracle_mismatches": mismatches,
        }
    finally:
        # the scaler may re-raise a parked actuation error — it must
        # not abort the rest of the cleanup (SIGSTOPped chaos pids,
        # worker processes) nor mask an exception already unwinding
        scaler_err = None
        if scaler is not None:
            try:
                scaler.close()
            except Exception as e:
                scaler_err = e
        drv.close()
        cl.close(timeout=120)
        if scaler_err is not None and sys.exc_info()[0] is None:
            raise scaler_err


_goodput_gate_cache = {}


def run_gate_goodput(preset="full", seed=0):
    """The ``gpt_serve_goodput`` gate: goodput fraction (in PERCENT)
    through the scripted burst10x scenario — a 10× arrival burst with
    one replica killed mid-burst while the autoscaler reacts — on the
    given preset.  The returned row carries the trace seed + sha; the
    perf harness refuses the gate if the hash is missing, so a gated
    number is always reproducible from the checked-in seed."""
    key = (preset, seed)
    if key in _goodput_gate_cache:
        return _goodput_gate_cache[key]
    import traffic_trace as TT
    p = PRESETS[preset]
    params, cfg = _model(p)
    trace = TT.generate_trace(_trace_spec(p, seed))
    row = run_trace_replay(params, cfg, p, trace)
    _goodput_gate_cache[key] = row
    return row


# --------------------------------------------- round-14 tensor parallel ---

def run_tp(params, cfg, p, workload, tp):
    """The ``--tp`` section: the engine at tp=1 vs tp=N on the
    IDENTICAL workload (closed loop: submit everything, drain), with a
    full token-identity cross-check — every request's output must be
    bit-equal between the two (f32 greedy; RuntimeError otherwise).
    Rows report tok/s, wall, and the per-device KV-pool accounting
    behind the ~1/tp claim (pages shard the heads axis, so
    ``hbm_held_per_device == hbm_held / tp`` exactly)."""
    import jax
    from mxnet_tpu.serving import ServingEngine
    if tp > len(jax.devices()):
        # fail BEFORE the tp=1 leg burns minutes of benchmark time
        # on a run whose tp=N twin can never construct
        raise SystemExit(
            "serve_bench --tp %d: only %d device(s) visible (the "
            "virtual CPU mesh provides 8)" % (tp, len(jax.devices())))
    geo = _engine_geometry(p, workload, section="tp")
    rows, outs = [], {}
    for deg in (1, tp):
        eng = ServingEngine(params, cfg, tp=deg, **geo)
        # pre-warm the compiled (and, at tp>1, mesh-lowered) step;
        # drop the warmup's stats so the reported steps/preemptions
        # cover exactly the timed window the tok/s covers
        wid = eng.submit(workload[0][1], workload[0][2])
        eng.run()
        del eng.requests[wid]
        for k in eng.stats:
            eng.stats[k] = type(eng.stats[k])()
        rids = []
        t0 = time.perf_counter()
        for _, prompt, n in workload:
            rids.append(eng.submit(prompt, n))
        peak_held = 0
        while True:
            r = eng.step()
            peak_held = max(peak_held, eng.hbm_held)
            if r is False:
                break
        wall = time.perf_counter() - t0
        outs[deg] = [eng.requests[rid].output for rid in rids]
        useful = sum(n for _, _, n in workload)
        rows.append({
            "section": "tp", "config": "tp%d" % deg, "tp": deg,
            "tok_s": useful / wall, "wall_s": wall,
            "hbm_peak_held": peak_held,
            "hbm_peak_held_per_device": peak_held // deg,
            "hbm_pool": eng.hbm_pool,
            "hbm_pool_per_device": eng.hbm_pool_per_device,
            "preemptions": eng.stats["preemptions"],
            "steps": eng.stats["steps"]})
    mismatches = sum(
        not np.array_equal(a, b) for a, b in zip(outs[1], outs[tp]))
    if mismatches:
        raise RuntimeError(
            "serve_bench --tp: %d/%d requests diverge between tp=1 "
            "and tp=%d — the f32-greedy identity contract is broken"
            % (mismatches, len(workload), tp))
    for r in rows:
        r["identity_checked"] = len(workload)
        r["identity_mismatches"] = 0
    return rows


# ------------------------------------------------- round-11 decode levers ---

def _decode_heavy_workload(p, n=None, seed=0):
    """Closed-loop request shapes that spend their steps DECODING:
    minimum prompt, maximum output.  The kernel ablation and the
    decode-step gate measure step time on this mix so the number is a
    decode-step pin, not a prefill/chunking blend."""
    rng = np.random.RandomState(seed)
    P, N = min(p.prompt_lens), max(p.out_lens)
    n = 2 * p.num_slots if n is None else n
    return [(0.0, rng.randint(1, p.vocab, P).astype(np.int32), N)
            for _ in range(n)]


def run_kernel_ablation(params, cfg, p, spec_K=0, tp=1, seed=0):
    """The kernel-vs-XLA decode-step-time comparison: one closed-loop
    decode-heavy run per kernel (k = num_slots, metrics on, external
    cross-check off), step time from the engine's own
    ``serving_step_ms`` histogram.  NOTE off-TPU the pallas kernel
    runs in INTERPRETER mode — correct, but the step time measures
    the interpreter, not the fusion (docs/perf.md 'Paged attention
    kernel'); the chip-side number is the ``gpt_serve_decode_step_ms``
    gate's to pin.

    Round 22, ``tp>1``: both kernels run mesh-lowered on the tp-way
    mesh (pallas through the shard_map heads-slice walk) — same
    workload, same closed loop, so the cell pair prices the lowering
    against the sharded XLA gather.  Rows carry seed + workload sha
    (MULTICHIP provenance) and the chip-side pin is
    ``gpt_serve_pallas_tp2_step_ms``'s."""
    import hashlib
    wl = _decode_heavy_workload(p, seed=seed)
    sha = hashlib.sha256()
    for _, prompt, n in wl:
        sha.update(prompt.tobytes())
        sha.update(np.int64(n).tobytes())
    rows = []
    for kern in ("xla", "pallas"):
        r = run_engine(params, cfg, p, wl,
                       closed_loop_k=p.num_slots, metrics=True,
                       cross_check=False, kernel=kern, spec_K=spec_K,
                       tp=tp)
        r.update(section="kernel", preset=p.name, tp=tp, seed=seed,
                 workload_sha=sha.hexdigest()[:16],
                 config="kernel_%s" % kern if tp == 1
                 else "kernel_%s_tp%d" % (kern, tp))
        rows.append(r)
    return rows


def _oracle_drafter(params, cfg, p, workload, accept, seed=0):
    """Controlled-accept drafter for the spec sweep: precompute every
    request's true greedy continuation (grouped by prompt length so
    one batched ``generate`` compile covers each length), then propose
    the true next token with probability ``accept`` and a deliberately
    wrong one otherwise.  This turns the accept axis into a KNOB — the
    natural ngram rate on random traffic against a random-init
    checkpoint is ~0 (the round-6 floor), which measures the
    speculation OVERHEAD but says nothing about where the economics
    flip.  The engine verifies every proposal, so the knob cannot
    break exactness — only the accept rate."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import gpt

    n_max = max(n for _, _, n in workload)
    by_len = {}
    for _, prompt, _ in workload:
        by_len.setdefault(len(prompt), []).append(prompt)
    # prompt-keyed index (O(1) per draft call — the drafter runs per
    # decode row per step INSIDE the timed window, so a linear scan
    # over requests would bias the measured tok/s with workload size)
    by_prompt = {}
    lens = sorted(by_len, reverse=True)
    for P, prompts in sorted(by_len.items()):
        out = gpt.generate(params, cfg, jnp.asarray(np.stack(prompts)),
                           n_max)
        for prompt, s in zip(prompts, np.asarray(out).astype(np.int32)):
            by_prompt[prompt.tobytes()] = s
    rng = np.random.RandomState(seed)
    vmax = cfg.vocab_size - 1

    def drafter(tokens, K):
        true = np.zeros(0, np.int32)
        n = tokens.size
        for P in lens:                    # a few known prompt lengths
            if P > n:
                continue
            s = by_prompt.get(tokens[:P].tobytes())
            # greedy determinism: prompt match + generated-prefix match
            # identifies the request's true continuation
            if s is not None and np.array_equal(s[:n], tokens):
                true = s[n:n + K]
                break
        out = np.empty(K, np.int32)
        for i in range(K):
            t = int(true[i]) if i < true.size else 1
            hit = i < true.size and rng.rand() < accept
            out[i] = t if hit else (t + 1) % (vmax + 1)
        return out

    return drafter


def run_spec_sweep(params, cfg, p, workload, num_pages=None,
                   Ks=(0, 2, 4), oracle_accept=None):
    """accept×K sweep under the mixed Poisson traffic: the e2e engine
    run repeated at each spec_K, reporting tok/s, accept rate, and
    tokens/step.  K=0 is the no-speculation control on the identical
    workload.  ``oracle_accept=A`` swaps the ngram drafter for the
    controlled-accept oracle (see ``_oracle_drafter``) — the
    break-even instrument: commits/step grows with A while step cost
    is fixed by K, so sweeping A at fixed K locates the accept rate
    where in-engine speculation pays on this backend."""
    rows = []
    drafter = "ngram" if oracle_accept is None else \
        _oracle_drafter(params, cfg, p, workload, oracle_accept)
    tag = "" if oracle_accept is None else \
        "_oracle%02d" % round(100 * oracle_accept)
    for K in Ks:
        r = run_engine(params, cfg, p, workload, num_pages=num_pages,
                       spec_K=K, spec_drafter=drafter)
        r.update(section="spec", config="spec%s_K%d" % (tag, K))
        if oracle_accept is not None:
            r["oracle_accept"] = oracle_accept
        rows.append(r)
    return rows


_decode_step_gate_cache = {}


def run_gate_decode_step(preset="full"):
    """The ``gpt_serve_decode_step_ms`` gate: engine-internal step-time
    p50 (``serving_step_ms``) of a closed-loop decode-heavy run with
    ``kernel="pallas"`` on the full preset — the direct pin on the
    fused paged-attention lever (a lost fusion or a kernel regression
    moves THIS number; tok/s gates also move with occupancy and
    accept rates).  Direction "lower": v <= hi."""
    if preset in _decode_step_gate_cache:
        return _decode_step_gate_cache[preset]
    p = PRESETS[preset]
    params, cfg = _model(p)
    wl = _decode_heavy_workload(p)
    best = min(
        run_engine(params, cfg, p, wl, closed_loop_k=p.num_slots,
                   metrics=True, cross_check=False,
                   kernel="pallas")["step_p50_ms"]
        for _ in range(3))
    _decode_step_gate_cache[preset] = best
    return best


# ------------------------------------------------------------------ main ---

def run_gate(preset="full"):
    """The ``gpt_serve_mixed_tok_s`` gate: e2e engine tok/s on the
    seeded mixed Poisson workload (equal-HBM config)."""
    p = PRESETS[preset]
    params, cfg = _model(p)
    wl = _workload(p, seed=0)
    batch = max(1, p.num_slots // 2)
    pages = _equal_hbm_pages(cfg, p, wl, batch)
    return run_engine(params, cfg, p, wl, num_pages=pages)["tok_s"]


_telemetry_gate_cache = {}


def run_gate_telemetry(preset="full"):
    """Shared run behind the ``gpt_serve_p99_ms`` and
    ``gpt_serve_metrics_overhead_pct`` gates.

    * ``p99_ms`` — engine-internal TBT p99 from the OPEN-loop e2e
      workload with metrics on (the latency-distribution gate rides
      the same Poisson workload as ``gpt_serve_mixed_tok_s``).
    * ``overhead_pct`` — measured CLOSED-loop (k = num_slots, no
      arrival pacing or sleeps) and BEST-OF-3 per side, the same
      jitter-stripping the decode gates use: open-loop tok/s carries
      multi-percent scheduler/arrival noise, and even closed-loop
      single runs swing ±10-20% on a busy host — best-of-reps compares
      the systematic per-step instrument cost, which is what the 3%
      budget is about.

    Memoized so the two gates share one set of runs."""
    if preset in _telemetry_gate_cache:
        return _telemetry_gate_cache[preset]
    p = PRESETS[preset]
    params, cfg = _model(p)
    wl = _workload(p, seed=0)
    batch = max(1, p.num_slots // 2)
    pages = _equal_hbm_pages(cfg, p, wl, batch)
    on = run_engine(params, cfg, p, wl, num_pages=pages, metrics=True)
    k = p.num_slots
    best_off = max(
        run_engine(params, cfg, p, wl, num_pages=pages,
                   closed_loop_k=k)["tok_s"] for _ in range(3))
    # cross_check=False: the bar charges the ENGINE's instrument cost,
    # not the harness's own external-observation loop
    best_on = max(
        run_engine(params, cfg, p, wl, num_pages=pages,
                   closed_loop_k=k, metrics=True,
                   cross_check=False)["tok_s"]
        for _ in range(3))
    out = {"p99_ms": on["tbt_p99_ms"],
           "overhead_pct": 100.0 * (best_off / best_on - 1.0),
           "tok_s_off": best_off, "tok_s_on": best_on}
    _telemetry_gate_cache[preset] = out
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="mid",
                    choices=sorted(PRESETS))
    ap.add_argument("--quick", action="store_true",
                    help="alias for --preset quick")
    ap.add_argument("--sweep", action="store_true",
                    help="also run the occupancy + page-size sweeps")
    ap.add_argument("--kernel", default=None,
                    choices=("xla", "pallas"),
                    help="attention path for the e2e engine runs: the "
                         "block-table-gather XLA path or the fused "
                         "Pallas paged-attention kernel (interpreter "
                         "mode off-TPU); default: the engine's own "
                         "choice by its device")
    ap.add_argument("--spec-K", type=int, default=0, metavar="N",
                    help="arm in-engine speculative decode (N drafts "
                         "per decode row per step) on the e2e engine "
                         "runs; rows then carry accept-rate and "
                         "tokens/step columns")
    ap.add_argument("--kernel-ablation", action="store_true",
                    help="run the kernel-vs-XLA decode-step-time "
                         "ablation section (closed loop, decode-heavy "
                         "shapes); with --tp N both kernels run "
                         "mesh-lowered at tp=N on the virtual mesh "
                         "(rides the --tp own-invocation rule)")
    ap.add_argument("--transport-ablation", action="store_true",
                    help="run the round-22 socket-vs-put disagg "
                         "transport pair (same seeded remote-hit "
                         "measurement per mode, cross-mode token "
                         "identity + put-coverage reconciliation "
                         "hard-enforced); runs ALONE like the other "
                         "cross-process sections")
    ap.add_argument("--trace-overhead", action="store_true",
                    help="run the round-23 observability-tax pair "
                         "(same seeded closed-loop disagg run with "
                         "flight recorder + span shipping on vs "
                         "killed via MXNET_SERVE_FLIGHT_SLOTS=0 / "
                         "MXNET_SERVE_SPANS=0, cross-mode token "
                         "identity + toggle reconciliation "
                         "hard-enforced); runs ALONE like the other "
                         "cross-process sections")
    ap.add_argument("--spec-sweep", action="store_true",
                    help="run the accept-rate x K sweep section "
                         "(e2e Poisson workload at spec_K = 0/2/4)")
    ap.add_argument("--spec-oracle", type=float, default=None,
                    metavar="A",
                    help="with --spec-sweep: replace the ngram "
                         "drafter by a controlled-accept oracle "
                         "(propose the true greedy continuation with "
                         "probability A) — the break-even instrument")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="run the round-14 tensor-parallel section: "
                         "engine at tp=1 vs tp=N on an 8-device "
                         "virtual CPU mesh (per-device HBM held, "
                         "tok/s, full tp={1,N} token-identity "
                         "cross-check).  Must be its own invocation "
                         "(the virtual mesh is requested before jax "
                         "initializes)")
    ap.add_argument("--disagg", action="store_true",
                    help="run the round-15 disaggregated section: a "
                         "cross-PROCESS cluster (2 prefill + 1 decode "
                         "worker processes) streaming KV pages, with "
                         "the cluster-level prefix index — includes "
                         "the remote-hit-vs-cold TTFT gate "
                         "measurement and the prefilled-once "
                         "reconciliation")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="run the round-10 cluster section over N "
                         "ServingEngine replicas (prefix-cache on/off "
                         "pair + a forced mid-run failover)")
    ap.add_argument("--shared-prefix-frac", type=float, default=None,
                    metavar="F",
                    help="fraction of cluster/disagg-workload "
                         "requests that open with one shared "
                         "system-prompt prefix (full pages, half the "
                         "max prompt length).  Defaults: 0 for "
                         "--replicas, 0.8 for --disagg — an explicit "
                         "value (including 0) always wins")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="skip the metrics-enabled telemetry section")
    ap.add_argument("--chrome-trace", default=None, metavar="FILE",
                    help="profile the telemetry run and dump the "
                         "combined chrome-trace (op events + request "
                         "lifecycle spans) to FILE (renamed from "
                         "--trace in round 16 — --trace now replays "
                         "workload traces).  With --disagg the dump "
                         "instead covers the disagg Poisson run: ONE "
                         "merged trace with router, per-worker, and "
                         "transport swimlanes on the "
                         "handshake-reconciled clock (round 23)")
    ap.add_argument("--trace", default=None, metavar="FILE|burst10x",
                    help="run the round-16 trace-replay section "
                         "ALONE: open-loop replay of a workload "
                         "trace (a traffic_trace.py JSON file, or "
                         "'burst10x' to generate the scripted "
                         "10x-burst scenario from --seed) against "
                         "the cluster with the autoscaler live and a "
                         "seeded chaos schedule (one replica death "
                         "mid-burst); reports goodput vs the preset "
                         "SLO budgets and cross-checks bit-exactness "
                         "vs the generate oracle.  Combine with "
                         "--disagg for the cross-process cluster "
                         "(real SIGKILL)")
    ap.add_argument("--tier-sweep", action="store_true",
                    help="round-18 KV-tiering section: per-tier "
                         "hit-TTFT (hot/warm/cold on one engine, "
                         "peer-host across processes) + swap-resume "
                         "vs recompute-resume; runs ALONE like the "
                         "gate sections it feeds")
    ap.add_argument("--standby", type=int, default=0, metavar="N",
                    help="--trace --disagg: pre-provision N standby "
                         "worker processes PER ROLE before the "
                         "replay clock starts (scale-up adopts one "
                         "in O(peer-map flip) instead of paying "
                         "spawn+compile mid-burst)")
    ap.add_argument("--no-autoscale", action="store_true",
                    help="trace replay: pin the replica count")
    ap.add_argument("--no-chaos", action="store_true",
                    help="trace replay: no fault injection")
    ap.add_argument("--no-oracle", action="store_true",
                    help="trace replay: skip the generate-oracle "
                         "bit-exactness cross-check")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="victim-draw seed for the chaos schedule")
    ap.add_argument("--chaos-kinds", default="kill",
                    metavar="K[,K...]",
                    help="trace replay: comma list of scripted fault "
                         "kinds spread through the burst window — "
                         "kill, stall, reset (disagg), cancel (the "
                         "round-20 client-disconnect fault: a seeded "
                         "live request cancelled end-to-end, "
                         "reconciled against "
                         "cluster_cancelled_total)")
    ap.add_argument("--min-replicas", type=int, default=2)
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    if args.chrome_trace and args.no_telemetry:
        ap.error("--chrome-trace needs the telemetry section; drop "
                 "--no-telemetry")
    if args.tp > 1:
        # request the virtual CPU mesh BEFORE any backend initializes
        # (the same mechanism the tests' conftest uses); the CPU
        # backend is not the default where a real chip is visible
        import jax
        jax.config.update("jax_num_cpu_devices", 8)
    p = PRESETS["quick" if args.quick else args.preset]

    params, cfg = _model(p)
    wl = _workload(p, seed=args.seed)
    rows = []

    if args.tp > 1:
        # the tp section runs ALONE (the help text's "own invocation",
        # enforced): the 8-virtual-device topology changes XLA:CPU
        # threading, so every other section's numbers would be
        # measured on a different host shape than their recorded
        # baselines
        if args.kernel_ablation:
            # round 22: the kernel pair at tp=N (mesh-lowered pallas
            # vs sharded XLA gather) replaces the identity section —
            # same topology rule, different question
            print("--kernel-ablation --tp %d: virtual 8-device mesh "
                  "active; running the tp-kernel section only"
                  % args.tp, flush=True)
            ab = run_kernel_ablation(params, cfg, p,
                                     spec_K=args.spec_K, tp=args.tp,
                                     seed=args.seed)
            rows.extend(ab)
            for r in ab:
                print(json.dumps(r), flush=True)
            ax, ap_ = ab
            print("kernel tp=%d step p50: %s %.2f ms vs %s %.2f ms "
                  "(interpreter mode off-TPU — correctness path; the "
                  "chip prices the fusion via "
                  "gpt_serve_pallas_tp2_step_ms)"
                  % (args.tp, ax["kernel"], ax["step_p50_ms"],
                     ap_["kernel"], ap_["step_p50_ms"]), flush=True)
            if args.json:
                with open(args.json, "w") as f:
                    json.dump(rows, f, indent=1)
            return 0
        print("--tp: virtual %d-device mesh active; running the tp "
              "section only (other sections need their recorded "
              "single-device topology)" % 8, flush=True)
        tp_rows = run_tp(params, cfg, p, wl, args.tp)
        rows.extend(tp_rows)
        for r in tp_rows:
            print(json.dumps(r), flush=True)
        t1, tN = tp_rows
        # both pairs read tp=1 first, matching the sentence's
        # "tp=1 vs tp=N" order
        print("tp identity: %d/%d requests token-identical tp=1 vs "
              "tp=%d; per-device pool %d B -> %d B (1/%d = %.3fx); "
              "tok/s %.0f -> %.0f (virtual CPU mesh — collective "
              "overhead, not ICI)"
              % (t1["identity_checked"] - tN["identity_mismatches"],
                 t1["identity_checked"], args.tp,
                 t1["hbm_pool_per_device"], tN["hbm_pool_per_device"],
                 args.tp,
                 tN["hbm_pool_per_device"]
                 / max(1, t1["hbm_pool_per_device"]),
                 t1["tok_s"], tN["tok_s"]), flush=True)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1)
        return 0

    if args.transport_ablation:
        # runs ALONE: two cross-process clusters back to back own the
        # host; sharing it with the closed-loop sections would
        # contaminate both sides of the pair
        tr = run_transport_ablation(p, seed=args.seed)
        rows.extend(tr)
        for r in tr:
            print(json.dumps(r), flush=True)
        sock = next(r for r in tr if r["transport"] == "socket")
        put = next(r for r in tr if r["transport"] == "put")
        print("transport remote-hit TTFT: socket %.2f ms vs put "
              "%.2f ms (%d pages, %d B; put run moved %d page(s) / "
              "%d B through /dev/shm segments, transfer p50 %.2f vs "
              "%.2f ms); %d/%d token-identical across modes "
              "(same-host shm handoff — the chip prices ICI via "
              "gpt_serve_put_remote_hit_ttft_ms)"
              % (sock["ttft_remote_hit_ms"],
                 put["ttft_remote_hit_ms"], put["pages_streamed"],
                 put["page_bytes_streamed"], put["pages_put"],
                 put["put_bytes"], sock["transfer_p50_ms"],
                 put["transfer_p50_ms"],
                 put["identity_checked"]
                 - put["identity_mismatches"],
                 put["identity_checked"]), flush=True)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1)
        return 0

    if args.trace_overhead:
        # runs ALONE for the same reason as --transport-ablation: two
        # cross-process clusters back to back own the host, and the
        # pair's delta IS the number — background sections would
        # drown it
        tr = run_trace_overhead(p, seed=args.seed)
        rows.extend(tr)
        for r in tr:
            print(json.dumps(r), flush=True)
        on = next(r for r in tr if r["obs"] == "on")
        off = next(r for r in tr if r["obs"] == "off")
        print("trace overhead: obs-on %.0f tok/s vs obs-off %.0f "
              "tok/s (%.1f%% tax; %d spans shipped, flight ring "
              "live); %d/%d token-identical across modes (the gated "
              "budget is gpt_serve_trace_overhead_pct)"
              % (on["tok_s"], off["tok_s"],
                 on["trace_overhead_pct"], on["spans_shipped"],
                 on["identity_checked"] - on["identity_mismatches"],
                 on["identity_checked"]), flush=True)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1)
        return 0

    if args.trace:
        # the trace-replay section runs ALONE: it owns the replica
        # topology (autoscaler!) and its goodput numbers assume the
        # host isn't also running the closed-loop sections
        import traffic_trace as TT
        if os.path.exists(args.trace):
            trace = TT.load_trace(args.trace)
        elif args.trace == "burst10x":
            trace = TT.generate_trace(_trace_spec(p, args.seed))
        else:
            ap.error("--trace: %r is neither a trace file nor "
                     "'burst10x'" % args.trace)
        r = run_trace_replay(
            params, cfg, p, trace, disagg=args.disagg,
            autoscale=not args.no_autoscale,
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas,
            chaos_events=[] if args.no_chaos else None,
            chaos_seed=args.chaos_seed,
            chaos_kinds=tuple(
                k.strip() for k in args.chaos_kinds.split(",")
                if k.strip()),
            verify_oracle=not args.no_oracle,
            standby_prefill=args.standby)
        rows.append(r)
        print(json.dumps(r), flush=True)
        print("trace %s (seed %d, sha %s): goodput %.1f%% (%d/%d "
              "arrivals in SLO ttft<=%.0fms tbt<=%.0fms), %.0f "
              "SLO-good tok/s of %.0f; TTFT p50/p99 %.1f/%.1f ms; "
              "%d failover(s), %d scale-up(s)/%d scale-down(s) "
              "(actuation %s s); "
              "oracle %d/%d bit-identical"
              % (trace["spec"]["name"], r["seed"], r["trace_sha"],
                 100 * r["goodput_frac"],
                 round(r["goodput_frac"] * r["arrivals"]),
                 r["arrivals"], r["slo_ttft_ms"], r["slo_tbt_ms"],
                 r["goodput_tok_s"], r["tok_s"], r["ttft_p50_ms"],
                 r["ttft_p99_ms"], r["failovers"], r["scale_ups"],
                 r["scale_downs"], r["scale_up_actuation_s"],
                 r["oracle_checked"] - r["oracle_mismatches"],
                 r["oracle_checked"]), flush=True)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1)
        return 0

    if args.tier_sweep:
        # the tier sweep runs ALONE: its TTFT numbers are
        # scheduling-deterministic single-engine measurements plus a
        # worker-process cluster — sharing the host with the
        # closed-loop sections would contaminate both
        tg = run_gate_tier(p.name, seed=args.seed)
        tg = dict(tg, section="tier", config="tier_local")
        rows.append(tg)
        print(json.dumps(tg), flush=True)
        print("tier TTFT: hot(hbm) %.2f ms < warm(host) %.2f ms < "
              "cold %.2f ms on a %d-token prompt (%d pages; install "
              "cost %.2f ms, warm saves %.2fx vs cold); "
              "swap-resume %.2f ms vs recompute-resume %.2f ms "
              "(%.2fx); %d/%d oracle-identical"
              % (tg["ttft_hot_ms"], tg["ttft_warm_ms"],
                 tg["ttft_cold_ms"], tg["prompt_len"],
                 tg["chain_pages"], tg["hot_vs_warm_install_ms"],
                 tg["warm_vs_cold_speedup"], tg["swap_resume_ms"],
                 tg["recompute_resume_ms"],
                 tg["swap_vs_recompute_speedup"],
                 tg["oracle_checked"] - tg["oracle_mismatches"],
                 tg["oracle_checked"]), flush=True)
        if not args.quick:
            tp_row = run_tier_peer(p, seed=args.seed)
            tp_row = dict(tp_row, section="tier", config="tier_peer")
            rows.append(tp_row)
            print(json.dumps(tp_row), flush=True)
            print("tier peer-host: %.2f ms vs cold %.2f ms (%.2fx) — "
                  "the chain fetched from the OWNER's host tier "
                  "across processes (%d host-tier remote hit(s), "
                  "%d B streamed)"
                  % (tp_row["ttft_peer_host_ms"],
                     tp_row["ttft_cold_ms"], tp_row["speedup"],
                     tp_row["remote_hits_host_tier"],
                     tp_row["page_bytes_streamed"]), flush=True)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1)
        return 0

    # baseline batch = half the engine's slots, engine pool = the
    # baseline's contiguous HBM: equal memory, 2x the concurrency
    batch = max(1, p.num_slots // 2)
    pages = _equal_hbm_pages(cfg, p, wl, batch)

    base = run_fixed_batch(params, cfg, p, wl, batch)
    base.update(section="e2e", config="fixed_batch_b%d" % batch)
    rows.append(base)
    print(json.dumps(base), flush=True)

    e = run_engine(params, cfg, p, wl, num_pages=pages,
                   kernel=args.kernel, spec_K=args.spec_K)
    e.update(section="e2e", config="engine_s%d_ps%d"
             % (p.num_slots, p.page_size))
    rows.append(e)
    print(json.dumps(e), flush=True)
    print("engine/baseline tok_s: %.2fx  (equal HBM: pool %d B vs "
          "contiguous %d B)" % (e["tok_s"] / base["tok_s"],
                                e["hbm_pool"], base["hbm_held"]),
          flush=True)

    if not args.no_telemetry:
        # the metrics-enabled rerun: engine-internal histograms are the
        # latency source of truth (the external wall-clock cross-check
        # runs inside run_engine and raises on >10% p99 divergence)
        t = run_engine(params, cfg, p, wl, num_pages=pages,
                       metrics=True)
        if args.chrome_trace and not args.disagg:
            # a SEPARATE profiled run produces the dump: tracing has
            # its own per-step cost (event construction + locked
            # appends) that must not contaminate the telemetry row's
            # overhead number above.  With --disagg the dump is the
            # disagg section's MERGED trace instead — one file per
            # invocation, one dump
            from mxnet_tpu import profiler
            profiler.set_config(filename=args.chrome_trace)
            profiler.set_state("run")
            run_engine(params, cfg, p, wl, num_pages=pages,
                       metrics=True)
            profiler.set_state("stop")
            print("chrome trace written to %s" % profiler.dump(),
                  flush=True)
        # NOTE the run behind this row keeps cross_check=True, so the
        # tok/s delta vs the plain e2e run includes the HARNESS's own
        # external-observation loop, not just the obs layer — hence
        # the explicit key name.  The clean 3%-budget number is the
        # gpt_serve_metrics_overhead_pct gate (closed loop,
        # cross_check off, best-of-3).
        t.update(section="telemetry", config="engine_metrics",
                 overhead_incl_harness_pct=100.0
                 * (e["tok_s"] / t["tok_s"] - 1.0))
        rows.append(t)
        print(json.dumps(t), flush=True)
        print("telemetry: TBT p50/p95/p99 = %.2f/%.2f/%.2f ms "
              "(engine-internal) vs external p99 %.2f ms "
              "(divergence %.1f%%); TTFT p99 = %.1f ms; run overhead "
              "incl. cross-check harness %.1f%% tok/s (the gated "
              "metrics-only number is gpt_serve_metrics_overhead_pct)"
              % (t["tbt_p50_ms"], t["tbt_p95_ms"], t["tbt_p99_ms"],
                 t["ext_tbt_p99_ms"], 100 * t["tbt_p99_divergence"],
                 t["ttft_p99_ms"], t["overhead_incl_harness_pct"]),
              flush=True)

    if args.kernel_ablation:
        ab = run_kernel_ablation(params, cfg, p, spec_K=args.spec_K,
                                 seed=args.seed)
        rows.extend(ab)
        for r in ab:
            print(json.dumps(r), flush=True)
        import jax
        interp_note = "" if jax.devices()[0].platform == "tpu" else \
            " (pallas in INTERPRETER mode off-TPU: a correctness " \
            "path, not a perf claim)"
        print("kernel ablation: step p50 xla %.2f ms vs pallas "
              "%.2f ms%s" % (ab[0]["step_p50_ms"],
                             ab[1]["step_p50_ms"], interp_note),
              flush=True)

    if args.spec_sweep:
        sp = run_spec_sweep(params, cfg, p, wl, num_pages=pages,
                            oracle_accept=args.spec_oracle)
        rows.extend(sp)
        for r in sp:
            print(json.dumps(r), flush=True)
        base_t = sp[0]["tok_s"]
        print("spec sweep: " + "; ".join(
            "K=%d %.0f tok/s (%.2fx)%s"
            % (r.get("spec_K", 0), r["tok_s"], r["tok_s"] / base_t,
               "" if "spec_accept_rate" not in r else
               " accept %.2f" % r["spec_accept_rate"])
            for r in sp), flush=True)

    if args.sweep:
        for k in sorted({max(1, p.num_slots // 4),
                         max(1, p.num_slots // 2), p.num_slots}):
            r = run_engine(params, cfg, p, wl, num_pages=pages,
                           closed_loop_k=k)
            r.update(section="occupancy", config="k%d" % k)
            rows.append(r)
            print(json.dumps(r), flush=True)
        for ps in (4, 8, 16, 32):
            if ps > cfg.max_len:
                continue
            pp = _equal_hbm_pages(
                cfg, dataclasses.replace(p, page_size=ps), wl, batch)
            r = run_engine(params, cfg, p, wl, num_pages=pp,
                           page_size=ps)
            r.update(section="pagesize", config="ps%d" % ps)
            rows.append(r)
            print(json.dumps(r), flush=True)

    if args.replicas > 0:
        frac_c = args.shared_prefix_frac or 0.0
        wl_c = _workload(p, seed=args.seed,
                         shared_prefix_frac=frac_c)
        # prefix-hit TTFT vs cold prefill, isolated on one engine
        # (the gpt_serve_prefix_hit_ttft_ms gate measurement)
        pg = run_gate_prefix(p.name)
        pg = dict(pg, section="prefix", config="prefix_hit_gate")
        rows.append(pg)
        print(json.dumps(pg), flush=True)
        print("prefix cache: hit TTFT %.2f ms vs cold %.2f ms "
              "(%.2fx) on a %d-token prompt"
              % (pg["ttft_hit_ms"], pg["ttft_cold_ms"],
                 pg["speedup"], pg["prompt_len"]), flush=True)

        pair = {}
        for prefix in (True, False):
            r = run_cluster(params, cfg, p, wl_c, args.replicas,
                            prefix=prefix)
            r.update(section="cluster",
                     config="cluster_r%d_%s"
                     % (args.replicas,
                        "prefix" if prefix else "cold"))
            pair[prefix] = r
            rows.append(r)
            print(json.dumps(r), flush=True)
        print("cluster r%d (shared-prefix frac %.2f): prefix-cache "
              "TTFT p50 %.2f ms vs cold %.2f ms; hit tokens %d; "
              "affinity-routed %d" % (
                  args.replicas, frac_c,
                  pair[True]["ttft_p50_ms"], pair[False]["ttft_p50_ms"],
                  pair[True]["prefix_hit_tokens"],
                  pair[True]["routed_affinity"]), flush=True)

        # failover: replica 0 dies mid-run; EVERY request must still
        # complete (run_cluster raises otherwise)
        f = run_cluster(params, cfg, p, wl_c, args.replicas,
                        prefix=True, fail_after_steps=10)
        f.update(section="cluster",
                 config="cluster_r%d_failover" % args.replicas)
        rows.append(f)
        print(json.dumps(f), flush=True)
        print("failover: %d/%d completed after %d failover(s), %d "
              "resubmitted" % (f["completed"], len(wl_c),
                               f["failovers"], f["resubmitted"]),
              flush=True)

    if args.disagg:
        # the disagg workload shares a system prompt (the traffic
        # shape the cluster-level index exists for); an explicit
        # --shared-prefix-frac wins — INCLUDING 0 — else 0.8
        frac = 0.8 if args.shared_prefix_frac is None \
            else args.shared_prefix_frac
        wl_d = _workload(p, seed=args.seed, shared_prefix_frac=frac)
        dg = run_gate_disagg(p.name)
        dg = dict(dg, section="disagg", config="disagg_remote_gate")
        rows.append(dg)
        print(json.dumps(dg), flush=True)
        print("disagg remote-hit TTFT %.2f ms vs cold %.2f ms "
              "(%.2fx) on a %d-token prompt fetched cross-process"
              % (dg["ttft_remote_hit_ms"], dg["ttft_cold_ms"],
                 dg["speedup"], dg["prompt_len"]), flush=True)
        if args.chrome_trace:
            # round 23: the merged-dump smoke — profile the Poisson
            # run so worker span batches (shipped on stats ticks,
            # clock-corrected by the handshake ping-pong) land in ONE
            # router-side trace next to the router's own real-pid
            # request lanes
            from mxnet_tpu import profiler
            profiler.set_config(filename=args.chrome_trace)
            profiler.set_state("run")
        d = run_disagg(params, cfg, p, wl_d, prefill=2, decode=1,
                       seed=args.seed)
        d.update(section="disagg", config="disagg_p2_d1")
        rows.append(d)
        print(json.dumps(d), flush=True)
        print("disagg p2/d1 (shared-prefix frac %.2f): %.0f tok/s, "
              "TTFT p50 %.2f ms; %d pages / %d B streamed between "
              "processes; remote hits %d (%d tokens); prefilled-once "
              "reconciled with %d tokens of margin"
              % (frac, d["tok_s"], d["ttft_p50_ms"],
                 d["pages_streamed"], d["page_bytes_streamed"],
                 d["prefix_remote_hits"],
                 d["prefix_remote_hit_tokens"],
                 d["prefilled_once_margin_tokens"]), flush=True)
        if args.chrome_trace:
            import hashlib
            from mxnet_tpu.obs.trace import LANE_PID_BASE
            profiler.set_state("stop")
            path = profiler.dump()
            with open(path) as f:
                evs = json.load(f)["traceEvents"]
            lanes = sorted({e["args"]["name"] for e in evs
                            if e.get("ph") == "M"
                            and e.get("name") == "process_name"
                            and e.get("pid", 0) >= LANE_PID_BASE})
            worker_lanes = [l for l in lanes if l != "transport"]
            router_evs = sum(e.get("pid", 0) < LANE_PID_BASE
                             for e in evs)
            # lane-coverage smoke: the acceptance shape is router +
            # every worker + (when pages moved cross-process) the
            # transport lane, all in one file
            if len(worker_lanes) < 3 or not router_evs:
                raise RuntimeError(
                    "serve_bench --disagg --chrome-trace: merged "
                    "dump has worker lanes %r and %d router-pid "
                    "events — expected all 3 workers plus the "
                    "router's own lane" % (lanes, router_evs))
            if d["prefix_remote_hits"] and "transport" not in lanes:
                raise RuntimeError(
                    "serve_bench --disagg --chrome-trace: %d remote "
                    "hits moved pages cross-process but no transport "
                    "swimlane reached the merged dump"
                    % d["prefix_remote_hits"])
            sha = hashlib.sha256()
            for _, pr, _ in wl_d:
                sha.update(np.asarray(pr, np.int32).tobytes())
            mrow = {"section": "disagg",
                    "config": "disagg_chrome_trace",
                    "preset": p.name, "seed": args.seed,
                    "prompts_sha": sha.hexdigest()[:16],
                    "trace_file": path,
                    "trace_events": len(evs),
                    "router_events": int(router_evs),
                    "merged_lanes": lanes}
            rows.append(mrow)
            print(json.dumps(mrow), flush=True)
            print("merged chrome trace written to %s: %d events; "
                  "router lane + swimlanes %s (seed %d, prompts sha "
                  "%s)" % (path, len(evs), ", ".join(lanes),
                           args.seed, mrow["prompts_sha"]),
                  flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
