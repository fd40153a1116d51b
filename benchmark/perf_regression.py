"""Perf regression harness (round-2 verdict item #10): runs the
headline benchmarks on the real chip, all in this one process, and
compares against stored expected ranges.  Off-chip it exits non-zero.

    python benchmark/perf_regression.py             # run + compare
    python benchmark/perf_regression.py --update    # rewrite ranges

Ranges live in benchmark/perf_expected.json.  Bars are wide (±15%): the
chip-measured rows date from rounds 1-5 and have not been re-taken on
the current machine, whose run-to-run spread is not measured.  A
regression that matters (a 130x sharding-path accident, a lost fusion)
blows far past these bars.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
EXPECTED = os.path.join(REPO, "benchmark", "perf_expected.json")


def bench_resnet():
    # in this process: a chip belongs to one process at a time, and
    # this one holds it from the first gate on
    import bench
    return bench.run()["value"]


def bench_bert():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.models import transformer as T
    B, L = 16, 512
    cfg = T.bert_base(use_flash=False, remat=False, dropout=0.1)
    init_state, step = T.make_train_step(cfg, learning_rate=1e-4,
                                         scan_steps=100)
    state = init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, L)),
                         jnp.int32)
    labels = jnp.where(jnp.asarray(rng.rand(B, L) < 0.15), tokens,
                       -100)
    batch = {"tokens": tokens, "labels": labels,
             "mask": jnp.ones((B, L), dtype=bool)}
    k = jax.random.PRNGKey(1)
    state, _ = step(state, batch, k)
    jax.block_until_ready(state)
    jax.device_get(jax.tree_util.tree_leaves(state)[0].ravel()[:1])
    best = 1e9
    for _ in range(2):
        t0 = time.time()
        state, _ = step(state, batch, k)
        jax.block_until_ready(state)
        jax.device_get(jax.tree_util.tree_leaves(state)[0].ravel()[:1])
        best = min(best, time.time() - t0)
    return B * L * 100 / best


def bench_flash():
    """Flash fwd+bwd at seq 8192 (the regime where the kernel wins)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.kernels import flash_attention as FA
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 8192, 8, 64) * 0.05, jnp.float32)

    def loss(fn):
        return lambda q: (fn(q, q, q, causal=True) ** 2).sum()

    g = jax.jit(jax.grad(loss(FA.flash_attention)))
    K = 20

    def loop(q):
        def body(q, _):
            gq = g(q)
            return q + 1e-9 * gq, None
        return jax.lax.scan(body, q, None, length=K)[0]

    f = jax.jit(loop)
    r = f(q)
    jax.block_until_ready(r)
    jax.device_get(r.ravel()[:1])
    best = 1e9
    for _ in range(2):
        t0 = time.time()
        r = f(q)
        jax.block_until_ready(r)
        jax.device_get(r.ravel()[:1])
        best = min(best, time.time() - t0)
    return best / K * 1e3    # ms per fwd+bwd


def bench_longctx():
    """Model-level long-context TRAINING (round-4 verdict item #5: the
    flash + fused-dropout stack was only ever gated at kernel level).
    bert-base-class encoder at seq 4096 — above MXNET_FLASH_MIN_SEQ, so
    attention runs the Pallas flash kernels with the positional-hash
    dropout fused into fwd+dq+dkv — remat_policy='dots', dropout 0.1,
    fast_rng, bf16-free f32 params (the default stack).  Device-loop
    scan of K steps + hard sync, differenced against a shorter scan to
    drop the dispatch constant."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.models import transformer as T
    B, L = 2, 4096
    cfg = T.bert_base(max_len=L, use_flash=True, remat=True,
                      remat_policy="dots", dropout=0.1)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, L)),
                         jnp.int32)
    labels = jnp.where(jnp.asarray(rng.rand(B, L) < 0.15), tokens, -100)
    batch = {"tokens": tokens, "labels": labels,
             "mask": jnp.ones((B, L), dtype=bool)}
    k = jax.random.PRNGKey(1)

    def run(scan_steps):
        init_state, step = T.make_train_step(cfg, learning_rate=1e-4,
                                             scan_steps=scan_steps)
        state = init_state(jax.random.PRNGKey(0))
        # the step donates its state argument — rebind every call or
        # the next call passes invalidated buffers (InvalidArgument)
        state, _ = step(state, batch, k)
        jax.block_until_ready(state)
        jax.device_get(jax.tree_util.tree_leaves(state)[0].ravel()[:1])
        best = 1e9
        for _ in range(2):
            t0 = time.time()
            state, _ = step(state, batch, k)
            jax.block_until_ready(state)
            jax.device_get(
                jax.tree_util.tree_leaves(state)[0].ravel()[:1])
            best = min(best, time.time() - t0)
        return best
    t_lo, t_hi = run(4), run(16)
    per_step = (t_hi - t_lo) / 12
    if per_step <= 0:
        raise RuntimeError("longctx: dispatch noise exceeded the "
                           "device-time delta")
    return B * L / per_step


def _bench_gpt_decode_common(label, quantize, batch=8):
    """Shared decode bench: GPT-2-small-class model, differenced
    64/448-token timings.  generate() is ONE dispatch for the whole
    decode; differencing two lengths cancels its fixed per-dispatch
    and prefill cost and reports the device-only decode rate (the
    dispatch floor on the current machine: ROADMAP A2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.models import gpt
    cfg = gpt.gpt_config(vocab_size=32000, max_len=512, d_model=768,
                         n_heads=12, n_layers=12, d_ff=3072,
                         dropout=0.0, use_flash=False, remat=False)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    if quantize:
        params = gpt.quantize_decode_params(params)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, 8)),
                         jnp.int32)

    def timed(n, reps=3):
        out = gpt.generate(params, cfg, prompt, max_new_tokens=n)
        jax.device_get(out.ravel()[:1])
        best = 1e9
        for _ in range(reps):
            t0 = time.time()
            out = gpt.generate(params, cfg, prompt, max_new_tokens=n)
            jax.device_get(out.ravel()[:1])
            best = min(best, time.time() - t0)
        return best
    t64, t448 = timed(64), timed(448)
    per_tok = (t448 - t64) / 384
    if per_tok <= 0:
        raise RuntimeError(
            "%s: dispatch noise exceeded the device-time delta "
            "(t64=%.1fms t448=%.1fms)" % (label, t64 * 1e3, t448 * 1e3))
    return batch / per_tok


def bench_gpt_decode():
    return _bench_gpt_decode_common("gpt_decode", quantize=False)


def bench_gpt_decode_w8():
    """Weight-only int8 decode (round 4)."""
    return _bench_gpt_decode_common("gpt_decode_w8", quantize=True)


def bench_gpt_decode_throughput():
    """Best-throughput decode config from the round-5 batch-scaling
    study (benchmark/decode_batch_sweep.py): batch 128, weight-only
    int8 — aggregate tok/s.  Throughput saturates ~b16 (the VPU
    matvec regime ends; cache streaming dominates from there)."""
    return _bench_gpt_decode_common("gpt_decode_b128_w8", quantize=True,
                                    batch=128)


def bench_gpt_serve():
    """Continuous-batching serving gate (round 7): the paged-KV
    ``ServingEngine`` on the seeded mixed-length Poisson workload
    (benchmark/serve_bench.py, ``full`` preset: GPT-2-small-class w8,
    16 slots, page 16, pool sized to the fixed-batch-8 contiguous HBM
    budget).  tok/s counts REQUESTED generated tokens per wall second
    from first arrival to last completion — it moves with slot
    occupancy as well as step time (docs/perf.md "Serving"), so it is
    not comparable to the fixed-batch decode gates."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    return serve_bench.run_gate("full")


def bench_gpt_serve_p99():
    """Tail-latency gate (round 8): engine-INTERNAL TBT p99 (ms) from
    the ``serving_tbt_ms`` histogram on the full-preset e2e workload —
    the first gate on the serving layer's latency distribution rather
    than its throughput.  The external wall-clock cross-check runs
    inside serve_bench (>10% divergence raises there).  Direction
    "lower": the check is v <= hi."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    return serve_bench.run_gate_telemetry("full")["p99_ms"]


def bench_gpt_serve_metrics_overhead():
    """Observability overhead gate (round 8): percent tok/s lost by
    enabling ``MXNET_SERVING_METRICS`` on the full-preset e2e workload
    (same seed/pool, metrics-off vs metrics-on).  Direction "lower"
    with hi = 3.0: telemetry must stay within 3% of the metrics-off
    run.  Shares one workload run with gpt_serve_p99_ms (memoized in
    serve_bench.run_gate_telemetry)."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    return serve_bench.run_gate_telemetry("full")["overhead_pct"]


def bench_gpt_serve_decode_step():
    """Decode-step-time gate (round 11): engine-internal step-time p50
    (ms, ``serving_step_ms``) of a closed-loop decode-heavy run with
    the fused Pallas paged-attention kernel (``kernel="pallas"``) on
    the full preset, best-of-3 — the direct pin on the block-table-
    walk fusion.  The tok/s gates blend occupancy/accept effects; a
    kernel regression (lost fusion, bad pipelining) moves THIS number
    first.  Direction "lower": v <= hi.  Only meaningful on chip —
    off-TPU the kernel interprets."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    return serve_bench.run_gate_decode_step("full")


def bench_gpt_serve_prefix_hit():
    """Shared-prefix KV reuse gate (round 10): TTFT (ms) of a request
    whose whole prompt sits in the prefix cache — the engine maps the
    cached pages, COWs the tail page, and re-feeds one token instead
    of running 12 chunked-prefill steps.  Direction "lower" (v <= hi);
    the cold-vs-hit speedup rides along in the serve_bench ``prefix``
    row and docs/perf.md "Serving cluster"."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    return serve_bench.run_gate_prefix("full")["ttft_hit_ms"]


def bench_gpt_serve_disagg_remote_hit():
    """Disaggregated-serving gate (round 15): TTFT (ms) of a request
    whose whole-page prompt prefix is cached in ANOTHER prefill
    PROCESS — the requester fetches the pages over the transport
    (raw int8/bf16 page bytes, ``serving/transport.py``) and COW
    re-feeds one token instead of recomputing the prefill.  This is
    the one number that prices the whole disaggregated path: peer
    fetch + page install + handoff stream + decode admission.
    Direction "lower": v <= hi; the cold-vs-remote speedup rides
    along in the serve_bench ``disagg`` row."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    return serve_bench.run_gate_disagg("full")["ttft_remote_hit_ms"]


def bench_gpt_serve_put_remote_hit():
    """Zero-copy put-transport gate (round 22): the SAME remote-hit
    TTFT measurement as ``gpt_serve_disagg_remote_hit_ttft_ms`` with
    ``MXNET_SERVE_TRANSPORT=put`` forced, so the pair prices the
    page-put lever from both sides — this number regressing while the
    socket one holds means the segment write/mmap-install path got
    expensive; both regressing means the disagg pipeline did.  The
    run underneath is the full --transport-ablation reconciliation:
    it hard-fails unless every streamed page byte rode a put segment
    and every token matches the socket transport bitwise.  Direction
    "lower": v <= hi.  Reproducibility enforced like the goodput
    gate's: the row must carry seed + prompts sha or the gate
    refuses."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    row = serve_bench.run_gate_put_transport("full")
    if not row.get("prompts_sha") or "seed" not in row:
        raise RuntimeError(
            "gpt_serve_put_remote_hit_ttft_ms: result row carries "
            "no seed/prompts sha — the measurement is not "
            "reproducible; refusing to gate it (got keys %s)"
            % sorted(row))
    return row["ttft_remote_hit_ms"]


def bench_gpt_serve_trace_overhead():
    """Observability-tax gate (round 23): percent tok/s cost of
    default-on tracing — per-worker flight-recorder rings, span
    batches shipped to the router on stats ticks, the router's span
    store — on the seeded closed-loop disagg pair
    (serve_bench.run_gate_trace_overhead, full preset).  The run
    underneath hard-fails unless the toggle demonstrably took on both
    sides (the on run ships spans and holds a live flight ring; the
    off run does neither) and both runs are token-BIT-identical — the
    off path must be the untraced path, not a cheaper trace.  The
    gate VALUE is only the tax.  Direction "lower": v <= hi; noise on
    a loaded host runs a few percent either way, so the budget is
    sized as a ceiling on the emit paths, not a micro-benchmark.
    Reproducibility enforced like the goodput gate's: the row must
    carry seed + prompts sha or the gate refuses."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    row = serve_bench.run_gate_trace_overhead("full")
    if not row.get("prompts_sha") or "seed" not in row:
        raise RuntimeError(
            "gpt_serve_trace_overhead_pct: result row carries no "
            "seed/prompts sha — the measurement is not reproducible; "
            "refusing to gate it (got keys %s)" % sorted(row))
    return row["trace_overhead_pct"]


def bench_gpt_serve_pallas_tp2_step():
    """Mesh-lowered kernel gate (round 22): engine-internal step-time
    p50 of the decode-heavy closed-loop pallas run at tp=2 — the
    shard_map lowering where each device walks its heads slice of the
    heads-sharded page pool.  Paired with ``gpt_serve_decode_step_ms``
    (the tp=1 twin): this number regressing alone means the lowering
    (replicated block-table prefetch, heads-slice walk, wo psum) got
    expensive; both regressing means the kernel body did.  Needs >= 2
    visible devices (RuntimeError otherwise).  Direction "lower":
    v <= hi.  Only meaningful on chip — off-TPU the kernel interprets
    and the virtual mesh shares one host.  Reproducibility enforced:
    the row carries seed + workload sha."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    row = serve_bench.run_gate_pallas_tp_step("full", tp=2)
    if not row.get("workload_sha") or "seed" not in row:
        raise RuntimeError(
            "gpt_serve_pallas_tp2_step_ms: result row carries no "
            "seed/workload sha — the measurement is not "
            "reproducible; refusing to gate it (got keys %s)"
            % sorted(row))
    return row["step_p50_ms"]


def bench_gpt_serve_goodput():
    """Goodput SLO gate (round 16): percent of arrivals that COMPLETE
    within their per-request SLO (TTFT + worst inter-token gap
    budgets) through the scripted burst10x scenario — a 10× arrival
    burst over a diurnal ramp with heavy-tailed lengths, one replica
    killed mid-burst, the metrics-driven autoscaler reacting
    (serve_bench.run_gate_goodput, full preset).  This is the "stays
    up" gate: tok/s gates measure speed at steady state, this one
    measures completions a client would call good while the cluster
    is being hurt.  The run itself hard-fails (RuntimeError) unless
    every request completes bit-identical to the generate oracle with
    zero leaked pages/refs — the gate VALUE is only the SLO fraction.
    Direction "higher": v >= lo.  Reproducibility is enforced here:
    the row must carry the trace seed + sha (the same pair checked
    into MULTICHIP_r08.json) or the gate refuses to report."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    row = serve_bench.run_gate_goodput("full")
    if not row.get("trace_sha") or "seed" not in row:
        raise RuntimeError(
            "gpt_serve_goodput: result row carries no trace seed/sha "
            "— the measurement is not reproducible; refusing to gate "
            "it (got keys %s)" % sorted(row))
    return 100.0 * row["goodput_frac"]


def bench_gpt_serve_tier_hit():
    """KV-tiering gate (round 18): TTFT (ms) of a request whose whole
    prompt chain was SPILLED to the host-DRAM tier — the engine
    re-installs the exact pool bytes through the bucketed donated
    scatter (the warm hit) instead of re-running 12 chunked-prefill
    steps.  This is the number that prices the middle tier of the
    hbm → host → peer hierarchy; the hot/cold TTFTs and the
    swap-vs-recompute resume pair ride along in the serve_bench
    ``tier`` rows and docs/perf.md "KV tiering".  The run itself
    hard-fails (RuntimeError) unless hot < warm < cold strictly,
    swap-resume beats recompute-resume, every completion is
    bit-identical to the generate oracle, and nothing leaks — the
    gate VALUE is only the warm TTFT.  Direction "lower": v <= hi.
    Reproducibility is enforced here like the goodput gate's: the row
    must carry its seed + sweep sha or the gate refuses to report."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import serve_bench
    row = serve_bench.run_gate_tier("full")
    if not row.get("sweep_sha") or "seed" not in row:
        raise RuntimeError(
            "gpt_serve_tier_hit_ttft_ms: result row carries no "
            "seed/sweep sha — the measurement is not reproducible; "
            "refusing to gate it (got keys %s)" % sorted(row))
    return row["ttft_warm_ms"]


def bench_gpt_spec_decode():
    """Speculative decode gate (round 6): batch 8, w8 target, ngram
    (prompt-lookup) drafter at K=4 on the structured ("loop") workload
    — the regime speculation is FOR; the random-prompt floor is the
    probe's job (benchmark/spec_decode_probe.py), not the gate's.
    NOTE the benchmark-definition change: tok/s here counts COMMITTED
    tokens per wall second; a verify step commits 1..K+1 of them, so
    this number moves with the accept rate as well as the step time
    (docs/perf.md "Speculative decode").  Differenced 64/448-token
    timings as in the other decode gates."""
    import jax
    from mxnet_tpu.models import gpt
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    from spec_decode_probe import _prompts
    batch, K = 8, 4
    cfg = gpt.gpt_config(vocab_size=32000, max_len=512, d_model=768,
                         n_heads=12, n_layers=12, d_ff=3072,
                         dropout=0.0, use_flash=False, remat=False)
    params = gpt.quantize_decode_params(
        gpt.init_params(jax.random.PRNGKey(0), cfg))
    # the probe's "loop" workload — the gate's lo/hi were derived on
    # this exact prompt, so the two must not drift apart
    prompt = _prompts(cfg, batch, "loop")

    def timed(n, reps=3):
        out = gpt.generate_speculative(params, cfg, prompt, n, K=K,
                                       drafter="ngram")
        jax.device_get(out.ravel()[:1])
        best = 1e9
        for _ in range(reps):
            t0 = time.time()
            out = gpt.generate_speculative(params, cfg, prompt, n,
                                           K=K, drafter="ngram")
            jax.device_get(out.ravel()[:1])
            best = min(best, time.time() - t0)
        return best
    t64, t448 = timed(64), timed(448)
    per_tok = (t448 - t64) / 384
    if per_tok <= 0:
        raise RuntimeError(
            "gpt_spec_decode: dispatch noise exceeded the device-"
            "time delta (t64=%.1fms t448=%.1fms)"
            % (t64 * 1e3, t448 * 1e3))
    return batch / per_tok


def bench_gpt_http_stream_ttfb():
    """HTTP front-door gate (round 20, ROADMAP 6): time-to-first-
    token-byte in ms for a streamed ``POST /v1/generate`` whose whole
    prompt is prefix-HOT, measured from just before the TCP connect
    to the first SSE token event on a REAL loopback socket
    (http_bench.run_gate_ttfb, full preset, single replica so the
    measurement is scheduling-deterministic).  This prices the edge
    itself — connect + parse + auth + token-bucket + submit + route +
    one hot-prefix COW re-feed step + the thread→asyncio bridge + the
    SSE chunk write — NOT a cold prefill; a regression here is the
    front door getting slower, not the model.  Direction "lower":
    v <= hi.  Reproducibility enforced like the goodput gate's: the
    prompt comes from the checked-in trace format and the row must
    carry its seed + trace sha or the gate refuses to report."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import http_bench
    row = http_bench.run_gate_ttfb("full")
    if not row.get("trace_sha") or "seed" not in row:
        raise RuntimeError(
            "gpt_http_stream_ttfb_ms: result row carries no trace "
            "seed/sha — the measurement is not reproducible; "
            "refusing to gate it (got keys %s)" % sorted(row))
    return row["ttfb_warm_ms"]


def bench_bert_pretrain():
    """Training scale-out gate (round 19, ROADMAP 5): examples/s of
    the ONE jitted FSDP BERT-base pretrain step at dp=8 — params +
    optimizer moments sharded by the `parallel/fsdp.py` rule table,
    batch sharded over dp, gradient sync lowered by GSPMD to the ICI
    reduce-scatter fused into the sharded optimizer update
    (train_scale_bench.run_gate_pretrain, full preset).  The run
    itself HARD-FAILS (RuntimeError) unless the dp=2 f32 loss
    trajectory through the ICI-allreduce KVStore is bit-identical to
    single-device accumulation AND the FSDP per-device param+opt
    bytes are exactly /dp against live addressable_shards — the gate
    VALUE is only the ex/s.  Direction "higher": v >= lo.
    Reproducibility enforced like the goodput gate's: the row must
    carry its seed + config sha or the gate refuses to report.
    Returns None (a visible SKIP, not a failure) on a single-device
    host: the gate is a multi-device claim and must not abort the
    single-chip gates measured alongside it."""
    import jax
    if len(jax.devices()) < 2:
        print("bert_pretrain_ex_s: SKIP — needs >= 2 devices "
              "(virtual mesh ok: jax.config.update("
              "'jax_num_cpu_devices', 8))", flush=True)
        return None
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import train_scale_bench
    row = train_scale_bench.run_gate_pretrain("full")
    if not row.get("cfg_sha") or "seed" not in row:
        raise RuntimeError(
            "bert_pretrain_ex_s: result row carries no seed/config "
            "sha — the measurement is not reproducible; refusing to "
            "gate it (got keys %s)" % sorted(row))
    return row["ex_s"]


BENCHES = {
    "resnet50_img_s": (bench_resnet, "higher"),
    "bert_base_tok_s": (bench_bert, "higher"),
    "longctx_4096_tok_s": (bench_longctx, "higher"),
    "flash_8192_fwdbwd_ms": (bench_flash, "lower"),
    "gpt_decode_tok_s": (bench_gpt_decode, "higher"),
    "gpt_decode_w8_tok_s": (bench_gpt_decode_w8, "higher"),
    "gpt_decode_b128_w8_tok_s": (bench_gpt_decode_throughput, "higher"),
    "gpt_spec_decode_b8_tok_s": (bench_gpt_spec_decode, "higher"),
    "gpt_serve_mixed_tok_s": (bench_gpt_serve, "higher"),
    "gpt_serve_p99_ms": (bench_gpt_serve_p99, "lower"),
    "gpt_serve_metrics_overhead_pct": (bench_gpt_serve_metrics_overhead,
                                       "lower"),
    "gpt_serve_prefix_hit_ttft_ms": (bench_gpt_serve_prefix_hit,
                                     "lower"),
    "gpt_serve_decode_step_ms": (bench_gpt_serve_decode_step, "lower"),
    "gpt_serve_disagg_remote_hit_ttft_ms":
        (bench_gpt_serve_disagg_remote_hit, "lower"),
    "gpt_serve_put_remote_hit_ttft_ms":
        (bench_gpt_serve_put_remote_hit, "lower"),
    "gpt_serve_pallas_tp2_step_ms":
        (bench_gpt_serve_pallas_tp2_step, "lower"),
    "gpt_serve_trace_overhead_pct":
        (bench_gpt_serve_trace_overhead, "lower"),
    "gpt_serve_goodput": (bench_gpt_serve_goodput, "higher"),
    "gpt_serve_tier_hit_ttft_ms": (bench_gpt_serve_tier_hit,
                                   "lower"),
    "gpt_http_stream_ttfb_ms": (bench_gpt_http_stream_ttfb, "lower"),
    "bert_pretrain_ex_s": (bench_bert_pretrain, "higher"),
}

BAR = 0.15


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--update", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated gate name(s) to run alone "
                         "(e.g. in CI for the gate a PR touched); "
                         "unknown names are an error, not a silent "
                         "no-op")
    args = ap.parse_args()

    only = None
    if args.only:
        only = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = sorted(set(only) - set(BENCHES))
        if unknown:
            print("unknown gate(s): %s\navailable: %s"
                  % (", ".join(unknown), ", ".join(sorted(BENCHES))),
                  file=sys.stderr)
            return 2

    import mxnet_tpu as mx
    mx.context.require_tpu("perf_regression.py")

    expected = {}
    if os.path.exists(EXPECTED):
        with open(EXPECTED) as f:
            expected = json.load(f)

    results = {}
    failures = []
    for name, (fn, direction) in BENCHES.items():
        if only is not None and name not in only:
            continue
        v = fn()
        if v is None:                  # precondition unmet — visible
            print("%-24s %10s  [skip]" % (name, "-"), flush=True)
            continue                   # skip, expected entry untouched
        results[name] = round(v, 1)
        exp = expected.get(name)
        status = "new"
        if exp is not None and not args.update:
            lo, hi = exp["lo"], exp["hi"]
            ok = v >= lo if direction == "higher" else v <= hi
            status = "ok" if ok else "REGRESSION"
            if not ok:
                failures.append((name, v, exp))
        print("%-24s %10.1f  [%s]  expected %s" % (
            name, v, status, exp), flush=True)

    if args.update or not expected:
        out = dict(expected)           # keep entries not re-measured
        for name, v in results.items():
            # merge, not rebuild: methodology notes on an entry survive
            # range refreshes
            entry = dict(out.get(name, {}))
            if entry.get("pinned"):
                # policy bars (e.g. the 3% telemetry-overhead budget)
                # record the new measurement but keep their lo/hi:
                # --update must not relax a budget into whatever was
                # measured
                entry["measured"] = v
            else:
                entry.update({"lo": round(v * (1 - BAR), 1),
                              "hi": round(v * (1 + BAR), 1),
                              "measured": v})
            out[name] = entry
        with open(EXPECTED, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        print("wrote", EXPECTED)
        return 0
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
