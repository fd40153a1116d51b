"""Decode batch-scaling study (round-4 verdict item #6).

Single-stream decode is closed (docs/perf.md "GPT decode"); this sweeps
the THROUGHPUT axis: aggregate tok/s over decode batch {8..128} for
bf16, weight-only int8, and int8-KV on the GPT-2-small-class config,
plus a long-context cache-capacity probe where int8-KV's halved cache
is expected to matter (capacity, not speed).

Per-token-step time comes from differenced 64- vs 448-token
``generate()`` timings (one compiled program per length; the fixed
per-dispatch and prefill cost cancels in the difference).

    python benchmark/decode_batch_sweep.py [--batches 8,16,32,64,128]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="8,16,32,64,128")
    ap.add_argument("--modes", default="bf16,w8")
    ap.add_argument("--longctx", action="store_true",
                    help="also run the seq-3584 cache-capacity probe")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.models import gpt

    cfg = gpt.gpt_config(vocab_size=32000, max_len=512, d_model=768,
                         n_heads=12, n_layers=12, d_ff=3072,
                         dropout=0.0, use_flash=False, remat=False)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    params_w8 = gpt.quantize_decode_params(params)
    rng = np.random.RandomState(0)

    def per_step(p, B, kv_int8, n_lo=64, n_hi=448, reps=3):
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 8)),
                             jnp.int32)

        def timed(n):
            out = gpt.generate(p, cfg, prompt, max_new_tokens=n,
                               kv_int8=kv_int8)
            jax.device_get(out.ravel()[:1])
            best = 1e9
            for _ in range(reps):
                t0 = time.time()
                out = gpt.generate(p, cfg, prompt, max_new_tokens=n,
                                   kv_int8=kv_int8)
                jax.device_get(out.ravel()[:1])
                best = min(best, time.time() - t0)
            return best
        t_lo, t_hi = timed(n_lo), timed(n_hi)
        dt = (t_hi - t_lo) / (n_hi - n_lo)
        return dt if dt > 0 else float("nan")

    modes = {
        "bf16": (params, False),
        "w8": (params_w8, False),
        "kv8": (params, True),
        "w8+kv8": (params_w8, True),
    }
    batches = [int(b) for b in args.batches.split(",")]
    sel = args.modes.split(",")

    print("%-8s %6s %12s %12s" % ("mode", "batch", "ms/tok-step",
                                  "agg tok/s"), flush=True)
    results = {}
    for mode in sel:
        p, kv = modes[mode]
        for B in batches:
            dt = per_step(p, B, kv)
            agg = B / dt
            results["%s_b%d" % (mode, B)] = round(agg, 1)
            print("%-8s %6d %12.3f %12.0f" % (mode, B, dt * 1e3, agg),
                  flush=True)

    if args.longctx:
        # cache capacity: seq 3584, batch 8.  bf16 caches:
        # 12L * 2 * (B*H=96, 3584, 64) bf16 = 1.06 GB; int8 halves it.
        # At v5e-1's 16 GB HBM capacity binds at larger batch/length —
        # report both cache footprints + measured rate.
        cfg_l = gpt.gpt_config(vocab_size=32000, max_len=4096,
                               d_model=768, n_heads=12, n_layers=12,
                               d_ff=3072, dropout=0.0, use_flash=False,
                               remat=False)
        p_l = gpt.init_params(jax.random.PRNGKey(0), cfg_l)
        B = 8
        prompt = jnp.asarray(rng.randint(0, cfg_l.vocab_size, (B, 8)),
                             jnp.int32)
        for kv, name in ((False, "bf16-kv"), (True, "int8-kv")):
            def timed(n):
                out = gpt.generate(p_l, cfg_l, prompt,
                                   max_new_tokens=n, kv_int8=kv)
                jax.device_get(out.ravel()[:1])
                t0 = time.time()
                out = gpt.generate(p_l, cfg_l, prompt,
                                   max_new_tokens=n, kv_int8=kv)
                jax.device_get(out.ravel()[:1])
                return time.time() - t0
            t_lo, t_hi = timed(512), timed(3584)
            dt = (t_hi - t_lo) / (3584 - 512)
            bytes_per_tok = 12 * 2 * B * 12 * 64 * (1 if kv else 2)
            cache_mb = bytes_per_tok * 3584 / 1e6
            print("longctx %-8s %8.3f ms/tok-step %8.0f tok/s "
                  "cache %.0f MB" % (name, dt * 1e3, B / dt, cache_mb),
                  flush=True)
            results["longctx_%s_tok_s" % name] = round(B / dt, 1)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
