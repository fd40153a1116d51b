"""Swept HBM-bandwidth probe (round-4 verdict item #1a).

Measures sustained HBM bandwidth on the attached chip with chained,
differenced elementwise kernels, sweeping the working set 1 MB -> 1 GB.

Method (the three discoveries that make the number honest are the three
things the round-2 single-shot triad probe missed):

1. **Differenced trip counts.** Each kernel runs ``iters`` passes inside
   ONE jitted ``lax.fori_loop`` with a *traced* trip count (one compile
   per (kind, size); no unroll).  Bandwidth comes from
   ``(t(I2) - t(I1)) / (I2 - I1)``, cancelling the fixed per-dispatch
   cost that swamped the single-shot number.
2. **Forced host readback.** Every kernel returns a scalar derived
   from the result and the timer waits on ``float(scalar)``, an actual
   device->host fetch that cannot complete before the loop does —
   whatever the transport reports about buffer readiness.
3. **Working sets past VMEM.** v5e has ~128 MB VMEM; loops whose carry
   fits stay VMEM-resident and report multi-TB/s.  Only sizes
   >~256 MB measure HBM.  The sweep keeps the small sizes on purpose —
   the VMEM cliff is part of the roofline story (docs/hbm_bandwidth.md).

Kernels (every pass depends on the previous carry, so XLA cannot hoist
the body):
    - ``read``  : s_{k+1} = s_k + sum(x * k)   -> 1 pass  (read x)
    - ``copy``  : y_{k+1} = y_k + 1            -> 2 passes (r+w y)
    - ``triad`` : y_{k+1} = a + 0.5 * y_k      -> 3 passes (r a, r+w y)

bf16 data, (rows, 1024) layout (8x128-tile friendly), best-of-N.
Prints one JSON line per (kind, MB), then a summary.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp


def _build(kind: str, n_elems: int):
    rows = n_elems // 1024

    if kind == "read":
        @jax.jit
        def run(x, iters):
            def body(k, s):
                return s + jnp.sum((x * k.astype(x.dtype))
                                   .astype(jnp.float32))
            s = jax.lax.fori_loop(
                0, iters, lambda k, s: body(jnp.bfloat16(k), s),
                jnp.zeros((), jnp.float32))
            return s, s
        passes = 1
    elif kind == "copy":
        @jax.jit
        def run(y, iters):
            y = jax.lax.fori_loop(
                0, iters, lambda k, y: y + jnp.bfloat16(1.0), y)
            return y, y[0, 0].astype(jnp.float32)
        passes = 2
    elif kind == "triad":
        @jax.jit
        def run(ya, iters):
            y, a = ya
            y = jax.lax.fori_loop(
                0, iters, lambda k, y: a + jnp.bfloat16(0.5) * y, y)
            return (y, a), y[0, 0].astype(jnp.float32)
        passes = 3
    else:
        raise ValueError(kind)

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (rows, 1024), jnp.bfloat16)
    if kind == "triad":
        arg = (x, x + jnp.bfloat16(1.0))
    else:
        arg = x
    return run, arg, passes


def _time_once(run, arg, iters) -> float:
    t0 = time.perf_counter()
    _, scalar = run(arg, iters)
    float(scalar)                       # real sync: device->host fetch
    return time.perf_counter() - t0


def probe(kind: str, mb: int, reps: int, target_gb: float) -> dict:
    n_elems = mb * 1024 * 1024 // 2          # bf16
    run, arg, passes = _build(kind, n_elems)
    bytes_per_pass = passes * n_elems * 2
    i1 = 4
    delta = max(32, min(200000, int(target_gb * 1e9 / bytes_per_pass)))
    i2 = i1 + delta
    # warm: compile + touch both trip counts
    _time_once(run, arg, i1)
    _time_once(run, arg, i2)
    t1 = min(_time_once(run, arg, i1) for _ in range(reps))
    t2 = min(_time_once(run, arg, i2) for _ in range(reps))
    per_pass = (t2 - t1) / delta
    gbs = bytes_per_pass / per_pass / 1e9 if per_pass > 0 else float("nan")
    # a differenced time under ~100 ms was inside the run-to-run jitter
    # of the rounds 1-5 machine (not re-measured on the current one) —
    # the GB/s figure would be noise-dominated; flag it
    noisy = (t2 - t1) < 0.1
    return {"kind": kind, "mb": mb, "passes": passes, "i2": i2,
            **({"jitter_dominated": True} if noisy else {}),
            "t_i1_ms": round(t1 * 1e3, 2), "t_i2_ms": round(t2 * 1e3, 2),
            "per_pass_us": round(per_pass * 1e6, 2),
            "gb_per_s": round(gbs, 1)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--sizes", default="1,8,64,256,512,1024")
    p.add_argument("--kinds", default="read,copy,triad")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--target-gb", type=float, default=400.0,
                   help="differenced traffic per measurement; sized so "
                        "the differenced time clears the ~100 ms jitter "
                        "floor even at VMEM-resident (TB/s) rates")
    args = p.parse_args()

    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "platform": dev.platform,
                      "argv": vars(args)}))
    hbm_best = {}
    for kind in args.kinds.split(","):
        for mb in (int(s) for s in args.sizes.split(",")):
            r = probe(kind, mb, args.reps, args.target_gb)
            print(json.dumps(r), flush=True)
            # summary: past-VMEM (true HBM) rows only, and never rows
            # the probe itself flagged as jitter-dominated
            if mb >= 256 and not r.get("jitter_dominated"):
                hbm_best[kind] = max(hbm_best.get(kind, 0.0),
                                     r["gb_per_s"])
    print(json.dumps({"hbm_best_gbs": hbm_best}))


if __name__ == "__main__":
    main()
