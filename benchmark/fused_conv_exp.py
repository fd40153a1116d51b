"""Round-3 experiment: Pallas implicit-GEMM conv+BN vs XLA emitter,
per ResNet-50 3x3 shape, on the real chip.

Methodology per docs/perf.md + memory notes: chained scan carries,
differenced 40- vs 200-step timings (removes the per-dispatch fixed
cost), hard sync via device_get.
"""
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from mxnet_tpu.kernels.fused_conv import conv3x3_fused

SHAPES = [  # (B, H, W, C==K, th, bk)  ResNet-50 3x3 residual convs, b128
    (128, 56, 56, 64, 28, 64),
    (128, 28, 28, 128, 28, 128),
    (128, 14, 14, 256, 14, 128),
    (128, 7, 7, 512, 7, 128),
]


def timed(fn, x0, steps, reps=3):
    def body(c, _):
        return fn(c), 0.0
    f = jax.jit(lambda x: jax.lax.scan(body, x, None, length=steps)[0])
    r = f(x0)
    jax.device_get(r.reshape(-1)[0])          # true sync (warm compile)
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        r = f(x0)
        jax.device_get(r.reshape(-1)[0])
        best = min(best, time.perf_counter() - t0)
    return best


def slope_ms(fn, x0):
    t40 = timed(fn, x0, 40)
    t200 = timed(fn, x0, 200)
    return (t200 - t40) / 160 * 1e3


def check():
    """--check: regenerate the on-chip correctness bounds cited in
    docs/conv_ceiling_experiment.md §6 (pallas vs XLA on device)."""
    rng = np.random.RandomState(0)
    print("dev:", jax.devices())
    for B, H, W, C, th, bk in SHAPES:
        K = C
        x = jnp.asarray(rng.randn(B // 8, H, W, C) * 0.1, jnp.bfloat16)
        w = jnp.asarray(rng.randn(3, 3, C, K) * 0.05, jnp.bfloat16)
        sc = jnp.asarray(rng.rand(C) + 0.5, jnp.float32)
        sh = jnp.asarray(rng.randn(C) * 0.1, jnp.float32)
        y, s, ss = jax.jit(lambda x: conv3x3_fused(
            x, w, scale=sc, shift=sh, relu=True, stats=True,
            th=th, bk=bk))(x)
        xr = jnp.maximum(x.astype(jnp.float32) * sc + sh,
                         0).astype(jnp.bfloat16)
        ref = jax.lax.conv_general_dilated(
            xr, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(
                jnp.float32)
        yerr = float(jnp.abs(y.astype(jnp.float32) - ref).max())
        serr = float((jnp.abs(s - ref.sum((0, 1, 2)))
                      / (jnp.abs(ref.sum((0, 1, 2))) + 1)).max())
        qerr = float((jnp.abs(ss - (ref * ref).sum((0, 1, 2)))
                      / ((ref * ref).sum((0, 1, 2)) + 1)).max())
        # sums of ~bf16-rounded values over few hundred elements carry
        # O(1e-2) relative error when the true sum is near zero
        status = "OK" if yerr < 5e-2 and serr < 2e-2 and qerr < 5e-3 \
            else "FAIL"
        print("  %dx%d C=%d: y err %.2e  sum rel %.2e  ssq rel %.2e  %s"
              % (H, W, C, yerr, serr, qerr, status))


def main():
    import sys
    if "--check" in sys.argv:
        check()
        return
    rng = np.random.RandomState(0)
    print("dev:", jax.devices())
    for B, H, W, C, th, bk in SHAPES:
        K = C
        x0 = jnp.asarray(rng.randn(B, H, W, C) * 0.1, jnp.bfloat16)
        w = jnp.asarray(rng.randn(3, 3, C, K) * 0.05, jnp.bfloat16)
        scale = jnp.asarray(rng.rand(C) + 0.5, jnp.float32)
        shift = jnp.asarray(rng.randn(C) * 0.1, jnp.float32)
        gamma = jnp.ones((K,), jnp.float32)
        beta = jnp.zeros((K,), jnp.float32)

        def xla_conv(x):
            y = jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return x + y * jnp.bfloat16(1e-3)

        def pallas_conv(x):
            y = conv3x3_fused(x, w, th=th, bk=bk)
            return x + y * jnp.bfloat16(1e-3)

        def xla_chain(x):
            # bn-apply + relu + conv + next-layer stats, all in XLA
            xf = x.astype(jnp.float32) * scale + shift
            xf = jnp.maximum(xf, 0.0).astype(jnp.bfloat16)
            y = jax.lax.conv_general_dilated(
                xf, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            yf = y.astype(jnp.float32)
            mu = jnp.mean(yf, axis=(0, 1, 2))
            var = jnp.mean(yf * yf, axis=(0, 1, 2)) - mu * mu
            norm = gamma * jax.lax.rsqrt(var + 1e-5)
            return x + (y * jnp.bfloat16(1e-3)
                        + (norm + beta + mu).astype(jnp.bfloat16)
                        * jnp.bfloat16(1e-6))

        def pallas_chain(x):
            y, s, ss = conv3x3_fused(x, w, scale=scale, shift=shift,
                                     relu=True, stats=True, th=th, bk=bk)
            n = x.shape[0] * H * W
            mu = s / n
            var = ss / n - mu * mu
            norm = gamma * jax.lax.rsqrt(var + 1e-5)
            return x + (y * jnp.bfloat16(1e-3)
                        + (norm + beta + mu).astype(jnp.bfloat16)
                        * jnp.bfloat16(1e-6))

        tfl = 2 * B * H * W * C * K * 9 / 1e12
        row = [("xla_conv", xla_conv), ("pallas_conv", pallas_conv),
               ("xla_chain", xla_chain), ("pallas_chain", pallas_chain)]
        print("shape B%d %dx%d C=K=%d  (%.2f GFLOP)"
              % (B, H, W, C, tfl * 1e3))
        for name, fn in row:
            try:
                ms = slope_ms(fn, x0)
                print("  %-12s %7.3f ms  %6.1f TF/s"
                      % (name, ms, tfl / (ms / 1e3)))
            except Exception as e:
                print("  %-12s ERROR %s" % (name, str(e)[:200]))


if __name__ == "__main__":
    main()
