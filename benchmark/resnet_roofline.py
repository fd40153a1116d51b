"""Per-layer ResNet-50 roofline + model-variant probe (round 5).

Two modes:

  --layers    per-conv-shape table: measured ms (differenced chained
              scans) vs the shape's own roofline bound
              max(FLOPs/PEAK_TF, bytes/PEAK_BW), for fwd, dgrad, wgrad.
  --variants  whole-train-step timing for model-level TPU transforms
              (verdict round-4 item #1): baseline, space-to-depth stem,
              channel-pad 3->4 stem, bf16 BN statistics, BN fixed
              scale/shift (the known ~3190 img/s bound), maxpool->
              stride-slice substitution, relu stripped — each isolates
              one term of the 47 ms step.

Methodology: docs/perf.md "Methodology" — every timing is a K-step
carry-chained lax.scan (nothing hoists), differenced between two K
values to remove the per-dispatch fixed cost, best of 3.

Peaks used for the roofline: 134 TF/s bf16 matmul and 700 GB/s HBM
(both measured on this chip: docs/perf.md, docs/hbm_bandwidth.md).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

B = 128
DT = jnp.bfloat16
PEAK_TF = 134e12
PEAK_BW = 700e9

STAGES = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
          (3, 512, 2048, 2)]


# ---------------------------------------------------------------------------
# timing helpers
# ---------------------------------------------------------------------------

def hard_sync(r):
    jax.block_until_ready(r)
    jax.device_get(jax.tree_util.tree_leaves(r)[0].ravel()[:1])


def time_scan(make_loop, arg, k):
    f = jax.jit(make_loop(k))
    r = f(arg)
    hard_sync(r)
    best = 1e9
    for _ in range(3):
        t0 = time.time()
        r = f(arg)
        hard_sync(r)
        best = min(best, time.time() - t0)
    return best


def diff_time(make_loop, arg, k1=30, k2=120):
    """ms per iteration from the slope between a k1- and k2-step scan."""
    t1 = time_scan(make_loop, arg, k1)
    t2 = time_scan(make_loop, arg, k2)
    return (t2 - t1) / (k2 - k1) * 1e3


# ---------------------------------------------------------------------------
# --layers: per-conv roofline
# ---------------------------------------------------------------------------

def conv_shapes():
    """Every distinct ResNet-50 conv as (label, H, W, Cin, Cout, k,
    stride, count) — count is the shape's multiplicity in the model.

    Spatial sizes are the conv's INPUT resolution at 224^2 images.
    """
    out = [("stem7x7/2", 224, 224, 3, 64, 7, 2, 1)]
    res = 56
    cin = 64
    for si, (n, mid, cout, stride) in enumerate(STAGES):
        s = si + 1
        r2 = res // stride
        out.append(("s%d 1x1 %d->%d" % (s, cin, mid), res, res, cin,
                    mid, 1, 1, 1))
        if stride > 1:
            out.append(("s%d 3x3/%d %d->%d" % (s, stride, mid, mid),
                        res, res, mid, mid, 3, stride, 1))
            out.append(("s%d 3x3/1 %d->%d" % (s, mid, mid), r2, r2,
                        mid, mid, 3, 1, n - 1))
        else:
            out.append(("s%d 3x3/1 %d->%d" % (s, mid, mid), res, res,
                        mid, mid, 3, 1, n))
        out.append(("s%d 1x1 %d->%d" % (s, mid, cout), r2, r2, mid,
                    cout, 1, 1, n))
        out.append(("s%d sc 1x1/%d %d->%d" % (s, stride, cin, cout),
                    res, res, cin, cout, 1, stride, 1))
        out.append(("s%d 1x1 %d->%d" % (s, cout, mid), r2, r2, cout,
                    mid, 1, 1, n - 1))
        cin = cout
        res = r2
    return out


def conv_cost(h, w, cin, cout, k, stride):
    ho, wo = h // stride, w // stride
    flops = 2.0 * B * ho * wo * cout * k * k * cin
    bytes_ = 2.0 * (B * h * w * cin + B * ho * wo * cout + k * k * cin
                    * cout)
    return flops, bytes_


def run_layers(k1, k2, K=60):
    """Per-shape conv cost via 2-vs-1 in-body differencing: each scan
    body runs the measured op once or twice on perturbed inputs (no
    CSE) with an identical carry chain, at the SAME scan length K — the
    dispatch constant AND the carry-chain tax cancel exactly in the
    difference (the earlier chained-input probe folded a full-tensor
    perturbation pass into every small conv's number)."""
    del k1, k2  # kept for CLI compat; K-differencing is not used here
    rows = []
    print("%-22s %3s %7s %7s %7s | %8s %8s %6s" % (
        "shape", "x", "fwd ms", "dgrad", "wgrad", "roof ms", "TF/s",
        "eff"))
    for label, h, w, cin, cout, k, stride, count in conv_shapes():
        ho, wo = h // stride, w // stride
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(B, h, w, cin), DT)
        wt = jnp.asarray(rng.randn(k, k, cin, cout) * 0.05, DT)
        dy = jnp.asarray(rng.randn(B, ho, wo, cout), DT)

        def fwd(xx, ww):
            return jax.lax.conv_general_dilated(
                xx, ww, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        # second-application constants: differ from the first ONLY in
        # small operands (weights / cotangent constants) so no CSE fires
        # and the 2-vs-1 delta is exactly one extra conv — everything
        # else in the body (carry update, any shared scalar scaling) is
        # identical between the two scans and cancels
        wt2 = wt * DT(1.01)
        dy2 = dy * DT(1.01)

        def measure(op_out):
            """op_out(xc, i, C) -> scalar folding application i's
            result (C = the big operands, passed as jit ARGS — captured
            device constants would be baked into the program body);
            timed with
            1 vs 2 applications inside an identical chain at the same
            scan length K (dispatch + chain tax cancel)."""
            def mk(n):
                def loop(x0, C):
                    def body(xc, _):
                        acc = op_out(xc, 0, C)
                        if n == 2:
                            acc = acc + op_out(xc, 1, C)
                        xc = xc * (1 + 1e-12 * acc.astype(DT))
                        return xc, ()
                    return jax.lax.scan(body, x0, None, length=K)[0]
                return loop
            C = (wt, wt2, dy, dy2)
            def timed(loopfn):
                f = jax.jit(loopfn)
                r = f(x, C)
                hard_sync(r)
                best = 1e9
                for _ in range(3):
                    t0 = time.time()
                    r = f(x, C)
                    hard_sync(r)
                    best = min(best, time.time() - t0)
                return best
            t1 = timed(mk(1))
            t2 = timed(mk(2))
            return max((t2 - t1) / K * 1e3, 0.0)

        def fwd_out(xc, i, C):
            wtA, wtB, _, _ = C
            return jnp.mean(fwd(xc, wtA if i == 0 else wtB))

        def dgrad_out(xc, i, C):
            # dx = conv_transpose(dy, w) is independent of the input,
            # so chain through a cheap carry-derived scalar on dy.
            # dyc is identical for both applications (CSE merges it),
            # so the 2-vs-1 delta stays one conv.
            wtA, wtB, dyA, _ = C
            s = jnp.sum(xc[0, 0, 0]).astype(DT)
            dyc = dyA * (1 + 1e-12 * s)
            _, vjp_x = jax.vjp(
                lambda xx: fwd(xx, wtA if i == 0 else wtB), xc)
            (dx,) = vjp_x(dyc)
            return jnp.mean(dx)

        def wgrad_out(xc, i, C):
            # dw = x (*) dy depends on the carried input directly
            wtA, _, dyA, dyB = C
            _, vjp_w = jax.vjp(lambda ww: fwd(xc, ww), wtA)
            (dw,) = vjp_w(dyA if i == 0 else dyB)
            return jnp.mean(dw)

        tf_ = measure(fwd_out)
        tdg = measure(dgrad_out)
        twg = measure(wgrad_out)

        flops, bytes_ = conv_cost(h, w, cin, cout, k, stride)
        roof_ms = max(flops / PEAK_TF, bytes_ / PEAK_BW) * 1e3
        tfs = flops / (tf_ * 1e-3) / 1e12 if tf_ > 0 else float("inf")
        eff = roof_ms / tf_ if tf_ > 0 else float("inf")
        rows.append((label, count, tf_, tdg, twg, roof_ms, tfs, eff))
        print("%-22s %3d %7.3f %7.3f %7.3f | %8.3f %8.1f %5.0f%%" % (
            label, count, tf_, tdg, twg, roof_ms, tfs, eff * 100),
            flush=True)
    tot_f = sum(r[1] * r[2] for r in rows)
    tot_d = sum(r[1] * r[3] for r in rows)
    tot_w = sum(r[1] * r[4] for r in rows)
    tot_roof = sum(r[1] * r[5] for r in rows)
    print("-" * 82)
    print("%-26s %7.3f %7.3f %7.3f | weighted roofline(x3)=%.2f ms"
          % ("WEIGHTED TOTAL", tot_f, tot_d, tot_w, 3 * tot_roof))
    return rows


# ---------------------------------------------------------------------------
# --variants: whole-step model transforms
# ---------------------------------------------------------------------------

def make_model(bn_mode="f32", stem="conv7", pool="max", relu=True,
               layout="NHWC"):
    """bn_mode: f32 | bf16 | fixed; stem: conv7 | s2d | pad4;
    pool: max | slice; layout: NHWC | NCHW (the framework path is
    NCHW — this isolates any layout-assignment cost)."""

    dimnums = (layout, "HWIO" if layout == "NHWC" else "OIHW", layout)

    def conv(x, w, stride=1, padding="SAME"):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=dimnums)

    red_ax = (0, 1, 2) if layout == "NHWC" else (0, 2, 3)
    cshape = (1, 1, 1, -1) if layout == "NHWC" else (1, -1, 1, 1)

    def bn_relu(x, scale, bias, act=True):
        if bn_mode == "fixed":
            out = (x * scale.astype(DT).reshape(cshape)
                   + bias.astype(DT).reshape(cshape))
        elif bn_mode == "bf16":
            m = jnp.mean(x, axis=red_ax)
            ex2 = jnp.mean(jnp.square(x), axis=red_ax)
            v = jnp.maximum((ex2 - m * m).astype(jnp.float32), 1e-6)
            inv = jax.lax.rsqrt(v)
            sc = (scale * inv).astype(DT).reshape(cshape)
            sh = (bias - m.astype(jnp.float32) * scale * inv
                  ).astype(DT).reshape(cshape)
            out = x * sc + sh
        else:
            m = jnp.mean(x, axis=red_ax, dtype=jnp.float32)
            ex2 = jnp.mean(jnp.square(x.astype(jnp.float32)),
                           axis=red_ax)
            v = jnp.maximum(ex2 - m * m, 0.0)
            inv = jax.lax.rsqrt(v + 1e-5)
            sc = (scale * inv).astype(DT).reshape(cshape)
            sh = (bias - m * scale * inv).astype(DT).reshape(cshape)
            out = x * sc + sh
        if act and relu:
            out = jnp.maximum(out, 0)
        return out

    def block(x, p, stride, expand):
        y = bn_relu(conv(x, p["w1"]), p["s1"], p["b1"])
        y = bn_relu(conv(y, p["w2"], stride), p["s2"], p["b2"])
        y = bn_relu(conv(y, p["w3"]), p["s3"], p["b3"], act=False)
        if expand:
            sc = bn_relu(conv(x, p["wsc"], stride), p["ssc"], p["bsc"],
                         act=False)
        else:
            sc = x
        return jnp.maximum(y + sc, 0) if relu else y + sc

    def init_params():
        rng = np.random.RandomState(0)

        def W(*s):
            # s given HWIO; transpose 4-D conv weights to OIHW for NCHW
            w = rng.randn(*s) * (1.0 / np.sqrt(np.prod(s[:-1])))
            if layout == "NCHW" and w.ndim == 4:
                w = w.transpose(3, 2, 0, 1)
            return jnp.asarray(w, DT)

        if stem == "s2d":
            stem_w = W(4, 4, 12, 64)
        elif stem == "pad4":
            stem_w = W(7, 7, 4, 64)
        else:
            stem_w = W(7, 7, 3, 64)
        P = {"stem": stem_w, "stem_s": jnp.ones(64),
             "stem_b": jnp.zeros(64), "stages": []}
        cin = 64
        for n, mid, cout, stride in STAGES:
            blocks = []
            for i in range(n):
                p = {"w1": W(1, 1, cin, mid), "s1": jnp.ones(mid),
                     "b1": jnp.zeros(mid),
                     "w2": W(3, 3, mid, mid), "s2": jnp.ones(mid),
                     "b2": jnp.zeros(mid),
                     "w3": W(1, 1, mid, cout), "s3": jnp.ones(cout),
                     "b3": jnp.zeros(cout)}
                if i == 0:
                    p["wsc"] = W(1, 1, cin, cout)
                    p["ssc"] = jnp.ones(cout)
                    p["bsc"] = jnp.zeros(cout)
                blocks.append(p)
                cin = cout
            P["stages"].append(blocks)
        P["fc"] = W(2048, 1000)
        return P

    def forward(P, x):
        if stem == "s2d":
            # space-to-depth(2): (B,224,224,3)->(B,112,112,12), then the
            # exact 7x7/s2 equivalent: 4x4/s1 conv, pad (2,1)
            b, h, w, c = x.shape
            z = x.reshape(b, h // 2, 2, w // 2, 2, c).transpose(
                0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
            y = jax.lax.conv_general_dilated(
                z, P["stem"], (1, 1), [(2, 1), (2, 1)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        elif stem == "pad4":
            x4 = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 1)))
            y = conv(x4, P["stem"], 2)
        else:
            y = conv(x, P["stem"], 2)
        y = bn_relu(y, P["stem_s"], P["stem_b"])
        pwin = (1, 3, 3, 1) if layout == "NHWC" else (1, 1, 3, 3)
        pstr = (1, 2, 2, 1) if layout == "NHWC" else (1, 1, 2, 2)
        if pool == "max":
            y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                      pwin, pstr, "SAME")
        elif layout == "NHWC":
            y = y[:, ::2, ::2, :]
        else:
            y = y[:, :, ::2, ::2]
        for si, (n, mid, cout, stride) in enumerate(STAGES):
            for i in range(n):
                y = block(y, P["stages"][si][i],
                          stride if i == 0 else 1, i == 0)
        y = jnp.mean(y, axis=(1, 2) if layout == "NHWC" else (2, 3))
        return y.astype(jnp.float32) @ P["fc"].astype(jnp.float32)

    def loss_fn(P, x, labels):
        lp = jax.nn.log_softmax(forward(P, x))
        return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=1))

    return init_params, loss_fn


VARIANTS = [
    ("baseline", {}),
    ("bn_bf16_stats", {"bn_mode": "bf16"}),
    ("bn_fixed", {"bn_mode": "fixed"}),
    ("stem_s2d", {"stem": "s2d"}),
    ("stem_pad4", {"stem": "pad4"}),
    ("pool_slice", {"pool": "slice"}),
    ("no_relu", {"relu": False}),
    ("s2d+bf16bn", {"stem": "s2d", "bn_mode": "bf16"}),
    ("nchw", {"layout": "NCHW"}),
    # momentum-SGD optimizer traffic (the framework bench runs momentum
    # 0.9 + f32 masters; the plain variants use bare SGD)
    ("momentum", {"_momentum": True}),
    ("s2d+momentum", {"stem": "s2d", "_momentum": True}),
]


def run_variants(k1, k2, only=None):
    rng = np.random.RandomState(1)
    labels = jnp.asarray(rng.randint(0, 1000, (B,)), jnp.int32)
    x_nhwc = jnp.asarray(rng.randn(B, 224, 224, 3), DT)

    variants = [(n, kw) for n, kw in VARIANTS
                if only is None or n in only]
    print("%-18s %9s %9s" % ("variant", "ms/step", "img/s"))
    results = {}
    for name, kw in variants:
        kw = dict(kw)
        momentum = kw.pop("_momentum", False)
        init_params, loss_fn = make_model(**kw)
        P = init_params()
        x = (jnp.transpose(x_nhwc, (0, 3, 1, 2))
             if kw.get("layout") == "NCHW" else x_nhwc)

        if momentum:
            M = jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, jnp.float32), P)

            def mk(K):
                def loop(PM):
                    def body(carry, _):
                        Pc, Mc = carry
                        l, g = jax.value_and_grad(loss_fn)(Pc, x,
                                                           labels)
                        Mc = jax.tree_util.tree_map(
                            lambda m, gg: 0.9 * m
                            + gg.astype(jnp.float32), Mc, g)
                        Pc = jax.tree_util.tree_map(
                            lambda p, m: p - 1e-9 * m.astype(p.dtype),
                            Pc, Mc)
                        return (Pc, Mc), ()
                    return jax.lax.scan(body, PM, None, length=K)[0]
                return loop

            ms = diff_time(mk, (P, M), k1, k2)
        else:
            def mk(K):
                def loop(P0):
                    def body(Pc, _):
                        l, g = jax.value_and_grad(loss_fn)(Pc, x,
                                                           labels)
                        Pc = jax.tree_util.tree_map(
                            lambda p, gg: p - 1e-9 * gg.astype(p.dtype),
                            Pc, g)
                        return Pc, ()
                    return jax.lax.scan(body, P0, None, length=K)[0]
                return loop

            ms = diff_time(mk, P, k1, k2)
        results[name] = ms
        print("%-18s %9.2f %9.0f" % (name, ms, B / ms * 1e3), flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", action="store_true")
    ap.add_argument("--variants", action="store_true")
    ap.add_argument("--k1", type=int, default=30)
    ap.add_argument("--k2", type=int, default=120)
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated variant names")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None
    if args.variants or not args.layers:
        run_variants(args.k1, args.k2, only=only)
    if args.layers:
        run_layers(args.k1, args.k2)


if __name__ == "__main__":
    main()
