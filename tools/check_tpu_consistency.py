"""CPU-vs-TPU operator consistency sweep on real hardware.

The §4.2 second-backend oracle (reference:
``tests/python/gpu/test_operator_gpu.py`` imports the whole CPU suite +
``check_consistency``), run as a standalone CLI because the pytest tier
pins itself to the 8-device virtual CPU mesh:

    python tools/check_tpu_consistency.py            # needs the chip
    python tools/check_tpu_consistency.py --family nn

Each case runs forward AND input gradients on cpu(0) and tpu(0) and
cross-compares within per-dtype tolerance.  128 cases spanning every
op family (round-2 verdict item #4).  Exit code 0 = all pass.
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# every case here calls each op ~once per context — the eager-jit cache
# would pay a per-op XLA compile for a single use (docs/perf.md "Eager
# dispatch"); the retracing path is faster for one-shot sweeps
os.environ.setdefault("MXNET_EAGER_JIT", "0")

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import check_consistency

RNG = np.random.RandomState(0)


def rand(*shape, scale=1.0, lo=None, hi=None):
    if lo is not None:
        return RNG.uniform(lo, hi, shape).astype("float32")
    return (RNG.randn(*shape) * scale).astype("float32")


def build_cases():
    cases = []

    # --- elementwise unary (one case each; positive-domain where needed)
    UNARY = ["relu", "sigmoid", "tanh", "erf", "softsign", "mish",
             "log_sigmoid", "hard_sigmoid", "sin", "cos", "tan",
             "arcsin", "arccos", "arctan", "sinh", "cosh", "arcsinh",
             "arctanh", "exp", "expm1", "square", "cbrt", "negative",
             "abs", "sign", "floor", "ceil", "round", "rint", "trunc",
             "fix", "logical_not", "degrees", "radians"]
    POS_UNARY = ["log", "log10", "log2", "log1p", "sqrt", "rsqrt",
                 "rcbrt", "reciprocal", "gamma", "gammaln", "digamma"]
    for name in UNARY:
        dom = dict(lo=-0.7, hi=0.7) if name in (
            "arcsin", "arccos", "arctanh") else {}
        cases.append(("u_" + name,
                      (lambda n: lambda x: getattr(nd, n)(x))(name),
                      [rand(4, 6, **dom) if dom else rand(4, 6)]))
    for name in POS_UNARY:
        cases.append(("u_" + name,
                      (lambda n: lambda x: getattr(nd, n)(x))(name),
                      [rand(4, 6, lo=0.4, hi=1.6)]))

    # --- binary / broadcast
    BINARY = ["broadcast_add", "broadcast_sub", "broadcast_mul",
              "broadcast_maximum", "broadcast_minimum",
              "broadcast_hypot", "broadcast_power", "arctan2",
              "elemwise_add", "elemwise_mul", "maximum", "minimum"]
    for name in BINARY:
        shapes = [(3, 4), (3, 1)] if name.startswith("broadcast") \
            else [(3, 4), (3, 4)]
        pos = name in ("broadcast_power",)
        cases.append(("b_" + name,
                      (lambda n: lambda a, b: getattr(nd, n)(a, b))(
                          name),
                      [rand(*shapes[0], lo=0.4, hi=1.6) if pos
                       else rand(*shapes[0]),
                       rand(*shapes[1], lo=0.4, hi=1.6) if pos
                       else rand(*shapes[1])]))
    cases.append(("b_broadcast_div", lambda a, b: nd.broadcast_div(a, b),
                  [rand(3, 4), rand(1, 4, lo=0.5, hi=1.5)]))

    # --- reductions / argsort family
    cases += [
        ("r_sum", lambda x: nd.sum(x, axis=1), [rand(4, 6)]),
        ("r_mean_keep", lambda x: nd.mean(x, axis=0, keepdims=True),
         [rand(4, 6)]),
        ("r_prod", lambda x: nd.prod(x, axis=1),
         [rand(3, 4, lo=0.5, hi=1.5)]),
        ("r_max", lambda x: nd.max(x, axis=1), [rand(4, 6, scale=2)]),
        ("r_min", lambda x: nd.min(x, axis=0), [rand(4, 6, scale=2)]),
        ("r_norm", lambda x: nd.norm(x, axis=1), [rand(4, 6)]),
        ("r_nansum", lambda x: nd.nansum(x, axis=1), [rand(4, 6)]),
        ("r_moments", lambda x: nd.moments(x, axes=(0,))[0],
         [rand(4, 6)]),
        ("r_cumsum", lambda x: nd.cumsum(x, axis=1), [rand(4, 6)]),
        ("r_logsumexp_path",
         lambda x: nd.log(nd.sum(nd.exp(x), axis=-1)), [rand(4, 6)]),
        ("r_softmax", lambda x: nd.softmax(x), [rand(4, 7)]),
        ("r_log_softmax", lambda x: nd.log_softmax(x), [rand(4, 7)]),
        ("r_softmin", lambda x: nd.softmin(x), [rand(4, 7)]),
        ("r_topk_val", lambda x: nd.topk(x, k=3, ret_typ="value",
                                         axis=-1), [rand(5, 12)]),
        ("r_sort", lambda x: nd.sort(x, axis=-1), [rand(5, 12)]),
    ]

    # --- shape / indexing
    cases += [
        ("s_transpose", lambda x: nd.transpose(x, axes=(1, 0, 2)),
         [rand(2, 3, 4)]),
        ("s_reshape", lambda x: nd.reshape(x, shape=(6, 4)),
         [rand(2, 3, 4)]),
        ("s_slice", lambda x: nd.slice(x, begin=(0, 1), end=(3, 4)),
         [rand(3, 4)]),
        ("s_slice_axis", lambda x: nd.slice_axis(x, axis=1, begin=1,
                                                 end=3), [rand(3, 4)]),
        ("s_flip", lambda x: nd.flip(x, axis=1), [rand(3, 4)]),
        ("s_tile", lambda x: nd.tile(x, reps=(2, 2)), [rand(2, 3)]),
        ("s_repeat", lambda x: nd.repeat(x, repeats=2, axis=0),
         [rand(2, 3)]),
        ("s_pad", lambda x: nd.pad(x, mode="constant",
                                   pad_width=(0, 0, 0, 0, 1, 1, 1, 1)),
         [rand(1, 1, 3, 3)]),
        ("s_expand_swap",
         lambda x: nd.swapaxes(nd.expand_dims(x, axis=0), 0, 1),
         [rand(3, 4)]),
        ("s_depth_to_space", lambda x: nd.depth_to_space(x,
                                                         block_size=2),
         [rand(1, 4, 3, 3)]),
        ("s_one_hot_path", lambda x: nd.dot(
            nd.one_hot(nd.argmax(x, axis=1), depth=4), x),
         [rand(4, 4)]),
        ("s_take", lambda x: nd.take(
            x, nd.array(np.array([0., 2.]), ctx=x.context)),
         [rand(4, 5)]),
        ("s_gather_nd", lambda x: nd.gather_nd(
            x, nd.array(np.array([[0, 1], [1, 2]], "int32"),
                        ctx=x.context)), [rand(3, 4)]),
        ("s_where", lambda x, y: nd.where(
            nd.array((np.arange(12).reshape(3, 4) % 2)
                     .astype("float32"), ctx=x.context), x, y),
         [rand(3, 4), rand(3, 4)]),
        ("s_concat", lambda a, b: nd.Concat(a, b, dim=1),
         [rand(3, 2), rand(3, 3)]),
        ("s_stack", lambda a, b: nd.stack(a, b, axis=1),
         [rand(3, 4), rand(3, 4)]),
        ("s_split_sq",
         lambda x: nd.split(x, num_outputs=2, axis=1)[0], [rand(4, 6)]),
        ("s_clip", lambda x: nd.clip(x, a_min=-0.5, a_max=0.5),
         [rand(3, 4, scale=2)]),
    ]

    # --- nn
    cases += [
        ("nn_dense_gelu", lambda x, w: nd.LeakyReLU(
            nd.FullyConnected(x, w, num_hidden=32, no_bias=True),
            act_type="gelu"), [rand(8, 16), rand(32, 16)]),
        ("nn_conv_bn_relu", lambda x, w: nd.relu(
            nd.Convolution(x, w, kernel=(3, 3), pad=(1, 1),
                           num_filter=8, no_bias=True)),
         [rand(2, 4, 12, 12), rand(8, 4, 3, 3)]),
        ("nn_conv_stride", lambda x, w: nd.Convolution(
            x, w, kernel=(3, 3), stride=(2, 2), num_filter=4,
            no_bias=True), [rand(1, 3, 9, 9), rand(4, 3, 3, 3)]),
        ("nn_deconv", lambda x, w: nd.Deconvolution(
            x, w, kernel=(2, 2), stride=(2, 2), num_filter=4,
            no_bias=True), [rand(1, 3, 4, 4), rand(3, 4, 2, 2)]),
        ("nn_depthwise", lambda x, w: nd.Convolution(
            x, w, kernel=(3, 3), pad=(1, 1), num_filter=4, num_group=4,
            no_bias=True), [rand(1, 4, 6, 6), rand(4, 1, 3, 3)]),
        ("nn_pool_max", lambda x: nd.Pooling(
            x, kernel=(2, 2), stride=(2, 2), pool_type="max"),
         [rand(2, 3, 8, 8)]),
        ("nn_pool_avg_incl", lambda x: nd.Pooling(
            x, kernel=(3, 3), stride=(2, 2), pool_type="avg"),
         [rand(2, 3, 9, 9)]),
        ("nn_pool_global", lambda x: nd.Pooling(
            x, kernel=(1, 1), global_pool=True, pool_type="avg"),
         [rand(2, 3, 5, 5)]),
        ("nn_layernorm", lambda x, g, b: nd.LayerNorm(x, g, b),
         [rand(4, 24), np.ones(24, "float32"),
          np.zeros(24, "float32")]),
        ("nn_groupnorm", lambda x, g, b: nd.GroupNorm(
            x, g, b, num_groups=2),
         [rand(2, 4, 5, 5), np.ones(4, "float32"),
          np.zeros(4, "float32")]),
        ("nn_instancenorm", lambda x, g, b: nd.InstanceNorm(x, g, b),
         [rand(2, 3, 5, 5), np.ones(3, "float32"),
          np.zeros(3, "float32")]),
        ("nn_l2norm", lambda x: nd.L2Normalization(x), [rand(4, 8)]),
        ("nn_lrn", lambda x: nd.LRN(x, nsize=3), [rand(1, 5, 4, 4)]),
        ("nn_embed", lambda w: nd.Embedding(
            nd.array(np.array([[1, 3], [0, 2]], "float32"),
                     ctx=w.context), w, input_dim=8, output_dim=5),
         [rand(8, 5)]),
        ("nn_smooth_l1", lambda x: nd.smooth_l1(x, scalar=1.0),
         [rand(4, 5, scale=2)]),
        ("nn_seq_mask", lambda x: nd.SequenceMask(
            x, nd.array(np.array([2., 3.]), ctx=x.context),
            use_sequence_length=True), [rand(4, 2, 3)]),
        ("nn_dense_bias", lambda x, w, b: nd.FullyConnected(
            x, w, b, num_hidden=6), [rand(3, 5), rand(6, 5), rand(6)]),
        ("nn_prelu", lambda x, a: nd.LeakyReLU(
            x, a, act_type="prelu"), [rand(3, 4), rand(4, lo=0.1,
                                                       hi=0.3)]),
    ]

    # --- linalg
    def spd(n=4):
        m = RNG.randn(n, n).astype("float32")
        return m @ m.T + n * np.eye(n, dtype="float32")

    tril = np.tril(RNG.uniform(0.5, 1.5, (4, 4))).astype("float32")
    cases += [
        ("la_dot", lambda a, b: nd.dot(a, b),
         [rand(4, 5), rand(5, 6)]),
        ("la_dot_t", lambda a, b: nd.dot(a, b, transpose_a=True),
         [rand(5, 4), rand(5, 6)]),
        ("la_batch_dot_t", lambda a, b: nd.batch_dot(a, b,
                                                     transpose_b=True),
         [rand(3, 5, 7), rand(3, 6, 7)]),
        ("la_gemm2", lambda a, b: nd.linalg_gemm2(a, b),
         [rand(3, 4), rand(4, 5)]),
        ("la_potrf", lambda a: nd.linalg_potrf(a), [spd()]),
        ("la_trmm", lambda b: nd.linalg_trmm(
            nd.array(tril, ctx=b.context), b), [rand(4, 4)]),
        ("la_sumlogdiag", lambda a: nd.linalg_sumlogdiag(a),
         [spd()]),
        ("la_det", lambda a: nd.linalg_det(a), [spd()]),
        ("la_syrk", lambda a: nd.linalg_syrk(a), [rand(3, 4)]),
        ("la_diag", lambda x: nd.diag(x), [rand(4, 4)]),
    ]

    # --- vision / detection
    cases += [
        ("v_roialign", lambda x: nd.contrib.ROIAlign(
            x, nd.array(np.array([[0, 1.0, 1.0, 7.0, 7.0]], "float32"),
                        ctx=x.context),
            pooled_size=(2, 2), spatial_scale=1.0),
         [rand(1, 3, 10, 10)]),
        ("v_bilinear_resize", lambda x: nd.contrib.BilinearResize2D(
            x, height=6, width=6), [rand(1, 2, 4, 4)]),
        ("v_adaptive_pool", lambda x: nd.contrib.AdaptiveAvgPooling2D(
            x, output_size=(2, 2)), [rand(1, 2, 6, 6)]),
        ("v_deform_conv", lambda x, w: nd.DeformableConvolution(
            x, nd.array(np.full((1, 8, 4, 4), 0.3, "float32"),
                        ctx=x.context), w,
            nd.array(np.zeros(3, "float32"), ctx=x.context),
            kernel=(2, 2), num_filter=3),
         [rand(1, 2, 5, 5), rand(3, 2, 2, 2)]),
        # grid drawn ONCE here: a lambda that consumes RNG per call
        # would hand each context a different grid
        ("v_grid_sample",
         (lambda grid: lambda x: nd.BilinearSampler(
             x, nd.array(grid, ctx=x.context)))(
                 RNG.uniform(-0.8, 0.8, (1, 2, 4, 4))
                 .astype("float32")),
         [rand(1, 2, 5, 5)]),
        ("v_interleaved_qk",
         lambda q: nd.contrib.interleaved_matmul_selfatt_qk(q, heads=2),
         [rand(4, 2, 2 * 3 * 8)]),
    ]

    # --- fused optimizer-style composites (fwd only via grad=False is
    # not supported by check_consistency; use differentiable proxies)
    cases += [
        ("o_adam_math", lambda w, g, m, v: w - 0.01 * (
            (0.9 * m + 0.1 * g) / (nd.sqrt(0.999 * v + 0.001 *
                                           nd.square(g)) + 1e-8)),
         [rand(6), rand(6), rand(6), rand(6, lo=0.1, hi=0.5)]),
        ("o_lars_math", lambda w, g: w * nd.norm(w) /
         (nd.norm(g) + 1e-6), [rand(8), rand(8)]),
        ("o_clip_global", lambda g1, g2: g1 * nd.minimum(
            nd.ones((1,), ctx=g1.context),
            1.0 / nd.sqrt(nd.sum(nd.square(g1)) +
                          nd.sum(nd.square(g2)) + 1e-12)),
         [rand(5), rand(7)]),
    ]
    return cases


def build_sweep_cases():
    """Auto-generate consistency cases from the registry sweep's own
    case builders (round-3 verdict #6): every op the CPU sweep
    grad/fwd-checks gets a cpu-vs-tpu comparison with the same inputs
    and attrs, so the hard families (conv/pool/norm/linalg/quantized/
    reduce) are sampled exactly as broadly as the sweep itself."""
    import json
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests"))
    import test_registry_sweep as sweep

    record_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "op_sweep_record.json")
    with open(record_path) as f:
        rec = json.load(f)["ops"]

    def first_out(out):
        return out[0] if isinstance(out, (tuple, list)) else out

    cases = []
    dropped = []
    # cross-backend comparison is ILL-POSED for these (documented, not
    # silent): eigen/singular vectors are sign- and degenerate-order-
    # indeterminate between backends; the CPU sweep's reconstruction-
    # style checks cover their correctness instead
    SIGN_AMBIGUOUS = {"_linalg_syevd": "eigenvector sign/order is "
                                       "backend-indeterminate",
                      "_np_linalg_svd": "singular-vector signs are "
                                        "backend-indeterminate"}
    for name in sorted(rec):
        r = rec[name]
        if r.get("status") != "pass":
            continue
        if name in SIGN_AMBIGUOUS:
            dropped.append((name, SIGN_AMBIGUOUS[name]))
            continue
        grad = r.get("mode") == "grad"
        try:
            if name in sweep.SPECS:
                mode, builder = sweep.SPECS[name]
                if mode == "gradf":
                    # gradf builders close over ctx-PINNED constant
                    # NDArrays — running the closure on the tpu context
                    # mixes committed devices; these ops are covered by
                    # the hand-written per-family cases instead
                    dropped.append((name, "gradf closure (ctx-pinned "
                                          "constants)"))
                    continue
                else:
                    nd_inputs, kwargs = builder()
                    fn = (lambda _n, _k: lambda *xs: first_out(
                        sweep.call(_n, *xs, **_k)))(name, kwargs)
            else:
                nd_inputs = sweep._auto_case(name)
                if nd_inputs is None:
                    dropped.append((name, "no auto pattern"))
                    continue
                fn = (lambda _n: lambda *xs: first_out(
                    sweep.call(_n, *xs)))(name)
        except Exception as e:  # noqa: BLE001 — builder broke
            dropped.append((name, "builder: %s" % str(e)[:80]))
            continue
        inputs = [x.asnumpy() if hasattr(x, "asnumpy") else
                  np.asarray(x) for x in nd_inputs]
        cases.append(("sw_" + name, fn, inputs, grad))
    if dropped:
        print("sweep cases dropped (%d):" % len(dropped))
        for n, why in dropped:
            print("  drop %s: %s" % (n, why))
    return cases


def _write_record(path, n_cases, record, failed, errored):
    """Incremental per-case record (a partial record beats none if the
    run is cut short)."""
    if not path:
        return
    import json
    done = len(record)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"summary": {"cases": n_cases, "completed": done,
                               "pass": done - len(failed) - len(errored),
                               "fail": len(failed),
                               "harness_error": len(errored)},
                   "cases": record}, f, indent=1, sort_keys=True)
    os.replace(tmp, path)      # atomic: a cut-short run keeps the last
                               # complete record instead of a torn file


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default=None,
                    help="prefix filter (u_, b_, r_, s_, nn_, la_, v_, "
                         "o_, sw_)")
    ap.add_argument("--max-cases", type=int, default=0)
    ap.add_argument("--no-sweep", action="store_true",
                    help="only the hand-written cases (round-2 set)")
    ap.add_argument("--record", default=None,
                    help="write the per-case JSON record here")
    args = ap.parse_args()

    mx.context.require_tpu("check_tpu_consistency.py")
    cases = [(n, f, i, True) for (n, f, i) in build_cases()]
    if not args.no_sweep:
        cases += build_sweep_cases()
    if args.family:
        prefixes = tuple(args.family.split(","))
        cases = [c for c in cases if c[0].startswith(prefixes)]
    if args.max_cases:
        cases = cases[:args.max_cases]
    total_cases = len(cases)

    failed = []
    errored = []
    record = {}
    for name, fn, inputs, grad in cases:
        try:
            # rtol 2e-3: TPU evaluates transcendentals (log/exp
            # family, gammaln, ...) with its own polynomial
            # approximations — observed cpu-vs-tpu forward deltas are
            # ~1.5e-4 relative and composed-transcendental GRADIENTS
            # (mish) reach ~1.3e-3 — the same reason the reference's
            # check_consistency grants GPU contexts looser f32
            # tolerances than CPU
            check_consistency(fn, inputs, grad=grad, rtol=2e-3,
                              atol=1e-5)
            record[name] = {"status": "pass",
                            "mode": "grad" if grad else "fwd"}
            print("ok  %s" % name, flush=True)
        except AssertionError as e:
            failed.append(name)
            record[name] = {"status": "FAIL", "error": str(e)[:200]}
            print("FAIL %s: %s" % (name, str(e)[:200]), flush=True)
        except Exception as e:  # noqa: BLE001 — classify below
            # harness limitation (int-typed inputs the f32 harness
            # can't re-place, etc.) ONLY if the same case also fails
            # on the CPU-only context — a TPU-side-only crash is a
            # real inconsistency and must fail the gate
            from mxnet_tpu.context import cpu as _cpu
            try:
                check_consistency(fn, inputs, ctx_list=[_cpu()],
                                  grad=grad, rtol=2e-3, atol=1e-5)
                cpu_ok = True
            except Exception:
                cpu_ok = False
            if cpu_ok:
                failed.append(name)
                record[name] = {"status": "FAIL",
                                "error": "tpu-only crash: %s"
                                         % str(e)[:200]}
                print("FAIL %s (tpu-only): %s"
                      % (name, str(e)[:150]), flush=True)
            else:
                errored.append(name)
                record[name] = {"status": "error",
                                "error": str(e)[:200]}
                print("err %s: %s" % (name, str(e)[:120]), flush=True)
        if args.record and len(record) % 25 == 0:
            _write_record(args.record, total_cases, record, failed,
                          errored)
    n_pass = len(record) - len(failed) - len(errored)
    print("%d/%d consistent (%d FAIL, %d harness-errored)"
          % (n_pass, len(record), len(failed), len(errored)))
    _write_record(args.record, total_cases, record, failed, errored)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
