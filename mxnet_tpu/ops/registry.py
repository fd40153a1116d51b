"""Operator registry — the TPU-native analog of the nnvm op registry.

Reference: ``NNVM_REGISTER_OP`` + per-op attrs ``FCompute<xpu>`` /
``FInferShape`` / ``FGradient`` (SURVEY.md §2.1 "Operator library", §1
invariant "op registration is data in the nnvm registry; Python op functions
are generated at import time by walking the registry").

TPU-native design decisions:

* An op's *kernel* is a pure JAX function ``impl(*arrays, **attrs)`` that
  lowers to XLA HLO.  There is no per-backend FCompute pair — XLA is the
  backend, and the same impl serves eager mode, ``jax.vjp`` (autograd,
  replacing per-op ``FGradient``), and ``jax.jit`` (CachedOp/hybridize).
* Shape/type inference (``FInferShape``/``FInferType``) comes for free from
  ``jax.eval_shape`` over the same impl — one source of truth, no separate
  inference functions to keep in sync.
* Python stubs for both the ``nd`` and ``sym`` namespaces are generated by
  walking this registry at import time, exactly like the reference's
  ``python/mxnet/ndarray/register.py`` / ``symbol/register.py``.
* Ops that mutate inputs (optimizer ``*_update``, BatchNorm running stats)
  declare ``mutate`` index lists; the invoke layer performs the
  buffer-swap that stands in for in-place mutation on immutable XLA arrays
  (SURVEY.md §7 hard-part #1).
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError

__all__ = ["OpDef", "register", "get_op", "list_ops", "invoke", "invoke_impl"]

_OPS: Dict[str, "OpDef"] = {}

# set by contrib.amp.init(); callable (op, arrays) -> arrays
_CAST_HOOK = None


def set_cast_hook(hook):
    global _CAST_HOOK
    _CAST_HOOK = hook

import threading as _threading

_RESOLVE_STATE = _threading.local()


def in_shape_resolve() -> bool:
    """True while a shape-only resolve pass runs (deferred-init inference).
    Mutation writeback and out= are suppressed so abstract tracers never
    leak into parameter buffers."""
    return getattr(_RESOLVE_STATE, "active", 0) > 0


class shape_resolve_scope:
    def __enter__(self):
        _RESOLVE_STATE.active = getattr(_RESOLVE_STATE, "active", 0) + 1

    def __exit__(self, *a):
        _RESOLVE_STATE.active -= 1


class OpDef:
    """A registered operator.

    Attributes
    ----------
    name : canonical MXNet-compatible op name (e.g. ``FullyConnected``).
    impl : pure JAX function ``impl(*arrays, **attrs)`` → array | tuple.
    num_outputs : static output count, or -1 if attr-dependent.
    mutate : indices of inputs mutated in place.  The impl must return the
        new values of mutated inputs *appended* to its normal outputs.
    no_grad : outputs are non-differentiable (int outputs etc.); the tape
        skips recording.  May be a callable(attrs) -> bool for ops whose
        differentiability depends on attrs (e.g. topk ret_typ).
    variadic : first argument is a list of arrays.
    """

    __slots__ = ("name", "impl", "num_outputs", "mutate", "no_grad",
                 "variadic", "aliases", "doc", "needs_rng",
                 "training_aware", "cacheable")

    def __init__(self, name, impl, num_outputs=1, mutate=(), no_grad=False,
                 variadic=False, aliases=(), doc="", needs_rng=False,
                 training_aware=False, cacheable=False):
        self.name = name
        self.impl = impl
        self.num_outputs = num_outputs
        # mutate may be a callable(attrs) -> indices for variadic ops
        # (e.g. multi_sgd_mom_update's momentum buffers)
        self.mutate = mutate if callable(mutate) else tuple(mutate)
        self.no_grad = no_grad
        self.variadic = variadic
        self.aliases = tuple(aliases)
        self.doc = doc or (impl.__doc__ or "")
        self.needs_rng = needs_rng
        self.training_aware = training_aware
        # eager-jit cache eligibility: ONLY process-lifetime OpDefs (the
        # registry's, or stable module-level ones) may set this — the
        # cache keys on id(op), and an ephemeral per-call OpDef's id can
        # be reused by a later allocation, poisoning the cache
        self.cacheable = cacheable

    def __repr__(self):
        return "OpDef(%s)" % self.name


def register(name: Optional[str] = None, aliases: Sequence[str] = (),
             num_outputs: int = 1, mutate: Sequence[int] = (),
             no_grad: bool = False, variadic: bool = False,
             needs_rng: bool = False, training_aware: bool = False):
    """Register a JAX impl as a framework op.  Returns the impl unchanged so
    op modules remain plain importable JAX code."""
    def _dec(fn: Callable):
        opname = name or fn.__name__
        op = OpDef(opname, fn, num_outputs=num_outputs, mutate=mutate,
                   no_grad=no_grad, variadic=variadic, aliases=aliases,
                   needs_rng=needs_rng, training_aware=training_aware,
                   cacheable=True)
        if opname in _OPS:
            raise MXNetError("Duplicate op registration: %s" % opname)
        _OPS[opname] = op
        for a in aliases:
            if a in _OPS:
                raise MXNetError("Duplicate op alias: %s" % a)
            _OPS[a] = op
        return fn
    return _dec


def get_op(name: str) -> OpDef:
    if name not in _OPS:
        raise MXNetError("Operator %r not registered (have %d ops)"
                         % (name, len(set(_OPS.values()))))
    return _OPS[name]


def list_ops() -> List[str]:
    return sorted(_OPS)


def op_exists(name: str) -> bool:
    return name in _OPS


# ---------------------------------------------------------------------------
# Imperative invoke — the §3.1 hot path.
# ---------------------------------------------------------------------------

# MXU-bound ops where float32 inputs must compute in true f32: without
# the precision pin, TPU matmuls run f32 through bf16 passes (~2e-3
# relative error — fails the §4.2 cpu-vs-tpu consistency oracle).
# bf16/f16 inputs (the AMP path) stay native single-pass.
_F32_MATMUL_OPS = frozenset((
    "dot", "batch_dot", "FullyConnected", "Convolution",
    "Deconvolution", "_npi_matmul", "khatri_rao",
    "_linalg_gemm", "_linalg_gemm2", "_linalg_trmm", "_linalg_trsm",
    "_linalg_syrk", "_linalg_potri",
    "_contrib_DeformableConvolution",
    "_contrib_ModulatedDeformableConvolution",
    "_contrib_interleaved_matmul_selfatt_qk",
    "_contrib_interleaved_matmul_selfatt_valatt",
    "_contrib_interleaved_matmul_encdec_qk",
    "_contrib_interleaved_matmul_encdec_valatt",
    # affine-grid matmuls: unpinned f32 runs bf16 passes on TPU and the
    # grid coordinates lose ~6e-3 abs (fails the §4.2 oracle at 2e-3)
    "GridGenerator", "SpatialTransformer",
    # oracle round-3 findings: these lower to dots/convs internally
    # (im2col/col2im extract patches via conv_general_dilated_patches
    # = conv with identity kernels) and drifted just past the 2e-3
    # cpu-vs-tpu gate through bf16 passes
    "_np_convolve", "_np_correlate", "_np_cov", "_linalg_gelqf",
    "_np_inner", "_np_linalg_matrix_power", "_np_matmul",
    "_np_linalg_qr", "col2im", "im2col",
))


def _all_floats_are_f32(arrays):
    import jax.numpy as jnp
    saw_float = False
    for a in arrays:
        dt = getattr(a, "dtype", None)
        if dt is None:
            continue
        if jnp.issubdtype(dt, jnp.floating):
            saw_float = True
            if dt != jnp.float32:
                return False
    return saw_float


def invoke_impl(op: OpDef, arrays: Sequence[Any], pos_attrs: Tuple,
                attrs: Dict[str, Any]):
    """Run the raw JAX impl.  Split out so autograd can re-enter it.

    The AMP cast hook (contrib.amp) applies here — the single choke point
    through which eager ``nd``, autograd replay, ``hybridize()`` traces AND
    the symbolic executor all pass (reference: amp.init patches both the
    nd and sym generated namespaces)."""
    if _CAST_HOOK is not None:
        arrays = _CAST_HOOK(op, arrays)
    if op.name in _F32_MATMUL_OPS and _all_floats_are_f32(arrays):
        import jax
        ctx = jax.default_matmul_precision("highest")
    else:
        import contextlib
        ctx = contextlib.nullcontext()
    with ctx:
        if _EAGER_JIT:
            handled, result = _eager_jit_call(op, arrays, pos_attrs,
                                              attrs)
            if handled:
                return result
        if op.variadic:
            return op.impl(list(arrays), *pos_attrs, **attrs)
        return op.impl(*arrays, *pos_attrs, **attrs)


# ---------------------------------------------------------------------------
# Eager fast path — per-op compiled-callable cache.
#
# Reference: the Cython/FFI fast path (``python/mxnet/_ctypes/``,
# ``python/mxnet/cython/``, ``src/api/`` — SURVEY.md §2.1 last row), which
# cuts per-op Python→C dispatch to ~2µs.  The TPU-native analog of the
# overhead is JAX *retracing* the impl on every eager call (~50–70µs of the
# measured 84µs median, docs/perf.md "Eager dispatch").  Caching one
# ``jax.jit``-compiled callable per (op, arity, static attrs) removes the
# retrace: repeat calls ride jit's C++ dispatch fast path, which itself
# keys on input shapes/dtypes/shardings and config state (x64 scope,
# default_device, matmul precision), so those need no handling here.
# Disable with ``MXNET_EAGER_JIT=0`` (docs/env_vars.md).
# ---------------------------------------------------------------------------

import collections as _collections
import os as _os

import numpy as _np

_EAGER_JIT = _os.environ.get("MXNET_EAGER_JIT", "1").lower() \
    not in ("0", "false", "off")
_EAGER_CACHE: "dict" = _collections.OrderedDict()
_EAGER_CACHE_MAX = int(_os.environ.get("MXNET_EAGER_JIT_CACHE_SIZE",
                                       "8192"))
# ops whose impls cannot trace (concrete-value inspection, dynamic output
# shapes, python callbacks): first build failure pins them to the direct
# eager path for the rest of the process
_EAGER_BLACKLIST = set()
# ops whose jitted form compiled but failed at runtime while the direct
# path succeeded; two strikes → blacklist (see _eager_jit_call)
_RUNTIME_STRIKES: Dict[str, int] = {}
# (op, event) pairs already logged — the fallback machinery recovers
# SILENTLY from the caller's perspective, so each distinct slowdown is
# announced exactly once (round-10 satellite: an op quietly pinned to
# the retrace-per-call path was undiagnosable without this)
_EAGER_LOGGED: set = set()


def _log_once(name: str, event: str, msg: str):
    if (name, event) in _EAGER_LOGGED:
        return
    _EAGER_LOGGED.add((name, event))
    import logging
    logging.getLogger(__name__).warning(msg)
# concrete jax.Array implementation type (NOT Tracer — tracer inputs mean
# a hybridize()/vjp/eval_shape re-entry and must bypass the cache);
# resolved lazily so importing this module never touches the backend
_ArrayImpl = None

# attr names whose (float or tuple-of-float) values are passed as traced
# jit arguments instead of baked-in statics: the learning rate changes
# every step under lr schedules, and baking it in would retrace each
# *_update op per step.  Other optimizer floats (wd, clip_gradient,
# momentum) gate python branches inside the impls and stay static.
_DYN_ATTR_NAMES = ("lr", "lrs")


def _freeze(v):
    if type(v) in (list, tuple):
        return tuple(_freeze(x) for x in v)
    if type(v) is dict:
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


def _is_floats(v):
    t = type(v)
    if t is float:
        return True
    if t in (tuple, list):
        return bool(v) and all(type(x) is float for x in v)
    return False


def _eager_jit_call(op, arrays, pos_attrs, attrs):
    """Try the compiled-callable cache; returns (handled, result).

    ``handled=False`` → caller runs the direct (retracing) path: tracer
    or non-array inputs, unhashable attrs, or a blacklisted op."""
    if not op.cacheable:
        return False, None
    name = op.name
    if name in _EAGER_BLACKLIST:
        return False, None
    global _ArrayImpl
    if _ArrayImpl is None:
        from jax._src.array import ArrayImpl as _AI
        _ArrayImpl = _AI
    AI = _ArrayImpl
    nda = _np.ndarray
    for a in arrays:
        t = type(a)
        if t is not AI and t is not nda:
            return False, None

    all_attrs = attrs
    dyn_attrs = None
    if attrs:
        for k in _DYN_ATTR_NAMES:
            if k in attrs and _is_floats(attrs[k]):
                if dyn_attrs is None:
                    attrs = dict(attrs)
                    dyn_attrs = {}
                v = attrs.pop(k)
                dyn_attrs[k] = tuple(v) if type(v) is list else v
        key = (id(op), len(arrays), _freeze(pos_attrs),
               _freeze(attrs),
               None if dyn_attrs is None else tuple(dyn_attrs))
    else:
        key = (id(op), len(arrays), _freeze(pos_attrs)) \
            if pos_attrs else (id(op), len(arrays))
    try:
        entry = _EAGER_CACHE.get(key)
    except TypeError:
        # unhashable attr value (raw np array etc.) → direct path
        return False, None
    if entry is not None:
        _EAGER_CACHE.move_to_end(key)   # true LRU, not FIFO

    fresh = entry is None
    if fresh:
        import jax
        impl = op.impl
        if op.variadic:
            if dyn_attrs is not None:
                def fn(arrs, dv):
                    return impl(list(arrs), *pos_attrs, **attrs, **dv)
            else:
                def fn(*arrs):
                    return impl(list(arrs), *pos_attrs, **attrs)
        elif dyn_attrs is not None:
            def fn(arrs, dv):
                return impl(*arrs, *pos_attrs, **attrs, **dv)
        else:
            def fn(*arrs):
                return impl(*arrs, *pos_attrs, **attrs)
        entry = (jax.jit(fn), dyn_attrs is not None)
        _EAGER_CACHE[key] = entry
        if len(_EAGER_CACHE) > _EAGER_CACHE_MAX:
            _EAGER_CACHE.popitem(last=False)
    jfn, takes_dyn = entry
    try:
        if takes_dyn:
            result = jfn(tuple(arrays), dyn_attrs)
        else:
            result = jfn(*arrays)
        if _RUNTIME_STRIKES:
            # a successful jit dispatch clears earlier transient-
            # runtime strikes — two transients days apart must not
            # add up to a permanent blacklist
            _RUNTIME_STRIKES.pop(name, None)
        return True, result
    except Exception:
        if fresh:
            # the first call failed — but that does NOT yet mean the
            # impl is jit-unsafe: a plain user error (wrong arity,
            # shape, dtype) raises here too, and permanently
            # blacklisting on it would pin a perfectly cacheable op to
            # the retrace-per-call path for the whole process (the
            # round-4 dispatch-tail analysis found 110 ops poisoned
            # this way by callers probing arities).  Disambiguate in
            # two steps.  First, re-attempt lowering AND compilation
            # (lower() alone would mask persistent XLA compile
            # failures as runtime ones): if both succeed the failure
            # was a RUNTIME one (e.g. a transient device OOM) —
            # re-raise and leave the op cacheable so a later jit
            # attempt can succeed.
            _EAGER_CACHE.pop(key, None)
            try:
                if takes_dyn:
                    jfn.lower(tuple(arrays), dyn_attrs).compile()
                else:
                    jfn.lower(*arrays).compile()
            except Exception:
                pass
            else:
                # Lowering+compile succeeded → the failure was at
                # RUNTIME.  Usually transient (device OOM) — but an op
                # that compiles yet deterministically fails compiled
                # execution (e.g. a pure_callback whose
                # host work only errors under jit) would be permanently
                # unusable if we always re-raised.  Try the direct path:
                # if it also fails, raise; if it succeeds, return the
                # result and blacklist after a second such recovery
                # (one strike spares transients the permanent pin).
                if op.variadic:
                    result = op.impl(list(arrays), *pos_attrs,
                                     **all_attrs)
                else:
                    result = op.impl(*arrays, *pos_attrs, **all_attrs)
                strikes = _RUNTIME_STRIKES.get(name, 0) + 1
                _RUNTIME_STRIKES[name] = strikes
                _log_once(
                    name, "recovered",
                    "op %r: compiled execution failed but the direct "
                    "eager path succeeded (strike %d/2) — this call "
                    "ran un-jitted" % (name, strikes))
                if strikes >= 2:
                    _EAGER_BLACKLIST.add(name)
                    _log_once(
                        name, "blacklisted",
                        "op %r: two compiled-execution failures "
                        "recovered via the direct path — pinned to "
                        "the retrace-per-call eager path for this "
                        "process" % (name,))
                return True, result
            # Lowering failed too → a trace-time error.  Run the
            # direct path: if it ALSO fails, the error is the
            # caller's — re-raise it and leave the op cacheable; if
            # it succeeds, the impl genuinely cannot trace →
            # blacklist and return the direct result.  (An impl with
            # host side effects runs them during the failed trace and
            # again here — the price of disambiguation, once per op.)
            if op.variadic:
                result = op.impl(list(arrays), *pos_attrs, **all_attrs)
            else:
                result = op.impl(*arrays, *pos_attrs, **all_attrs)
            _EAGER_BLACKLIST.add(name)
            _log_once(
                name, "blacklisted",
                "op %r: impl cannot trace (direct path succeeded "
                "where trace+compile failed) — pinned to the "
                "retrace-per-call eager path for this process"
                % (name,))
            return True, result
        raise


_SIG_CACHE: Dict[int, List[str]] = {}


def _bind_pos_attrs(op: "OpDef", n_arrays: int, pos_attrs: Tuple,
                    attrs: Dict[str, Any]) -> Dict[str, Any]:
    """attrs + positional attrs bound to impl parameter names.  Only
    called for ops with callable (attr-dependent) mutate/no_grad, so
    the signature inspection stays off the per-op hot path; the name
    list is cached per OpDef."""
    if not pos_attrs:
        return attrs
    key = id(op)
    names = _SIG_CACHE.get(key)
    if names is None:
        try:
            params = inspect.signature(op.impl).parameters.values()
            names = [p.name for p in params
                     if p.kind in (p.POSITIONAL_OR_KEYWORD,
                                   p.POSITIONAL_ONLY)]
        except (TypeError, ValueError):
            names = []
        _SIG_CACHE[key] = names
    # variadic impls take the array LIST as one leading parameter
    base = 1 if op.variadic else n_arrays
    merged = dict(attrs)
    for i, v in enumerate(pos_attrs):
        j = base + i
        if j < len(names):
            merged.setdefault(names[j], v)
    return merged


# invoke's collaborators live in modules that import THIS module, so they
# cannot be imported at module scope; resolving them through sys.modules on
# every call cost ~0.9 µs/op (round-6 line profile, docs/perf.md "Eager
# dispatch").  One lazy resolve caches the four bindings for the process.
_INVOKE_DEPS = None


def _resolve_invoke_deps():
    global _INVOKE_DEPS
    from ..ndarray.ndarray import NDArray, _wrap
    from .. import autograd
    from ..engine import Engine
    _INVOKE_DEPS = (NDArray, _wrap, autograd, Engine)
    return _INVOKE_DEPS


def invoke(op: OpDef, inputs: Sequence, pos_attrs: Tuple = (),
           attrs: Optional[Dict[str, Any]] = None, out=None, ctx=None):
    """Imperative operator invocation (reference: ``Imperative::Invoke``,
    ``src/imperative/imperative.cc`` — SURVEY.md §3.1).

    ``inputs`` are NDArrays; returns NDArray or tuple of NDArrays.  Handles:
    dispatch through the engine (async semantics / NaiveEngine sync mode),
    autograd tape recording, ``out=`` buffer-swap, and input mutation.

    ``attrs`` is NOT copied: every caller (the generated nd stubs, the
    NDArray methods, the C train API) builds a fresh dict per call, and
    the round-6 line profile priced the defensive copy into the per-op
    overhead (see docs/perf.md "Eager dispatch", round-6 pass).
    """
    deps = _INVOKE_DEPS
    if deps is None:
        deps = _resolve_invoke_deps()
    NDArray, _wrap, autograd, Engine = deps

    if attrs is None:
        attrs = {}
    if op.training_aware and "_training" not in attrs:
        attrs = dict(attrs)   # insertion → private copy
        attrs["_training"] = autograd.is_training()
    if op.needs_rng:
        # RNG key is threaded as an explicit (tape-visible) input so that
        # autograd replay reproduces the sample and CachedOp traces thread a
        # per-call key (see mxnet_tpu/random.py).
        from .. import random as _random
        inputs = [_wrap(_random.next_key())] + list(inputs)

    arrays = [i._data if isinstance(i, NDArray) else i for i in inputs]

    eng = Engine.get()

    if ctx is not None:
        import jax
        def run():
            with jax.default_device(ctx.jax_device):
                return invoke_impl(op, arrays, pos_attrs, attrs)
        results = eng.push(run, op.name)
    elif eng._op_hooks or eng.synchronous:
        results = eng.push(
            lambda: invoke_impl(op, arrays, pos_attrs, attrs), op.name)
    else:
        # fast lane: no profiler hooks, async engine — skip the closure
        # + push bookkeeping (µs-scale dispatch matters here; reference:
        # the ctypes→cython fast-path motivation, SURVEY §2.1)
        results = invoke_impl(op, arrays, pos_attrs, attrs)
        eng.note(results)

    # fast tail (round-6 line profile): the overwhelmingly common eager
    # shape — single result, no mutation, no out=, not recording — skips
    # the multi/mutate/record bookkeeping below (the pass as a whole
    # took the invoke-layer median 7.0 → 3.6 µs/op, docs/perf.md)
    if (out is None and type(results) is not tuple
            and type(results) is not list and not op.mutate):
        no_grad = op.no_grad
        if not callable(no_grad) and (
                no_grad or not autograd.is_recording()):
            return _wrap(results)

    multi = isinstance(results, (tuple, list))
    rlist = list(results) if multi else [results]

    # attr-dependent op metadata (callable mutate/no_grad) must also see
    # attrs the caller passed POSITIONALLY — bind them to the impl's
    # parameter names (impl is called impl(*arrays, *pos_attrs, **attrs))
    if callable(op.mutate) or callable(op.no_grad):
        eff_attrs = _bind_pos_attrs(op, len(arrays), pos_attrs, attrs)
    else:
        eff_attrs = attrs

    # Mutated inputs come back appended after the declared outputs.
    mutate_idx = op.mutate(eff_attrs) if callable(op.mutate) else op.mutate
    n_out = len(rlist) - len(mutate_idx)
    new_mutated = rlist[n_out:]
    rlist = rlist[:n_out]
    if not in_shape_resolve():
        for idx, newval in zip(mutate_idx, new_mutated):
            tgt = inputs[idx]
            if isinstance(tgt, NDArray):
                tgt._set_data(newval)

    no_grad = op.no_grad(eff_attrs) if callable(op.no_grad) \
        else op.no_grad
    recording = autograd.is_recording() and not no_grad
    if recording and out is not None:
        raise MXNetError(
            "Inplace/out= operations are not supported when autograd "
            "recording is on (reference semantics).")

    outputs = [_wrap(r) for r in rlist]

    if recording:
        # pass ALL inputs: record_op stores non-NDArray operands
        # (python scalars etc.) as tape constants — filtering them
        # out would misalign the replay arguments
        autograd.record_op(op, pos_attrs, attrs, inputs, arrays,
                           outputs)

    if out is not None and in_shape_resolve():
        return out
    if out is not None:
        outs = out if isinstance(out, (tuple, list)) else [out]
        if len(outs) != len(outputs):
            raise MXNetError("out= arity mismatch for op %s" % op.name)
        for o, r in zip(outs, outputs):
            o._set_data(r._data)
        return out

    if len(outputs) == 1 and not multi:
        return outputs[0]
    if len(outputs) == 1 and multi and op.num_outputs == 1:
        return outputs[0]
    return tuple(outputs)
