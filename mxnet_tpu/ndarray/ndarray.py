"""NDArray — imperative, mutable, asynchronously-evaluated array on XLA.

Reference: ``src/ndarray/ndarray.cc`` + ``python/mxnet/ndarray/ndarray.py``
(SURVEY.md §2.1 "NDArray core", §2.2 "NDArray API", §7 hard-part #1
"Mutation semantics on immutable XLA buffers").

TPU-native design: an NDArray owns a *chunk* holding a ``jax.Array``.
Mutation (``+=``, slice-assign, optimizer updates, ``out=``) computes a new
buffer functionally and swaps the chunk, bumping a version counter — the
same observable semantics as the reference's engine-var versioning, with
XLA/PjRt supplying the async ordering that the reference's ThreadedEngine
provided (every op returns immediately; ``wait_to_read``/``asnumpy`` are the
sync points).  Basic-slice *views* are therefore copies here (documented
divergence: reference basic slices alias; ``__setitem__`` on the base array
is the supported mutation path and matches reference behavior).
"""
from __future__ import annotations

import numpy as _np
from typing import Any, List, Optional, Sequence, Tuple, Union

from ..base import MXNetError, numeric_types, integer_types
from ..context import Context, current_context, cpu

__all__ = ["NDArray", "_wrap", "array", "zeros", "ones", "full", "empty",
           "arange", "concat", "stack", "save", "load", "waitall",
           "from_numpy", "from_dlpack", "to_dlpack_for_read"]


def _jnp():
    import jax.numpy as jnp
    return jnp


# Set by profiler._mem_start() when ``profile_memory=True`` is active:
# called with every chunk buffer entering the NDArray layer (construction
# and chunk-swap mutation).  None → zero overhead on the hot path.
_MEM_HOOK = None


def _dev_of(data):
    try:
        devs = data.devices()
        return next(iter(devs))
    except Exception:
        return None


def _ctx_of(data) -> Context:
    dev = _dev_of(data)
    if dev is None:
        return current_context()
    if dev.platform == "cpu":
        import jax
        try:
            accel = jax.devices()[0].platform != "cpu"
        except Exception:
            accel = False
        if accel:
            return Context("cpu", dev.id)
        # CPU-only harness: report the virtual device as tpu ctx only if
        # user asked; default to cpu ctx with matching id.
        return Context("cpu", dev.id)
    return Context("tpu", dev.id)


class NDArray:
    """Multi-dimensional array with imperative mutation semantics."""

    __slots__ = ("_data", "_version", "_grad", "_grad_req", "_ag",
                 "_ctx_hint", "__weakref__")

    def __init__(self, data, ctx: Optional[Context] = None):
        jnp = _jnp()
        if isinstance(data, NDArray):
            data = data._data
        if not hasattr(data, "dtype") or isinstance(data, _np.ndarray):
            data = jnp.asarray(data)
        self._data = data
        self._version = 0
        self._grad = None
        self._grad_req = "null"
        self._ag = None
        self._ctx_hint = ctx
        if _MEM_HOOK is not None:
            _MEM_HOOK(data)

    # ------------------------------------------------------------------
    # chunk swap = mutation
    # ------------------------------------------------------------------
    def _set_data(self, new_data):
        """Swap the underlying buffer (the mutation primitive).  Bumps the
        version counter — reference: engine write-var version++."""
        self._data = new_data
        self._version += 1
        if _MEM_HOOK is not None:
            _MEM_HOOK(new_data)

    # ------------------------------------------------------------------
    # properties
    # ------------------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(str(self._data.dtype))

    @property
    def size(self) -> int:
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def context(self) -> Context:
        if self._ctx_hint is not None:
            return self._ctx_hint
        return _ctx_of(self._data)

    ctx = context

    @property
    def stype(self) -> str:
        return "default"

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad

    @property
    def T(self) -> "NDArray":
        return self.transpose()

    @property
    def version(self) -> int:
        return self._version

    # ------------------------------------------------------------------
    # sync / host transfer
    # ------------------------------------------------------------------
    def wait_to_read(self):
        """Block until the value is computed (reference:
        ``Engine::WaitForVar``); deferred device errors surface here."""
        self._data.block_until_ready()
        return self

    def asnumpy(self) -> _np.ndarray:
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def tolist(self):
        return self.asnumpy().tolist()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("Ambiguous truth value of multi-element NDArray; "
                         "use .any() or .all()")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------
    # context movement
    # ------------------------------------------------------------------
    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self.context:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def copyto(self, other: Union[Context, "NDArray"]) -> "NDArray":
        import jax
        if isinstance(other, Context):
            moved = jax.device_put(self._data, other.jax_device)
            out = NDArray(moved, ctx=other)
            return out
        if isinstance(other, NDArray):
            moved = jax.device_put(self._data, _dev_of(other._data))
            other._set_data(moved)
            return other
        raise MXNetError("copyto target must be Context or NDArray")

    def copy(self) -> "NDArray":
        jnp = _jnp()
        return NDArray(jnp.array(self._data), ctx=self._ctx_hint)

    def astype(self, dtype, copy: bool = True) -> "NDArray":
        if not copy and _np.dtype(dtype) == self.dtype:
            return self
        return _wrap(self._data.astype(_np.dtype(dtype).name))

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None):
        """Allocate a gradient buffer (on this array's device) and mark
        this array as a variable."""
        from .. import autograd
        import jax
        jnp = _jnp()
        with jax.default_device(_dev_of(self._data)):
            grad = NDArray(jnp.zeros(self.shape, self._data.dtype))
        autograd.mark_variables([self], [grad], [grad_req])

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def detach(self) -> "NDArray":
        out = NDArray(self._data)
        return out

    # ------------------------------------------------------------------
    # operator sugar — routed through registered scalar/broadcast ops so
    # everything lands on the autograd tape uniformly.
    # ------------------------------------------------------------------
    def _binop(self, other, op_name, scalar_op, reverse=False):
        from ..ops.registry import get_op, invoke
        if isinstance(other, NDArray):
            return invoke(get_op(op_name), [self, other])
        if isinstance(other, numeric_types + (bool, _np.generic)):
            return invoke(get_op(scalar_op), [self],
                          attrs={"scalar": float(other)})
        return NotImplemented

    def __add__(self, other):
        return self._binop(other, "broadcast_add", "_plus_scalar")

    def __radd__(self, other):
        return self.__add__(other)

    def __sub__(self, other):
        return self._binop(other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        return self._binop(other, "broadcast_sub", "_rminus_scalar")

    def __mul__(self, other):
        return self._binop(other, "broadcast_mul", "_mul_scalar")

    def __rmul__(self, other):
        return self.__mul__(other)

    def __truediv__(self, other):
        return self._binop(other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        return self._binop(other, "broadcast_div", "_rdiv_scalar")

    def __floordiv__(self, other):
        return self._binop(other, "_broadcast_floordiv", "_floordiv_scalar")

    def __mod__(self, other):
        return self._binop(other, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, other):
        return self._binop(other, "broadcast_mod", "_rmod_scalar")

    def __pow__(self, other):
        return self._binop(other, "broadcast_power", "_power_scalar")

    def __rpow__(self, other):
        return self._binop(other, "broadcast_power", "_rpower_scalar")

    def __matmul__(self, other):
        from ..ops.registry import get_op, invoke
        return invoke(get_op("_npi_matmul"), [self, other])

    def __neg__(self):
        from ..ops.registry import get_op, invoke
        return invoke(get_op("negative"), [self])

    def __abs__(self):
        from ..ops.registry import get_op, invoke
        return invoke(get_op("abs"), [self])

    def __eq__(self, other):
        if other is None:
            return False
        r = self._binop(other, "broadcast_equal", "_equal_scalar")
        return r

    def __ne__(self, other):
        if other is None:
            return True
        return self._binop(other, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return self._binop(other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._binop(other, "broadcast_greater_equal",
                           "_greater_equal_scalar")

    def __lt__(self, other):
        return self._binop(other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._binop(other, "broadcast_lesser_equal",
                           "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # in-place: functional compute + chunk swap
    def _inplace(self, other, op_name, scalar_op):
        from .. import autograd
        if autograd.is_recording() and self._ag is not None:
            raise MXNetError("Inplace update on a recorded array is not "
                             "allowed under autograd.record()")
        res = self._binop(other, op_name, scalar_op)
        self._set_data(res._data)
        return self

    def __iadd__(self, other):
        return self._inplace(other, "broadcast_add", "_plus_scalar")

    def __isub__(self, other):
        return self._inplace(other, "broadcast_sub", "_minus_scalar")

    def __imul__(self, other):
        return self._inplace(other, "broadcast_mul", "_mul_scalar")

    def __itruediv__(self, other):
        return self._inplace(other, "broadcast_div", "_div_scalar")

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def _int64_index_scope(self):
        """x64 scope for indexing arrays whose element count exceeds
        int32 range: without it JAX truncates slice starts/scatter
        indices to int32 — reads past 2^31 raise OverflowError and
        writes silently land nowhere (reference:
        tests/nightly/test_large_array.py, the INT64_TENSOR_SIZE build
        flag; SURVEY.md §4.7)."""
        import contextlib
        if self.size >= 2**31:
            import jax
            return jax.enable_x64(True)
        return contextlib.nullcontext()

    def _widen_index_arrays(self, k):
        """Inside the int64 scope, integer index ARRAYS must also be
        int64 — XLA computes gather/scatter offsets in the index dtype,
        so int32 indices overflow on >=2^31-element arrays even with
        x64 on."""
        jnp = _jnp()

        def widen(x):
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype,
                                                      jnp.integer):
                return x.astype(jnp.int64)
            return x

        if isinstance(k, tuple):
            return tuple(widen(e) for e in k)
        return widen(k)

    def __getitem__(self, key):
        key = _clean_index(key)
        from ..ops.registry import OpDef, invoke
        idx_arrays = _extract_index_arrays(key)

        # cacheable lane: basic/int-fancy indexing on <2^31-element
        # arrays goes through a stable op with the index as a hashable
        # attr, so the eager-jit cache applies (slicing is the data
        # pipeline's hottest imperative op).  Bool masks (data-dependent
        # output shape) and int64-widening cases use the direct path.
        if self.size < 2**31:
            tmpl = _index_template(key)
            if tmpl is not None and not any(
                    a.dtype == _np.bool_ for a in idx_arrays):
                return invoke(_getitem_op(), [self] + idx_arrays,
                              attrs={"key_tmpl": tmpl})

        def impl(data, *idx_arrs):
            k = _rebuild_index(key, list(idx_arrs))
            with self._int64_index_scope():
                if self.size >= 2**31:
                    k = self._widen_index_arrays(k)
                return data[k]

        op = OpDef("_getitem", impl, num_outputs=1)
        return invoke(op, [self] + idx_arrays)

    def __setitem__(self, key, value):
        from .. import autograd
        if autograd.is_recording() and self._ag is not None:
            raise MXNetError("Slice-assign on a recorded array is not "
                             "allowed under autograd.record()")
        jnp = _jnp()
        key = _clean_index(key)
        idx_arrays = _extract_index_arrays(key)
        k = _rebuild_index(key, [a._data for a in idx_arrays])
        if isinstance(value, NDArray):
            v = value._data
        else:
            v = value
        with self._int64_index_scope():
            if self.size >= 2**31:
                k = self._widen_index_arrays(k)
            new = self._data.at[k].set(v)
        self._set_data(new)
        return self

    # ------------------------------------------------------------------
    # misc reference-API methods
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        from ..ops.registry import get_op, invoke
        return invoke(get_op("reshape"), [self], attrs={"shape": shape})

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def expand_dims(self, axis):
        from ..ops.registry import get_op, invoke
        return invoke(get_op("expand_dims"), [self], attrs={"axis": axis})

    def squeeze(self, axis=None):
        from ..ops.registry import get_op, invoke
        return invoke(get_op("squeeze"), [self], attrs={"axis": axis})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        from ..ops.registry import get_op, invoke
        return invoke(get_op("transpose"), [self],
                      attrs={"axes": axes if axes else None})

    def flatten(self):
        from ..ops.registry import get_op, invoke
        return invoke(get_op("Flatten"), [self])

    def flip(self, axis):
        from ..ops.registry import get_op, invoke
        return invoke(get_op("flip"), [self], attrs={"axis": axis})

    def tile(self, reps):
        from ..ops.registry import get_op, invoke
        return invoke(get_op("tile"), [self], attrs={"reps": reps})

    def broadcast_to(self, shape):
        from ..ops.registry import get_op, invoke
        return invoke(get_op("broadcast_to"), [self], attrs={"shape": shape})

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def tostype(self, stype):
        if stype == "default":
            return self.copy()  # reference tostype always returns a new array
        from .sparse import cast_storage
        return cast_storage(self, stype)

    def todense(self):
        return self.copy()

    def __repr__(self):
        return "\n%s\n<NDArray %s @%s>" % (
            str(self.asnumpy()), "x".join(str(d) for d in self.shape),
            self.context)


# Install op-delegating methods (sum, mean, max, ... — reference NDArray has
# method mirrors for common ops, generated alongside the function stubs).
_METHOD_OPS = [
    "sum", "mean", "max", "min", "prod", "argmax", "argmin", "abs", "exp",
    "log", "sqrt", "square", "clip", "round", "floor", "ceil", "sign",
    "relu", "sigmoid", "tanh", "softmax", "log_softmax", "norm", "sort",
    "argsort", "topk", "one_hot", "take", "pick", "dot", "split",
    "slice_axis", "slice_like", "swapaxes", "repeat", "pad", "nansum",
    "nanprod", "cumsum", "diag", "zeros_like", "ones_like",
]


def _install_methods():
    from ..ops import registry as _r

    def make(opname):
        def method(self, *args, **kwargs):
            op = _r.get_op(opname)
            extra = [a for a in args if isinstance(a, NDArray)]
            pos = tuple(a for a in args if not isinstance(a, NDArray))
            return _r.invoke(op, [self] + extra, pos_attrs=pos, attrs=kwargs)
        method.__name__ = opname
        return method

    for opname in _METHOD_OPS:
        if not hasattr(NDArray, opname) and _r.op_exists(opname):
            setattr(NDArray, opname, make(opname))


_SCALAR_REVERSIBLE = {}


def _wrap(data) -> NDArray:
    return NDArray(data)


# ---------------------------------------------------------------------------
# indexing helpers
# ---------------------------------------------------------------------------

def _clean_index(key):
    if isinstance(key, NDArray):
        return key
    if isinstance(key, tuple):
        return tuple(_clean_index(k) for k in key)
    return key


def _extract_index_arrays(key) -> List[NDArray]:
    out = []
    if isinstance(key, NDArray):
        out.append(key)
    elif isinstance(key, tuple):
        for k in key:
            if isinstance(k, NDArray):
                out.append(k)
    return out


def _rebuild_index(key, arrays: List[Any]):
    it = iter(arrays)
    if isinstance(key, NDArray):
        return next(it)
    if isinstance(key, tuple):
        return tuple(next(it) if isinstance(k, NDArray) else k for k in key)
    return key


# --- cacheable __getitem__ lane -------------------------------------------

class _Arr:
    """Hashable placeholder marking an index-array position in a key
    template (the arrays themselves travel as op inputs)."""
    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst

    def __repr__(self):
        return "<ARR>"


_ARR = _Arr()


def _index_template(key):
    """Hashable template of a cleaned index, or None if the index uses
    constructs the cacheable lane does not handle (lists — whose
    fancy-index semantics a tuple template would corrupt — np arrays,
    or anything unhashable)."""
    def one(k):
        if isinstance(k, NDArray):
            return _ARR
        if k is None or k is Ellipsis or type(k) is slice:
            return k
        if isinstance(k, (int, _np.integer)) and not isinstance(k, bool):
            return int(k)
        return _INVALID

    _INVALID = object()
    if isinstance(key, tuple):
        out = tuple(one(k) for k in key)
        return None if any(o is _INVALID for o in out) else out
    o = one(key)
    return None if o is _INVALID else o


def _rebuild_index_tmpl(tmpl, arrays: List[Any]):
    it = iter(arrays)
    if tmpl is _ARR:
        return next(it)
    if isinstance(tmpl, tuple):
        return tuple(next(it) if k is _ARR else k for k in tmpl)
    return tmpl


def _getitem_cacheable_impl(*args, key_tmpl=None):
    data = args[0]
    return data[_rebuild_index_tmpl(key_tmpl, list(args[1:]))]


_GETITEM_OP = None


def _getitem_op():
    global _GETITEM_OP
    if _GETITEM_OP is None:
        from ..ops.registry import OpDef
        # module-lifetime OpDef → safe to mark cacheable (id is stable)
        _GETITEM_OP = OpDef("_getitem", _getitem_cacheable_impl,
                            num_outputs=1, cacheable=True)
    return _GETITEM_OP


# ---------------------------------------------------------------------------
# creation API (reference: mx.nd.zeros/ones/array/...)
# ---------------------------------------------------------------------------

def _creation_ctx(ctx):
    return ctx if ctx is not None else current_context()


def array(source_array, ctx=None, dtype=None) -> NDArray:
    import jax
    ctx = _creation_ctx(ctx)
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    np_arr = _np.asarray(source_array, dtype=dtype)
    if np_arr.dtype == _np.float64 and dtype is None:
        np_arr = np_arr.astype(_np.float32)
    # device_put the NUMPY buffer directly: wrapping it in jnp.asarray
    # first would materialize it on the DEFAULT device and then move it
    # — on a chip host the default device is the chip, so every
    # cpu-context nd.array() would cross to the device and back
    data = jax.device_put(np_arr, ctx.jax_device)
    return NDArray(data, ctx=ctx)


def from_numpy(np_array, zero_copy=False) -> NDArray:
    return array(np_array)


def empty(shape, ctx=None, dtype="float32") -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype="float32", **kwargs) -> NDArray:
    import jax
    jnp = _jnp()
    ctx = _creation_ctx(ctx)
    if isinstance(shape, int):
        shape = (shape,)
    with jax.default_device(ctx.jax_device):
        data = jnp.zeros(shape, dtype or "float32")
    return NDArray(data, ctx=ctx)


def ones(shape, ctx=None, dtype="float32", **kwargs) -> NDArray:
    import jax
    jnp = _jnp()
    ctx = _creation_ctx(ctx)
    if isinstance(shape, int):
        shape = (shape,)
    with jax.default_device(ctx.jax_device):
        data = jnp.ones(shape, dtype or "float32")
    return NDArray(data, ctx=ctx)


def full(shape, val, ctx=None, dtype="float32") -> NDArray:
    import jax
    jnp = _jnp()
    ctx = _creation_ctx(ctx)
    if isinstance(shape, int):
        shape = (shape,)
    with jax.default_device(ctx.jax_device):
        data = jnp.full(shape, val, dtype or "float32")
    return NDArray(data, ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None,
           dtype="float32") -> NDArray:
    import jax
    jnp = _jnp()
    ctx = _creation_ctx(ctx)
    with jax.default_device(ctx.jax_device):
        data = jnp.arange(start, stop, step, dtype)
        if repeat > 1:
            data = jnp.repeat(data, repeat)
    return NDArray(data, ctx=ctx)


def concat(*arrays, dim=1) -> NDArray:
    from ..ops.registry import get_op, invoke
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return invoke(get_op("Concat"), list(arrays), attrs={"dim": dim})


def stack(*arrays, axis=0) -> NDArray:
    from ..ops.registry import get_op, invoke
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return invoke(get_op("stack"), list(arrays), attrs={"axis": axis})


def waitall():
    from ..engine import Engine
    Engine.get().wait_for_all()


# ---------------------------------------------------------------------------
# save / load — the ``.params`` container format.
#
# Reference: ``NDArray::Save/Load`` binary container (SURVEY.md §5.4).  The
# reference mount was empty this round, so byte-level compatibility could not
# be verified; this container uses a documented magic-tagged format of our
# own ("MXTP0001") with an identical API surface.
# ---------------------------------------------------------------------------

_PARAMS_MAGIC = b"MXTP0001"


def save(fname: str, data):
    import struct
    if isinstance(data, NDArray):
        data = [("", data)]
    if isinstance(data, dict):
        data = list(data.items())
    elif isinstance(data, (list, tuple)) and not (
            data and isinstance(data[0], tuple)):
        data = [("", d) for d in data]
    with open(fname, "wb") as f:
        f.write(_PARAMS_MAGIC)
        f.write(struct.pack("<Q", len(data)))
        for name, arr in data:
            nb = name.encode("utf-8")
            np_arr = arr.asnumpy() if isinstance(arr, NDArray) else _np.asarray(arr)
            dt = np_arr.dtype.str.encode()
            f.write(struct.pack("<I", len(nb)))
            f.write(nb)
            f.write(struct.pack("<I", len(dt)))
            f.write(dt)
            f.write(struct.pack("<I", np_arr.ndim))
            for d in np_arr.shape:
                f.write(struct.pack("<q", d))
            payload = np_arr.tobytes()
            f.write(struct.pack("<Q", len(payload)))
            f.write(payload)


def load(fname: str):
    import struct
    with open(fname, "rb") as f:
        magic = f.read(8)
        if magic != _PARAMS_MAGIC:
            raise MXNetError("Invalid parameter file %s (bad magic %r)"
                             % (fname, magic))
        (count,) = struct.unpack("<Q", f.read(8))
        entries = []
        for _ in range(count):
            (nlen,) = struct.unpack("<I", f.read(4))
            name = f.read(nlen).decode("utf-8")
            (dlen,) = struct.unpack("<I", f.read(4))
            dt = _np.dtype(f.read(dlen).decode())
            (ndim,) = struct.unpack("<I", f.read(4))
            shape = tuple(struct.unpack("<q", f.read(8))[0]
                          for _ in range(ndim))
            (plen,) = struct.unpack("<Q", f.read(8))
            buf = f.read(plen)
            np_arr = _np.frombuffer(buf, dtype=dt).reshape(shape)
            entries.append((name, array(np_arr, dtype=dt)))
        if any(name for name, _ in entries):
            return dict(entries)
        return [arr for _, arr in entries]
