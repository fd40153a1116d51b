"""Runtime kernel compilation — user Pallas kernels from Python.

Reference: ``python/mxnet/rtc.py`` + ``src/common/rtc.cc`` (SURVEY.md §2.1
"Init/runtime misc": user CUDA kernels compiled with NVRTC at runtime and
launched from Python as ``CudaModule``/``CudaKernel``).

The TPU analog compiles **Pallas** kernels instead of CUDA: the source
string defines kernel functions against ``pl.BlockSpec``-style refs; the
module evaluates it with jax/jnp/pallas in scope and wraps each exported
function in ``pl.pallas_call`` at launch time.  Like the reference, this
is the escape hatch for hand-written kernels without rebuilding the
framework — and the same object also accepts an already-imported Python
function, for kernels defined inline.

Example::

    mod = rtc.PallasModule(r'''
    def scale(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0
    ''', exports=["scale"])
    k = mod.get_kernel("scale")
    y = k(x)                       # same shape/dtype out
"""
from __future__ import annotations

from typing import Optional, Sequence

from .base import MXNetError

__all__ = ["PallasModule", "PallasKernel"]


class PallasKernel:
    """A launchable kernel (reference: ``CudaKernel.launch``).

    Calling it runs ``pl.pallas_call`` with out_shape defaulting to the
    first input's shape/dtype; pass ``out_shape=(shape, dtype)`` to
    override, and ``grid``/``interpret`` for tiled launches and CPU
    debugging.  Inputs/outputs are jax arrays or mxnet_tpu NDArrays.
    """

    def __init__(self, fn, name):
        self._fn = fn
        self.name = name

    def __call__(self, *inputs, out_shape=None, grid=None,
                 interpret=None, **pallas_kw):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from .ndarray import NDArray

        unwrapped = []
        want_nd = False
        for a in inputs:
            if isinstance(a, NDArray):
                want_nd = True
                unwrapped.append(a._data)
            else:
                unwrapped.append(jnp.asarray(a))
        if out_shape is None:
            ref = unwrapped[0]
            out = jax.ShapeDtypeStruct(ref.shape, ref.dtype)
        else:
            shape, dtype = out_shape
            out = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
        kw = dict(out_shape=out, **pallas_kw)
        if grid is not None:
            kw["grid"] = grid

        def call(interp):
            return pl.pallas_call(self._fn, interpret=interp, **kw)

        # interpret mode keeps kernels runnable where the inputs live
        # on the CPU backend (tests / debugging); Mosaic compiles them
        # on the chip
        from .kernels.platform import run_kernel
        result = run_kernel(call, *unwrapped, interpret=interpret)
        if want_nd:
            from . import ndarray as nd
            return nd.array(result)
        return result


class PallasModule:
    """Compile a source string of Pallas kernels
    (reference: ``CudaModule``).

    ``source`` is Python executed with ``jax``, ``jnp``, ``pl`` (pallas)
    pre-imported; ``exports`` names the kernel functions to expose.
    """

    def __init__(self, source: str, options=(),
                 exports: Sequence[str] = ()):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        self._namespace = {"jax": jax, "jnp": jnp, "pl": pl}
        try:
            exec(compile(source, "<rtc.PallasModule>", "exec"),
                 self._namespace)
        except Exception as e:
            raise MXNetError("rtc source failed to compile: %s" % e)
        self._exports = list(exports) or [
            k for k, v in self._namespace.items()
            if callable(v) and not k.startswith("_")
            and k not in ("jax", "jnp", "pl")]
        for name in self._exports:
            if name not in self._namespace:
                raise MXNetError("export %r not defined in rtc source"
                                 % name)

    def get_kernel(self, name: str, signature: Optional[str] = None):
        """Kernel by name.  ``signature`` is accepted for reference-API
        compatibility and ignored (shapes/dtypes are traced, not
        declared)."""
        if name not in self._exports:
            raise MXNetError("unknown kernel %r; exports: %s"
                             % (name, self._exports))
        return PallasKernel(self._namespace[name], name)
