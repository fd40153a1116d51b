"""Which backend a kernel call will run on — the one place the Pallas
call sites (``flash_attention``, ``paged_attention``,
``fused_multi_sgd``, ``rtc.PallasKernel``) ask.

Mosaic compiles for TPU only, so on the CPU backend a kernel runs in
the Pallas interpreter (or a jnp reference).  That choice must follow
the *operands*, not the process: on a chip host the default context is
still ``cpu(0)`` and ``jax.default_backend()`` says ``tpu``, so a
process-wide test sends CPU-resident arrays to Mosaic.
"""
from __future__ import annotations

__all__ = ["platform_of", "default_platform", "run_kernel"]


def platform_of(*operands):
    """Platform of the device the concrete ``operands`` live on (host
    values go where ``jit`` would put them), or ``None`` when any is a
    tracer: a ``jit`` is being traced and the program's placement is
    not known yet."""
    import jax
    leaves = jax.tree_util.tree_leaves(operands)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return None
    for x in leaves:
        if isinstance(x, jax.Array):
            return next(iter(x.devices())).platform
    return default_platform()


def default_platform():
    """Platform ``jit`` places a program on when no operand is
    committed: ``jax.default_device`` if set, else the first device."""
    import jax
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.devices()[0].platform
    return dev if isinstance(dev, str) else dev.platform


def run_kernel(build, *args, interpret=None):
    """``build(interpret)(*args)`` for a Pallas call: interpreted where
    it runs on the CPU backend, compiled by Mosaic on an accelerator.
    An explicit ``interpret`` decides by itself.

    Concrete operands decide by where they live.  Under tracing the
    choice is staged with ``lax.platform_dependent`` and made when the
    program is lowered, so only the taken branch is ever compiled and
    one traced program is right on either backend.  Both branches are
    traced; do not differentiate through this (``cond`` residuals are
    the union of both branches') — ``flash_attention`` decides at trace
    time for that reason."""
    import jax
    if interpret is None:
        plat = platform_of(*args)
        if plat is None:
            return jax.lax.platform_dependent(
                *args, cpu=build(True), default=build(False))
        interpret = plat == "cpu"
    return build(bool(interpret))(*args)
