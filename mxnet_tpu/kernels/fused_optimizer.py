"""Grouped optimizer update — Pallas TPU kernel.

Reference: ``src/operator/optimizer_op.cc`` ``multi_sgd_*`` /
``multi_mp_sgd_*`` (SURVEY.md §2.1 "Operator library" row: grouped
``multi_*`` fused updates; §7 names the grouped optimizer update as a
Pallas target).  The reference fuses N per-tensor CUDA kernel launches
into one; the TPU analog flattens the whole parameter group into one 1-D
buffer and runs a single Pallas kernel over VPU-aligned blocks — one
launch, one HBM sweep, regardless of tensor count.

Per-tensor learning rates / weight decays become flat per-element
vectors built once at trace time (cheap next to the param bytes).
Numerics match sgd_update/sgd_mom_update exactly for float32 tensors —
the dispatchers in ops/optimizer_ops.py only take this path when every
tensor is f32, because the packed buffer computes in f32 end-to-end
while the per-tensor loop would round each intermediate in the storage
dtype (bf16/f16 groups fall back to the loop).
"""
from __future__ import annotations

import functools

__all__ = ["fused_multi_sgd", "group_flatten", "group_unflatten"]

_BLOCK = 8 * 128 * 16  # VPU lane-aligned 1-D block (16K elements)


def group_flatten(tensors):
    """Concat arbitrary-shaped tensors into one padded 1-D f32 buffer;
    returns (flat, meta) where meta restores shapes via
    :func:`group_unflatten`."""
    import jax.numpy as jnp
    meta = []
    offset = 0
    parts = []
    for t in tensors:
        n = t.size
        meta.append((t.shape, t.dtype, offset, n))
        parts.append(t.astype(jnp.float32).ravel())
        offset += n
    flat = jnp.concatenate(parts) if parts else jnp.zeros((0,),
                                                          jnp.float32)
    pad = (-flat.size) % _BLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, meta


def group_unflatten(flat, meta):
    import jax.numpy as jnp
    outs = []
    for shape, dtype, offset, n in meta:
        outs.append(jnp.reshape(flat[offset:offset + n],
                                shape).astype(dtype))
    return outs


def _expand_per_tensor(values, meta, total):
    """Per-tensor scalars → flat per-element vector matching the packed
    buffer layout."""
    import jax.numpy as jnp
    # v may be a traced scalar: the eager-jit path passes lr/lrs as
    # jit arguments so an lr schedule does not retrace per step
    parts = [jnp.full((n,), v, jnp.float32)
             for v, (_, _, _, n) in zip(values, meta)]
    flat = jnp.concatenate(parts) if parts else jnp.zeros((0,),
                                                          jnp.float32)
    pad = total - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat


# per-element math matches sgd_update/sgd_mom_update exactly:
# g = clip(grad*rescale) + wd*w ; m_new = mu*m - lr*g ; w_new = w + m_new
# (MXNet convention — the momentum buffer stores the lr-scaled update)

def _sgd_kernel(w_ref, g_ref, lr_ref, wd_ref, o_ref, *, rescale, clip):
    import jax.numpy as jnp
    w = w_ref[...]
    g = g_ref[...] * rescale
    if clip is not None and clip >= 0:
        g = jnp.clip(g, -clip, clip)
    g = g + wd_ref[...] * w
    o_ref[...] = w - lr_ref[...] * g


def _sgd_mom_kernel(w_ref, g_ref, m_ref, lr_ref, wd_ref, o_ref,
                    om_ref, *, momentum, rescale, clip):
    import jax.numpy as jnp
    w = w_ref[...]
    g = g_ref[...] * rescale
    if clip is not None and clip >= 0:
        g = jnp.clip(g, -clip, clip)
    g = g + wd_ref[...] * w
    m = momentum * m_ref[...] - lr_ref[...] * g
    om_ref[...] = m
    o_ref[...] = w + m


def fused_multi_sgd(weights, grads, moms=None, *, lrs, wds,
                    momentum=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                    interpret=None):
    """One-kernel grouped SGD(+momentum) over a list of tensors.

    Returns (new_weights, new_moms) with new_moms=None when ``moms`` is.
    Bit-exact per element with sgd_update/sgd_mom_update in f32.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .platform import run_kernel

    if len(lrs) != len(weights) or len(wds) != len(weights):
        # the per-tensor loop path would IndexError; fail just as loudly
        # instead of silently zero-padding lr over trailing tensors
        raise ValueError(
            "fused_multi_sgd: %d weights need %d lrs / %d wds"
            % (len(weights), len(lrs), len(wds)))

    wflat, meta = group_flatten(weights)
    gflat, _ = group_flatten(grads)
    total = wflat.size
    lrvec = _expand_per_tensor(lrs, meta, total)
    wdvec = _expand_per_tensor(wds, meta, total)

    n_blocks = max(1, total // _BLOCK)
    spec = pl.BlockSpec((_BLOCK,), lambda i: (i,))
    out_shape = jax.ShapeDtypeStruct((total,), jnp.float32)

    if moms is None:
        kern = functools.partial(_sgd_kernel, rescale=rescale_grad,
                                 clip=clip_gradient)
        args = (wflat, gflat, lrvec, wdvec)
        out_specs, out_shapes = spec, out_shape
    else:
        mflat, _ = group_flatten(moms)
        kern = functools.partial(_sgd_mom_kernel, momentum=momentum,
                                 rescale=rescale_grad,
                                 clip=clip_gradient)
        args = (wflat, gflat, mflat, lrvec, wdvec)
        out_specs, out_shapes = [spec, spec], [out_shape, out_shape]

    def call(interp):
        return pl.pallas_call(
            kern, grid=(n_blocks,), in_specs=[spec] * len(args),
            out_specs=out_specs, out_shape=out_shapes,
            interpret=interp)

    # interpreted exactly where the group lives on the CPU backend
    out = run_kernel(call, *args, interpret=interpret)
    if moms is None:
        return group_unflatten(out, meta), None
    new_flat, new_mflat = out
    return (group_unflatten(new_flat, meta),
            group_unflatten(new_mflat, meta))
