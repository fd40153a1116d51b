"""Fused optimizer-update kernels.

Reference: ``src/operator/optimizer_op.cc`` — ``sgd_update``,
``sgd_mom_update``, ``adam_update``, ``lamb_*``, ``multi_*`` grouped and
``mp_*`` multi-precision variants (SURVEY.md §2.1).  Semantics: the caller
passes ``out=weight`` (buffer-swap mutation); optimizer *state* inputs are
declared via ``mutate=`` and written back by the invoke layer.  XLA fuses
each update into a single elementwise kernel; the grouped ``multi_*`` ops
exist so one dispatch covers many small parameters (same motivation as the
reference's grouped kernels).
"""
from __future__ import annotations

from .registry import register


def _j():
    import jax.numpy as jnp
    return jnp


def _prep_grad(grad, rescale_grad, clip_gradient, wd, weight):
    jnp = _j()
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient >= 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    if wd:
        g = g + wd * weight
    return g


@register("sgd_update")
def sgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
               clip_gradient=-1.0, lazy_update=True, **kw):
    g = _prep_grad(grad, rescale_grad, clip_gradient, wd, weight)
    return weight - lr * g


@register("sgd_mom_update", mutate=(2,))
def sgd_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True,
                   **kw):
    g = _prep_grad(grad, rescale_grad, clip_gradient, wd, weight)
    new_mom = momentum * mom - lr * g
    return weight + new_mom, new_mom


@register("mp_sgd_update", mutate=(2,))
def mp_sgd_update(weight, grad, weight32, lr=0.01, wd=0.0, rescale_grad=1.0,
                  clip_gradient=-1.0, lazy_update=True, **kw):
    g32 = grad.astype("float32")
    g = _prep_grad(g32, rescale_grad, clip_gradient, wd, weight32)
    new_w32 = weight32 - lr * g
    return new_w32.astype(weight.dtype), new_w32


@register("mp_sgd_mom_update", mutate=(2, 3))
def mp_sgd_mom_update(weight, grad, mom, weight32, lr=0.01, momentum=0.0,
                      wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                      lazy_update=True, **kw):
    g = _prep_grad(grad.astype("float32"), rescale_grad, clip_gradient, wd,
                   weight32)
    new_mom = momentum * mom - lr * g
    new_w32 = weight32 + new_mom
    return new_w32.astype(weight.dtype), new_mom, new_w32


@register("nag_mom_update", mutate=(2,))
def nag_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, **kw):
    g = _prep_grad(grad, rescale_grad, clip_gradient, wd, weight)
    new_mom = momentum * mom + g
    return weight - lr * (g + momentum * new_mom), new_mom


@register("mp_nag_mom_update", mutate=(2, 3))
def mp_nag_mom_update(weight, grad, mom, weight32, lr=0.01, momentum=0.0,
                      wd=0.0, rescale_grad=1.0, clip_gradient=-1.0, **kw):
    g = _prep_grad(grad.astype("float32"), rescale_grad, clip_gradient, wd,
                   weight32)
    new_mom = momentum * mom + g
    new_w32 = weight32 - lr * (g + momentum * new_mom)
    return new_w32.astype(weight.dtype), new_mom, new_w32


@register("adam_update", mutate=(2, 3))
def adam_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                lazy_update=True, **kw):
    jnp = _j()
    g = _prep_grad(grad, rescale_grad, clip_gradient, wd, weight)
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    new_w = weight - lr * new_mean / (jnp.sqrt(new_var) + epsilon)
    return new_w, new_mean, new_var


@register("mp_adam_update", mutate=(2, 3, 4))
def mp_adam_update(weight, grad, mean, var, weight32, lr=0.001, beta1=0.9,
                   beta2=0.999, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                   clip_gradient=-1.0, **kw):
    jnp = _j()
    g = _prep_grad(grad.astype("float32"), rescale_grad, clip_gradient, wd,
                   weight32)
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    new_w32 = weight32 - lr * new_mean / (jnp.sqrt(new_var) + epsilon)
    return new_w32.astype(weight.dtype), new_mean, new_var, new_w32


@register("adamw_update", mutate=(2, 3))
def adamw_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, wd=0.0, eta=1.0, rescale_grad=1.0,
                 clip_gradient=-1.0, **kw):
    jnp = _j()
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient >= 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    new_w = weight - eta * (lr * new_mean / (jnp.sqrt(new_var) + epsilon)
                            + wd * weight)
    return new_w, new_mean, new_var


@register("ftrl_update", mutate=(2, 3))
def ftrl_update(weight, grad, z, n, lr=0.1, lamda1=0.01, beta=1.0, wd=0.0,
                rescale_grad=1.0, clip_gradient=-1.0, **kw):
    jnp = _j()
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient >= 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    new_n = n + jnp.square(g)
    sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
    new_z = z + g - sigma * weight
    new_w = jnp.where(
        jnp.abs(new_z) <= lamda1,
        jnp.zeros_like(weight),
        -(new_z - jnp.sign(new_z) * lamda1) /
        ((beta + jnp.sqrt(new_n)) / lr + wd))
    return new_w, new_z, new_n


@register("rmsprop_update", mutate=(2,))
def rmsprop_update(weight, grad, n, lr=0.001, gamma1=0.95, epsilon=1e-8,
                   wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                   clip_weights=-1.0, **kw):
    jnp = _j()
    g = _prep_grad(grad, rescale_grad, clip_gradient, wd, weight)
    new_n = gamma1 * n + (1 - gamma1) * jnp.square(g)
    new_w = weight - lr * g / jnp.sqrt(new_n + epsilon)
    if clip_weights is not None and clip_weights > 0:
        new_w = jnp.clip(new_w, -clip_weights, clip_weights)
    return new_w, new_n


@register("rmspropalex_update", mutate=(2, 3, 4))
def rmspropalex_update(weight, grad, n, g, delta, lr=0.001, gamma1=0.95,
                       gamma2=0.9, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                       clip_gradient=-1.0, clip_weights=-1.0, **kw):
    jnp = _j()
    gr = _prep_grad(grad, rescale_grad, clip_gradient, wd, weight)
    new_n = gamma1 * n + (1 - gamma1) * jnp.square(gr)
    new_g = gamma1 * g + (1 - gamma1) * gr
    new_delta = gamma2 * delta - lr * gr / jnp.sqrt(
        new_n - jnp.square(new_g) + epsilon)
    new_w = weight + new_delta
    if clip_weights is not None and clip_weights > 0:
        new_w = jnp.clip(new_w, -clip_weights, clip_weights)
    return new_w, new_n, new_g, new_delta


@register("signsgd_update")
def signsgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
                   clip_gradient=-1.0, **kw):
    jnp = _j()
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient >= 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return weight - lr * (jnp.sign(g) + wd * weight)


@register("signum_update", mutate=(2,))
def signum_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                  rescale_grad=1.0, clip_gradient=-1.0, wd_lh=0.0, **kw):
    jnp = _j()
    g = _prep_grad(grad, rescale_grad, clip_gradient, wd, weight)
    new_mom = momentum * mom - (1 - momentum) * g
    new_w = (1 - lr * wd_lh) * weight + lr * jnp.sign(new_mom)
    return new_w, new_mom


@register("lamb_update_phase1", mutate=(2, 3))
def lamb_update_phase1(weight, grad, mean, var, beta1=0.9, beta2=0.999,
                       epsilon=1e-6, t=1, bias_correction=True, wd=0.0,
                       rescale_grad=1.0, clip_gradient=-1.0, **kw):
    """LAMB phase 1; mean/var moments are mutated in place (reference
    FMutateInputs contract, ``optimizer_op.cc``)."""
    jnp = _j()
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient >= 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    m, v = new_mean, new_var
    if bias_correction:
        m = m / (1 - beta1 ** t)
        v = v / (1 - beta2 ** t)
    out = m / (jnp.sqrt(v) + epsilon) + wd * weight
    return out, new_mean, new_var


@register("lamb_update_phase2")
def lamb_update_phase2(weight, g_update, r1, r2, lr=0.01,
                       lower_bound=-1.0, upper_bound=-1.0, **kw):
    jnp = _j()
    r1_ = r1
    r2_ = r2
    if lower_bound is not None and lower_bound >= 0:
        r1_ = jnp.maximum(r1_, lower_bound)
    if upper_bound is not None and upper_bound >= 0:
        r1_ = jnp.minimum(r1_, upper_bound)
    ratio = jnp.where(jnp.logical_and(r1_ > 0, r2_ > 0), r1_ / r2_,
                      jnp.ones_like(r1_))
    return weight - lr * ratio * g_update


# ---------------------------------------------------------------------------
# grouped multi-tensor updates (one dispatch, many params)
# ---------------------------------------------------------------------------

def _scalar_rates(lrs, wds):
    """True when every per-tensor rate is a scalar the fused kernel can
    broadcast: a host number, or a 0-d device value — what the
    eager-jit path turns ``lrs`` into (``registry._DYN_ATTR_NAMES``)
    and what iterating the preloaded_* ops' rate vectors yields.  The
    kernel takes them traced, so neither forces a host sync."""
    import numbers
    return all(isinstance(v, numbers.Number)
               or getattr(v, "shape", None) == ()
               for seq in (lrs, wds) for v in list(seq))


def _use_fused_group(tensors):
    # fused path computes in f32 end-to-end; restrict it to f32 groups
    # so numerics stay bit-identical with the per-tensor loop
    import os
    if os.environ.get("MXNET_FUSED_OPTIMIZER", "1") != "1":
        return False
    import jax.numpy as jnp
    return all(getattr(t, "dtype", None) == jnp.float32
               for t in tensors)


@register("multi_sgd_update", variadic=True, num_outputs=-1)
def multi_sgd_update(data, lrs=None, wds=None, rescale_grad=1.0,
                     clip_gradient=-1.0, num_weights=1, **kw):
    ws = [data[2 * i] for i in range(num_weights)]
    if num_weights > 1 and _use_fused_group(data) \
            and _scalar_rates(lrs, wds):
        from ..kernels.fused_optimizer import fused_multi_sgd
        gs = [data[2 * i + 1] for i in range(num_weights)]
        outs, _ = fused_multi_sgd(ws, gs, lrs=lrs, wds=wds,
                                  rescale_grad=rescale_grad,
                                  clip_gradient=clip_gradient)
        return tuple(outs)
    outs = []
    for i in range(num_weights):
        w, g = data[2 * i], data[2 * i + 1]
        outs.append(sgd_update(w, g, lr=lrs[i], wd=wds[i],
                               rescale_grad=rescale_grad,
                               clip_gradient=clip_gradient))
    return tuple(outs)


@register("multi_sgd_mom_update", variadic=True, num_outputs=-1,
          mutate=lambda attrs: tuple(
              3 * i + 2 for i in range(attrs.get("num_weights", 1))))
def multi_sgd_mom_update(data, lrs=None, wds=None, momentum=0.0,
                         rescale_grad=1.0, clip_gradient=-1.0,
                         num_weights=1, **kw):
    ws = [data[3 * i] for i in range(num_weights)]
    if num_weights > 1 and _use_fused_group(data) \
            and _scalar_rates(lrs, wds):
        from ..kernels.fused_optimizer import fused_multi_sgd
        gs = [data[3 * i + 1] for i in range(num_weights)]
        ms = [data[3 * i + 2] for i in range(num_weights)]
        outs, moms = fused_multi_sgd(ws, gs, ms, lrs=lrs, wds=wds,
                                     momentum=momentum,
                                     rescale_grad=rescale_grad,
                                     clip_gradient=clip_gradient)
        return tuple(outs) + tuple(moms)
    outs = []
    moms = []
    for i in range(num_weights):
        w, g, m = data[3 * i], data[3 * i + 1], data[3 * i + 2]
        nw, nm = sgd_mom_update(w, g, m, lr=lrs[i], momentum=momentum,
                                wd=wds[i], rescale_grad=rescale_grad,
                                clip_gradient=clip_gradient)
        outs.append(nw)
        moms.append(nm)
    # momenta appended after outputs; written back via the mutate contract
    return tuple(outs) + tuple(moms)


@register("mp_lamb_update_phase1", mutate=(2, 3))
def mp_lamb_update_phase1(weight, grad, mean, var, weight32, beta1=0.9,
                          beta2=0.999, epsilon=1e-6, t=1,
                          bias_correction=True, wd=0.0, rescale_grad=1.0,
                          clip_gradient=-1.0, **kw):
    """Mixed-precision LAMB phase 1: the phase-1 math on the f32 master
    weight (reference: mp_lamb_update_phase1)."""
    return lamb_update_phase1(weight32, grad.astype("float32"), mean, var,
                              beta1=beta1, beta2=beta2, epsilon=epsilon,
                              t=t, bias_correction=bias_correction, wd=wd,
                              rescale_grad=rescale_grad,
                              clip_gradient=clip_gradient)


@register("mp_lamb_update_phase2", mutate=(4,))
def mp_lamb_update_phase2(weight, g_update, r1, r2, weight32, lr=0.01,
                          lower_bound=-1.0, upper_bound=-1.0, **kw):
    """Mixed-precision LAMB phase 2: updates the f32 master, emits the
    low-precision weight (reference: mp_lamb_update_phase2)."""
    new32 = lamb_update_phase2(weight32, g_update, r1, r2, lr=lr,
                               lower_bound=lower_bound,
                               upper_bound=upper_bound)
    return new32.astype(weight.dtype), new32


@register("multi_lars")
def multi_lars(lrs, weights_sum_sq, grads_sum_sq, wds, eta=0.001,
               eps=1e-8, rescale_grad=1.0, **kw):
    """LARS layerwise-rate computation over stacked per-layer norms
    (reference: ``optimizer_op.cc`` multi_lars): out lr_i = lr_i *
    eta * ||w_i|| / (||g_i|| * rescale + wd_i * ||w_i|| + eps)."""
    jnp = _j()
    w_norm = jnp.sqrt(weights_sum_sq)
    g_norm = jnp.sqrt(grads_sum_sq) * rescale_grad
    ratio = eta * w_norm / (g_norm + wds * w_norm + eps)
    # the lars ratio applies only when BOTH norms are positive
    # (reference: a zero-grad layer passes its lr through unchanged,
    # not lr*eta*||w||/eps)
    return jnp.where((w_norm > 0) & (g_norm > 0), lrs * ratio, lrs)


@register("preloaded_multi_sgd_update", variadic=True, num_outputs=-1)
def preloaded_multi_sgd_update(data, rescale_grad=1.0, clip_gradient=-1.0,
                               num_weights=1, **kw):
    """multi_sgd_update with per-layer lrs/wds passed as ARRAYS (the
    last two inputs) instead of attrs — avoids re-jitting when LARS
    recomputes rates every step (reference: preloaded_multi_sgd)."""
    # delegate: array lrs/wds index identically to attr lists, and the
    # fused-group fast path applies unchanged
    return multi_sgd_update(data[:-2], lrs=data[-2], wds=data[-1],
                            rescale_grad=rescale_grad,
                            clip_gradient=clip_gradient,
                            num_weights=num_weights)


@register("preloaded_multi_sgd_mom_update", variadic=True, num_outputs=-1,
          mutate=lambda attrs: tuple(
              3 * i + 2 for i in range(attrs.get("num_weights", 1))))
def preloaded_multi_sgd_mom_update(data, momentum=0.0, rescale_grad=1.0,
                                   clip_gradient=-1.0, num_weights=1,
                                   **kw):
    return multi_sgd_mom_update(data[:-2], lrs=data[-2], wds=data[-1],
                                momentum=momentum,
                                rescale_grad=rescale_grad,
                                clip_gradient=clip_gradient,
                                num_weights=num_weights)


@register("multi_sum_sq", variadic=True, num_outputs=1, no_grad=True)
def multi_sum_sq(data, num_arrays=1, **kw):
    """Per-array sum of squares over a group, one fused launch
    (reference: ``contrib/multi_sum_sq.cc`` — feeds ``multi_lars``)."""
    jnp = _j()
    return jnp.stack([jnp.sum(jnp.square(a.astype("float32")))
                      for a in data[:num_arrays]])


@register("reset_arrays", variadic=True, num_outputs=-1,
          mutate=lambda attrs: tuple(range(attrs.get("num_arrays", 1))),
          no_grad=True)
def reset_arrays(data, num_arrays=1, **kw):
    """Zero a group of arrays in one call (reference:
    ``contrib/reset_arrays.cc`` — gradient clearing between
    accumulation windows)."""
    jnp = _j()
    return tuple(jnp.zeros_like(a) for a in data[:num_arrays])


@register("multi_mp_sgd_update", variadic=True, num_outputs=-1,
          mutate=lambda attrs: tuple(
              3 * i + 2 for i in range(attrs.get("num_weights", 1))))
def multi_mp_sgd_update(data, lrs=None, wds=None, rescale_grad=1.0,
                        clip_gradient=-1.0, num_weights=1, **kw):
    """Grouped multi-precision SGD: per weight the triple is
    (weight16, grad16, weight32 master) — reference:
    ``optimizer_op.cc multi_mp_sgd_update``."""
    outs, masters = [], []
    for i in range(num_weights):
        w, g, w32 = data[3 * i], data[3 * i + 1], data[3 * i + 2]
        nw, nw32 = mp_sgd_update(w, g, w32, lr=lrs[i], wd=wds[i],
                                 rescale_grad=rescale_grad,
                                 clip_gradient=clip_gradient)
        outs.append(nw)
        masters.append(nw32)
    return tuple(outs) + tuple(masters)


@register("multi_mp_sgd_mom_update", variadic=True, num_outputs=-1,
          mutate=lambda attrs: tuple(
              v for i in range(attrs.get("num_weights", 1))
              for v in (4 * i + 2, 4 * i + 3)))
def multi_mp_sgd_mom_update(data, lrs=None, wds=None, momentum=0.0,
                            rescale_grad=1.0, clip_gradient=-1.0,
                            num_weights=1, **kw):
    """Grouped multi-precision momentum SGD: quadruples of
    (weight16, grad16, momentum32, weight32)."""
    outs, moms, masters = [], [], []
    for i in range(num_weights):
        w, g, m, w32 = (data[4 * i], data[4 * i + 1], data[4 * i + 2],
                        data[4 * i + 3])
        nw, nm, nw32 = mp_sgd_mom_update(
            w, g, m, w32, lr=lrs[i], momentum=momentum, wd=wds[i],
            rescale_grad=rescale_grad, clip_gradient=clip_gradient)
        outs.append(nw)
        moms.append(nm)
        masters.append(nw32)
    out = list(outs)
    for nm, nw32 in zip(moms, masters):
        out += [nm, nw32]
    return tuple(out)


@register("_contrib_group_adagrad_update",
          aliases=("group_adagrad_update",), mutate=(2,))
def group_adagrad_update(weight, grad, history, lr=0.01,
                         rescale_grad=1.0, clip_gradient=-1.0,
                         epsilon=1e-5, **kw):
    """Group AdaGrad (reference: ``contrib/optimizer_op.cc``): history
    is per-ROW — mean of squared grads over trailing dims — so the
    state is a vector, not a full weight copy."""
    jnp = _j()
    g = grad * rescale_grad
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    if g.ndim > 1:
        h_new = history + jnp.mean(jnp.square(g),
                                   axis=tuple(range(1, g.ndim)),
                                   keepdims=True)
    else:
        h_new = history + jnp.square(g)
    w_new = weight - lr * g / (jnp.sqrt(h_new) + epsilon)
    return w_new, h_new
