"""Sharded data-parallel training for Gluon blocks.

Reference semantics: ``DataParallelExecutorGroup`` + KVStore allreduce
(SURVEY.md §2.4 row 1, §3.4).  TPU-native mechanism: ONE jitted train step
over a Mesh — params placed replicated, batch sharded over ``dp`` — and
XLA GSPMD emits the gradient psum over ICI.  This subsumes
``split_and_load`` + push/pull: no Python-level per-device loop, no
explicit collective calls.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

from ..base import MXNetError

__all__ = ["DataParallelTrainer"]


class DataParallelTrainer:
    """Compile a (block, loss, optimizer) triple into one sharded step.

    Usage::

        mesh = make_mesh({"dp": 8})
        dpt  = DataParallelTrainer(net, loss_fn, "sgd",
                                   {"learning_rate": 0.1}, mesh)
        loss = dpt.step(data_batch, label_batch)   # batch sharded on dp

    The Gluon block's parameters are read once into a pytree; updates run
    inside the jitted step (fused with the backward, like the reference's
    engine-overlapped ``*_update`` ops); ``sync_back()`` writes final
    values into the Parameter buffers for checkpointing.
    """

    def __init__(self, block, loss_fn, optimizer="sgd",
                 optimizer_params=None, mesh=None, grad_clip=None,
                 amp=False, shard_optimizer=False):
        import jax
        import optax
        from .mesh import default_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.block = block
        self.loss_fn = loss_fn
        # amp=True: the contrib/amp per-op cast hook runs during the
        # traced forward — MXU-bound ops (conv/FC/matmul) take bfloat16
        # inputs while the FP32_OPS list (BatchNorm, softmax, reductions,
        # losses) stays float32; params remain f32 masters.  No loss
        # scaler needed, bf16 exponent range matches f32.
        self.amp = amp
        self.mesh = mesh if mesh is not None else default_mesh()
        optimizer_params = dict(optimizer_params or {})
        lr = optimizer_params.pop("learning_rate", 0.01)
        momentum = optimizer_params.pop("momentum", 0.0)
        wd = optimizer_params.pop("wd", 0.0)
        if optimizer == "sgd":
            tx = optax.sgd(lr, momentum=momentum)
            if wd:
                tx = optax.chain(optax.add_decayed_weights(wd), tx)
        elif optimizer == "adam":
            tx = optax.adam(lr)
        elif optimizer == "adamw":
            tx = optax.adamw(lr, weight_decay=wd)
        elif optimizer == "lamb":
            tx = optax.lamb(lr, weight_decay=wd)
        else:
            raise MXNetError("DataParallelTrainer: unknown optimizer %r"
                             % optimizer)
        if grad_clip:
            tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)
        self.tx = tx

        self._param_objs = list(block.collect_params().values())
        # on a trivial (1-device) mesh, committing arrays to a
        # NamedSharding routes execution through the SPMD-partitioned
        # path, which buys nothing on one device (rounds 1-5 measured
        # it much slower; not re-measured on the current machine) — so
        # skip all sharding commits
        self._trivial = self.mesh.size == 1
        self._rep = None if self._trivial else NamedSharding(self.mesh, P())
        # ZeRO-1: optimizer state sharded over the data axis — 'dp' if
        # present, else the mesh's first axis, matching how the batch is
        # sharded (SURVEY.md §2.4 — the PS server-side optimizer update)
        self._data_axis = ("dp" if "dp" in self.mesh.axis_names
                           else self.mesh.axis_names[0])
        self._shard_opt = (shard_optimizer
                           and self.mesh.shape[self._data_axis] > 1)
        self._batch_sharding = None
        self._state = None
        self._jit_step = None
        self._multi_jit = {}

    # -- param pytree <-> gluon Parameters --------------------------------
    def _gather_params(self):
        import jax
        vals = [p.data()._data for p in self._param_objs]
        if self._trivial:
            # Guardrail (round 4): on a trivial mesh no sharding commit
            # happens, so params initialized without ctx=mx.tpu() would
            # keep the whole train step on the HOST backend — resnet18
            # silently ran at 25 s/step on this 1-vCPU box while
            # looking like a TPU run.  Move host-platform params onto
            # the mesh device instead (one-time transfer, same place
            # sync_back reads from).
            dev = self.mesh.devices.ravel()[0]
            if dev.platform != "cpu":
                moved = False
                out = []
                for v in vals:
                    vdev = next(iter(v.devices()))
                    if vdev.platform == "cpu":
                        out.append(jax.device_put(v, dev))
                        moved = True
                    else:
                        out.append(v)
                if moved:
                    import logging
                    logging.getLogger(__name__).info(
                        "DataParallelTrainer: moved host-resident "
                        "params onto %s (initialize with ctx=mx.tpu() "
                        "to avoid the transfer)", dev)
                return out
            return vals
        from .multihost import host_staged_put
        return [host_staged_put(v, self._rep) for v in vals]

    def sync(self):
        """Block until every queued step has fully executed (the loss
        buffer alone can materialize before the tail of the donated-state
        pipeline — benchmark timing must drain the params too).

        Besides ``block_until_ready`` it fetches one element of the
        newest state output: a timed region that ends here ends with
        the result on the host, whatever the transport reports about
        readiness — the analog of the reference engine's ``WaitForAll``
        (SURVEY.md §3.1 sync points)."""
        import jax
        if self._state is not None:
            jax.block_until_ready(self._state)
            leaf = jax.tree_util.tree_leaves(self._state)[0]
            jax.device_get(leaf.ravel()[:1])
        return self

    def sync_back(self):
        """Write trained values back into the Gluon Parameters."""
        if self._state is None:
            return
        params = self._state[0]
        for p, v in zip(self._param_objs, params):
            for c in p._data:
                p._data[c]._set_data(v)

    # -- the step ----------------------------------------------------------
    def _build(self, data, label):
        import jax
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..gluon.block import _CachedOp
        from .. import autograd

        block = self.block
        loss_fn = self.loss_fn
        params = self._param_objs
        tx = self.tx

        # trace block+loss into a pure function of (param_list, data, label)
        from ..ndarray.ndarray import NDArray
        from collections import OrderedDict
        from ..gluon.block import _TRACE_STATE

        # resolve any deferred-init parameter shapes before gathering
        if hasattr(block, "_resolve_deferred"):
            block._resolve_deferred(NDArray(data))

        amp = self.amp
        # filled during tracing: which params an op mutated in-place
        # (BatchNorm running stats via the mutate=(3,4) contract); those
        # carry their forward-computed value instead of an optimizer step.
        mutated_flags: List[bool] = []

        def pure_loss(param_vals, d, l):
            import jax.numpy as jnp
            from .. import random as mxrand
            from ..ops import registry as _registry
            mxrand.push_trace_key(jax.random.PRNGKey(0))
            _TRACE_STATE.active = getattr(_TRACE_STATE, "active", 0) + 1
            saved = [(p, dict(p._data)) for p in params]
            prev_hook = _registry._CAST_HOOK
            try:
                if amp:
                    from ..contrib.amp.amp import _make_hook
                    _registry.set_cast_hook(_make_hook("bfloat16"))
                wrapped = [NDArray(v) for v in param_vals]
                for p, w in zip(params, wrapped):
                    c = next(iter(p._data))
                    p._data = OrderedDict({c: w})
                with autograd._scope(False, True):
                    out = block.forward_raw(NDArray(d))
                    loss = loss_fn(out, NDArray(l))
                # capture in-place mutations (aux states) before restore
                del mutated_flags[:]
                new_vals = []
                for w, orig in zip(wrapped, param_vals):
                    mutated_flags.append(w._data is not orig)
                    new_vals.append(w._data)
                return loss._data.astype(jnp.float32).mean(), new_vals
            finally:
                _registry.set_cast_hook(prev_hook)
                for p, old in saved:
                    p._data = OrderedDict(old)
                _TRACE_STATE.active -= 1
                mxrand.pop_trace_key()

        def step(state, d, l):
            pvals, opt_state = state
            (loss, new_vals), grads = jax.value_and_grad(
                pure_loss, has_aux=True)(pvals, d, l)
            updates, opt_state = tx.update(grads, opt_state, pvals)
            pvals = optax.apply_updates(pvals, updates)
            # mutated aux (e.g. BN moving stats) take their in-forward
            # value — the reference's engine applies the same write
            pvals = [nv.astype(pv.dtype) if m else pv
                     for pv, nv, m in zip(pvals, new_vals,
                                          mutated_flags)]
            return (pvals, opt_state), loss

        pvals = self._gather_params()
        if self._shard_opt:
            from .mesh import init_sharded_opt_state
            opt_state = init_sharded_opt_state(
                self.tx, pvals, self.mesh, axis=self._data_axis)
        elif self._trivial:
            opt_state = self.tx.init(pvals)
        else:
            opt_state = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, self._rep),
                self.tx.init(pvals))
        self._state = (pvals, opt_state)
        self._batch_sharding = None if self._trivial else NamedSharding(
            self.mesh, P(self._data_axis))
        self._step_fn = step
        self._jit_step = jax.jit(step, donate_argnums=(0,))
        self._multi_jit = {}

    def _place_batch(self, d, l):
        """Batch placement: shard over the mesh, or (trivial mesh) move
        host arrays to the accelerator so they match the params the
        round-4 guardrail placed there."""
        import jax
        if not self._trivial:
            return (jax.device_put(d, self._batch_sharding),
                    jax.device_put(l, self._batch_sharding))
        dev = self.mesh.devices.ravel()[0]
        if dev.platform != "cpu":
            def plat(x):                 # numpy input counts as host
                try:
                    return next(iter(x.devices())).platform
                except AttributeError:
                    return "cpu"
            if plat(d) == "cpu":
                d = jax.device_put(d, dev)
            if plat(l) == "cpu":
                l = jax.device_put(l, dev)
        return d, l

    def step(self, data, label):
        """One data-parallel training step; returns scalar loss."""
        from ..ndarray.ndarray import NDArray, _wrap
        d = data._data if isinstance(data, NDArray) else data
        l = label._data if isinstance(label, NDArray) else label
        if self._jit_step is None:
            self._build(d, l)
        d, l = self._place_batch(d, l)
        self._state, loss = self._jit_step(self._state, d, l)
        return _wrap(loss)

    def run_steps(self, data, label, steps=None):
        """Run many training steps inside ONE jitted device loop.

        Per-dispatch latency (graph launch, host bookkeeping) bounds
        the step rate of :meth:`step` from the host side (its size on
        the current machine is not measured — ROADMAP A2).  The
        TPU-native cure is the device
        loop: ``lax.scan`` over the train step, one dispatch for K steps
        (the same shape as the reference's engine-level op bulking,
        ``MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN`` — SURVEY.md §3.3 — and
        classic TPU infeed training loops).

        Two data modes:

        * ``steps=None`` — *superbatch*: ``data``/``label`` carry a
          leading ``K`` axis (``(K, batch, ...)``); step ``i`` trains on
          slice ``i``.
        * ``steps=K`` — *reuse*: the single batch is reused for every
          step (synthetic benchmarking).

        Returns the per-step losses as an NDArray of shape ``(K,)``.
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..ndarray.ndarray import NDArray, _wrap
        d = data._data if isinstance(data, NDArray) else data
        l = label._data if isinstance(label, NDArray) else label
        superbatch = steps is None
        if superbatch:
            if d.shape[0] != l.shape[0]:
                raise MXNetError("run_steps: superbatch leading dims "
                                 "disagree: %r vs %r"
                                 % (d.shape, l.shape))
            steps = int(d.shape[0])
        if self._jit_step is None:
            self._build(d[0] if superbatch else d,
                        l[0] if superbatch else l)
        key = (steps, superbatch)
        if key not in self._multi_jit:
            step_fn = self._step_fn

            def multi(state, d, l):
                def body(st, xs):
                    dd, ll = (d, l) if xs is None else xs
                    return step_fn(st, dd, ll)
                return jax.lax.scan(
                    body, state,
                    (d, l) if superbatch else None, length=steps)

            self._multi_jit[key] = jax.jit(multi, donate_argnums=(0,))
        if self._trivial:
            d, l = self._place_batch(d, l)
        elif superbatch:
            sb = NamedSharding(
                self.mesh, P(None, self._data_axis))
            d = jax.device_put(d, sb)
            l = jax.device_put(l, sb)
        else:
            d = jax.device_put(d, self._batch_sharding)
            l = jax.device_put(l, self._batch_sharding)
        self._state, losses = self._multi_jit[key](self._state, d, l)
        return _wrap(losses)
