"""Device-mesh utilities — the substrate for all parallelism.

No reference counterpart: MXNet 1.x scales via per-device replicas + NCCL
(SURVEY.md §2.4).  The TPU-native design replaces that with one logical
array sharded over a ``jax.sharding.Mesh``; XLA GSPMD inserts the ICI
collectives (psum/all-gather/reduce-scatter) that ``kvstore_nccl.h``
issued by hand.  Axes follow scaling-book conventions:

* ``dp`` — data parallel (batch dim)
* ``tp`` — tensor parallel (hidden dims of attention/FFN weights)
* ``pp`` — pipeline stages
* ``sp`` — sequence/context parallel (ring attention)
* ``ep`` — expert parallel (MoE)
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

from ..base import MXNetError

__all__ = ["make_mesh", "default_mesh", "serving_mesh", "current_mesh",
           "mesh_scope", "live_axis"]

_CURRENT = []


def make_mesh(shape: Optional[dict] = None, devices=None):
    """Create a Mesh.  ``shape`` maps axis name -> size; sizes must
    multiply to the device count.  ``{"dp": -1}`` means "all devices"."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if not shape:
        shape = {"dp": n}
    names = list(shape.keys())
    sizes = list(shape.values())
    n_auto = sizes.count(-1)
    if n_auto > 1:
        raise MXNetError("At most one mesh axis may be -1")
    if n_auto == 1:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        if n % known:
            raise MXNetError("Mesh %s does not divide %d devices"
                             % (shape, n))
        sizes[sizes.index(-1)] = n // known
    total = 1
    for s in sizes:
        total *= s
    if total != n:
        raise MXNetError("Mesh %s needs %d devices but %d are visible"
                         % (dict(zip(names, sizes)), total, n))
    dev_array = np.array(devices).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def default_mesh():
    """All devices on one ``dp`` axis."""
    return make_mesh()


def serving_mesh(tp=1, devices=None):
    """Serving-shaped mesh: one ``tp`` axis over the first ``tp``
    devices.  The serving engine is single-program (no batch axis to
    data-parallelize inside one replica — scale-out is the
    ``ServingCluster``'s job), so its mesh is one tensor-parallel axis
    and nothing else; the megatron rules in ``models/transformer.py``
    and the engine's pool/row specs (``serving/engine.py
    step_input_specs``) name only ``tp``.  Devices beyond ``tp`` stay
    free for other replicas/work."""
    import jax

    if devices is None:
        devices = jax.devices()
    if tp < 1:
        raise MXNetError("serving_mesh: tp must be >= 1, got %r"
                         % (tp,))
    if tp > len(devices):
        raise MXNetError(
            "serving_mesh: tp=%d needs %d devices but only %d are "
            "visible (CPU hosts: jax.config.update("
            "'jax_num_cpu_devices', N) before a backend initializes "
            "— the virtual mesh the MULTICHIP dry-runs use)"
            % (tp, tp, len(devices)))
    return make_mesh({"tp": tp}, devices=list(devices)[:tp])


class mesh_scope:
    """Context manager setting the current mesh."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        _CURRENT.append(self.mesh)
        return self.mesh

    def __exit__(self, *a):
        _CURRENT.pop()


def current_mesh():
    return _CURRENT[-1] if _CURRENT else None


def live_axis(mesh, name):
    """``name`` if the mesh has that axis AND it actually partitions
    (size > 1), else None.  Sharding constraints over trivial axes are
    semantically no-ops but not guaranteed free (rounds 1-5 measured a
    copy per constraint; not re-measured on the current machine), so
    constraint sites build specs from live axes only."""
    if mesh is None or name not in mesh.axis_names:
        return None
    return name if mesh.shape[name] > 1 else None


def zero1_sharding(leaf, mesh, axis="dp", base=None):
    """ZeRO-1 placement for one optimizer-state leaf: COMPOSE the data
    axis onto the param's own sharding (SURVEY.md §2.4 — the PS
    server-side optimizer update).

    ``base`` is the param's PartitionSpec/NamedSharding (tp etc.).  The
    dp axis is added on the first dimension the base leaves free and
    that divides — keeping the tp entries intact.  Dropping them (the
    round-1 design, P(dp, None, ...)) forced GSPMD into "Involuntary
    full rematerialization" on every gradient all-reduce: the grads
    arrive tp-sharded and the tp→dp transition has no efficient
    collective.  With the composed spec the transition is a plain
    reduce-scatter on the free dim.  Leaves where no dim divides keep
    the base sharding (replicated over dp — no ZeRO for that leaf, but
    no reshard either)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if hasattr(base, "spec"):
        base = base.spec
    ndim = getattr(leaf, "ndim", 0)
    entries = list(base) if base is not None else []
    entries = entries[:ndim] + [None] * (ndim - len(entries))
    # FSDP (round 19): the param's own sharding may already carry the
    # data axis — then the moment takes the param placement verbatim
    # (state is ALREADY ÷dp; composing dp twice would be a spec error)
    for e in entries:
        if e == axis or (isinstance(e, tuple) and axis in e):
            return NamedSharding(mesh, P(*entries))
    n = mesh.shape[axis]
    for i in range(ndim):
        if entries[i] is None and leaf.shape[i] > 0 \
                and leaf.shape[i] % n == 0:
            entries[i] = axis
            break
    return NamedSharding(mesh, P(*entries))


def opt_state_shardings(tx, params, mesh, axis="dp",
                        param_shardings=None):
    """Placement tree for ``tx.init(params)`` under ZeRO-1/FSDP:
    param-shaped state leaves compose the data axis with the param's
    own sharding (or take it verbatim when it already carries the
    axis — the FSDP case); non-param leaves (step counts) replicate.
    ``params`` may be live arrays or abstract shapes — round 19 also
    hands this tree to ``jax.jit(in_shardings=...)`` so state
    donation is provable at lowering."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    shapes = jax.eval_shape(tx.init, params)
    if param_shardings is None:
        return jax.tree_util.tree_map(
            lambda l: zero1_sharding(l, mesh, axis=axis), shapes)
    import optax
    rep = NamedSharding(mesh, P())
    return optax.tree_map_params(
        tx,
        lambda l, s: zero1_sharding(l, mesh, axis=axis, base=s),
        shapes, param_shardings,
        transform_non_params=lambda l: rep)


def init_sharded_opt_state(tx, params, mesh, axis="dp",
                           param_shardings=None):
    """Initialize an optax state directly INTO its ZeRO-1 shards —
    init-then-reshard would peak at full replicated size, defeating the
    reason to shard.  ``param_shardings`` (a tree aligned with
    ``params``) lets param-shaped state leaves compose dp with the
    param's own tp/sp sharding; non-param leaves (step counts)
    replicate."""
    import jax

    placements = opt_state_shardings(tx, params, mesh, axis=axis,
                                     param_shardings=param_shardings)
    return jax.jit(tx.init, out_shardings=placements)(params)
