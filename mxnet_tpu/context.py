"""Device context abstraction.

Reference: ``python/mxnet/context.py`` (see SURVEY.md §2.2 "base/context" —
"``mx.tpu()`` goes here").  TPU-native design: a :class:`Context` maps onto a
concrete ``jax.Device``.  ``tpu(i)`` is the first-class accelerator context;
``gpu(i)`` is accepted as an alias for portability of reference-era scripts
and resolves to the accelerator backend too.  ``cpu()`` maps to the JAX CPU
backend (always present).

Under the test harness (``JAX_PLATFORMS=cpu`` with
``jax_num_cpu_devices=N``) ``tpu(i)`` resolves to virtual host device ``i``
so multi-device code paths are exercisable without hardware.
"""
from __future__ import annotations

import threading
from typing import List, Optional

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_tpus", "num_gpus", "device", "accel_platform",
           "require_tpu", "host_tpu_chips", "one_chip_env",
           "held_accelerator"]


def accel_platform() -> str:
    """Platform of the default JAX backend: ``"tpu"`` on a chip host,
    ``"cpu"`` under the test harness.  ``num_tpus()`` cannot say this —
    it counts ``tpu(i)``-addressable devices, and the harness's virtual
    CPU devices are addressable as ``tpu(i)``."""
    import jax
    return jax.devices()[0].platform


def require_tpu(what: str) -> None:
    """Measurement and oracle entry points call this first: a number
    taken on the CPU backend must never be printed under the name of a
    device metric, so off-chip they exit non-zero instead of falling
    back."""
    plat = accel_platform()
    if plat != "tpu":
        raise SystemExit(
            "%s needs a TPU: JAX found platform %r and will not fall "
            "back to it" % (what, plat))


# -- one process per chip -------------------------------------------------
# A TPU chip belongs to one process at a time: a process that has touched
# JAX holds every chip it can see, and a child that needs one then fails
# ("The TPU is already in use by process with pid N").  A parent that
# starts chip-using children therefore stays off the chips itself and
# hands each child exactly one.  These three helpers initialise no
# backend, so a launcher can call them.

def host_tpu_chips() -> int:
    """TPU chips on this host, counted from the device nodes libtpu
    opens (``/dev/vfio/N`` on v5e; ``/dev/accelN`` on older hosts)."""
    import glob
    return len(glob.glob("/dev/vfio/[0-9]*")
               + glob.glob("/dev/accel[0-9]*"))


def one_chip_env(index: int) -> dict:
    """Environment that lets a child process claim exactly chip
    ``index`` of this host and leaves the others free.  The set libtpu
    0.0.34 honours: four such children ran side by side on a v5e 2x2
    host, each seeing one device (PR 21 four-chip run); without it the
    first child takes all four and the second fails on libtpu's
    lockfile."""
    return {"TPU_VISIBLE_CHIPS": str(int(index)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def held_accelerator():
    """Platform name of an accelerator backend THIS process has
    already initialised — and whose chips it therefore holds — or None.
    Never initialises one."""
    # the registry of initialised backends has no public reader that
    # does not itself initialise them
    from jax._src import xla_bridge
    return next((p for p in xla_bridge._backends if p != "cpu"), None)


class Context:
    """Execution device descriptor (reference: ``mxnet.context.Context``).

    ``Context('tpu', 0)`` pins work to accelerator chip 0.  Arithmetic on
    arrays in different contexts is an error, matching reference semantics
    (explicit ``copyto``/``as_in_context`` moves data).
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in Context.devstr2type:
            raise MXNetError("Unknown device type %r" % device_type)
        # gpu is accepted as an alias for the accelerator (tpu) backend so
        # reference-era scripts run unchanged.
        self.device_type = device_type
        self.device_id = device_id

    @property
    def device_typeid(self) -> int:
        return Context.devstr2type[self.device_type]

    # -- jax integration ---------------------------------------------------
    @property
    def jax_device(self):
        import jax
        dt = self.device_type
        # a Context addresses THIS process's devices: under multi-host
        # (jax.distributed) jax.devices() lists the whole cluster, and
        # placing an eager array on another host's device is an error —
        # the reference's Context is likewise process-local (each worker
        # sees its own gpu(0..n)); cross-host placement happens only
        # through mesh shardings.
        if dt in ("cpu", "cpu_pinned", "cpu_shared"):
            # local_devices(backend=...) keeps the cpu path process-local
            # too — jax.devices("cpu") is cluster-global under multi-host
            # and could hand a non-zero worker another host's CPU device.
            # (raises RuntimeError where no CPU backend exists — a cpu
            # context never silently lands on the accelerator)
            devs = [d for d in jax.local_devices()
                    if d.platform == "cpu"] \
                or jax.local_devices(backend="cpu")
            return devs[self.device_id % len(devs)]
        # tpu/gpu → accelerator backend; under the CPU test harness this is
        # the virtual host-device array.
        devs = jax.local_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                "Context %s: device_id %d out of range (%d devices visible)"
                % (self, self.device_id, len(devs)))
        return devs[self.device_id]

    # -- identity ----------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *args):
        Context._default_ctx.stack.pop()

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return _DEFAULT


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    """The TPU context — the reason this framework exists."""
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias context for reference-era scripts; resolves to the accelerator
    backend (TPU) at runtime."""
    return Context("gpu", device_id)


def device(dev: str) -> Context:
    """Parse 'tpu(0)' / 'cpu' style strings."""
    dev = dev.strip()
    if "(" in dev:
        name, rest = dev.split("(", 1)
        return Context(name.strip(), int(rest.rstrip(")")))
    return Context(dev, 0)


_DEFAULT = Context("cpu", 0)


def current_context() -> Context:
    return Context.default_ctx()


def num_tpus() -> int:
    """Process-local accelerator count — matches ``Context.jax_device``
    semantics so ``[mx.tpu(i) for i in range(mx.num_gpus())]`` stays
    valid on every worker of a multi-host job (the reference's
    ``num_gpus()`` is likewise per-worker)."""
    import jax
    try:
        devs = jax.local_devices()
    except Exception:
        return 0
    return len(devs)


def num_gpus() -> int:
    """Reference-compat: reports accelerator count (TPU chips here)."""
    return num_tpus()
