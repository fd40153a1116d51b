"""Foundation utilities for mxnet_tpu.

TPU-native re-imagination of MXNet's `python/mxnet/base.py` plus the
dmlc-core foundations (`dmlc/registry.h`, `dmlc/parameter.h`,
`dmlc/logging.h` — see SURVEY.md §2.1 "RecordIO + dmlc-core").

Unlike the reference there is no C ABI boundary here for the compute path:
operator semantics live in the Python/JAX layer and lower to XLA.  What this
module keeps from the reference is the *shape* of the foundation:

* ``MXNetError`` — the single exception type surfaced to users
  (reference: ``MXGetLastError`` / ``check_call``).
* ``Registry`` — a generic name->factory registry
  (reference: ``DMLC_REGISTRY_*`` macros).
* ``Parameter`` descriptors — declarative, introspectable parameter structs
  used to generate operator signatures and docstrings
  (reference: ``DMLC_DECLARE_PARAMETER``).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "MXNetError", "Registry", "Parameter", "ParamSpec", "env_flag", "env_int",
    "string_types", "numeric_types", "integer_types",
]

string_types = (str,)
numeric_types = (float, int)
integer_types = (int,)


class MXNetError(RuntimeError):
    """Framework-level error, mirrors the reference's ``MXNetError``."""


def place_compile_cache() -> None:
    """Give JAX's persistent compilation cache a home that can be placed
    from outside.  ``JAX_COMPILATION_CACHE_DIR``, if set, is JAX's own
    setting and nothing is touched; otherwise the cache goes to
    ``<checkout>/.jax_cache`` — a fixed path derived from this package's
    location alone (the path is part of the cache key, so it must not
    move between runs or differ between a parent and the workers it
    spawns).  A process pinned to the CPU backend gets none: the cache
    is for the chip's minutes-long compiles, and XLA:CPU reloads a
    cached executable with a machine-feature warning per entry.  Called
    once, from the package ``__init__``; touches no backend."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    if jax.config.jax_platforms == "cpu":
        return
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))


def env_flag(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() not in ("0", "false", "off", "")


def env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return int(v)
    except ValueError:
        return default


class Registry:
    """Generic name→object registry (reference: ``dmlc::Registry``).

    Used for optimizers, initializers, metrics, data iterators, kvstore
    backends — every pluggable family in the framework.
    """

    _registries: Dict[str, "Registry"] = {}

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: Dict[str, Any] = {}
        Registry._registries[kind] = self

    @classmethod
    def get(cls, kind: str) -> "Registry":
        if kind not in cls._registries:
            cls._registries[kind] = Registry.__new__(Registry)
            cls._registries[kind].kind = kind
            cls._registries[kind]._entries = {}
        return cls._registries[kind]

    def register(self, name: Optional[str] = None, aliases: Optional[List[str]] = None):
        def _reg(obj):
            key = (name or obj.__name__).lower()
            self._entries[key] = obj
            for a in (aliases or []):
                self._entries[a.lower()] = obj
            return obj
        return _reg

    def find(self, name: str) -> Any:
        key = name.lower()
        if key not in self._entries:
            raise MXNetError(
                "Cannot find %s %r. Registered: %s"
                % (self.kind, name, sorted(self._entries)))
        return self._entries[key]

    def create(self, name: str, *args, **kwargs) -> Any:
        return self.find(name)(*args, **kwargs)

    def list(self) -> List[str]:
        return sorted(self._entries)

    def __contains__(self, name: str) -> bool:
        return name.lower() in self._entries


class ParamSpec:
    """One declared parameter field (reference: ``dmlc::parameter::FieldEntry``)."""

    __slots__ = ("name", "type", "default", "required", "doc", "choices")

    def __init__(self, name, type=None, default=None, required=False, doc="",
                 choices=None):
        self.name = name
        self.type = type
        self.default = default
        self.required = required
        self.doc = doc
        self.choices = choices

    def validate(self, value):
        if self.choices is not None and value not in self.choices:
            raise MXNetError(
                "Parameter %s=%r not in allowed choices %s"
                % (self.name, value, self.choices))
        return value


class Parameter:
    """Declarative parameter struct (reference: ``dmlc::Parameter<T>``).

    Subclasses declare fields as class attributes of type :class:`ParamSpec`.
    ``init(**kwargs)`` validates and fills defaults; ``__DICT__`` style
    introspection drives generated docstrings.
    """

    @classmethod
    def fields(cls) -> Dict[str, ParamSpec]:
        out = {}
        for klass in reversed(cls.__mro__):
            for k, v in vars(klass).items():
                if isinstance(v, ParamSpec):
                    out[k] = v
        return out

    @classmethod
    def init(cls, **kwargs) -> Dict[str, Any]:
        fields = cls.fields()
        out = {}
        for name, spec in fields.items():
            if name in kwargs:
                out[name] = spec.validate(kwargs.pop(name))
            elif spec.required:
                raise MXNetError("Required parameter %s missing" % name)
            else:
                out[name] = spec.default
        if kwargs:
            raise MXNetError("Unknown parameters: %s" % sorted(kwargs))
        return out


class _ThreadLocalStack(threading.local):
    def __init__(self):
        self.stack: List[Any] = []


def classproperty(f):
    class _cp:
        def __get__(self, obj, owner):
            return f(owner)
    return _cp()
