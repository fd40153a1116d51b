"""mxnet_tpu — a TPU-native deep-learning framework with MXNet's API.

An imperative, asynchronously-scheduled mutable NDArray API, Gluon
(``Block``/``HybridBlock`` with ``hybridize()`` compiling to a single XLA
computation), autograd, the Symbol/Module API with a bucketing executor, a
RecordIO data pipeline and a KVStore data-parallel interface — with XLA/PjRt
as the execution substrate instead of mshadow/CUDA.  See SURVEY.md for the
reference blueprint.

Usage mirrors the reference::

    import mxnet_tpu as mx
    x = mx.nd.zeros((2, 3), ctx=mx.tpu())
"""
__version__ = "0.1.0"

from .base import MXNetError, place_compile_cache
place_compile_cache()
del place_compile_cache
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, \
    num_gpus, num_tpus
from . import engine
from . import random
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd

# Submodules that layer on the core.  This list grows as subsystems land;
# the package stays importable at every commit.
from . import initializer      # noqa: E402
from . import optimizer        # noqa: E402
from . import lr_scheduler     # noqa: E402
from . import metric           # noqa: E402
from . import kvstore          # noqa: E402
from . import kvstore as kv    # noqa: E402
from . import recordio         # noqa: E402
from . import io               # noqa: E402
from . import image            # noqa: E402
from . import gluon            # noqa: E402
from . import parallel         # noqa: E402
from . import models           # noqa: E402
from . import symbol           # noqa: E402
from . import symbol as sym    # noqa: E402
from . import callback         # noqa: E402
from . import model            # noqa: E402
from . import module           # noqa: E402
from . import module as mod    # noqa: E402
from . import contrib          # noqa: E402
from . import operator         # noqa: E402
from . import name             # noqa: E402
from . import attribute       # noqa: E402
from .attribute import AttrScope  # noqa: E402
from . import visualization    # noqa: E402
from . import visualization as viz  # noqa: E402
from . import util             # noqa: E402
from . import numpy as np      # noqa: E402
from . import numpy_extension as npx  # noqa: E402
from . import profiler         # noqa: E402
from . import obs              # noqa: E402
from . import runtime          # noqa: E402
from . import library          # noqa: E402
from . import rtc              # noqa: E402
from . import monitor          # noqa: E402
from .monitor import Monitor   # noqa: E402
from . import test_utils       # noqa: E402
