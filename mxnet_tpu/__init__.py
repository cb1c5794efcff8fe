"""mxnet_tpu — a TPU-native framework with MXNet 1.2 capabilities.

Structure mirrors the reference Python package (python/mxnet/__init__.py)
while the implementation is idiomatic jax/XLA/pjit/Pallas throughout.
"""
import time as _time
_IMPORT_T0 = _time.perf_counter()   # first: mxnet_import_seconds, below

from .libinfo import __version__  # noqa: F401
from .base import MXNetError  # noqa: F401
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus  # noqa: F401
from . import base  # noqa: F401
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from . import random  # noqa: F401
from . import autograd  # noqa: F401
from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from .symbol import Symbol  # noqa: F401
from . import executor  # noqa: F401
from .executor import Executor  # noqa: F401
from . import attribute  # noqa: F401
from .attribute import AttrScope  # noqa: F401
from . import name  # noqa: F401
from .name import NameManager, Prefix  # noqa: F401
from . import initializer  # noqa: F401
from . import initializer as init  # noqa: F401
from . import optimizer  # noqa: F401
from .optimizer import Optimizer  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import metric  # noqa: F401
from . import callback  # noqa: F401
from . import gluon  # noqa: F401
from . import parallel  # noqa: F401
from . import kvstore  # noqa: F401
from . import kvstore as kv  # noqa: F401
from . import kvstore_server  # noqa: F401
from . import registry  # noqa: F401
from . import misc  # noqa: F401
from . import executor_manager  # noqa: F401
from . import model  # noqa: F401
from . import module  # noqa: F401
from . import module as mod  # noqa: F401
from . import io  # noqa: F401
from . import recordio  # noqa: F401
from . import rnn  # noqa: F401
from . import image  # noqa: F401
from . import profiler  # noqa: F401
from . import monitor  # noqa: F401
from .monitor import Monitor  # noqa: F401
from . import visualization  # noqa: F401
from . import visualization as viz  # noqa: F401
from . import engine  # noqa: F401
from . import operator  # noqa: F401
from .operator import CustomOp, CustomOpProp  # noqa: F401
from . import log  # noqa: F401
from . import rtc  # noqa: F401
from . import contrib  # noqa: F401
from . import config  # noqa: F401
from . import compile_cache  # noqa: F401
from . import telemetry  # noqa: F401
from . import torch  # noqa: F401  (the pytorch bridge, reference mx.th)
from .torch import TorchModule as _TorchModule
th = _TorchModule("torch")
from . import predictor  # noqa: F401
from .predictor import Predictor  # noqa: F401
from . import checkpoint  # noqa: F401
from . import serving  # noqa: F401
from . import test_utils  # noqa: F401

# graftsan runtime sanitizers: arm at import when any MXNET_SAN* knob
# is set, so subprocess workloads (bench legs, CI smoke) need no code —
# the same pure-env-knob convention telemetry and checkpoints follow.
# All knobs off costs these five config reads once, then one boolean
# per instrumentation site (mxnet_tpu/analysis/sanitizers/hooks.py).
if any(config.get(_k) for _k in (
        "MXNET_SAN", "MXNET_SAN_RECOMPILE", "MXNET_SAN_HOST_SYNC",
        "MXNET_SAN_LOCK_ORDER", "MXNET_SAN_DONATION")):
    from .analysis import sanitizers as _sanitizers
    _sanitizers.install()

# graftfault: arm the fault-injection plan at import when
# MXNET_FAULT_PLAN is set — drills and chaos soaks configure child
# processes purely through the environment, same convention as the
# sanitizers above.  Unset costs one config read here and one boolean
# per instrumented site (mxnet_tpu/fault/hooks.py).
from . import fault  # noqa: F401,E402
if config.get("MXNET_FAULT_PLAN"):
    fault.install()

# last: what importing this package's own modules cost (jax, where the
# caller had imported it already, is not in it) — a gauge once telemetry
# is enabled; the span ring is not armed while the package imports
telemetry.note_import_seconds(_time.perf_counter() - _IMPORT_T0)
