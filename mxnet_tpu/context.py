"""Device context.

Reference: ``python/mxnet/context.py`` (Context class, cpu()/gpu(),
thread-local default-context stack).  TPU-native redesign: a Context is a
named view onto a ``jax.Device``.  ``tpu()`` is the accelerator context
(the north-star `mx.tpu()` from BASELINE.json); ``gpu()`` is aliased to
the accelerator so reference scripts written for `mx.gpu(0)` run
unmodified on TPU.  ``cpu()`` maps to the host platform.

Unlike the reference there is no per-device stream/thread state here —
placement is expressed to XLA via ``jax.device_put`` / shardings, and
the Context only names the device.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


def _jax():
    import jax

    return jax


class Context:
    """Device context (reference: python/mxnet/context.py:23)."""

    # keep the reference's devtype enum, extended with tpu
    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devid2type = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devtype2id:
                raise MXNetError("unknown device type %s" % device_type)
            self.device_typeid = self.devtype2id[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return self.devid2type[self.device_typeid]

    # -- jax integration ---------------------------------------------------
    @property
    def jax_device(self):
        """The jax.Device this context names."""
        jax = _jax()
        dt = self.device_type
        if dt in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = jax.devices("cpu")
        else:
            # accelerator: whatever jax's default backend exposes; `gpu`
            # is an alias so reference scripts run unmodified.  In a
            # CPU-only process (the tests) that backend IS the host, so
            # a tpu context names a host device — entry points that
            # report numbers say which platform they ran on
            # (common/fit.py's header line, perfbench/run.py's "device").
            devs = jax.devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                "context %s: device_id %d out of range (%d devices)"
                % (self, self.device_id, len(devs))
            )
        return devs[self.device_id]

    # -- identity ----------------------------------------------------------
    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(self._default_ctx, "value"):
            self._default_ctx.value = Context("cpu", 0)
        self._old_ctx = self._default_ctx.value
        self._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        self._default_ctx.value = self._old_ctx

    @classmethod
    def default_ctx(cls):
        if not hasattr(cls._default_ctx, "value"):
            # the reference defaults to cpu() because CPU is its only
            # always-present device; here the accelerator is the natural
            # home — defaulting to cpu() on a TPU host would pin params
            # and grads to host memory (device_put to CpuDevice) and mix
            # platforms inside one jit
            has_acc = any(d.platform != "cpu" for d in _jax().devices())
            cls._default_ctx.value = Context("tpu" if has_acc else "cpu", 0)
        return cls._default_ctx.value


def cpu(device_id=0):
    """Host context (reference: python/mxnet/context.py:141)."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Accelerator context; on this framework an alias for tpu()."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """TPU context — the new first-class accelerator context."""
    return Context("tpu", device_id)


def num_gpus():
    jax = _jax()
    devs = jax.devices()
    return 0 if devs[0].platform == "cpu" else len(devs)


def num_tpus():
    return num_gpus()


def current_context():
    """Reference: python/mxnet/context.py:216."""
    return Context.default_ctx()
