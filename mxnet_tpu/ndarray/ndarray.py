"""NDArray — the user-facing tensor.

Reference: ``include/mxnet/ndarray.h:82`` + ``python/mxnet/ndarray/ndarray.py``
(the 181-method Python class).  TPU-native redesign:

- The buffer is a ``jax.Array``.  jax arrays are immutable, so the
  reference's shared mutable Chunk becomes a *rebindable reference*:
  in-place APIs (``x += y``, ``x[:] = v``, optimizer updates) compute a
  new functional value and rebind ``self._data``.  Aliasing views
  (reference zero-copy Reshape/Slice, ndarray.h:82) are emulated by a
  write-through link: a basic-indexing ``__getitem__`` or ``reshape``
  result (outside autograd recording) remembers its base and window;
  writes through either side propagate to the other by functional
  scatter + rebind, so reference scripts that assign through slices
  compute the same values.  Advanced (array-) indexing returns a copy,
  as in the reference.
- Asynchrony comes from jax's dispatch: every op returns immediately;
  ``wait_to_read`` = ``block_until_ready`` (reference
  NDArray::WaitToRead, engine WaitForVar).  ``asnumpy`` blocks and
  copies to host (reference ndarray.py asnumpy -> SyncCopyToCPU).
- Autograd state (``attach_grad``) hangs directly off the array,
  mirroring the reference's ``entry_`` autograd link (ndarray.h:98).
"""
from __future__ import annotations

import struct

import numpy as np
import jax
import jax.numpy as jnp

from .. import autograd
from ..analysis.sanitizers import hooks as _san_hooks
from ..base import MXNetError, dtype_np, dtype_id, _DTYPE_MX_TO_NP, numeric_types
from ..context import Context, current_context
from ..imperative import invoke, invoke_fn
from ..ops.registry import get_op

__all__ = ["NDArray", "array", "zeros", "ones", "full", "arange", "empty",
           "concat", "concatenate", "save", "load", "waitall", "_wrap",
           "imdecode", "moveaxis", "onehot_encode"]


# sync-point metric handles, cached per registry generation (hot path:
# one dict lookup per asnumpy would still be cheap, but these run per
# output per request under the serving batcher — avoid the registry lock)
_SYNC_METRICS = None


def _sync_metrics():
    global _SYNC_METRICS
    from .. import telemetry
    reg = telemetry.get_registry()
    gen = reg.generation
    if _SYNC_METRICS is None or _SYNC_METRICS[0] != gen:
        _SYNC_METRICS = (
            gen,
            reg.counter("mxnet_sync_waits_total",
                        "host blocks on device work "
                        "(wait_to_read/waitall)").labels(),
            reg.counter("mxnet_transfer_d2h_total",
                        "device->host copies (asnumpy sync points)"
                        ).labels(),
            reg.counter("mxnet_transfer_d2h_bytes_total",
                        "bytes copied device->host at asnumpy sync "
                        "points").labels())
    return _SYNC_METRICS


def _dev_ctx(jarr):
    try:
        dev = next(iter(jarr.devices()))
    except Exception:
        return current_context()
    if dev.platform == "cpu":
        return Context("cpu", dev.id)
    return Context("tpu", dev.id)


class NDArray:
    """Multi-dimensional array on a device, with async semantics."""

    __slots__ = ("_buf", "_grad", "_grad_req", "_ag_leaf", "_ag_slot",
                 "_views", "_view_base", "_view_spec", "__weakref__")
    # make numpy defer to our reflected ops
    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            data = jnp.asarray(data)
        if ctx is not None:
            data = jax.device_put(data, Context(ctx).jax_device)
        self._buf = data
        self._grad = None
        self._grad_req = "null"
        self._ag_leaf = False
        self._ag_slot = None
        self._views = None
        self._view_base = None
        self._view_spec = None

    # -- buffer + write-through view maintenance ---------------------------
    @property
    def _data(self):
        if self._view_spec is not None and self._view_spec[2]:
            self._refresh_window()
        return self._buf

    @_data.setter
    def _data(self, value):
        self._rebind(value)

    def _rebind(self, value):
        """Swap the buffer; keep aliasing views coherent in both directions
        (reference shared-Chunk semantics, include/mxnet/ndarray.h:82).

        Views are marked stale (flag only — no device work) and
        recompute their window lazily on next read, so held-but-unused
        views are free; the write-back into the base is immediate."""
        self._buf = value
        if self._view_spec is not None:
            self._view_spec = (*self._view_spec[:2], False)  # now fresh
        self._mark_views_stale()
        if self._view_base is not None:
            base = self._view_base
            new_base = self._write_back(base._data)
            if new_base is None:  # window no longer fits: detach
                self._view_base = None
                self._view_spec = None
            else:
                base._rebind(new_base)
                # base._rebind marked us stale; this buffer IS the
                # freshest value (it caused the write) — unmark
                self._view_spec = (*self._view_spec[:2], False)

    def _mark_views_stale(self):
        if self._views is None:
            return
        live = []
        for ref in self._views:
            v = ref()
            if v is not None and v._view_spec is not None:
                v._view_spec = (*v._view_spec[:2], True)
                v._mark_views_stale()
                live.append(ref)
        self._views = live or None

    def _refresh_window(self):
        """Recompute this view's value from its (possibly stale) base."""
        base = self._view_base
        kind, arg, _ = self._view_spec
        base_buf = base._data  # refreshes the chain upward
        try:
            fresh = base_buf[arg] if kind == "index" else \
                base_buf.reshape(self._buf.shape)
        except (TypeError, ValueError):
            fresh = None
        if fresh is None or fresh.shape != self._buf.shape:
            # base was rebound to an incompatible buffer (e.g. a
            # checkpoint reload changed its shape): the alias link is
            # meaningless now — detach, keep the last value
            self._view_base = None
            self._view_spec = None
        else:
            self._buf = fresh
            self._view_spec = (kind, arg, False)

    def _write_back(self, base_buf):
        """The base's new buffer after this view's value is written in,
        or None when the window no longer fits the base."""
        kind, arg, _ = self._view_spec
        try:
            if kind == "index":
                win = base_buf[arg]
                if win.shape != self._buf.shape:
                    return None
                return base_buf.at[arg].set(self._buf.astype(base_buf.dtype))
            if base_buf.size != self._buf.size:
                return None
            return self._buf.reshape(base_buf.shape)
        except (TypeError, ValueError):
            return None

    def _attach_view(self, out, spec):
        """Link ``out`` as a write-through alias of ``self``.

        Only outside autograd recording (the tape's scatter-cotangent
        entries own mutation semantics while recording) and never on
        sparse arrays (compact payload, no shared dense chunk)."""
        import weakref

        if autograd.is_recording() or type(self) is not NDArray:
            return out
        out._view_base = self
        out._view_spec = (*spec, False)  # (kind, arg, stale)
        if self._views is None:
            self._views = []
        elif len(self._views) >= 32:
            # read-mostly bases accumulate dead refs (views are usually
            # short-lived); compact before growing further
            self._views = [r for r in self._views if r() is not None]
        self._views.append(weakref.ref(out))
        return out

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return _dev_ctx(self._data)

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return self.transpose()

    @property
    def handle(self):
        """Reference exposes the C handle; here the jax.Array IS the handle."""
        return self._data

    # -- sync / host transfer ----------------------------------------------
    def wait_to_read(self):
        """Reference: NDArray::WaitToRead (include/mxnet/ndarray.h:305);
        sync points rethrow deferred worker exceptions."""
        from .. import engine, telemetry
        engine.check_raise()
        if telemetry.enabled():
            _sync_metrics()[1].inc()
        if _san_hooks.HOST_SYNC[0]:
            _san_hooks.on_host_sync("wait_to_read")
        if _san_hooks.DONATION[0]:
            _san_hooks.on_buffer_read(self)
        self._data.block_until_ready()

    wait_to_write = wait_to_read

    def asnumpy(self):
        """Blocking copy to host (reference: ndarray.py asnumpy).

        Telemetry: each call is one device->host transfer of the whole
        buffer — the sync point the ISSUE's transfer accounting counts."""
        from .. import engine, telemetry
        engine.check_raise()
        data = self._data
        if telemetry.enabled():
            _gen, _sync, d2h, d2h_bytes = _sync_metrics()
            d2h.inc()
            d2h_bytes.inc(int(data.size) * np.dtype(data.dtype).itemsize)
        # graftsan: the asnumpy funnel covers asscalar/item/__float__
        # too — the sanitizer names the outermost caller from the stack
        if _san_hooks.HOST_SYNC[0]:
            _san_hooks.on_host_sync("asnumpy")
        if _san_hooks.DONATION[0]:
            _san_hooks.on_buffer_read(self)
        return np.asarray(data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return "%s\n<NDArray %s @%s>" % (
            np.asarray(self._data), "x".join(map(str, self.shape)), self.context)

    # jax/dlpack interop (replaces reference TBlob/DLPack, tensor_blob.h:66)
    def __dlpack__(self, **kw):
        return self._data.__dlpack__(**kw)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # -- dtype/device movement ---------------------------------------------
    def astype(self, dtype, copy=True):
        return invoke_fn(lambda x: x.astype(dtype_np(dtype)), [self])

    def as_in_context(self, context):
        """Reference: ndarray.py as_in_context (engine CopyFromTo)."""
        ctx = Context(context)
        if ctx == self.context:
            return self
        out = NDArray(jax.device_put(self._data, ctx.jax_device))
        return out

    def copyto(self, other):
        """Reference: CopyFromTo (src/ndarray/ndarray.cc:1162)."""
        if isinstance(other, NDArray):
            src = self._data if self._data.dtype == other._data.dtype \
                else self._data.astype(other.dtype)
            other._data = jax.device_put(
                src, next(iter(other._data.devices())))
            return other
        ctx = Context(other)
        return NDArray(jax.device_put(self._data, ctx.jax_device))

    def copy(self):
        return NDArray(jnp.array(self._data))

    def detach(self):
        out = NDArray(self._data)
        return out

    # -- autograd -----------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Reference: ndarray.py attach_grad -> MXAutogradMarkVariables."""
        # host-built zeros: a transfer, not a per-shape XLA program
        self._grad = NDArray(jnp.asarray(
            np.zeros(self._data.shape, self._data.dtype)))
        self._grad_req = grad_req
        self._ag_leaf = True

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # -- shape ops ----------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        out = invoke("Reshape", [self], {"shape": shape,
                                         "reverse": kwargs.get("reverse", False)})
        # reference Reshape shares the chunk (ndarray.h:82); same
        # write-through aliasing as basic-index views
        return self._attach_view(out, ("reshape", None))

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def broadcast_to(self, shape):
        return invoke("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other):
        return invoke("broadcast_like", [self, other])

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return invoke("transpose", [self], {"axes": axes} if axes else {})

    def swapaxes(self, dim1, dim2):
        return invoke("SwapAxis", [self], {"dim1": dim1, "dim2": dim2})

    def flatten(self):
        return invoke("Flatten", [self])

    def expand_dims(self, axis):
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke("squeeze", [self], {"axis": axis} if axis is not None else {})

    def flip(self, axis):
        return invoke("reverse", [self], {"axis": axis})

    def tile(self, reps):
        return invoke("tile", [self], {"reps": reps})

    def repeat(self, repeats, axis=None):
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def pad(self, mode, pad_width, constant_value=0.0):
        return invoke("Pad", [self], {"mode": mode, "pad_width": pad_width,
                                      "constant_value": constant_value})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("SliceChannel", [self],
                      {"num_outputs": num_outputs, "axis": axis,
                       "squeeze_axis": squeeze_axis})

    def slice(self, begin, end, step=None):
        return invoke("slice", [self], {"begin": begin, "end": end, "step": step})

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def pick(self, index, axis=-1, keepdims=False):
        return invoke("pick", [self, index], {"axis": axis, "keepdims": keepdims})

    def one_hot(self, depth, on_value=1.0, off_value=0.0, dtype="float32"):
        return invoke("one_hot", [self], {"depth": depth, "on_value": on_value,
                                          "off_value": off_value, "dtype": dtype})

    def clip(self, a_min=None, a_max=None):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return invoke("abs", [self])

    def sign(self):
        return invoke("sign", [self])

    def sqrt(self):
        return invoke("sqrt", [self])

    def square(self):
        return invoke("square", [self])

    def exp(self):
        return invoke("exp", [self])

    def log(self):
        return invoke("log", [self])

    def sigmoid(self):
        return invoke("sigmoid", [self])

    def tanh(self):
        return invoke("tanh", [self])

    def relu(self):
        return invoke("relu", [self])

    def softmax(self, axis=-1):
        return invoke("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", [self], {"axis": axis})

    # -- reductions ---------------------------------------------------------
    def sum(self, axis=None, keepdims=False):
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return invoke("topk", [self], {"axis": axis, "k": k, "ret_typ": ret_typ,
                                       "is_ascend": is_ascend})

    def dot(self, other, transpose_a=False, transpose_b=False):
        return invoke("dot", [self, other],
                      {"transpose_a": transpose_a, "transpose_b": transpose_b})

    # -- sparse compat ------------------------------------------------------
    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse
        return sparse.cast_storage(self, stype)

    def as_nd_ndarray(self):
        return self

    # -- arithmetic ---------------------------------------------------------
    def _binop(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            args = [other, self] if reverse else [self, other]
            return invoke(op, args)
        if isinstance(other, numeric_types):
            return invoke(scalar_op, [self], {"scalar": float(other)})
        if isinstance(other, np.ndarray):
            o = NDArray(other)
            args = [o, self] if reverse else [self, o]
            return invoke(op, args)
        return NotImplemented

    def __add__(self, other):
        return self._binop(other, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binop(other, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, other):
        if isinstance(other, numeric_types):
            return invoke("_rminus_scalar", [self], {"scalar": float(other)})
        return self._binop(other, "elemwise_sub", None, reverse=True)

    def __mul__(self, other):
        return self._binop(other, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binop(other, "elemwise_div", "_div_scalar")

    def __rtruediv__(self, other):
        if isinstance(other, numeric_types):
            return invoke("_rdiv_scalar", [self], {"scalar": float(other)})
        return self._binop(other, "elemwise_div", None, reverse=True)

    def __mod__(self, other):
        return self._binop(other, "_mod", "_mod_scalar")

    def __rmod__(self, other):
        if isinstance(other, numeric_types):
            return invoke("_rmod_scalar", [self], {"scalar": float(other)})
        return self._binop(other, "_mod", None, reverse=True)

    def __pow__(self, other):
        return self._binop(other, "_power", "_power_scalar")

    def __rpow__(self, other):
        if isinstance(other, numeric_types):
            return invoke("_rpower_scalar", [self], {"scalar": float(other)})
        return NotImplemented

    def __neg__(self):
        return invoke("negative", [self])

    def __eq__(self, other):
        if other is None:
            return False
        return self._binop(other, "_equal", "_equal_scalar")

    def __ne__(self, other):
        if other is None:
            return True
        return self._binop(other, "_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return self._binop(other, "_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._binop(other, "_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return self._binop(other, "_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._binop(other, "_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__

    # in-place: rebind to new functional value
    def __iadd__(self, other):
        res = self.__add__(other)
        self._data = res._data
        self._ag_slot = res._ag_slot
        return self

    def __isub__(self, other):
        res = self.__sub__(other)
        self._data = res._data
        self._ag_slot = res._ag_slot
        return self

    def __imul__(self, other):
        res = self.__mul__(other)
        self._data = res._data
        self._ag_slot = res._ag_slot
        return self

    def __itruediv__(self, other):
        res = self.__truediv__(other)
        self._data = res._data
        self._ag_slot = res._ag_slot
        return self

    # -- indexing -----------------------------------------------------------
    def _conv_index(self, key):
        if isinstance(key, NDArray):
            return key._data.astype(jnp.int32)
        if isinstance(key, tuple):
            return tuple(self._conv_index(k) for k in key)
        if isinstance(key, (list, np.ndarray)):
            return jnp.asarray(key)
        return key

    @staticmethod
    def _is_basic_index(key):
        if isinstance(key, tuple):
            return all(NDArray._is_basic_index(k) for k in key)
        return key is None or key is Ellipsis or \
            isinstance(key, (int, np.integer, slice))

    def __getitem__(self, key):
        key = self._conv_index(key)
        out = invoke_fn(lambda x: x[key], [self])
        if self._is_basic_index(key):
            # basic indexing aliases the chunk in the reference
            # (zero-copy Slice); emulate with a write-through link
            self._attach_view(out, ("index", key))
        return out

    def __setitem__(self, key, value):
        key = self._conv_index(key)
        if isinstance(value, NDArray):
            v = value._data
        else:
            v = value
        if isinstance(key, slice) and key == slice(None) and \
                isinstance(v, (bool, int, float, np.number)):
            # full-slice constant fill: build on host and transfer — no
            # XLA program (parameter init hits this path for every
            # distinct shape, and each would be its own compile). A
            # constant overwrite disconnects the array from the tape
            # by definition.
            self._data = jnp.asarray(
                np.full(self.shape, v, dtype=self._data.dtype))
            self._ag_slot = None
        elif isinstance(key, slice) and key == slice(None) and \
                isinstance(v, np.ndarray):
            # host-array full overwrite: broadcast/cast in numpy, one
            # device transfer, no compile (same disconnect semantics)
            self._data = jnp.asarray(np.broadcast_to(
                v.astype(self._data.dtype, copy=False), self.shape))
            self._ag_slot = None
        elif isinstance(key, slice) and key == slice(None) and not isinstance(v, (int, float)):
            v = jnp.asarray(v)
            if v.shape == self.shape and v.dtype == self._data.dtype:
                # immutable buffers make sharing safe — no device program
                self._data = v
            else:
                self._data = jnp.broadcast_to(v.astype(self._data.dtype),
                                              self.shape)
            if isinstance(value, NDArray):
                self._ag_slot = value._ag_slot
        else:
            # route through invoke_fn so a recorded tape entry routes
            # cotangents through the scatter (zero at overwritten slots)
            inputs = [self] + ([value] if isinstance(value, NDArray) else [])

            def _set(x, *maybe_v):
                vv = maybe_v[0] if maybe_v else v
                return x.at[key].set(
                    vv if not hasattr(vv, "astype") else vv.astype(x.dtype))

            res = invoke_fn(_set, inputs)
            self._data = res._data
            self._ag_slot = res._ag_slot

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    def __reduce__(self):
        # pickling (optimizer state save, DataLoader workers): serialize
        # via host numpy (reference: ndarray.py __reduce__/NDArrayBase)
        return (_rebuild_ndarray, (self.asnumpy(),))


def _rebuild_ndarray(a):
    return NDArray(jnp.asarray(a))


def _wrap(jarr):
    return NDArray(jarr)


@jax.jit
def _copy_buffers(xs):
    """Fresh device copies of a tuple of jax arrays as ONE program —
    per-array copies would compile one tiny XLA program per distinct
    shape (dozens for a ResNet's parameters)."""
    return tuple(jnp.array(x) for x in xs)


# ---------------------------------------------------------------------------
# creation functions (reference: python/mxnet/ndarray/utils.py + ndarray.py)
# ---------------------------------------------------------------------------
def _ctx_device(ctx):
    return Context(ctx).jax_device if ctx is not None else None


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        data = source_array._data
        if dtype is not None:
            data = data.astype(dtype_np(dtype))
        return NDArray(data, ctx=ctx)
    if dtype is not None:
        a = np.asarray(source_array, dtype=dtype_np(dtype))
    elif isinstance(source_array, np.ndarray):
        a = source_array
        if a.dtype == np.float64:
            a = a.astype(np.float32)  # MXNet default dtype
    else:
        # python lists/scalars default to float32 (reference: ndarray.py array)
        a = np.asarray(source_array, dtype=np.float32)
    return NDArray(jnp.asarray(a), ctx=ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    # constant creators build on HOST and transfer: a broadcast would
    # be one XLA compile per distinct shape, and executor binds create
    # one buffer per argument shape
    return NDArray(jnp.asarray(np.zeros(shape, dtype_np(dtype))), ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    return NDArray(jnp.asarray(np.ones(shape, dtype_np(dtype))), ctx=ctx)


def full(shape, val, ctx=None, dtype=None):
    return NDArray(jnp.asarray(np.full(shape, val, dtype_np(dtype))),
                   ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    out = jnp.arange(start, stop, step, dtype_np(dtype))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return NDArray(out, ctx=ctx)


def moveaxis(tensor, source, destination):
    return invoke_fn(lambda x: jnp.moveaxis(x, source, destination), [tensor])


def concat(*data, dim=1):
    return invoke("Concat", list(data), {"dim": dim})


def concatenate(arrays, axis=0, always_copy=True):
    return invoke("Concat", list(arrays), {"dim": axis})


def onehot_encode(indices, out):
    depth = out.shape[1]
    res = invoke("one_hot", [indices], {"depth": depth})
    out._data = res._data
    return out


def imdecode(buf, **kwargs):  # pragma: no cover - needs cv2
    import cv2
    img = cv2.imdecode(np.frombuffer(buf, dtype=np.uint8), cv2.IMREAD_COLOR)
    return array(img[:, :, ::-1])


def waitall():
    """Reference: MXNDArrayWaitAll / Engine::WaitForAll.

    Rethrows exceptions recorded by worker threads (prefetchers, custom
    ops) — the reference's async-exception contract
    (threaded_engine.cc:463-467, test_exc_handling.py)."""
    from .. import engine, telemetry
    if telemetry.enabled():
        _sync_metrics()[1].inc()
    jax.effects_barrier()
    engine.check_raise()


# ---------------------------------------------------------------------------
# serialization — NDArray V2 container (reference: src/ndarray/ndarray.cc:1552)
# Binary layout (little-endian), faithful to the reference's dmlc::Stream
# writes: magic 0xF993fac9 (uint64), reserved uint64, then the two vectors
# (data blobs, names) each prefixed with uint64 count.
# ---------------------------------------------------------------------------
_NDARRAY_V2_MAGIC = 0xF993FAC9
_NDARRAY_V1_MAGIC = 0xF993FAC8


def _write_ndarray(f, arr):
    a = arr.asnumpy()
    f.write(struct.pack("<Q", _NDARRAY_V2_MAGIC))
    # stype (-1 dense), shape ndim + dims (uint32 each), context (int32 x2),
    # dtype id (int32), data bytes
    f.write(struct.pack("<i", -1))
    f.write(struct.pack("<I", a.ndim))
    for d in a.shape:
        f.write(struct.pack("<q", d))
    f.write(struct.pack("<ii", 1, 0))  # ctx: cpu(0)
    f.write(struct.pack("<i", dtype_id(a.dtype)))
    f.write(a.tobytes())


def _read_ndarray(f):
    magic = struct.unpack("<Q", f.read(8))[0]
    if magic != _NDARRAY_V2_MAGIC:
        raise MXNetError("invalid NDArray file format (magic %x)" % magic)
    struct.unpack("<i", f.read(4))  # stype
    ndim = struct.unpack("<I", f.read(4))[0]
    shape = tuple(struct.unpack("<q", f.read(8))[0] for _ in range(ndim))
    struct.unpack("<ii", f.read(8))
    tid = struct.unpack("<i", f.read(4))[0]
    dt = _DTYPE_MX_TO_NP[tid]
    n = int(np.prod(shape)) if shape else 1
    a = np.frombuffer(f.read(n * dt.itemsize), dtype=dt).reshape(shape)
    return array(a)


def save(fname, data):
    """Save dict/list of NDArrays (reference: mx.nd.save, c_api.cc:261).

    Crash-safe: the container is written to a hidden temp sibling and
    committed with one ``os.replace`` — a killed writer leaves either
    the previous complete file or the new one, never a truncated
    container at the target name."""
    if isinstance(data, NDArray):
        data = [data]
    names, arrays = [], []
    if isinstance(data, dict):
        for k, v in data.items():
            names.append(k)
            arrays.append(v)
    else:
        arrays = list(data)
    from .._atomic_io import atomic_writer
    with atomic_writer(fname) as f:
        f.write(struct.pack("<Q", 0x112))  # container magic (kMXAPINDArrayListMagic)
        f.write(struct.pack("<Q", 0))
        f.write(struct.pack("<Q", len(arrays)))
        for a in arrays:
            _write_ndarray(f, a)
        f.write(struct.pack("<Q", len(names)))
        for nme in names:
            b = nme.encode()
            f.write(struct.pack("<Q", len(b)))
            f.write(b)


def _load_stream(f):
    magic = struct.unpack("<Q", f.read(8))[0]
    if magic != 0x112:
        raise MXNetError("invalid NDArray container (magic %x)" % magic)
    struct.unpack("<Q", f.read(8))
    n = struct.unpack("<Q", f.read(8))[0]
    arrays = [_read_ndarray(f) for _ in range(n)]
    m = struct.unpack("<Q", f.read(8))[0]
    names = []
    for _ in range(m):
        ln = struct.unpack("<Q", f.read(8))[0]
        names.append(f.read(ln).decode())
    if names:
        return dict(zip(names, arrays))
    return arrays


def load(fname):
    """Load NDArrays (reference: mx.nd.load, c_api.cc:279)."""
    with open(fname, "rb") as f:
        return _load_stream(f)


def load_buffer(buf):
    """Load NDArrays from an in-memory container (the byte layout the
    reference's c_predict_api receives as param_bytes,
    c_predict_api.cc MXPredCreate)."""
    import io
    return _load_stream(io.BytesIO(buf))
