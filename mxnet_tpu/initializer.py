"""Weight initializers.

Reference: ``python/mxnet/initializer.py`` — Initializer base + registry,
Zero/One/Constant/Uniform/Normal/Orthogonal/Xavier/MSRAPrelu/Bilinear/
LSTMBias/FusedRNN, InitDesc, Load, Mixed.
"""
from __future__ import annotations

import json
import logging
import re

import numpy as np

from .base import MXNetError
from .ndarray import NDArray, array
from .ndarray import random as ndrandom

__all__ = ["InitDesc", "Initializer", "Zero", "One", "Constant", "Uniform",
           "Normal", "Orthogonal", "Xavier", "MSRAPrelu", "Bilinear",
           "LSTMBias", "FusedRNN", "Load", "Mixed", "register"]

_INITIALIZER_REGISTRY = {}


def _host_generator():
    """numpy Generator seeded off the global host-side key chain
    (mxnet_tpu/random.py next_key_data).

    Initializer sampling runs on HOST: a device-side random op would
    compile one tiny XLA program per distinct parameter shape (dozens
    for a ResNet-50, before the first step).  Host sampling + one
    transfer per param removes that entirely, and stays deterministic
    under
    ``mx.random.seed`` (same seed -> same chain counters -> same
    streams)."""
    from . import random as _mxrandom
    hi, lo = (int(w) for w in _mxrandom.next_key_data())
    return np.random.Generator(np.random.Philox(key=(hi << 32) | lo))


def _host_uniform(arr, low, high):
    g = _host_generator()
    arr[:] = g.uniform(low, high, arr.shape).astype(np.float32)


def _host_normal(arr, loc, scale):
    g = _host_generator()
    arr[:] = (loc + scale * g.standard_normal(arr.shape)).astype(np.float32)


def register(klass):
    _INITIALIZER_REGISTRY[klass.__name__.lower()] = klass
    return klass


class InitDesc(str):
    """Name + attrs descriptor for a parameter (reference: initializer.py:39)."""

    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


class Initializer:
    """Base initializer (reference: initializer.py:52)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs
        self._verbose = False
        self._print_func = None

    def set_verbosity(self, verbose=False, print_func=None):
        self._verbose = verbose
        if print_func is None:
            def asum_stat(x):
                return str((np.abs(x.asnumpy()).mean(),))
            print_func = asum_stat
        self._print_func = print_func
        return self

    def _verbose_print(self, desc, init, arr):
        if self._verbose and self._print_func:
            logging.info("Initialized %s as %s: %s", desc, init,
                         self._print_func(arr))

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        if not isinstance(desc, str):
            raise TypeError("desc must be a string or InitDesc")
        if isinstance(desc, InitDesc) and desc.global_init is None:
            desc.global_init = self
        init = desc.attrs.get("__init__", "") if isinstance(desc, InitDesc) else ""
        if init:
            create(init)._init_weight(desc, arr)
            self._verbose_print(desc, init, arr)
        elif desc.endswith("weight"):
            self._init_weight(desc, arr)
            self._verbose_print(desc, "weight", arr)
        elif desc.endswith("bias"):
            self._init_bias(desc, arr)
            self._verbose_print(desc, "bias", arr)
        elif desc.endswith("gamma"):
            self._init_gamma(desc, arr)
            self._verbose_print(desc, "gamma", arr)
        elif desc.endswith("beta"):
            self._init_beta(desc, arr)
            self._verbose_print(desc, "beta", arr)
        elif desc.endswith("min"):
            self._init_zero(desc, arr)
        elif desc.endswith("max"):
            self._init_one(desc, arr)
        elif desc.endswith("moving_mean") or desc.endswith("running_mean") \
                or desc.endswith("moving_avg") or desc.endswith("moving_inv_var"):
            # BatchNorm aux states (reference initializer legacy patterns)
            self._init_zero(desc, arr)
        elif desc.endswith("moving_var") or desc.endswith("running_var"):
            self._init_one(desc, arr)
        else:
            self._init_default(desc, arr)

    def _init_bilinear(self, _, arr):
        weight = np.zeros(np.prod(arr.shape), dtype="float32")
        shape = arr.shape
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(np.prod(shape)):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        arr[:] = array(weight.reshape(shape))

    def _init_loc_bias(self, _, arr):
        shape = arr.shape
        assert shape[0] == 6
        arr[:] = array(np.array([1.0, 0, 0, 0, 1.0, 0], dtype=np.float32))

    def _init_zero(self, _, arr):
        arr[:] = 0.0

    def _init_one(self, _, arr):
        arr[:] = 1.0

    # the zero/one fills cover bias and BN affine state
    _init_bias = _init_zero
    _init_beta = _init_zero
    _init_gamma = _init_one

    def _init_weight(self, name, arr):  # pragma: no cover - abstract
        raise NotImplementedError("Must override it")

    def _init_default(self, name, _):
        raise ValueError(
            "Unknown initialization pattern for %s. Default initialization "
            "is now limited to \"weight\", \"bias\", \"gamma\" (1.0), and "
            "\"beta\" (0.0). Please use mx.sym.Variable(init=mx.init.*) to "
            "set initialization pattern" % name)


@register
class Zero(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 0.0

    def _init_default(self, _, arr):
        arr[:] = 0.0


@register
class One(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 1.0

    def _init_default(self, _, arr):
        arr[:] = 1.0


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, _, arr):
        arr[:] = self.value

    def _init_default(self, _, arr):
        arr[:] = self.value


@register
class Uniform(Initializer):
    """U(-scale, scale) (reference: initializer.py Uniform)."""

    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, _, arr):
        _host_uniform(arr, -self.scale, self.scale)


@register
class Normal(Initializer):
    """N(0, sigma) (reference: initializer.py Normal)."""

    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        _host_normal(arr, 0.0, self.sigma)


@register
class Orthogonal(Initializer):
    """Orthogonal matrix init (reference: initializer.py Orthogonal)."""

    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = np.random.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = np.random.normal(0.0, 1.0, (nout, nin))
        u, _, v = np.linalg.svd(tmp, full_matrices=False)
        q = u if u.shape == tmp.shape else v
        arr[:] = array(self.scale * q.reshape(arr.shape).astype(np.float32))


@register
class Xavier(Initializer):
    """Xavier/Glorot init (reference: initializer.py Xavier)."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, name, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) < 2:
            raise ValueError(
                "Xavier initializer cannot be applied to vector %s. It requires"
                " at least 2D." % name)
        if len(shape) > 2:
            hw_scale = np.prod(shape[2:])
        fan_in, fan_out = shape[1] * hw_scale, shape[0] * hw_scale
        fans = {"avg": (fan_in + fan_out) / 2.0,
                "in": fan_in, "out": fan_out}
        if self.factor_type not in fans:
            raise ValueError("Incorrect factor type")
        scale = np.sqrt(self.magnitude / fans[self.factor_type])
        if self.rnd_type == "uniform":
            _host_uniform(arr, -scale, scale)
        elif self.rnd_type == "gaussian":
            _host_normal(arr, 0.0, scale)
        else:
            raise ValueError("Unknown random type")


@register
class MSRAPrelu(Xavier):
    """MSRA (He) init for PReLU nets (reference: initializer.py MSRAPrelu)."""

    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    def _init_weight(self, _, arr):
        self._init_bilinear(_, arr)


@register
class LSTMBias(Initializer):
    """Init LSTM biases with forget-gate bias = forget_bias
    (reference: initializer.py LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_bias(self, desc, arr):
        # bias-named params dispatch here; gate order i,f,c,o
        num_hidden = int(arr.shape[0] / 4)
        a = np.zeros(arr.shape, dtype=np.float32)
        a[num_hidden:2 * num_hidden] = self.forget_bias
        arr[:] = array(a)

    _init_weight = _init_bias


@register
class FusedRNN(Initializer):
    """Initialize the packed parameter blob of a fused RNN
    (reference: initializer.py FusedRNN): weights by the wrapped
    initializer, biases zero, LSTM forget gates set to ``forget_bias``.
    The packed layout matches ops/nn.py _unpack_rnn_params (all weights
    layer-major, then all biases bi/bh per layer-direction, gate order
    i,f,g,o)."""

    def __init__(self, init, num_hidden, num_layers, mode,
                 bidirectional=False, forget_bias=1.0):
        if isinstance(init, str):
            klass, kwargs = json.loads(init)
            init = create(klass, **kwargs)
        super().__init__(init=init.dumps() if init is not None else None,
                         num_hidden=num_hidden, num_layers=num_layers,
                         mode=mode, bidirectional=bidirectional,
                         forget_bias=forget_bias)
        self._init = init
        self._num_hidden = num_hidden
        self._num_layers = num_layers
        self._mode = mode
        self._bidirectional = bidirectional
        self._forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        from .ndarray import zeros as nd_zeros

        gates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[self._mode]
        H = self._num_hidden
        L = self._num_layers
        D = 2 if self._bidirectional else 1
        num_bias = L * D * 2 * gates * H
        blob = np.zeros(arr.shape, np.float32)
        # solve layer-0 input size from the blob length (packed layout of
        # ops/nn.py _unpack_rnn_params: per layer/direction W_i2h then
        # W_h2h, all weights first, then bi/bh biases)
        upper_w = (L - 1) * D * (gates * H * H * D + gates * H * H)
        l0_w = blob.size - num_bias - upper_w
        in0 = (l0_w // D - gates * H * H) // (gates * H)
        offset = 0
        for layer in range(L):
            in_sz = in0 if layer == 0 else H * D
            for _ in range(D):
                for rows, cols in ((gates * H, in_sz), (gates * H, H)):
                    n = rows * cols
                    # the wrapped initializer sees each packed matrix as
                    # the 2-D array it is (Xavier needs real fan-in/out)
                    mat = nd_zeros((rows, cols))
                    if self._init is not None:
                        self._init._init_weight(desc, mat)
                    blob[offset: offset + n] = \
                        mat.asnumpy().reshape(-1)
                    offset += n
        # biases stay zero; LSTM forget gate (second H-slice, gate order
        # i,f,g,o) gets forget_bias in BOTH bi and bh — the reference
        # writes every *_f_bias array, and the cell adds bi+bh
        if self._mode == "lstm":
            base = blob.size - num_bias
            for ld in range(L * D):
                off = base + ld * 2 * gates * H
                blob[off + H: off + 2 * H] = self._forget_bias
                blob[off + gates * H + H: off + gates * H + 2 * H] = \
                    self._forget_bias
        arr[:] = array(blob)


class Load:
    """Init from a dict of arrays, falling back to default_init
    (reference: initializer.py Load)."""

    def __init__(self, param, default_init=None, verbose=False):
        self.param = {
            (k[4:] if k.startswith("arg:") or k.startswith("aux:") else k): v
            for k, v in param.items()}
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        if name in self.param:
            if self.param[name].shape != arr.shape:
                raise AssertionError(
                    "Parameter %s cannot be initialized from loading. Shape "
                    "mismatch, target %s vs loaded %s"
                    % (name, str(arr.shape), str(self.param[name].shape)))
            arr[:] = self.param[name]
            if self.verbose:
                logging.info("Initialized %s by loading", name)
        else:
            if self.default_init is None:
                raise AssertionError(
                    "Cannot Initialize parameter %s. Not found in loaded "
                    "param and no default Initializer is provided." % name)
            self.default_init(name, arr)
            if self.verbose:
                logging.info("Initialized %s by default", name)


class Mixed:
    """Regex-pattern dispatch over initializers (reference: initializer.py Mixed)."""

    def __init__(self, patterns, initializers):
        assert len(patterns) == len(initializers)
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(name):
                init(name, arr)
                return
        raise ValueError(
            "Parameter name %s did not match any pattern. Consider adding a "
            "\".*\" pattern at the and with default Initializer." % name)


def create(init, **kwargs):
    """Create initializer from name or serialized json."""
    if isinstance(init, Initializer):
        return init
    if init.startswith("["):
        klass_name, kw = json.loads(init)
        return _INITIALIZER_REGISTRY[klass_name.lower()](**kw)
    return _INITIALIZER_REGISTRY[init.lower()](**kwargs)
