"""Gluon basic layers.

Reference: ``python/mxnet/gluon/nn/basic_layers.py`` — Sequential,
HybridSequential, Dense, Dropout, BatchNorm, Embedding, Flatten,
InstanceNorm, LayerNorm, Lambda, HybridLambda (:32-638).
"""
from __future__ import annotations

import numpy as np

from ... import ndarray
from ...base import MXNetError
from ..block import Block, HybridBlock
from ..parameter import DeferredInitializationError
from .activations import Activation

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
           "Embedding", "Flatten", "InstanceNorm", "LayerNorm", "RMSNorm",
           "Lambda", "HybridLambda"]


class Sequential(Block):
    """Stack of Blocks (reference: basic_layers.py:32)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(["  ({key}): {block}".format(
            key=key, block=str(block))
            for key, block in self._children.items()])
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)

    def hybridize(self, active=True, **kwargs):
        if self._children and all(isinstance(c, HybridBlock)
                                  for c in self._children.values()):
            import warnings
            warnings.warn(
                "All children of this Sequential layer '%s' are HybridBlocks. "
                "Consider using HybridSequential for the best performance." %
                self.prefix, stacklevel=2)
        super().hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """Stack of HybridBlocks (reference: basic_layers.py:99)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        # children handle their own hybrid state; the container just chains
        for block in self._children.values():
            x = block(x)
        return x

    def hybrid_forward(self, F, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(["  ({key}): {block}".format(
            key=key, block=str(block))
            for key, block in self._children.items()])
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)


class Dense(HybridBlock):
    """Fully-connected layer (reference: basic_layers.py:162)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._flatten = flatten
        with self.name_scope():
            self._units = units
            self._in_units = in_units
            self.weight = self.params.get(
                "weight", shape=(units, in_units),
                init=weight_initializer, dtype=dtype,
                allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), init=_init(bias_initializer),
                    dtype=dtype, allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def _shape_hook(self, inputs):
        x = inputs[0]
        in_units = int(np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight.shape = (self._units, in_units)

    def hybrid_forward(self, F, x, weight, bias=None):
        act = F.FullyConnected(x, weight, bias, no_bias=bias is None,
                               num_hidden=self._units, flatten=self._flatten,
                               name="fwd")
        if self.act is not None:
            act = self.act(act)
        return act

    def __repr__(self):
        shape = self.weight.shape
        return "{name}({layout}, {act})".format(
            name=self.__class__.__name__,
            act=self.act if self.act else "linear",
            layout="{0} -> {1}".format(
                shape[1] if shape[1] else None, shape[0]))


class Dropout(HybridBlock):
    """Dropout (reference: basic_layers.py:238)."""

    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        return F.Dropout(x, p=self._rate, axes=self._axes, name="fwd")

    def __repr__(self):
        return "{name}(p = {_rate}, axes={_axes})".format(
            name=self.__class__.__name__, _rate=self._rate, _axes=self._axes)


class BatchNorm(HybridBlock):
    """Batch normalization (reference: basic_layers.py:291)."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"axis": axis, "eps": epsilon, "momentum": momentum,
                        "fix_gamma": not scale,
                        "use_global_stats": use_global_stats}
        self._axis = axis
        if in_channels != 0:
            self.in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=_init(gamma_initializer),
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=_init(beta_initializer),
                allow_deferred_init=True, differentiable=center)
            self.running_mean = self.params.get(
                "running_mean", grad_req="null", shape=(in_channels,),
                init=_init(running_mean_initializer),
                allow_deferred_init=True, differentiable=False)
            self.running_var = self.params.get(
                "running_var", grad_req="null", shape=(in_channels,),
                init=_init(running_variance_initializer),
                allow_deferred_init=True, differentiable=False)

    def _shape_hook(self, inputs):
        c = inputs[0].shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (c,)

    def cast(self, dtype):
        if np.dtype(dtype).name == "float16":
            dtype = "float32"
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        return F.BatchNorm(x, gamma, beta, running_mean, running_var,
                           name="fwd", **self._kwargs)

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return "{name}({content}, in_channels={in_channels})".format(
            name=self.__class__.__name__, in_channels=in_channels,
            content=", ".join(["=".join([k, v.__repr__()])
                               for k, v in self._kwargs.items()]))


class Embedding(HybridBlock):
    """Embedding lookup (reference: basic_layers.py:397)."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"input_dim": input_dim, "output_dim": output_dim,
                        "dtype": dtype}
        with self.name_scope():
            # sparse_grad: backward produces a row_sparse gradient so the
            # optimizer's lazy path updates only the looked-up rows
            # (reference: basic_layers.py Embedding sparse_grad)
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim),
                init=weight_initializer, dtype=dtype,
                allow_deferred_init=True,
                grad_stype="row_sparse" if sparse_grad else "default")

    def _shape_hook(self, inputs):
        pass

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, name="fwd", **self._kwargs)

    def __repr__(self):
        return "{block_name}({input_dim} -> {output_dim}, {dtype})".format(
            block_name=self.__class__.__name__, **self._kwargs)


class Flatten(HybridBlock):
    """Flatten to 2D (reference: basic_layers.py:446)."""

    def hybrid_forward(self, F, x):
        return F.Flatten(x)

    def __repr__(self):
        return self.__class__.__name__


class InstanceNorm(HybridBlock):
    """Instance normalization (reference: basic_layers.py:467)."""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"eps": epsilon, "axis": axis, "center": center,
                        "scale": scale}
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=_init(gamma_initializer),
                allow_deferred_init=True)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=_init(beta_initializer),
                allow_deferred_init=True)

    def _shape_hook(self, inputs):
        c = inputs[0].shape[self._axis]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        if self._axis == 1:
            return F.InstanceNorm(x, gamma, beta, name="fwd",
                                  eps=self._epsilon)
        x = x.swapaxes(1, self._axis)
        return F.InstanceNorm(x, gamma, beta, name="fwd",
                              eps=self._epsilon).swapaxes(1, self._axis)

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return "{name}({content}, in_channels={in_channels})".format(
            name=self.__class__.__name__, in_channels=in_channels,
            content=", ".join(["=".join([k, v.__repr__()])
                               for k, v in self._kwargs.items()]))


class LayerNorm(HybridBlock):
    """Layer normalization (reference: basic_layers.py:553)."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._kwargs = {"eps": epsilon, "axis": axis, "center": center,
                        "scale": scale}
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=_init(gamma_initializer),
                allow_deferred_init=True)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=_init(beta_initializer),
                allow_deferred_init=True)

    def _shape_hook(self, inputs):
        c = inputs[0].shape[self._axis]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, data, gamma, beta):
        return F.LayerNorm(data, gamma=gamma, beta=beta, axis=self._axis,
                           eps=self._epsilon)

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return "{name}({content}, in_channels={in_channels})".format(
            name=self.__class__.__name__, in_channels=in_channels,
            content=", ".join(["=".join([k, v.__repr__()])
                               for k, v in self._kwargs.items()]))


class RMSNorm(HybridBlock):
    """Root-mean-square normalisation over ``axis`` with a learned gain
    (arXiv:1910.07467; no reference analogue — MXNet 1.2 predates it)."""

    def __init__(self, axis=-1, epsilon=1e-6, gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,),
                init=_init(gamma_initializer), allow_deferred_init=True)

    def _shape_hook(self, inputs):
        self.gamma.shape = (inputs[0].shape[self._axis],)

    def hybrid_forward(self, F, data, gamma):
        return F.RMSNorm(data, gamma=gamma, axis=self._axis,
                         eps=self._epsilon)

    def __repr__(self):
        return "RMSNorm(eps=%r, axis=%r, in_channels=%d)" % (
            self._epsilon, self._axis, self.gamma.shape[0])


class Lambda(Block):
    """Wrap a function as a Block (reference: basic_layers.py:633)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            assert hasattr(ndarray, function), \
                "Function name %s is not found in ndarray." % function
            self._func_impl = getattr(ndarray, function)
            self._func_name = function
        elif callable(function):
            self._func_impl = function
            self._func_name = function.__name__
        else:
            raise ValueError(
                "Unrecognized function in lambda: {} of type {}".format(
                    function, type(function)))

    def forward(self, *args):
        return self._func_impl(*args)

    def __repr__(self):
        return "{name}({function})".format(name=self.__class__.__name__,
                                           function=self._func_name)


class HybridLambda(HybridBlock):
    """Wrap a function as a HybridBlock (reference: basic_layers.py:676)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            assert hasattr(ndarray, function), \
                "Function name %s is not found in ndarray." % function
            self._func = lambda F, *args: getattr(F, function)(*args)
            self._func_name = function
        elif callable(function):
            self._func = function
            self._func_name = function.__name__
        else:
            raise ValueError(
                "Unrecognized function in lambda: {} of type {}".format(
                    function, type(function)))

    def hybrid_forward(self, F, x, *args):
        return self._func(F, x, *args)

    def __repr__(self):
        return "{name}({function})".format(name=self.__class__.__name__,
                                           function=self._func_name)


def _init(init):
    """Resolve string initializer names (zeros/ones) to Initializer."""
    from ... import initializer
    if init is None or not isinstance(init, str):
        return init
    return initializer.create({"zeros": "zero", "ones": "one"}.get(init, init))
