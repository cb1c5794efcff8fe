"""Optimizers.

Reference: ``python/mxnet/optimizer.py`` — Optimizer base + registry (:35),
SGD (+momentum, multi-precision :434), Signum, FTML, LBSGD, DCASGD, NAG,
SGLD, Adam, AdaGrad, RMSProp, AdaDelta, Ftrl, Adamax, Nadam (:539-1368),
``Updater`` (:1453) wrapping an optimizer for kvstore use.

TPU-native: each update is a fused jitted op from ``ops/optimizer_ops.py``
(the reference's ``src/operator/optimizer_op-inl.h`` kernels) or inline
NDArray math (which XLA fuses per step).  State is explicit NDArrays
threaded through the fused ops — no hidden mutation.
"""
from __future__ import annotations

import logging
import math
import pickle

import numpy as np

from .base import MXNetError
from .ndarray import NDArray, zeros, ones, array
from .ndarray import ndarray as nd


def _is_row_sparse(grad):
    return getattr(grad, "stype", "default") == "row_sparse"


def _rs_parts(grad):
    """(touched-row values, row indices) of a RowSparseNDArray grad.

    Reads the compact payload — O(nnz), no dense materialization."""
    grad._fresh()
    idx = grad._indices.astype("int32")
    return grad._values, idx
from . import ndarray as ndmod

__all__ = ["Optimizer", "SGD", "Signum", "FTML", "LBSGD", "DCASGD", "NAG",
           "SGLD", "Adam", "AdaGrad", "RMSProp", "AdaDelta", "Ftrl",
           "Adamax", "Nadam", "Test", "Updater", "get_updater", "create",
           "register"]


class Optimizer:
    """Base optimizer (reference: optimizer.py:35)."""

    opt_registry = {}

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        # gradient conditioning applied before every update
        self.rescale_grad = rescale_grad
        self.clip_gradient = clip_gradient
        self.wd = wd
        self.multi_precision = multi_precision
        # per-parameter lr/wd multipliers (set_lr_mult / set_wd_mult)
        self.lr_mult = {}
        self.wd_mult = {}
        # update bookkeeping: num_update feeds schedulers/bias correction
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        if param_idx2name is None:
            param_idx2name = {}
        if not isinstance(param_idx2name, dict):
            raise MXNetError("param_idx2name should be a dict of param indexes to names.")
        self.idx2name = param_idx2name.copy()
        self.sym_info = (sym.attr_dict(), sym.list_arguments()) if sym is not None else ()
        self.param_dict = param_dict if param_dict else {}
        self.set_lr_mult({})
        self.set_wd_mult({})

    # -- registry -----------------------------------------------------------
    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    # -- state --------------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        """fp16 weights keep an fp32 master copy (reference :434)."""
        weight_master_copy = None
        if self.multi_precision and weight.dtype == np.float16:
            weight_master_copy = weight.astype(np.float32)
            return (self.create_state(index, weight_master_copy),
                    weight_master_copy)
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):  # pragma: no cover - abstract
        raise NotImplementedError()

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype == np.float16:
            original_state, weight_master_copy = state
            grad32 = grad.astype(np.float32)
            self.update(index, weight_master_copy, grad32, original_state)
            weight._data = weight_master_copy._data.astype(weight.dtype)
        else:
            self.update(index, weight, grad, state)

    # -- lr/wd plumbing ------------------------------------------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been defined.")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            # biases/norms get no weight decay by convention
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _param_mult(self, index, table, attr):
        """Per-parameter multiplier resolution, one rule for lr and wd:
        a gluon Parameter object wins, then the explicit index table,
        then the name table (via idx2name); default 1."""
        if index in self.param_dict:
            return getattr(self.param_dict[index], attr)
        if index in table:
            return table[index]
        if index in self.idx2name:
            return table.get(self.idx2name[index], 1.0)
        return 1.0

    def _get_lr(self, index):
        base = self.lr if self.lr_scheduler is None \
            else self.lr_scheduler(self.num_update)
        return base * self._param_mult(index, self.lr_mult, "lr_mult")

    def _get_wd(self, index):
        return self.wd * self._param_mult(index, self.wd_mult, "wd_mult")

    def __getstate__(self):
        ret = self.__dict__.copy()
        del ret["sym_info"]
        return ret

    def __setstate__(self, state):
        self.__dict__ = state
        self.sym_info = ()


register = Optimizer.register


@register
class SGD(Optimizer):
    """SGD with momentum and optional multi-precision
    (reference: optimizer.py:434; kernels optimizer_op-inl.h sgd_update)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        if self.lazy_update and _is_row_sparse(grad):
            # lazy semantics: momentum of untouched rows does not decay
            # (reference SGDMomUpdateRspRspImpl, optimizer_op-inl.h)
            from .ops import optimizer_ops as oo
            vals, idx = _rs_parts(grad)
            kw = dict(lr=lr, wd=wd, rescale=self.rescale_grad,
                      clip=-1.0 if self.clip_gradient is None
                      else self.clip_gradient)
            if state is not None:
                new_w, new_m = oo.sgd_mom_rowsparse(
                    weight._data, state._data, vals, idx,
                    momentum=self.momentum, **kw)
                state._data = new_m
            else:
                new_w = oo.sgd_rowsparse(weight._data, vals, idx, **kw)
            weight._data = new_w
            return
        kwargs = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad)
        if self.clip_gradient is not None:
            kwargs["clip_gradient"] = self.clip_gradient
        if state is not None:
            ndmod.sgd_mom_update(weight, grad, state, out=weight,
                                 momentum=self.momentum, **kwargs)
        else:
            ndmod.sgd_update(weight, grad, out=weight, **kwargs)


@register
class ccSGD(SGD):
    """Deprecated alias of SGD kept for reference CLI compatibility
    (reference: optimizer.py ccSGD)."""


@register
class Signum(Optimizer):
    """Sign-based SGD (reference: optimizer.py Signum; signum_update op)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        kwargs = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                      wd_lh=self.wd_lh)
        if self.clip_gradient is not None:
            kwargs["clip_gradient"] = self.clip_gradient
        if state is not None:
            ndmod.signum_update(weight, grad, state, out=weight,
                                momentum=self.momentum, **kwargs)
        else:
            ndmod.signsgd_update(weight, grad, out=weight,
                                 **{k: v for k, v in kwargs.items()
                                    if k != "wd_lh"})


@register
class FTML(Optimizer):
    """FTML optimizer (reference: optimizer.py FTML)."""

    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # d
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # v
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))  # z

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        d, v, z = state
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        v[:] = self.beta2 * v + (1.0 - self.beta2) * g * g
        d_t = (1.0 - pow(self.beta1, t)) / lr * (
            (v / (1.0 - pow(self.beta2, t))).sqrt() + self.epsilon)
        sigma_t = d_t - self.beta1 * d
        z[:] = self.beta1 * z + (1.0 - self.beta1) * g - sigma_t * weight
        d[:] = d_t
        weight[:] = -1.0 * z / d_t


@register
class LBSGD(Optimizer):
    """Large-batch SGD with LARS-style layer-wise adaptive rates
    (reference: optimizer.py LBSGD)."""

    def __init__(self, momentum=0.0, multi_precision=False, warmup_strategy="linear",
                 warmup_epochs=5, batch_scale=1, updates_per_epoch=32,
                 begin_epoch=0, num_epochs=60, **kwargs):
        super().__init__(multi_precision=multi_precision, **kwargs)
        self.momentum = momentum
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch
        self.init_updates = begin_epoch * updates_per_epoch
        self.num_epochs = num_epochs
        self.lbmult = 1.0
        self.cumgrads = {}
        self.adaptive = False
        self.admult = 1

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    # warmup ramp shapes: fraction of warmup done -> fraction of the
    # extra (batch_scale - 1) LR to apply
    _WARMUP_RAMPS = {
        "linear": lambda f: f,
        "power2": lambda f: f * f,
        "sqrt": math.sqrt,
    }

    def _get_lbmult(self, nup):
        """Large-batch LR multiplier after `nup` updates: ramp from 1 to
        batch_scale over the warmup epochs along the chosen shape."""
        warmup_updates = self.warmup_epochs * self.updates_per_epoch
        if nup >= warmup_updates:
            return float(self.batch_scale)
        if warmup_updates <= 1:
            return 1.0
        ramp = self._WARMUP_RAMPS.get(self.warmup_strategy)
        if ramp is None:
            return 1.0
        done = float(nup) / warmup_updates
        return 1.0 + (float(self.batch_scale) - 1.0) * ramp(done)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        if self.warmup_strategy == "lars":
            # deliberate d2h sync: the LARS trust ratio scales a host-side
            # python float LR; folding it on-device would change every
            # optimizer kernel's signature for one warmup strategy
            w_norm = float(weight.norm().asscalar())  # graftlint: disable=host-sync
            g_norm = float(grad.norm().asscalar())  # graftlint: disable=host-sync
            if w_norm > 0 and g_norm > 0:
                lbmult = w_norm / (g_norm + wd * w_norm + 1e-9)
            else:
                lbmult = 1.0
            lr = lr * lbmult
        else:
            lr = lr * self._get_lbmult(self.num_update + self.init_updates)
        kwargs = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad)
        if self.clip_gradient is not None:
            kwargs["clip_gradient"] = self.clip_gradient
        if state is not None:
            ndmod.sgd_mom_update(weight, grad, state, out=weight,
                                 momentum=self.momentum, **kwargs)
        else:
            ndmod.sgd_update(weight, grad, out=weight, **kwargs)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        mom, previous_weight = state
        d = g + wd * weight + self.lamda * g * g * (weight - previous_weight)
        if mom is not None:
            mom[:] = self.momentum * mom - lr * d
            step = mom
        else:
            step = -lr * d
        previous_weight[:] = weight
        weight[:] = weight + step


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (reference: optimizer.py NAG)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        g = g + wd * weight
        if state is not None:
            mom = state
            mom[:] = self.momentum * mom + g
            weight[:] = weight - lr * (g + self.momentum * mom)
        else:
            weight[:] = weight - lr * g


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference: optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        from .ndarray import random as ndrandom
        noise = ndrandom.normal(0, math.sqrt(lr), shape=weight.shape,
                                dtype=weight.dtype)
        weight[:] = weight - lr / 2 * (g + wd * weight) + noise


@register
class Test(Optimizer):
    """Reference: optimizer.py Test (for testing only)."""

    def create_state(self, index, weight):
        return zeros(weight.shape, ctx=weight.context)

    def update(self, index, weight, grad, state):
        weight[:] = weight + grad * self.rescale_grad
        state[:] = weight


@register
class Adam(Optimizer):
    """Adam (reference: optimizer.py Adam; adam_update kernel)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # mean
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))  # var

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1
        kwargs = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                      beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon)
        if self.clip_gradient is not None:
            kwargs["clip_gradient"] = self.clip_gradient
        mean, var = state
        if self.lazy_update and _is_row_sparse(grad):
            # reference AdamUpdateRspRspImpl: mean/var of untouched rows
            # stay frozen (no decay)
            from .ops import optimizer_ops as oo
            vals, idx = _rs_parts(grad)
            new_w, new_m, new_v = oo.adam_rowsparse(
                weight._data, mean._data, var._data, vals, idx,
                lr=lr, beta1=self.beta1, beta2=self.beta2,
                epsilon=self.epsilon, wd=wd, rescale=self.rescale_grad,
                clip=-1.0 if self.clip_gradient is None
                else self.clip_gradient)
            weight._data, mean._data, var._data = new_w, new_m, new_v
            return
        ndmod.adam_update(weight, grad, mean, var, out=weight, **kwargs)


@register
class AdaGrad(Optimizer):
    """AdaGrad (reference: optimizer.py AdaGrad)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, ctx=weight.context)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        history = state
        history[:] = history + g * g
        div = g / (history + self.float_stable_eps).sqrt()
        weight[:] = weight - lr * (div + wd * weight)


@register
class RMSProp(Optimizer):
    """RMSProp, both non-centered (Tieleman) and centered (Alex Graves)
    variants (reference: optimizer.py RMSProp; rmsprop_update kernels)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (zeros(weight.shape, ctx=weight.context),  # n
                    zeros(weight.shape, ctx=weight.context),  # g
                    zeros(weight.shape, ctx=weight.context))  # delta
        return (zeros(weight.shape, ctx=weight.context),)  # n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        kwargs = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                      gamma1=self.gamma1, epsilon=self.epsilon)
        if self.clip_gradient is not None:
            kwargs["clip_gradient"] = self.clip_gradient
        if self.clip_weights is not None:
            kwargs["clip_weights"] = self.clip_weights
        if not self.centered:
            (n,) = state
            ndmod.rmsprop_update(weight, grad, n, out=weight, **kwargs)
        else:
            n, g, delta = state
            ndmod.rmspropalex_update(weight, grad, n, g, delta, out=weight,
                                     gamma2=self.gamma2, **kwargs)


@register
class AdaDelta(Optimizer):
    """AdaDelta (reference: optimizer.py AdaDelta)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context),  # accumulated g
                zeros(weight.shape, ctx=weight.context))  # accumulated delta

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        acc_g, acc_delta = state
        acc_g[:] = self.rho * acc_g + (1.0 - self.rho) * g * g
        current_delta = ((acc_delta + self.epsilon).sqrt()
                         / (acc_g + self.epsilon).sqrt()) * g
        acc_delta[:] = self.rho * acc_delta + (1.0 - self.rho) * current_delta * current_delta
        weight[:] = weight - current_delta - wd * weight


@register
class Ftrl(Optimizer):
    """FTRL (reference: optimizer.py Ftrl; ftrl_update kernel)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self.lamda1 = lamda1
        self.beta = beta
        self.lr = learning_rate

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context),  # z
                zeros(weight.shape, ctx=weight.context))  # n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        kwargs = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                      lamda1=self.lamda1, beta=self.beta)
        if self.clip_gradient is not None:
            kwargs["clip_gradient"] = self.clip_gradient
        z, n = state
        ndmod.ftrl_update(weight, grad, z, n, out=weight, **kwargs)


@register
class Adamax(Optimizer):
    """AdaMax, Adam with infinity norm (reference: optimizer.py Adamax)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        lr /= (1.0 - self.beta1 ** t)
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        m_t, u_t = state
        m_t[:] = self.beta1 * m_t + (1.0 - self.beta1) * g
        u_t[:] = ndmod._maximum(self.beta2 * u_t, g.abs())
        weight[:] = weight - lr * m_t / u_t


@register
class Nadam(Optimizer):
    """Nesterov Adam (reference: optimizer.py Nadam)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m_t, v_t = state
        m_t[:] = self.beta1 * m_t + (1.0 - self.beta1) * g
        v_t[:] = self.beta2 * v_t + (1.0 - self.beta2) * g * g
        grad_prime = g / (1.0 - self.m_schedule)
        m_t_prime = m_t / (1.0 - m_schedule_next)
        v_t_prime = v_t / (1.0 - self.beta2 ** t)
        m_t_bar = ((1.0 - momentum_t) * grad_prime + momentum_t_1 * m_t_prime)
        weight[:] = weight - lr * m_t_bar / (v_t_prime.sqrt() + self.epsilon)


# ---------------------------------------------------------------------------
# Updater — the kvstore-facing wrapper (reference: optimizer.py:1453)
# ---------------------------------------------------------------------------
class Updater:
    """Wraps an optimizer for kvstore use; owns the state dict."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(
                index, weight)
            self.states_synced[index] = True
        elif not self.states_synced[index]:
            self.states[index] = self.sync_state_context(
                self.states[index], weight.context)
            self.states_synced[index] = True
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def sync_state_context(self, state, context):
        if isinstance(state, NDArray):
            return state.as_in_context(context)
        if isinstance(state, (tuple, list)):
            return type(state)(self.sync_state_context(i, context) for i in state)
        return state

    def set_states(self, states):
        """Reference: optimizer.py set_states (pickle payload)."""
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, self.optimizer = states
        else:
            self.states = states
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        return pickle.dumps((self.states, self.optimizer) if dump_optimizer
                            else self.states)


def get_updater(optimizer):
    return Updater(optimizer)


def create(name, **kwargs):
    """Reference: mx.optimizer.create."""
    return Optimizer.create_optimizer(name, **kwargs)


def fused_update_kernel(optimizer):
    """Pure-jax fused update kernel for a stock optimizer, or None.

    Returns ``(init_state, one)`` where ``init_state(w) -> state tuple``
    of jax arrays and ``one(w, g, state, lr, wd) -> (new_w, new_state)``
    runs the exact math of ``optimizer.update`` (same kernels,
    ops/optimizer_ops.py, reference src/operator/optimizer_op-inl.h) on
    raw arrays — callable inside a jit so a whole parameter set updates
    as one XLA program (KVStoreTPU flush, Executor fused train step).
    lr/wd arrive as traced scalars; scheduler/count bookkeeping stays in
    Python via ``fused_lr_wd``.
    """
    import jax.numpy as jnp
    from .ops import optimizer_ops as oo

    def _host_zeros_like(w):
        # host-built zeros: optimizer-state init must not compile one
        # XLA broadcast program per weight shape
        import numpy as _onp
        return jnp.asarray(_onp.zeros(w.shape, w.dtype))

    kind = type(optimizer).__name__
    if kind not in ("SGD", "Adam") or getattr(optimizer, "multi_precision",
                                              False):
        return None
    rescale = float(optimizer.rescale_grad)
    clip = optimizer.clip_gradient if optimizer.clip_gradient is not None \
        else -1.0

    if kind == "SGD":
        momentum = float(optimizer.momentum)

        def init_state(w):
            return () if momentum == 0.0 else (_host_zeros_like(w),)

        def one(w, g, state, lr, wd):
            if not state:
                return oo._sgd_update(w, g, lr=lr, wd=wd,
                                      rescale_grad=rescale,
                                      clip_gradient=clip), ()
            nw, nm = oo._sgd_mom_update(w, g, state[0], lr=lr,
                                        momentum=momentum, wd=wd,
                                        rescale_grad=rescale,
                                        clip_gradient=clip)
            return nw, (nm,)
        return init_state, one

    beta1, beta2 = float(optimizer.beta1), float(optimizer.beta2)
    eps = float(optimizer.epsilon)

    def init_state(w):
        return (_host_zeros_like(w), _host_zeros_like(w))

    def one(w, g, state, lr, wd):
        nw, nme, nva = oo._adam_update(w, g, state[0], state[1], lr=lr,
                                       beta1=beta1, beta2=beta2, epsilon=eps,
                                       wd=wd, rescale_grad=rescale,
                                       clip_gradient=clip)
        return nw, (nme, nva)
    return init_state, one


def fused_lr_wd(optimizer, index):
    """Python-side per-step scheduler/count bookkeeping for the fused
    kernels: advances num_update and returns the effective (lr, wd) —
    including Adam's bias-correction lr scaling — as floats to be fed
    into the compiled update as traced scalars."""
    optimizer._update_count(index)
    lr = optimizer._get_lr(index)
    wd = optimizer._get_wd(index)
    if type(optimizer).__name__ == "Adam":
        t = optimizer._index_update_count[index]
        lr *= math.sqrt(1.0 - optimizer.beta2 ** t) / \
            (1.0 - optimizer.beta1 ** t)
    return lr, wd
