"""Neural-network operators.

Reference: ``src/operator/nn/`` (Convolution convolution-inl.h, Pooling
pool.h, FullyConnected, BatchNorm, LayerNorm layer_norm-inl.h, Activation,
Dropout, Softmax softmax-inl.h, LRN, UpSampling) plus the cuDNN stateful
variants under ``src/operator/nn/cudnn/`` and the fused RNN
(``src/operator/rnn-inl.h``, ``cudnn_rnn-inl.h``).

TPU-native design decisions:
- Convolution/FullyConnected lower to ``lax.conv_general_dilated`` /
  ``lax.dot_general`` — the MXU systolic-array primitives.  There is no
  im2col (reference nn/im2col.h) and no algo autotuning registry
  (nn/cudnn/cudnn_algoreg-inl.h): XLA picks the conv algorithm.
- BatchNorm moving stats are explicit auxiliary state: the op returns the
  updated stats as extra outputs (``mutate_aux``) instead of mutating
  hidden buffers — keeping everything functionally traceable under jit.
- Dropout takes an explicit RNG key (``__rng__``) injected by the runtime;
  inside a jitted training step the key is threaded functionally.
- The fused RNN is a ``lax.scan`` over time — compiler-unrolled gates,
  one matmul per gate group per step, same packed-parameter layout as the
  reference so Gluon rnn_layer checkpoints stay compatible.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, Param as P, normalize_tuple
from ..base import MXNetError


# -- FullyConnected ---------------------------------------------------------
@register("FullyConnected", params=[
    P("num_hidden", int, required=True, low=1,
      doc="number of output units"),
    P("no_bias", bool, default=False),
    P("flatten", bool, default=True,
      doc="collapse all trailing input dims before the matmul")])
def _fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                     flatten=True, **attrs):
    """Reference: src/operator/nn/fully_connected-inl.h.
    One MXU matmul; bias-add fuses into the matmul epilogue."""
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = jnp.matmul(data, weight.T)
    if not no_bias and bias is not None:
        out = out + bias
    return out


# -- Activation -------------------------------------------------------------
@register("Activation", params=[
    P("act_type", ("relu", "sigmoid", "tanh", "softrelu", "softsign"),
      required=True)])
def _activation(data, act_type="relu", **attrs):
    """Reference: src/operator/nn/activation-inl.h."""
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return lax.logistic(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jnp.logaddexp(data, 0.0)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    raise MXNetError("unknown act_type %s" % act_type)


@register("LeakyReLU", needs_is_train=True, needs_rng=True, params=[
    P("act_type", ("leaky", "elu", "selu", "prelu", "rrelu", "gelu"),
      default="leaky"),
    P("slope", float, default=0.25, low=0.0),
    P("lower_bound", float, default=0.125, low=0.0),
    P("upper_bound", float, default=0.334, low=0.0)])
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334,
                __is_train__=False, __rng__=None, **attrs):
    """Reference: src/operator/leaky_relu-inl.h (leaky/prelu/elu/rrelu/selu)."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if data.ndim > 1 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data > 0, data, alpha * jnp.expm1(data))
    if act_type == "rrelu":
        if __is_train__ and __rng__ is not None:
            s = jax.random.uniform(__rng__, data.shape, dtype=data.dtype,
                                   minval=lower_bound, maxval=upper_bound)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, s * data)
    raise MXNetError("unknown LeakyReLU act_type %s" % act_type)


# -- softmax family ---------------------------------------------------------
@register("softmax", params=[
    P("axis", int, default=-1),
    P("temperature", float, default=1.0)])
def _softmax(data, axis=-1, temperature=None, **attrs):
    """Reference: src/operator/nn/softmax-inl.h."""
    if temperature:
        data = data / temperature
    return jax.nn.softmax(data, axis=axis)


@register("log_softmax", params=[P("axis", int, default=-1)])
def _log_softmax(data, axis=-1, temperature=None, **attrs):
    if temperature:
        data = data / temperature
    return jax.nn.log_softmax(data, axis=axis)


@register("SoftmaxActivation", params=[
    P("mode", ("instance", "channel"), default="instance")])
def _softmax_activation(data, mode="instance", **attrs):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label, **attrs):
    logp = jax.nn.log_softmax(data, axis=-1)
    return -jnp.sum(jnp.take_along_axis(
        logp, label.astype(jnp.int32)[:, None], axis=-1))


# -- Convolution ------------------------------------------------------------
def _conv_dn(ndim, layout):
    if layout in (None, "NCHW", "NCW", "NCDHW"):
        spec = "NC" + "DHW"[3 - ndim:]
        return (spec, "OI" + "DHW"[3 - ndim:], spec)
    if layout in ("NHWC", "NWC", "NDHWC"):
        spec = "N" + "DHW"[3 - ndim:] + "C"
        return (spec, "O" + "DHW"[3 - ndim:] + "I", spec)
    raise MXNetError("unsupported layout %s" % layout)


@register("Convolution", aliases=("Convolution_v1",), params=[
    P("kernel", tuple, required=True, low=1, doc="conv window (h, w)"),
    P("num_filter", int, required=True, low=1, high=100000),
    P("stride", tuple, default=None, low=1),
    P("dilate", tuple, default=None, low=1),
    P("pad", tuple, default=None, low=0),
    P("num_group", int, default=1, low=1),
    P("no_bias", bool, default=False),
    P("layout", ("NCHW", "NHWC", "NCW", "NCDHW", None), default=None)])
def _convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                 pad=None, num_filter=None, num_group=1, no_bias=False,
                 layout=None, cudnn_tune=None, cudnn_off=False, workspace=None,
                 **attrs):
    """Reference: src/operator/nn/convolution-inl.h.

    TPU-native: one ``lax.conv_general_dilated`` (MXU). num_group maps to
    feature_group_count (covers depthwise, reference
    nn/depthwise_convolution-inl.h, as a special case)."""
    kernel = normalize_tuple(kernel)
    nd = len(kernel)
    stride = normalize_tuple(stride, nd) if stride else (1,) * nd
    dilate = normalize_tuple(dilate, nd) if dilate else (1,) * nd
    pad = normalize_tuple(pad, nd) if pad else (0,) * nd
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _conv_dn(nd, layout))
    # bf16 in -> bf16 out: the TPU MXU accumulates in fp32 internally, and
    # an explicit preferred_element_type=f32 upcast breaks the conv
    # transpose rule (f32 cotangent vs bf16 residual in grad-of-weight)
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    if not no_bias and bias is not None:
        c_axis = dn.out_spec.index(1) if hasattr(dn, "out_spec") else 1
        shape = [1] * out.ndim
        shape[1 if layout in (None, "NCHW", "NCW", "NCDHW") else out.ndim - 1] = -1
        out = out + bias.reshape(shape)
    return out


@register("Deconvolution", params=[
    P("kernel", tuple, required=True, low=1),
    P("num_filter", int, required=True, low=1),
    P("stride", tuple, default=None, low=1),
    P("dilate", tuple, default=None, low=1),
    P("pad", tuple, default=None, low=0),
    P("adj", tuple, default=None, low=0),
    P("num_group", int, default=1, low=1),
    P("no_bias", bool, default=True)])
def _deconvolution(data, weight, bias=None, kernel=None, stride=None,
                   dilate=None, pad=None, adj=None, target_shape=None,
                   num_filter=None, num_group=1, no_bias=True, layout=None,
                   workspace=None, cudnn_tune=None, cudnn_off=False, **attrs):
    """Reference: src/operator/nn/deconvolution-inl.h (transposed conv)."""
    kernel = normalize_tuple(kernel)
    nd = len(kernel)
    stride = normalize_tuple(stride, nd) if stride else (1,) * nd
    dilate = normalize_tuple(dilate, nd) if dilate else (1,) * nd
    pad = normalize_tuple(pad, nd) if pad else (0,) * nd
    adj = normalize_tuple(adj, nd) if adj else (0,) * nd
    # transposed conv = lhs-dilated conv with flipped kernel
    pads = []
    for i in range(nd):
        k_eff = (kernel[i] - 1) * dilate[i] + 1
        lo = k_eff - 1 - pad[i]
        hi = k_eff - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    # weight layout (in_ch, out_ch/g, *k) -> conv expects (out, in/g, *k)
    w = jnp.swapaxes(weight, 0, 1)
    if num_group > 1:
        cin = data.shape[1]
        w = weight.reshape((num_group, cin // num_group) + weight.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((-1, cin // num_group) + weight.shape[2:])
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _conv_dn(nd, layout))
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# -- Pooling ----------------------------------------------------------------
@register("Pooling", aliases=("Pooling_v1",), params=[
    P("kernel", tuple, default=None, low=1),
    P("pool_type", ("max", "avg", "sum", "lp"), default="max"),
    P("stride", tuple, default=None, low=1),
    P("pad", tuple, default=None, low=0),
    P("global_pool", bool, default=False),
    P("pooling_convention", ("valid", "full", "same"), default="valid"),
    P("count_include_pad", bool, default=True)])
def _pooling(data, kernel=None, pool_type="max", stride=None, pad=None,
             global_pool=False, pooling_convention="valid", cudnn_off=False,
             count_include_pad=True, **attrs):
    """Reference: src/operator/nn/pooling-inl.h + nn/pool.h.
    lax.reduce_window lowers to the TPU vector unit."""
    nd = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = normalize_tuple(kernel)
        stride = normalize_tuple(stride, nd) if stride else (1,) * nd
        pad = normalize_tuple(pad, nd) if pad else (0,) * nd
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    base_pad = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if pooling_convention == "full" and not global_pool:
        # ceil-mode: add extra right-pad so ceil((x+2p-k)/s)+1 windows fit
        for i in range(nd):
            x = data.shape[2 + i]
            p, k, s = pad[i], kernel[i], stride[i]
            out_full = int(np.ceil((x + 2 * p - k) / s)) + 1
            need = (out_full - 1) * s + k - (x + 2 * p)
            lo, hi = base_pad[2 + i]
            base_pad[2 + i] = (lo, hi + max(need, 0))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, base_pad)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides, base_pad)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            return summed / np.prod(kernel)
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, base_pad)
        return summed / counts
    raise MXNetError("unknown pool_type %s" % pool_type)


@register("_contrib_AdaptiveAvgPooling2D")
def _adaptive_avg_pool(data, output_size=None, **attrs):
    if not output_size:
        out = (1, 1)
    else:
        out = normalize_tuple(output_size, 2)
    n, c, h, w = data.shape
    if h % out[0] == 0 and w % out[1] == 0:
        kh, kw = h // out[0], w // out[1]
        x = data.reshape(n, c, out[0], kh, out[1], kw)
        return x.mean(axis=(3, 5))
    return jax.image.resize(data, (n, c, out[0], out[1]), method="linear")


@register("_contrib_BilinearResize2D")
def _bilinear_resize(data, height=None, width=None, scale_height=None,
                     scale_width=None, **attrs):
    n, c, h, w = data.shape
    th = height if height else int(h * scale_height)
    tw = width if width else int(w * scale_width)
    return jax.image.resize(data, (n, c, th, tw), method="linear")


@register("UpSampling", params=[
    P("scale", int, required=True, low=1),
    P("sample_type", ("nearest", "bilinear"), default="nearest"),
    P("num_filter", int, default=0, low=0),
    P("multi_input_mode", ("concat", "sum"), default="concat")])
def _upsampling(*args, scale=1, sample_type="nearest", num_filter=0,
                num_args=1, multi_input_mode="concat", workspace=None, **attrs):
    """Reference: src/operator/upsampling-inl.h."""
    data = args[0]
    n, c, h, w = data.shape
    if sample_type == "nearest":
        outs = []
        for a in args:
            up = jnp.repeat(jnp.repeat(a, scale, axis=2), scale, axis=3)
            outs.append(up)
        return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    # bilinear uses a deconv with provided weight (args[1])
    weight = args[1]
    return _deconvolution(data, weight, None,
                          kernel=(2 * scale - scale % 2,) * 2,
                          stride=(scale, scale),
                          pad=((scale - scale % 2 + 1) // 2,) * 2,
                          num_filter=num_filter, num_group=c, no_bias=True)


# -- normalization ----------------------------------------------------------
@register("BatchNorm", aliases=("BatchNorm_v1",), needs_is_train=True, params=[
    P("eps", float, default=1e-3, low=0.0),
    P("momentum", float, default=0.9, low=0.0, high=1.0),
    P("fix_gamma", bool, default=True),
    P("use_global_stats", bool, default=False),
    P("axis", int, default=1),
    P("output_mean_var", bool, default=False)],
          num_outputs=3, mutate_aux=("moving_mean", "moving_var"))
def _batch_norm(data, gamma, beta, moving_mean, moving_var,
                eps=1e-3, momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False,
                __is_train__=False, **attrs):
    """Reference: src/operator/nn/batch_norm-inl.h.

    Outputs: (out, updated_moving_mean, updated_moving_var); the runtime
    writes outputs[1:] back to the aux arrays (mutate_aux), replacing the
    reference's hidden in-place update of aux states."""
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if __is_train__ and not use_global_stats:
        # stats in f32 even for bf16 activations (mixed-precision policy):
        # a bf16 mean over a 224x224x64 channel loses ~3 decimal digits
        sdata = data.astype(jnp.float32) if data.dtype != jnp.float32 else data
        mean = jnp.mean(sdata, axis=red)
        var = jnp.var(sdata, axis=red)
        new_mean = momentum * moving_mean + (1 - momentum) * mean.astype(moving_mean.dtype)
        new_var = momentum * moving_var + (1 - momentum) * var.astype(moving_var.dtype)
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(var + eps)
    out = (data - mean.reshape(shape)) * (inv * g).reshape(shape) + beta.reshape(shape)
    return out.astype(data.dtype), new_mean, new_var


def fused_bn_relu_eval(data, gamma, beta, moving_mean, moving_var,
                       eps=1e-3, fix_gamma=True, relu=True):
    """Inference BatchNorm(+ReLU) as ONE Pallas pass: the moving stats
    fold into per-channel scale/bias and ``fused_scale_bias_relu``
    applies them (+ the activation) in a single VMEM-resident sweep —
    the MKL-DNN BN+Activation epilogue fusion, TPU-native.  NCHW; the
    executor's eval-graph peephole (symbol.py build_graph_fn,
    ``MXNET_PALLAS_BN_RELU``) is the call site."""
    from .pallas_kernels import fused_scale_bias_relu
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    scale = g * lax.rsqrt(moving_var + eps)
    bias = beta - moving_mean * scale
    b, c, h, w = data.shape
    flat = jnp.transpose(data, (0, 2, 3, 1)).reshape(-1, c)
    y = fused_scale_bias_relu(flat, scale, bias, relu=relu)
    return jnp.transpose(y.reshape(b, h, w, c), (0, 3, 1, 2))


@register("LayerNorm", params=[
    P("axis", int, default=-1),
    P("eps", float, default=1e-5, low=0.0),
    P("output_mean_var", bool, default=False)])
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False, **attrs):
    """Reference: src/operator/nn/layer_norm-inl.h.

    Last-axis norms route through the fused Pallas kernel
    (``ops/pallas_kernels.py`` — mean/var/normalize/affine in one VMEM
    pass, custom_vjp backward) when ``MXNET_PALLAS_NORM`` is on; other
    axes and the knob-off A/B keep the jnp reduction chain."""
    from .pallas_kernels import (family_enabled, fused_layernorm,
                                 fused_layernorm_eligible)
    if (axis % data.ndim == data.ndim - 1 and data.ndim >= 2
            and family_enabled("MXNET_PALLAS_NORM")
            and fused_layernorm_eligible(data.shape[-1])):
        return fused_layernorm(data, gamma, beta, float(eps))
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("RMSNorm", params=[
    P("axis", int, default=-1),
    P("eps", float, default=1e-6, low=0.0)])
def _rms_norm(data, gamma, axis=-1, eps=1e-6, **attrs):
    """Root-mean-square normalisation (Zhang & Sennrich,
    arXiv:1910.07467): ``data * rsqrt(mean(data**2, axis) + eps) *
    gamma`` — no mean subtracted, no beta.  The statistic and the scaling
    run in float32 whatever ``data``'s dtype; the result is cast back."""
    xf = data.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=axis, keepdims=True) + eps)
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    out = xf * inv * gamma.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@register("InstanceNorm", params=[
    P("eps", float, default=1e-3, low=0.0)])
def _instance_norm(data, gamma, beta, eps=1e-3, **attrs):
    """Reference: src/operator/instance_norm-inl.h."""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("LRN", params=[
    P("nsize", int, required=True, low=1),
    P("alpha", float, default=1e-4, low=0.0),
    P("beta", float, default=0.75, low=0.0),
    P("knorm", float, default=2.0)])
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **attrs):
    """Reference: src/operator/nn/lrn-inl.h (cross-channel LRN)."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = jnp.stack([padded[:, i:i + data.shape[1]] for i in range(nsize)], 0).sum(0)
    return data / jnp.power(knorm + alpha / nsize * window, beta)


# -- Dropout ----------------------------------------------------------------
@register("Dropout", needs_is_train=True, needs_rng=True, params=[
    P("p", float, default=0.5, low=0.0, high=1.0,
      doc="fraction of units dropped in train mode"),
    P("mode", ("training", "always"), default="training"),
    P("axes", tuple, default=(), low=0)])
def _dropout(data, p=0.5, mode="training", axes=(), __is_train__=False,
             __rng__=None, **attrs):
    """Reference: src/operator/nn/dropout-inl.h (inverted dropout)."""
    if (not __is_train__ and mode != "always") or p == 0 or __rng__ is None:
        return data
    shape = list(data.shape)
    for a in normalize_tuple(axes) if axes else ():
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(__rng__, keep, tuple(shape))
    return jnp.where(mask, data / keep, 0.0).astype(data.dtype)


# -- Fused RNN (reference: src/operator/rnn-inl.h, cudnn_rnn-inl.h) --------
def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def _unpack_rnn_params(params, mode, num_layers, input_size, H, D):
    """Split the reference's packed cuDNN-layout parameter vector:
    all weights (layer-major, direction inner: W_i2h then W_h2h), then all
    biases (b_i2h then b_h2h).  Matches rnn-inl.h GetRnnParamSize."""
    G = _gates(mode)
    ws, offset = [], 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else H * D
        for d in range(D):
            wi = params[offset: offset + G * H * in_sz].reshape(G * H, in_sz)
            offset += G * H * in_sz
            wh = params[offset: offset + G * H * H].reshape(G * H, H)
            offset += G * H * H
            ws.append((wi, wh))
    bs = []
    for layer in range(num_layers):
        for d in range(D):
            bi = params[offset: offset + G * H]; offset += G * H
            bh = params[offset: offset + G * H]; offset += G * H
            bs.append((bi, bh))
    return ws, bs


def _rnn_cell_step(mode, H):
    def step(carry, gates_x, wh, bh):
        if mode == "lstm":
            h, c = carry
            g = gates_x + jnp.matmul(h, wh.T) + bh
            i, f, gg, o = jnp.split(g, 4, axis=-1)
            i, f, o = lax.logistic(i), lax.logistic(f), lax.logistic(o)
            c2 = f * c + i * jnp.tanh(gg)
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2
        if mode == "gru":
            h = carry[0]
            gx_r, gx_z, gx_n = jnp.split(gates_x, 3, axis=-1)
            gh = jnp.matmul(h, wh.T) + bh
            gh_r, gh_z, gh_n = jnp.split(gh, 3, axis=-1)
            r = lax.logistic(gx_r + gh_r)
            z = lax.logistic(gx_z + gh_z)
            n = jnp.tanh(gx_n + r * gh_n)
            h2 = (1 - z) * n + z * h
            return (h2,), h2
        h = carry[0]
        a = gates_x + jnp.matmul(h, wh.T) + bh
        h2 = jnp.maximum(a, 0) if mode == "rnn_relu" else jnp.tanh(a)
        return (h2,), h2
    return step


def _rnn_nout(attrs):
    if attrs.get("state_outputs", False):
        return 3 if attrs.get("mode") == "lstm" else 2
    return 1


@register("RNN", needs_is_train=True, needs_rng=True, num_outputs=_rnn_nout,
          params=[
    P("state_size", int, required=True, low=1),
    P("num_layers", int, required=True, low=1),
    P("mode", ("rnn_relu", "rnn_tanh", "lstm", "gru"), required=True),
    P("bidirectional", bool, default=False),
    P("p", float, default=0.0, low=0.0, high=1.0,
      doc="dropout between stacked layers"),
    P("state_outputs", bool, default=False)])
def _rnn(data, params, state, state_cell=None, mode="lstm", state_size=None,
         num_layers=1, bidirectional=False, p=0.0, state_outputs=False,
         __is_train__=False, __rng__=None, **attrs):
    """Fused multi-layer (bi)RNN (reference: src/operator/rnn-inl.h).

    data: (T, N, I) time-major like the reference.  Each layer is one
    ``lax.scan`` whose per-step h2h matmul runs on the MXU; the i2h
    projection for ALL timesteps is hoisted out of the scan into a single
    big matmul (T*N, I)x(I, G*H) — the TPU-native equivalent of cuDNN's
    fused RNN kernel."""
    T, N, _ = data.shape
    H = state_size
    D = 2 if bidirectional else 1
    G = _gates(mode)
    if mode == "lstm" and state_cell is None:
        state_cell = jnp.zeros_like(state)
    ws, bs = _unpack_rnn_params(params, mode, num_layers, data.shape[2], H, D)
    step = _rnn_cell_step(mode, H)

    x = data
    h_states, c_states = [], []
    key = __rng__
    for layer in range(num_layers):
        outs = []
        for d in range(D):
            idx = layer * D + d
            wi, wh = ws[idx]
            bi, bh = bs[idx]
            xs = jnp.flip(x, axis=0) if d == 1 else x
            gates_x = jnp.einsum("tni,gi->tng", xs, wi) + bi
            h0 = state[idx]
            carry = (h0, state_cell[idx]) if mode == "lstm" else (h0,)

            def scan_fn(carry, gx, wh=wh, bh=bh):
                return step(carry, gx, wh, bh)

            carry, ys = lax.scan(scan_fn, carry, gates_x)
            if d == 1:
                ys = jnp.flip(ys, axis=0)
            outs.append(ys)
            h_states.append(carry[0])
            if mode == "lstm":
                c_states.append(carry[1])
        x = jnp.concatenate(outs, axis=-1) if D == 2 else outs[0]
        if p > 0 and __is_train__ and layer < num_layers - 1 and key is not None:
            key, sub = jax.random.split(key)
            mask = jax.random.bernoulli(sub, 1 - p, x.shape)
            x = jnp.where(mask, x / (1 - p), 0.0).astype(x.dtype)
    if state_outputs:
        hs = jnp.stack(h_states, axis=0)
        if mode == "lstm":
            return x, hs, jnp.stack(c_states, axis=0)
        return x, hs
    return x


@register("SpatialTransformer", params=[
    P("transform_type", ("affine",), default="affine"),
    P("sampler_type", ("bilinear",), default="bilinear"),
    P("target_shape", tuple, required=True, low=1)])
def _spatial_transformer(data, loc, target_shape=None, transform_type="affine",
                         sampler_type="bilinear", **attrs):
    """Reference: src/operator/spatial_transformer-inl.h."""
    n, c, h, w = data.shape
    th, tw = normalize_tuple(target_shape, 2)
    theta = loc.reshape(n, 2, 3)
    ys = jnp.linspace(-1, 1, th)
    xs = jnp.linspace(-1, 1, tw)
    gx, gy = jnp.meshgrid(xs, ys)
    grid = jnp.stack([gx.ravel(), gy.ravel(), jnp.ones(th * tw)], axis=0)
    src = jnp.einsum("nij,jk->nik", theta, grid)  # (n, 2, th*tw)
    return _bilinear_sample(data, src.reshape(n, 2, th, tw))


def _bilinear_sample(data, grid):
    """grid: (n,2,h,w) normalized coords; shared by GridGenerator/BilinearSampler
    (reference: src/operator/bilinear_sampler-inl.h)."""
    n, c, H, W = data.shape
    gx = (grid[:, 0] + 1) * (W - 1) / 2
    gy = (grid[:, 1] + 1) * (H - 1) / 2
    x0 = jnp.floor(gx); y0 = jnp.floor(gy)
    wx = gx - x0; wy = gy - y0

    def gather(yi, xi):
        yi_c = jnp.clip(yi.astype(jnp.int32), 0, H - 1)
        xi_c = jnp.clip(xi.astype(jnp.int32), 0, W - 1)
        valid = ((yi >= 0) & (yi <= H - 1) & (xi >= 0) & (xi <= W - 1))
        vals = jax.vmap(lambda d, y, x: d[:, y, x])(data, yi_c, xi_c)  # (n, c, h, w)
        return vals * valid[:, None].astype(data.dtype)

    out = (gather(y0, x0) * ((1 - wx) * (1 - wy))[:, None]
           + gather(y0, x0 + 1) * (wx * (1 - wy))[:, None]
           + gather(y0 + 1, x0) * ((1 - wx) * wy)[:, None]
           + gather(y0 + 1, x0 + 1) * (wx * wy)[:, None])
    return out.astype(data.dtype)


@register("BilinearSampler")
def _bilinear_sampler(data, grid, **attrs):
    return _bilinear_sample(data, grid)


@register("GridGenerator", params=[
    P("transform_type", ("affine", "warp"), default="affine"),
    P("target_shape", tuple, default=None, low=1)])
def _grid_generator(data, transform_type="affine", target_shape=None, **attrs):
    if transform_type == "affine":
        # warp mode needs no target_shape (the flow field carries it)
        if target_shape is None:
            raise MXNetError(
                "GridGenerator: target_shape is required when "
                "transform_type='affine'")
        th, tw = normalize_tuple(target_shape, 2)
        n = data.shape[0]
        theta = data.reshape(n, 2, 3)
        ys = jnp.linspace(-1, 1, th)
        xs = jnp.linspace(-1, 1, tw)
        gx, gy = jnp.meshgrid(xs, ys)
        grid = jnp.stack([gx.ravel(), gy.ravel(), jnp.ones(th * tw)], axis=0)
        src = jnp.einsum("nij,jk->nik", theta, grid)
        return src.reshape(n, 2, th, tw)
    # warp: data is (n,2,h,w) flow field
    n, _, h, w = data.shape
    xs = jnp.arange(w); ys = jnp.arange(h)
    gx, gy = jnp.meshgrid(xs, ys)
    base = jnp.stack([gx, gy], axis=0)[None]
    absg = data + base
    normx = absg[:, 0] * 2 / (w - 1) - 1
    normy = absg[:, 1] * 2 / (h - 1) - 1
    return jnp.stack([normx, normy], axis=1)
