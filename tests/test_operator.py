"""Operator tests (reference: tests/python/unittest/test_operator.py).

Numeric-gradient checking is the universal oracle (test_utils.py:792 in
the reference); forward values check against numpy."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import (
    assert_almost_equal, check_numeric_gradient, check_symbolic_forward, same)


def test_unary_math_ops():
    x = np.random.uniform(0.1, 1.0, (3, 4)).astype(np.float32)
    a = nd.array(x)
    for name, ref in [
        ("exp", np.exp), ("log", np.log), ("sqrt", np.sqrt),
        ("square", np.square), ("abs", np.abs), ("sign", np.sign),
        ("sin", np.sin), ("cos", np.cos), ("tan", np.tan),
        ("sinh", np.sinh), ("cosh", np.cosh), ("tanh", np.tanh),
        ("arcsin", np.arcsin), ("arctan", np.arctan),
        ("floor", np.floor), ("ceil", np.ceil), ("round", np.round),
        ("log2", np.log2), ("log10", np.log10), ("log1p", np.log1p),
        ("expm1", np.expm1), ("rsqrt", lambda v: 1 / np.sqrt(v)),
        ("reciprocal", lambda v: 1 / v), ("cbrt", np.cbrt),
    ]:
        out = getattr(mx.nd, name)(a)
        assert_almost_equal(out, ref(x), rtol=1e-4, atol=1e-6)


def test_activations():
    x = np.random.uniform(-2, 2, (5, 5)).astype(np.float32)
    a = nd.array(x)
    assert_almost_equal(nd.relu(a), np.maximum(x, 0))
    assert_almost_equal(nd.sigmoid(a), 1 / (1 + np.exp(-x)), rtol=1e-5)
    assert_almost_equal(nd.softrelu(a), np.log1p(np.exp(x)), rtol=1e-5)
    assert_almost_equal(nd.softsign(a), x / (1 + np.abs(x)), rtol=1e-5)
    for act in ["relu", "sigmoid", "tanh", "softrelu", "softsign"]:
        out = mx.nd.Activation(a, act_type=act)
        assert out.shape == x.shape
    out = mx.nd.LeakyReLU(a, act_type="leaky", slope=0.1)
    assert_almost_equal(out, np.where(x > 0, x, 0.1 * x), rtol=1e-5)
    out = mx.nd.LeakyReLU(a, act_type="elu", slope=1.0)
    assert_almost_equal(out, np.where(x > 0, x, np.exp(x) - 1), rtol=1e-5)


def test_softmax():
    x = np.random.uniform(-1, 1, (4, 10)).astype(np.float32)
    e = np.exp(x - x.max(1, keepdims=True))
    expected = e / e.sum(1, keepdims=True)
    assert_almost_equal(nd.softmax(nd.array(x), axis=1), expected, rtol=1e-5)
    assert_almost_equal(nd.log_softmax(nd.array(x), axis=1),
                        np.log(expected), rtol=1e-4, atol=1e-5)


def test_fully_connected():
    x = np.random.rand(4, 7).astype(np.float32)
    w = np.random.rand(3, 7).astype(np.float32)
    b = np.random.rand(3).astype(np.float32)
    out = mx.nd.FullyConnected(nd.array(x), nd.array(w), nd.array(b),
                               num_hidden=3)
    assert_almost_equal(out, x @ w.T + b, rtol=1e-5)
    out = mx.nd.FullyConnected(nd.array(x), nd.array(w), num_hidden=3,
                               no_bias=True)
    assert_almost_equal(out, x @ w.T, rtol=1e-5)


def test_convolution_shapes():
    x = nd.array(np.random.rand(2, 3, 8, 8).astype(np.float32))
    w = nd.array(np.random.rand(4, 3, 3, 3).astype(np.float32))
    b = nd.zeros((4,))
    out = mx.nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4)
    assert out.shape == (2, 4, 6, 6)
    out = mx.nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4,
                            pad=(1, 1), stride=(2, 2))
    assert out.shape == (2, 4, 4, 4)


def test_convolution_vs_numpy():
    # direct correlation check on a tiny case
    x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    w = np.ones((1, 1, 2, 2), np.float32)
    out = mx.nd.Convolution(nd.array(x), nd.array(w), nd.zeros((1,)),
                            kernel=(2, 2), num_filter=1)
    expected = np.zeros((1, 1, 3, 3), np.float32)
    for i in range(3):
        for j in range(3):
            expected[0, 0, i, j] = x[0, 0, i:i + 2, j:j + 2].sum()
    assert_almost_equal(out, expected, rtol=1e-5)


def test_pooling():
    x = np.random.rand(2, 3, 6, 6).astype(np.float32)
    a = nd.array(x)
    out = mx.nd.Pooling(a, kernel=(2, 2), stride=(2, 2), pool_type="max")
    assert out.shape == (2, 3, 3, 3)
    expected = x.reshape(2, 3, 3, 2, 3, 2).max(axis=(3, 5))
    assert_almost_equal(out, expected, rtol=1e-5)
    out = mx.nd.Pooling(a, kernel=(2, 2), stride=(2, 2), pool_type="avg")
    assert_almost_equal(out, x.reshape(2, 3, 3, 2, 3, 2).mean(axis=(3, 5)),
                        rtol=1e-5)
    out = mx.nd.Pooling(a, global_pool=True, pool_type="max", kernel=(1, 1))
    assert_almost_equal(out, x.max(axis=(2, 3), keepdims=True), rtol=1e-5)
    # ceil mode: a 5x5 input keeps its ragged last window
    out = mx.nd.Pooling(nd.array(x[:, :, :5, :5]), kernel=(2, 2),
                        stride=(2, 2), pool_type="max",
                        pooling_convention="full")
    padded = np.pad(x[:, :, :5, :5], ((0, 0), (0, 0), (0, 1), (0, 1)),
                    constant_values=-np.inf)
    assert_almost_equal(out, padded.reshape(2, 3, 3, 2, 3, 2).max(axis=(3, 5)),
                        rtol=1e-5)


def test_batchnorm_inference_train():
    x = np.random.rand(4, 3, 5, 5).astype(np.float32)
    gamma, beta = np.ones(3, np.float32), np.zeros(3, np.float32)
    mm, mv = np.zeros(3, np.float32), np.ones(3, np.float32)
    moving_mean, moving_var = nd.array(mm), nd.array(mv)
    with mx.autograd.record(train_mode=True):
        out = mx.nd.BatchNorm(nd.array(x), nd.array(gamma), nd.array(beta),
                              moving_mean, moving_var, fix_gamma=False)
    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3))
    expected = (x - mean[None, :, None, None]) / np.sqrt(
        var[None, :, None, None] + 1e-3)
    assert_almost_equal(out, expected, rtol=1e-3, atol=1e-4)
    # moving stats updated in train mode
    assert not np.allclose(moving_mean.asnumpy(), mm)


def test_embedding_take():
    w = np.random.rand(10, 4).astype(np.float32)
    idx = np.array([1, 3, 5], np.float32)
    out = mx.nd.Embedding(nd.array(idx), nd.array(w), input_dim=10,
                          output_dim=4)
    assert_almost_equal(out, w[[1, 3, 5]])
    out = mx.nd.take(nd.array(w), nd.array(idx, dtype="int32"), axis=0)
    assert_almost_equal(out, w[[1, 3, 5]])


def test_broadcast_ops():
    a = np.random.rand(2, 1, 3).astype(np.float32)
    b = np.random.rand(1, 4, 3).astype(np.float32)
    for name, ref in [("broadcast_add", a + b), ("broadcast_mul", a * b),
                      ("broadcast_sub", a - b), ("broadcast_div", a / b),
                      ("broadcast_maximum", np.maximum(a, b)),
                      ("broadcast_minimum", np.minimum(a, b))]:
        if hasattr(mx.nd, name):
            assert_almost_equal(getattr(mx.nd, name)(nd.array(a), nd.array(b)),
                                ref, rtol=1e-5)
    # elemwise with same shape
    x = np.random.rand(3, 3).astype(np.float32)
    assert_almost_equal(mx.nd.elemwise_add(nd.array(x), nd.array(x)), 2 * x)


def test_where_clip():
    cond = nd.array([1, 0, 1], dtype="float32")
    x = nd.array([1.0, 2.0, 3.0])
    y = nd.array([4.0, 5.0, 6.0])
    assert same(mx.nd.where(cond, x, y), [1, 5, 3])
    assert same(nd.array([-2.0, 0.5, 9.0]).clip(0, 1), [0, 0.5, 1])


def test_gather_scatter_nd():
    data = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    indices = nd.array([[0, 2], [1, 3]], dtype="int32")
    out = mx.nd.gather_nd(data, indices)
    assert same(out, [1.0, 11.0])


def test_slice_ops():
    x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    a = nd.array(x)
    assert same(mx.nd.slice(a, begin=(0, 1), end=(2, 3)), x[0:2, 1:3])
    assert same(mx.nd.slice_axis(a, axis=2, begin=1, end=3), x[:, :, 1:3])
    outs = mx.nd.SliceChannel(a, num_outputs=3, axis=1)
    assert len(outs) == 3
    assert same(outs[0], x[:, 0:1, :])


def test_sequence_ops():
    x = np.random.rand(4, 2, 3).astype(np.float32)  # (seq, batch, feat)
    slen = nd.array([2, 4], dtype="float32")
    out = mx.nd.SequenceMask(nd.array(x), sequence_length=slen,
                             use_sequence_length=True)
    expected = x.copy()
    expected[2:, 0] = 0
    assert_almost_equal(out, expected)
    out = mx.nd.SequenceLast(nd.array(x), sequence_length=slen,
                             use_sequence_length=True)
    assert_almost_equal(out, np.stack([x[1, 0], x[3, 1]]))
    out = mx.nd.SequenceReverse(nd.array(x), sequence_length=slen,
                                use_sequence_length=True)
    assert_almost_equal(out[0, 0], x[1, 0])
    assert_almost_equal(out[0, 1], x[3, 1])


def test_optimizer_update_ops():
    w = nd.array([1.0, 2.0])
    g = nd.array([0.1, 0.2])
    out = mx.nd.sgd_update(w, g, lr=0.1, wd=0.0)
    assert_almost_equal(out, [0.99, 1.98], rtol=1e-5)
    mom = nd.zeros((2,))
    out = mx.nd.sgd_mom_update(w, g, mom, lr=0.1, momentum=0.9, wd=0.0)
    assert_almost_equal(out, [0.99, 1.98], rtol=1e-5)
    mean, var = nd.zeros((2,)), nd.zeros((2,))
    out = mx.nd.adam_update(w, g, mean, var, lr=0.1, beta1=0.9, beta2=0.999,
                            epsilon=1e-8, wd=0.0)
    assert out.shape == (2,)


def test_random_ops():
    mx.random.seed(42)
    u = mx.nd.random.uniform(0, 1, shape=(1000,))
    assert 0.4 < float(u.asnumpy().mean()) < 0.6
    n = mx.nd.random.normal(0, 1, shape=(1000,))
    assert abs(float(n.asnumpy().mean())) < 0.15
    mx.random.seed(42)
    u2 = mx.nd.random.uniform(0, 1, shape=(1000,))
    assert same(u, u2)  # reproducible under seed


def test_symbolic_fc_grad():
    x = mx.sym.var("x")
    w = mx.sym.var("w")
    b = mx.sym.var("b")
    fc = mx.sym.FullyConnected(x, w, b, num_hidden=3)
    loss = mx.sym.sum(fc)
    check_numeric_gradient(
        loss, {"x": np.random.rand(2, 4).astype(np.float32),
               "w": np.random.rand(3, 4).astype(np.float32),
               "b": np.random.rand(3).astype(np.float32)}, rtol=0.05)


def test_symbolic_conv_grad():
    x = mx.sym.var("x")
    w = mx.sym.var("w")
    conv = mx.sym.Convolution(x, w, kernel=(2, 2), num_filter=2, no_bias=True,
                              name="c")
    loss = mx.sym.sum(conv)
    check_numeric_gradient(
        loss, {"x": np.random.rand(1, 2, 4, 4).astype(np.float32),
               "w": np.random.rand(2, 2, 2, 2).astype(np.float32)}, rtol=0.05)


def test_elemwise_numeric_grads():
    for op in [mx.sym.tanh, mx.sym.sigmoid, mx.sym.exp, mx.sym.square]:
        x = mx.sym.var("x")
        loss = mx.sym.sum(op(x))
        check_numeric_gradient(
            loss, {"x": np.random.uniform(0.2, 0.8, (3, 3)).astype(np.float32)},
            rtol=0.05)


def test_layer_norm():
    x = np.random.rand(4, 6).astype(np.float32)
    gamma = np.random.rand(6).astype(np.float32)
    beta = np.random.rand(6).astype(np.float32)
    out = mx.nd.LayerNorm(nd.array(x), nd.array(gamma), nd.array(beta))
    mean = x.mean(-1, keepdims=True)
    std = np.sqrt(x.var(-1, keepdims=True) + 1e-5)
    assert_almost_equal(out, (x - mean) / std * gamma + beta, rtol=1e-4,
                        atol=1e-5)


def test_dropout_modes():
    x = nd.ones((200, 200))
    with mx.autograd.record(train_mode=True):
        y = mx.nd.Dropout(x, p=0.3)
    m = y.asnumpy()
    frac_zero = (m == 0).mean()
    assert 0.2 < frac_zero < 0.4
    kept = m[m != 0]
    assert_almost_equal(kept, np.full_like(kept, 1 / 0.7), rtol=1e-4)
