"""Native c_predict_api ABI (reference: include/mxnet/c_predict_api.h,
tested the way the reference's predict-cpp example exercises it):
create-from-buffers, set input, forward, read shape + output, and a
fully standalone C++ host program."""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.native import get_predict_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_model(tmp_path):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(net, mx.sym.Variable("softmax_label"),
                               name="softmax")
    rng = np.random.RandomState(0)
    exe = net.simple_bind(data=(2, 5), softmax_label=(2,))
    params = {}
    for k, v in exe.arg_dict.items():
        if k not in ("data", "softmax_label"):
            a = rng.rand(*v.shape).astype(np.float32)
            v._data = mx.nd.array(a)._data
            params["arg:" + k] = mx.nd.array(a)
    pfile = str(tmp_path / "toy-0000.params")
    sfile = str(tmp_path / "toy-symbol.json")
    mx.nd.save(pfile, params)
    with open(sfile, "w") as f:
        f.write(net.tojson())
    return net, exe, sfile, pfile


def test_c_predict_roundtrip(tmp_path):
    lib = get_predict_lib()
    if lib is None:
        pytest.skip("no native toolchain")
    net, exe, sfile, pfile = _toy_model(tmp_path)
    json_str = open(sfile).read().encode()
    param_bytes = open(pfile, "rb").read()

    h = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape = (ctypes.c_uint * 2)(2, 5)
    rc = lib.MXPredCreate(json_str, param_bytes, len(param_bytes), 1, 0,
                          1, keys, indptr, shape, ctypes.byref(h))
    assert rc == 0, lib.MXGetLastError()

    rng = np.random.RandomState(1)
    x = rng.rand(2, 5).astype(np.float32)
    assert lib.MXPredSetInput(
        h, b"data", x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        x.size) == 0, lib.MXGetLastError()

    # the canonical C call order sizes the output buffer BETWEEN
    # SetInput and Forward — the shape query must not run (and clobber)
    # anything
    sd = ctypes.POINTER(ctypes.c_uint)()
    ndim = ctypes.c_uint()
    assert lib.MXPredGetOutputShape(h, 0, ctypes.byref(sd),
                                    ctypes.byref(ndim)) == 0
    oshape = tuple(sd[i] for i in range(ndim.value))
    assert oshape == (2, 3)

    assert lib.MXPredForward(h) == 0, lib.MXGetLastError()
    out = np.zeros(6, np.float32)
    assert lib.MXPredGetOutput(
        h, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size) == 0, lib.MXGetLastError()

    exe.forward(is_train=False, data=x)
    assert np.allclose(out.reshape(2, 3), exe.outputs[0].asnumpy(),
                       atol=1e-5)

    # errors surface through MXGetLastError, not crashes
    bad = np.zeros(4, np.float32)
    assert lib.MXPredSetInput(
        h, b"nonexistent",
        bad.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), bad.size) != 0
    assert b"nonexistent" in lib.MXGetLastError()
    assert lib.MXPredFree(h) == 0


def test_c_predict_standalone_host(tmp_path):
    """Compile and run the predict-cpp example — a C++ main with no
    Python of its own, inference through the embedded interpreter."""
    lib = get_predict_lib()
    if lib is None:
        pytest.skip("no native toolchain")
    _, _, sfile, pfile = _toy_model(tmp_path)
    src = os.path.join(REPO, "example", "image-classification",
                       "predict-cpp", "image_classification_predict.cc")
    exe_path = str(tmp_path / "predict_demo")
    ldflags = subprocess.run(
        ["python3-config", "--ldflags", "--embed"],
        capture_output=True, text=True, check=True).stdout.split()
    so = os.path.join(REPO, "mxnet_tpu", "native", "libmxnet_predict.so")
    subprocess.run(["g++", "-O2", src, "-o", exe_path, so,
                    "-Wl,-rpath," + os.path.dirname(so)] + ldflags,
                   check=True, capture_output=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([exe_path, sfile, pfile, "2,5"],
                          capture_output=True, text=True, env=env,
                          timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "predict-cpp OK" in proc.stdout
    assert "output shape: (2, 3)" in proc.stdout
