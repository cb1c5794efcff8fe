"""Header-only C++ frontend (cpp-package/include/mxnet-cpp).

Reference: cpp-package/include/mxnet-cpp/ — the C++ frontend over the
C API; here validated by compiling the mlp_predict example against the
header and diffing its outputs against the Python executor.
"""
import os
import shutil
import subprocess

import numpy as np
import pytest

from mxnet_tpu.native import get_predict_lib
from tests.test_c_predict_api import _toy_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cpp_package_predictor(tmp_path):
    if get_predict_lib() is None:
        pytest.skip("no native predict library")
    if not (shutil.which("g++") and shutil.which("python3-config")):
        # prebuilt .so without a compiler: nothing to build the demo with
        pytest.skip("no C++ toolchain to compile the example")
    _, exe, sfile, pfile = _toy_model(tmp_path)
    src = os.path.join(REPO, "cpp-package", "example", "mlp_predict.cc")
    bin_path = str(tmp_path / "mlp_predict")
    ldflags = subprocess.run(
        ["python3-config", "--ldflags", "--embed"],
        capture_output=True, text=True, check=True).stdout.split()
    so = os.path.join(REPO, "mxnet_tpu", "native", "libmxnet_predict.so")
    subprocess.run(
        ["g++", "-std=c++14", "-O2",
         "-I" + os.path.join(REPO, "cpp-package", "include"),
         src, "-o", bin_path, so,
         "-Wl,-rpath," + os.path.dirname(so)] + ldflags,
        check=True, capture_output=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([bin_path, sfile, pfile, "2,5"],
                          capture_output=True, text=True, env=env,
                          timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "output shape: 2 3" in proc.stdout

    # diff against the Python executor on the same ramp input
    x = (0.01 * np.arange(10, dtype=np.float32)).reshape(2, 5)
    exe.forward(is_train=False, data=x)
    want = exe.outputs[0].asnumpy().ravel()
    got = np.array([float(t) for t in
                    proc.stdout.strip().splitlines()[-1].split()],
                   np.float32)
    assert np.allclose(got, want, atol=1e-5), (got, want)
