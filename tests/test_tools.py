"""Tools tests: im2rec list/encode round-trip, rec2idx, parse_log.

Reference analogue: tools/im2rec.py + tools/rec2idx.py behavior
(dataset packing used by every image training example).
"""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "tool_" + name, os.path.join(TOOLS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _make_images(root, classes=2, per_class=3, size=12):
    from PIL import Image
    rng = np.random.RandomState(0)
    for c in range(classes):
        d = os.path.join(root, "class%d" % c)
        os.makedirs(d, exist_ok=True)
        for i in range(per_class):
            arr = rng.randint(0, 255, (size, size, 3), dtype=np.uint8)
            Image.fromarray(arr).save(os.path.join(d, "img%d.jpg" % i))


def test_im2rec_roundtrip(tmp_path):
    from mxnet_tpu import recordio
    root = str(tmp_path / "imgs")
    _make_images(root)
    prefix = str(tmp_path / "data")
    env = dict(os.environ, PYTHONPATH=REPO)
    # phase 1: listing
    subprocess.run([sys.executable, os.path.join(TOOLS, "im2rec.py"),
                    prefix, root, "--list", "--recursive"],
                   check=True, env=env, capture_output=True)
    assert os.path.exists(prefix + ".lst")
    lines = open(prefix + ".lst").read().strip().splitlines()
    assert len(lines) == 6
    # phase 2: encode
    subprocess.run([sys.executable, os.path.join(TOOLS, "im2rec.py"),
                    prefix, root, "--num-thread", "2"],
                   check=True, env=env, capture_output=True)
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    assert len(rec.keys) == 6
    header, img = recordio.unpack_img(rec.read_idx(rec.keys[0]))
    assert img.shape == (12, 12, 3)
    assert float(np.asarray(header.label).reshape(-1)[0]) in (0.0, 1.0)
    rec.close()


def test_rec2idx(tmp_path):
    from mxnet_tpu import recordio
    rec_path = str(tmp_path / "x.rec")
    w = recordio.MXRecordIO(rec_path, "w")
    for i in range(5):
        w.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                              b"payload%d" % i))
    w.close()
    r2i = _load("rec2idx")
    idx_path = str(tmp_path / "x.idx")
    assert r2i.build_index(rec_path, idx_path) == 5
    rec = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    header, payload = recordio.unpack(rec.read_idx(3))
    assert payload == b"payload3"
    assert header.label == 3.0
    rec.close()


def test_parse_log():
    pl = _load("parse_log")
    lines = [
        "INFO Epoch[0] Train-accuracy=0.5",
        "INFO Epoch[0] Validation-accuracy=0.4",
        "INFO Epoch[0] Time cost=12.3",
        "INFO Epoch[1] Train-accuracy=0.7",
        "INFO Epoch[1] Validation-accuracy=0.6",
        "INFO Epoch[1] Time cost=11.1",
    ]
    table = pl.parse(lines, ["accuracy"])
    assert sorted(table) == [0, 1]
    (tsum, tcnt), (vsum, vcnt), (time_sum, time_cnt) = table[1]
    assert tsum == pytest.approx(0.7) and tcnt == 1
    assert vsum == pytest.approx(0.6)
    assert time_sum == pytest.approx(11.1)


DIST_TRAIN = r"""
import os, sys
import numpy as np
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, %(repo)r)
import mxnet_tpu as mx
from mxnet_tpu import nd

kv = mx.kv.create("dist_sync")
rank = kv.rank
rng = np.random.RandomState(123)  # same data on both ranks
X = rng.rand(64, 3).astype(np.float32)
true_w = np.array([[1.0], [-2.0], [0.5]], np.float32)
y = X @ true_w

kv._set_updater(lambda k, g, w: w.__isub__(0.5 * g / 64 / kv.num_workers))
w = nd.zeros((3, 1))
kv.init("w", w)
# each rank trains on its half-batch; dist_sync sums the pushes
lo, hi = (0, 32) if rank == 0 else (32, 64)
for it in range(400):
    kv.pull("w", out=w)
    xb, yb = X[lo:hi], y[lo:hi]
    pred = xb @ w.asnumpy()
    grad = 2 * xb.T @ (pred - yb)
    kv.push("w", nd.array(grad))
kv.pull("w", out=w)
err = float(np.abs(w.asnumpy() - true_w).max())
assert err < 0.05, (rank, w.asnumpy())
print("LAUNCHED_TRAIN_OK rank=%%d err=%%.4f" %% (rank, err))
"""


@pytest.mark.slow
def test_launch_py_local_distributed_training(tmp_path):
    """tools/launch.py --launcher local spawns N DMLC-env workers that
    converge together over dist_sync (reference: launch.py + nightly
    dist_lenet.py pattern)."""
    script = tmp_path / "dist_train.py"
    script.write_text(DIST_TRAIN % {"repo": REPO})
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["MXNET_KVSTORE_HEARTBEAT_DIR"] = str(tmp_path / "hb")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--root-port", "9427", "--launcher", "local",
         sys.executable, str(script)],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    assert out.count("LAUNCHED_TRAIN_OK") == 2, out[-3000:]


def test_launch_py_local_workers_stay_off_the_chip(tmp_path):
    """The local launcher starts N identical workers, none pinned to a
    chip of its own — a chip belongs to one process, so they run on the
    CPU unless the command line names JAX_PLATFORMS itself."""
    show = "import os; print('platform=' + os.environ['JAX_PLATFORMS'])"
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    base = [sys.executable, os.path.join(TOOLS, "launch.py"), "-n", "2",
            "--launcher", "local"]
    out = subprocess.run(base + ["--", sys.executable, "-c", show],
                         env=env, capture_output=True, text=True,
                         timeout=60, check=True).stdout
    assert out.count("platform=cpu") == 2
    out = subprocess.run(base + ["--env", "JAX_PLATFORMS:tpu", "--",
                                 sys.executable, "-c", show],
                         env=env, capture_output=True, text=True,
                         timeout=60, check=True).stdout
    assert out.count("platform=tpu") == 2
