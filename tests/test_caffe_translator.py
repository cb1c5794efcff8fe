"""caffe_translator: solver+net prototxt -> runnable training script.

Reference analogue: tools/caffe_translator (Java) test flow — translate
a Caffe training setup and execute the generated MXNet script.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LENET = """
name: "LeNetLite"
input: "data"
input_dim: 16
input_dim: 1
input_dim: 12
input_dim: 12
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 4 kernel_size: 3 stride: 1 } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 4 } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip1"
  bottom: "label" top: "loss" }
"""

SOLVER = """
net: "lenet.prototxt"
base_lr: 0.05
momentum: 0.9
weight_decay: 0.0005
lr_policy: "step"
stepsize: 300
gamma: 0.5
max_iter: 300
snapshot_prefix: "lenet_lite"
type: "SGD"
"""


def test_translate_and_run(tmp_path):
    (tmp_path / "lenet.prototxt").write_text(LENET)
    (tmp_path / "solver.prototxt").write_text(SOLVER)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "caffe_translator",
                                      "translate.py"),
         "--solver", str(tmp_path / "solver.prototxt"),
         "--output", str(tmp_path / "train_lenet.py")],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    script = (tmp_path / "train_lenet.py").read_text()
    # solver semantics made it into the script
    assert "FactorScheduler(step=300, factor=0.5)" in script
    assert "momentum=0.9" in script
    assert '"sgd"' in script
    r = subprocess.run([sys.executable, str(tmp_path / "train_lenet.py")],
                       capture_output=True, text=True, env=env,
                       cwd=str(tmp_path), timeout=300)
    out = r.stdout + r.stderr
    assert "caffe-translated training done" in out, out[-2000:]
    # checkpoints written under the solver's snapshot_prefix
    assert any(f.startswith("lenet_lite") and f.endswith(".params")
               for f in os.listdir(tmp_path)), os.listdir(tmp_path)
