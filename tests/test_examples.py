"""Every example script must run end to end (tiny configurations).

Reference analogue: tests/nightly/test_image_classification.sh and the
tutorial-execution suite — examples are executable documentation and
break silently unless exercised.

Budget: tier-1 runs ``-m 'not slow'`` under a hard 870 s wall.  The
full example sweep measures ~36 min on this class of container — it
used to blow the whole budget (rc=124 on every run, killing the suite
at ~28% and silently masking failures in everything alphabetically
after this file).  Examples measured over ~10 s are therefore marked
``slow`` (they still run in the slow leg / nightly); the fast third
keeps end-to-end example coverage inside tier-1.  If you add an
example test, time it and mark accordingly.
"""
import os
import re
import signal
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EX = os.path.join(REPO, "example")


def run_example(relpath, *argv, timeout=1800, env_extra=None, done_marker=None):
    """Run an example script to its end and return its combined
    output.  The exit code decides; ``done_marker``, when given, must
    also appear in the output.  On timeout the process group is
    killed and the run fails."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    if env_extra:
        env.update(env_extra)
    import threading
    import time

    proc = subprocess.Popen(
        [sys.executable, "-u", os.path.join(EX, relpath), *argv],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO, start_new_session=True)
    chunks = []

    def _reader():
        for line in proc.stdout:
            chunks.append(line)

    t = threading.Thread(target=_reader, daemon=True)
    t.start()
    deadline = time.time() + timeout
    rc = None
    while time.time() < deadline:
        rc = proc.poll()
        if rc is not None:
            break
        time.sleep(0.5)
    else:
        os.killpg(proc.pid, signal.SIGKILL)
    t.join(timeout=10)
    out = "".join(chunks)
    assert rc == 0, "%s failed (rc=%s):\n%s" % (relpath, rc, out[-3000:])
    assert done_marker is None or done_marker in out, \
        "%s exited 0 without printing %r:\n%s" % (relpath, done_marker,
                                                  out[-3000:])
    return out


@pytest.mark.slow
def test_train_mnist():
    out = run_example("image-classification/train_mnist.py",
                      "--num-epochs", "2", "--batch-size", "64",
                      done_marker="Train-accuracy")
    assert "Train-accuracy" in out


@pytest.mark.slow
def test_train_imagenet_benchmark():
    out = run_example("image-classification/train_imagenet.py",
                      "--benchmark", "1", "--kv-store", "tpu",
                      "--network", "resnet", "--num-layers", "18",
                      "--batch-size", "8", "--num-epochs", "1",
                      "--num-batches", "4", "--disp-batches", "2",
                      "--image-shape", "3,64,64", done_marker="Speed:")
    assert "Speed:" in out


@pytest.mark.slow
def test_gluon_mnist():
    out = run_example("gluon/mnist.py", "--epochs", "1",
                      "--batch-size", "64", done_marker="Validation-accuracy")
    assert "training acc" in out.lower() or "accuracy" in out.lower()


@pytest.mark.slow
def test_lstm_bucketing():
    out = run_example("rnn/lstm_bucketing.py", "--num-epochs", "1",
                      "--num-hidden", "32", "--num-embed", "32",
                      "--num-layers", "1", done_marker="Train-perplexity")
    assert "Train-perplexity" in out


@pytest.mark.slow
def test_quantization_example():
    out = run_example("quantization/quantize_model.py",
                      "--num-epochs", "3", "--calib-mode", "naive",
                      done_marker="int8 accuracy")
    assert "int8 accuracy" in out


@pytest.mark.slow
def test_sparse_example():
    out = run_example("sparse/linear_classification.py",
                      "--num-epochs", "4",
                      done_marker="final train accuracy")
    assert "final train accuracy" in out


@pytest.mark.slow
def test_ssd_example():
    out = run_example("ssd/train.py", "--num-iters", "120",
                      "--disp", "40", "--min-iou", "0.25",
                      done_marker="mean IoU")
    assert "mean IoU" in out


def test_memcost_example():
    out = run_example("memcost/inception_memcost.py",
                      "--depth", "8", "--hidden", "128",
                      done_marker="gradients identical")
    assert "gradients identical" in out


def test_profiler_example():
    out = run_example("profiler/profiler_demo.py", "--iters", "4",
                      "--file", "/tmp/test_profiler_example.json",
                      done_marker="trace events")
    assert "trace events" in out


@pytest.mark.slow
def test_custom_op_example():
    out = run_example("numpy-ops/custom_softmax.py", "--num-iters", "80",
                      done_marker="final accuracy")
    assert "final accuracy" in out


@pytest.mark.slow
def test_svm_example():
    out = run_example("svm_mnist/svm_mnist.py", "--num-epochs", "3",
                      done_marker="validation accuracy")
    assert "validation accuracy" in out


@pytest.mark.slow
def test_multi_task_example():
    out = run_example("multi-task/multi_task.py", "--num-epochs", "4",
                      done_marker="parity-acc")
    assert "parity-acc" in out


def test_model_parallel_example():
    out = run_example(
        "model-parallel/model_parallel_mlp.py", "--num-iters", "8",
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        done_marker="matches single-device")
    assert "matches single-device" in out


def test_benchmark_score():
    out = run_example("image-classification/benchmark_score.py",
                      "--networks", "mlp", "--batch-sizes", "1,8",
                      "--num-batches", "2", done_marker="img/s")
    assert "img/s" in out


@pytest.mark.slow
def test_gluon_image_classification():
    out = run_example("gluon/image_classification.py",
                      "--model", "mobilenet0_25", "--batch-size", "2",
                      "--image-shape", "3,32,32", "--num-classes", "10",
                      "--num-batches", "2", done_marker="samples/sec")
    assert "samples/sec" in out


@pytest.mark.slow
def test_matrix_fact_example():
    out = run_example("recommenders/matrix_fact.py", "--users", "200",
                      "--items", "100", "--ratings", "8000",
                      "--epochs", "6", done_marker="final validation RMSE")
    # planted rank-8 model with 0.1 noise has rating std ~0.37:
    # predict-zero scores ~0.37 RMSE, so < 0.3 requires actual learning
    rmse = float(out.split("final validation RMSE:")[-1].split()[0])
    assert rmse < 0.3, out[-500:]


@pytest.mark.slow
def test_dcgan_example():
    out = run_example("gan/dcgan.py", "--epochs", "1",
                      "--batches-per-epoch", "6", "--batch-size", "16",
                      done_marker="generated sample shape")
    assert "(4, 1, 28, 28)" in out


@pytest.mark.slow
def test_autoencoder_example():
    out = run_example("autoencoder/mnist_sae.py", "--pretrain-epochs", "1",
                      "--finetune-epochs", "1", "--batch-size", "128",
                      "--dims", "784,128,32",
                      done_marker="final reconstruction loss")
    final = float(out.split("final reconstruction loss:")[-1].split()[0])
    assert final < 0.05, out[-500:]


@pytest.mark.slow
def test_fgsm_example():
    out = run_example("adversary/fgsm.py", "--epochs", "1",
                      "--batch-size", "128", done_marker="adversarial accuracy")
    # the script asserts adv < clean BEFORE printing the marker line;
    # re-check here so the attack's effectiveness is test-enforced too
    clean = float(out.split("clean accuracy=")[-1].split()[0])
    adv = float(out.split("adversarial accuracy=")[-1].split()[0])
    assert adv < clean, out[-500:]


@pytest.mark.slow
def test_benchmark_sweep_driver():
    out = run_example("image-classification/benchmark.py",
                      "--networks", "mlp", "--batch-sizes", "32",
                      "--num-batches", "6", "--image-shape", "3,28,28",
                      done_marker="img/s")
    assert '"network": "mlp"' in out and "FAILED" not in out


@pytest.mark.slow
def test_long_context_transformer_example():
    out = run_example(
        "long-context/transformer_lm.py", "--epochs", "1",
        "--batches-per-epoch", "25", "--batch-size", "8",
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        done_marker="ring-attention max")
    err = float(out.split("|delta logits| =")[-1].split()[0])
    assert err < 1e-3


@pytest.mark.slow
def test_bi_lstm_sort_example():
    out = run_example("bi-lstm-sort/lstm_sort.py", "--num-epochs", "3",
                      "--batches-per-epoch", "40",
                      done_marker="sort accuracy")
    acc = float(out.split("sort accuracy:")[-1].split()[0])
    assert acc > 0.8, out[-500:]


@pytest.mark.slow
def test_checkpoint_resume_roundtrip(tmp_path):
    """fit -> do_checkpoint -> resume with --load-epoch (reference:
    model.py save/load_checkpoint + base_module.fit(begin_epoch))."""
    prefix = str(tmp_path / "mnist")
    out1 = run_example("image-classification/train_mnist.py",
                       "--num-epochs", "1", "--batch-size", "64",
                       "--model-prefix", prefix,
                       done_marker="Train-accuracy")
    acc1 = float(out1.split("Train-accuracy=")[-1].split()[0])
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0001.params")
    out2 = run_example("image-classification/train_mnist.py",
                       "--num-epochs", "2", "--batch-size", "64",
                       "--model-prefix", prefix, "--load-epoch", "1",
                       done_marker="Train-accuracy")
    acc2 = float(out2.split("Train-accuracy=")[-1].split()[0])
    # resumed training must not restart from scratch: epoch-2 accuracy
    # continues from (not below) the checkpointed level
    assert acc2 >= acc1 - 0.05, (acc1, acc2)
    assert "Resumed" in out2 or "load" in out2.lower()


@pytest.mark.slow
def test_cnn_text_classification():
    out = run_example("cnn_text_classification/text_cnn.py",
                      "--num-epochs", "8",
                      done_marker="text-cnn done")
    m = re.search(r"final validation accuracy: ([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.9, out[-1500:]


@pytest.mark.slow
def test_rcnn_lite_end2end():
    out = run_example("rcnn/train_end2end.py",
                      "--epochs", "60",
                      done_marker="rcnn-lite done")
    m = re.search(r"loss ([0-9.]+) -> ([0-9.]+) \| mean IoU ([0-9.]+) \| "
                  r"cls acc ([0-9.]+)%", out)
    assert m, out[-1500:]
    first, last, miou, acc = map(float, m.groups())
    assert last < first * 0.5, (first, last)      # real learning signal
    assert acc >= 70.0, acc                       # head classifies boxes
    assert miou > 0.30, miou                      # proposals find objects


@pytest.mark.slow
def test_toy_nce():
    out = run_example("nce-loss/toy_nce.py", "--steps", "300",
                      done_marker="toy-nce done")
    m = re.search(r"full-softmax top-1 acc ([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.8, out[-1500:]


@pytest.mark.slow
def test_lstm_ocr_ctc():
    out = run_example("ctc/lstm_ocr_train.py", "--steps", "80",
                      "--lr", "0.02",
                      done_marker="lstm-ocr done")
    m = re.search(r"ctc loss ([0-9.]+) -> ([0-9.]+) \| "
                  r"exact-sequence acc ([0-9.]+)", out)
    assert m, out[-1500:]
    first, last, acc = map(float, m.groups())
    assert last < 1.0 and acc >= 0.8, (first, last, acc)


@pytest.mark.slow
def test_neural_style():
    out = run_example("neural-style/nstyle.py", "--iters", "90",
                      done_marker="neural-style done")
    m = re.search(r"loss ([0-9.]+) -> ([0-9.]+)", out)
    assert m, out[-1500:]
    first, last = map(float, m.groups())
    assert last < first * 0.2, (first, last)


@pytest.mark.slow
def test_vae():
    out = run_example("vae/vae.py", "--steps", "300",
                      done_marker="vae done")
    m = re.search(r"cluster purity ([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.9, out[-1500:]


@pytest.mark.slow
def test_sgld_posterior():
    out = run_example("bayesian-methods/sgld.py", "--steps", "3000",
                      "--burn-in", "800", done_marker="sgld done")
    m = re.search(r"mean_err ([0-9.]+) \| std_ratio ([0-9.]+)", out)
    assert m, out[-1500:]
    mean_err, std_ratio = map(float, m.groups())
    # the SGLD cloud must match the EXACT conjugate posterior
    assert mean_err < 0.1 and 0.6 < std_ratio < 1.6, (mean_err, std_ratio)


@pytest.mark.slow
def test_fcn_segmentation():
    out = run_example("fcn-xs/fcn_train.py", "--epochs", "12",
                      done_marker="fcn done")
    m = re.search(r"mean IoU ([0-9.]+) \| pixel acc ([0-9.]+)", out)
    assert m, out[-1500:]
    miou, acc = map(float, m.groups())
    assert miou > 0.6 and acc > 0.9, (miou, acc)


@pytest.mark.slow
def test_dqn_cartpole():
    out = run_example("reinforcement-learning/dqn_cartpole.py",
                      "--episodes", "200", "--target-sync", "100",
                      done_marker="dqn done", timeout=900)
    m = re.search(r"best10 ([0-9.]+)", out)
    assert m and float(m.group(1)) > 50.0, out[-1500:]


@pytest.mark.slow
def test_onnx_roundtrip_example(tmp_path):
    out = run_example("onnx/onnx_inference.py",
                      "--output", str(tmp_path / "m.onnx"),
                      done_marker="onnx-inference done")
    m = re.search(r"agreement source vs onnx-imported: ([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.95, out[-1500:]


@pytest.mark.slow
def test_stochastic_depth():
    out = run_example("stochastic-depth/sd_resnet.py", "--steps", "150",
                      done_marker="stochastic-depth done")
    m = re.search(r"dropped (\d+) block-steps \| test acc ([0-9.]+)", out)
    assert m, out[-1500:]
    dropped, acc = int(m.group(1)), float(m.group(2))
    assert dropped > 50 and acc > 0.9, (dropped, acc)


@pytest.mark.slow
def test_dsd_training():
    out = run_example("dsd/dsd_train.py", "--steps", "250",
                      done_marker="dsd done")
    m = re.search(r"dsd: ([0-9.]+) -> ([0-9.]+) -> ([0-9.]+)", out)
    assert m, out[-1500:]
    dense, sparse_, redense = map(float, m.groups())
    assert redense >= dense - 0.02, (dense, redense)   # DSD must not hurt
    assert sparse_ > 0.5                               # sparse net works


@pytest.mark.slow
def test_lstnet_forecast():
    out = run_example("multivariate_time_series/lstnet.py",
                      "--steps", "200",
                      done_marker="lstnet done", timeout=900)
    m = re.search(r"ratio ([0-9.]+)", out)
    assert m and float(m.group(1)) < 0.85, out[-1500:]  # beats persistence


@pytest.mark.slow
def test_deep_embedded_clustering():
    out = run_example("deep-embedded-clustering/dec.py",
                      done_marker="dec done")
    m = re.search(r"final cluster purity ([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.9, out[-1500:]


def test_caffe_example():
    out = run_example("caffe/caffe_to_mxnet.py", "--num-epochs", "8",
                      done_marker="caffe-example done")
    m = re.search(r"caffe-converted net accuracy: ([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.9, out[-1500:]


@pytest.mark.slow
def test_capsnet_routing():
    out = run_example("capsnet/capsnet.py", "--steps", "80",
                      done_marker="capsnet done")
    m = re.search(r"capsule-length acc ([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.9, out[-1500:]


@pytest.mark.slow
def test_speech_keyword_spotting():
    out = run_example("speech_recognition/speech_commands.py",
                      "--steps", "60", done_marker="speech done")
    m = re.search(r"keyword acc ([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.9, out[-1500:]


def test_python_howto():
    out = run_example("python-howto/howto.py",
                      done_marker="python-howto done")
    assert "multiple_outputs: both heads returned" in out


@pytest.mark.slow
def test_rnn_time_major():
    out = run_example("rnn-time-major/rnn_cell_demo.py",
                      done_marker="rnn-time-major done")
    m = re.search(r"TNC vs NTC max diff: ([0-9.e+-]+)", out)
    assert m and float(m.group(1)) < 1e-5, out[-1500:]


@pytest.mark.slow
def test_module_mnist_mlp_example():
    out = run_example("module/mnist_mlp.py", "--epochs", "3",
                      done_marker="DONE")
    assert "FINAL train accuracy" in out and "DONE" in out


@pytest.mark.slow
def test_module_sequential_example():
    out = run_example("module/sequential_module.py", "--epochs", "8",
                      done_marker="DONE")
    assert "FINAL train accuracy" in out and "DONE" in out


@pytest.mark.slow
def test_module_python_loss_example():
    out = run_example("module/python_loss.py", "--epochs", "8",
                      done_marker="DONE")
    assert "FINAL train accuracy" in out and "DONE" in out


@pytest.mark.slow
def test_adversarial_vae_example():
    out = run_example("mxnet_adversarial_vae/vaegan.py",
                      "--epochs", "20", done_marker="DONE")
    assert "latent linear separation" in out and "DONE" in out


@pytest.mark.slow
def test_chinese_text_cnn_example():
    out = run_example("cnn_chinese_text_classification/text_cnn.py",
                      "--epochs", "8", done_marker="DONE")
    assert "FINAL train accuracy" in out and "DONE" in out


@pytest.mark.slow
def test_captcha_example():
    out = run_example("captcha/captcha_cnn.py", "--epochs", "10",
                      done_marker="DONE")
    assert "whole-captcha acc" in out and "DONE" in out
