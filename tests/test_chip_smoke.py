"""chip_smoke.py's control flow, here on the CPU.

The script itself runs only on a TPU (through the chip tool).  These
tests drive the SAME phases at toy width through its one non-TPU entry,
``chip_smoke.run(TOY, platform="cpu")`` — Pallas families forced on in
interpret mode, each in a fresh process like the real thing — and pin
the refusal contract of the real entry: no TPU, no work, no result
line, a non-zero exit.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
PALLAS_ON = {"MXNET_PALLAS_FUSED_OPT": "1", "MXNET_PALLAS_SOFTMAX": "1",
             "MXNET_PALLAS_BN_RELU": "1", "MXNET_PALLAS_NORM": "1"}
TOY = "import chip_smoke as c; c.run(c.TOY, platform='cpu', four_chip=%s)"


def _run(args, cwd=REPO, devices=1, timeout=900, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % devices
    env["PYTHONPATH"] = cwd
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _result_lines(proc):
    return [l for l in proc.stdout.splitlines() if l.startswith('{"ok"')]


def test_phases_pass_at_toy_width():
    proc = _run(["-c", TOY % False], **PALLAS_ON)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    for phase in ("kernels", "train", "eval", "serve"):
        assert "%s passed" % phase in out, out[-3000:]
    assert "12 kernels (interpret mode) match their references" in out
    assert "fused step installed" in out
    assert "no cache miss after warmup" in out
    summary = json.loads(out.split("[chip_smoke] summary ", 1)[1]
                         .splitlines()[0])
    assert set(summary["setup_parts_s"]) == {
        "kernels", "train_first_step", "eval_first_forward",
        "serve_warmup"}
    # run() is the test entry: the result line belongs to main() alone
    assert not _result_lines(proc)


@pytest.mark.slow
def test_four_chip_mode_passes_at_toy_width():
    proc = _run(["-c", TOY % True], devices=4, **PALLAS_ON)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "four-chip passed" in proc.stdout


def test_real_entry_refuses_without_a_tpu():
    """``python chip_smoke.py`` where jax has no TPU: exit 2 before any
    work (mxnet_tpu is not even imported), a clear message, no result."""
    proc = _run([SCRIPT])
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "needs platform 'tpu' but jax reports 'cpu'" in proc.stderr
    assert "nothing was run" in proc.stderr
    assert not _result_lines(proc) and "----" not in proc.stdout


def test_a_failed_phase_is_a_nonzero_exit():
    """Pallas families left on ``auto`` resolve to OFF on the CPU, so
    the first kernel check (did the sweep kernel run?) fails — the
    failure must propagate, whatever later phases would have done."""
    proc = _run(["-c", TOY % False])
    assert proc.returncode != 0
    assert "kernels FAILED" in proc.stdout
    assert "the sweep kernel did not run for every bucket" in proc.stderr
    assert not _result_lines(proc)


def test_script_alone_fails(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the
    repo there is no program to drive: non-zero, no result."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run(["-c", TOY % False], cwd=str(tmp_path), **PALLAS_ON)
    assert proc.returncode != 0
    assert "No module named 'mxnet_tpu'" in proc.stderr
    assert not _result_lines(proc)
