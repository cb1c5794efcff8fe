"""Predictor (c_predict_api analogue), config registry, failure
detection surface.

Reference analogues: c_predict_api.h call contract, docs/faq/env_var.md
registry, kvstore.h:338 get_num_dead_node.
"""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym


def _save_model(tmp_path):
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=4, name="fc")
    out = sym.softmax(fc, name="prob")
    rng = np.random.RandomState(0)
    arg_params = {"fc_weight": nd.array(rng.randn(4, 6).astype(np.float32)),
                  "fc_bias": nd.array(rng.randn(4).astype(np.float32))}
    prefix = str(tmp_path / "m")
    mx.model.save_checkpoint(prefix, 1, out, arg_params, {})
    return prefix, arg_params


def test_predictor_roundtrip(tmp_path):
    prefix, arg_params = _save_model(tmp_path)
    p = mx.Predictor(prefix + "-symbol.json", prefix + "-0001.params",
                     {"data": (2, 6)})
    x = np.random.RandomState(1).rand(2, 6).astype(np.float32)
    p.forward(data=x)
    out = p.get_output(0).asnumpy()
    assert out.shape == (2, 4)
    assert np.allclose(out.sum(axis=1), 1.0, atol=1e-5)
    # matches the training-side executor
    w = arg_params["fc_weight"].asnumpy()
    b = arg_params["fc_bias"].asnumpy()
    logits = x @ w.T + b
    ref = np.exp(logits - logits.max(1, keepdims=True))
    ref /= ref.sum(1, keepdims=True)
    assert np.abs(out - ref).max() < 1e-5
    assert p.get_output_shape(0) == (2, 4)
    # reshape shares params
    p2 = p.reshape({"data": (5, 6)})
    p2.forward(data=np.tile(x[:1], (5, 1)))
    assert np.abs(p2.get_output(0).asnumpy() - ref[0]).max() < 1e-5
    p.free()


def test_predictor_errors(tmp_path):
    prefix, _ = _save_model(tmp_path)
    p = mx.Predictor(prefix + "-symbol.json", prefix + "-0001.params",
                     {"data": (1, 6)})
    with pytest.raises(mx.MXNetError, match="unknown input"):
        p.set_input("nope", np.zeros((1, 6)))
    with pytest.raises(mx.MXNetError, match="forward"):
        p.get_output(0)


def test_config_registry():
    from mxnet_tpu import config
    assert config.get("MXNET_CPU_WORKER_NTHREADS") == 4
    table = config.list_env()
    assert "MXNET_PROFILER_AUTOSTART" in table
    assert table.startswith("| variable |")
    with pytest.raises(KeyError):
        config.get("MXNET_NOT_A_REAL_KNOB")
    os.environ["MXNET_TYPO_VAR"] = "1"
    try:
        assert "MXNET_TYPO_VAR" in config.check_unknown()
    finally:
        del os.environ["MXNET_TYPO_VAR"]
    os.environ["MXNET_CPU_WORKER_NTHREADS"] = "9"
    try:
        assert config.get("MXNET_CPU_WORKER_NTHREADS") == 9
    finally:
        del os.environ["MXNET_CPU_WORKER_NTHREADS"]


def test_dead_node_detection(tmp_path):
    hb = str(tmp_path / "hb")
    os.environ["MXNET_KVSTORE_HEARTBEAT_DIR"] = hb
    try:
        kv = mx.kv.create("dist_sync")   # single process: rank 0 of 1
        assert kv.get_num_dead_node(timeout_sec=60) == 0
        # fake a second worker that went silent
        stale = os.path.join(hb, "worker-1.hb")
        with open(stale, "w") as f:
            f.write("0")
        os.utime(stale, (time.time() - 120, time.time() - 120))
        # rank 1 within num_workers? single-process num_workers==1, so
        # only rank 0 is counted; rank 0's heartbeat is fresh
        assert kv.get_num_dead_node(timeout_sec=60) == 0
    finally:
        del os.environ["MXNET_KVSTORE_HEARTBEAT_DIR"]


def test_role_predicates():
    assert mx.kvstore.is_worker_node()
    assert not mx.kvstore.is_server_node()
    assert mx.kvstore.is_scheduler_node()   # process 0 is the coordinator


def test_every_registered_env_var_is_documented():
    """docs/faq/env_var.md is the contract surface for knobs; every var
    in the config registry must appear there.  Thin wrapper over the
    graftlint env-knob-drift checker (the single source of truth for
    this property — docs/faq/static_analysis.md)."""
    from mxnet_tpu.analysis.checkers import env_knobs
    rep = env_knobs.drift_report()
    assert not rep["registered_undocumented"], \
        "registered env vars missing from docs/faq/env_var.md: %s" \
        % rep["registered_undocumented"]


def test_telemetry_knobs_registered_and_documented():
    """Registry-drift guard for the telemetry knob family: every
    MXNET_TELEMETRY* name the source reads must be
    register_env'd AND documented.  Thin wrapper over the graftlint
    env-knob-drift checker — the enforcement logic lives once, in
    mxnet_tpu/analysis/checkers/env_knobs.py."""
    from mxnet_tpu.analysis.checkers import env_knobs
    rep = env_knobs.drift_report(prefix="MXNET_TELEMETRY")
    # sanity: the scan really sees the family before asserting clean
    assert {"MXNET_TELEMETRY", "MXNET_TELEMETRY_STEP_LOG",
            "MXNET_TELEMETRY_STEP_INTERVAL",
            "MXNET_TELEMETRY_PROM_FILE"} <= set(rep["used"])
    assert not rep["unregistered"], \
        "telemetry knobs referenced but never register_env'd: %s" \
        % rep["unregistered"]
    assert not rep["undocumented"], \
        "telemetry knobs missing from docs/faq/env_var.md: %s" \
        % rep["undocumented"]
