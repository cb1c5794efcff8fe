"""bench.py secondary-leg plumbing (stubbed measurer, no TPU needed).

The driver's BENCH capture is the round's artifact of record; these
tests pin the contract that keeps it robust: the primary JSON line is
printed before any secondary leg runs, side files are written
incrementally, and a wall budget (MXNET_BENCH_SECONDARY_BUDGET_S)
skips legs instead of letting an external kill (the r2 rc=124) void
the invocation.
"""
import importlib
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture()
def bench_mod(tmp_path, monkeypatch, capsys):
    import bench
    importlib.reload(bench)
    monkeypatch.setattr(bench, "HERE", str(tmp_path))
    calls = []

    def fake_measure(nb, db, to, extra_env=None):
        calls.append(dict(extra_env or {}))
        return 2000.0, dict(TPU), None

    monkeypatch.setattr(bench, "_measure", fake_measure)
    # the static cost trace is a real 9-s jax subprocess (covered by
    # tests/test_ir.py); its failure path has its own test below
    monkeypatch.setattr(bench, "_ir_cost_columns",
                        lambda: {"ir_predicted_flops": 1})
    # the sharded-sweep rider is a real dp8 jax subprocess — stub it so
    # plumbing tests stay fast; its own numbers are covered by running
    # bench.py for real (and the parity bar by the fault drill)
    monkeypatch.setattr(
        bench, "_sharded_sweep_rider",
        lambda to: {"sharded_fused_us_per_step": 100.0,
                    "sharded_treemap_us_per_step": 150.0,
                    "sharded_treemap_vs_fused": 1.5})
    bench._test_calls = calls
    return bench


def test_all_legs_run_within_budget(bench_mod, tmp_path, capsys,
                                    monkeypatch):
    monkeypatch.delenv("MXNET_BENCH_SECONDARY_BUDGET_S", raising=False)
    bench_mod.main()
    line = capsys.readouterr().out.strip().splitlines()[0]
    primary = json.loads(line)
    assert primary["metric"] == "resnet50_train_img_per_sec"
    assert primary["value"] == 2000.0
    # every number names the device it was taken on
    assert primary["device"] == TPU
    ab = json.loads((tmp_path / "BENCH_NHWC.json").read_text())
    rd = json.loads((tmp_path / "BENCH_RIDERS.json").read_text())
    assert ab["nhwc_vs_nchw"] == 1.0
    assert rd["pallas_unfused_vs_baseline"] == 1.0
    assert rd["stem_s2d_vs_baseline"] == 1.0
    assert rd["unfused_metric_vs_baseline"] == 1.0
    # the sharded-sweep microbench rider (ZeRO shard_map fused vs
    # tree_map) rides the same riders file, not the img/s measurer
    assert rd["sharded_treemap_vs_fused"] == 1.5
    # primary + nhwc + 3 riders (the sharded leg is its own subprocess)
    assert len(bench_mod._test_calls) == 5
    assert {"MXNET_STEM_SPACE_TO_DEPTH": "1"} in bench_mod._test_calls
    assert {"MXNET_FUSED_METRIC": "0"} in bench_mod._test_calls
    # the pallas A/B rider turns the WHOLE mega-kernel family off
    assert {"MXNET_PALLAS_FUSED_OPT": "0", "MXNET_PALLAS_NORM": "0",
            "MXNET_PALLAS_SOFTMAX": "0",
            "MXNET_PALLAS_BN_RELU": "0"} in bench_mod._test_calls


def test_exhausted_budget_skips_secondary_legs(bench_mod, tmp_path,
                                               capsys, monkeypatch):
    monkeypatch.setenv("MXNET_BENCH_SECONDARY_BUDGET_S", "0")
    bench_mod.main()
    assert json.loads(
        capsys.readouterr().out.strip().splitlines()[0])["value"] == 2000.0
    ab = json.loads((tmp_path / "BENCH_NHWC.json").read_text())
    rd = json.loads((tmp_path / "BENCH_RIDERS.json").read_text())
    assert "nhwc_skipped" in ab
    assert "stem_s2d_skipped" in rd and "unfused_metric_skipped" in rd
    assert "pallas_unfused_skipped" in rd
    assert "sharded_sweep_skipped" in rd
    assert len(bench_mod._test_calls) == 1  # primary only


def test_malformed_budget_falls_back_to_default(bench_mod, tmp_path,
                                                capsys, monkeypatch):
    monkeypatch.setenv("MXNET_BENCH_SECONDARY_BUDGET_S", "600s")  # typo
    bench_mod.main()
    rd = json.loads((tmp_path / "BENCH_RIDERS.json").read_text())
    assert rd["unfused_metric_vs_baseline"] == 1.0  # legs still ran
    capsys.readouterr()


def test_primary_leg_carries_telemetry_knobs(bench_mod, tmp_path, capsys,
                                             monkeypatch):
    """Every bench capture ships the why alongside the img/s: the
    primary measurement subprocess runs with telemetry enabled, a
    step-JSONL path, and a Prometheus exposition path — and stale
    artifacts from a previous run are removed first."""
    stale = tmp_path / "BENCH_STEPS.jsonl"
    stale.write_text('{"old": true}\n')
    monkeypatch.setenv("MXNET_BENCH_SECONDARY_BUDGET_S", "0")
    bench_mod.main()
    capsys.readouterr()
    primary = bench_mod._test_calls[0]
    assert primary["MXNET_TELEMETRY"] == "1"
    assert primary["MXNET_TELEMETRY_STEP_LOG"] == \
        str(tmp_path / "BENCH_STEPS.jsonl")
    assert primary["MXNET_TELEMETRY_PROM_FILE"] == \
        str(tmp_path / "BENCH_TELEMETRY.prom")
    assert not stale.exists(), \
        "a new bench run must not append to a previous run's step log"


# -- a leg only counts when its child ran clean, on a TPU --------------------

_TRAIN_LOG = (
    "Node[0] device platform=%s kind=%s count=1\n"
    + "".join("Epoch[0] Batch [%d]\tSpeed: 2000.00 samples/sec\n" % b
              for b in (20, 40, 60)))


def _real_measure(monkeypatch, rc, text):
    import bench
    importlib.reload(bench)
    monkeypatch.setattr(bench, "_run_bounded",
                        lambda cmd, env, to, cwd=None: (rc, text))
    return bench._measure(60, 20, 10)


def test_measure_reports_the_childs_device(monkeypatch, capsys):
    v, dev, err = _real_measure(monkeypatch, 0,
                                _TRAIN_LOG % ("tpu", "TPU v5 lite"))
    assert (v, dev, err) == (2000.0, TPU, None)


@pytest.mark.parametrize("rc", [None, 1, -9])
def test_killed_or_failed_child_is_not_a_measurement(monkeypatch, capsys,
                                                     rc):
    """A full set of Speedometer readings does not redeem a child that
    was killed at the deadline or exited non-zero."""
    v, dev, err = _real_measure(monkeypatch, rc,
                                _TRAIN_LOG % ("tpu", "TPU v5 lite"))
    assert v is None and dev is None and err[1] == 3
    capsys.readouterr()


def test_cpu_child_is_refused(monkeypatch, capsys):
    v, dev, err = _real_measure(monkeypatch, 0, _TRAIN_LOG % ("cpu", "cpu"))
    assert v is None and err[1] == 4 and "not on a TPU" in err[0]


def test_failed_cost_trace_fails_the_run(monkeypatch, capsys):
    import bench
    importlib.reload(bench)
    monkeypatch.setattr(bench, "_run_bounded",
                        lambda cmd, env, to, cwd=None: (1, "Traceback"))
    with pytest.raises(SystemExit) as exc:
        bench._ir_cost_columns()
    assert exc.value.code == 6
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "error" in out and out["value"] == 0.0


# -- bench.py --tune (grafttune leg) -----------------------------------------

def _stub_sweep(bench, summary):
    calls = []

    def fake(journal, db_dir=None, measure_timeout=240.0):
        calls.append({"journal": journal, "timeout": measure_timeout})
        return summary

    bench._run_tune_sweep = fake
    return calls


TUNE_SUMMARY = {
    "proposed": 12, "pruned": 7, "admissible": 0, "measured": 5,
    "failed": 0, "duplicates": 0, "budget": 12, "seed": 0,
    "prune_rules": {"oom-risk": 4, "kern-grid-coverage": 3},
    "default_us_per_step": 200.0,
    "winner": {"candidate": {"bucket_bytes": 2097152},
               "us_per_step": 150.0, "k": 10},
    "stored": ["/tmp/db/parallel-trainer-abc.json"],
    "resumed_records": 0,
}


def test_tune_leg_writes_side_json_and_one_stdout_line(
        bench_mod, tmp_path, capsys, monkeypatch):
    monkeypatch.delenv("MXNET_BENCH_SECONDARY_BUDGET_S", raising=False)
    calls = _stub_sweep(bench_mod, dict(TUNE_SUMMARY))
    bench_mod.tune_main()
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.strip()]
    assert len(lines) == 1                 # ONE stdout JSON line
    out = json.loads(lines[0])
    side = json.loads((tmp_path / "BENCH_TUNE.json").read_text())
    assert out == side
    assert out["proposed"] == 12 and out["pruned"] == 7
    assert out["measured"] == 5
    assert out["prune_rules"] == {"oom-risk": 4,
                                  "kern-grid-coverage": 3}
    assert out["default_us_per_step"] == 200.0
    assert out["tuned_us_per_step"] == 150.0
    assert out["tuned_vs_default"] == 0.75     # tuned <= default
    assert out["tuned_candidate"] == {"bucket_bytes": 2097152}
    assert out["stored"] == ["/tmp/db/parallel-trainer-abc.json"]
    # the journal lands next to the side file (resumable sweep)
    assert calls[0]["journal"] == str(tmp_path
                                      / "BENCH_TUNE.journal.jsonl")


def test_tune_leg_skips_under_exhausted_budget(bench_mod, tmp_path,
                                               capsys, monkeypatch):
    monkeypatch.setenv("MXNET_BENCH_SECONDARY_BUDGET_S", "0")
    calls = _stub_sweep(bench_mod, dict(TUNE_SUMMARY))
    bench_mod.tune_main()
    out = json.loads(capsys.readouterr().out.strip())
    side = json.loads((tmp_path / "BENCH_TUNE.json").read_text())
    assert out == side == {
        "tune_skipped": "secondary wall budget exhausted"}
    assert calls == []                     # the driver never ran


def test_tune_leg_without_winner_reports_counts_only(
        bench_mod, tmp_path, capsys, monkeypatch):
    monkeypatch.delenv("MXNET_BENCH_SECONDARY_BUDGET_S", raising=False)
    summary = dict(TUNE_SUMMARY, winner=None, measured=0,
                   default_us_per_step=None, stored=[])
    _stub_sweep(bench_mod, summary)
    bench_mod.tune_main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["pruned"] == 7
    assert "tuned_us_per_step" not in out
    assert "tuned_vs_default" not in out


def test_tune_leg_clamps_measure_timeout_to_budget(
        bench_mod, tmp_path, capsys, monkeypatch):
    monkeypatch.setenv("MXNET_BENCH_SECONDARY_BUDGET_S", "90")
    calls = _stub_sweep(bench_mod, dict(TUNE_SUMMARY))
    bench_mod.tune_main()
    capsys.readouterr()
    assert calls[0]["timeout"] == 90.0
