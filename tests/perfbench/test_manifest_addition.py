"""The manifest takes an addition (CPU, quick, no chip).

A later PR brings a cell as new files and new entries: a configuration,
a cell appended to every ``workloads`` list it joins, per-layer metrics
of its own.  It may not edit a file under ``paths``, these tests
included, so every rule a test here states about the manifest has to
hold after such an addition.  This file makes one — in a copy of the
checkout: ``BENCHMARK.json``, ``perfbench/`` and ``tests/perfbench/`` —
and runs over the copy every manifest rule of every test file it finds
there.

The convention it leans on: a test of the manifest has ``manifest`` or
``every_cell`` in its name, takes only the fixtures ``pb`` / ``bench``
beside its parameters, and reads the manifest and every path from the
fixture's ``bench`` (or its own file's ``ROOT``), never from this
repository.
"""
import argparse
import importlib.util
import inspect
import json
import os
import shutil
import sys
import traceback

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PB = os.path.join(ROOT, "perfbench")
LIKE = "ouro2p6b-train-s2048"       # the cell whose files the new one copies
ADDED = "added-cell"


@pytest.fixture(scope="module")
def pb():
    sys.path.insert(0, PB)
    try:
        import loader
        import phase_reduce
        import run
        import trace_reduce
        import traffic
        yield argparse.Namespace(
            loader=loader, traffic=traffic, trace_reduce=trace_reduce,
            run=run, phase_reduce=phase_reduce, bench=loader.Bench(ROOT))
    finally:
        sys.path.remove(PB)


def _add_a_cell(root, bench):
    """What a ``model_config`` PR does, with copies of ``LIKE``'s files:
    new files under ``perfbench/``, new entries, names appended."""
    like = bench.cell(LIKE)
    m = json.loads(json.dumps(bench.manifest))
    entry = dict(bench.config_entry(like.config_name), name="added-config",
                 file="perfbench/configs/added-config.json")
    config = dict(like.config, name="added-config")
    with open(os.path.join(root, entry["file"]), "w") as f:
        json.dump(config, f)
    shutil.copy(bench.path("traffic", like.traffic_name + ".json"),
                os.path.join(root, "perfbench/traffic/added-tokens.json"))
    with open(os.path.join(root, "perfbench/limits", ADDED + ".json"),
              "w") as f:
        json.dump(dict(like.limits(), cell=ADDED), f)
    shutil.copy(bench.path("metrics", "loop_ms.py"),
                os.path.join(root, "perfbench/metrics/added_ms.py"))
    m["configs"].append(entry)
    m["workloads"].append({"name": ADDED, "config": "added-config",
                           "traffic": "added-tokens", "chips": 1,
                           "why": "a later PR's cell"})
    for group in ("end_to_end", "per_layer"):
        for spec in m[group]:
            if LIKE in spec.get("workloads", ()):
                spec["workloads"].append(ADDED)
    m["per_layer"].append({"name": "added_ms", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "a later PR's layer",
                           "moves": "train_step_ms", "workloads": [ADDED]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)


def _load(path):
    name = "added_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cases(fn, fixtures):
    """The keyword arguments of every case of a test function: its one
    ``parametrize`` mark unrolled, its other arguments from ``fixtures``."""
    marks = [mk for mk in getattr(fn, "pytestmark", ())
             if mk.name == "parametrize"]
    assert len(marks) <= 1, fn.__name__
    cases = [{}]
    if marks:
        names = [n.strip() for n in marks[0].args[0].split(",")]
        cases = [dict(zip(names, v if len(names) > 1 else (v,)))
                 for v in marks[0].args[1]]
    wanted = inspect.signature(fn).parameters
    for case in cases:
        missing = set(wanted) - set(case) - set(fixtures)
        assert not missing, "%s takes %s: a manifest rule takes pb / bench " \
            "and its parameters" % (fn.__name__, sorted(missing))
        yield dict(case, **{k: v for k, v in fixtures.items()
                            if k in wanted})


def test_the_manifest_takes_an_addition(pb, tmp_path):
    root = str(tmp_path / "checkout")
    ignore = shutil.ignore_patterns("__pycache__", "testdata")
    shutil.copytree(PB, os.path.join(root, "perfbench"), ignore=ignore)
    shutil.copytree(HERE, os.path.join(root, "tests", "perfbench"),
                    ignore=ignore)
    before = {}
    for d, _dirs, files in os.walk(root):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    _add_a_cell(root, pb.bench)

    bench = pb.loader.Bench(root)
    fixtures = {"bench": bench,
                "pb": argparse.Namespace(**dict(vars(pb), bench=bench))}
    ran, failed = [], []
    tests_dir = os.path.join(root, "tests", "perfbench")
    for fname in sorted(os.listdir(tests_dir)):
        if not (fname.startswith("test_") and fname.endswith(".py")) \
                or fname == os.path.basename(__file__):
            continue
        # imported from the copy: its ROOT is the copy, and what it
        # parametrises over at import is the copy's manifest
        mod = _load(os.path.join(tests_dir, fname))
        for name, fn in sorted(vars(mod).items()):
            if not (name.startswith("test_") and inspect.isfunction(fn)
                    and ("manifest" in name or "every_cell" in name)):
                continue
            for kwargs in _cases(fn, fixtures):
                case = "%s::%s%s" % (fname, name, sorted(
                    (k, v) for k, v in kwargs.items() if k not in fixtures))
                ran.append(case)
                try:
                    fn(**kwargs)
                except Exception as exc:    # every rule reports, then fail
                    at = traceback.extract_tb(exc.__traceback__)[-1]
                    failed.append("%s: line %d: %s: %s %s" % (
                        case, at.lineno, at.line, type(exc).__name__,
                        str(exc)[:300]))
    assert not failed, "%d of %d manifest rules do not survive an " \
        "addition:\n%s" % (len(failed), len(ran), "\n".join(failed))
    # every file's rules ran, the per-cell ones over the added cell too
    for fname in ("test_perfbench_harness.py", "test_looped_lm_cell.py",
                  "test_phase_reduce.py"):
        assert any(c.startswith(fname + "::") for c in ran), ran
    assert any("test_every_cell_resolves_to_files" in c and ADDED in c
               for c in ran), ran

    # the added cell reads what the cell it copied reads, then its own
    like = [s["name"] for s in bench.cell(LIKE).per_layer_metrics()]
    mine = [s["name"] for s in bench.cell(ADDED).per_layer_metrics()]
    rest = iter(mine)
    assert all(name in rest for name in like + ["added_ms"]), mine
    assert "added_ms" not in like
    assert bench.cell(ADDED).driver().Driver and bench.metric_reader(
        "added_ms").read({}) is None
    # and nothing that was there was edited
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, path
