"""The readers PR 25 added beside the harness (CPU, quick, no chip):
``perfbench/phase_reduce.py`` and the five metric files that read it.

- each returns ``None`` on an empty ``ctx`` and the right number on a
  synthetic one (three ops, a three-entry phase map, two spans);
- on the trace recorded on the chip, the join reproduces the numbers the
  recording run printed.
"""
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
READERS = ("fwd_ms", "bwd_ms", "update_ms", "phase_unattributed_share",
           "step_host_ms")


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, PB)
    try:
        import loader
        yield loader.Bench(ROOT)
    finally:
        sys.path.remove(PB)


HLO = """HloModule jit_fbu, is_scheduled=true

ENTRY %main.4 (w.1: f32[4]) -> f32[4] {
  %w.1 = f32[4]{0} parameter(0), metadata={op_name="w"}
  %fusion.1 = f32[4]{0} fusion(%w.1), kind=kLoop, calls=%f, metadata={op_name="jit(fbu)/jvp(mx_fwd)/mul"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%f, metadata={op_name="jit(fbu)/transpose(jvp(mx_fwd))/mul"}
  ROOT %reshape.3 = f32[4]{0} reshape(%fusion.2), metadata={op_name="jit(fbu)/mx_update/flatten/reshape"}
}
"""


def _synthetic():
    """Two steps of three ops inside two ``jit_fbu`` module events, a
    stray op of another program between them, two program spans."""
    ops = []
    for base in (0, 1000):
        ops += [("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %w.1)",
                 base + 0, base + 100),
                ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %fusion.1)",
                 base + 100, base + 400),
                ("%reshape.3 = f32[4]{0} reshape(f32[4]{0} %fusion.2)",
                 base + 400, base + 450)]
    # the same instruction name in another program: not the step's
    ops.append(("%fusion.1 = s32[] fusion(s32[] %p)", 600, 650))
    return {
        "steps": 2, "chips": 1,
        "spans": [(10.0, 10.001), (10.002, 10.003), (10.003, 10.004,
                                                     "block")],
        "trace": {"busy_s": 950e-9,
                  "ops_by_device": {0: ops},
                  "modules_by_device": {0: [("jit_fbu(123)", 0, 450),
                                            ("jit_argmax(7)", 590, 660),
                                            ("jit_fbu(123)", 1000, 1450)]}},
        "program_hlo": [HLO],
        "program_spans": [
            {"name": "module.forward_backward", "t0_ns": 10.0001e9,
             "dur_ms": 0.5},
            {"name": "executor.dispatch", "t0_ns": 10.0002e9,
             "dur_ms": 0.3},                        # a child: not summed
            {"name": "module.forward_backward", "t0_ns": 10.0021e9,
             "dur_ms": 0.7},
            {"name": "module.forward_backward", "t0_ns": 9.5e9,
             "dur_ms": 9.0},                        # set-up: outside
            {"name": "trainer.step", "ts": 10.0, "dur_ms": 5.0}],  # no clock
    }


def test_readers_on_a_synthetic_ctx(bench):
    ctx = _synthetic()
    read = {n: bench.metric_reader(n).read(ctx) for n in READERS}
    assert read["fwd_ms"] == pytest.approx(1e3 * 200e-9 / 2)
    assert read["bwd_ms"] == pytest.approx(1e3 * 600e-9 / 2)
    assert read["update_ms"] == pytest.approx(1e3 * 100e-9 / 2)
    assert read["phase_unattributed_share"] == pytest.approx(
        100.0 * 50 / 950)
    assert read["step_host_ms"] == pytest.approx((0.5 + 0.7) / 2)
    assert "_phases" in ctx             # joined once, memoised on ctx


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_when_there_is_nothing(bench, name):
    reader = bench.metric_reader(name)
    assert reader.read({}) is None
    # a program that names no phase and records no span (the parent)
    ctx = _synthetic()
    ctx["program_hlo"] = []
    ctx["program_spans"] = [{"name": "fit.step", "ts": 1.0, "dur_ms": 2.0}]
    assert reader.read(ctx) is None
    # a module text that maps nothing of the trace
    ctx = dict(_synthetic(), program_hlo=["HloModule jit_other\n"],
               program_spans=[])
    assert reader.read(ctx) is None


def _in_order(part, whole):
    """Every name of ``part`` is in ``whole``, in ``part``'s order."""
    rest = iter(whole)
    return all(name in rest for name in part)


# a rule on ``bench`` (any checkout's), which an addition keeps
# (tests/perfbench/test_manifest_addition.py runs it over one)
@pytest.mark.parametrize("name", READERS)
def test_the_manifest_lists_the_readers(bench, name):
    """Each reader lists AT LEAST the two cells PR 25 gave it, and in
    every cell it lists the five stand in their order among that cell's
    readers, whatever else a later PR puts around them."""
    specs = {s["name"]: s for s in bench.manifest["per_layer"]}
    assert set(specs[name]["workloads"]) >= {"resnet50-train-b256",
                                             "opt1p3b-train-s2048"}
    assert specs[name]["moves"] == "train_step_ms"
    assert specs[name]["better"] == "lower"
    for cell in specs[name]["workloads"]:
        names = [s["name"] for s in bench.cell(cell).per_layer_metrics()]
        assert _in_order([r for r in READERS
                          if cell in specs[r]["workloads"]], names), cell


def test_phase_split_of_the_recorded_trace(bench, tmp_path):
    """``resnet50_b256_phases.*``: a traced run of cell 1 on the chip
    (its xplane, the step's optimized module, what the run printed)."""
    import trace_reduce
    stem = os.path.join(PB, "testdata", "resnet50_b256_phases")
    with open(stem + ".json") as f:
        recorded = json.load(f)
    xplane = tmp_path / "phases.xplane.pb"
    with gzip.open(stem + ".xplane.pb.gz", "rb") as f:
        xplane.write_bytes(f.read())
    with gzip.open(stem + ".hlo.txt.gz", "rt") as f:
        hlo = f.read()
    ctx = {"steps": recorded["steps"], "chips": 1, "program_hlo": [hlo],
           "trace": trace_reduce.reduce(str(xplane), chips=1)}
    for name in ("fwd_ms", "bwd_ms", "update_ms"):
        assert bench.metric_reader(name).read(ctx) == pytest.approx(
            recorded[name], abs=1e-6), name
    share = bench.metric_reader("phase_unattributed_share").read(ctx)
    assert share == pytest.approx(recorded["phase_unattributed_share"],
                                  abs=1e-6)
    assert 0 <= share < 5.0
    phases = sum(recorded[n] for n in ("fwd_ms", "bwd_ms", "update_ms"))
    busy_ms = 1e3 * ctx["trace"]["busy_s"] / recorded["steps"]
    assert phases == pytest.approx(busy_ms, rel=0.05)
