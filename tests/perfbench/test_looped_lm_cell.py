"""The looped LM's cell, ``ouro2p6b-train-s2048`` (CPU, quick, nothing at
import time that touches jax or libtpu):

- at its ``rehearse`` sizes the cell runs through ``run.py`` and reads
  ``correct`` true; the fp8 control and the two broken timed paths read
  false;
- ``counts/looped_lm.py`` against hand-worked values at the published
  widths;
- the three readers this cell brought (``loop_ms``, ``exit_ms``,
  ``recompute_ms``) on the optimized module of a small looped step,
  and on a module without the scopes;
- a ``while``'s own event against its body's events
  (``phase_reduce.control_cover``), and what they leave uncovered counted
  as unattributed;
- the manifest lists the cell under every per-layer metric it reports.
"""
import argparse
import copy
import gzip
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
CELL = "ouro2p6b-train-s2048"
NEW_READERS = ("loop_ms", "exit_ms", "recompute_ms")
# what the cell's traced line carries, in the manifest's order: seven
# shared metrics (PR 27), the five phase readers (joined in PR 31), its own
REPORTED = ("step_mfu", "device_idle_share", "hbm_peak_share", "dispatch_ms",
            "compiles_in_window", "sweep_roofline", "flash_roofline",
            "fwd_ms", "bwd_ms", "update_ms", "phase_unattributed_share",
            "step_host_ms") + NEW_READERS


@pytest.fixture(scope="module")
def pb():
    sys.path.insert(0, PB)
    try:
        import loader
        import traffic
        import run
        import phase_reduce
        yield argparse.Namespace(loader=loader, traffic=traffic, run=run,
                                 phase_reduce=phase_reduce,
                                 bench=loader.Bench(ROOT))
    finally:
        sys.path.remove(PB)


def _rehearsal(pb, seed, driver_cls=None):
    import jax
    cell = pb.bench.cell(CELL)
    args = argparse.Namespace(seed=seed, seconds=0.3, trace=0,
                              rehearse=True, trace_dir=None)
    dev = {"platform": "cpu", "kind": "cpu", "count": 1}
    return pb.run.run_cell(pb.bench, cell, args, dev,
                           jax.devices()[:cell.chips], driver_cls=driver_cls)


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def good(pb):
    return _rehearsal(pb, 2 ** 31 + 27)


def test_the_cell_at_its_rehearsal_sizes_is_correct(good):
    assert good["correct"] and good["attempted"] > 0, good["compared"]
    assert set(good["metrics"]) == {"train_step_ms", "setup_s"}
    assert good["device"]["platform"] == "cpu"
    assert all(v <= lim for v, lim in good["compared"].values())


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_control_one_precision_below_is_not_correct(pb, seed):
    """The reference in the program's place, computed in fp8, against
    the cell's own limits; the reference against itself passes them."""
    cell = pb.bench.cell(CELL)
    cfg, ref, check = cell.config_for(rehearse=True), cell.reference(), \
        cell.check()
    w = ref.init_weights(cfg, seed)
    feed = pb.traffic.Feed(cell.traffic, cfg, seed)
    feed.place = lambda host: host
    batches = [feed.next().host for _ in range(check.CHECK_STEPS)]
    want = ref.train_steps(cfg, w, batches)
    same = check.judge(copy.deepcopy(want), want, cell.limits())
    assert same["correct"] and same["compared"]
    control = ref.train_steps(cfg, w, batches, precision="fp8")
    verdict = check.judge(control, want, cell.limits())
    assert not verdict["correct"], verdict


def _broken(real, fault):
    class Broken(real):
        def build(self, weights):
            if fault == "state_unchanged":
                self.config = copy.deepcopy(self.config)
                self.config["optimizer"]["learning_rate"] = 0.0
            super().build(weights)

        def step(self, batch):
            if fault == "half_batch":
                x, y = batch.host
                half = (x[:len(x) // 2], y[:len(y) // 2])
                batch = type(batch)(half, self.place(half))
            return super().step(batch)
    return Broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(pb, good, fault):
    cell = pb.bench.cell(CELL)
    bad = _rehearsal(pb, 2 ** 31 + 27,
                     driver_cls=_broken(cell.driver().Driver, fault))
    assert bad["correct"] is False, bad["compared"]
    assert any(v > lim for v, lim in bad["compared"].values())


# ---------------------------------------------------------------------------
# counts, by hand
# ---------------------------------------------------------------------------
def _published(pb, **sizes):
    cfg = pb.bench.cell(CELL).config_for()
    cfg.update(sizes)
    return cfg


def test_a_layer_is_51_388_416_parameters(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # q, k, v, out 4 x 2048^2; gate, up, down 3 x 2048 x 5632; 4 gains
    assert counts.layer_parameters(cfg) == 4 * 2048 ** 2 \
        + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416


def test_the_whole_model_is_2_668_b_and_shared_weights_count_once(pb):
    counts = pb.bench.cell(CELL).counts()
    whole = _published(pb, num_hidden_layers=48)
    # 48 layers + embedding and head 2 x 49152 x 2048 + final gain + gate
    assert counts.parameters(whole) == 48 * 51_388_416 + 201_326_592 \
        + 2048 + 2049 == 2_667_974_657
    # the passes share the stack: parameters (and the sweep's bytes) do
    # not grow with total_ut_steps, the work does
    once = _published(pb, total_ut_steps=1)
    cfg = _published(pb)
    assert counts.parameters(once) == counts.parameters(cfg)
    assert counts.sweep_bytes(cfg, 1) == 7 * 4 * counts.parameters(cfg)
    assert counts.layer_applications(cfg) == 4 * cfg["num_hidden_layers"]
    assert counts.step_flops(cfg) > 3.9 * counts.step_flops(once)


def test_a_step_at_8_layers_and_2048_tokens_is_26_8_tflop(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb, num_hidden_layers=8, batch_size=1)
    macs = 4 * (8 * (4 * 2048 ** 2 + 3 * 2048 * 5632) + 49152 * 2048)
    assert counts.matmul_macs_per_token(cfg) == macs == 2_046_820_352
    attention = 32 * 2048 * 2048 * 2048      # 32 applications, causal half
    assert counts.attention_macs_forward(cfg) == attention
    assert counts.step_flops(cfg) == 6 * (2048 * macs + attention)
    assert round(counts.step_flops(cfg) / 1e12, 1) == 26.8
    assert counts.attention_flops(cfg) == 6 * attention


# ---------------------------------------------------------------------------
# the three readers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def looped_ctx(pb):
    """A traced window made up over the REAL optimized module of a small
    looped step (compiled here, on the CPU, through ParallelTrainer):
    every instruction of the module runs once a step for 1 us."""
    import jax
    import numpy as np
    from mxnet_tpu import nd, telemetry
    from mxnet_tpu.gluon.contrib.transformer import LoopedLM
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh
    from mxnet_tpu.telemetry import phases
    import mxnet_tpu as mx
    net = LoopedLM(64, units=32, hidden_size=48, num_layers=2, num_heads=4,
                   num_passes=3)
    net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    trainer = ParallelTrainer(
        net, net.exit_loss(), "adam", {"learning_rate": 1e-3},
        mesh=make_mesh(dp=1, devices=jax.devices()[:1]), zero=2,
        dtype="bfloat16")
    telemetry.enable()
    try:
        rng = np.random.default_rng(0)
        trainer.step(nd.array(rng.integers(0, 64, (2, 8)), dtype="int32"),
                     nd.array(rng.integers(0, 64, (2, 8)).astype("f")))
        text = telemetry.program_hlo("step")
    finally:
        telemetry.disable()
    names = list(phases.instruction_phases(text))
    steps, ops, t = 2, [], 1000
    for _ in range(steps):
        for name in names:
            ops.append(("%%%s = f32[] op()" % name, t, t + 1000))
            t += 1000
    module = pb.phase_reduce._module_name(text)
    return {"steps": steps, "chips": 1, "program_hlo": [text],
            "trace": {"busy_s": (t - 1000) * 1e-9,
                      "ops_by_device": {0: ops},
                      "modules_by_device": {0: [(module + "(1)", 0, t)]}}}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_the_looped_module(pb, looped_ctx, name):
    value = pb.bench.metric_reader(name).read(looped_ctx)
    assert value is not None and value > 0
    read = {n: pb.bench.metric_reader(n).read(looped_ctx)
            for n in NEW_READERS + ("fwd_ms", "bwd_ms")}
    # recomputation is a share of the loop and the exits, which are a
    # share of forward + backward (the embedding and the loss's last
    # mean are outside both)
    assert read["recompute_ms"] < read["loop_ms"] + read["exit_ms"] \
        <= read["fwd_ms"] + read["bwd_ms"]
    assert read["loop_ms"] > read["exit_ms"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_nothing_without_the_scopes(pb, name):
    reader = pb.bench.metric_reader(name)
    assert reader.read({}) is None
    # the optimized module of cell 1 (recorded on the chip): phases, but
    # no pass and no exit
    with gzip.open(os.path.join(PB, "testdata",
                                "resnet50_b256_phases.hlo.txt.gz"),
                   "rt") as f:
        hlo = f.read()
    module = pb.phase_reduce._module_name(hlo)
    from mxnet_tpu.telemetry import phases
    some = list(phases.instruction_phases(hlo))[:200]
    ops = [("%%%s = f32[] op()" % n, 1000 * i, 1000 * i + 900)
           for i, n in enumerate(some)]
    ctx = {"steps": 1, "chips": 1, "program_hlo": [hlo],
           "trace": {"busy_s": 1e-3, "ops_by_device": {0: ops},
                     "modules_by_device": {0: [(module + "(1)", 0,
                                                10 ** 9)]}}}
    assert reader.read(ctx) is None
    assert pb.bench.metric_reader("fwd_ms").read(dict(ctx)) is not None
    # a program from before the scopes: no module text at all
    assert reader.read(dict(ctx, program_hlo=[])) is None


LOOP = "%while.1 = (s32[], f32[4]{0}) while(%tuple.2), condition=%c"
BODY = "%fusion.{0} = f32[4]{{0}} fusion(%p.{0}), kind=kLoop"


def test_a_loops_event_is_weighed_against_its_bodys_events(pb):
    """The readers count a ``while``'s body, not the ``while``: what the
    traced run prints to back that is the loop's own time and the time
    of the other events inside it."""
    cover = pb.phase_reduce.control_cover
    rest = [(0, 100),                                   # before the loop
            (100, 500), (500, 1000)]                    # its body
    # the loop is covered but for its last 100 ns; a call whose body
    # left no events, not at all
    control, covered = cover([(100, 1100), (2000, 2500)], rest)
    assert control == pytest.approx(1500e-9)
    assert covered == pytest.approx(900e-9)
    # a loop in a loop counts once
    assert cover([(100, 1100), (200, 900)], rest)[0] == pytest.approx(1e-6)
    assert cover([], rest) == (0.0, 0.0)


def test_what_a_loops_body_leaves_uncovered_is_unattributed(pb):
    """``phase_unattributed_share`` guards ``loop_ms`` / ``exit_ms``: the
    part of a ``control`` event that no other event covers is charged to
    ``other``, the covered part to nobody (the body's events carry it)."""
    hlo = """HloModule jit_step, is_scheduled=true

%body.1 (p.0: f32[4]) -> f32[4] {
  %p.0 = f32[4]{0} parameter(0)
  ROOT %fusion.1 = f32[4]{0} fusion(%p.0), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(mx_fwd)/mx_loop/while/body/mul"}
}

ENTRY %main.4 (w.1: f32[4]) -> f32[4] {
  %w.1 = f32[4]{0} parameter(0), metadata={op_name="w"}
  %while.1 = f32[4]{0} while(%w.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/jvp(mx_fwd)/mx_loop/while"}
  ROOT %fusion.2 = f32[4]{0} fusion(%while.1), kind=kLoop, calls=%f, metadata={op_name="jit(step)/mx_update/sweep/mul"}
}
"""
    ops = [(LOOP, 100, 1100),
           (BODY.format(1), 100, 500), (BODY.format(1), 500, 1000),
           ("%fusion.2 = f32[4]{0} fusion(%while.1)", 1100, 1300)]
    ctx = {"steps": 1, "chips": 1, "program_hlo": [hlo],
           "trace": {"busy_s": 1200e-9, "ops_by_device": {0: ops},
                     "modules_by_device": {0: [("jit_step(1)", 0, 2000)]}}}
    read = {n: pb.bench.metric_reader(n).read(ctx) for n in
            ("fwd_ms", "update_ms", "phase_unattributed_share", "loop_ms")}
    assert read["fwd_ms"] == read["loop_ms"] == pytest.approx(900e-6)
    assert read["update_ms"] == pytest.approx(200e-6)
    assert read["phase_unattributed_share"] == pytest.approx(100 * 100 / 1200)
    # the phases sum to busy time: nothing counted twice, nothing lost
    seconds = ctx["_phases"]["seconds"]
    assert "control" not in seconds
    assert sum(seconds.values()) == pytest.approx(ctx["trace"]["busy_s"])


# ---------------------------------------------------------------------------
# the manifest: rules on ``pb.bench`` (any checkout's), which an addition
# keeps (tests/perfbench/test_manifest_addition.py runs them over one)
# ---------------------------------------------------------------------------
def _in_order(part, whole):
    """Every name of ``part`` is in ``whole``, in ``part``'s order."""
    rest = iter(whole)
    return all(name in rest for name in part)


@pytest.mark.parametrize("name", REPORTED)
def test_the_manifest_lists_the_cell_under_what_it_reports(pb, name):
    """The cell is IN the ``workloads`` of each metric it reports, and
    its readers hold these, in this order, among whatever a later PR
    lists it under."""
    specs = {s["name"]: s for s in pb.bench.manifest["per_layer"]}
    assert CELL in specs[name]["workloads"]
    assert specs[name]["moves"] == "train_step_ms"
    if name in NEW_READERS:
        assert specs[name]["source"] == "device_trace"
    names = [s["name"] for s in pb.bench.cell(CELL).per_layer_metrics()]
    assert _in_order(REPORTED, names), names


def test_the_manifest_keeps_the_configuration_as_it_was_cut(pb):
    cell = pb.bench.cell(CELL)
    entry = pb.bench.config_entry(cell.config_name)
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers"]
    assert cell.config["total_ut_steps"] == 4 \
        and cell.config["vocab_size"] == 49152
