"""The benchmark's own tests (CPU, quick, no child process, nothing at
import time that touches jax or libtpu).

- the manifest: names, units and the files every cell resolves to;
- data-driven: a configuration, a traffic mix, a cell's limits and a
  per-layer metric dropped into a copy of ``perfbench/`` are found with
  no edit to a file that is there;
- the count functions against hand-worked values and, once, against the
  program's ``analysis/ir/cost.py``;
- the trace reduction on a small xplane recorded on the chip;
- ``run.py`` refuses to run off a TPU;
- ``correct``: the control (the reference one precision below what the
  configuration states) comes out NOT correct, and so does a run whose
  timed path is broken underneath (a state left unchanged; half of the
  batch left out).
"""
import argparse
import copy
import gzip
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def pb():
    """perfbench's modules, imported by path (they are not a package)."""
    sys.path.insert(0, PB)
    try:
        import loader
        import traffic
        import trace_reduce
        import run
        yield argparse.Namespace(loader=loader, traffic=traffic,
                                 trace_reduce=trace_reduce, run=run,
                                 bench=loader.Bench(ROOT))
    finally:
        sys.path.remove(PB)


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------
# rules on ``pb.bench`` (any checkout's), which an addition keeps
# (tests/perfbench/test_manifest_addition.py runs them over one)
def test_manifest_names_and_units(pb):
    m = pb.bench.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(set(names)) == len(names)
    for w in m["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in m["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    e2e = {e["name"] for e in m["end_to_end"]}
    assert "setup_s" in e2e
    for e in m["end_to_end"]:
        assert 0 < e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert e["moves"] in e2e
    assert sum(w["chips"] == 4 for w in m["workloads"]) \
        <= max(1, len(m["workloads"]) // 4)
    assert 1 <= m["run_seconds"] <= 51


def _in_order(part, whole):
    """Every name of ``part`` is in ``whole``, in ``part``'s order."""
    rest = iter(whole)
    return all(name in rest for name in part)


def test_manifest_lists_cells_in_the_order_they_were_accepted(pb):
    """A metric's ``workloads`` names cells of the manifest, each once,
    in the manifest's own order: a PR appends its cell to ``workloads``
    and to every list it joins, so the accepted cells keep their order
    and come before a later one."""
    m = pb.bench.manifest
    order = [w["name"] for w in m["workloads"]]
    for e in m["end_to_end"] + m["per_layer"]:
        listed = e.get("workloads", order)
        assert listed and len(set(listed)) == len(listed), e["name"]
        assert _in_order(listed, order), e["name"]
    for w in m["workloads"]:
        cell = pb.bench.cell(w["name"])
        assert cell.per_layer_metrics(), w["name"]
        assert {"setup_s"} < {s["name"] for s in cell.end_to_end_metrics()}


@pytest.mark.parametrize("cell_name",
                         [w["name"] for w in _manifest()["workloads"]])
def test_every_cell_resolves_to_files(pb, cell_name):
    cell = pb.bench.cell(cell_name)
    cfg = cell.config
    for sub, name in (("drivers", cfg["driver"]), ("reference", cfg["family"]),
                      ("counts", cfg["family"]), ("checks", cfg["check"])):
        assert os.path.isfile(os.path.join(PB, sub, name + ".py"))
    limits = cell.limits()
    assert limits["cell"] == cell_name and limits["limits"]
    specs = cell.per_layer_metrics()
    assert specs and cell.end_to_end_metrics()
    for spec in specs:
        assert os.path.isfile(os.path.join(PB, "metrics",
                                           spec["name"] + ".py"))
    entry = pb.bench.config_entry(cell.config_name)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    # the feed draws what the configuration's rehearsal sizes say
    rc = cell.config_for(rehearse=True)
    feed = pb.traffic.Feed(cell.traffic, rc, 2 ** 31 + 7)
    feed.place = lambda host: host
    a, b = feed.next(), feed.next()
    assert a.host[0].shape[0] == rc[cell.traffic["rows_key"]]
    fresh = cell.traffic["fresh_each_step"]
    assert (a is b) != fresh
    assert len({r.tobytes() for r in a.host[0]}) == len(a.host[0])


def test_new_files_are_found_without_an_edit(pb, tmp_path):
    """A later PR's cell: new files and new entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(PB, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = {}
    for d, _dirs, files in os.walk(root / "perfbench"):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    m = _manifest()
    base = pb.bench.cell(m["workloads"][0]["name"])
    cfg = dict(base.config, name="new-config")
    (root / "perfbench/configs/new-config.json").write_text(json.dumps(cfg))
    mix = dict(base.traffic, what="a new mix")
    (root / "perfbench/traffic/new-mix.json").write_text(json.dumps(mix))
    (root / "perfbench/limits/new-cell.json").write_text(json.dumps(
        {"cell": "new-cell", "limits": {"loss1": 0.5}}))
    (root / "perfbench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['steps']\n")
    m["configs"].append({"name": "new-config", "source": "x",
                         "file": "perfbench/configs/new-config.json",
                         "reduced": [], "why": "y"})
    m["workloads"].append({"name": "new-cell", "config": "new-config",
                           "traffic": "new-mix", "chips": 1, "why": "z"})
    m["per_layer"].append({"name": "new_metric", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "host loop", "moves": "train_step_ms",
                           "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    bench = pb.loader.Bench(str(root))
    cell = bench.cell("new-cell")
    assert cell.config["name"] == "new-config"
    assert cell.traffic["what"] == "a new mix"
    assert cell.limits()["limits"] == {"loss1": 0.5}
    assert cell.driver().Driver and cell.reference().train_steps
    assert bench.metric_reader("new_metric").read({"steps": 4}) == 8.0
    # the new cell reads its metric and none that lists other cells, the
    # old cells do not see the new metric, and nothing was edited
    old = bench.cell(m["workloads"][0]["name"])
    theirs = {s["name"] for s in old.per_layer_metrics()}
    mine = {s["name"] for s in cell.per_layer_metrics()}
    assert "new_metric" in mine - theirs and not mine & theirs
    for p, data in before.items():
        assert open(p, "rb").read() == data
    with pytest.raises(KeyError):
        bench.cell("no-such-cell")
    with pytest.raises(KeyError):
        bench.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_resnet50_counts_by_hand(pb):
    counts = pb.bench.cell("resnet50-train-b256").counts()
    cfg = pb.bench.cell("resnet50-train-b256").config
    # by hand, stride on the 3x3 (v1.5 placement): stem 7x7x3x64 at 112^2,
    # then per stage [first unit, later units] of 1x1 / 3x3 / 1x1 (+ 1x1
    # shortcut) at 56, 28, 14, 7, then the 2048x1000 classifier
    stem = 64 * 3 * 49 * 112 * 112
    total = stem
    cin, size = 64, 56
    for i, (n, cout) in enumerate(((3, 256), (4, 512), (6, 1024),
                                   (3, 2048))):
        mid = cout // 4
        for j in range(n):
            s_in = size
            if j == 0 and i > 0:
                size //= 2
            total += mid * cin * s_in * s_in            # 1x1 at the input size
            total += mid * mid * 9 * size * size        # 3x3 carries the stride
            total += cout * mid * size * size           # 1x1
            if j == 0:
                total += cout * cin * size * size       # projection shortcut
            cin = cout
    total += 2048 * 1000
    assert counts.forward_macs_per_image(cfg) == total == 4089184256
    assert counts.parameters(cfg) == 25557032
    assert counts.step_flops(cfg) == 6 * total * 256
    assert counts.sweep_bytes(cfg, 1) == 5 * 4 * 25557032
    assert counts.sweep_bytes(cfg, 4) == 5 * 25557032


def test_opt_layer_counts_by_hand(pb):
    cell = pb.bench.cell("opt1p3b-train-s2048")
    counts, cfg = cell.counts(), dict(cell.config)
    one = dict(cfg, num_hidden_layers=1)
    two = dict(cfg, num_hidden_layers=2)
    # one OPT-1.3B layer: q, k, v, out (4 x 2048^2) and the FFN
    # (2 x 2048 x 8192) = 50,331,648 multiply-adds a token
    assert counts.matmul_macs_per_token(two) \
        - counts.matmul_macs_per_token(one) == 50331648
    assert counts.parameters(two) - counts.parameters(one) \
        == 50331648 + 8192 + 2048 + 4 * 2048
    tokens = cfg["batch_size"] * 2048
    # causal attention: scores + values, halved: B * T^2 * u a layer
    assert counts.attention_macs_forward(one) \
        == cfg["batch_size"] * 2048 * 2048 * 2048
    assert counts.step_flops(one) == 6 * (
        tokens * (50331648 + 50272 * 2048)
        + counts.attention_macs_forward(one))
    assert counts.rows_per_step(cfg) == tokens
    assert counts.sweep_bytes(cfg, 1) == 7 * 4 * counts.parameters(cfg)


def test_counts_agree_with_the_programs_cost_model(pb):
    """Once, against ``analysis/ir/cost.py``: its exact conv / dot FLOPs
    over the reference's forward jaxpr are twice the benchmark's
    multiply-adds."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.analysis.ir.cost import eqn_flops
    cell = pb.bench.cell("resnet50-train-b256")
    ref, counts, cfg = cell.reference(), cell.counts(), cell.config
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s, _i in ref.leaf_specs(cfg)}
    x = jax.ShapeDtypeStruct((1,) + tuple(cfg["image_shape"]), jnp.float32)
    y = jax.ShapeDtypeStruct((1,), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x, y: ref.loss_fn(p, x, y, cfg))(
        params, x, y)

    def walk(jx):
        total = 0
        for eqn in jx.eqns:
            if eqn.primitive.name in ("conv_general_dilated", "dot_general"):
                total += eqn_flops(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += walk(sub)
        return total

    assert walk(jaxpr.jaxpr) == 2 * counts.forward_macs_per_image(cfg)


# ---------------------------------------------------------------------------
# the trace reduction, on a small xplane recorded on the chip
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_trace(tmp_path_factory):
    src = os.path.join(PB, "testdata", "resnet50_b256_6steps.xplane.pb.gz")
    dst = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(PB, "testdata",
                           "resnet50_b256_6steps.json")) as f:
        return str(dst), json.load(f)


def test_trace_reduction_on_a_recorded_xplane(pb, small_trace):
    path, recorded = small_trace
    red = pb.trace_reduce.reduce(path, chips=1)
    # what the run that recorded it printed, to the nanosecond
    assert red["window_s"] == pytest.approx(recorded["window_s"], abs=1e-9)
    assert red["busy_s"] == pytest.approx(recorded["busy_s"], abs=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert len(red["modules_by_device"][0]) >= recorded["steps"]
    bd = red["breakdown"]
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(len(n) <= 200 and t > 0 for n, t in bd["device_ops"])
    assert bd["device_ops"][0][0].startswith("fusion:kOutput (")
    assert sum(t for _n, t in bd["device_ops"]) \
        == pytest.approx(red["busy_s"], rel=0.02)
    assert bd["device_ops"] == sorted(bd["device_ops"], key=lambda r: -r[1])
    idle = sum(t for _n, t in bd["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    # the sweep kernel is found by its pallas_call's name, once a step
    sec, n = pb.trace_reduce.event_seconds(
        red, re.compile(r"_sgd_mom_kernel"))
    assert n >= recorded["steps"] and sec > 0
    assert pb.trace_reduce.event_seconds(red, re.compile("no_such"))[1] == 0


def test_interval_arithmetic(pb):
    tr = pb.trace_reduce
    assert tr.union_length([(0, 10), (5, 12), (20, 21), (3, 4)]) == 13
    assert tr.union_length([]) == 0
    assert tr.merged([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
    assert tr.short_name("%a.1 = f32[2,3]{1,0:T(8,128)} add(f32[2,3]{1,0} "
                         "%b)") == "a.1 f32[2,3] add(f32[2,3] %b)"
    assert tr.opcode("%fusion.23 = (f32[256]{0}, bf16[2]{0}) fusion(f32[256]"
                     "{0} %custom-call.3), kind=kOutput, calls=%f") \
        == "fusion:kOutput"
    assert tr.opcode("%_sgd_mom_kernel.3 = (f32[8,128]{1,0}, f32[8,128]{1,0})"
                     " custom-call(f32[4]{0} %p)") \
        == "custom-call:_sgd_mom_kernel"
    assert tr.opcode("%transpose_jvp__flash_bwd_dkv_kernel__.7 = (bf16[4]{0}, "
                     "bf16[4]{0}) custom-call(bf16[4]{0} %x)") \
        == "custom-call:_flash_bwd_dkv_kernel"
    assert tr.opcode("%reshape.38 = f32[2,3]{1,0} reshape(f32[6]{0} "
                     "%copy-done.27)") == "reshape"
    assert tr.opcode("%all-reduce-start.1 = f32[4]{0} all-reduce-start("
                     "f32[4]{0} %x), replica_groups={}") == "all-reduce-start"


def test_metric_readers_return_nothing_when_there_is_nothing(pb):
    """No peak table (a CPU rehearsal) or no matching event: no number,
    never a 0 share of a roofline."""
    ctx = {"peaks": None, "steps": 3, "window_s": 1.0, "chips": 1,
           "spans": [], "memory_peak_bytes": 0, "compiles_in_window": 0,
           "trace": {"window_s": 1.0, "busy_s": 0.5,
                     "ops_by_device": {0: [("%x = f32[] add()", 0, 5)]}},
           "config": {}, "counts": None}
    for name in ("step_mfu", "hbm_peak_share", "sweep_roofline",
                 "flash_roofline", "dispatch_ms",
                 "collective_exposed_share"):
        assert pb.bench.metric_reader(name).read(ctx) is None, name
    assert pb.bench.metric_reader("device_idle_share").read(ctx) == 50.0
    assert pb.bench.metric_reader("compiles_in_window").read(ctx) == 0.0


# ---------------------------------------------------------------------------
# run.py
# ---------------------------------------------------------------------------
def test_run_refuses_to_run_off_a_tpu(pb, monkeypatch, capsys):
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", "")
    monkeypatch.setenv("MXNET_COMPILE_CACHE_MAX_BYTES", "1")
    with pytest.raises(SystemExit) as exc:
        pb.run.main(["--workload", "resnet50-train-b256", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert exc.value.code == 2
    out = capsys.readouterr()
    assert out.out.strip() == "" and "nothing was run" in out.err


# ---------------------------------------------------------------------------
# correct: the control and the broken runs come out NOT correct
# ---------------------------------------------------------------------------
def _rehearsal(pb, cell_name, seed, driver_cls=None):
    import jax
    cell = pb.bench.cell(cell_name)
    args = argparse.Namespace(seed=seed, seconds=0.3, trace=0,
                              rehearse=True, trace_dir=None)
    dev = {"platform": "cpu", "kind": "cpu", "count": 1}
    return pb.run.run_cell(pb.bench, cell, args, dev,
                           jax.devices()[:cell.chips], driver_cls=driver_cls)


# the control at sizes a test run can hold: the configuration's CPU
# rehearsal sizes, ResNet at its own depth (at 18 layers fp8's error does
# not build up to what the cell's limits were set against)
CONTROL_CASES = [
    ("resnet50-train-b256", {"num_layers": 50, "num_classes": 100}, 11),
    ("resnet50-train-b256", {"num_layers": 50, "num_classes": 100},
     2 ** 31 + 12),
    ("opt1p3b-train-s2048", {}, 11),
    ("opt1p3b-train-s2048", {}, 2 ** 31 + 12),
    ("opt1p3b-train-s2048", {}, 13),
]


@pytest.mark.parametrize("cell_name,sizes,seed", CONTROL_CASES)
def test_control_one_precision_below_is_not_correct(pb, cell_name, sizes,
                                                    seed):
    """The reference in the program's place, computed in fp8 (below the
    configuration's bf16: operands e4m3, gradients e5m2), against the
    cell's own limits.  The reference against itself passes them."""
    cell = pb.bench.cell(cell_name)
    cfg, ref, check = cell.config_for(rehearse=True), cell.reference(), \
        cell.check()
    cfg.update(sizes)
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
                # the program steps with a zero rate: its state comes
                # back as it went in
                self.config = copy.deepcopy(self.config)
                self.config["optimizer"]["learning_rate"] = 0.0
            super().build(weights)

        def step(self, batch):
            if fault == "half_batch":
                # half of the rows left out, the mean taken over the rest
                x, y = batch.host
                half = (x[:len(x) // 2], y[:len(y) // 2])
                batch = type(batch)(half, self.place(half))
            return super().step(batch)
    return Broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(pb, fault):
    cell = pb.bench.cell("opt1p3b-train-s2048")
    good = _rehearsal(pb, cell.name, 2 ** 31 + 21)
    assert good["correct"] and good["attempted"] > 0
    assert set(good["metrics"]) == {"train_step_ms", "setup_s"}
    assert good["device"]["platform"] == "cpu"
    bad = _rehearsal(pb, cell.name, 2 ** 31 + 21,
                     driver_cls=_broken(cell.driver().Driver, fault))
    assert bad["correct"] is False, bad["compared"]
    assert any(v > lim for v, lim in bad["compared"].values())
