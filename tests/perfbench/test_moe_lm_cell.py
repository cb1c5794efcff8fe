"""The sparse-expert LM's cell, ``mellum2-12b-train-s8192`` (CPU, quick,
nothing at import time that touches jax or libtpu):

- at its ``rehearse`` sizes the cell runs through ``run.py`` and reads
  ``correct`` true; the fp8 control and three broken timed paths (the
  window ignored, the top-k weights not renormalised, one held expert
  left out) read false — under limits read AT those sizes
  (``REHEARSAL_LIMITS``), not under the chip's;
- the chip's limits (``limits/<cell>.json``) each lie between the two
  readings they were set from;
- ``counts/moe_lm.py`` against hand-worked values at the published
  widths;
- the six readers this cell brought (``moe_ms``, ``moe_route_ms``,
  ``expert_roofline``, ``attn_window_ms``, ``attn_full_ms``,
  ``block_recompute_ms``) on the optimized module of a small ``MoELM``
  step, and on a module without the scopes;
- the manifest lists the cell under every per-layer metric it reports
  (rules an addition keeps: ``test_manifest_addition.py`` runs every
  ``test_*manifest*`` of this directory over a checkout with one more
  cell).
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
CELL = "mellum2-12b-train-s8192"
NEW_READERS = ("moe_ms", "moe_route_ms", "expert_roofline",
               "attn_window_ms", "attn_full_ms", "block_recompute_ms")
# what the cell's traced line carries, in the manifest's order: the seven
# shared metrics, the five phase readers, its own six
REPORTED = ("step_mfu", "device_idle_share", "hbm_peak_share", "dispatch_ms",
            "compiles_in_window", "sweep_roofline", "flash_roofline",
            "fwd_ms", "bwd_ms", "update_ms", "phase_unattributed_share",
            "step_host_ms") + NEW_READERS
FAULTS = ("window_ignored", "weights_not_renormalised", "one_expert_left_out")
# The rehearsal's own limits.  ``limits/<cell>.json`` holds what the chip
# read at the timed size; the 128-wide rehearsal routes 256 tokens to 4
# held experts, a handful of bf16 routing flips move a whole leaf, and
# its worst-leaf numbers read ten times higher.  Read here on the CPU
# over 7 seeds (program largest / fp8 control smallest / smallest fault):
#   grad1_med 0.00021 / 0.0019 / 0.00026   the control fails by it (9 x)
#   grad1_top 0.00016 / 0.00090 / 0.00007  and by this one (5.6 x)
#   grad1     0.0085  / 0.0097 / 0.11      every fault fails by it (13 x)
#   dparam    0.0025  / 0.0040 / 0.047     and by this one (19 x)
# dparam_med and dparam_top (program up to 0.00013 / 0.00011, faults from
# 0.00014 / 0.00006) separate too little there and are read only.
REHEARSAL_LIMITS = {"grad1_med": 0.0006, "grad1_top": 0.0004,
                    "grad1": 0.03, "dparam": 0.01}


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


def _rehearsal_cell(pb):
    """The cell, held to the rehearsal's own limits."""
    cell = pb.bench.cell(CELL)
    cell.limits = lambda: {"cell": CELL, "limits": dict(REHEARSAL_LIMITS)}
    return cell


def _rehearsal(pb, seed, driver_cls=None):
    import jax
    cell = _rehearsal_cell(pb)
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
    return _rehearsal(pb, 2 ** 31 + 32)


def test_the_cell_at_its_rehearsal_sizes_is_correct(good):
    assert good["correct"] and good["attempted"] > 0, good["compared"]
    assert set(good["metrics"]) == {"train_step_ms", "setup_s"}
    assert good["device"]["platform"] == "cpu"
    assert all(v <= lim for v, lim in good["compared"].values())


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_control_one_precision_below_is_not_correct(pb, seed):
    """The reference in the program's place, computed in fp8, against
    the rehearsal's limits; the reference against itself passes them."""
    cell = _rehearsal_cell(pb)
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
    failed = {k for k, row in verdict["compared"].items() if not row["ok"]}
    assert {"grad1_med", "grad1_top"} <= failed, verdict


def test_each_limit_of_the_chip_lies_between_its_two_readings(pb):
    """``limits/<cell>.json`` is the timed size's: every limit stands
    over the program's largest reading and under the fp8 control's
    smallest, with at least twice of room on either side and no less
    of it above the program's than below the control's."""
    limits = pb.bench.cell(CELL).limits()
    assert set(limits["limits"]) == set(limits["readings"])
    for name, limit in limits["limits"].items():
        low = limits["readings"][name]["program_largest"]
        high = limits["readings"][name]["control_smallest"]
        assert 2 * low <= limit <= high / 2, name
        assert limit / low >= high / limit, name


def broken(real, fault):
    """The cell's driver with one piece of the mathematics left out of
    the timed path (the scratch script that read the limits on the chip
    planted the same three)."""
    class Broken(real):
        def build(self, weights):
            self.config = copy.deepcopy(self.config)
            if fault == "window_ignored":
                self.config["sliding_window"] = 1 << 30
            elif fault == "weights_not_renormalised":
                self.config["norm_topk_prob"] = False
            elif fault == "one_expert_left_out":
                from mxnet_tpu.parallel import moe
                whole = moe.routed_experts

                def fewer(x, router_w, experts, top_k, held, **kw):
                    return whole(x, router_w, tuple(w[:-1] for w in experts),
                                 top_k, (held[0], held[1] - 1), **kw)

                self._whole, moe.routed_experts = whole, fewer
            else:
                raise ValueError(fault)
            super().build(weights)

        def free(self):
            if fault == "one_expert_left_out":
                from mxnet_tpu.parallel import moe
                moe.routed_experts = self._whole
            super().free()
    return Broken


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(pb, good, fault):
    cell = pb.bench.cell(CELL)
    bad = _rehearsal(pb, 2 ** 31 + 32,
                     driver_cls=broken(cell.driver().Driver, fault))
    assert bad["correct"] is False, bad["compared"]
    assert any(v > lim for v, lim in bad["compared"].values())


# ---------------------------------------------------------------------------
# counts, by hand
# ---------------------------------------------------------------------------
def _published(pb, **sizes):
    cfg = pb.bench.cell(CELL).config_for()
    cfg.update(sizes)
    return cfg


def test_a_held_layer_is_120_476_160_parameters(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # q, out 2 x 2304 x 4096; k, v 2 x 2304 x 512
    assert counts.attention_parameters(cfg) == 21_233_664
    # gate, up, down 3 x 2304 x 896
    assert counts.expert_parameters(cfg) == 6_193_152
    # + two gains + the router over all 64 + 16 held experts
    assert counts.layer_parameters(cfg) == 21_233_664 + 4_608 + 147_456 \
        + 16 * 6_193_152 == 120_476_160
    assert counts.layer_parameters(cfg, experts=64) == 417_747_456


def test_the_cut_model_is_595_m_and_the_published_one_12_15_b(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # four layers + embedding and head 2 x 24576 x 2304 + the final gain
    assert counts.parameters(cfg) == 4 * 120_476_160 + 113_246_208 + 2304 \
        == 595_153_152
    assert counts.published_parameters(cfg) == 28 * 417_747_456 \
        + 2 * 98304 * 2304 + 2304 == 12_149_915_904 \
        == cfg["published"]["parameters"]
    assert counts.sweep_bytes(cfg, 1) == 7 * 4 * 595_153_152
    ref = pb.bench.cell(CELL).reference()
    import numpy as np
    assert sum(int(np.prod(s)) for _n, s, _i in ref.leaf_specs(cfg)) \
        == 595_153_152


def test_a_window_layer_sees_7_864_832_pairs(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # the first 1024 queries see 1..1024 keys, the other 7168 see 1024
    assert counts.attention_pairs(cfg, "sliding_attention") \
        == 1024 * 1025 // 2 + 7168 * 1024 == 7_864_832
    assert counts.attention_pairs(cfg, "full_attention") \
        == 8192 * 8192 // 2 == 33_554_432
    # scores and values, 32 heads of 128: three window layers and one full
    assert counts.attention_macs_forward(cfg) \
        == 2 * (3 * 7_864_832 + 33_554_432) * 4096 == 468_164_018_176
    assert counts.attention_flops(cfg) == 6 * 468_164_018_176
    # a window as long as the sequence is the causal triangle
    wide = _published(pb, sliding_window=8192)
    assert counts.attention_pairs(wide, "sliding_attention") \
        == 8192 * 8193 // 2
    with pytest.raises(ValueError):
        counts.attention_pairs(cfg, "linear_attention")


def test_expert_flops_count_the_rows_routed_and_a_step_is_12_2_tflop(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # 8192 tokens x 8 slots x 16 of 64 experts
    assert counts.expected_expert_rows(cfg) == 16384
    assert counts.expert_flops(cfg) == 3 * 2 * 3 * 2304 * 896 * 16384 * 4 \
        == 2_435_246_456_832
    macs = 4 * (21_233_664 + 147_456) + 24576 * 2304
    assert counts.matmul_macs_per_token(cfg) == macs == 142_147_584
    assert counts.step_flops(cfg) == 6 * 8192 * macs \
        + counts.expert_flops(cfg) + counts.attention_flops(cfg)
    assert round(counts.step_flops(cfg) / 1e12, 1) == 12.2
    # twice the held experts, twice the experts' work, the same attention
    more = _published(pb, num_experts=32)
    assert counts.expert_flops(more) == 2 * counts.expert_flops(cfg)
    assert counts.attention_flops(more) == counts.attention_flops(cfg)


# ---------------------------------------------------------------------------
# the six readers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def moe_ctx(pb):
    """A traced window made up over the REAL optimized module of a small
    ``MoELM`` step (compiled here, on the CPU, through ParallelTrainer):
    every instruction of the module runs once a step for 1 us."""
    import jax
    import numpy as np
    from mxnet_tpu import nd, telemetry
    from mxnet_tpu.gluon.contrib.transformer import MoELM
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh
    from mxnet_tpu.telemetry import phases
    import mxnet_tpu as mx
    net = MoELM(64, units=32, expert_width=16, num_heads=4, num_kv_heads=2,
                num_routed=4, held=(0, 2), top_k=2, window=4)
    net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    trainer = ParallelTrainer(
        net, net.lm_loss(), "adam", {"learning_rate": 1e-3},
        mesh=make_mesh(dp=1, devices=jax.devices()[:1]), zero=2,
        dtype="bfloat16")
    telemetry.enable()
    try:
        rng = np.random.default_rng(0)
        trainer.step(nd.array(rng.integers(0, 64, (2, 8)), dtype="int32"),
                     nd.array(rng.integers(0, 64, (2, 8)).astype("f")))
        text = telemetry.program_hlo("step")
        gauges = {name: telemetry.gauge(name) for name in (
            "mxnet_moe_experts", "mxnet_moe_top_k", "mxnet_moe_expected_rows",
            "mxnet_attn_layers", "mxnet_attn_window")}
        read = {"published": gauges["mxnet_moe_experts"].labels(
                    which="published").value,
                "held": gauges["mxnet_moe_experts"].labels(
                    which="held").value,
                "top_k": gauges["mxnet_moe_top_k"].labels().value,
                "rows": gauges["mxnet_moe_expected_rows"].labels().value,
                "sliding": gauges["mxnet_attn_layers"].labels(
                    kind="sliding_attention").value,
                "full": gauges["mxnet_attn_layers"].labels(
                    kind="full_attention").value,
                "window": gauges["mxnet_attn_window"].labels().value}
    finally:
        telemetry.disable()
    names = list(phases.instruction_phases(text))
    steps, ops, t = 2, [], 1000
    for _ in range(steps):
        for name in names:
            ops.append(("%%%s = f32[] op()" % name, t, t + 1000))
            t += 1000
    module = pb.phase_reduce._module_name(text)
    counts = argparse.Namespace(expert_flops=lambda config: 1e6)
    return {"steps": steps, "chips": 1, "program_hlo": [text],
            "gauges": read, "counts": counts, "config": {},
            "peaks": {"bf16_flops": 1e12},
            "trace": {"busy_s": (t - 1000) * 1e-9,
                      "ops_by_device": {0: ops},
                      "modules_by_device": {0: [(module + "(1)", 0, t)]}}}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_the_moe_module(pb, moe_ctx, name):
    value = pb.bench.metric_reader(name).read(moe_ctx)
    assert value is not None and value > 0
    read = {n: pb.bench.metric_reader(n).read(moe_ctx)
            for n in NEW_READERS + ("fwd_ms", "bwd_ms")}
    # routing is a share of the expert part; the expert part and both
    # kinds of attention are a share of forward + backward
    assert read["moe_route_ms"] < read["moe_ms"]
    assert read["moe_ms"] + read["attn_window_ms"] + read["attn_full_ms"] \
        <= read["fwd_ms"] + read["bwd_ms"]
    experts_ms = read["moe_ms"] - read["moe_route_ms"]
    assert read["expert_roofline"] == pytest.approx(
        100.0 * 1e6 / 1e12 / (1e-3 * experts_ms))
    # every layer is a jax.checkpoint: its forward runs again inside the
    # backward pass, the parts' share of it and the projections' as well
    assert 0 < read["block_recompute_ms"] < read["bwd_ms"]
    from mxnet_tpu.telemetry import phases
    again = [n for n, (_part, re) in phases.instruction_block_parts(
        moe_ctx["program_hlo"][0]).items() if re]
    assert read["block_recompute_ms"] == pytest.approx(1e-3 * len(again))


def test_the_block_says_what_it_is_in_gauges(moe_ctx):
    assert moe_ctx["gauges"] == {
        "published": 4, "held": 2, "top_k": 2, "rows": 2 * 8 * 2 * 2 / 4,
        "sliding": 1, "full": 1, "window": 4}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_nothing_without_the_scopes(pb, name):
    reader = pb.bench.metric_reader(name)
    assert reader.read({}) is None
    # the optimized module of cell 1 (recorded on the chip): phases, but
    # no expert part and no attention
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
           "peaks": {"bf16_flops": 1e12}, "config": {},
           "counts": argparse.Namespace(expert_flops=lambda config: 1e6),
           "trace": {"busy_s": 1e-3, "ops_by_device": {0: ops},
                     "modules_by_device": {0: [(module + "(1)", 0,
                                                10 ** 9)]}}}
    assert reader.read(ctx) is None
    assert pb.bench.metric_reader("fwd_ms").read(dict(ctx)) is not None
    # a program from before the scopes: no module text at all
    assert reader.read(dict(ctx, program_hlo=[])) is None
    # a program without the map (the parent of the PR that added it)
    import moe_reduce
    real = pb.phase_reduce.program
    pb.phase_reduce.program = lambda: argparse.Namespace()
    try:
        assert moe_reduce.parts(dict(ctx)) is None
    finally:
        pb.phase_reduce.program = real


def test_the_maps_classes_name_the_scopes():
    from mxnet_tpu.telemetry import phases
    part = phases.block_part_of
    assert part("jit(step)/jvp(mx_fwd)/mx_moe/mx_moe_experts/dot") \
        == ("experts", False)
    assert part("jit(step)/transpose(jvp(mx_fwd))/rematted_computation/"
                "mx_moe/sort") == ("route", True)
    assert part("jit(step)/jvp(mx_fwd)/mx_attn_window/cos") \
        == ("attn_window", False)
    assert part("jit(step)/jvp(mx_fwd)/mx_attn_full/cos") \
        == ("attn_full", False)
    assert part("jit(step)/mx_update/sweep/mul") == (None, False)
    assert part("") == (None, False)


# ---------------------------------------------------------------------------
# the manifest: rules on ``pb.bench`` (any checkout's), which an addition
# keeps (tests/perfbench/test_manifest_addition.py runs them over one)
# ---------------------------------------------------------------------------
def _in_order(part, whole):
    """Every name of ``part`` is in ``whole``, in ``part``'s order."""
    rest = iter(whole)
    return all(name in rest for name in part)


@pytest.mark.parametrize("name", REPORTED)
def test_the_manifest_lists_the_moe_cell_under_what_it_reports(pb, name):
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


def test_the_manifest_keeps_the_moe_configuration_as_it_was_cut(pb):
    cell = pb.bench.cell(CELL)
    entry = pb.bench.config_entry(cell.config_name)
    cfg = cell.config
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cell.chips == 1 and cell.traffic["inputs"]["seq_len"] == 8192
    # inside the floors: a whole period and four layers, eight experts,
    # an eighth of the vocabulary; no width moved
    pub = cfg["published"]
    assert cfg["num_hidden_layers"] == 4 and cfg["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["num_experts"] == 16 >= 8 and pub["num_experts"] == 64
    assert cfg["vocab_size"] == 24576 >= pub["vocab_size"] // 8
    assert cfg["deployment"]["experts_held"] == [0, 16]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"]) == (2304, 32, 4, 128, 896, 8, 1024)
    assert cfg["rope_parameters"]["full_attention"]["factor"] == 16
