"""The compressed-convolutional-attention cell, ``zaya1-8b-train-s16384``
(CPU, quick, nothing at import time that touches jax or libtpu):

- at its ``rehearse`` sizes the cell runs through ``run.py`` and reads
  ``correct`` true; the fp8 control and three broken timed paths of this
  mechanism (the convolution over time left out, the top-1 weight
  renormalised to 1, the rotary turned over the whole head) read false —
  under limits read AT those sizes (``REHEARSAL_LIMITS``), not under the
  chip's;
- the chip's limits (``limits/<cell>.json``) stand where the readings
  they were set from say;
- ``counts/zaya_moe_lm.py`` against hand-worked values at the published
  widths, and against the reference's leaves;
- the reader this cell brought (``cca_mix_ms``) on the optimized module
  of a small CCA ``MoELM`` step beside the shared ones, and on a module
  without the scope;
- the manifest lists the cell under every per-layer metric it reports,
  and keeps the configuration as it was cut (rules an addition keeps:
  ``test_manifest_addition.py`` runs every ``test_*manifest*`` of this
  directory over a checkout with one more cell).
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
CELL = "zaya1-8b-train-s16384"
NEW_READERS = ("cca_mix_ms",)
SHARED_READERS = ("moe_ms", "moe_route_ms", "expert_roofline",
                  "attn_full_ms", "block_recompute_ms")
# what the cell's traced line carries, in the manifest's order
REPORTED = ("step_mfu", "device_idle_share", "hbm_peak_share", "dispatch_ms",
            "compiles_in_window", "sweep_roofline", "flash_roofline",
            "fwd_ms", "bwd_ms", "update_ms", "phase_unattributed_share",
            "step_host_ms", "moe_ms", "moe_route_ms", "expert_roofline",
            "attn_full_ms", "block_recompute_ms", "setup_trace_lower_s",
            "setup_backend_s", "setup_cache_misses", "setup_place_s",
            "setup_import_s", "jit_compiles_in_window") + NEW_READERS
FAULTS = ("time_conv_left_out", "weights_renormalised", "rotary_whole_head")
# The rehearsal's own limits.  ``limits/<cell>.json`` holds what the chip
# read at the timed size; the 128-wide rehearsal sends 256 tokens to 8
# experts, a bf16 routing flip moves a 32nd of an expert's rows, and its
# numbers read higher.  Read here on the CPU (the program over 10 seeds,
# the control over 6, each fault over 3: program largest / fp8 control
# smallest; each fault at the seed the test runs, in FAULTS' order):
#   loss1      9.2e-05 / 7.5e-04    the control fails by these four
#   loss2      6.6e-05 / 1.1e-03
#   grad1_med  5.0e-04 / 2.3e-03 / 0.011, 0.0044, 0.0028
#   grad1_top  9.7e-05 / 2.7e-04 / 0.040, 8e-05, 8.7e-04
#   grad1      0.013   / 0.019  / 0.93, 6.4, 0.044
#   dparam     0.0097  / 0.0077 / 0.29, 0.72, 0.0094
# so every fault fails by three or more numbers: the convolution over time
# left out by the last four, the weight renormalised by grad1_med and the
# worst-leaf two, the whole head turned by the three gradient numbers.
# The losses move under no fault here; the rest separate too little and
# are read only.
REHEARSAL_LIMITS = {"loss1": 2.5e-4, "loss2": 2.5e-4, "grad1_med": 0.0011,
                    "grad1_top": 0.00016, "grad1": 0.03, "dparam": 0.03}


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
    return _rehearsal(pb, 2 ** 31 + 40)


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


def test_the_chips_limits_stand_where_their_readings_say(pb):
    """``limits/<cell>.json`` is the timed size's.  A number compared
    against the control stands between the program's largest reading and
    the fp8 control's smallest, with room on both sides; the losses,
    which the control does not move, take the accepted cells' 1e-4 and
    stand between the program's largest and the least fault reading ten
    times over it; the others are read only, each with its readings.
    The control fails by every control limit on every seed it ran on;
    four of the five faults planted in the reference fail by three limits
    or more on every seed, three of them by every loss and the worst
    leaf, and the file says which one the check cannot see and why."""
    limits = pb.bench.cell(CELL).limits()
    check = pb.bench.cell(CELL).check()
    lim, read = limits["limits"], limits["readings"]
    losses = {"loss%d" % (i + 1) for i in range(check.CHECK_STEPS)}
    names = losses | {"grad1_top", "grad1_med", "grad1", "dparam_top",
                      "dparam_med", "dparam"}
    assert set(read) == names and set(lim) < names and len(lim) >= 2
    assert len(limits["program_seeds"]) >= 12
    for k in names:
        r = read[k]
        if k not in lim:
            assert r["by"] == "none" and "limit" not in r, k
            continue
        assert r["limit"] == lim[k], k
        if k in losses:
            assert r["by"] == "fault" and lim[k] == 1e-4, k
            assert 5 * r["program_largest"] <= lim[k] \
                <= r["fault_least_tenfold"] / 5, k
        else:
            assert r["by"] == "control", k
            assert 1.8 * r["program_largest"] <= lim[k] \
                <= r["control_smallest"] / 1.5, k
    by_control = [k for k in lim if read[k]["by"] == "control"]
    assert len(limits["control_by_seed"]) >= 6
    for row in limits["control_by_seed"]:
        assert all(row[k] > lim[k] for k in by_control), row
    faults = limits["faults"]
    assert set(FAULTS) <= set(faults)
    for f, row in faults.items():
        assert row["fails_by"] == [k for k in lim if row[k] > lim[k]], f
    seen = {f for f, row in faults.items() if len(row["fails_by"]) >= 3}
    assert set(faults) - seen == {"rotary_whole_head"}
    for f in ("time_conv_left_out", "weights_renormalised",
              "qk_mean_left_out"):
        assert losses | {"grad1"} <= set(faults[f]["fails_by"]), f
    assert len(faults["rotary_whole_head"]["seeds"]) >= 3
    assert "rotary turned over the whole head fails by none on every seed" \
        in limits["set_from"]


def broken(real, fault):
    """The cell's driver with one piece of the mathematics wrong in the
    timed path (the chip's readings planted the same three, and two more,
    in the reference)."""
    class Broken(real):
        def build(self, weights):
            if fault == "time_conv_left_out":
                from mxnet_tpu.ops import contrib
                whole = contrib._causal_conv

                def causal_conv(data, weight, **kw):
                    if weight.ndim == 2:        # the convolution over time
                        return data
                    return whole(data, weight, **kw)

                self._undo = lambda: setattr(contrib, "_causal_conv", whole)
                contrib._causal_conv = causal_conv
            super().build(weights)

        def _block(self, mx, weights):
            net = super()._block(mx, weights)
            if fault == "weights_renormalised":
                net._config["norm_topk"] = True
            elif fault == "rotary_whole_head":
                net._config["rotary_dim"] = None
            return net

        def free(self):
            getattr(self, "_undo", lambda: None)()
            super().free()
    return Broken


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(pb, good, fault):
    cell = pb.bench.cell(CELL)
    bad = _rehearsal(pb, 2 ** 31 + 40,
                     driver_cls=broken(cell.driver().Driver, fault))
    assert bad["correct"] is False, bad["compared"]
    assert sum(v > lim for v, lim in bad["compared"].values()) >= 2, \
        bad["compared"]


# ---------------------------------------------------------------------------
# counts, by hand
# ---------------------------------------------------------------------------
def _published(pb, **sizes):
    cfg = pb.bench.cell(CELL).config_for()
    cfg.update(sizes)
    return cfg


def test_a_held_layer_is_106_899_970_parameters(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # q, out 2 x 2048 x 1024; k, v 2 x 2048 x 256
    assert counts.attention_parameters(cfg) == 5_242_880
    # the convolution over time 2 x 1280, across a head 10 x 2 x 128 x
    # 128, two temperatures
    assert counts.cca_parameters(cfg) == 2_560 + 327_680 + 2 == 330_242
    # down 2048 x 256, two hidden 256 x 256, last 256 x 16
    assert counts.router_parameters(cfg) == 524_288 + 131_072 + 4_096
    # gate, up, down 3 x 2048 x 2048
    assert counts.expert_parameters(cfg) == 12_582_912
    outside = 5_242_880 + 330_242 + 4_096 + 659_456
    assert outside == 6_236_674
    assert counts.layer_parameters(cfg) == outside + 8 * 12_582_912 \
        == 106_899_970
    assert counts.layer_parameters(cfg, experts=16) == 207_563_266


def test_the_cut_model_is_0_495_b_and_the_published_8_84_b(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # four layers + the tied table 32784 x 2048 (once) + the final gain
    assert counts.parameters(cfg) == 4 * 106_899_970 + 67_141_632 + 2048 \
        == 494_743_560
    assert counts.published_parameters(cfg) == 40 * 207_563_266 \
        + 262_272 * 2048 + 2048 == 8_839_665_744 \
        == cfg["published"]["parameters"]
    # the family's "A0.76B": one expert a layer, no embedding
    assert 40 * (6_236_674 + 12_582_912) == 752_783_440
    assert counts.sweep_bytes(cfg, 1) == 7 * 4 * counts.parameters(cfg)
    ref = pb.bench.cell(CELL).reference()
    import numpy as np
    assert sum(int(np.prod(s)) for _n, s, _i in ref.leaf_specs(cfg)) \
        == counts.parameters(cfg)


def test_a_step_is_18_1_tflop_attention_and_head_a_third_each(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    t, n = 16384, 4
    assert counts.rows_per_step(cfg) == t
    # top-1 of 16, 8 held: 8192 rows a layer, 1024 an expert
    assert counts.expected_expert_rows(cfg) == 8192
    assert counts.expert_flops(cfg) == 6 * n * 8192 * 12_582_912
    # 8 query heads of 128 over T^2 / 2 pairs, scores and values
    assert counts.attention_macs_forward(cfg) == 2 * n * (t * t // 2) * 1024
    assert counts.attention_flops(cfg) == 6 * counts.attention_macs_forward(
        cfg)
    # projections, the mix across heads and the router; the tied head
    assert counts.cca_mix_macs_per_token(cfg) == 327_680
    per_token = n * (5_242_880 + 327_680 + 659_456) + 32784 * 2048
    assert counts.matmul_macs_per_token(cfg) == per_token
    step = 6 * (t * per_token + n * 8192 * 12_582_912
                + counts.attention_macs_forward(cfg))
    assert counts.step_flops(cfg) == step
    assert step == pytest.approx(18.12e12, rel=1e-3)
    head = 6 * t * 32784 * 2048
    for part in (counts.attention_flops(cfg), head):
        assert 0.35 < part / step < 0.38
    assert 0.13 < counts.expert_flops(cfg) / step < 0.14


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cca_ctx(pb):
    """A traced window made up over the REAL optimized module of a small
    CCA ``MoELM`` step (compiled here, on the CPU, through
    ParallelTrainer): every instruction of the module runs once a step
    for 1 us."""
    import jax
    import numpy as np
    from mxnet_tpu import nd
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.contrib.transformer import FULL, MoELM
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh
    from mxnet_tpu.telemetry import phases
    import mxnet_tpu as mx
    net = MoELM(64, units=32, expert_width=16, layer_types=[FULL, FULL],
                num_heads=4, num_kv_heads=2, num_routed=4, held=(0, 2),
                top_k=1, norm_topk=False, cca=(2, 2), rotary_dim=4,
                router_hidden=8, router_layers=3, tie_embeddings=True)
    net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    trainer = ParallelTrainer(
        net, net.lm_loss(), "adam", {"learning_rate": 1e-3},
        mesh=make_mesh(dp=1, devices=jax.devices()[:1]), zero=2,
        dtype="bfloat16")
    telemetry.enable()
    try:
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 64, (2, 17))
        trainer.step(nd.array(ids[:, :-1], dtype="int32"),
                     nd.array(ids[:, 1:], dtype="float32"))
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
    counts = argparse.Namespace(expert_flops=lambda config: 1e6)
    return {"steps": steps, "chips": 1, "program_hlo": [text],
            "counts": counts, "config": {}, "peaks": {"bf16_flops": 1e12},
            "trace": {"busy_s": (t - 1000) * 1e-9,
                      "ops_by_device": {0: ops},
                      "modules_by_device": {0: [(module + "(1)", 0, t)]}}}


@pytest.mark.parametrize("name", NEW_READERS + SHARED_READERS)
def test_readers_read_the_cca_module(pb, cca_ctx, name):
    value = pb.bench.metric_reader(name).read(cca_ctx)
    assert value is not None and value > 0
    read = {n: pb.bench.metric_reader(n).read(cca_ctx)
            for n in NEW_READERS + SHARED_READERS + ("fwd_ms", "bwd_ms")}
    # the mixing, the flash call and the expert part are shares of
    # forward + backward, and lie beside each other
    assert read["cca_mix_ms"] + read["attn_full_ms"] + read["moe_ms"] \
        <= read["fwd_ms"] + read["bwd_ms"]
    # this block's attention runs under no other attention scope
    for other in ("attn_window_ms", "attn_latent_ms", "attn_blockdiff_ms"):
        assert not pb.bench.metric_reader(other).read(cca_ctx)
    from mxnet_tpu.telemetry import phases
    parts = phases.instruction_cca_parts(cca_ctx["program_hlo"][0])
    mine = [n for n, (p, _re) in parts.items() if p == "cca"]
    assert read["cca_mix_ms"] == pytest.approx(1e-3 * len(mine))
    # every layer is a jax.checkpoint: its mixing runs again
    assert any(p == "cca" and again for p, again in parts.values())


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_nothing_without_the_scopes(pb, name):
    reader = pb.bench.metric_reader(name)
    assert reader.read({}) is None
    # the optimized module of cell 1 (recorded on the chip): phases, but
    # no compressed attention
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
           "trace": {"busy_s": 1e-3, "ops_by_device": {0: ops},
                     "modules_by_device": {0: [(module + "(1)", 0,
                                                10 ** 9)]}}}
    assert reader.read(ctx) is None
    assert pb.bench.metric_reader("fwd_ms").read(dict(ctx)) is not None
    # a program from before the scopes: no module text at all
    assert reader.read(dict(ctx, program_hlo=[])) is None
    # a program without the map (the parent of the PR that added it)
    import cca_reduce
    real = pb.phase_reduce.program
    pb.phase_reduce.program = lambda: argparse.Namespace()
    try:
        assert cca_reduce.cca_seconds(dict(ctx)) is None
    finally:
        pb.phase_reduce.program = real


def test_the_maps_class_names_the_scope():
    from mxnet_tpu.telemetry import phases
    part = phases.cca_part_of
    assert part("jit(step)/jvp(mx_fwd)/mx_cca/mul") == ("cca", False)
    assert part("jit(step)/transpose(jvp(mx_fwd))/rematted_computation/"
                "mx_cca/dot_general") == ("cca", True)
    assert part("jit(step)/jvp(mx_fwd)/mx_attn_full/_flash_fwd_kernel") \
        == (None, False)
    assert part("") == (None, False)
    # the older maps do not take the new scope for theirs
    assert phases.block_part_of("jit(step)/jvp(mx_fwd)/mx_cca/cos") \
        == (None, False)


# ---------------------------------------------------------------------------
# the manifest: rules on ``pb.bench`` (any checkout's), which an addition
# keeps (tests/perfbench/test_manifest_addition.py runs them over one)
# ---------------------------------------------------------------------------
def _in_order(part, whole):
    """Every name of ``part`` is in ``whole``, in ``part``'s order."""
    rest = iter(whole)
    return all(name in rest for name in part)


@pytest.mark.parametrize("name", REPORTED)
def test_the_manifest_lists_the_zaya1_cell_under_what_it_reports(pb, name):
    """The cell is IN the ``workloads`` of each metric it reports, and
    its readers hold these, in this order, among whatever a later PR
    lists it under."""
    specs = {s["name"]: s for s in pb.bench.manifest["per_layer"]}
    assert CELL in specs[name]["workloads"]
    assert specs[name]["moves"] == ("setup_s" if name.startswith("setup_")
                                    else "train_step_ms")
    if name in NEW_READERS:
        assert specs[name]["source"] == "device_trace"
        assert specs[name]["layer"] == "compressed convolutional attention"
    names = [s["name"] for s in pb.bench.cell(CELL).per_layer_metrics()]
    assert _in_order(REPORTED, names), names
    # it runs no window, latent or block-diffusion attention
    for other in ("attn_window_ms", "attn_latent_ms", "attn_blockdiff_ms",
                  "noise_ms"):
        assert CELL not in specs[other]["workloads"]


def test_the_manifest_keeps_the_zaya1_configuration_as_it_was_cut(pb):
    cell = pb.bench.cell(CELL)
    entry = pb.bench.config_entry(cell.config_name)
    cfg = cell.config
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    assert cell.chips == 1 and cell.traffic["inputs"]["seq_len"] == 16384
    assert cell.traffic_name == "packed-tokens-16384"
    # inside the floors: four layers of the one kind, eight experts, an
    # eighth of the vocabulary; no width moved
    pub = cfg["published"]
    assert cfg["num_hidden_layers"] == 4 and pub["num_hidden_layers"] == 40
    assert set(cfg["layer_types"]) == {"hybrid"} \
        and len(cfg["layer_types"]) == 40
    assert cfg["num_experts"] == 8 and pub["num_experts"] == 16
    assert cfg["vocab_size"] == 32784 == pub["vocab_size"] // 8
    dep = cfg["deployment"]
    assert dep["experts_held"] == [0, 8] and dep["chips_sharing_a_layer"] == 2
    assert dep["vocab_rows_held"] == [0, 32784] \
        and dep["vocab_split_over"] == 8
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_hidden_size"], cfg["cca_time0"], cfg["cca_time1"],
            cfg["partial_rotary_factor"], cfg["rms_norm_eps"]) \
        == (2048, 8, 2, 128, 2048, 1, 256, 2, 2, 0.5, 1e-05)
    assert cfg["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    assert cfg["tie_word_embeddings"] is True \
        and cfg["assumed"]["norm_topk_prob"] is False \
        and cfg["sliding_window"] is None
    for key in ("cca_time_conv", "cca_channel_conv", "cca_qk_mean",
                "cca_value_shift", "cca_qk_norm", "temperature_init",
                "router", "router_init", "conv_init", "init_std"):
        assert key in cfg["assumed"], key
    departures = " ".join(cfg["departures"])
    for what in ("EDA", "mixture-of-depths", "residual scaling", "PID"):
        assert what in departures, what
