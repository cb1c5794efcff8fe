"""The block-diffusion cell, ``sdar-30b-train-s4096`` (CPU, quick, nothing
at import time that touches jax or libtpu):

- at its ``rehearse`` sizes the cell runs through ``run.py`` and reads
  ``correct`` true; the fp8 control and four broken timed paths of this
  mechanism (the mask taken as plain causal over the 2 L rows, QK-norm
  left out, the weights ``m`` in place of ``m / p``, the objective
  halved) read false — under
  limits read AT those sizes (``REHEARSAL_LIMITS``), not under the
  chip's;
- the chip's limits (``limits/<cell>.json``) stand where the readings
  they were set from say;
- the driver places a batch as ``[ids ; draws]`` made from the ids alone,
  and the step is a function of them;
- ``counts/sdar_moe_lm.py`` against hand-worked values at the published
  widths;
- the two readers this cell brought (``attn_blockdiff_ms``, ``noise_ms``)
  on the optimized module of a small block-diffusion ``MoELM`` step
  beside the shared ones, and on a module without the scopes;
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
CELL = "sdar-30b-train-s4096"
NEW_READERS = ("attn_blockdiff_ms", "noise_ms")
SHARED_READERS = ("moe_ms", "moe_route_ms", "expert_roofline",
                  "block_recompute_ms")
# what the cell's traced line carries, in the manifest's order
REPORTED = ("step_mfu", "device_idle_share", "hbm_peak_share", "dispatch_ms",
            "compiles_in_window", "sweep_roofline", "flash_roofline",
            "fwd_ms", "bwd_ms", "update_ms", "phase_unattributed_share",
            "step_host_ms", "moe_ms", "moe_route_ms", "expert_roofline",
            "block_recompute_ms", "setup_trace_lower_s", "setup_backend_s",
            "setup_cache_misses", "setup_place_s", "setup_import_s",
            "jit_compiles_in_window") + NEW_READERS
FAULTS = ("mask_plain_causal", "qk_norm_left_out", "weights_m_not_m_over_p",
          "objective_halved")
# The rehearsal's own limits.  ``limits/<cell>.json`` holds what the chip
# read at the timed size; the 128-wide rehearsal sends 512 rows to 4 held
# experts, a handful of bf16 routing flips move a whole leaf, and its
# numbers read higher.  Read here on the CPU (the program over 10 seeds,
# the control and the faults over 7: program largest / fp8 control smallest
# / smallest reading of any of the four faults):
#   grad1_med  0.00056 / 0.0049 / 0.0085   the control fails by it (8.8 x)
#   grad1_top  0.00081 / 0.0029 / 0.00068  and by this one (3.6 x)
#   grad1      0.0059  / 0.032  / 0.50     every fault fails by it (31 x)
#   dparam_med 0.00030 / 0.0013 / 0.0023   and by this one (7.7 x)
#   loss1-3    0.00005 / 0.0001 / -        the two faults that move a loss
#                                          read 0.45 and 0.5
# dparam (program up to 0.012, control from 0.015) and dparam_top (0.00018
# / 0.00036) separate too little there and are read only.
REHEARSAL_LIMITS = {"grad1_med": 0.002, "grad1_top": 0.0013,
                    "grad1": 0.016, "dparam_med": 0.00057,
                    "loss1": 0.002, "loss2": 0.002, "loss3": 0.002}


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
    return _rehearsal(pb, 2 ** 31 + 38)


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


def test_the_chips_limits_stand_where_their_readings_say(pb):
    """``limits/<cell>.json`` is the timed size's, and EVERY number the
    check reads carries a limit.  The four medians stand between the
    program's largest reading and the fp8 control's smallest with room
    on both sides; the two worst-leaf numbers and the losses, which the
    precision hardly moves, between the program's largest and the least
    fault's reading, the more room above.  The control fails by a limit
    on every seed it ran on, every fault by at least two, five times
    over."""
    limits = pb.bench.cell(CELL).limits()
    check = pb.bench.cell(CELL).check()
    lim, read = limits["limits"], limits["readings"]
    names = {"loss%d" % (i + 1) for i in range(check.CHECK_STEPS)} | {
        "grad1_top", "grad1_med", "grad1", "dparam_top", "dparam_med",
        "dparam"}
    assert set(lim) == set(read) == names
    assert len(limits["program_seeds"]) >= 36
    for k in ("grad1_top", "grad1_med", "dparam_top", "dparam_med"):
        assert 1.8 * read[k]["program_largest"] <= lim[k] \
            <= read[k]["control_smallest"] / 1.75, k
    for k in ("grad1", "dparam"):
        assert 3 * read[k]["program_largest"] <= lim[k] \
            <= read[k]["fault_least"] / 5, k
    for k in ("loss1", "loss2", "loss3"):
        assert 10 * read[k]["program_largest"] <= lim[k] <= 0.47 / 100, k
    as_they_came = [r for r in limits["control_by_seed"]
                    if r["how"] == "as it came"]
    assert len(as_they_came) >= 19
    for row in limits["control_by_seed"]:
        assert sum(row[k] > lim[k] for k in row if k in lim) >= 2, row
    assert set(limits["faults"]) == set(FAULTS)
    for fault, row in limits["faults"].items():
        failed = [k for k in lim if row[k] > 5 * lim[k]]
        assert len(failed) >= 2, (fault, failed)
    # a wrong SCALE of the objective, which Adam's step does not see,
    # fails by the first gradient's numbers and by the losses
    halved = limits["faults"]["objective_halved"]
    assert all(halved[k] > 100 * lim[k] for k in (
        "loss1", "grad1_top", "grad1_med"))


def broken(real, fault):
    """The cell's driver with one piece of the mathematics wrong in the
    timed path (the scratch script that read the limits on the chip
    planted the same three)."""
    class Broken(real):
        def build(self, weights):
            from mxnet_tpu.ops import contrib, registry
            if fault == "mask_plain_causal":
                whole = contrib._flash_attention_op

                def causal(q, k, v, block_diffusion=None, **kw):
                    return whole(q, k, v, causal=True, **kw)

                self._undo = lambda: setattr(contrib, "_flash_attention_op",
                                             whole)
                contrib._flash_attention_op = causal
            elif fault in ("weights_m_not_m_over_p", "objective_halved"):
                # m in place of m / p; or the mean taken over the 2 L
                # rows of the stack in place of the L positions
                op = registry.get_op("_contrib_block_diffusion_noise")
                whole = op.fn

                def reweighed(*a, **kw):
                    noised, weight = whole(*a, **kw)
                    if fault == "objective_halved":
                        return noised, 0.5 * weight
                    return noised, (weight > 0).astype(weight.dtype)

                self._undo = lambda: setattr(op, "fn", whole)
                op.fn = reweighed
            elif fault != "qk_norm_left_out":
                raise ValueError(fault)
            super().build(weights)

        def _block(self, mx, weights):
            net = super()._block(mx, weights)
            if fault == "qk_norm_left_out":
                # the gains stay leaves of the block; no head is normed
                net._config["qk_norm"] = False
            return net

        def free(self):
            getattr(self, "_undo", lambda: None)()
            super().free()
    return Broken


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(pb, good, fault):
    cell = pb.bench.cell(CELL)
    bad = _rehearsal(pb, 2 ** 31 + 38,
                     driver_cls=broken(cell.driver().Driver, fault))
    assert bad["correct"] is False, bad["compared"]
    assert sum(v > lim for v, lim in bad["compared"].values()) >= 2, \
        bad["compared"]


def test_the_driver_places_ids_and_draws_made_from_the_ids_alone(pb):
    """``place`` hands the block ONE int32 array ``[ids ; position draws
    ; block draws]`` and the clean ids as the label; the draws are the
    reference's for those ids, whatever the traffic's labels say."""
    import numpy as np
    cell = pb.bench.cell(CELL)
    cfg = cell.config_for(rehearse=True)
    driver = cell.driver().Driver(cfg, None, rehearse=True)
    seen = []
    driver._jax = argparse.Namespace(device_put=lambda a, _s: seen.append(a)
                                     or a)
    driver._batch_ns = None
    feed = pb.traffic.Feed(cell.traffic, cfg, 5)
    ids, labels = feed._draw()
    packed, label = driver.place((ids, labels))
    assert packed.dtype == np.int32 and packed.shape == (2, 3, 128)
    assert (packed[:, 0] == ids).all() and (label == ids).all()
    u, t = cell.reference().draws(ids, cfg["block_length"])
    assert (packed[:, 1] == u).all()
    assert (packed[:, 2, ::4] == t).all() and (packed[:, 2, 1::4]
                                               == t).all()
    again, _ = driver.place((ids, labels * 0))
    assert (again == packed).all()


# ---------------------------------------------------------------------------
# counts, by hand
# ---------------------------------------------------------------------------
def _published(pb, **sizes):
    cfg = pb.bench.cell(CELL).config_for()
    cfg.update(sizes)
    return cfg


def test_a_held_layer_is_94_638_336_parameters(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # q, out 2 x 2048 x 4096; k, v 2 x 2048 x 512; the two QK gains
    assert counts.attention_parameters(cfg) == 18_874_368 + 256
    # gate, up, down 3 x 2048 x 768
    assert counts.expert_parameters(cfg) == 4_718_592
    # + two gains + the router over all 128 + 16 held experts
    assert counts.layer_parameters(cfg) == 18_874_624 + 4_096 + 262_144 \
        + 16 * 4_718_592 == 94_638_336
    assert counts.layer_parameters(cfg, experts=128) == 623_120_640


def test_the_cut_model_and_the_published_30_5_b(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    n = cfg["num_hidden_layers"]
    # the layers + embedding and head 2 x 18992 x 2048 + the final gain
    assert counts.parameters(cfg) == n * 94_638_336 + 77_791_232 + 2048
    assert counts.parameters(dict(cfg, num_hidden_layers=4)) == 456_346_624
    assert counts.parameters(dict(cfg, num_hidden_layers=6)) == 645_623_296
    assert counts.published_parameters(cfg) == 48 * 623_120_640 \
        + 2 * 151936 * 2048 + 2048 == 30_532_122_624 \
        == cfg["published"]["parameters"]
    assert counts.sweep_bytes(cfg, 1) == 7 * 4 * counts.parameters(cfg)
    ref = pb.bench.cell(CELL).reference()
    import numpy as np
    assert sum(int(np.prod(s)) for _n, s, _i in ref.leaf_specs(cfg)) \
        == counts.parameters(cfg)


def test_a_layer_sees_16_793_600_pairs_of_67_108_864(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # L^2 + L B: 4096 x 4 noised-noised, 4096 x 4092 / 2 noised-clean,
    # 4096 x 4100 / 2 clean-clean
    assert counts.attention_pairs(cfg) == 16_384 + 8_380_416 + 8_396_800 \
        == 4096 * 4096 + 4096 * 4 == 16_793_600
    assert 4 * counts.attention_pairs(cfg) // (8192 * 8192) == 1
    n = cfg["num_hidden_layers"]
    # scores and values, 32 heads of 128
    assert counts.attention_macs_forward(cfg) == 2 * n * 16_793_600 * 4096
    assert counts.attention_flops(cfg) == 6 * counts.attention_macs_forward(
        cfg)
    # blocks of 8: L more pairs in each diagonal part
    assert counts.attention_pairs(dict(cfg, block_length=8)) \
        == 4096 * 4096 + 4096 * 8


def test_expert_flops_count_the_ordinary_rows_384_an_expert(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    n = cfg["num_hidden_layers"]
    assert counts.rows_per_step(cfg) == 8192 \
        and counts.tokens_per_step(cfg) == 4096
    # half the positions (and eps more) are masked, and every masked row
    # takes the MASK token's eight experts, none of them held here: the
    # clean copy and the unmasked noised rows x 8 slots x 16 of 128
    assert counts.masked_share(cfg) == pytest.approx(0.5005)
    rows = (8192 - 4096 * 0.5005) * 8 * 16 / 128
    assert counts.expected_expert_rows(cfg) == pytest.approx(rows) \
        == pytest.approx(6141.952)
    # a symmetric router over all 8192 rows would send 512 an expert
    assert 8192 * 8 * 16 / 128 == 16 * 512
    assert counts.expert_flops(cfg) == pytest.approx(
        3 * 2 * 3 * 2048 * 768 * rows * n)
    per_row = n * (18_874_368 + 262_144)
    assert counts.matmul_macs_per_row(cfg) == per_row
    assert counts.head_macs_per_token(cfg) == 18992 * 2048
    assert counts.step_flops(cfg) == pytest.approx(6 * (
        8192 * per_row + 4096 * 18992 * 2048) + counts.expert_flops(cfg)
        + counts.attention_flops(cfg))
    # attention is about two fifths of the step
    assert 0.35 < counts.attention_flops(cfg) / counts.step_flops(cfg) < 0.42
    more = _published(pb, num_experts=32)
    assert counts.expert_flops(more) == pytest.approx(
        2 * counts.expert_flops(cfg))
    assert counts.attention_flops(more) == counts.attention_flops(cfg)


def test_the_mask_tokens_experts_are_held_as_the_configuration_says(pb):
    """``deployment.mask_experts_held`` is the one place the decision
    stands: the reference seats the router's rows by it and the counts
    count by it.  The cell's chip holds none; the group's mean chip,
    which holds one, sees the symmetric router's 512 rows an expert."""
    import numpy as np
    cell = pb.bench.cell(CELL)
    counts, ref = cell.counts(), cell.reference()
    assert cell.config["deployment"]["mask_experts_held"] == 0
    one = _published(pb)
    one["deployment"] = dict(one["deployment"], mask_experts_held=1)
    assert counts.expected_expert_rows(one) == pytest.approx(8192)
    cfg = cell.config_for(rehearse=True)
    first, end = cfg["deployment"]["experts_held"]
    for held in (0, 1, 2):
        cfg["deployment"] = dict(cfg["deployment"], mask_experts_held=held)
        w = ref.init_weights(cfg, 7)
        unit = w["embed_weight"][cfg["mask_token_id"]]
        for i in range(cfg["num_hidden_layers"]):
            top = np.argsort(-(w["l%d_router_weight" % i] @ unit))[
                :cfg["num_experts_per_tok"]]
            assert sum(first <= e < end for e in top) == held
    cfg["deployment"] = dict(cfg["deployment"], mask_experts_held=3)
    with pytest.raises(ValueError, match="mask_experts_held"):
        ref.init_weights(cfg, 7)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def diffusion_ctx(pb):
    """A traced window made up over the REAL optimized module of a small
    block-diffusion ``MoELM`` step (compiled here, on the CPU, through
    ParallelTrainer): every instruction of the module runs once a step
    for 1 us."""
    import jax
    import numpy as np
    from mxnet_tpu import nd, telemetry
    from mxnet_tpu.gluon.contrib.transformer import FULL, MoELM
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh
    from mxnet_tpu.telemetry import phases
    import mxnet_tpu as mx
    net = MoELM(64, units=32, expert_width=16, layer_types=[FULL, FULL],
                num_heads=4, num_kv_heads=2, num_routed=4, held=(0, 2),
                top_k=2, qk_norm=True, block_length=4, mask_token_id=63)
    net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    trainer = ParallelTrainer(
        net, net.diffusion_loss(), "adam", {"learning_rate": 1e-3},
        mesh=make_mesh(dp=1, devices=jax.devices()[:1]), zero=2,
        dtype="bfloat16")
    telemetry.enable()
    try:
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 64, (2, 16))
        trainer.step(nd.array(ids, dtype="int32"),
                     nd.array(ids, dtype="int32"))
        text = telemetry.program_hlo("step")
        read = {"block": telemetry.gauge(
                    "mxnet_diffusion_block_length").labels().value,
                "stack": telemetry.gauge(
                    "mxnet_diffusion_stack_rows").labels().value,
                "rows": telemetry.gauge(
                    "mxnet_moe_expected_rows").labels().value,
                "held": telemetry.gauge("mxnet_moe_experts").labels(
                    which="held").value}
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


@pytest.mark.parametrize("name", NEW_READERS + SHARED_READERS)
def test_readers_read_the_block_diffusion_module(pb, diffusion_ctx, name):
    value = pb.bench.metric_reader(name).read(diffusion_ctx)
    assert value is not None and value > 0
    read = {n: pb.bench.metric_reader(n).read(diffusion_ctx)
            for n in NEW_READERS + SHARED_READERS + ("fwd_ms", "bwd_ms")}
    # attention, the noising and the expert part are shares of forward +
    # backward, and lie beside each other
    assert read["attn_blockdiff_ms"] + read["noise_ms"] + read["moe_ms"] \
        <= read["fwd_ms"] + read["bwd_ms"]
    assert read["noise_ms"] < read["attn_blockdiff_ms"]
    # this block's attention runs under no other cell's scope: their
    # readers find nothing, or no time
    for other in ("attn_full_ms", "attn_window_ms", "attn_latent_ms"):
        assert not pb.bench.metric_reader(other).read(diffusion_ctx)
    from mxnet_tpu.telemetry import phases
    parts = phases.instruction_diffusion_parts(
        diffusion_ctx["program_hlo"][0])
    for part, metric in (("attn_blockdiff", "attn_blockdiff_ms"),
                         ("noise", "noise_ms")):
        mine = [n for n, (p, _re) in parts.items() if p == part]
        assert read[metric] == pytest.approx(1e-3 * len(mine))
    # every layer is a jax.checkpoint: its attention runs again
    assert any(p == "attn_blockdiff" and again
               for p, again in parts.values())


def test_the_block_says_what_it_is_in_gauges(diffusion_ctx):
    # 2 sequences x 16 tokens: 64 rows through the stack, and 64 x 2
    # slots x 2 of 4 experts held
    assert diffusion_ctx["gauges"] == {"block": 4, "stack": 64,
                                       "rows": 64.0, "held": 2}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_nothing_without_the_scopes(pb, name):
    reader = pb.bench.metric_reader(name)
    assert reader.read({}) is None
    # the optimized module of cell 1 (recorded on the chip): phases, but
    # no attention and no noising
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
    import diffusion_reduce
    real = pb.phase_reduce.program
    pb.phase_reduce.program = lambda: argparse.Namespace()
    try:
        assert diffusion_reduce.parts(dict(ctx)) is None
    finally:
        pb.phase_reduce.program = real


def test_the_maps_classes_name_the_scopes():
    from mxnet_tpu.telemetry import phases
    part = phases.diffusion_part_of
    assert part("jit(step)/jvp(mx_fwd)/mx_attn_blockdiff/rsqrt") \
        == ("attn_blockdiff", False)
    assert part("jit(step)/transpose(jvp(mx_fwd))/rematted_computation/"
                "mx_attn_blockdiff/_flash_fwd_kernel") \
        == ("attn_blockdiff", True)
    assert part("jit(step)/jvp(mx_fwd)/mx_noise/gather") == ("noise", False)
    assert part("jit(step)/jvp(mx_fwd)/mx_attn_full/cos") == (None, False)
    assert part("") == (None, False)
    # the older maps do not take the new scopes for theirs
    assert phases.block_part_of(
        "jit(step)/jvp(mx_fwd)/mx_attn_blockdiff/cos") == (None, False)


# ---------------------------------------------------------------------------
# the manifest: rules on ``pb.bench`` (any checkout's), which an addition
# keeps (tests/perfbench/test_manifest_addition.py runs them over one)
# ---------------------------------------------------------------------------
def _in_order(part, whole):
    """Every name of ``part`` is in ``whole``, in ``part``'s order."""
    rest = iter(whole)
    return all(name in rest for name in part)


@pytest.mark.parametrize("name", REPORTED)
def test_the_manifest_lists_the_sdar_cell_under_what_it_reports(pb, name):
    """The cell is IN the ``workloads`` of each metric it reports, and
    its readers hold these, in this order, among whatever a later PR
    lists it under."""
    specs = {s["name"]: s for s in pb.bench.manifest["per_layer"]}
    assert CELL in specs[name]["workloads"]
    assert specs[name]["moves"] == ("setup_s" if name.startswith("setup_")
                                    else "train_step_ms")
    if name in NEW_READERS:
        assert specs[name]["source"] == "device_trace"
    names = [s["name"] for s in pb.bench.cell(CELL).per_layer_metrics()]
    assert _in_order(REPORTED, names), names
    # its attention runs under its own scope, so the causal cells'
    # attention readers do not list it
    for other in ("attn_full_ms", "attn_window_ms", "attn_latent_ms"):
        assert CELL not in specs[other]["workloads"]


def test_the_manifest_keeps_the_sdar_configuration_as_it_was_cut(pb):
    cell = pb.bench.cell(CELL)
    entry = pb.bench.config_entry(cell.config_name)
    cfg = cell.config
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    assert cell.chips == 1 and cell.traffic["inputs"]["seq_len"] == 4096
    assert cell.traffic_name == "packed-tokens-4096"
    # inside the floors: four layers or more of the one kind, eight
    # experts or more, an eighth of the vocabulary; no width moved
    pub = cfg["published"]
    assert 4 <= cfg["num_hidden_layers"] <= 6 \
        and pub["num_hidden_layers"] == 48
    assert cfg["num_experts"] == 16 >= 8 and pub["num_experts"] == 128
    assert cfg["vocab_size"] == 18992 == pub["vocab_size"] // 8
    assert cfg["deployment"]["experts_held"] == [0, 16]
    assert cfg["deployment"]["vocab_rows_held"] == [0, 18992]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["rope_theta"], cfg["intermediate_size"]) \
        == (2048, 32, 4, 128, 768, 8, 1000000, 6144)
    # what the config.json does not give is assumed, each with its reason
    assert cfg["block_length"] == 4 and cfg["noise_eps"] == 0.001 \
        and cfg["qk_norm"] is True
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1 \
        and pub["mask_token_id"] == 151669
    for key in ("block_length", "noise_schedule", "noise_eps",
                "mask_token_id", "mask_experts", "qk_norm", "prompt"):
        assert key in cfg["assumed"], key
    assert len(cfg["departures"]) >= 2
