"""The latent-attention LM's cell, ``joyai-flash-train-s8192`` (CPU,
quick, nothing at import time that touches jax or libtpu):

- at its ``rehearse`` sizes the cell runs through ``run.py`` and reads
  ``correct`` true; the fp8 control and five broken timed paths (the
  score scale taken over the 128 un-rotated dimensions, the selection
  bias added to the weights, the factor 2.5 dropped, the shared expert
  left out, the prediction module held to the next token instead of the
  one after it) read false — under limits read AT those sizes
  (``REHEARSAL_LIMITS``), not under the chip's;
- the chip's limits (``limits/<cell>.json``) each lie between the two
  readings they were set from;
- ``counts/latent_moe_lm.py`` against hand-worked values at the
  published widths;
- the three readers this cell brought (``attn_latent_ms``,
  ``shared_expert_ms``, ``mtp_ms``) on the optimized module of a small
  ``LatentMoELM`` step, and on a module without the scopes; no new
  scope's name holds an older one;
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
CELL = "joyai-flash-train-s8192"
NEW_READERS = ("attn_latent_ms", "shared_expert_ms", "mtp_ms")
# what the cell's traced line carries, in the manifest's order: the seven
# shared metrics, the five phase readers, the five of the expert cell's
# that this program's scopes feed, its own three
REPORTED = ("step_mfu", "device_idle_share", "hbm_peak_share", "dispatch_ms",
            "compiles_in_window", "sweep_roofline", "flash_roofline",
            "fwd_ms", "bwd_ms", "update_ms", "phase_unattributed_share",
            "step_host_ms", "moe_ms", "moe_route_ms", "expert_roofline",
            "attn_full_ms", "block_recompute_ms") + NEW_READERS
FAULTS = ("scale_over_the_nope_dimensions", "bias_added_to_the_weights",
          "scaling_factor_dropped", "shared_expert_left_out",
          "mtp_target_shifted_by_one")
# The rehearsal's own limits.  ``limits/<cell>.json`` holds what the chip
# read at the timed size; the 128-wide rehearsal routes 256 tokens to 4
# held experts of 8 under a selection bias of 0.1 (so that the bias fault
# shows at four experts), a handful of bf16 routing flips move a whole
# leaf, and its worst-leaf numbers read higher.  Read here on the CPU
# (program largest over 8 seeds / fp8 control smallest over 3 / smallest
# reading of a fault over 2 seeds):
#   grad1_med  8.0e-5 / 9.0e-4 / -       the control fails by it (11 x)
#   grad1_top  2.2e-4 / 2.1e-3 / -       and by this one (9.5 x)
#   dparam_med 8.3e-5 / 7.2e-4 / -       and by this one (8.7 x)
#   grad1      0.0061 / 0.017 / 0.052    four faults fail by it: the bias
#              in the weights 0.052, the factor dropped 0.60, the shared
#              expert left out 1.0, the target shifted 0.19 (the scale
#              over 128 dimensions reads 0.0055 here and fails by dparam)
#   dparam     0.0027 / 0.0034 / 0.0129  every fault fails by it: the
#              bias 0.0129, the scale 0.049, the target 0.065, the factor
#              0.17, the shared expert 1.0
# dparam_top (program up to 1.1e-4, control from 4.3e-4) separates less
# there and is read only.
REHEARSAL_LIMITS = {"grad1_med": 0.0003, "grad1_top": 0.0007,
                    "dparam_med": 0.00025, "grad1": 0.02, "dparam": 0.008}


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
    return _rehearsal(pb, 2 ** 31 + 34)


def test_the_cell_at_its_rehearsal_sizes_is_correct(good):
    assert good["correct"] and good["attempted"] > 0, good["compared"]
    assert set(good["metrics"]) == {"train_step_ms", "setup_s"}
    assert good["device"]["platform"] == "cpu"
    assert all(v <= lim for v, lim in good["compared"].values())


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12])
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
    # the held selection bias: no gradient, no change
    held = [k for k in want["grad1"] if k.endswith("router_bias")]
    assert len(held) == 3 and all(
        want["grad1"][k] == 0.0 == want["dparam"][k] for k in held)
    control = ref.train_steps(cfg, w, batches, precision="fp8")
    verdict = check.judge(control, want, cell.limits())
    assert not verdict["correct"], verdict
    failed = {k for k, row in verdict["compared"].items() if not row["ok"]}
    assert {"grad1_med", "grad1_top"} <= failed, verdict


def test_each_limit_of_the_chip_lies_between_its_two_readings(pb):
    """``limits/<cell>.json`` is the timed size's: every limit stands
    over the program's largest reading and under the fp8 control's
    smallest — the medians and top-quarter numbers with at least twice
    of room on either side, the worst-leaf change (which separates
    program and control by 2.5 times only, and which two faults need)
    with at least one and a half — and never with less room above the
    program's reading than below the control's.  The worst-leaf gradient
    has no room on both sides and is read, not compared."""
    limits = pb.bench.cell(CELL).limits()
    assert set(limits["limits"]) == set(limits["readings"]) == {
        "grad1_med", "grad1_top", "dparam", "dparam_med", "dparam_top"}
    assert set(limits["read_only"]) == {"grad1"}
    for name, limit in limits["limits"].items():
        low = limits["readings"][name]["program_largest"]
        high = limits["readings"][name]["control_smallest"]
        room = 1.5 if name == "dparam" else 2.0
        assert room * low <= limit <= high / room, name
        assert limit / low >= high / limit, name
    # and every planted fault read over at least one of them
    assert set(limits["faults"]) == set(FAULTS)
    for fault in FAULTS:
        over = [name for name, limit in limits["limits"].items()
                if limits["faults"][fault][name] > limit]
        assert over, fault


def broken(real, fault):
    """The cell's driver with one piece of the mathematics wrong in the
    timed path (the scratch script that read the limits on the chip
    planted the same five)."""
    class Broken(real):
        def build(self, weights):
            from mxnet_tpu.gluon import loss as gloss
            from mxnet_tpu.gluon.contrib import transformer
            from mxnet_tpu.ops import contrib as ops_contrib
            from mxnet_tpu.parallel import moe
            self.config = copy.deepcopy(self.config)
            self._undo = []

            def plant(owner, name, value):
                self._undo.append((owner, name, owner.__dict__[name]))
                setattr(owner, name, value)

            if fault == "scale_over_the_nope_dimensions":
                nope = int(self.config["qk_nope_head_dim"])
                whole = ops_contrib._flash_attention_op

                def scaled(q, k, v, **kw):
                    return whole(q, k, v, scale=nope ** -0.5, **kw)

                plant(ops_contrib, "_flash_attention_op", scaled)
            elif fault == "bias_added_to_the_weights":
                import jax
                import jax.numpy as jnp
                from jax import lax

                def biased(x, router_w, top_k, norm_topk=True,
                           scoring="softmax", bias=None, scale=1.0):
                    logits = jnp.einsum(
                        "tu,eu->te", x, router_w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
                    weights, experts = lax.top_k(
                        jax.nn.sigmoid(logits) + bias.astype(jnp.float32),
                        top_k)
                    weights = weights / jnp.sum(weights, -1, keepdims=True)
                    return weights * scale, experts

                plant(moe, "_route_top_k", biased)
            elif fault == "scaling_factor_dropped":
                self.config["routed_scaling_factor"] = 1.0
            elif fault == "shared_expert_left_out":
                whole = transformer.latent_moe_lm_forward

                def without(params, tokens, **kw):
                    return whole(params, tokens,
                                 **dict(kw, shared_expert=False))

                plant(transformer, "latent_moe_lm_forward", without)
            elif fault == "mtp_target_shifted_by_one":
                right = gloss.MultiTokenCELoss._target

                def early(F, label, k):
                    return label if k == 1 else right(F, label, k - 1)

                plant(gloss.MultiTokenCELoss, "_target", staticmethod(early))
            else:
                raise ValueError(fault)
            super().build(weights)

        def free(self):
            for owner, name, value in self._undo:
                setattr(owner, name, value)
            super().free()
    return Broken


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(pb, good, fault):
    cell = pb.bench.cell(CELL)
    bad = _rehearsal(pb, 2 ** 31 + 34,
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


def test_a_layers_latent_attention_is_26_347_520_parameters(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # q_a 2048 x 1536, its gain, q_b 1536 x 32 x 192, kv_a 2048 x 576,
    # its gain, kv_b 512 x 32 x 256, out 4096 x 2048
    assert counts.attention_parameters(cfg) == 3_145_728 + 1_536 \
        + 9_437_184 + 1_179_648 + 512 + 4_194_304 + 8_388_608 == 26_347_520
    assert counts.attention_matrices(cfg) == 26_347_520 - 2_048
    # + two gains + SwiGLU 3 x 2048 x 7168
    assert counts.dense_layer_parameters(cfg) == 26_347_520 + 4_096 \
        + 44_040_192 == 70_391_808
    # + two gains + router 256 x 2048 + shared 3 x 2048 x 768 + 16 held
    assert counts.expert_parameters(cfg) == 4_718_592
    assert counts.sparse_layer_parameters(cfg) == 26_347_520 + 4_096 \
        + 524_288 + 4_718_592 + 16 * 4_718_592 == 107_091_968
    assert counts.sparse_layer_parameters(cfg, experts=256) == 1_239_554_048
    # eh_proj 4096 x 2048 + three gains + a sparse layer
    assert counts.mtp_parameters(cfg) == 8_388_608 + 6_144 + 107_091_968 \
        == 115_486_720
    assert counts.mtp_parameters(cfg, experts=256) == 1_247_948_800


def test_the_cut_model_is_680_m_and_the_published_one_48_9_b(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    assert counts.parameters(cfg) == 70_391_808 + 4 * 107_091_968 + 2_048 \
        + 115_486_720 + 2 * 16160 * 2048 == 680_439_808
    assert counts.published_parameters(cfg) == 70_391_808 \
        + 39 * 1_239_554_048 + 2 * 129280 * 2048 + 2_048 \
        == 48_942_532_608 == cfg["published"]["parameters"]
    assert counts.published_parameters(cfg, with_mtp=True) \
        == 50_190_481_408 == cfg["published"]["parameters_with_mtp_module"]
    assert counts.sweep_bytes(cfg, 1) == 7 * 4 * 680_439_808
    ref = pb.bench.cell(CELL).reference()
    import numpy as np
    sizes = {n: int(np.prod(s)) for n, s, _i in ref.leaf_specs(cfg)}
    trained = set(ref.trainable(cfg))
    assert sum(v for n, v in sizes.items() if n in trained) == 680_439_808
    # five sparse layers' selection bias, 256 values each: held, not swept
    assert sum(v for n, v in sizes.items() if n not in trained) == 5 * 256


def test_attention_flops_score_over_192_and_sum_values_of_128(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # five trunk layers and the module's; 8192^2 / 2 pairs, 32 heads
    assert counts.attention_layers(cfg) == 6
    assert counts.attention_macs_forward(cfg) \
        == 6 * (8192 * 8192 // 2) * 32 * (128 + 64 + 128) \
        == 2_061_584_302_080
    assert counts.attention_flops(cfg) == 6 * 2_061_584_302_080
    # a value as wide as the key would cost a fifth more
    same = _published(pb, v_head_dim=192)
    assert counts.attention_flops(same) * 320 \
        == counts.attention_flops(cfg) * 384


def test_expert_flops_count_the_rows_routed_and_a_step_is_27_8_tflop(pb):
    counts = pb.bench.cell(CELL).counts()
    cfg = _published(pb)
    # 8192 tokens x 8 slots x 16 of 256 experts: 256 rows an expert
    assert counts.expected_expert_rows(cfg) == 4096 == 16 * 256
    assert counts.sparse_layers(cfg) == 5
    assert counts.expert_flops(cfg) == 3 * 2 * 3 * 2048 * 768 * 4096 * 5 \
        == 579_820_584_960
    macs = 6 * 26_345_472 + 44_040_192 + 5 * (524_288 + 4_718_592) \
        + 2 * 2048 * 2048 + 2 * 16160 * 2048
    assert counts.matmul_macs_per_token(cfg) == macs == 302_907_392
    assert counts.step_flops(cfg) == 6 * 8192 * macs \
        + counts.expert_flops(cfg) + counts.attention_flops(cfg)
    assert round(counts.step_flops(cfg) / 1e12, 1) == 27.8
    # attention is 44 % of the step's work, the routed products 2 %
    assert round(100 * counts.attention_flops(cfg)
                 / counts.step_flops(cfg)) == 44
    assert round(100 * counts.expert_flops(cfg)
                 / counts.step_flops(cfg)) == 2
    # the fallback's eight held experts: half the routed work
    fewer = _published(pb, n_routed_experts=8)
    assert counts.expert_flops(fewer) * 2 == counts.expert_flops(cfg)
    assert counts.parameters(fewer) == 491_696_128


# ---------------------------------------------------------------------------
# the three readers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def latent_ctx(pb):
    """A traced window made up over the REAL optimized module of a small
    ``LatentMoELM`` step (compiled here, on the CPU, through
    ParallelTrainer): every instruction of the module runs once a step
    for 1 us."""
    import jax
    import numpy as np
    from mxnet_tpu import nd, telemetry
    from mxnet_tpu.gluon.contrib.transformer import LatentMoELM
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh
    from mxnet_tpu.telemetry import phases
    import mxnet_tpu as mx
    net = LatentMoELM(64, units=32, dense_width=48, expert_width=16,
                      mlp_layer_types=("dense", "sparse"), num_heads=4,
                      q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4,
                      v_dim=8, num_routed=4, held=(0, 2), top_k=2,
                      route_scale=2.5, mtp_depth=1)
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


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_the_latent_module(pb, latent_ctx, name):
    value = pb.bench.metric_reader(name).read(latent_ctx)
    assert value is not None and value > 0
    read = {n: pb.bench.metric_reader(n).read(latent_ctx)
            for n in NEW_READERS + ("fwd_ms", "bwd_ms", "moe_ms",
                                    "attn_full_ms", "block_recompute_ms")}
    # the expert cell's readers find this program's scopes as they stand
    assert all(read[n] > 0 for n in ("moe_ms", "attn_full_ms",
                                     "block_recompute_ms"))
    # parts beside parts: none counts an instruction of another
    assert read["attn_latent_ms"] + read["shared_expert_ms"] \
        + read["moe_ms"] + read["attn_full_ms"] \
        <= read["fwd_ms"] + read["bwd_ms"]
    from mxnet_tpu.telemetry import phases
    found = phases.instruction_latent_parts(latent_ctx["program_hlo"][0])
    for reader, want in (("attn_latent_ms", "attn_latent"),
                         ("shared_expert_ms", "shared_expert")):
        n = sum(part == want for part, _in_mtp in found.values())
        assert read[reader] == pytest.approx(1e-3 * n)
    in_mtp = [k for k, (_part, m) in found.items() if m]
    assert read["mtp_ms"] == pytest.approx(1e-3 * len(in_mtp))
    # the module crosses the parts: its layer's latent attention and
    # shared expert carry their own scopes inside it, as do (through the
    # expert cell's map) its flash call and its routed experts
    assert {found[k][0] for k in in_mtp} == {None, "attn_latent",
                                             "shared_expert"}
    block = phases.instruction_block_parts(latent_ctx["program_hlo"][0])
    assert {"attn_full", "route", "experts"} \
        <= {block[k][0] for k in in_mtp}
    # one sparse layer in the trunk and one in the module: each part of
    # the module is a share of the whole part, not all of it
    assert 0 < sum(found[k][0] == "shared_expert" for k in in_mtp) \
        < 1e3 * read["shared_expert_ms"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_nothing_without_the_scopes(pb, name):
    reader = pb.bench.metric_reader(name)
    assert reader.read({}) is None
    # the optimized module of cell 1 (recorded on the chip): phases, but
    # no latent attention, shared expert or prediction module
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
           "counts": argparse.Namespace(),
           "trace": {"busy_s": 1e-3, "ops_by_device": {0: ops},
                     "modules_by_device": {0: [(module + "(1)", 0,
                                                10 ** 9)]}}}
    assert reader.read(ctx) is None
    assert pb.bench.metric_reader("fwd_ms").read(dict(ctx)) is not None
    # a program from before the scopes: no module text at all
    assert reader.read(dict(ctx, program_hlo=[])) is None
    # a program without the map (the parent of the PR that added it)
    import latent_reduce
    real = pb.phase_reduce.program
    pb.phase_reduce.program = lambda: argparse.Namespace()
    try:
        assert latent_reduce.parts(dict(ctx)) is None
        assert reader.read(dict(ctx)) is None
    finally:
        pb.phase_reduce.program = real


def test_no_new_scope_name_holds_an_old_one():
    """The maps match by SUBSTRING (``MOE_SCOPE in op_name``): a shared
    expert named ``mx_moe_shared`` would be counted as routing.  No scope
    this block added holds the name of one that was there, or the other
    way round, and the older maps see nothing in them."""
    from mxnet_tpu.telemetry import phases
    new = (phases.ATTN_LATENT_SCOPE, phases.SHARED_EXPERT_SCOPE,
           phases.MTP_SCOPE)
    old = (phases.FWD_SCOPE, phases.LOSS_SCOPE, phases.UPDATE_SCOPE,
           phases.CODEC_SCOPE, phases.COLLECTIVE_PREFIX, phases.LOOP_SCOPE,
           phases.EXIT_SCOPE, phases.MOE_SCOPE, phases.MOE_EXPERTS_SCOPE,
           phases.ATTN_WINDOW_SCOPE, phases.ATTN_FULL_SCOPE)
    for a in new:
        for b in old + tuple(n for n in new if n != a):
            assert a not in b and b not in a, (a, b)
        name = "jit(step)/%s/dot" % a
        assert phases.block_part_of(name) == (None, False)
        assert phases.loop_part_of(name) == (None, False)
        assert phases.phase_of(name) == phases.OTHER
    part = phases.latent_part_of
    assert part("jit(step)/jvp(mx_fwd)/mx_attn_latent/dot") \
        == ("attn_latent", False)
    assert part("jit(step)/transpose(jvp(mx_fwd))/mx_mtp/"
                "rematted_computation/mx_shared_expert/dot") \
        == ("shared_expert", True)
    assert part("jit(step)/jvp(mx_fwd)/mx_mtp/mx_moe/sort") == (None, True)
    assert part("jit(step)/jvp(mx_fwd)/mx_attn_full/cos") == (None, False)
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
def test_the_manifest_lists_the_joyai_cell_under_what_it_reports(pb, name):
    """The cell is IN the ``workloads`` of each metric it reports, and
    its readers hold these, in this order, among whatever a later PR
    lists it under."""
    specs = {s["name"]: s for s in pb.bench.manifest["per_layer"]}
    assert CELL in specs[name]["workloads"]
    assert specs[name]["moves"] == "train_step_ms"
    if name in NEW_READERS:
        assert specs[name]["source"] == "device_trace"
        assert specs[name]["unit"] == "ms"
    names = [s["name"] for s in pb.bench.cell(CELL).per_layer_metrics()]
    assert _in_order(REPORTED, names), names


def test_the_manifest_keeps_the_joyai_configuration_as_it_was_cut(pb):
    cell = pb.bench.cell(CELL)
    entry = pb.bench.config_entry(cell.config_name)
    cfg = cell.config
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert cell.chips == 1 and cell.traffic_name == "packed-tokens-8192"
    assert cell.traffic["inputs"]["seq_len"] == 8192 == cfg["seq_len"]
    assert cfg["batch_size"] == 1
    # inside the floors: the leading dense layer and four after it (the
    # period is 1), eight experts or more, an eighth of the vocabulary
    pub = cfg["published"]
    assert cfg["num_hidden_layers"] == 5 and pub["num_hidden_layers"] == 40
    assert cfg["first_k_dense_replace"] == 1 and cfg["moe_layer_freq"] == 1
    assert cfg["n_routed_experts"] in (16, 8) \
        and pub["n_routed_experts"] == 256
    assert cfg["deployment"]["experts_held"] == [0, cfg["n_routed_experts"]]
    assert cfg["deployment"]["chips_sharing_a_layer"] \
        == 256 // cfg["n_routed_experts"]
    assert cfg["vocab_size"] == 16160 == pub["vocab_size"] // 8
    assert cfg["deployment"]["vocab_rows_held"] == [0, 16160]
    # no width moved
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["qk_head_dim"], cfg["v_head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["n_shared_experts"], cfg["routed_scaling_factor"],
            cfg["rope_theta"], cfg["num_nextn_predict_layers"]) \
        == (2048, 32, 1536, 512, 128, 64, 192, 128, 7168, 768, 8, 1, 2.5,
            32000000, 1)
    assert cfg["scoring_func"] == "sigmoid" and cfg["rope_interleave"]
    assert cfg["topk_method"] == "noaux_tc" and cfg["norm_topk_prob"]
    for key in ("departures", "assumed", "rehearse"):
        assert cfg[key], key
