"""PR 7 — bucketed overlapped collectives, ZeRO-sharded optimizer
state, compressed bucket reductions (docs/faq/parallel.md).

Runs on the 8-device virtual CPU mesh (conftest).  Coverage:

- bucket-plan construction (reverse order, caps, first-bucket, padding)
- the ring wire model (``comm_stats``) and the >= 1.8x grad-reduction
  acceptance bar
- zero=1/2 numerics vs the zero=0 oracle, compression vs uncompressed
- measured optimizer-state residency ~ 1/mesh (slots AND residuals)
- mesh-independent checkpoints: bit-identical restore onto a DIFFERENT
  fsdp width / zero stage, trajectory continuation, manager round-trip
- error-feedback convergence for every codec
- recompile guard: step count stays flat across bucketing/compression
  configs; collective telemetry counters advance by the wire model
"""
import glob
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gradient_compression import GradientCompression, make_codec
from mxnet_tpu.parallel.collectives import (build_bucket_plan, comm_stats,
                                            flatten_bucket, unflatten_bucket)


# -- bucket planning ---------------------------------------------------------

def test_bucket_plan_reverse_order_and_caps():
    names = ["a", "b", "c", "d"]
    shapes = [(64,), (64,), (64,), (64,)]  # 256 B each
    plan = build_bucket_plan(names, shapes, bucket_bytes=512,
                             first_bucket_bytes=256)
    # reverse registration order: output-side params first
    assert plan[0].names == ["d"]          # first bucket capped at 256 B
    assert plan[1].names == ["c", "b"]     # then 512 B buckets
    assert plan[2].names == ["a"]
    assert [b.index for b in plan] == [0, 1, 2]


def test_bucket_plan_monolithic_fallback():
    plan = build_bucket_plan(["a", "b"], [(8,), (4,)], bucket_bytes=0)
    assert len(plan) == 1
    assert plan[0].names == ["b", "a"]
    assert plan[0].n == 12


def test_bucket_padding_divides_mesh():
    plan = build_bucket_plan(["a"], [(13,)], bucket_bytes=1 << 20,
                             pad_multiple=8)
    (b,) = plan
    assert b.n == 13 and b.padded_n == 16
    vals = [jnp.arange(13, dtype=jnp.float32)]
    flat = flatten_bucket(vals, b)
    assert flat.shape == (16,)
    back = unflatten_bucket(flat, b)
    assert np.array_equal(np.asarray(back["a"]), np.arange(13))


def test_bucket_plan_oversized_param_gets_own_bucket():
    plan = build_bucket_plan(["big", "small"], [(1024,), (4,)],
                             bucket_bytes=256)
    assert [b.names for b in plan] == [["small"], ["big"]]


# -- the wire model ----------------------------------------------------------

def test_comm_stats_ring_math():
    plan = build_bucket_plan(["a"], [(1024,)], bucket_bytes=1 << 20,
                             pad_multiple=8)
    # zero=0: all-reduce, 2 * B * (n-1)/n
    s0 = comm_stats(plan, 8, 0)
    assert s0["kinds"]["all_reduce"]["ops"] == 1
    assert s0["grad_reduce_bytes"] == 2 * 4096 * 7 // 8
    # zero=2: reduce-scatter B*(n-1)/n + param all-gather
    s2 = comm_stats(plan, 8, 2)
    assert s2["kinds"]["reduce_scatter"]["bytes"] == 4096 * 7 // 8
    assert s2["kinds"]["all_gather"]["bytes"] == 4096 * 7 // 8
    # the acceptance bar: monolithic all-reduce vs reduce-scatter path
    assert s0["grad_reduce_bytes"] / s2["grad_reduce_bytes"] == 2.0
    # single device: silence
    assert comm_stats(plan, 1, 2)["total_bytes"] == 0


def test_comm_stats_codec_payload():
    plan = build_bucket_plan(["a"], [(1024,)], bucket_bytes=1 << 20,
                             pad_multiple=8)
    full = comm_stats(plan, 8, 2)["grad_reduce_bytes"]
    bf16 = comm_stats(plan, 8, 2,
                      codec=make_codec("bf16"))["grad_reduce_bytes"]
    two = comm_stats(plan, 8, 2,
                     codec=make_codec("2bit"))["grad_reduce_bytes"]
    assert bf16 * 2 == full
    assert two == full // 16


# -- codecs ------------------------------------------------------------------

def test_codec_registry_and_errors():
    assert make_codec(None) is None
    assert make_codec("none") is None
    assert make_codec("2bit", threshold=0.25).threshold == 0.25
    assert make_codec("bf16").wire_bytes(8) == 16
    with pytest.raises(mx.MXNetError):
        make_codec("lz4")


def test_codec_error_feedback_is_unbiased():
    # decode(encode(g + r)) + r' == g + r exactly (the residual carries
    # ALL quantization error forward) for every codec
    rng = np.random.RandomState(3)
    g = jnp.asarray(rng.randn(64).astype(np.float32) * 0.3)
    for name in ("2bit", "bf16", "fp8"):
        try:
            codec = make_codec(name)
        except mx.MXNetError:
            pytest.skip("fp8 dtype unavailable")
        r = jnp.zeros_like(g)
        decoded, new_r = codec.roundtrip(g, r)
        np.testing.assert_allclose(np.asarray(decoded + new_r),
                                   np.asarray(g + r), rtol=1e-6,
                                   atol=1e-7)


def test_kvstore_front_matches_codec():
    # the eager GradientCompression front and the raw codec are the
    # same kernels (one numeric contract across call sites)
    rng = np.random.RandomState(5)
    g = rng.randn(32).astype(np.float32)
    gc = GradientCompression(type="2bit", threshold=0.5)
    codec = make_codec("2bit", threshold=0.5)
    out_front = np.asarray(gc.compress_decompress("k", jnp.asarray(g)))
    decoded, _ = codec.roundtrip(jnp.asarray(g), jnp.zeros(32, jnp.float32))
    np.testing.assert_array_equal(out_front, np.asarray(decoded))


# -- trainer numerics --------------------------------------------------------

def _make_net(seed=42, hidden=16, classes=8):
    # dims divisible by fsdp widths used below; deterministic values so
    # separately-constructed instances start identical
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, in_units=12, activation="relu"),
            nn.Dense(classes, in_units=hidden))
    net.initialize(mx.init.Zero())
    r = np.random.RandomState(seed)
    for _, p in sorted(net.collect_params().items()):
        p.set_data(nd.array((r.randn(*p.shape) * 0.2).astype(np.float32)))
    return net


def _data(batch=16, classes=8):
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(batch, 12).astype(np.float32))
    y = nd.array(rng.randint(0, classes, batch).astype(np.float32))
    return x, y


def _train(trainer, steps=4):
    x, y = _data()
    losses = []
    for _ in range(steps):
        losses.append(float(trainer.step(x, y).asnumpy()))
    return losses


def _params_np(trainer):
    return {n: np.asarray(jax.device_get(v))
            for n, v in trainer.params.items()}


def _trainer(net, zero=0, compression=None, mesh=None, optimizer="adam",
             bucket_bytes=256):
    # tiny bucket caps so the plan has SEVERAL buckets even on this net
    # (the env default FIRST_BYTES of 1 MiB would swallow it whole)
    return parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer,
        {"learning_rate": 0.05}, mesh=mesh or parallel.make_mesh(),
        zero=zero, compression=compression, bucket_bytes=bucket_bytes,
        first_bucket_bytes=min(bucket_bytes, 128) or None)


@pytest.mark.parametrize("zero", [1, 2])
def test_zero_stages_match_replicated_oracle(zero):
    net = _make_net()
    base = _trainer(net, zero=0)
    l0 = _train(base)
    zt = _trainer(net, zero=zero)
    lz = _train(zt)
    np.testing.assert_allclose(lz, l0, rtol=2e-5, atol=1e-6)
    pa, pb = _params_np(base), _params_np(zt)
    for n in pa:
        np.testing.assert_allclose(pb[n], pa[n], rtol=2e-5, atol=1e-6,
                                    err_msg=n)
    assert len(zt.bucket_plan) >= 2  # the cap actually split the params


def test_zero2_state_and_bytes_contract():
    net = _make_net()
    z0 = _trainer(net, zero=0)
    z2 = _trainer(net, zero=2, compression="2bit")
    # >= 1.8x grad-reduction cut (ring model; exactly 2.0 uncompressed)
    cut = (z0.comm_stats()["grad_reduce_bytes"]
           / _trainer(net, zero=2).comm_stats()["grad_reduce_bytes"])
    assert cut >= 1.8
    # slots AND residuals resident ~1/mesh per chip
    _train(z2, steps=2)
    sb = z2.optimizer_state_bytes()
    ratio = sb["per_device"] / sb["total"]
    assert ratio <= 1.5 / 8, (sb, ratio)


@pytest.mark.parametrize("codec", ["2bit", "bf16"])
def test_compression_error_feedback_converges(codec):
    # linear regression: compressed training must reach the same loss
    # neighborhood as uncompressed — error feedback makes the quantized
    # stream unbiased over time
    rng = np.random.RandomState(0)
    X = rng.randn(64, 4).astype(np.float32)
    w_true = np.array([[1.0], [-2.0], [3.0], [0.5]], np.float32)
    Y = (X @ w_true).astype(np.float32)

    def run(compression):
        net = nn.Dense(1, in_units=4, use_bias=False)
        net.initialize(mx.init.Zero())
        net.weight.set_data(nd.array(np.full((1, 4), 0.1, np.float32)))
        tr = parallel.ParallelTrainer(
            net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.2},
            mesh=parallel.make_mesh(), zero=2, compression=compression)
        loss = None
        for _ in range(200):
            loss = float(tr.step(nd.array(X), nd.array(Y)).asnumpy())
        return loss

    ref = run(None)
    got = run(codec)
    assert ref < 1e-3, ref
    # bf16 is near-exact; 2bit converges via residual feedback
    assert got < (5e-3 if codec == "2bit" else 1e-3), (codec, got, ref)


# -- mesh-independent checkpoints -------------------------------------------

def test_resume_across_fsdp_width_and_zero_stage(tmp_path):
    # train on dp=8/zero=2, snapshot, restore onto dp=2 x fsdp=4 /
    # zero=1: restored values BIT-identical, trajectories then match
    net = _make_net()
    a = _trainer(net, zero=2, optimizer="adam")
    _train(a, steps=3)
    sd = a.state_dict()

    wide = parallel.make_mesh(dp=2, fsdp=4)
    b = _trainer(net, zero=1, mesh=wide, optimizer="adam")
    b.load_state_dict(sd)
    # bit-identical restore (placement changed, values must not)
    pb = _params_np(b)
    for n, v in sd["params"].items():
        np.testing.assert_array_equal(pb[n], v, err_msg=n)
    sd_b = b.state_dict()
    for slot, per_param in sd["slots"].items():
        for n, v in per_param.items():
            np.testing.assert_array_equal(sd_b["slots"][slot][n], v,
                                          err_msg="%s/%s" % (slot, n))
    for s, v in sd["scalars"].items():
        np.testing.assert_array_equal(sd_b["scalars"][s], v, err_msg=s)
    # continuation: both trainers step on, trajectories agree (fsdp
    # resharding changes collective placement, not numerics)
    la = _train(a, steps=2)
    lb = _train(b, steps=2)
    np.testing.assert_allclose(lb, la, rtol=5e-5, atol=1e-6)


def test_resume_preserves_compression_residuals(tmp_path):
    net = _make_net()
    a = _trainer(net, zero=2, compression="2bit", optimizer="sgd")
    _train(a, steps=3)
    sd = a.state_dict()
    assert sd["residuals"] and sd["meta"]["codec"] == "2bit"
    assert any(np.abs(v).max() > 0 for v in sd["residuals"].values()), \
        "after 3 steps the 2bit residuals should be non-zero"
    b = _trainer(net, zero=2, compression="2bit", optimizer="sgd")
    b.load_state_dict(sd)
    la = _train(a, steps=2)
    lb = _train(b, steps=2)
    # same mesh + same codec: identical programs on identical state
    np.testing.assert_allclose(lb, la, rtol=1e-6, atol=1e-7)


def test_checkpoint_manager_roundtrip(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager, ParallelTrainerState
    net = _make_net()
    a = _trainer(net, zero=2, compression="bf16")
    _train(a, steps=2)
    mgr = CheckpointManager(directory=str(tmp_path))
    assert a.save_checkpoint(mgr, step=7, block=True)
    # restore onto a DIFFERENT layout through the PR 5 store machinery
    b = _trainer(net, zero=0, compression="bf16",
                 mesh=parallel.make_mesh(dp=4, fsdp=2))
    got = b.restore_checkpoint(str(tmp_path))
    assert got == 7
    pa, pb = _params_np(a), _params_np(b)
    for n in pa:
        np.testing.assert_array_equal(pb[n], pa[n], err_msg=n)
    # wrong-kind payloads are skipped, not crashed on
    st = ParallelTrainerState.restore_latest(mgr.store, b, step=None)
    assert st == 7


def test_load_state_dict_rejects_mismatches():
    net = _make_net()
    a = _trainer(net, zero=2)
    sd = a.state_dict()
    bad = {**sd, "params": {k: v for i, (k, v)
                            in enumerate(sd["params"].items()) if i}}
    with pytest.raises(mx.MXNetError):
        a.load_state_dict(bad)
    sgd = _trainer(net, zero=2, optimizer="sgd",
                   compression=None)
    with pytest.raises(mx.MXNetError):
        sgd.load_state_dict(sd)  # adam slots into sgd trainer


# -- recompile guard + telemetry ---------------------------------------------

def test_recompile_guard_and_collective_counters():
    """One program per trainer configuration: steps after the first
    never grow jax's compile count, whatever the bucketing/compression
    config; and the collective counters advance by exactly the wire
    model each step."""
    telemetry.enable()
    try:
        net = _make_net()
        before = telemetry.scalar_totals().get(
            "mxnet_collective_bytes_total", 0)
        configs = [dict(zero=0), dict(zero=2),
                   dict(zero=2, compression="2bit"),
                   dict(zero=2, compression="bf16", bucket_bytes=0)]
        for cfg in configs:
            tr = _trainer(net, **cfg)
            x, y = _data()
            tr.step(x, y)               # compile + warm
            jit = tr._jit_step
            n0 = jit._cache_size()
            for _ in range(3):
                tr.step(x, y)
            assert jit._cache_size() == n0, \
                "steady-state recompile under %r" % (cfg,)
        after = telemetry.scalar_totals().get(
            "mxnet_collective_bytes_total", 0)
        # every config stepped 4x; zero=0 on a pure-dp mesh still
        # all-reduces, so bytes strictly accumulate
        expected = sum(4 * _trainer(net, **cfg).comm_stats()["total_bytes"]
                       for cfg in configs)
        assert after - before == expected, (after - before, expected)
        snap = telemetry.snapshot()
        kinds = {v["labels"].get("kind")
                 for v in snap["mxnet_collective_ops_total"]["values"]}
        assert {"all_reduce", "reduce_scatter", "all_gather"} <= kinds
    finally:
        telemetry.disable()


def test_step_logger_carries_collective_column(tmp_path):
    from mxnet_tpu.telemetry.step_logger import _DELTA_METRICS
    assert "mxnet_collective_bytes_total" in _DELTA_METRICS
    assert "mxnet_collective_ops_total" in _DELTA_METRICS


# -- one-sweep fused optimizer (PR 12, MXNET_PALLAS_FUSED_OPT) ---------------

def _slots_np(trainer):
    sd = trainer.state_dict()
    return {(s, k): np.asarray(v) for s in sorted(sd["slots"])
            for k, v in sorted(sd["slots"][s].items())}


@pytest.mark.parametrize("zero", [0, 1, 2])
@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_trainer_fused_sweep_matches_treemap(zero, optimizer, monkeypatch):
    """End-to-end trainer: the Pallas one-sweep update vs the per-array
    tree_map oracle, zero ∈ {0, 1, 2}.

    Tolerance note: the UPDATE itself is bit-identical on identical
    inputs — tests/test_pallas.py asserts exact equality including
    these ZeRO layouts and over multi-step sequences.  Here the two
    runs are differently-composed WHOLE-STEP XLA CPU programs, whose
    FMA-contraction choices (e.g. around `momentum*m - lr*g` or the
    backward's reductions) legitimately differ by 1-3 ulps per step
    (measured; docs/faq/perf.md) — so end-to-end asserts a 1e-6
    absolute band, not bits."""
    def run(knob, steps):
        monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", knob)
        tr = _trainer(_make_net(), zero=zero, optimizer=optimizer)
        losses = _train(tr, steps=steps)
        return tr, losses
    tf, lf = run("1", 4)
    tu, lu = run("0", 4)
    np.testing.assert_allclose(lf, lu, rtol=0, atol=1e-5)
    # separately-built nets get fresh gluon name suffixes; sorted
    # order still pairs the same parameters
    for (n, a), (_, b) in zip(sorted(_params_np(tf).items()),
                              sorted(_params_np(tu).items())):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6,
                                   err_msg="%s/%s/%s" % (zero, optimizer, n))
    for (k, a), (_, b) in zip(sorted(_slots_np(tf).items()),
                              sorted(_slots_np(tu).items())):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6,
                                   err_msg="%s/%s/%s" % (zero, optimizer, k))


def test_trainer_fused_sweep_checkpoint_cycle_bit_identical(monkeypatch):
    """ACCEPTANCE: fused sweep + checkpoint save/restore cycle is
    bit-identical to the uninterrupted fused run — the bucket-major
    slot layout survives the per-param slicing of state_dict and the
    re-flattening of load_state_dict exactly."""
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")
    net = _make_net()          # ONE net: checkpoint restore pairs by name
    oracle = _trainer(net, zero=2, optimizer="sgd")
    _train(oracle, steps=4)

    first = _trainer(net, zero=2, optimizer="sgd")
    _train(first, steps=2)
    snap = first.state_dict()
    resumed = _trainer(net, zero=2, optimizer="sgd")
    resumed.load_state_dict(snap)
    _train(resumed, steps=2)

    for (n, a), (_, b) in zip(sorted(_params_np(oracle).items()),
                              sorted(_params_np(resumed).items())):
        assert np.array_equal(a, b), n
    for (k, a), (_, b) in zip(sorted(_slots_np(oracle).items()),
                              sorted(_slots_np(resumed).items())):
        assert np.array_equal(a, b), k


def test_trainer_fused_sweep_plan_predictions_stay_exact(monkeypatch):
    """graftplan closed loop with the fused sweep ON: bucket-major slot
    layout is unchanged, so predicted optimizer-state bytes (and comm)
    must still equal the measured values byte-for-byte."""
    from mxnet_tpu.analysis.plan import (PlanSpec, predict_comm,
                                         predict_opt_state)
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")
    for zero in (1, 2):
        tr = _trainer(_make_net(), zero=zero)
        spec = PlanSpec.from_trainer(tr)
        assert spec.optimizer.get("fused_sweep") is True
        assert predict_opt_state(spec) == tr.optimizer_state_bytes()
        assert predict_comm(spec) == tr.comm_stats()


# -- native buckets (PR 30): a one-leaf bucket keeps its leaf's layout -------

class _Mixed(gluon.HybridBlock):
    """Eligible matrices beside every kind of leaf that must stay flat:
    a conv weight, a ``rows % 8`` leaf, a ``C % 128`` leaf, a ``[1, C]``
    gate, biases — and a head that is eligible on one device only."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.conv = nn.Conv2D(2, 3, padding=1, in_channels=1)
            self.d1 = nn.Dense(128, in_units=256, activation="relu")
            self.d2 = nn.Dense(100, in_units=128, activation="relu")
            self.d3 = nn.Dense(128, in_units=100, activation="relu")
            self.d4 = nn.Dense(256, in_units=128, activation="relu")
            self.gate = nn.Dense(1, in_units=256)
            self.head = nn.Dense(8, in_units=256)

    def hybrid_forward(self, F, x):
        h = self.d4(self.d3(self.d2(self.d1(self.conv(x)))))
        return F.broadcast_mul(self.head(h), F.sigmoid(self.gate(h)))


# leaf (by the suffix of its name) -> layout on a mesh of 1 and of 4
_MIXED_LAYOUTS = {
    "conv0_weight": ("flat", "flat"),       # [2, 1, 3, 3]: C = 3
    "dense0_weight": ("native", "native"),  # [128, 256]
    "dense1_weight": ("flat", "flat"),      # [100, 128]: rows % 8
    "dense2_weight": ("flat", "flat"),      # [128, 100]: C % 128
    "dense3_weight": ("native", "native"),  # [256, 128]
    "dense4_weight": ("flat", "flat"),      # [1, 256]: one row
    "dense5_weight": ("native", "flat"),    # [8, 256]: rows % (8 * 4)
}


def _mixed_net(seed=7):
    net = _Mixed(prefix="mixed_")
    net.initialize(mx.init.Zero())
    r = np.random.RandomState(seed)
    for _, p in sorted(net.collect_params().items()):
        p.set_data(nd.array((r.randn(*p.shape) * 0.1).astype(np.float32)))
    return net


def _mixed_trainer(net, ndev=1, zero=2, optimizer="adam", **kw):
    opt = {"learning_rate": 0.05, "wd": 1e-3}
    if optimizer == "sgd":
        opt["momentum"] = 0.9
    kw.setdefault("bucket_bytes", 1024)
    kw.setdefault("first_bucket_bytes", 512)
    return parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer, opt,
        mesh=parallel.make_mesh(dp=ndev, devices=jax.devices()[:ndev]),
        zero=zero, **kw)


def _mixed_train(trainer, steps=3):
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(16, 1, 8, 16).astype(np.float32))
    y = nd.array(rng.randint(0, 8, 16).astype(np.float32))
    return [float(trainer.step(x, y).asnumpy()) for _ in range(steps)]


def _all_flat(monkeypatch):
    """The same plan with no native bucket: what the parent builds."""
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "sweep_native_rows",
                        lambda shape, shards=1: None)


@pytest.mark.parametrize("shape,shards,rows_c", [
    ((2048, 2048), 1, (2048, 2048)),
    ((50272, 2048), 1, (50272, 2048)),      # the blocks need not divide it
    ((2048, 8192), 4, (2048, 8192)),
    ((4, 16, 256), 1, (64, 256)),           # leading dimensions collapse
    ((1, 2048), 1, None),                   # a gate: one row
    ((1000, 2048), 4, None),                # rows % (8 * mesh)
    ((1000, 2048), 1, (1000, 2048)),
    ((128, 100), 1, None),                  # C % 128
    ((64, 3, 3, 3), 1, None),               # a conv weight
    ((2048,), 1, None),                     # 1-D
    ((8, 32768), 1, None),                  # eight rows over a grid step
])
def test_sweep_native_rows(shape, shards, rows_c):
    from mxnet_tpu.ops.pallas_kernels import sweep_native_rows
    assert sweep_native_rows(shape, shards=shards) == rows_c
    (b,) = build_bucket_plan(["w"], [shape], 1 << 20, pad_multiple=shards,
                             native=True)
    assert b.layout == ("native" if rows_c else "flat")
    assert b.buffer_shape == (rows_c or (b.padded_n,))
    assert b.n == int(np.prod(shape)) and b.offsets == [0, b.n]
    # the default plan (the executor's, a codec's) is flat whatever fits
    (f,) = build_bucket_plan(["w"], [shape], 1 << 20, pad_multiple=shards)
    assert f.layout == "flat" and f.buffer_shape == (f.padded_n,)
    w = jnp.arange(b.n, dtype=jnp.float32).reshape(shape)
    buf = flatten_bucket([w], b)
    assert buf.shape == b.buffer_shape
    assert np.array_equal(np.asarray(unflatten_bucket(buf, b)["w"]),
                          np.asarray(w))
    assert np.array_equal(np.asarray(buf).reshape(-1)[:b.n],
                          np.asarray(flatten_bucket([w], f))[:b.n])


def test_a_grouped_bucket_is_never_native():
    plan = build_bucket_plan(["a", "b"], [(8, 128), (8, 128)], 1 << 20,
                             native=True)
    assert [b.layout for b in plan] == ["flat"]


@pytest.mark.parametrize("ndev", [1, 4])
def test_native_plan_keeps_membership_and_comm(ndev, monkeypatch):
    """Which leaves ride which bucket, the order, the indices, the
    number of collectives and their bytes: the parent's."""
    net = _mixed_net()
    tr = _mixed_trainer(net, ndev)
    layouts = {n: b.layout for b in tr.bucket_plan for n in b.names}
    for suffix, want in _MIXED_LAYOUTS.items():
        assert layouts["mixed_" + suffix] == want[ndev == 4], suffix
    assert all(layouts[n] == "flat" for n in layouts if n.endswith("bias"))
    _all_flat(monkeypatch)
    flat = _mixed_trainer(net, ndev)
    assert {b.layout for b in flat.bucket_plan} == {"flat"}
    keys = ("index", "names", "shapes", "sizes", "offsets", "n", "padded_n")
    for a, b in zip(tr.bucket_plan, flat.bucket_plan):
        da, db = a.to_dict(), b.to_dict()
        assert [da[k] for k in keys] == [db[k] for k in keys]
    assert tr.comm_stats() == flat.comm_stats()
    assert tr.optimizer_state_bytes() == flat.optimizer_state_bytes()


@pytest.mark.parametrize("zero", [1, 2])
@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("optimizer", ["adam", "sgd"])
def test_native_step_is_bit_identical(optimizer, ndev, zero, monkeypatch):
    """ACCEPTANCE: the step over native buckets equals, bit for bit over
    several steps, the sweep over the same plan with every bucket flat —
    params, slots and losses — and the ``tree_map`` oracle on the same
    plan within the band two differently composed whole-step CPU
    programs keep (``test_trainer_fused_sweep_matches_treemap``'s note;
    the UPDATE against the oracle is held to the bit in
    tests/test_pallas.py, native layouts and ZeRO shardings included)."""
    net = _mixed_net()      # ONE net: the three trainers pair by name

    def run(knob):
        monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", knob)
        tr = _mixed_trainer(net, ndev, zero, optimizer)
        return tr, _mixed_train(tr), _params_np(tr), _slots_np(tr)

    tr, losses, params, slots = run("1")
    assert "native" in {b.layout for b in tr.bucket_plan}
    fused = tr.opt_state["fused"]["mean" if optimizer == "adam" else "mom"]
    for b in tr.bucket_plan:
        buf = fused["b%d" % b.index]
        assert buf.shape == b.buffer_shape
        assert buf.sharding.shard_shape(buf.shape)[0] * ndev == buf.shape[0]
    _, oracle_losses, oracle_params, oracle_slots = run("0")
    _all_flat(monkeypatch)
    _, flat_losses, flat_params, flat_slots = run("1")
    assert losses == flat_losses
    np.testing.assert_allclose(losses, oracle_losses, rtol=0, atol=1e-5)
    for n, v in params.items():
        assert np.array_equal(v, flat_params[n]), n
        np.testing.assert_allclose(v, oracle_params[n], rtol=0, atol=1e-6,
                                   err_msg=n)
    for k, v in slots.items():
        assert np.array_equal(v, flat_slots[k]), k
        np.testing.assert_allclose(v, oracle_slots[k], rtol=0, atol=1e-6,
                                   err_msg=str(k))


@pytest.mark.parametrize("codec", ["2bit", "bf16"])
def test_a_codec_keeps_the_flat_plan_and_program(codec, monkeypatch):
    """The codecs' wire format is defined on the flat buffer: with
    ``compression=`` no bucket is native, and the program handed to the
    compiler is the one built with no native bucket to be had."""
    net = _mixed_net()

    def lowered():
        tr = _mixed_trainer(net, 4, 2, compression=codec)
        assert {b.layout for b in tr.bucket_plan} == {"flat"}
        assert all(r.ndim == 1 for r in tr._resids)
        jit_fn, args = tr.step_callable((16, 1, 8, 16))
        with parallel.mesh.mesh_scope(tr.mesh):
            return jit_fn.lower(*args).as_text()

    here = lowered()
    _all_flat(monkeypatch)
    assert lowered() == here


@pytest.mark.parametrize("ndev", [1, 4])
def test_zero_bucket_gauges(ndev):
    telemetry.enable()
    try:
        tr = _mixed_trainer(_mixed_net(), ndev)
        snap = telemetry.snapshot()
    finally:
        telemetry.disable()

    def read(metric):
        return {v["labels"]["layout"]: v["value"]
                for v in snap[metric]["values"]}

    native = [n for n, want in _MIXED_LAYOUTS.items()
              if want[ndev == 4] == "native"]
    sizes = {n.split("mixed_", 1)[1]: int(np.prod(v.shape))
             for n, v in tr.params.items()}
    leaves, nbytes = read("mxnet_zero_bucket_leaves"), \
        read("mxnet_zero_bucket_bytes")
    assert leaves == {"native": len(native),
                      "flat": len(sizes) - len(native)}
    assert nbytes["native"] == 4 * sum(sizes[n] for n in native)
    assert nbytes["native"] + nbytes["flat"] == 4 * sum(sizes.values())


def _assert_same_state(sd, other):
    for n, v in sd["params"].items():
        np.testing.assert_array_equal(other["params"][n], v, err_msg=n)
    for slot, per_param in sd["slots"].items():
        for n, v in per_param.items():
            np.testing.assert_array_equal(other["slots"][slot][n], v,
                                          err_msg="%s/%s" % (slot, n))
    for s, v in sd["scalars"].items():
        np.testing.assert_array_equal(other["scalars"][s], v, err_msg=s)


@pytest.mark.parametrize("src,dst", [
    ((1, 1024), (1, 0)),        # native -> one flat bucket, and back
    ((1, 0), (1, 1024)),
    ((1, 1024), (4, 1024)),     # across mesh sizes: the head turns flat
    ((4, 1024), (1, 1024)),
    ((4, 1024), (4, 0)),
])
def test_state_round_trips_between_native_and_flat_plans(src, dst):
    """``state_dict`` is per parameter and knows no layout: a native
    trainer restores into a flat plan, another mesh, and back, bit for
    bit, and steps on from there as the source does."""
    net = _mixed_net()
    a = _mixed_trainer(net, src[0], bucket_bytes=src[1],
                       first_bucket_bytes=src[1] and 512)
    _mixed_train(a, steps=2)
    sd = a.state_dict()
    b = _mixed_trainer(net, dst[0], bucket_bytes=dst[1],
                       first_bucket_bytes=dst[1] and 512)
    b.load_state_dict(sd)
    _assert_same_state(sd, b.state_dict())
    for bk in b.bucket_plan:
        for slot in ("mean", "var"):
            buf = b.opt_state["fused"][slot]["b%d" % bk.index]
            assert buf.shape == bk.buffer_shape
            assert buf.sharding.shard_shape(buf.shape)[0] * dst[0] \
                == buf.shape[0]
    back = _mixed_trainer(net, src[0], bucket_bytes=src[1],
                          first_bucket_bytes=src[1] and 512)
    back.load_state_dict(b.state_dict())
    _assert_same_state(sd, back.state_dict())
    assert _mixed_train(back, steps=2) == _mixed_train(a, steps=2)
    _assert_same_state(a.state_dict(), back.state_dict())


def test_a_snapshot_in_the_parents_format_loads():
    """A snapshot as the parent wrote it — plain per-parameter arrays,
    nothing about buckets — lands in native buffers."""
    net = _mixed_net()
    tr = _mixed_trainer(net, 4)
    r = np.random.RandomState(3)
    shapes = {n: v.shape for n, v in tr.params.items()}
    draw = lambda: {n: r.randn(*s).astype(np.float32)
                    for n, s in shapes.items()}
    sd = {"params": draw(),
          "slots": {"mean": draw(),
                    "var": {n: np.abs(v) for n, v in draw().items()}},
          "scalars": {"t": np.asarray(5, np.int32)},
          "residuals": {},
          "meta": {"zero": 2, "codec": None, "optimizer": "PureAdam"}}
    tr.load_state_dict(sd)
    _assert_same_state(sd, tr.state_dict())
    for b in tr.bucket_plan:
        if b.layout == "native":
            buf = tr.opt_state["fused"]["var"]["b%d" % b.index]
            assert buf.shape == b.buffer_shape and buf.ndim == 2
            np.testing.assert_array_equal(
                np.asarray(buf), sd["slots"]["var"][b.names[0]])
    assert np.isfinite(_mixed_train(tr, steps=1)[0])


@pytest.mark.parametrize("zero", [1, 2])
@pytest.mark.parametrize("ndev", [1, 4])
def test_native_plan_predictions_stay_exact(ndev, zero, monkeypatch):
    """graftplan over a plan with native buckets: the predicted
    optimizer-state bytes and collectives equal the measured ones byte
    for byte, the spec says which bucket keeps its layout, and the
    divisibility contract holds (and catches a native bucket whose rows
    the mesh cannot cut into whole tiles)."""
    from mxnet_tpu.analysis.plan import (PlanSpec, predict_comm,
                                         predict_opt_state)
    from mxnet_tpu.analysis.plan.contracts import check_divisibility
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")
    tr = _mixed_trainer(_mixed_net(), ndev, zero)
    spec = PlanSpec.from_trainer(tr)
    assert predict_opt_state(spec) == tr.optimizer_state_bytes()
    assert predict_comm(spec) == tr.comm_stats()
    assert [b["layout"] for b in spec.buckets] \
        == [b.layout for b in tr.bucket_plan]
    assert [tuple(b["buffer_shape"]) for b in spec.buckets] \
        == [b.buffer_shape for b in tr.bucket_plan]
    assert check_divisibility(spec) == []
    native = next(b for b in spec.buckets if b["layout"] == "native")
    native["buffer_shape"] = [native["buffer_shape"][0] + 4,
                              native["buffer_shape"][1]]
    (problem,) = check_divisibility(spec)
    assert "native bucket %d" % native["index"] in problem["detail"]
