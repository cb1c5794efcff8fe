"""Fused-update kvstore + executor fused step + mixed precision.

Reference analogues: tests/python/unittest/test_kvstore.py (updater on
store semantics), test_module.py (fit loop), and the fp16 training mode
(optimizer.py:434 multi-precision) — here the TPU-native bf16 policy.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def _toy_symbol():
    data = mx.sym.var("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                             name="c1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         kernel=(1, 1))
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _toy_data(n=64):
    x = np.random.rand(n, 1, 8, 8).astype(np.float32)
    y = (x.mean(axis=(1, 2, 3)) * 10).astype(np.int32).clip(0, 9)
    return x, y.astype(np.float32)


def test_fused_kvstore_matches_eager_sgd():
    """KVStoreTPU's one-dispatch flush must produce the same weights as
    the eager per-key Updater (same kernels, ops/optimizer_ops.py)."""
    rng = np.random.RandomState(0)
    shapes = [(8, 4), (16,), (3, 5, 2)]
    keys = ["w%d" % i for i in range(len(shapes))]
    init = [rng.randn(*s).astype(np.float32) for s in shapes]
    grads = [[rng.randn(*s).astype(np.float32) for s in shapes]
             for _ in range(4)]

    def run(kv_name):
        kv = mx.kvstore.create(kv_name)
        opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                                  wd=0.01, rescale_grad=1.0 / 8)
        kv.set_optimizer(opt)
        outs = [nd.array(v.copy()) for v in init]
        for k, v in zip(keys, outs):
            kv.init(k, v)
        for step_grads in grads:
            for k, g in zip(keys, step_grads):
                kv.push(k, [nd.array(g)])
            for k, o in zip(keys, outs):
                kv.pull(k, out=[o])
        return [o.asnumpy() for o in outs]

    fused = run("tpu")      # KVStoreTPU: buffered push, fused flush
    eager = run("local")    # eager per-key updater
    for f, e in zip(fused, eager):
        np.testing.assert_allclose(f, e, rtol=2e-5, atol=2e-6)


def test_fused_kvstore_matches_eager_adam():
    rng = np.random.RandomState(1)
    shape = (6, 3)
    init = rng.randn(*shape).astype(np.float32)
    grads = [rng.randn(*shape).astype(np.float32) for _ in range(5)]

    def run(kv_name):
        kv = mx.kvstore.create(kv_name)
        kv.set_optimizer(mx.optimizer.create("adam", learning_rate=0.01,
                                             wd=0.001))
        out = nd.array(init.copy())
        kv.init("w", out)
        for g in grads:
            kv.push("w", [nd.array(g)])
            kv.pull("w", out=[out])
        return out.asnumpy()

    np.testing.assert_allclose(run("tpu"), run("local"), rtol=2e-5, atol=2e-6)


def test_module_fused_step_matches_unfused():
    """kvstore=tpu (fused executor step) and kvstore=local (eager
    updater) must train to the same weights from the same init."""
    sym = _toy_symbol()
    x, y = _toy_data()
    it = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False,
                           label_name="softmax_label")

    def train(kv):
        mx.random.seed(7)
        np.random.seed(7)
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.fit(it, num_epoch=2, kvstore=kv,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.init.Xavier(), force_init=True,
                force_rebind=True)
        args, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in args.items()}

    w_fused = train("tpu")
    w_eager = train("local")
    assert set(w_fused) == set(w_eager)
    for k in w_fused:
        np.testing.assert_allclose(w_fused[k], w_eager[k], rtol=1e-4,
                                   atol=1e-5, err_msg=k)


def test_module_bf16_trains():
    """compute_dtype='bfloat16': fp32 masters, bf16 compute; the toy
    problem must still learn."""
    sym = _toy_symbol()
    x, y = _toy_data()
    it = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=True,
                           label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.cpu(), compute_dtype="bfloat16")
    mod.fit(it, num_epoch=8, kvstore="tpu",
            optimizer_params={"learning_rate": 0.3, "momentum": 0.9},
            initializer=mx.init.Xavier())
    args, _ = mod.get_params()
    for k, v in args.items():
        assert v.dtype == np.float32, "master params must stay fp32 (%s)" % k
    it.reset()
    score = mod.score(it, mx.metric.Accuracy())
    assert score[0][1] > 0.4, score


def test_parallel_trainer_bf16():
    """ParallelTrainer dtype='bfloat16' — loss decreases, masters fp32."""
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, in_channels=3),
            nn.BatchNorm(in_channels=8), nn.Activation("relu"),
            nn.GlobalAvgPool2D(), nn.Flatten(), nn.Dense(4, in_units=8))
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh(dp=8)
    tr = parallel.ParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  "sgd", {"learning_rate": 0.1,
                                          "momentum": 0.9},
                                  mesh=mesh, dtype="bfloat16")
    x = nd.array(np.random.rand(16, 3, 8, 8).astype(np.float32))
    y = nd.array(np.random.randint(0, 4, 16).astype(np.float32))
    losses = [float(tr.step(x, y).asnumpy()) for _ in range(15)]
    assert losses[-1] < losses[0]
    assert all(v.dtype == np.float32 for v in tr.params.values())


def test_module_compression_reaches_fused_step():
    """Module(compression_params=...) must run the codec INSIDE the
    compiled fused step (the reference C-API contract: compression
    follows the module wherever its update runs), matching the eager
    kvstore push path's numerics — the same shared kernels."""
    sym = _toy_symbol()
    x, y = _toy_data()

    def train(kv, comp):
        mx.random.seed(7)
        np.random.seed(7)
        it = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False,
                               label_name="softmax_label")
        mod = mx.mod.Module(sym, context=mx.cpu(),
                            compression_params=comp)
        mod.fit(it, num_epoch=2, kvstore=kv,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.init.Xavier(), force_init=True,
                force_rebind=True)
        exe = mod._exec_group.execs[0]
        args, _ = mod.get_params()
        return ({k: v.asnumpy() for k, v in args.items()},
                getattr(exe, "_fused_codec", None))

    comp = {"type": "bf16"}
    w_fused, codec = train("tpu", comp)
    assert codec is not None and codec.name == "bf16", \
        "compression_params did not reach the compiled step"
    # bf16 here rather than 2bit: compiled vs eager gradient noise
    # (~1e-7) near a 2bit threshold would flip a whole +-t decision;
    # a bf16 cast moves at most one ulp (2^-8 relative), which bounds
    # the tolerance below
    w_eager, _ = train("local", comp)
    for k in w_fused:
        np.testing.assert_allclose(w_fused[k], w_eager[k], rtol=2e-3,
                                   atol=5e-5, err_msg=k)
    # and the codec measurably changes training vs uncompressed
    w_plain, none_codec = train("tpu", None)
    assert none_codec is None
    assert any(np.abs(w_fused[k] - w_plain[k]).max() > 0
               for k in w_fused), "codec installed but inert"


def test_module_2bit_compression_trains():
    """The reference 2bit quantizer inside the fused step: error
    feedback converges on the same well-conditioned regression the
    trainer-level test proves (a multi-class toy with sub-threshold
    gradients can collapse under +-t steps — that is the quantizer's
    nature, not a routing bug)."""
    rng = np.random.RandomState(0)
    X = rng.randn(64, 4).astype(np.float32)
    w_true = np.array([[1.0], [-2.0], [3.0], [0.5]], np.float32)
    Y = (X @ w_true).astype(np.float32)
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=1, no_bias=True,
                               name="fc")
    sym = mx.sym.LinearRegressionOutput(fc, mx.sym.var("lro_label"),
                                        name="lro")
    it = mx.io.NDArrayIter(X, Y, batch_size=64, shuffle=False,
                           label_name="lro_label")
    # the codec sees the PRE-rescale (batch-summed) gradient — the
    # reference kvstore compresses pushes before the optimizer's
    # rescale_grad — so the threshold scales with batch size:
    # 0.5 * 64 here is the trainer-level test's threshold=0.5 dynamics
    mod = mx.mod.Module(sym, context=mx.cpu(), label_names=("lro_label",),
                        compression_params={"type": "2bit",
                                            "threshold": 32.0})
    mod.fit(it, num_epoch=250, kvstore="tpu",
            optimizer_params={"learning_rate": 0.2},
            initializer=mx.init.Zero(), eval_metric="mse")
    exe = mod._exec_group.execs[0]
    assert getattr(exe, "_fused_codec", None) is not None
    assert exe._fused_resids, "error-feedback residuals not carried"
    got = mod.get_params()[0]["fc_weight"].asnumpy().T
    assert np.abs(got - w_true).max() < 0.05, got


def test_accuracy_device_accumulation():
    """Accuracy over NDArrays accumulates lazily on device; get() syncs
    and returns the right value."""
    m = mx.metric.Accuracy()
    pred = nd.array(np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]],
                             dtype=np.float32))
    label = nd.array(np.array([1, 0, 0], dtype=np.float32))
    m.update([label], [pred])
    name, val = m.get()
    assert name == "accuracy"
    assert abs(val - 2.0 / 3.0) < 1e-6
    # numpy inputs still work
    m2 = mx.metric.Accuracy()
    m2.update([np.array([1, 0])], [np.array([[0.1, 0.9], [0.2, 0.8]])])
    assert abs(m2.get()[1] - 0.5) < 1e-6


@pytest.mark.parametrize("opt, params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 0.01}),
    ("adam", {"learning_rate": 0.01, "wd": 0.001}),
])
def test_module_pallas_sweep_matches_per_array(monkeypatch, opt, params):
    """The executor's mixed update (MXNET_PALLAS_FUSED_OPT, default on:
    1-D leaves in flat Pallas buckets, every N-D weight per array in its
    own layout) must train to EXACTLY the all-per-array kernel stream's
    weights — same expressions, same grouping, concatenate/slice is
    value-preserving.  Bucketed leaves group by static (lr_mult,
    wd_mult): biases/betas ride a wd=0 bucket (reference wd_mult
    convention), the BatchNorm gamma one with weight decay."""
    sym = _toy_symbol()
    x, y = _toy_data()

    def train(knob):
        monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", knob)
        mx.random.seed(7)
        np.random.seed(7)
        it = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False,
                               label_name="softmax_label")
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.fit(it, num_epoch=2, kvstore="tpu", optimizer=opt,
                optimizer_params=params,
                initializer=mx.init.Xavier(), force_init=True,
                force_rebind=True)
        exe = mod._exec_group.execs[0]
        args, _ = mod.get_params()
        return ({k: v.asnumpy() for k, v in args.items()},
                getattr(exe, "_sweep", None))

    w_sweep, sweep = train("1")
    w_array, off = train("0")
    assert sweep is not None, "sweep did not engage"
    assert off is None, "knob=0 must fall back to the per-array path"
    assert len(sweep["plan"]) >= 2   # wd_mult split the gamma out
    # selection is by rank alone: no bucket holds an N-D leaf, and every
    # N-D leaf is on the per-array list
    for b, idxs in sweep["plan"]:
        assert all(len(shape) <= 1 for shape in b.shapes), b
        assert len(idxs) == len(b.names)
    bucketed = sorted(n for b, _ in sweep["plan"] for n in b.names)
    assert bucketed == sorted(k for k, v in w_sweep.items() if v.ndim <= 1)
    assert len(sweep["rest"]) == sum(v.ndim > 1 for v in w_sweep.values())
    for k in w_sweep:
        np.testing.assert_array_equal(w_sweep[k], w_array[k],
                                      err_msg="%s/%s" % (opt, k))


def test_sweep_is_none_without_a_1d_leaf(monkeypatch):
    """A module whose every leaf is N-D has nothing to bucket: the plan
    is None, i.e. the all-per-array program."""
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")
    net = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=16,
                                no_bias=True, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, no_bias=True, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.rand(32, 8).astype(np.float32),
                           rng.randint(0, 4, 32).astype(np.float32),
                           batch_size=8, label_name="softmax_label")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    exe = mod._exec_group.execs[0]
    assert mod._fused_exec_update is True
    assert exe._sweep is None
    before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    batch = next(iter(it))
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
    assert len(exe._fused_state) == 2           # one slot tuple a weight
    after = mod.get_params()[0]
    assert all((after[k].asnumpy() != before[k]).any() for k in before)


def test_fused_update_gauges_read_the_leaf_split(monkeypatch):
    """mxnet_fused_update_{leaves,bytes}{path=sweep|per_array} are set
    when the plan is made: the toy module has four 1-D leaves (c1_bias,
    bn1_gamma, bn1_beta, fc_bias: 8 + 8 + 8 + 10 floats) and two N-D
    (c1_weight 8x1x3x3, fc_weight 10x8)."""
    from mxnet_tpu import telemetry
    sym = _toy_symbol()
    x, y = _toy_data(32)

    def install(knob):
        monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", knob)
        it = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False,
                               label_name="softmax_label")
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        leaves = telemetry.gauge("mxnet_fused_update_leaves")
        nbytes = telemetry.gauge("mxnet_fused_update_bytes")
        return {path: (leaves.labels(path=path).value,
                       nbytes.labels(path=path).value)
                for path in ("sweep", "per_array")}

    telemetry.enable()
    try:
        assert install("1") == {"sweep": (4, 4 * 34),
                                "per_array": (2, 4 * (72 + 80))}
        assert install("0") == {"sweep": (0, 0),
                                "per_array": (6, 4 * (34 + 72 + 80))}
    finally:
        telemetry.disable()


def test_fused_sweep_lr_schedule_no_recompile(monkeypatch):
    """ACCEPTANCE: lr/wd ride the sweep kernel's scalar-prefetch
    operand — an lr-schedule change is a new argument VALUE, so the
    fused step's jit cache must not grow across a sweep of lr values
    (mxnet_xla_compiles_total stays flat in steady state)."""
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")
    sym = _toy_symbol()
    x, y = _toy_data(32)
    it = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False,
                           label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    exe = mod._exec_group.execs[0]
    assert getattr(exe, "_sweep", None) is not None
    batch = next(iter(it))
    it.reset()
    # two warm steps: the first dispatch seeds the key from the host
    # chain, the second consumes the device-resident key the step
    # emits — a one-time (pre-existing) retrace unrelated to lr
    for _ in range(2):
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    assert exe._jit_fbu is not None
    before = exe._jit_fbu._cache_size()
    for lr in (0.05, 0.02, 0.01, 0.004):
        mod._optimizer.set_learning_rate(lr)
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    assert exe._jit_fbu._cache_size() == before, \
        "lr change retraced the fused step"


def test_sweep_negative_clip_sentinel_means_disabled(monkeypatch):
    """clip_gradient=-1.0 is the per-array kernels' 'disabled' sentinel
    (_prep_grad gates on clip >= 0) — the sweep plan must normalize it
    to None, not clip every gradient into [1, -1]."""
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")
    sym = _toy_symbol()
    x, y = _toy_data(32)
    it = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False,
                           label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "clip_gradient": -1.0})
    exe = mod._exec_group.execs[0]
    assert exe._sweep is not None
    assert exe._sweep["clip"] is None


@pytest.mark.parametrize("leaf, demotes", [("fc_bias", True),
                                           ("fc_weight", False)])
def test_sweep_demotes_on_runtime_mult_change(monkeypatch, leaf, demotes):
    """set_lr_mult on a BUCKETED leaf AFTER install breaks the
    uniform-bucket contract: the executor must demote to the per-array
    path (both kinds of slot carried over) instead of stepping with a
    stale group lr.  On an N-D weight it breaks nothing — that leaf
    carries its own lr — and the plan stays.  Either way the final
    weights must match a run that was per-array throughout."""
    sym = _toy_symbol()
    x, y = _toy_data(32)

    def train(knob):
        monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", knob)
        mx.random.seed(7)
        np.random.seed(7)
        it = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False,
                               label_name="softmax_label")
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label, force_rebind=True)
        mod.init_params(mx.init.Xavier(), force_init=True)
        mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9},
                           force_init=True)
        batch = next(iter(it))
        for step in range(4):
            if step == 2:
                mod._optimizer.set_lr_mult({leaf: 0.1})
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        exe = mod._exec_group.execs[0]
        args, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in args.items()}, exe

    w_sweep, exe = train("1")
    if demotes:
        assert exe._sweep is None, "mult change must demote the sweep"
        # per-weight slots again, in the weights' order and shapes
        shapes = [exe.arg_dict[exe.arg_names[i]].shape
                  for i in exe._diff_idx]
        assert [st[0].shape for st in exe._fused_state] == shapes
    else:
        assert exe._sweep is not None, "an N-D leaf's mult is its own"
    w_array, _ = train("0")
    for k in w_sweep:
        np.testing.assert_array_equal(w_sweep[k], w_array[k], err_msg=k)


def test_exported_params_survive_further_fused_steps():
    """The fused step DONATES (deletes) the weight buffers it is handed
    and rebinds the aux NDArrays every step.  What get_params /
    export_serving hand out must be snapshots: training that goes on
    after an export may neither free the exported weights ("Array has
    been deleted") nor move the exported BatchNorm statistics."""
    from mxnet_tpu import serving

    rng = np.random.RandomState(0)
    x = rng.rand(32, 8).astype(np.float32)
    y = rng.randint(0, 4, 32).astype(np.float32)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, kvstore="tpu", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert mod._fused_exec_update is True

    arg, aux = mod.get_params()
    kept = {k: v.asnumpy() for k, v in list(arg.items()) + list(aux.items())}
    it.reset()
    want = mod.predict(it).asnumpy()
    srv = serving.ModelServer(max_batch=4)
    mod.export_serving("m", srv)

    it.reset()
    for batch in it:                 # training continues after the export
        mod.forward_backward(batch)
        mod.update()

    for k, v in list(arg.items()) + list(aux.items()):
        assert not v._data.is_deleted(), k
        np.testing.assert_array_equal(v.asnumpy(), kept[k], err_msg=k)
    srv.start()
    try:
        got = np.concatenate([srv.infer("m", {"data": x[i:i + 1]})[0]
                              for i in range(8)])
    finally:
        srv.stop(drain=False)
    np.testing.assert_allclose(got, want[:8], rtol=1e-5, atol=1e-6)
    # and the module itself moved on
    it.reset()
    assert np.abs(mod.predict(it).asnumpy() - want).max() > 1e-4
