"""The looped LM (``gluon.contrib.transformer.LoopedLM``: one stack of
layers applied several times with shared weights, an exit after every
pass) against the plain reference ``perfbench/reference/looped_lm.py``,
at tiny sizes on the CPU, in float32, on seeded weights.

Tolerance 1e-5 relative throughout, and why: program and reference
compute the same float32 mathematics in another order (the program
builds the exit distribution through log-probabilities, folds heads
into the batch for attention, sums a shared weight's four contributions
inside a ``scan``); float32 round-off over a few hundred-term sums at
these sizes is 1e-6, and a wrong pairing of the rotary halves, a norm
left out or a gate read at the wrong exit moves the numbers by 1e-2 and
more.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon.contrib.transformer import (LoopedLM,
                                                 looped_lm_forward)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PB = os.path.join(ROOT, "perfbench")
RTOL = 1e-5

CONFIG = {
    "vocab_size": 96, "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 2, "num_attention_heads": 4, "total_ut_steps": 4,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "exit_entropy_beta": 0.05,
    "init_std": 0.2, "seq_len": 12, "batch_size": 3,
    "optimizer": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8, "wd": 0.0},
}


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, PB)
    try:
        import loader
        yield loader.load_module(os.path.join(PB, "reference",
                                              "looped_lm.py"))
    finally:
        sys.path.remove(PB)


def _close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    gap = float(np.max(np.abs(got - want))) / scale
    assert gap <= rtol, "%s: gap %.3g over %.3g" % (what, gap, rtol)


def _weights(ref, config, seed=7):
    """Seeded weights; gains and the gate's bias moved off 1 and 0 so
    that a norm or a bias left out would show."""
    w = ref.init_weights(config, seed)
    rng = np.random.default_rng(seed)
    for k in w:
        if k.endswith("_gamma"):
            w[k] = (1.0 + 0.3 * rng.standard_normal(w[k].shape)).astype("f")
    w["exit_bias"] = np.array([0.3], "f")
    return w


def _batch(config, seed=3):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, config["vocab_size"],
                       (config["batch_size"], config["seq_len"] + 1))
    return ids[:, :-1].astype("int32"), ids[:, 1:].astype("float32")


def _block(config, weights, **kwargs):
    net = LoopedLM(config["vocab_size"], units=config["hidden_size"],
                   hidden_size=config["intermediate_size"],
                   num_layers=config["num_hidden_layers"],
                   num_heads=config["num_attention_heads"],
                   num_passes=config["total_ut_steps"],
                   epsilon=config["rms_norm_eps"],
                   rope_base=config["rope_theta"], **kwargs)
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    params = net.collect_params()
    assert len(list(params.keys())) == len(weights)
    for p, (name, w) in zip(params.values(), weights.items()):
        assert p.name.endswith(name) and tuple(p.shape) == w.shape
        p.set_data(nd.array(w))
    return net


def _program_grads(net, config, x, y):
    loss_blk = net.exit_loss(beta=config["exit_entropy_beta"])
    with autograd.record():
        loss = loss_blk(*net(nd.array(x, dtype="int32")), nd.array(y)).mean()
    loss.backward()
    short = len(net.prefix)
    return float(loss.asnumpy()), {
        k[short:]: p.grad().asnumpy()
        for k, p in net.collect_params().items()}


# -- block against reference --------------------------------------------------
@pytest.fixture(scope="module")
def both(ref):
    w = _weights(ref, CONFIG)
    x, y = _batch(CONFIG)
    net = _block(CONFIG, w)
    logits, states, gates = net(nd.array(x, dtype="int32"))
    loss, grads = _program_grads(net, CONFIG, x, y)
    jw = {k: jnp.asarray(v) for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        want_exits = ref.exits(jw, jnp.asarray(x), jnp.asarray(y), CONFIG)
        want_loss, want_grads = jax.value_and_grad(ref.loss_fn)(
            jw, jnp.asarray(x), jnp.asarray(y), CONFIG)
    return dict(w=w, x=x, y=y, logits=logits.asnumpy(),
                states=states.asnumpy(), gates=gates.asnumpy(), loss=loss,
                grads=grads, want_exits=want_exits,
                want_loss=float(want_loss), want_grads=want_grads)


@pytest.mark.parametrize("t", range(4))
def test_exit_logits_and_gate_match_the_reference(both, t):
    logits_t = both["states"][:, t] @ both["w"]["head_weight"].T
    want_logits, _ce, want_gate = both["want_exits"][t]
    _close(logits_t, want_logits, "logits of exit %d" % (t + 1))
    _close(both["gates"][:, t], want_gate, "gate of exit %d" % (t + 1))
    if t == 3:      # the block's first output is the LAST exit's logits
        _close(both["logits"], want_logits, "the block's own logits")


def test_loss_matches_the_reference(both):
    _close(both["loss"], both["want_loss"], "loss")


LEAVES = ["embed_weight"] + [
    "l%d_%s" % (i, k) for i in range(CONFIG["num_hidden_layers"])
    for k in ("norm1_gamma", "q_weight", "k_weight", "v_weight",
              "out_weight", "norm2_gamma", "norm3_gamma", "gate_weight",
              "up_weight", "down_weight", "norm4_gamma")] + [
    "norm_gamma", "head_weight", "exit_weight", "exit_bias"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_matches_the_reference(both, leaf):
    assert set(both["grads"]) == set(both["want_grads"])
    _close(both["grads"][leaf], both["want_grads"][leaf],
           "gradient of " + leaf)


def test_blocked_reference_equals_its_whole(ref):
    """``loss_and_grads`` (what the chip run follows, one layer
    application at a time) against ``value_and_grad(loss_fn)``."""
    w = {k: jnp.asarray(v) for k, v in _weights(ref, CONFIG, 9).items()}
    x, y = (jnp.asarray(a) for a in _batch(CONFIG, 4))
    with jax.default_matmul_precision("highest"):
        loss, grads = ref.loss_and_grads(w, x, y, CONFIG)
        want_loss, want = jax.value_and_grad(ref.loss_fn)(w, x, y, CONFIG)
    _close(loss, want_loss, "loss")
    for k in want:
        _close(grads[k], want[k], "gradient of " + k)


# -- the mechanism ------------------------------------------------------------
def test_one_pass_is_a_plain_stack(ref):
    """``num_passes`` 1: the layers once, one exit taken with
    probability 1, so the loss is the plain next-token cross-entropy."""
    cfg = dict(CONFIG, total_ut_steps=1)
    w = _weights(ref, cfg)
    x, y = _batch(cfg)
    net = _block(cfg, w)
    logits = net(nd.array(x, dtype="int32"))[0].asnumpy()
    h = jnp.asarray(w["embed_weight"])[x]
    with jax.default_matmul_precision("highest"):
        for i in range(cfg["num_hidden_layers"]):
            h = ref._layer(h, ref._layer_params(
                {k: jnp.asarray(v) for k, v in w.items()}, i), cfg, False)
        h = ref._rms(h, w["norm_gamma"], cfg["rms_norm_eps"])
        want = np.asarray(h) @ w["head_weight"].T
    _close(logits, want, "logits")
    logp = jax.nn.log_softmax(jnp.asarray(want), -1)
    ce = -np.take_along_axis(np.asarray(logp), y.astype(int)[..., None],
                             -1).mean()
    loss, _grads = _program_grads(net, cfg, x, y)
    _close(loss, ce, "loss")


@pytest.mark.parametrize("leaf", ["l0_q_weight", "l1_down_weight",
                                  "l0_norm2_gamma", "norm_gamma",
                                  "head_weight"])
def test_shared_weights_gradient_is_the_sum_over_passes(ref, both, leaf):
    """An UNSHARED copy — every pass and exit with weights of its own,
    built from the reference's pieces — gives one gradient per pass;
    the shared weight's gradient is their sum."""
    cfg, passes = CONFIG, CONFIG["total_ut_steps"]
    w = {k: jnp.asarray(v) for k, v in both["w"].items()}
    x, y = jnp.asarray(both["x"]), jnp.asarray(both["y"])

    def unshared(copies):
        h = copies[0]["embed_weight"][x]
        ces, gates = [], []
        for p in copies:
            for i in range(cfg["num_hidden_layers"]):
                h = ref._layer(h, ref._layer_params(p, i), cfg, False)
            h, _logits, ce, gate = ref._exit(h, p, y, cfg, False)
            ces.append(ce)
            gates.append(gate)
        return ref._objective(ces, gates, cfg["exit_entropy_beta"])

    with jax.default_matmul_precision("highest"):
        per_pass = jax.grad(unshared)([dict(w) for _ in range(passes)])
    parts = [np.asarray(g[leaf]) for g in per_pass]
    assert all(np.abs(p).max() > 0 for p in parts)   # every pass has a say
    _close(both["grads"][leaf], sum(parts), "summed gradient of " + leaf)


def test_exit_distribution_sums_to_one_and_entropy_has_the_reference_sign(ref):
    rng = np.random.default_rng(0)
    gates = rng.standard_normal((2, 4, 5)).astype("f") * 2
    ce = rng.uniform(1, 3, (2, 4, 5)).astype("f")
    p = np.stack([np.asarray(a) for a in ref.exit_distribution(
        [jnp.asarray(gates[:, t]) for t in range(4)])], 1)
    _close(p.sum(1), np.ones((2, 5)), "sum of the exit distribution")
    want = (p * ce).sum(1)
    # beta 0: the expected cross-entropy under the reference's p
    got0 = nd.contrib.exit_weighted_loss(nd.array(ce), nd.array(gates),
                                         beta=0.0).asnumpy()
    _close(got0, want, "expected loss")
    # the entropy LOWERS the objective (it is subtracted), by beta * H
    entropy = -(p * np.log(p)).sum(1)
    assert (entropy > 0).all()
    got = nd.contrib.exit_weighted_loss(nd.array(ce), nd.array(gates),
                                        beta=0.05).asnumpy()
    _close(got0 - got, 0.05 * entropy, "beta * H")
    ref_obj = ref._objective([jnp.asarray(ce[:, t]) for t in range(4)],
                             [jnp.asarray(gates[:, t]) for t in range(4)],
                             0.05)
    _close(got.mean(), ref_obj, "objective")


def test_gradients_with_and_without_rematerialisation_are_bit_identical(ref):
    w = {k: jnp.asarray(v) for k, v in _weights(ref, CONFIG, 5).items()}
    x, y = (jnp.asarray(a) for a in _batch(CONFIG, 6))
    from mxnet_tpu.ops.contrib import (_exit_weighted_loss,
                                       _linear_cross_entropy)

    def loss(params, remat):
        _logits, states, gates = looped_lm_forward(
            params, x, num_layers=2, num_heads=4, num_passes=4, eps=1e-6,
            rope_base=1e6, remat=remat)
        ce = jnp.stack([_linear_cross_entropy(
            states[:, t], params["head_weight"], y) for t in range(4)], 1)
        return jnp.mean(_exit_weighted_loss(ce, gates, beta=0.05))

    with_remat = jax.jit(jax.grad(lambda p: loss(p, True)))(w)
    without = jax.jit(jax.grad(lambda p: loss(p, False)))(w)
    for k in w:
        assert np.array_equal(np.asarray(with_remat[k]),
                              np.asarray(without[k])), k
    # and the rematerialised forward is in the program, marked as such
    text = jax.jit(jax.grad(lambda p: loss(p, True))).lower(w).compile() \
        .as_text()
    assert "rematted_computation" in text and "mx_loop" in text \
        and "mx_exit" in text


def test_a_rematerialised_pass_runs_its_flash_forward_again(monkeypatch,
                                                            ref):
    """The pass's checkpoint is bare, unlike the layers' of the two
    sparse-expert blocks (``check_flash_kept``): what a pass keeps is
    stacked over the scan, and on the chip the stacking cost what the
    forward kernel took (PERF.md, PR 35); nor does the pass say
    ``kept``, so its step is the program it was.  The scan's two bodies
    hold the stack once each: two layers, four forward kernels, two of
    each backward kernel."""
    from conftest import kernel_calls
    from mxnet_tpu.parallel import attention
    monkeypatch.setattr(attention, "_flash_eligible", lambda *a: True)
    w = {k: jnp.asarray(v) for k, v in _weights(ref, CONFIG, 35).items()}
    x = jnp.asarray(_batch(CONFIG, 36)[0])

    def loss(params):
        logits, states, gates = looped_lm_forward(
            params, x, num_layers=2, num_heads=4, num_passes=4, eps=1e-6,
            rope_base=1e6)
        return jnp.mean(logits ** 2) + jnp.mean(states ** 2) \
            + jnp.mean(gates ** 2)

    assert kernel_calls(jax.make_jaxpr(jax.grad(loss))(w).jaxpr) == {
        "_flash_fwd_kernel": 4, "_flash_bwd_dq_kernel": 2,
        "_flash_bwd_dkv_kernel": 2}


def test_three_trainer_steps_follow_the_reference(ref):
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh
    cfg = dict(CONFIG, batch_size=4)
    w = _weights(ref, cfg, 11)
    net = _block(cfg, w)
    opt = dict(cfg["optimizer"])
    name = opt.pop("name")
    trainer = ParallelTrainer(
        net, net.exit_loss(beta=cfg["exit_entropy_beta"]), name, opt,
        mesh=make_mesh(dp=1, devices=jax.devices()[:1]), zero=2,
        dtype="float32")
    batches = [_batch(cfg, 20 + i) for i in range(3)]
    losses = [float(trainer.step(nd.array(x, dtype="int32"),
                                 nd.array(y)).asnumpy())
              for x, y in batches]
    want = ref.train_steps(cfg, w, batches)
    _close(losses, want["loss"], "the three losses")
    short = len(net.prefix)
    now = {k[short:]: np.asarray(v) for k, v in trainer.params.items()}
    for k, w0 in w.items():
        got = float(np.sqrt(np.sum(np.square(now[k] - w0))))
        # Adam's step is lr * m / (sqrt(v) + eps): where a gradient is
        # tiny the quotient amplifies round-off, so the steps are held to
        # 1e-3 of their size, the losses above to 1e-5
        assert abs(got - want["dparam"][k]) <= 1e-3 * want["dparam"][k], k
    # forward() answers with the last exit's logits
    x = batches[0][0]
    logits = trainer.forward(nd.array(x, dtype="int32")).asnumpy()
    assert logits.shape == (4, cfg["seq_len"], cfg["vocab_size"])
    trainer.sync_to_block()
    _close(logits, net(nd.array(x, dtype="int32"))[0].asnumpy(), "forward")


# -- the small pieces, each against a three-line oracle ------------------------
def test_rms_norm_against_an_oracle():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 5, 16)).astype("f")
    g = rng.uniform(0.5, 1.5, 16).astype("f")
    blk = gluon.nn.RMSNorm(epsilon=1e-6)
    blk.initialize()
    blk(nd.array(x))
    blk.gamma.set_data(nd.array(g))
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * g
    _close(blk(nd.array(x)).asnumpy(), want, "RMSNorm")


def test_rotary_against_an_oracle():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 7, 3, 8)).astype("f")
    ang = np.arange(7)[:, None] * 1e6 ** (-np.arange(4) / 4.0)[None, :]
    z = (x[..., :4] + 1j * x[..., 4:]) * np.exp(1j * ang)[None, :, None, :]
    want = np.concatenate([z.real, z.imag], -1)
    got = nd.contrib.rotary_embedding(nd.array(x), base=1e6).asnumpy()
    _close(got, want, "rotary")
    # position 0 is left as it is, and a rotation keeps every pair's norm
    assert np.array_equal(got[:, 0], x[:, 0])


def test_gated_ffn_against_an_oracle():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, 5, 8)).astype("f")
    wg, wu = (rng.standard_normal((12, 8)).astype("f") * 0.5 for _ in "gu")
    wd = rng.standard_normal((8, 12)).astype("f") * 0.5
    g = x @ wg.T
    want = ((g / (1 + np.exp(-g))) * (x @ wu.T)) @ wd.T
    got = nd.contrib.gated_ffn(*map(nd.array, (x, wg, wu, wd))).asnumpy()
    _close(got, want, "gated FFN")


def test_linear_cross_entropy_against_an_oracle():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((2, 5, 8)).astype("f")
    w = rng.standard_normal((11, 8)).astype("f")
    y = rng.integers(0, 11, (2, 5))
    logits = x @ w.T
    want = np.log(np.exp(logits).sum(-1)) - np.take_along_axis(
        logits, y[..., None], -1)[..., 0]
    got = nd.contrib.linear_cross_entropy(
        nd.array(x), nd.array(w), nd.array(y.astype("f"))).asnumpy()
    _close(got, want, "linear cross-entropy")


def test_gauges_say_what_the_loop_runs():
    from mxnet_tpu import telemetry
    telemetry.enable()
    try:
        LoopedLM(32, units=16, hidden_size=24, num_layers=3, num_heads=2,
                 num_passes=4)
        totals = telemetry.scalar_totals()
    finally:
        telemetry.disable()
    assert totals["mxnet_loop_passes"] == 4
    assert totals["mxnet_loop_layers"] == 3
    assert totals["mxnet_loop_layer_applications"] == 12
