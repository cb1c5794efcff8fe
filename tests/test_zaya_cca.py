"""Compressed convolutional attention (CCA), the top-1 expert layer under a
router that is an MLP, partial rotary and the tied head on the CPU (small
sizes, float32, seeded weights), against the plain reference
``perfbench/reference/zaya_moe_lm.py``:

- ``MoELM`` with all four against the reference: loss, last-position
  logits, every leaf's gradient (and the reference's walk in blocks
  against its whole loss);
- the CCA layer alone, output and gradients;
- the convolutions are causal: position 0 sees zeros before it, a change
  at ``t`` moves nothing before ``t``; so is the whole layer;
- the value shift: one key/value head reads the token, the other the
  token before;
- partial rotary against a whole-head turn of the first ``rotary_dim``;
- top-1 weights are the chosen expert's probability, not renormalised,
  and the router MLP gets a gradient;
- the tied leaf's gradient is the sum of its two uses;
- the shares add up: experts [0, 8) and [8, 16), attention counted once,
  give the uncut reference layer;
- each layer keeps its flash results and its routing across its
  checkpoint, as the other sparse blocks do.
"""
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon.contrib.transformer import (FULL, MoELM,
                                                 compressed_conv_attention,
                                                 moe_lm_forward)
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.contrib import _causal_conv, _rotary_embedding
from mxnet_tpu.ops.nn import _rms_norm
from mxnet_tpu.parallel import moe
from mxnet_tpu.parallel.moe import _route_top_k, routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PB = os.path.join(ROOT, "perfbench")

CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"rope_theta": 5000000,
                                   "rope_type": "default"}},
    "cca_time0": 2, "cca_time1": 2, "moe_intermediate_size": 32,
    "num_experts": 4, "num_experts_per_tok": 1, "router_hidden_size": 16,
    "assumed": {"norm_topk_prob": False, "router_mlp_layers": 3},
    "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "deployment": {"experts_held": [2, 6]},
    "published": {"num_experts": 8}, "init_std": 0.05,
    "embed_init_std": 0.5, "residual_init_std": 0.05, "seq_len": 96,
    "batch_size": 2}


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, PB)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_zaya_moe_lm",
            os.path.join(PB, "reference", "zaya_moe_lm.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.path.remove(PB)


def _block(ref, config, weights, tied=True):
    s = ref.sizes(config)
    net = MoELM(s["vocab"], units=s["units"], expert_width=s["expert_width"],
                layer_types=[FULL] * s["layers"], num_heads=s["heads"],
                num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
                num_routed=s["routed"], held=s["held"], top_k=s["top_k"],
                rope={FULL: config["rope_parameters"]["hybrid"]},
                norm_topk=False, epsilon=s["eps"], cca=s["taps"],
                rotary_dim=s["rotary"], router_hidden=s["router_hidden"],
                router_layers=s["router_layers"], tie_embeddings=tied)
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    params = net.collect_params()
    names = list(params.keys())
    for rname, w in weights.items():
        pname = [n for n in names if n.endswith(rname)]
        assert len(pname) == 1, rname
        assert tuple(params[pname[0]].shape) == w.shape, rname
        params[pname[0]].set_data(nd.array(w))
    if not tied:
        params[[n for n in names if n.endswith("head_weight")][0]].set_data(
            nd.array(weights["embed_weight"]))
    return net


def _rope(config):
    return (float(config["rope_parameters"]["hybrid"]["rope_theta"]), None,
            1.0)


def _layer_p(ref, weights, config, i=0):
    return {k: jnp.asarray(v)
            for k, v in ref._layer_params(weights, i, config).items()}


# ---------------------------------------------------------------------------
# the block against the reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def against_reference(ref):
    weights = ref.init_weights(CONFIG, 2 ** 31 + 40)
    net = _block(ref, CONFIG, weights)
    tokens = np.random.default_rng(40).integers(0, 256, (2, 97))
    x, y = tokens[:, :-1], tokens[:, 1:]
    with autograd.record():
        states = net(nd.array(x, dtype="int32"))
        loss = net.lm_loss()(states, nd.array(y.astype("f"))).mean()
    loss.backward()
    params = {k: jnp.asarray(v) for k, v in weights.items()}
    blocks = ref.Q_BLOCK, ref.ROW_BLOCK
    ref.Q_BLOCK, ref.ROW_BLOCK = 40, 56     # ragged last blocks
    try:
        with jax.default_matmul_precision("highest"):
            want, grads = jax.value_and_grad(ref.loss_fn)(
                params, jnp.asarray(x), jnp.asarray(y), CONFIG)
            walked, walked_grads = ref.loss_and_grads(
                params, jnp.asarray(x), jnp.asarray(y), CONFIG)
            logits = ref.forward(params, jnp.asarray(x), jnp.asarray(y),
                                 CONFIG)[0]
    finally:
        ref.Q_BLOCK, ref.ROW_BLOCK = blocks
    got = {r: p.grad().asnumpy()
           for p, r in zip(net.collect_params().values(), weights)}
    return {"loss": (float(loss.asnumpy()), float(want), float(walked)),
            "logits": (net.logits(states).asnumpy()[:, -1],
                       np.asarray(logits)[:, -1]),
            "grads": (got, grads, walked_grads)}


def test_cca_lm_loss_matches_the_reference(against_reference):
    got, want, walked = against_reference["loss"]
    assert got == pytest.approx(want, rel=2e-6)
    assert walked == pytest.approx(want, rel=2e-6)


def test_cca_lm_last_position_logits_match_the_reference(against_reference):
    got, want = against_reference["logits"]
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_cca_lm_every_leafs_gradient_matches_the_reference(
        against_reference):
    got, want, walked = against_reference["grads"]
    # the tied table, 16 leaves a layer, the final gain: no head_weight
    assert set(got) == set(want) and len(want) == 1 + 2 * 16 + 1
    for name, g in want.items():
        scale = float(jnp.abs(g).max())
        assert scale > 0, name
        assert np.abs(got[name] - np.asarray(g)).max() <= 2e-5 * scale, name
        # the reference's walk in blocks is its whole loss's gradient
        assert np.abs(np.asarray(walked[name] - g)).max() <= 2e-5 * scale


# ---------------------------------------------------------------------------
# the CCA layer
# ---------------------------------------------------------------------------
def _cca(p, x, config=CONFIG):
    s = {"heads": config["num_attention_heads"],
         "kv": config["num_key_value_heads"]}
    h = _rms_norm(x, p["norm1_gamma"], eps=config["rms_norm_eps"])
    return compressed_conv_attention(
        h, p, num_heads=s["heads"], num_kv_heads=s["kv"],
        rope=_rope(config), rotary_dim=int(config["head_dim"] // 2))


def test_the_cca_layer_and_its_gradients_match_the_reference(ref):
    weights = ref.init_weights(CONFIG, 41)
    p = {k: v for k, v in _layer_p(ref, weights, CONFIG).items()
         if k in ref.ATTN_IN_LEAVES + ("out_weight",)}
    rng = np.random.default_rng(41)
    x = jnp.asarray(rng.normal(size=(2, 80, 64)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(2, 80, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, got_vjp = jax.vjp(lambda x_, p_: _cca(p_, x_), x, p)
        want, want_vjp = jax.vjp(lambda x_, p_: ref.cca(x_, p_, CONFIG), x, p)
        got_g, want_g = got_vjp(ct), want_vjp(ct)
    assert float(jnp.abs(got - want).max()) <= 2e-5 * float(
        jnp.abs(want).max())
    flat_got, flat_want = (jax.tree_util.tree_leaves_with_path(g)
                           for g in (got_g, want_g))
    for (path, a), (_p, b) in zip(flat_got, flat_want):
        scale = float(jnp.abs(b).max())
        assert scale > 0, path
        assert float(jnp.abs(a - b).max()) <= 2e-5 * scale, path


@pytest.mark.parametrize("conv", ["time", "mix"])
def test_the_convolutions_are_causal(conv):
    """Tap ``j`` reads the row ``j`` back: position 0 sees zeros before
    it, and a change at ``t`` moves rows ``t .. t + K - 1`` and nothing
    before ``t``."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 12, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 32) if conv == "time"
                               else (2, 3, 16, 16)), jnp.float32)
    out = _causal_conv(x, w)
    first = x[:, 0] * w[0] if conv == "time" else jnp.einsum(
        "bgc,gcd->bgd", x[:, 0].reshape(2, 2, 16), w[:, 0]).reshape(2, 32)
    assert np.allclose(out[:, 0], first, atol=1e-5)
    for t in (0, 5, 11):
        moved = _causal_conv(x.at[:, t].add(1.0), w) - out
        changed = np.asarray(jnp.abs(moved).max(axis=(0, 2)) > 0)
        assert not changed[:t].any(), t
        assert changed[t:t + 3].all(), t
        assert not changed[t + 3:].any(), t


def test_the_cca_layer_is_causal(ref):
    weights = ref.init_weights(CONFIG, 42)
    p = _layer_p(ref, weights, CONFIG)
    x = jnp.asarray(np.random.default_rng(42).normal(size=(1, 48, 64)),
                    jnp.float32)
    out = _cca(p, x)
    for t in (0, 17, 47):
        moved = np.asarray(jnp.abs(_cca(p, x.at[:, t].add(1.0)) - out).max(
            axis=(0, 2)))
        assert (moved[:t] == 0).all() and moved[t] > 0, t


def test_the_value_shift_reads_the_token_and_the_one_before(ref):
    """Position 0's query sees only key 0, so its output is its value
    alone: with the first key/value head's value rows zeroed it is ZERO
    (the second head reads the row before the first, which is zero); with
    the second head's zeroed it is not, and at position 1 the second
    head carries position 0's value."""
    weights = ref.init_weights(CONFIG, 43)
    p = _layer_p(ref, weights, CONFIG)
    x = jnp.asarray(np.random.default_rng(43).normal(size=(1, 24, 64)),
                    jnp.float32)
    d = CONFIG["head_dim"]
    first = dict(p, v_weight=p["v_weight"].at[:d].set(0.0))
    second = dict(p, v_weight=p["v_weight"].at[d:].set(0.0))
    assert float(jnp.abs(_cca(first, x)[:, 0]).max()) == 0.0
    assert float(jnp.abs(_cca(first, x)[:, 1]).max()) > 0
    assert float(jnp.abs(_cca(second, x)[:, 0]).max()) > 0
    # position 1 then reads, through the second head, row 0's state alone
    with jax.default_matmul_precision("highest"):
        assert np.allclose(_cca(first, x)[:, 1],
                           ref.cca(x, first, CONFIG)[:, 1], atol=1e-5)


@pytest.mark.parametrize("rotary_dim", [4, 8, 16])
def test_partial_rotary_turns_the_first_dims_as_a_head_of_that_size(
        rotary_dim):
    rng = np.random.default_rng(rotary_dim)
    x = jnp.asarray(rng.normal(size=(2, 20, 3, 16)), jnp.float32)
    got = _rotary_embedding(x, base=5e6, rotary_dim=rotary_dim)
    whole = _rotary_embedding(x[..., :rotary_dim], base=5e6)
    assert np.allclose(got[..., :rotary_dim], whole, atol=1e-6)
    assert np.array_equal(np.asarray(got[..., rotary_dim:]),
                          np.asarray(x[..., rotary_dim:]))
    # the closed form: pair (i, i + R/2) turns by t * 5e6^(-2i/R)
    r = rotary_dim
    ang = np.arange(20)[:, None] * 5e6 ** (-np.arange(0, r, 2) / r)[None]
    a, b = np.asarray(x[..., :r // 2]), np.asarray(x[..., r // 2:r])
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    assert np.allclose(got[..., :r // 2], a * cos - b * sin, atol=1e-5)
    assert np.allclose(got[..., r // 2:r], b * cos + a * sin, atol=1e-5)
    with pytest.raises(ValueError, match="rotary_dim"):
        _rotary_embedding(x, rotary_dim=5)


# ---------------------------------------------------------------------------
# the router MLP and top-1
# ---------------------------------------------------------------------------
def _router(ref, seed, routed=8):
    rng = np.random.default_rng(seed)
    hr = 16
    ws = [rng.normal(size=(hr, 64)) * 0.1, rng.normal(size=(hr, hr)) * 0.3,
          rng.normal(size=(hr, hr)) * 0.3, rng.normal(size=(routed, hr)) * 0.3]
    return [jnp.asarray(w, jnp.float32) for w in ws]


def _mlp(ws):
    from mxnet_tpu.gluon.contrib.transformer import _RouterMLP
    return _RouterMLP(ws)


def test_top1_weight_is_the_chosen_probability_not_renormalised(ref):
    ws = _router(ref, 5)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(64, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        weights, chosen = _route_top_k(x, _mlp(ws), 1,
                                       norm_topk=False)
        p = jax.nn.softmax(_mlp(ws)(x), axis=-1)
        p_ref = ref.route(x[None], dict(zip(
            ("router_weight", "router0_weight", "router1_weight",
             "router2_weight"), ws)), dict(CONFIG, published={
                 "num_experts": 8}, deployment={"experts_held": [0, 4]}),
            False)[0]
    assert np.array_equal(np.asarray(chosen[:, 0]),
                          np.asarray(jnp.argmax(p, -1)))
    assert np.allclose(weights[:, 0], jnp.max(p, -1), atol=1e-6)
    assert float(jnp.max(weights)) < 1.0
    assert np.allclose(jnp.sum(p_ref, -1), weights[:, 0], atol=1e-6)
    renormalised, _ = _route_top_k(x, _mlp(ws), 1)
    assert np.array_equal(np.asarray(renormalised), np.ones((64, 1)))


def test_the_router_mlp_gets_a_gradient_at_top1(ref):
    """Every layer of the router gets a nonzero gradient through the
    chosen expert's weight; renormalised, the weight is 1 and the router
    gets none."""
    rng = np.random.default_rng(6)
    ws = _router(ref, 6)
    f = 8
    experts = [jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
               for s in ((4, f, 64), (4, f, 64), (4, 64, f))]
    x = jnp.asarray(rng.normal(size=(48, 64)), jnp.float32)

    def out(ws_, norm):
        y = routed_experts(x, _mlp(ws_), experts, 1, (2, 4),
                           norm_topk=norm)
        return jnp.sum(jnp.sin(y))

    grads = jax.grad(out)(ws, False)
    assert all(float(jnp.abs(g).max()) > 0 for g in grads)
    for g, none in zip(grads, jax.grad(out)(ws, True)):
        assert float(jnp.abs(none).max()) <= 1e-6 * float(jnp.abs(g).max())


# ---------------------------------------------------------------------------
# the tied head
# ---------------------------------------------------------------------------
def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses(ref):
    """The same weights through the tied block and through an untied one
    whose head is a copy of the table: the tied table's gradient is the
    look-up's plus the head's."""
    weights = ref.init_weights(CONFIG, 44)
    tokens = np.random.default_rng(44).integers(0, 256, (2, 49))
    x, y = tokens[:, :-1], tokens[:, 1:]
    grads = {}
    for tied in (True, False):
        net = _block(ref, CONFIG, weights, tied=tied)
        with autograd.record():
            loss = net.lm_loss()(net(nd.array(x, dtype="int32")),
                                 nd.array(y.astype("f"))).mean()
        loss.backward()
        grads[tied] = {k: p.grad().asnumpy()
                       for k, p in net.collect_params().items()}
    pick = lambda g, end: next(v for k, v in g.items() if k.endswith(end))
    lookup = pick(grads[False], "embed_weight")
    head = pick(grads[False], "head_weight")
    assert np.abs(lookup).max() > 0 and np.abs(head).max() > 0
    np.testing.assert_allclose(pick(grads[True], "embed_weight"),
                               lookup + head, rtol=1e-5,
                               atol=1e-6 * np.abs(head).max())


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------
@pytest.fixture(params=["einsum", "pallas"])
def product(request, monkeypatch):
    """The expert layer's plain product, or its kernels in interpret
    mode, over tiles small enough that an expert's rows span several."""
    monkeypatch.setattr(pk, "GROUPED_TILE_ROWS", 16)
    if request.param == "pallas":
        monkeypatch.setattr(moe, "_tile_product", pk.grouped_matmul)
    return request.param


def test_the_shares_add_up_to_the_uncut_layer(ref, product):
    """Two chips' layers — experts [0, 8) and [8, 16) of 16 under the
    whole router MLP, top-1 — with the attention part counted once, sum
    to the uncut reference layer: ``x + CCA + share_0 + share_1``."""
    whole = dict(CONFIG, num_experts=16, published={"num_experts": 16},
                 deployment={"experts_held": [0, 16]})
    weights = ref.init_weights(whole, 45)
    p = _layer_p(ref, weights, whole)
    x = jnp.asarray(np.random.default_rng(45).normal(size=(1, 64, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = ref._layer(x, p, whole, False)
        x1 = x + _cca(p, x)
        h = _rms_norm(x1, p["norm2_gamma"], eps=whole["rms_norm_eps"])[0]
        ws = [p["router_weight"]] + [p["router%d_weight" % j]
                                     for j in range(3)]
        parts = [routed_experts(
            h, _mlp(ws),
            tuple(p[k][first:first + 8] for k in ("gate_weight", "up_weight",
                                                  "down_weight")),
            1, (first, 8), norm_topk=False)[None] for first in (0, 8)]
        experts = ref.expert_layer(h[None], p, whole)
    scale = float(jnp.abs(experts).max())
    assert float(jnp.abs(sum(parts) - experts).max()) <= 2e-5 * scale
    assert all(float(jnp.abs(part).max()) > 0.1 * scale for part in parts)
    assert float(jnp.abs(x1 + sum(parts) - uncut).max()) \
        <= 2e-6 * float(jnp.abs(uncut).max())


# ---------------------------------------------------------------------------
# the checkpoint keeps what the other sparse blocks keep
# ---------------------------------------------------------------------------
def _loss_of(ref, seed):
    weights = ref.init_weights(CONFIG, seed)
    net = _block(ref, CONFIG, weights)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(0, 256,
                                                              (2, 96)))

    def loss(params):
        return jnp.mean(moe_lm_forward(params, tokens, **net._config) ** 2)

    return loss, {k: jnp.asarray(v) for k, v in weights.items()}


def test_a_cca_layer_keeps_its_flash_results(ref, check_flash_kept):
    loss, params = _loss_of(ref, 46)
    check_flash_kept(loss, params, 2)


def test_a_cca_layer_keeps_its_routing(ref, check_route_kept):
    loss, params = _loss_of(ref, 47)
    check_route_kept(loss, params, layers=2, tokens=192, top_k=1, held=4)


# ---------------------------------------------------------------------------
# what the block says of itself, and what it refuses
# ---------------------------------------------------------------------------
def test_the_block_exports_its_kernels_rotary_and_rows(ref):
    from mxnet_tpu import telemetry
    telemetry.enable()
    try:
        net = _block(ref, CONFIG, ref.init_weights(CONFIG, 48))
        net(nd.array(np.zeros((2, 96)), dtype="int32"))
        taps = telemetry.gauge("mxnet_cca_kernel")
        read = {"time": taps.labels(conv="time").value,
                "mix": taps.labels(conv="mix").value,
                "rotary": telemetry.gauge("mxnet_rotary_dims").labels().value,
                "rows": telemetry.gauge(
                    "mxnet_moe_expected_rows").labels().value}
    finally:
        telemetry.disable()
    # 192 tokens x 1 slot x 4 held of 8 experts
    assert read == {"time": 2, "mix": 2, "rotary": 8, "rows": 96.0}


@pytest.mark.parametrize("kwargs", [
    {"cca": (2,)}, {"cca": (2, 0)}, {"cca": (2, 2), "qk_norm": True},
    {"cca": (2, 2), "num_kv_heads": 1, "num_heads": 4},
    {"cca": (2, 2), "layer_types": ["sliding_attention", FULL]},
    {"router_hidden": 16}, {"router_layers": 2},
    {"rotary_dim": 5}, {"rotary_dim": 40}])
def test_the_block_refuses_what_it_cannot_run(kwargs):
    args = dict(units=64, num_heads=4, num_kv_heads=2, head_dim=16,
                num_routed=8, top_k=1, layer_types=[FULL])
    args.update(kwargs)
    with pytest.raises(ValueError):
        MoELM(64, **args)


def test_rotary_at_its_whole_width_is_the_call_it_has_always_been():
    x = jnp.asarray(np.random.default_rng(7).normal(size=(1, 10, 2, 16)),
                    jnp.float32)
    assert np.array_equal(np.asarray(_rotary_embedding(x, rotary_dim=16)),
                          np.asarray(_rotary_embedding(x)))
    assert math.isclose(float(jnp.sum(_rotary_embedding(x) ** 2)),
                        float(jnp.sum(x ** 2)), rel_tol=1e-5)
