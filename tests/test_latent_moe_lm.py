"""The latent-attention sparse-expert LM on the CPU (small sizes, seeded
weights):

- ``LatentMoELM`` against the plain reference
  ``perfbench/reference/latent_moe_lm.py``: loss, both outputs, every
  leaf's gradient (float32), and the reference's walk in blocks against
  its own whole loss; the loss in bfloat16 through ``ParallelTrainer``;
- latent attention alone against an explicit per-head form with the key
  written out as ``[k_nope | k_rope]`` and the pairs turned as complex
  numbers, float32 and bfloat16;
- the flash kernels at a value head size that differs from the key's
  (interpret mode: forward, dQ, dK/dV) and the einsum form against a
  plain masked softmax;
- the router on a hand-made case where the bias changes the choice and
  not the weight;
- the shares add up: every share's routed output plus the shared expert
  counted ONCE is the uncut reference layer;
- the prediction modules' targets on a hand-made sequence;
- the stacked expert leaves of the published sizes ride native buckets;
- the new arguments at their defaults leave ``MoELM``'s step program as
  it was.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon.contrib import transformer
from mxnet_tpu.gluon.contrib.transformer import (LatentMoELM, MoELM,
                                                 latent_attention)
from mxnet_tpu.ops import contrib as ops_contrib
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.contrib import _rotary_embedding
from mxnet_tpu.parallel import moe
from mxnet_tpu.parallel.attention import local_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PB = os.path.join(ROOT, "perfbench")

CONFIG = {
    "vocab_size": 256, "hidden_size": 128, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 192,
    "moe_intermediate_size": 64, "n_shared_experts": 1,
    "n_routed_experts": 2, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "rope_scaling": None,
    "rope_theta": 32000000, "rope_interleave": True, "rms_norm_eps": 1e-6,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3,
    "deployment": {"experts_held": [2, 4]},
    "published": {"n_routed_experts": 8},
    "init_std": 0.05, "embed_init_std": 0.5, "residual_init_std": 0.05,
    "selection_bias_std": 0.05, "seq_len": 96, "batch_size": 2}


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, PB)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_latent_moe_lm",
            os.path.join(PB, "reference", "latent_moe_lm.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.path.remove(PB)


def _block(ref, config, weights):
    s = ref.sizes(config)
    net = LatentMoELM(
        s["vocab"], units=s["units"], dense_width=s["dense_width"],
        expert_width=s["expert_width"],
        mlp_layer_types=ref.layer_kinds(config), num_heads=s["heads"],
        q_rank=s["q_rank"], kv_rank=s["kv_rank"], nope_dim=s["nope"],
        rope_dim=s["rope"], v_dim=s["v"], num_routed=s["routed"],
        held=s["held"], top_k=s["top_k"], shared_experts=s["shared"],
        route_scale=s["route_scale"], rope_base=s["theta"],
        mtp_depth=s["mtp"])
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    params = net.collect_params()
    assert len(list(params.values())) == len(weights)
    for (pname, p), (rname, w) in zip(params.items(), weights.items()):
        assert pname.endswith(rname) and tuple(p.shape) == w.shape
        assert (p.grad_req == "null") == rname.endswith("router_bias")
        p.set_data(nd.array(w))
    return net


def _batch(seed=3):
    tokens = np.random.default_rng(seed).integers(0, 256, (2, 97))
    return tokens[:, :-1], tokens[:, 1:]


@pytest.fixture(scope="module", params=[1, 2], ids=["mtp1", "mtp2"])
def against_reference(ref, request):
    config = dict(CONFIG, num_nextn_predict_layers=request.param)
    weights = ref.init_weights(config, 2 ** 31 + 7)
    net = _block(ref, config, weights)
    x, y = _batch()
    with autograd.record():
        states, mtp_states = net(nd.array(x, dtype="int32"))
        loss = net.lm_loss(mtp_weight=0.3)(
            states, mtp_states, nd.array(y.astype("f"))).mean()
    loss.backward()
    params = {k: jnp.asarray(v) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(ref.loss_fn)(
            params, jnp.asarray(x), jnp.asarray(y), config)
        walked, walked_grads = ref.loss_and_grads(
            params, jnp.asarray(x), jnp.asarray(y), config)
        want_states, want_mtp, _ = ref.forward(
            params, jnp.asarray(x), jnp.asarray(y), config)
    got = {r: p.grad().asnumpy()
           for p, r in zip(net.collect_params().values(), weights)
           if p.grad_req != "null"}
    return {"depth": request.param,
            "loss": (float(loss.asnumpy()), float(want), float(walked)),
            "states": (states.asnumpy(), np.asarray(want_states)),
            "mtp": (mtp_states.asnumpy(), np.asarray(want_mtp)),
            "grads": (got, grads, walked_grads)}


def test_latent_moe_lm_loss_matches_the_reference(against_reference):
    got, want, walked = against_reference["loss"]
    # float32 against float32 at highest precision: rounding only
    assert got == pytest.approx(want, rel=3e-6)
    assert walked == pytest.approx(want, rel=3e-6)
    # both terms are in it: ln 256 = 5.55, and 0.3 of as much again
    assert 1.25 * 5.4 < want < 1.35 * 5.8


def test_latent_moe_lm_states_match_the_reference(against_reference):
    got, want = against_reference["states"]
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    got, want = against_reference["mtp"]
    assert got.shape == want.shape == (2, against_reference["depth"], 96,
                                       128)
    # a module's last positions read tokens past the sequence's end —
    # each side its own filler — and are left out of the loss
    for k in range(got.shape[1]):
        seen = slice(0, 96 - (k + 1))
        assert np.abs(got[:, k, seen] - want[:, k, seen]).max() \
            <= 2e-5 * np.abs(want).max()


def test_latent_moe_lm_every_leafs_gradient_matches_the_reference(
        against_reference):
    got, want, walked = against_reference["grads"]
    depth = against_reference["depth"]
    held = {k for k in want if k.endswith("router_bias")}
    assert len(held) == 2 + depth
    assert set(got) == set(want) - held
    # embedding, a dense layer, two sparse ones, the gain, the modules, head
    assert len(want) == 1 + 12 + 2 * 17 + 1 + depth * (3 + 17 + 1) + 1
    for name, g in want.items():
        scale = float(jnp.abs(g).max())
        # the reference's walk in blocks is its whole loss's gradient
        assert np.abs(np.asarray(walked[name] - g)).max() \
            <= 2e-5 * max(scale, 1e-30), name
        if name in held:
            assert scale == 0.0     # chosen by it, never weighed by it
            continue
        assert scale > 0, name
        # float32 against float32: 2e-5 of the leaf's largest entry is
        # rounding; a product in bfloat16 reads 1e-3 and more
        assert np.abs(got[name] - np.asarray(g)).max() <= 2e-5 * scale, name


def test_bf16_step_keeps_the_loss_and_leaves_the_bias_alone(ref):
    """Through ``ParallelTrainer(dtype=bfloat16)``, the path the cell
    times: the loss within bfloat16's rounding of the float32
    reference's (8 bits of mantissa: 4e-3; the reference's own fp8
    control is not asked here, the cell's test holds it by the
    gradients), the held selection bias bit for bit what it was."""
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh
    weights = ref.init_weights(CONFIG, 11)
    net = _block(ref, CONFIG, weights)
    trainer = ParallelTrainer(
        net, net.lm_loss(mtp_weight=0.3), "adam", {"learning_rate": 1e-3},
        mesh=make_mesh(dp=1, devices=jax.devices()[:1]), zero=2,
        dtype="bfloat16")
    x, y = _batch(4)
    got = float(trainer.step(nd.array(x, dtype="int32"),
                             nd.array(y.astype("f"))).asnumpy())
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss_fn({k: jnp.asarray(v)
                                  for k, v in weights.items()},
                                 jnp.asarray(x), jnp.asarray(y), CONFIG))
    assert got == pytest.approx(want, rel=4e-3)
    after = {k: np.asarray(v) for k, v in trainer.params.items()}
    for (pname, p), (rname, w) in zip(net.collect_params().items(),
                                      weights.items()):
        if rname.endswith("router_bias"):
            assert np.array_equal(after[pname], w), rname
        else:
            assert not np.array_equal(after[pname], w), rname


# ---------------------------------------------------------------------------
# latent attention alone
# ---------------------------------------------------------------------------
def _per_head_mla(h, p, heads, nope, rope, v_dim, theta, eps=1e-6):
    """Latent attention head by head, float64 numpy: the key of a head
    written out as ``[k_nope | k_rope]``, each rotary pair ``(2i, 2i +
    1)`` turned as one complex number."""
    h, p = np.asarray(h, np.float64), {k: np.asarray(v, np.float64)
                                       for k, v in p.items()}
    t = h.shape[1]

    def rms(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def turn(x):                                    # (T, rope)
        z = x[:, 0::2] + 1j * x[:, 1::2]
        ang = np.arange(t)[:, None] * theta ** (
            -np.arange(0, rope, 2) / rope)[None, :]
        z = z * np.exp(1j * ang)
        out = np.empty_like(x)
        out[:, 0::2], out[:, 1::2] = z.real, z.imag
        return out

    outs = []
    for hb in h:
        cq = rms(hb @ p["q_a_weight"].T, p["q_a_norm_gamma"])
        q = (cq @ p["q_b_weight"].T).reshape(t, heads, nope + rope)
        ckv = hb @ p["kv_a_weight"].T
        kv = (rms(ckv[:, :-rope], p["kv_a_norm_gamma"])
              @ p["kv_b_weight"].T).reshape(t, heads, nope + v_dim)
        k_rope = turn(ckv[:, -rope:])
        o = np.zeros((t, heads, v_dim))
        for n in range(heads):
            key = np.concatenate([kv[:, n, :nope], k_rope], -1)
            qry = np.concatenate([q[:, n, :nope], turn(q[:, n, nope:])], -1)
            s = qry @ key.T / np.sqrt(nope + rope)
            s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
            pr = np.exp(s - s.max(-1, keepdims=True))
            o[:, n] = (pr / pr.sum(-1, keepdims=True)) @ kv[:, n, nope:]
        outs.append(o.reshape(t, -1) @ p["out_weight"].T)
    return np.stack(outs)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_latent_attention_is_the_per_head_form(ref, dtype, tol):
    """float32: rounding.  bfloat16 operands (8 bits of mantissa through
    five products and a softmax): a few per cent of the largest entry —
    the same function, no other path."""
    s = ref.sizes(CONFIG)
    shapes = {n[3:]: sh for n, sh, _i in ref.leaf_specs(CONFIG)
              if n.startswith("l0_")}
    rng = np.random.default_rng(8)
    names = ("q_a_weight", "q_a_norm_gamma", "q_b_weight", "kv_a_weight",
             "kv_a_norm_gamma", "kv_b_weight", "out_weight")
    p = {n: (1 + 0.1 * rng.normal(size=shapes[n]) if n.endswith("gamma")
             else 0.2 * rng.normal(size=shapes[n])).astype("f")
         for n in names}
    h = rng.normal(size=(2, 40, 128)).astype("f")
    want = _per_head_mla(h, p, s["heads"], s["nope"], s["rope"], s["v"],
                         s["theta"])
    cast = lambda a: jnp.asarray(a, dtype)
    got = latent_attention(
        cast(h), {k: cast(v) for k, v in p.items()}, num_heads=s["heads"],
        nope_dim=s["nope"], rope_dim=s["rope"], v_dim=s["v"],
        rope_base=s["theta"])
    assert got.dtype == jnp.dtype(dtype)
    assert np.abs(np.asarray(got, np.float64) - want).max() \
        <= tol * np.abs(want).max()
    if dtype == "float32":
        # and the reference's own layer (its first norm with gain 1)
        with jax.default_matmul_precision("highest"):
            theirs = ref.attention_layer(
                jnp.asarray(h), dict({k: jnp.asarray(v)
                                      for k, v in p.items()},
                                     norm1_gamma=jnp.ones((128,))), CONFIG)
        normed = h / np.sqrt((h * h).mean(-1, keepdims=True) + 1e-6)
        want_normed = _per_head_mla(normed, p, s["heads"], s["nope"],
                                    s["rope"], s["v"], s["theta"])
        assert np.abs(np.asarray(theirs) - want_normed).max() \
            <= 2e-5 * np.abs(want_normed).max()


def test_rotary_turns_interleaved_pairs_where_they_lie():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(1, 24, 3, 8)), jnp.float32)
    got = np.asarray(_rotary_embedding(x, base=3.2e7, interleaved=True))
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    ang = np.arange(24)[:, None] * 3.2e7 ** (-np.arange(0, 8, 2) / 8)[None]
    z = z * np.exp(1j * ang)[None, :, None, :]
    assert np.abs(got[..., 0::2] - z.real).max() < 1e-5
    assert np.abs(got[..., 1::2] - z.imag).max() < 1e-5
    # the half-split call is the one it has always been
    assert np.array_equal(
        np.asarray(_rotary_embedding(x, base=3.2e7)),
        np.asarray(_rotary_embedding(x, base=3.2e7, interleaved=False)))
    # and the two pairings are one rotation seen through a permutation
    half = np.asarray(_rotary_embedding(
        jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1), base=3.2e7))
    assert np.abs(np.concatenate([got[..., 0::2], got[..., 1::2]], -1)
                  - half).max() < 1e-5


# ---------------------------------------------------------------------------
# a value head size that is not the key's
# ---------------------------------------------------------------------------
def _masked_attention(q, k, v):
    t = q.shape[1]
    see = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(t, d, dv, heads=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(1, t, heads, w)), jnp.float32)
                 for w in (d, d, dv))


def _fold(a):
    b, t, h, d = a.shape
    return jnp.transpose(a, (0, 2, 1, 3)).reshape(b * h, t, d)


# T, key size, value size, block_q, block_k: the published pair, the
# value wider than the key, blocks that are not square, blocks picked
# from the shape
UNEQUAL = [(256, 192, 128, 128, 128), (256, 24, 16, 64, 64),
           (256, 64, 128, 128, 64), (384, 192, 128, None, None)]


@pytest.mark.parametrize("t,d,dv,bq,bk", UNEQUAL)
@pytest.mark.parametrize("wrt", ["forward", "dq", "dkv"])
def test_flash_kernels_take_a_value_size_of_its_own(t, d, dv, bq, bk, wrt):
    q, k, v = _qkv(t, d, dv)

    def flash(q_, k_, v_):
        o = pk.flash_attention(_fold(q_), _fold(k_), _fold(v_), True, None,
                               bq, bk)
        return o if wrt == "forward" else jnp.sum(jnp.sin(o))

    def masked(q_, k_, v_):
        o = _fold(_masked_attention(q_, k_, v_))
        return o if wrt == "forward" else jnp.sum(jnp.sin(o))

    if wrt == "forward":
        got, want = [flash(q, k, v)], [masked(q, k, v)]
        assert got[0].shape == (2, t, dv)
    else:
        args = (0,) if wrt == "dq" else (1, 2)
        got = jax.grad(flash, args)(q, k, v)
        want = jax.grad(masked, args)(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 5e-5


@pytest.mark.parametrize("impl", ["einsum", "flash"])
def test_local_attention_takes_a_value_size_of_its_own(impl):
    q, k, v = _qkv(128, 24, 16, seed=1)
    fn = lambda *a: local_attention(*a, causal=True, impl=impl)
    assert fn(q, k, v).shape == (1, 128, 2, 16)
    assert float(jnp.abs(fn(q, k, v) - _masked_attention(q, k, v)).max()) \
        < 5e-6
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(_masked_attention(*a))),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 2e-5


def test_equal_sizes_plan_what_they_always_planned():
    """A call whose value size is the key's builds the plans it built
    before there was a ``dv``: the same blocks, shapes and scratch."""
    for kernel, plan in pk._FLASH_PLANS.items():
        old = plan(4, 1024, 1024, 128, 512, 256, True, jnp.bfloat16)
        new = plan(4, 1024, 1024, 128, 512, 256, True, jnp.bfloat16, None,
                   128)
        for key in ("grid", "in_shapes", "out_shapes", "scratch", "tiles"):
            assert old[key] == new[key], (kernel, key)
        assert [sp.block_shape for sp in old["in_specs"] + old["out_specs"]] \
            == [sp.block_shape for sp in new["in_specs"] + new["out_specs"]]
        assert pk._flash_blocks(8192, 8192, 128, jnp.bfloat16, kernel) \
            == pk._flash_blocks(8192, 8192, 128, jnp.bfloat16, kernel, 128)
    wide = pk.flash_bwd_dkv_plan(4, 1024, 1024, 192, 512, 256, True,
                                 jnp.bfloat16, None, 128)
    assert wide["in_shapes"][:4] == [(4, 1024, 192), (4, 1024, 192),
                                     (4, 1024, 128), (4, 1024, 128)]
    assert wide["out_shapes"] == [(4, 1024, 192), (4, 1024, 128)]
    assert wide["scratch"] == [(256, 192), (256, 128)]


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------
def test_the_bias_changes_the_choice_and_not_the_weight():
    """Four experts, top-2, one token whose sigmoid scores are 0.8, 0.6,
    0.5, 0.2.  Without a bias it takes experts 0 and 1.  A bias of +0.2
    on expert 2 makes it take 0 and 2 — weighed 0.8 and 0.5 over 1.3,
    the scores WITHOUT the bias — times the scale."""
    scores = np.array([0.8, 0.6, 0.5, 0.2])
    x = jnp.asarray([[1.0, 0.0]], jnp.float32)
    router = jnp.asarray(np.stack([np.log(scores / (1 - scores)),
                                   np.zeros(4)], 1), jnp.float32)
    w, e = moe._route_top_k(x, router, 2, True, "sigmoid", None, 2.5)
    assert sorted(np.asarray(e)[0]) == [0, 1]
    assert np.allclose(sorted(np.asarray(w)[0]),
                       [2.5 * 0.6 / 1.4, 2.5 * 0.8 / 1.4], atol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.2, 0.0], jnp.float32)
    w, e = moe._route_top_k(x, router, 2, True, "sigmoid", bias, 2.5)
    order = np.argsort(np.asarray(e)[0])
    assert list(np.asarray(e)[0][order]) == [0, 2]
    assert np.allclose(np.asarray(w)[0][order],
                       [2.5 * 0.8 / 1.3, 2.5 * 0.5 / 1.3], atol=1e-6)
    # not renormalised, no scale: the plain scores
    w, e = moe._route_top_k(x, router, 2, False, "sigmoid", bias)
    assert np.allclose(np.asarray(w)[0][np.argsort(np.asarray(e)[0])],
                       [0.8, 0.5], atol=1e-6)
    # a softmax router takes the same bias
    w, e = moe._route_top_k(x, router, 1, True, "softmax",
                            jnp.asarray([0.0, 0.0, 0.0, 9.0]))
    assert int(e[0, 0]) == 3 and float(w[0, 0]) == pytest.approx(1.0)
    with pytest.raises(mx.base.MXNetError, match="scoring"):
        moe._route_top_k(x, router, 2, scoring="tanh")


@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_weights_are_top_ks_values_read_at_the_choice(scoring, norm_topk):
    """Without a bias the router took ``lax.top_k``'s values; now it
    reads the scores at ``lax.top_k``'s indices, as it always did under
    a bias (so a layer that keeps the choice across its checkpoint
    needs no second top-k for the values) — by a masked sum, where it
    gathered: the same weights bit for bit, and the same gradient to the
    router and to the tokens."""
    from jax import lax
    rng = np.random.default_rng(41)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 32)) * 0.3, jnp.float32)
    mix = jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)

    def by_values(x, router):
        logits = jnp.einsum("tu,eu->te", x, router,
                            preferred_element_type=jnp.float32)
        scores = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
            else jax.nn.sigmoid(logits)
        weights, experts = lax.top_k(scores, 4)
        if norm_topk:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights * 2.5, experts

    def at_the_choice(x, router):
        return moe._route_top_k(x, router, 4, norm_topk, scoring, None, 2.5)

    for a, b in zip(at_the_choice(x, router), by_values(x, router)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    grads = [jax.grad(lambda x, r: jnp.sum(route(x, r)[0] * mix), (0, 1))(
        x, router) for route in (at_the_choice, by_values)]
    for a, b in zip(*grads):
        assert np.any(np.asarray(a)) and np.array_equal(np.asarray(a),
                                                        np.asarray(b))


def _sparse_part(ref, rng, routed=16, tokens=64):
    cfg = dict(CONFIG, n_routed_experts=routed,
               deployment={"experts_held": [0, routed]},
               published={"n_routed_experts": routed},
               num_experts_per_tok=4)
    u, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h = jnp.asarray(rng.normal(size=(1, tokens, u)), jnp.float32)
    mat = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.1,
                                     jnp.float32)
    whole = {"router_weight": mat(routed, u) * 3,
             "router_bias": jnp.asarray(rng.normal(size=(routed,)) * 0.05,
                                        jnp.float32),
             "gate_weight": mat(routed, f, u), "up_weight": mat(routed, f, u),
             "down_weight": mat(routed, u, f),
             "shared_gate_weight": mat(f, u), "shared_up_weight": mat(f, u),
             "shared_down_weight": mat(u, f)}
    return cfg, h, whole


def test_the_shares_add_up_with_the_shared_expert_counted_once(ref):
    """Eight chips' routed partial results — each its own two experts of
    sixteen, every one under the whole router and the whole bias — plus
    the shared expert, which every chip computes alike, ONCE: the uncut
    reference layer.  (Summing the shares' whole layers would count the
    shared expert eight times.)"""
    rng = np.random.default_rng(12)
    cfg, h, whole = _sparse_part(ref, rng)
    stacked = ("gate_weight", "up_weight", "down_weight")
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_layer(h, whole, cfg)
        shared = ref.expert_layer(h, whole, cfg) \
            - ref.expert_layer(h, whole, cfg, shared=False)
        parts, ref_parts = [], []
        for first in range(0, 16, 2):
            p = {k: v[first:first + 2] if k in stacked else v
                 for k, v in whole.items()}
            parts.append(moe.routed_experts(
                h[0], p["router_weight"], tuple(p[k] for k in stacked), 4,
                (first, 2), scoring="sigmoid", bias=p["router_bias"],
                scale=2.5)[None])
            ref_parts.append(ref.expert_layer(
                h, p, dict(cfg, n_routed_experts=2, deployment={
                    "experts_held": [first, first + 2]}), shared=False))
    scale = float(jnp.abs(uncut).max())
    assert float(jnp.abs(shared).max()) > 0.01 * scale
    assert float(jnp.abs(sum(parts) + shared - uncut).max()) <= 1e-5 * scale
    assert float(jnp.abs(sum(ref_parts) + shared - uncut).max()) \
        <= 1e-5 * scale
    for got, want in zip(parts, ref_parts):
        assert float(jnp.abs(got - want).max()) <= 1e-5 * scale


# ---------------------------------------------------------------------------
# the prediction modules' targets
# ---------------------------------------------------------------------------
def test_mtp_targets_on_a_hand_made_sequence(ref):
    """Tokens 5 1 4 2 3 0 (the label of a position the next token): the
    next-token term is held to 1 4 2 3 0, module 1 to 4 2 3 0 over the
    positions that have one, module 2 to 2 3 0.  A state that is the
    one-hot of its target under an identity head costs nothing; the one
    position (two) past the end is not read."""
    from mxnet_tpu.gluon.loss import MultiTokenCELoss
    seq = np.array([[5, 1, 4, 2, 3, 0]])
    tokens, label = seq[:, :-1], seq[:, 1:]
    eye = 50.0 * np.eye(6, dtype="f")
    onehot = lambda ids: np.eye(6, dtype="f")[ids]
    states = onehot(label)                                      # (1, 5, 6)
    junk = 3                     # what the states past the end point at
    mtp = np.stack([onehot(np.array([[4, 2, 3, 0, junk]])),
                    onehot(np.array([[2, 3, 0, junk, junk]]))], axis=1)
    loss = MultiTokenCELoss(mtp_weight=0.3)
    loss.head_weight.shape = (6, 6)
    loss.initialize(mx.init.Zero(), ctx=mx.cpu())
    loss.head_weight.set_data(nd.array(eye))
    got = float(loss(nd.array(states), nd.array(mtp),
                     nd.array(label.astype("f"))).asnumpy()[0])
    assert got < 1e-6
    # held to the NEXT token instead (shifted by one, not two): module 1
    # is wrong at every position that differs
    wrong = np.stack([onehot(label), onehot(np.array([[2, 3, 0, junk,
                                                       junk]]))], axis=1)
    bad = float(loss(nd.array(states), nd.array(wrong),
                     nd.array(label.astype("f"))).asnumpy()[0])
    assert bad == pytest.approx(0.3 / 2 * 50.0, rel=1e-3)
    # the reference names the same targets, and what each module reads
    for k, (reads, target) in enumerate(
            [([1, 4, 2, 3], [4, 2, 3, 0]), ([4, 2, 3], [2, 3, 0])], 1):
        nxt, tgt, mask = ref.shifted(jnp.asarray(tokens), jnp.asarray(label),
                                     k)
        assert list(np.asarray(mask)) == [1.0] * (5 - k) + [0.0] * k
        assert list(np.asarray(nxt)[0, :5 - k]) == reads
        assert list(np.asarray(tgt)[0, :5 - k]) == target


def test_scale_gradient_leaves_the_value_and_scales_the_gradient():
    x = nd.array(np.arange(6, dtype="f").reshape(2, 3))
    x.attach_grad()
    with autograd.record():
        y = nd.contrib.scale_gradient(x, scale=0.3)
        loss = (y * y).sum()
    loss.backward()
    assert np.array_equal(y.asnumpy(), x.asnumpy())
    assert np.allclose(x.grad.asnumpy(), 0.3 * 2 * x.asnumpy(), rtol=1e-6)
    # a bfloat16 cotangent stays bfloat16
    g = jax.grad(lambda a: jnp.sum(
        ops_contrib._scale_gradient(a, scale=0.5).astype(jnp.float32)))(
            jnp.ones((4,), jnp.bfloat16))
    assert g.dtype == jnp.bfloat16 and float(g[0]) == 0.5


# ---------------------------------------------------------------------------
# the published leaves in the optimizer's buckets
# ---------------------------------------------------------------------------
def test_stacked_expert_leaves_ride_native_buckets():
    """A chip's sixteen experts are three stacked leaves a layer, ``(16,
    768, 2048)`` twice and ``(16, 2048, 768)``: each a bucket of its own
    that keeps its layout — ``(12288, 2048)`` and ``(32768, 768)`` rows
    the sweep tiles as they lie — like every matrix of latent attention;
    gains and the held bias' neighbours fall into flat buckets."""
    from mxnet_tpu.parallel.collectives import build_bucket_plan
    from mxnet_tpu import config as knobs
    net = LatentMoELM(16160, units=2048, dense_width=7168, expert_width=768,
                      mlp_layer_types=("dense", "sparse"), num_heads=32,
                      q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
                      v_dim=128, num_routed=256, held=(0, 16), top_k=8,
                      mtp_depth=1)
    shapes = {k[len(net.prefix):]: p.shape
              for k, p in net.collect_params().items()
              if p.grad_req != "null"}
    plan = build_bucket_plan(
        list(shapes), list(shapes.values()),
        knobs.get("MXNET_PARALLEL_BUCKET_BYTES"),
        knobs.get("MXNET_PARALLEL_BUCKET_FIRST_BYTES"), native=True)
    by_leaf = {b.names[0]: b for b in plan if len(b.names) == 1}
    for name, rows_c in (("l1_gate_weight", (12288, 2048)),
                         ("l1_up_weight", (12288, 2048)),
                         ("l1_down_weight", (32768, 768)),
                         ("mtp0_gate_weight", (12288, 2048)),
                         ("l1_q_b_weight", (6144, 1536)),
                         ("l1_kv_a_weight", (576, 2048)),
                         ("l1_kv_b_weight", (8192, 512)),
                         ("l0_gate_weight", (7168, 2048)),
                         ("mtp0_proj_weight", (2048, 4096)),
                         ("l1_shared_down_weight", (2048, 768)),
                         ("head_weight", (16160, 2048))):
        assert by_leaf[name].layout == "native", name
        assert by_leaf[name].buffer_shape == rows_c, name
    # what is left to flat buckets is small: the gains and the routers,
    # which share buckets with them
    flat = [n for b in plan if b.layout == "flat" for n in b.names]
    assert flat and sum(int(np.prod(shapes[n])) for n in flat) \
        < 0.005 * sum(int(np.prod(sh)) for sh in shapes.values()), flat


# ---------------------------------------------------------------------------
# what was there stays what it was
# ---------------------------------------------------------------------------
def _moe_lm_step_text():
    """The optimized module of a small ``MoELM`` step, instruction by
    instruction, without the metadata (which names the Python frames a
    call came through)."""
    import re
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh
    net = MoELM(64, units=32, expert_width=16, num_heads=4, num_kv_heads=2,
                num_routed=4, held=(0, 2), top_k=2, window=4,
                prefix="same_")
    net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    trainer = ParallelTrainer(
        net, net.lm_loss(), "adam", {"learning_rate": 1e-3},
        mesh=make_mesh(dp=1, devices=jax.devices()[:1]), zero=2,
        dtype="bfloat16")
    rng = np.random.default_rng(0)
    telemetry.enable()
    try:
        trainer.step(nd.array(rng.integers(0, 64, (2, 8)), dtype="int32"),
                     nd.array(rng.integers(0, 64, (2, 8)).astype("f")))
        text = telemetry.program_hlo("step")
    finally:
        telemetry.disable()
    lines = [re.sub(r", metadata=\{[^}]*\}", "", line)
             for line in text.splitlines()
             if re.match(r"^\s+(?:ROOT )?%[\w.\-]+ = ", line)]
    assert len(lines) > 500
    return lines


def test_moe_lm_step_program_is_unchanged_by_the_new_arguments(monkeypatch):
    """``MoELM``'s step — Mellum2's program at a small size — compiles to
    the same optimized module whether its expert layer, rotary and
    attention are called as they always were or with every argument this
    block added spelled out at its default: the new data adds no
    instruction to the old path."""
    plain = _moe_lm_step_text()
    real_experts, real_rotary = moe.routed_experts, _rotary_embedding
    seen = []

    def experts(*args, **kwargs):
        seen.append("experts")
        return real_experts(*args, scoring="softmax", bias=None, scale=1.0,
                            **kwargs)

    def rotary(*args, **kwargs):
        seen.append("rotary")
        return real_rotary(*args, interleaved=False, **kwargs)

    monkeypatch.setattr(moe, "routed_experts", experts)
    monkeypatch.setattr(ops_contrib, "_rotary_embedding", rotary)
    spelled = _moe_lm_step_text()
    assert {"experts", "rotary"} <= set(seen)
    assert spelled == plain


def test_a_rematerialised_layer_keeps_its_flash_results(ref,
                                                        check_flash_kept):
    """The dense layer, one sparse layer and the prediction module's own
    layer: three flash calls at keys of 24 over values of 16
    (``check_flash_kept``, shared with the other block whose layers
    keep them)."""
    config = dict(CONFIG, num_hidden_layers=2)
    weights = ref.init_weights(config, 2 ** 31 + 35)
    net = _block(ref, config, weights)
    tokens = jnp.asarray(_batch(35)[0])

    def loss(params):
        states, mtp_states = transformer.latent_moe_lm_forward(
            params, tokens, **net._config)
        return jnp.mean(states ** 2) + jnp.mean(mtp_states ** 2)

    check_flash_kept(loss, {k: jnp.asarray(v) for k, v in weights.items()
                            if k != "head_weight"}, 3)


@pytest.mark.parametrize("block", ["MoELM", "LatentMoELM"])
def test_a_rematerialised_layer_keeps_its_routing(block, check_route_kept):
    """``MoELM`` (softmax scores, no bias; two layers, both routed) and
    ``LatentMoELM`` (sigmoid scores, a selection bias; a dense layer, a
    routed one and the prediction module's own): two routed layers each,
    192 tokens taking 2 of 8 experts, 4 of them held
    (``check_route_kept``)."""
    if block == "MoELM":
        net = MoELM(256, units=128, expert_width=64, num_heads=4,
                    num_kv_heads=2, num_routed=8, held=(2, 4), top_k=2)
        forward = transformer.moe_lm_forward
    else:
        net = LatentMoELM(256, units=128, dense_width=192, expert_width=64,
                          num_routed=8, held=(2, 4), top_k=2,
                          route_scale=2.5, mtp_depth=1)
        forward = transformer.latent_moe_lm_forward
    net.initialize(mx.init.Normal(0.05), ctx=mx.cpu())
    rng = np.random.default_rng(37)
    tokens = jnp.asarray(rng.integers(0, 256, (2, 96)))
    net(nd.array(np.asarray(tokens), dtype="int32"))    # deferred shapes
    short = len(net.prefix)
    params = {name[short:]: p.data()._data
              for name, p in net.collect_params().items()
              if not name.endswith("head_weight")}
    for name in params:
        if name.endswith("router_bias"):    # one that changes the choice
            params[name] = jnp.asarray(rng.normal(size=8) * 0.05,
                                       jnp.float32)

    def loss(p):
        return sum(jnp.mean(states ** 2) for states in
                   jax.tree_util.tree_leaves(forward(p, tokens,
                                                     **net._config)))

    check_route_kept(loss, params, layers=2, tokens=192, top_k=2, held=4,
                     bias=8 * (block == "LatentMoELM"))


def test_the_block_says_what_it_is_in_gauges():
    from mxnet_tpu import telemetry
    telemetry.enable()
    try:
        net = LatentMoELM(64, units=32, dense_width=48, expert_width=16,
                          mlp_layer_types=("dense", "sparse", "sparse"),
                          num_heads=4, q_rank=24, kv_rank=16, nope_dim=8,
                          rope_dim=4, v_dim=8, num_routed=256, held=(0, 16),
                          top_k=8, route_scale=2.5, mtp_depth=1)
        # what a forward over 1 x 8192 tokens exports beside them
        transformer._export_expert_rows("LatentMoELM", 8192, 8, (0, 16), 256)
        g = lambda name, **labels: telemetry.gauge(name).labels(
            **labels).value
        assert g("mxnet_moe_experts", which="published") == 256
        assert g("mxnet_moe_experts", which="held") == 16
        assert g("mxnet_moe_top_k") == 8
        assert g("mxnet_moe_scoring", scoring="sigmoid") == 1
        assert g("mxnet_moe_scoring", scoring="softmax") == 0
        assert g("mxnet_mlp_layers", kind="dense") == 1
        assert g("mxnet_mlp_layers", kind="sparse") == 2
        assert g("mxnet_mtp_depth") == 1
        # 8192 tokens x 8 slots x 16 of 256: 256 rows an expert
        assert g("mxnet_moe_expected_rows") == 16 * 256
        assert g("mxnet_moe_slot_rows") == 65536
    finally:
        telemetry.disable()


def test_a_block_without_modules_or_shared_expert_has_one_output():
    net = LatentMoELM(64, units=32, dense_width=48, expert_width=16,
                      mlp_layer_types=("sparse",), num_heads=4, q_rank=24,
                      kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
                      num_routed=4, held=(1, 2), top_k=2, shared_experts=0,
                      selection_bias=False, scoring="softmax")
    names = [k[len(net.prefix):] for k in net.collect_params()]
    assert not [n for n in names if "shared" in n or "bias" in n
                or n.startswith("mtp")]
    net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    tokens = nd.array(np.arange(16).reshape(2, 8), dtype="int32")
    with autograd.record():
        states = net(tokens)
        loss = net.lm_loss()(states, nd.array(np.ones((2, 8), "f"))).mean()
    loss.backward()
    assert states.shape == (2, 8, 32) and np.isfinite(float(loss.asnumpy()))
    assert net.logits(states).shape == (2, 8, 64)
    with pytest.raises(ValueError, match="mlp_layer_types"):
        LatentMoELM(64, mlp_layer_types=("moe",))
