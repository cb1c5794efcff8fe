"""The decoder-hybrid-decoder block (``SambaYLM``: Mamba mixers,
differential window / full / cross attention, gated memory units) and its
selective scan on the CPU (small sizes, float32, seeded weights):

- the scan's Pallas kernel pair in interpret mode against the plain
  ``lax.scan``, forward and every operand's gradient, with rows that are
  not a whole number of chunks and several channel blocks;
- differential attention (window, full, cross) against explicit softmax
  maps, and its one flash call in interpret mode against the einsum form;
- the block's loss, logits and every leaf's gradient against
  ``perfbench/reference/sambay_lm.py`` (and the reference's walk in
  pieces against its whole loss), the boundary layers' leaves among them,
  whose gradient comes also from the layers that read what they wrote;
- a layer keeps its flash and scan results across its checkpoint.
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
from mxnet_tpu.gluon.contrib import transformer
from mxnet_tpu.gluon.contrib.transformer import (SambaYLM,
                                                 differential_attention)
from mxnet_tpu.ops import contrib
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import attention as attn_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PB = os.path.join(ROOT, "perfbench")

CONFIG = {
    "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "sliding_window": 12, "layer_norm_eps": 1e-5,
    "tie_word_embeddings": True, "mlp_bias": False,
    "num_hidden_layers": 5, "first_layer": 15,
    "layer_kinds": ["mamba", "sliding_attention", "full_attention", "gmu",
                    "cross_attention"],
    "assumed": {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2},
    "init_std": 0.1, "residual_init_std": 0.1, "seq_len": 40,
    "batch_size": 2}


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, PB)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_sambay_lm", os.path.join(PB, "reference",
                                                "sambay_lm.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.path.remove(PB)


def _block(ref, config, weights):
    s = ref.sizes(config)
    net = SambaYLM(s["vocab"], units=s["units"], hidden_size=s["dense"],
                   layer_types=s["kinds"], num_heads=s["heads"],
                   num_kv_heads=s["kv_heads"], window=s["window"],
                   state_size=s["state"], conv_kernel=s["conv"],
                   first_layer=s["first"], epsilon=s["eps"])
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    params = net.collect_params()
    assert len(list(params.keys())) == len(weights)
    for (pname, p), (rname, w) in zip(params.items(), weights.items()):
        assert pname.endswith(rname) and tuple(p.shape) == w.shape, rname
        p.set_data(nd.array(w))
    return net


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------
def _scan_operands(bs, t, e, n, seed, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(k[0], (bs, t, e)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(k[1], (bs, t, e)) - 2.0)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (e, n)))
    b = jax.random.normal(k[3], (bs, t, n)).astype(dtype)
    c = jax.random.normal(k[4], (bs, t, n)).astype(dtype)
    d = jax.random.normal(k[5], (e,))
    return (u, delta, a, b, c, d), jax.random.normal(k[6], (bs, t, e))


@pytest.mark.parametrize("bs,t,e", [(2, 37, 64), (1, 130, 384)],
                         ids=["one-short-chunk", "ragged-chunks-2-blocks"])
def test_the_scan_kernels_match_the_plain_scan(bs, t, e):
    ops, dy = _scan_operands(bs, t, e, 16, t)
    tc, tp, eb = pk.ssm_blocks(t, e, "bwd")
    assert tp % tc == 0 and (t % tc or tp > t)     # a padded last chunk
    y1, back1 = jax.vjp(pk.selective_scan, *ops)
    y2, back2 = jax.vjp(contrib._selective_scan_plain, *ops)
    assert float(jnp.abs(y1 - y2).max()) <= 1e-5 * float(jnp.abs(y2).max())
    for name, g1, g2 in zip("u delta a b c d".split(), back1(dy), back2(dy)):
        assert g1.shape == g2.shape, name
        scale = float(jnp.abs(g2).max())
        assert float(jnp.abs(g1 - g2).max()) <= 1e-5 * scale, name


# ---------------------------------------------------------------------------
# differential attention
# ---------------------------------------------------------------------------
def _diff_leaves(rng, u, hq, hkv, d, cross):
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
    p = {"lambda_%s_weight" % n: w(d) for n in ("q1", "k1", "q2", "k2")}
    p.update(subln_gamma=1.0 + w(2 * d), out_weight=w(u, hq * d),
             out_bias=w(u))
    if cross:
        p.update(q_weight=w(hq * d, u), q_bias=w(hq * d),
                 shared_k=w(2, 24, hkv, d), shared_v=w(2, 24, hkv, d))
    else:
        p.update(qkv_weight=w((hq + 2 * hkv) * d, u),
                 qkv_bias=w((hq + 2 * hkv) * d))
    return p


def _explicit(h, p, hq, hkv, lam0, window, cross):
    """Both softmax maps of every query pair, written out."""
    b, t, _u = h.shape
    d = p["lambda_q1_weight"].shape[0]
    if cross:
        q = h @ p["q_weight"].T + p["q_bias"]
        k, v = p["shared_k"], p["shared_v"]
    else:
        qkv = h @ p["qkv_weight"].T + p["qkv_bias"]
        q = qkv[..., :hq * d]
        k = qkv[..., hq * d:(hq + hkv) * d].reshape(b, t, hkv, d)
        v = qkv[..., (hq + hkv) * d:].reshape(b, t, hkv, d)
    q = q.reshape(b, t, hq, d)
    lam = math.exp(float(jnp.sum(p["lambda_q1_weight"]
                                 * p["lambda_k1_weight"]))) \
        - math.exp(float(jnp.sum(p["lambda_q2_weight"]
                                 * p["lambda_k2_weight"]))) + lam0
    pos = np.arange(t)
    see = pos[None, :] <= pos[:, None]
    if window is not None:
        see &= pos[:, None] - pos[None, :] < window
    outs = []
    for i in range(hq // 2):
        j = i // 2
        vj = jnp.concatenate([v[:, :, 2 * j], v[:, :, 2 * j + 1]], -1)
        maps = []
        for m in range(2):
            s = jnp.einsum("bqd,bkd->bqk", q[:, :, 2 * i + m],
                           k[:, :, 2 * j + m]) / math.sqrt(d)
            maps.append(jax.nn.softmax(jnp.where(see, s, -1e30), -1) @ vj)
        o = maps[0] - lam * maps[1]
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        outs.append(o * p["subln_gamma"] * (1 - lam0))
    return jnp.concatenate(outs, -1) @ p["out_weight"].T + p["out_bias"]


@pytest.mark.parametrize("kind", ["window", "full", "cross"])
@pytest.mark.parametrize("path", ["einsum", "flash"])
def test_differential_attention_against_explicit_maps(kind, path,
                                                      monkeypatch):
    if path == "flash":
        monkeypatch.setattr(attn_mod, "_flash_eligible", lambda *a: True)
    rng = np.random.default_rng(7)
    hq, hkv, d, u = 8, 4, 16, 32
    p = _diff_leaves(rng, u, hq, hkv, d, kind == "cross")
    h = jnp.asarray(rng.normal(size=(2, 24, u)), jnp.float32)
    window = 5 if kind == "window" else None
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * 17)
    with jax.default_matmul_precision("highest"):
        got = differential_attention(
            h, p, num_heads=hq, num_kv_heads=hkv, lambda_init=lam0,
            window=window, cross=kind == "cross")
        want = _explicit(h, p, hq, hkv, lam0, window, kind == "cross")
    assert float(jnp.abs(got - want).max()) <= 2e-5 * float(
        jnp.abs(want).max())


# ---------------------------------------------------------------------------
# the block against the reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def against_reference(ref):
    weights = ref.init_weights(CONFIG, 2 ** 31 + 45)
    net = _block(ref, CONFIG, weights)
    tokens = np.random.default_rng(45).integers(0, 128, (2, 25))
    x, y = tokens[:, :-1], tokens[:, 1:]
    with autograd.record():
        states = net(nd.array(x, dtype="int32"))
        loss = net.lm_loss()(states, nd.array(y.astype("f"))).mean()
    loss.backward()
    params = {k: jnp.asarray(v) for k, v in weights.items()}
    blocks = ref.Q_BLOCK, ref.SCAN_BLOCK, ref.ROW_BLOCK
    ref.Q_BLOCK, ref.SCAN_BLOCK, ref.ROW_BLOCK = 16, 80, 16  # ragged
    try:
        with jax.default_matmul_precision("highest"):
            want, grads = jax.value_and_grad(ref.loss_fn)(
                params, jnp.asarray(x), jnp.asarray(y), CONFIG)
            walked, walked_grads = ref.loss_and_grads(
                params, jnp.asarray(x), jnp.asarray(y), CONFIG)
            logits = ref.forward(params, jnp.asarray(x), jnp.asarray(y),
                                 CONFIG)[0]
    finally:
        ref.Q_BLOCK, ref.SCAN_BLOCK, ref.ROW_BLOCK = blocks
    got = {r: p.grad().asnumpy()
           for p, r in zip(net.collect_params().values(), weights)}
    return {"loss": (float(loss.asnumpy()), float(want), float(walked)),
            "logits": (net.logits(states).asnumpy()[:, -1],
                       np.asarray(logits)[:, -1]),
            "grads": (got, grads, walked_grads)}


def test_sambay_loss_matches_the_reference(against_reference):
    got, want, walked = against_reference["loss"]
    assert got == pytest.approx(want, rel=2e-6)
    assert walked == pytest.approx(want, rel=2e-6)


def test_sambay_last_position_logits_match_the_reference(against_reference):
    got, want = against_reference["logits"]
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_sambay_every_leafs_gradient_matches_the_reference(
        against_reference):
    got, want, walked = against_reference["grads"]
    assert set(got) == set(want)
    for name, g in want.items():
        scale = float(jnp.abs(g).max())
        assert scale > 0, name
        assert np.abs(got[name] - np.asarray(g)).max() <= 2e-5 * scale, name
        assert np.abs(np.asarray(walked[name] - g)).max() <= 2e-5 * scale, \
            name
