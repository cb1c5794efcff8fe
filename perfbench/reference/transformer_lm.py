"""Plain reference: a decoder-only pre-LN transformer language model
(the block of OPT, Zhang et al., arXiv:2205.01068) trained by Adam,
float32 at the highest matmul precision, jax.numpy only.

It imports nothing of the program and is handed nothing the program
made.  The model is ``gluon.contrib.transformer.TransformerLM`` as it
stands, written down again: token + learned position embeddings, N
blocks of ``x + Attn(LN(x))``, ``x + FFN(LN(x))`` with causal softmax
attention (no biases on q, k, v, out), a ReLU FFN with biases, a final
LayerNorm, and an untied head with a bias.  LayerNorm eps 1e-5.  The
loss is the mean cross-entropy of the next token over every position.

Leaves are named in the block's construction order
(``embed_weight``, ``pos_weight``, ``l0_ln1_gamma`` …); the driver
matches them to the block's parameters by order and shape.

Each block is rematerialised (``jax.checkpoint``): memory, not
arithmetic.  ``precision="fp8"`` is the CONTROL, the step below the bf16
the configuration states: both operands of every matrix product
(projections, attention scores and values, FFN, head) rounded to float8
e4m3 on the way forward and the gradient arriving at each product's
output rounded to e5m2 on the way back (the two formats of fp8
training), each with one scale per tensor.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

from reference_common import (WEIGHT_STREAM, fp8, fp8_grad, seed_key,  # noqa: F401
                              wd_mult)

HI = lax.Precision.HIGHEST
LN_EPS = 1e-5


def leaf_specs(config):
    """Ordered [(name, shape, init)] of every trainable leaf."""
    v, u = int(config["vocab_size"]), int(config["hidden_size"])
    f, t = int(config["ffn_dim"]), int(config["max_position_embeddings"])
    specs = [("embed_weight", (v, u), "normal"),
             ("pos_weight", (t, u), "normal")]
    for i in range(int(config["num_hidden_layers"])):
        p = "l%d_" % i
        specs += [(p + "ln1_gamma", (u,), "one"), (p + "ln1_beta", (u,), "zero"),
                  (p + "q_weight", (u, u), "normal"),
                  (p + "k_weight", (u, u), "normal"),
                  (p + "v_weight", (u, u), "normal"),
                  (p + "out_weight", (u, u), "normal"),
                  (p + "ln2_gamma", (u,), "one"), (p + "ln2_beta", (u,), "zero"),
                  (p + "ffn1_weight", (f, u), "normal"),
                  (p + "ffn1_bias", (f,), "zero"),
                  (p + "ffn2_weight", (u, f), "normal"),
                  (p + "ffn2_bias", (u,), "zero")]
    specs += [("lnf_gamma", (u,), "one"), ("lnf_beta", (u,), "zero"),
              ("head_weight", (v, u), "normal"), ("head_bias", (v,), "zero")]
    return specs


def init_weights(config, seed):
    """Normal(0, init_std) matrices and embeddings (OPT's init_std
    0.02), gamma 1, beta and biases 0, float32 — made on the device in
    ONE jitted call from the seed (half a billion normals take the host
    tens of seconds), then read back once: the program and the
    reference are both handed these host arrays."""
    std = float(config["init_std"])
    specs = leaf_specs(config)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            if init == "normal":
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                out[name] = jnp.full(shape, 1.0 if init == "one" else 0.0,
                                     jnp.float32)
        return out

    made = jax.device_get(make(seed_key(seed, WEIGHT_STREAM)))
    return {name: made[name] for name, _shape, _init in specs}  # in order


def _mm(a, b, spec, quant):
    if quant:
        return fp8_grad(jnp.einsum(spec, fp8(a), fp8(b), precision=HI))
    return jnp.einsum(spec, a, b, precision=HI)


def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * g + b


def _block(x, p, heads, quant):
    b, t, u = x.shape
    d = u // heads
    h = _ln(x, p["ln1_gamma"], p["ln1_beta"])
    q = _mm(h, p["q_weight"], "btu,ou->bto", quant).reshape(b, t, heads, d)
    k = _mm(h, p["k_weight"], "btu,ou->bto", quant).reshape(b, t, heads, d)
    v = _mm(h, p["v_weight"], "btu,ou->bto", quant).reshape(b, t, heads, d)
    s = _mm(q, k, "bqhd,bkhd->bhqk", quant) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), bool))
    pr = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
    o = _mm(pr, v, "bhqk,bkhd->bqhd", quant).reshape(b, t, u)
    x = x + _mm(o, p["out_weight"], "btu,ou->bto", quant)
    h = _ln(x, p["ln2_gamma"], p["ln2_beta"])
    h = jax.nn.relu(_mm(h, p["ffn1_weight"], "btu,fu->btf", quant)
                    + p["ffn1_bias"])
    return x + _mm(h, p["ffn2_weight"], "btf,uf->btu", quant) \
        + p["ffn2_bias"]


def loss_fn(params, tokens, labels, config, quant=False):
    heads = int(config["num_attention_heads"])
    t = tokens.shape[1]
    x = params["embed_weight"][tokens.astype(jnp.int32)] \
        + params["pos_weight"][:t][None]
    for i in range(int(config["num_hidden_layers"])):
        pre = "l%d_" % i
        sub = {k[len(pre):]: v for k, v in params.items()
               if k.startswith(pre)}
        x = jax.checkpoint(
            lambda x_, sub_: _block(x_, sub_, heads, quant))(x, sub)
    x = _ln(x, params["lnf_gamma"], params["lnf_beta"])
    logits = _mm(x, params["head_weight"], "btu,vu->btv", quant) \
        + params["head_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked)


def train_steps(config, weights, batches, precision="reference",
                rows=None, devices=None):
    """Follow ``len(batches)`` steps of Adam from ``weights``; returns
    ``{"loss": [...], "grad1": {leaf: norm}, "dparam": {leaf: norm}}``.
    ``rows`` (a slice) plants the fault "part of the batch left out".
    It runs on the first of ``devices``."""
    opt = config["optimizer"]
    lr, b1, b2 = (float(opt["learning_rate"]), float(opt["beta1"]),
                  float(opt["beta2"]))
    eps, wd = float(opt["epsilon"]), float(opt["wd"])
    quant = precision == "fp8"
    if precision not in ("reference", "fp8"):
        raise ValueError("unknown precision %r" % precision)

    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}

    def step(params, mean, var, t, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, config,
                                                  quant)
        gn = norms(grads)
        t = t + 1
        coef = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        new_p, new_m, new_v = {}, {}, {}
        for k in params:
            g = grads[k] + wd * wd_mult(k, config) * params[k]
            new_m[k] = b1 * mean[k] + (1 - b1) * g
            new_v[k] = b2 * var[k] + (1 - b2) * jnp.square(g)
            new_p[k] = params[k] - lr * coef * new_m[k] \
                / (jnp.sqrt(new_v[k]) + eps)
        return new_p, new_m, new_v, t, loss, gn

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))

    with jax.default_matmul_precision("highest"):
        params = {k: jnp.asarray(v) for k, v in weights.items()}
        mean = {k: jnp.zeros(v.shape, jnp.float32)
                for k, v in weights.items()}
        var = {k: jnp.zeros(v.shape, jnp.float32)
               for k, v in weights.items()}
        t = jnp.zeros((), jnp.float32)
        out = {"loss": []}
        for i, (x, y) in enumerate(batches):
            if rows is not None:
                x, y = x[rows], y[rows]
            params, mean, var, t, loss, gn = step(
                params, mean, var, t, jnp.asarray(x), jnp.asarray(y))
            out["loss"].append(float(loss))
            if i == 0:
                out["grad1"] = {k: float(v) for k, v in gn.items()}
        del mean, var
        out["dparam"] = {k: float(diff(params[k], jnp.asarray(weights[k])))
                         for k in weights}
    return out
