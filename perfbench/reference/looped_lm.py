"""Plain reference: a LOOPED decoder-only language model — one stack of
layers applied ``total_ut_steps`` times with the same weights, an exit
(final norm, LM head, one-output gate) after every pass — trained by
Adam on the exit-weighted objective, float32 at the highest matmul
precision, jax.numpy only.  The model is Ouro (ByteDance,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741)
as its ``config.json`` and the configuration file's ``assumed`` list give
it.

It imports nothing of the program and is handed nothing the program
made: no kernel, no ``checkpoint``, no ``scan`` — Python loops.

Layer (sandwich norm, four gains):
``x += RMS2(Attn(RMS1(x)))``, ``x += RMS4(Wd(silu(Wg RMS3(x)) * Wu RMS3(x)))``;
``Attn`` is causal multi-head attention with rotary q and k (half-split
pairing, ``rope_theta``), scale ``head_dim**-0.5``, no biases.
Model: ``h0 = E[tokens]``; for t = 1..P: ``ht = RMSf(stack(h(t-1)))`` —
the normed state is what enters the next pass —, ``logits_t = Wh ht``,
``g_t = w_gate . ht + b_gate``.
Loss: ``lam_t = sigmoid(g_t)``; ``p_t = lam_t prod_{j<t}(1 - lam_j)`` for
t < P and ``p_P = prod_{j<P}(1 - lam_j)``; mean over tokens of
``sum_t p_t CE_t - beta H(p)``.

:func:`loss_fn` is the whole of it in one function, for
``jax.value_and_grad`` at sizes where everything fits (the CPU tests).
At the timed sizes 32 layer applications in float32 with their
``(heads, T, T)`` probabilities do not fit beside the parameters, their
gradients and Adam's two slots, so :func:`loss_and_grads` computes the
same numbers IN BLOCKS: it keeps the state that enters each layer
application, and walks back one application (and one exit) at a time
with ``jax.vjp``, adding up each shared weight's contributions.  A test
holds the two to each other.

``precision="fp8"`` is the CONTROL, the step below the bf16 the
configuration states (``reference_common.py``): both operands of every
matrix product rounded to e4m3, the gradient arriving at its output to
e5m2.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

from reference_common import (WEIGHT_STREAM, fp8, fp8_grad, seed_key,  # noqa: F401
                              wd_mult)

HI = lax.Precision.HIGHEST
LAYER_LEAVES = ("norm1_gamma", "q_weight", "k_weight", "v_weight",
                "out_weight", "norm2_gamma", "norm3_gamma", "gate_weight",
                "up_weight", "down_weight", "norm4_gamma")
EXIT_LEAVES = ("norm_gamma", "head_weight", "exit_weight", "exit_bias")


def _sizes(config):
    return (int(config["vocab_size"]), int(config["hidden_size"]),
            int(config["intermediate_size"]),
            int(config["num_hidden_layers"]),
            int(config["num_attention_heads"]),
            int(config["total_ut_steps"]))


def leaf_specs(config):
    """Ordered [(name, shape, init)] of every trainable leaf, in the
    block's construction order; a weight the passes share appears once."""
    v, u, f, n, _h, _p = _sizes(config)
    shape = {"norm1_gamma": (u,), "q_weight": (u, u), "k_weight": (u, u),
             "v_weight": (u, u), "out_weight": (u, u), "norm2_gamma": (u,),
             "norm3_gamma": (u,), "gate_weight": (f, u), "up_weight": (f, u),
             "down_weight": (u, f), "norm4_gamma": (u,)}
    specs = [("embed_weight", (v, u), "normal")]
    for i in range(n):
        specs += [("l%d_%s" % (i, k), shape[k],
                   "one" if k.endswith("gamma") else "normal")
                  for k in LAYER_LEAVES]
    specs += [("norm_gamma", (u,), "one"), ("head_weight", (v, u), "normal"),
              ("exit_weight", (1, u), "normal"), ("exit_bias", (1,), "zero")]
    return specs


def init_weights(config, seed):
    """Normal(0, init_std) matrices, embedding and gate, gains 1, the
    gate's bias 0, float32 — made on the device in ONE jitted call from
    the seed, then read back once."""
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


# -- the pieces ---------------------------------------------------------------
def _mm(a, b, spec, quant):
    if quant:
        return fp8_grad(jnp.einsum(spec, fp8(a), fp8(b), precision=HI))
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, T, H, D); pairs (i, i + D/2) turn by t * theta**(-2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + turned * jnp.sin(emb)


def _layer(x, p, config, quant):
    heads = int(config["num_attention_heads"])
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    b, t, u = x.shape
    d = u // heads
    h = _rms(x, p["norm1_gamma"], eps)
    q = _mm(h, p["q_weight"], "btu,ou->bto", quant).reshape(b, t, heads, d)
    k = _mm(h, p["k_weight"], "btu,ou->bto", quant).reshape(b, t, heads, d)
    v = _mm(h, p["v_weight"], "btu,ou->bto", quant).reshape(b, t, heads, d)
    q, k = _rope(q, theta), _rope(k, theta)
    s = _mm(q, k, "bqhd,bkhd->bhqk", quant) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), bool))
    pr = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
    o = _mm(pr, v, "bhqk,bkhd->bqhd", quant).reshape(b, t, u)
    x = x + _rms(_mm(o, p["out_weight"], "btu,ou->bto", quant),
                 p["norm2_gamma"], eps)
    h = _rms(x, p["norm3_gamma"], eps)
    h = jax.nn.silu(_mm(h, p["gate_weight"], "btu,fu->btf", quant)) \
        * _mm(h, p["up_weight"], "btu,fu->btf", quant)
    return x + _rms(_mm(h, p["down_weight"], "btf,uf->btu", quant),
                    p["norm4_gamma"], eps)


def _exit(x, p, labels, config, quant):
    """One exit over the stack's output ``x``: the normed state, the
    logits, each position's cross-entropy and gate logit."""
    h = _rms(x, p["norm_gamma"], float(config["rms_norm_eps"]))
    logits = _mm(h, p["head_weight"], "btu,vu->btv", quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels.astype(jnp.int32)[..., None],
                              axis=-1)[..., 0]
    gate = _mm(h, p["exit_weight"], "btu,ou->bto", quant)[..., 0] \
        + p["exit_bias"][0]
    return h, logits, ce, gate


def exit_distribution(gates):
    """``gates``: [P] arrays of gate logits -> [P] arrays of
    probabilities that sum to 1 (the last gate is not read)."""
    left, out = jnp.ones_like(gates[0]), []
    for g in gates[:-1]:
        lam = jax.nn.sigmoid(g)
        out.append(lam * left)
        left = left * (1.0 - lam)
    return out + [left]


def _objective(ces, gates, beta):
    p = exit_distribution(gates)
    expected = sum(pt * ce for pt, ce in zip(p, ces))
    entropy = -sum(pt * jnp.log(jnp.maximum(pt, 1e-30)) for pt in p)
    return jnp.mean(expected - beta * entropy)


def _layer_params(params, i):
    pre = "l%d_" % i
    return {k: params[pre + k] for k in LAYER_LEAVES}


def exits(params, tokens, labels, config, quant=False):
    """Every exit, whole: ``[(logits, ce, gate)]`` — what the tests hold
    the block's outputs to."""
    _v, _u, _f, n, _h, passes = _sizes(config)
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    out = []
    for _ in range(passes):
        for i in range(n):
            x = _layer(x, _layer_params(params, i), config, quant)
        x, logits, ce, gate = _exit(x, params, labels, config, quant)
        out.append((logits, ce, gate))
    return out


def loss_fn(params, tokens, labels, config, quant=False):
    per_exit = exits(params, tokens, labels, config, quant)
    return _objective([e[1] for e in per_exit], [e[2] for e in per_exit],
                      float(config["exit_entropy_beta"]))


# -- the same numbers in blocks ----------------------------------------------
def _blocks(config, quant):
    """The jitted pieces :func:`loss_and_grads` walks with."""
    beta = float(config["exit_entropy_beta"])

    def layer_fwd(x, p):
        return _layer(x, p, config, quant)

    def layer_bwd(x, p, ct):
        return jax.vjp(layer_fwd, x, p)[1](ct)

    def exit_fwd(x, p, labels):
        h, _logits, ce, gate = _exit(x, p, labels, config, quant)
        return h, ce, gate

    def exit_bwd(x, p, labels, cts):
        return jax.vjp(lambda x_, p_: exit_fwd(x_, p_, labels), x, p)[1](cts)

    def objective(ces, gates):
        return jax.value_and_grad(
            lambda c, g: _objective(c, g, beta), argnums=(0, 1))(ces, gates)

    def embed_bwd(shape, tokens, ct):
        return jnp.zeros(shape, jnp.float32).at[
            tokens.astype(jnp.int32)].add(ct)

    return {"layer_fwd": jax.jit(layer_fwd), "layer_bwd": jax.jit(layer_bwd),
            "exit_fwd": jax.jit(exit_fwd), "exit_bwd": jax.jit(exit_bwd),
            "objective": jax.jit(objective),
            "embed_bwd": jax.jit(embed_bwd, static_argnums=0)}


def described_programs(config, sds):
    """``(what, lowered)`` of the two largest programs the walk runs, the
    backward blocks, for ``rehearse_compile.py --reference``; ``sds(shape,
    dtype=float32)`` makes an argument on the described chip."""
    b, t, u = int(config["batch_size"]), int(config["seq_len"]), \
        int(config["hidden_size"])
    shapes = {n: s for n, s, _i in leaf_specs(config)}
    layer = {k: sds(shapes["l0_" + k]) for k in LAYER_LEAVES}
    exit_p = {k: sds(shapes[k]) for k in EXIT_LEAVES}
    x, bt = sds((b, t, u)), sds((b, t))
    fn = _blocks(config, False)
    print("the walk keeps %d states of %.3f GB beside parameters and "
          "gradients" % (int(config["total_ut_steps"])
                         * (int(config["num_hidden_layers"]) + 1),
                         4 * b * t * u / 1e9))
    yield "one layer application backward", fn["layer_bwd"].lower(x, layer, x)
    yield "one exit backward", fn["exit_bwd"].lower(x, exit_p, bt,
                                                    (x, bt, bt))


def loss_and_grads(params, tokens, labels, config, quant=False, blocks=None):
    """``(loss, {leaf: gradient})`` — :func:`loss_fn`'s value and
    gradient, one layer application and one exit at a time."""
    fn = blocks or _blocks(config, quant)
    _v, _u, _f, n, _h, passes = _sizes(config)
    layers = [_layer_params(params, i) for i in range(n)]
    exit_p = {k: params[k] for k in EXIT_LEAVES}
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    entered, left, ces, gates = [], [], [], []
    for _ in range(passes):
        entered.append([])
        for p in layers:
            entered[-1].append(x)
            x = fn["layer_fwd"](x, p)
        left.append(x)
        x, ce, gate = fn["exit_fwd"](x, exit_p, labels)
        ces.append(ce)
        gates.append(gate)
    loss, (d_ces, d_gates) = fn["objective"](ces, gates)

    grads = {}

    def add(name, g):
        grads[name] = grads[name] + g if name in grads else g

    ct = jnp.zeros_like(x)      # nothing reads the last exit's state
    for t in reversed(range(passes)):
        ct, d_exit = fn["exit_bwd"](left[t], exit_p, labels,
                                    (ct, d_ces[t], d_gates[t]))
        for k, g in d_exit.items():
            add(k, g)
        for i in reversed(range(n)):
            ct, d_layer = fn["layer_bwd"](entered[t][i], layers[i], ct)
            for k, g in d_layer.items():
                add("l%d_%s" % (i, k), g)
        left[t] = entered[t] = None
    grads["embed_weight"] = fn["embed_bwd"](
        params["embed_weight"].shape, tokens, ct)
    return loss, {k: grads[k] for k in params}


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
    if precision not in ("reference", "fp8"):
        raise ValueError("unknown precision %r" % precision)
    quant = precision == "fp8"

    def adam(p, m, v, g, t, decay):
        g = g + decay * p
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        coef = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        return p - lr * coef * m / (jnp.sqrt(v) + eps), m, v

    adam = jax.jit(adam, donate_argnums=(0, 1, 2))
    norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))))
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))

    with jax.default_matmul_precision("highest"):
        fn = _blocks(config, quant)
        params = {k: jnp.asarray(v) for k, v in weights.items()}
        mean = {k: jnp.zeros(v.shape, jnp.float32)
                for k, v in weights.items()}
        var = {k: jnp.zeros(v.shape, jnp.float32)
               for k, v in weights.items()}
        out = {"loss": []}
        for i, (x, y) in enumerate(batches):
            if rows is not None:
                x, y = x[rows], y[rows]
            loss, grads = loss_and_grads(params, jnp.asarray(x),
                                         jnp.asarray(y), config, quant, fn)
            out["loss"].append(float(loss))
            if i == 0:
                out["grad1"] = {k: float(norm(g)) for k, g in grads.items()}
            t = jnp.float32(i + 1)
            for k in list(params):
                params[k], mean[k], var[k] = adam(
                    params[k], mean[k], var[k], grads.pop(k), t,
                    wd * wd_mult(k, config))
        del mean, var
        out["dparam"] = {k: float(diff(params[k], jnp.asarray(weights[k])))
                         for k in weights}
    return out
