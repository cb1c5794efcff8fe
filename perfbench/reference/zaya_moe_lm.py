"""Plain reference: a decoder-only language model whose every layer is
COMPRESSED CONVOLUTIONAL ATTENTION (CCA; Figliolia et al., arXiv:2510.04476)
followed by a TOP-1 expert layer under a router that is a small MLP, the
head TIED to the embedding, trained by Adam on the next token's
cross-entropy, float32 at the highest matmul precision, jax.numpy only.
The model is ZAYA1-8B (Zyphra, ``config.json``) as its config and the
configuration file's ``assumed`` list give it, and it is handed ONE CHIP'S
SHARE of the deployment the configuration states: experts
``deployment.experts_held = [first, end)`` of the
``published.num_experts`` the router runs over, and the first
``vocab_size`` rows of the (tied) vocabulary table.

It imports nothing of the program and is handed nothing the program
made: no kernel, no sort, no gather of rows by expert, no grouped
product, no ``checkpoint`` — Python loops.

Layer ``l`` on ``x (B, T, U)``, no bias anywhere, a row before the first
read as zero::

    h   = RMS(x; g1)
    q~, k~, v~ = h Wq', h Wk', h Wv'     -> (T, H D), (T, Hkv D), (T, Hkv D)
    z   = [q~ ; k~]                       (T, (H + Hkv) D)
    a_t = sum_j time[j] * z_{t-j}         depthwise over time, K0 taps
    b_t[g] = sum_j a_{t-j}[g] @ mix[g, j] each head's D channels, K1 taps
    m_g = (mean of kv group g's query heads of q~ + k~_g) / 2
    q_n = b_t[n] + m_{g(n)},  k_g = b_t[H + g] + m_g
    v_t = [kv head 0 of v~_t ; kv head 1 of v~_{t-1}]     (the value shift)
    q_n = tau_g q_n / |q_n|,  k_g = k_g / |k_g|   (|a| = sqrt(a.a + 1e-6))
    q, k = rope(q), rope(k)   the first R = partial_rotary_factor D of a
                              head, half-split pairs (i, i + R/2), theta
    s_ij = q_i . k_j  for j <= i (no further scale), masked otherwise
    a   = softmax_j(s) v      query head n reads kv head n // (H / Hkv)
    x   = x + a Wo'
    h2  = RMS(x; g2)
    r   = h2 Wr'  (U -> router_hidden_size), then the MLP
          r Wr0' -> GELU -> Wr1' -> GELU -> Wr2'  (-> E logits)
    p   = softmax(logits) over ALL published experts (float32)
    S   = the top_k largest of p; w_e = p_e, NOT renormalised
    y   = sum_{e in S and held} w_e Wd_e (silu(Wg_e h2) * (Wu_e h2))
    x   = x + y

GELU is the exact one.  Then ``logits = RMS(x; gf) E'`` over the held rows
of the vocabulary — ``E`` the EMBEDDING table (``tie_word_embeddings``),
whose gradient is the look-up's plus the head's — loss = mean over
positions of the next token's cross-entropy.

The expert part is the DENSE MASKED form: every held expert is applied
to every token and weighed by that token's ``w_e``, which is zero where
the expert was not chosen.

:func:`loss_fn` is the whole of it in one function, for
``jax.value_and_grad`` at sizes where everything fits (the CPU tests).
At the timed sizes one layer's ``(H, T, T)`` float32 probabilities are
8.6 GB, and a layer's held experts on every token 1.1 GB an array, so
:func:`loss_and_grads` computes the same numbers IN BLOCKS: a layer is
three pieces — the projections with the convolutions, norms and rotary,
attention over ``Q_BLOCK`` query rows at a time, and the output
projection with the expert part over ``ROW_BLOCK`` rows at a time (every
operation in it is a token's own) — and the head too goes ``ROW_BLOCK``
rows at a time; it walks back one piece at a time with ``jax.vjp``.  A
test holds the two to each other.

``precision="fp8"`` is the CONTROL, the step below the bf16 the
configuration states (``reference_common.py``): both operands of every
matrix product (the convolution across a head's channels among them)
rounded to e4m3, the gradient arriving at its output to e5m2.
"""
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference_common import (WEIGHT_STREAM, fp8, fp8_grad, seed_key,  # noqa: F401
                              wd_mult)

HI = lax.Precision.HIGHEST
ATTN_IN_LEAVES = ("norm1_gamma", "q_weight", "k_weight", "v_weight",
                  "cca_time_weight", "cca_mix_weight",
                  "cca_temperature_gamma")
EXPERT_LEAVES = ("gate_weight", "up_weight", "down_weight")
HEAD_LEAVES = ("norm_gamma", "embed_weight")
Q_BLOCK = 512       # query rows an attention piece works on
ROW_BLOCK = 4096    # rows the expert part and the head work on at a time
NORM_EPS = 1e-6     # inside the root of a head's L2 norm


def sizes(config):
    """The sizes the equations read, by name."""
    first, end = (int(e) for e in config["deployment"]["experts_held"])
    d = int(config["head_dim"])
    s = {"vocab": int(config["vocab_size"]),
         "units": int(config["hidden_size"]),
         "heads": int(config["num_attention_heads"]),
         "kv_heads": int(config["num_key_value_heads"]),
         "head_dim": d,
         "rotary": int(round(d * float(config["partial_rotary_factor"]))),
         "theta": float(config["rope_parameters"]["hybrid"]["rope_theta"]),
         "taps": (int(config["cca_time0"]), int(config["cca_time1"])),
         "expert_width": int(config["moe_intermediate_size"]),
         "held": (first, end - first),
         "routed": int(config["published"]["num_experts"]),
         "top_k": int(config["num_experts_per_tok"]),
         "router_hidden": int(config["router_hidden_size"]),
         "router_layers": int(config["assumed"]["router_mlp_layers"]),
         "layers": int(config["num_hidden_layers"]),
         "eps": float(config["rms_norm_eps"])}
    if s["held"][1] != int(config["num_experts"]):
        raise ValueError("deployment.experts_held %s is not num_experts %s"
                         % (config["deployment"]["experts_held"],
                            config["num_experts"]))
    if not config["tie_word_embeddings"] \
            or config["assumed"]["norm_topk_prob"]:
        raise ValueError("this reference is of a tied head and top-k "
                         "weights that are not renormalised")
    return s


def router_leaves(config):
    return ("router_weight",) + tuple(
        "router%d_weight" % j for j in range(sizes(config)["router_layers"]))


def layer_leaves(config):
    """One layer's leaves in the block's construction order."""
    return ATTN_IN_LEAVES + ("out_weight", "norm2_gamma") \
        + router_leaves(config) + EXPERT_LEAVES


def rest_leaves(config):
    return ("out_weight", "norm2_gamma") + router_leaves(config) \
        + EXPERT_LEAVES


RESIDUAL_LEAVES = ("out_weight", "down_weight")   # they write the residual


def leaf_specs(config):
    """Ordered [(name, shape, init)] of every trainable leaf, in the
    block's construction order; a held expert's weights are stacked.
    ``init``: "normal" (std by the name, :func:`init_weights`), "one",
    "router", "temperature" or "conv" (see there)."""
    s = sizes(config)
    u, d, f, n = s["units"], s["head_dim"], s["expert_width"], s["held"][1]
    heads, hr = s["heads"] + s["kv_heads"], s["router_hidden"]
    shape = {"norm1_gamma": (u,), "q_weight": (s["heads"] * d, u),
             "k_weight": (s["kv_heads"] * d, u),
             "v_weight": (s["kv_heads"] * d, u),
             "cca_time_weight": (s["taps"][0], heads * d),
             "cca_mix_weight": (heads, s["taps"][1], d, d),
             "cca_temperature_gamma": (s["kv_heads"],),
             "out_weight": (u, s["heads"] * d), "norm2_gamma": (u,),
             "router_weight": (hr, u), "gate_weight": (n, f, u),
             "up_weight": (n, f, u), "down_weight": (n, u, f)}
    for j in range(s["router_layers"]):
        shape["router%d_weight" % j] = (
            s["routed"] if j == s["router_layers"] - 1 else hr, hr)
    # the router MLP's layers (its down-projection is an ordinary matrix)
    init = dict({k: "router" for k in router_leaves(config)[1:]},
                cca_time_weight="conv", cca_mix_weight="conv",
                cca_temperature_gamma="temperature")
    specs = [("embed_weight", (s["vocab"], u), "normal")]
    for i in range(s["layers"]):
        specs += [("l%d_%s" % (i, k), shape[k], init.get(
            k, "one" if k.endswith("gamma") else "normal"))
            for k in layer_leaves(config)]
    return specs + [("norm_gamma", (u,), "one")]


_NODES, _WEIGHTS = np.polynomial.hermite_e.hermegauss(64)
_WEIGHTS = _WEIGHTS / _WEIGHTS.sum()


def _gelu_moments(s):
    """Mean and variance of GELU(N(0, s^2)), each unit's own ``s``
    (Gauss-Hermite quadrature)."""
    g = jax.nn.gelu(s[:, None] * jnp.asarray(_NODES, jnp.float32)[None],
                    approximate=False)
    w = jnp.asarray(_WEIGHTS, jnp.float32)
    mean = g @ w
    return mean, (g * g) @ w - mean * mean


def _balanced(router):
    """The router's matrices made to favour no expert over inputs of no
    preferred direction (unit variance a coordinate): every layer that
    reads a GELU's output is made orthogonal to that GELU's mean — the
    same positive number in every unit to first order, which would give
    each expert a fixed offset in its logit — then the rows of the last
    are scaled so that every expert's row of the four matrices' product
    has the mean norm."""
    down, layers = router[0], list(router[1:])
    scale = jnp.sqrt(jnp.sum(jnp.matmul(layers[0], down, precision=HI) ** 2,
                             axis=1))
    for j in range(1, len(layers)):
        mean, var = _gelu_moments(scale)
        w = layers[j]
        layers[j] = w - jnp.outer(jnp.matmul(w, mean, precision=HI),
                                  mean) / jnp.dot(mean, mean)
        scale = jnp.sqrt(jnp.matmul(layers[j] ** 2, var, precision=HI))
    product = down
    for w in layers:
        product = jnp.matmul(w, product, precision=HI)
    norms = jnp.sqrt(jnp.sum(product * product, axis=1))
    layers[-1] = layers[-1] * (jnp.mean(norms) / norms)[:, None]
    return [down] + layers


def init_weights(config, seed):
    """Normal(0, init_std) matrices — Normal(0, embed_init_std) the
    table, Normal(0, residual_init_std) the two projections that write
    into the residual stream, Normal(0, 1 / sqrt(router_hidden_size)) the
    router MLP's layers, the router then made to favour no expert by
    :func:`_balanced` — gains 1, every key/value head's temperature
    ``sqrt(head_dim)``, each convolution Uniform(-1/sqrt(fan in), 1/sqrt(fan
    in)) with fan in its taps times the channels a channel reads (1 over
    time, ``head_dim`` across a head); float32, made on the device in ONE
    jitted call from the seed, read back once."""
    def std(name):
        return float(config[
            "embed_init_std" if name == "embed_weight" else
            "residual_init_std" if name.endswith(RESIDUAL_LEAVES) else
            "init_std"])

    specs = leaf_specs(config)
    d = sizes(config)["head_dim"]
    routers = [["l%d_%s" % (i, k) for k in router_leaves(config)]
               for i in range(sizes(config)["layers"])]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if init in ("normal", "router"):
                scale = shape[1] ** -0.5 if init == "router" else std(name)
                out[name] = scale * jax.random.normal(k, shape, jnp.float32)
            elif init == "conv":
                fan_in = shape[1] * shape[2] if len(shape) == 4 else shape[0]
                bound = 1.0 / math.sqrt(fan_in)
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -bound, bound)
            elif init == "temperature":
                out[name] = jnp.full(shape, math.sqrt(d), jnp.float32)
            else:
                out[name] = jnp.ones(shape, jnp.float32)
        for names in routers:
            out.update(zip(names, _balanced([out[n] for n in names])))
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


def _earlier(a, j):
    """``a`` (B, T, ...) moved ``j`` rows later in time: row t holds row
    t - j, zeros before the first."""
    if j == 0:
        return a
    return jnp.concatenate([jnp.zeros_like(a[:, :j]), a[:, :-j]], axis=1)


def time_conv(z, w):
    """``a_t = sum_j w[j] * z_{t-j}``, channel by channel: ``z (B, T, C)``,
    ``w (K, C)``."""
    return sum(w[j] * _earlier(z, j) for j in range(w.shape[0]))


def mix_conv(a, w, quant):
    """``b_t[g] = sum_j a_{t-j}[g] @ w[g, j]``: ``a (B, T, G D)``, ``w (G,
    K, D, D)`` (channels in, channels out) -> ``(B, T, G D)``."""
    b, t, _c = a.shape
    groups, taps, d, _ = w.shape
    a = a.reshape(b, t, groups, d)
    out = sum(_mm(_earlier(a, j), w[:, j], "btgc,gcd->btgd", quant)
              for j in range(taps))
    return out.reshape(b, t, groups * d)


def _unit(a):
    return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + NORM_EPS)


def _rope(x, dims, theta):
    """x: (B, T, H, D); of the first ``dims`` of a head the pairs (i, i +
    dims/2) turn by t * theta^(-2i/dims); the rest pass through."""
    t = x.shape[1]
    inv = theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    r, rest = x[..., :dims], x[..., dims:]
    turned = jnp.concatenate([-r[..., dims // 2:], r[..., :dims // 2]], -1)
    return jnp.concatenate([r * jnp.cos(emb) + turned * jnp.sin(emb), rest],
                           -1)


def _attn_in(x, p, config, quant):
    """The normed state's three projections, mixed, normed and turned:
    ``(q (B, T, H, D) with the temperature in it, k, v (B, T, Hkv, D))``."""
    s = sizes(config)
    b, t, _u = x.shape
    hq, hkv, d = s["heads"], s["kv_heads"], s["head_dim"]
    h = _rms(x, p["norm1_gamma"], s["eps"])
    q0 = _mm(h, p["q_weight"], "btu,ou->bto", quant)
    k0 = _mm(h, p["k_weight"], "btu,ou->bto", quant)
    v0 = _mm(h, p["v_weight"], "btu,ou->bto", quant)
    z = mix_conv(time_conv(jnp.concatenate([q0, k0], -1),
                           p["cca_time_weight"]), p["cca_mix_weight"], quant)
    mean = 0.5 * (jnp.mean(q0.reshape(b, t, hkv, hq // hkv, d), axis=3)
                  + k0.reshape(b, t, hkv, d))
    q = z[..., :hq * d].reshape(b, t, hkv, hq // hkv, d) + mean[:, :, :, None]
    k = z[..., hq * d:].reshape(b, t, hkv, d) + mean
    tau = p["cca_temperature_gamma"]
    q = (_unit(q) * tau[:, None, None]).reshape(b, t, hq, d)
    k = _unit(k)
    v = v0.reshape(b, t, hkv, d)
    v = jnp.stack([v[:, :, g] if g < hkv // 2 else _earlier(v[:, :, g], 1)
                   for g in range(hkv)], axis=2)
    return (_rope(q, s["rotary"], s["theta"]),
            _rope(k, s["rotary"], s["theta"]), v)


def _attn_block(q, k, v, row0, quant):
    """Causal attention of the query rows ``row0 .. row0 + q.shape[1] -
    1`` over every key, no scale beyond the temperature in ``q``."""
    b, tq, hq, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, tq, hkv, hq // hkv, d)    # head n reads kv head n // g
    sc = _mm(qg, k, "bqhgd,bkhd->bhgqk", quant)
    see = jnp.arange(k.shape[1])[None, :] <= row0 + jnp.arange(tq)[:, None]
    pr = jax.nn.softmax(jnp.where(see[None, None, None], sc, -1e30), axis=-1)
    return _mm(pr, v, "bhgqk,bkhd->bqhgd", quant).reshape(b, tq, hq, d)


def router_logits(h, p, config, quant):
    """``(B, T, E)``: the router's down-projection, then its MLP, GELU
    between the MLP's layers."""
    r = _mm(h, p["router_weight"], "btu,ou->bto", quant)
    for j in range(sizes(config)["router_layers"]):
        if j:
            r = jax.nn.gelu(r, approximate=False)
        r = _mm(r, p["router%d_weight" % j], "bti,oi->bto", quant)
    return r


def route(h, p, config, quant):
    """``(B, T, E)`` float32: each token's weight for every published
    expert — softmax over all of them, zero outside its ``top_k``
    largest (a tie to the expert of the lower index), NOT
    renormalised."""
    r = jax.nn.softmax(router_logits(h, p, config, quant), axis=-1)
    chosen = lax.top_k(r, sizes(config)["top_k"])[1]
    picked = jnp.sum(jax.nn.one_hot(chosen, r.shape[-1], dtype=r.dtype), -2)
    return r * picked


def experts_dense(h, w, p, config, quant):
    """The held experts' part of the layer, DENSE MASKED: every held
    expert on every token, times the token's weight for it."""
    first, n = sizes(config)["held"]
    g = _mm(h, p["gate_weight"], "btu,efu->btef", quant)
    u = _mm(h, p["up_weight"], "btu,efu->btef", quant)
    y = _mm(jax.nn.silu(g) * u, p["down_weight"], "btef,euf->bteu", quant)
    return jnp.sum(w[..., first:first + n, None] * y, axis=2)


def _rest(x, o, p, config, quant):
    """Attention's output projected and added, then the expert part —
    every operation a token's own."""
    s = sizes(config)
    b, t, _u = x.shape
    x = x + _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu", quant)
    h = _rms(x, p["norm2_gamma"], s["eps"])
    return x + experts_dense(h, route(h, p, config, quant), p, config, quant)


def _blocks_of(t, size):
    return [(r, min(size, t - r)) for r in range(0, t, size)]


def cca(x, p, config, quant=False):
    """One layer's attention part whole, ``(B, T, U)`` — the normed
    state's CCA with the output projection, not yet added — for the
    tests of the layer."""
    q, k, v = _attn_in(x, p, config, quant)
    o = _attn_block(q, k, v, 0, quant)
    b, t, _u = x.shape
    return _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu", quant)


def _layer(x, p, config, quant):
    q, k, v = _attn_in(x, p, config, quant)
    o = jnp.concatenate(
        [_attn_block(q[:, r:r + n], k, v, r, quant)
         for r, n in _blocks_of(x.shape[1], Q_BLOCK)], axis=1)
    return _rest(x, o, p, config, quant)


def _head(x, p, labels, config, quant):
    """``(logits, per-position cross-entropy)`` over the held rows of
    the vocabulary, through the embedding table."""
    h = _rms(x, p["norm_gamma"], sizes(config)["eps"])
    logits = _mm(h, p["embed_weight"], "btu,vu->btv", quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels.astype(jnp.int32)[..., None],
                              axis=-1)[..., 0]
    return logits, ce


def _layer_params(params, i, config):
    pre = "l%d_" % i
    return {k: params[pre + k] for k in layer_leaves(config)}


def forward(params, tokens, labels, config, quant=False):
    """``(logits, per-position cross-entropy)``, whole — what the tests
    hold the block's outputs to."""
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    for i in range(sizes(config)["layers"]):
        x = _layer(x, _layer_params(params, i, config), config, quant)
    return _head(x, params, labels, config, quant)


def loss_fn(params, tokens, labels, config, quant=False):
    return jnp.mean(forward(params, tokens, labels, config, quant)[1])


def expert_layer(h, p, config, quant=False):
    """One layer's expert part alone over normed states ``h (B, T, U)``
    — the router MLP, top-k, the held experts — for the test that sums
    the shares."""
    return experts_dense(h, route(h, p, config, quant), p, config, quant)


# -- what the router did (read, printed, not compared) ------------------------
def routing_stats(h, p, config):
    """``(rows per held expert (count,), share of (token, slot)
    assignments that differ when the router and the state are rounded to
    bfloat16)`` of one layer's normed states."""
    s = sizes(config)
    first, n = s["held"]
    w = route(h, p, config, False)
    rows = jnp.sum(w[..., first:first + n] > 0, axis=(0, 1))
    r = h.astype(jnp.bfloat16)
    for j, name in enumerate(router_leaves(config)):
        if j > 1:
            r = jax.nn.gelu(r, approximate=False)
        r = jnp.einsum("bti,oi->bto", r.astype(jnp.bfloat16),
                       p[name].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    chosen_low = lax.top_k(jax.nn.softmax(r, axis=-1), s["top_k"])[1]
    picked = jnp.take_along_axis(w, chosen_low, axis=-1) > 0
    return rows, 1.0 - jnp.mean(picked.astype(jnp.float32))


# -- the same numbers in blocks ----------------------------------------------
def _blocks(config, quant):
    """The jitted pieces :func:`loss_and_grads` walks with; block sizes
    are static, a block's first row is data."""
    def attn_in(x, p):
        return _attn_in(x, p, config, quant)

    def attn_in_bwd(x, p, cts):
        return jax.vjp(attn_in, x, p)[1](cts)

    def attn(q, k, v, row0):
        return _attn_block(q, k, v, row0, quant)

    def attn_bwd(q, k, v, row0, ct):
        return jax.vjp(lambda q_, k_, v_: attn(q_, k_, v_, row0),
                       q, k, v)[1](ct)

    def rest(x, o, p):
        return _rest(x, o, p, config, quant)

    def rest_bwd(x, o, p, ct):
        return jax.vjp(rest, x, o, p)[1](ct)

    def head(x, p, labels, count):
        """The block's cross-entropy summed, over ``count`` positions in
        all, with its gradient."""
        return jax.value_and_grad(
            lambda x_, p_: jnp.sum(_head(x_, p_, labels, config, quant)[1])
            / count, argnums=(0, 1))(x, p)

    def embed_bwd(table_grad, tokens, ct):
        return table_grad.at[tokens.astype(jnp.int32)].add(ct)

    def stats(x, o, p):
        s = sizes(config)
        b, t, _u = x.shape
        x = x + _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu",
                    False)
        return routing_stats(_rms(x, p["norm2_gamma"], s["eps"]), p, config)

    return {"attn_in": jax.jit(attn_in), "attn_in_bwd": jax.jit(attn_in_bwd),
            "attn": jax.jit(attn), "attn_bwd": jax.jit(attn_bwd),
            "rest": jax.jit(rest), "rest_bwd": jax.jit(rest_bwd),
            "head": jax.jit(head), "stats": jax.jit(stats),
            "embed_bwd": jax.jit(embed_bwd, donate_argnums=0)}


def described_programs(config, sds):
    """``(what, lowered)`` of the largest programs the walk runs, for
    ``rehearse_compile.py --reference``; ``sds(shape, dtype=float32)``
    makes an argument on the described chip."""
    s = sizes(config)
    b, t = int(config["batch_size"]), int(config["seq_len"])
    rows = min(ROW_BLOCK, t)
    shapes = {n: sh for n, sh, _i in leaf_specs(config)}
    in_p = {k: sds(shapes["l0_" + k]) for k in ATTN_IN_LEAVES}
    rest_p = {k: sds(shapes["l0_" + k]) for k in rest_leaves(config)}
    head_p = {k: sds(shapes[k]) for k in HEAD_LEAVES}
    q = sds((b, t, s["heads"], s["head_dim"]))
    kv = sds((b, t, s["kv_heads"], s["head_dim"]))
    qb = sds((b, min(Q_BLOCK, t), s["heads"], s["head_dim"]))
    fn = _blocks(config, False)
    print("the walk keeps, a layer, the state that enters it and its q, "
          "k, v and attention output: %.3f GB beside parameters and "
          "gradients" % (4 * b * t * (s["units"] + 2 * s["heads"]
                                      * s["head_dim"] + 2 * s["kv_heads"]
                                      * s["head_dim"]) / 1e9))
    yield "projections, convolutions, norms and rotary backward", \
        fn["attn_in_bwd"].lower(sds((b, t, s["units"])), in_p, (q, kv, kv))
    yield "attention of %d query rows backward" % qb.shape[1], \
        fn["attn_bwd"].lower(qb, kv, kv, sds((), jnp.int32), qb)
    yield "output projection and expert part of %d rows backward" % rows, \
        fn["rest_bwd"].lower(sds((b, rows, s["units"])),
                             sds((b, rows, s["heads"], s["head_dim"])),
                             rest_p, sds((b, rows, s["units"])))
    yield "head and loss of %d rows with gradient" % rows, \
        fn["head"].lower(sds((b, rows, s["units"])), head_p,
                         sds((b, rows)), sds(()))


def loss_and_grads(params, tokens, labels, config, quant=False, blocks=None,
                   routing=None):
    """``(loss, {leaf: gradient})`` — :func:`loss_fn`'s value and
    gradient, one piece of one layer at a time.  ``routing``, a list,
    gets one :func:`routing_stats` per layer."""
    fn = blocks or _blocks(config, quant)
    n_layers = sizes(config)["layers"]
    layers = [_layer_params(params, i, config) for i in range(n_layers)]
    rest_names = rest_leaves(config)
    t = tokens.shape[1]
    pieces, rows = _blocks_of(t, Q_BLOCK), _blocks_of(t, ROW_BLOCK)
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    kept = []
    for p in layers:
        p_in = {n: p[n] for n in ATTN_IN_LEAVES}
        p_rest = {n: p[n] for n in rest_names}
        q, k, v = fn["attn_in"](x, p_in)
        o = jnp.concatenate([fn["attn"](q[:, r:r + n], k, v, jnp.int32(r))
                             for r, n in pieces], axis=1)
        if routing is not None:
            routing.append(fn["stats"](x, o, p_rest))
        kept.append((x, q, k, v, o))
        x = jnp.concatenate([fn["rest"](x[:, r:r + n], o[:, r:r + n], p_rest)
                             for r, n in rows], axis=1)
    head_p = {k: params[k] for k in HEAD_LEAVES}
    count = jnp.float32(tokens.shape[0] * t)
    loss, cts, d_head = 0.0, [], None
    for r, n in rows:
        part, (ct_b, d_b) = fn["head"](x[:, r:r + n], head_p,
                                       labels[:, r:r + n], count)
        loss, d_head = loss + part, d_b if d_head is None else \
            jax.tree_util.tree_map(jnp.add, d_head, d_b)
        cts.append(ct_b)
    ct = jnp.concatenate(cts, axis=1)
    grads = dict(d_head)
    for i in reversed(range(n_layers)):
        p = layers[i]
        x, q, k, v, o = kept.pop()
        p_rest = {n: p[n] for n in rest_names}
        ct_x, ct_o, d_rest = [], [], None
        for r, n in rows:
            cx, co, d_b = fn["rest_bwd"](x[:, r:r + n], o[:, r:r + n],
                                         p_rest, ct[:, r:r + n])
            ct_x.append(cx)
            ct_o.append(co)
            d_rest = d_b if d_rest is None else jax.tree_util.tree_map(
                jnp.add, d_rest, d_b)
        ct_x, ct_o = (jnp.concatenate(a, axis=1) for a in (ct_x, ct_o))
        dq, dk, dv = [], jnp.zeros_like(k), jnp.zeros_like(v)
        for r, n in pieces:
            dq_b, dk_b, dv_b = fn["attn_bwd"](
                q[:, r:r + n], k, v, jnp.int32(r), ct_o[:, r:r + n])
            dq.append(dq_b)
            dk, dv = dk + dk_b, dv + dv_b
        ct_in, d_in = fn["attn_in_bwd"](
            x, {n: p[n] for n in ATTN_IN_LEAVES},
            (jnp.concatenate(dq, axis=1), dk, dv))
        ct = ct_x + ct_in
        for name, g in {**d_in, **d_rest}.items():
            grads["l%d_%s" % (i, name)] = g
    # the tied table: the head's gradient, then the look-up's added in
    grads["embed_weight"] = fn["embed_bwd"](grads["embed_weight"], tokens, ct)
    return loss, {k: grads[k] for k in params}


def _print_routing(step, routing, config):
    s = sizes(config)
    loads = [np.asarray(r).astype(int).tolist() for r, _d in routing]
    rows = np.concatenate(loads)
    tokens = int(config["batch_size"]) * int(config["seq_len"])
    print("[perfbench] step %d routing (reference, float32): rows per held "
          "expert over %d layers least %d / mean %.1f / most %d, expected "
          "%.1f; %.3f%% of (token, slot) assignments differ when router "
          "and state are rounded to bfloat16 (read, not compared)"
          % (step, len(routing), rows.min(), rows.mean(), rows.max(),
             tokens * s["top_k"] / s["routed"],
             100.0 * float(np.mean([float(d) for _r, d in routing]))),
          file=sys.stderr, flush=True)
    # every load, so that a reader can price any tile's padding
    print("[perfbench] step %d rows per held expert, by layer: %s"
          % (step, loads), file=sys.stderr, flush=True)


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
            routing = [] if not quant else None
            loss, grads = loss_and_grads(params, jnp.asarray(x),
                                         jnp.asarray(y), config, quant, fn,
                                         routing)
            out["loss"].append(float(loss))
            if routing:
                _print_routing(i + 1, routing, config)
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
