"""Plain reference: a DECODER-HYBRID-DECODER language model (SambaY; Ren et
al., arXiv:2507.06607) — Mamba mixers (Gu & Dao, arXiv:2312.00752),
differential attention over a window or over every key before (Ye et al.,
arXiv:2410.05258), gated memory units that read a Mamba layer's scan
output, and cross-attention that reads a full-attention layer's keys and
values — under SwiGLU feed-forward parts and pre-LayerNorm, the head TIED
to the embedding, trained by Adam on the next token's cross-entropy,
float32 at the highest matmul precision, jax.numpy only.  The model is
Phi-4-mini-flash-reasoning (``config.json``, ``model_type`` phi4flash) as
its config and the configuration file's ``assumed`` list give it; the run
holds the published layers ``first_layer .. first_layer + L - 1`` of kinds
``layer_kinds``, and the first ``vocab_size`` rows of the tied table.

It imports nothing of the program and is handed nothing the program made:
no kernel, no ``checkpoint``, the scan a ``lax.scan`` over rows.

Layer ``l`` (published index ``first_layer + l``) on ``x (B, T, U)``::

    h = LN(x; g1, b1)
    x = x + Mixer(h)
    x = x + W_down (silu(W_gate h2) * (W_up h2)),  h2 = LN(x; g2, b2)

Mixers (E = expand U, N the state, R the step rank, K the taps)::

    mamba:   [u ; z] = W_in h;  u' = silu(sum_j conv[j] * u_{t-j} + b_c)
             [dl ; B ; C] = W_x u';  delta = softplus(W_dt dl + b_dt)
             s_t = exp(delta_t A) * s_{t-1} + (delta_t u'_t) B_t,  s_0 = 0,
             A = -exp(A_log);  y_t = s_t C_t + D u'_t
             out = W_out (y * silu(z));  the layer a gmu reads: m = y
    gmu:     out = W_o (silu(W_i h) * m)
    attention (sliding: a query sees its last `window` keys; full; cross:
             queries from h, keys and values the full layer's):
             [Q ; K ; V] = W_qkv h + b  (cross: Q = W_q h + b)
             query heads pair (2i, 2i+1), key heads (2j, 2j+1), V is
             Hkv / 2 heads of 2 D; pair i reads key pair i // 2:
             O_i = softmax(Q1 K1^T / sqrt(D) + M) V
                   - lam softmax(Q2 K2^T / sqrt(D) + M) V
             lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
             lam0 = 0.8 - 0.6 exp(-0.3 (first_layer + l))
             O_i = RMS(O_i; subln, eps) (1 - lam0);  out = W_o O + b_o

A gmu reads the last mamba layer before it, a cross layer the last full
layer before it.  Then ``logits = LN(x; gf, bf) E'`` over the held rows of
the vocabulary — ``E`` the EMBEDDING table, whose gradient is the look-up's
plus the head's — loss = mean over positions of the next token's
cross-entropy.  There is no positional encoding.

:func:`loss_fn` is the whole of it in one function, for
``jax.value_and_grad`` at sizes where everything fits (the CPU tests).  At
the timed sizes a layer's 40 score maps are 10.7 GB and the scan's states
over every row 2.7 GB, so :func:`loss_and_grads` computes the same numbers
IN PIECES, a layer at a time, each walked back with ``jax.vjp``: the
projections into a mixer whole; attention over ``Q_BLOCK`` query rows at a
time; the scan over ``SCAN_BLOCK`` channels at a time; the mixer's output
projection with the feed-forward part, the gated memory unit and the head
over ``ROW_BLOCK`` rows at a time.  The walk keeps only the state entering
each layer (and the memory, keys and values the readers take) and makes
every piece again on the way back.  A test holds it to :func:`loss_fn`.

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
Q_BLOCK = 256       # query rows an attention piece works on
SCAN_BLOCK = 512    # channels a scan piece works on
ROW_BLOCK = 4096    # rows a token-local piece works on
KINDS = ("mamba", "sliding_attention", "full_attention", "gmu",
         "cross_attention")
FFN_LEAVES = ("norm2_gamma", "norm2_beta", "gate_weight", "up_weight",
              "down_weight")
MAMBA_IN = ("norm1_gamma", "norm1_beta", "in_weight", "conv_weight",
            "conv_bias", "x_weight", "dt_weight", "dt_bias")
MAMBA_SCAN = ("a_log_weight", "d_weight")
LAMBDAS = ("lambda_q1_weight", "lambda_k1_weight", "lambda_q2_weight",
           "lambda_k2_weight")
HEAD_LEAVES = ("norm_gamma", "norm_beta", "embed_weight")
RESIDUAL_LEAVES = ("out_weight", "down_weight")   # they write the residual


def sizes(config):
    """The sizes the equations read, by name."""
    a = config["assumed"]
    u = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    s = {"vocab": int(config["vocab_size"]), "units": u,
         "inner": int(a["mamba_expand"]) * u,
         "state": int(a["mamba_d_state"]), "conv": int(a["mamba_d_conv"]),
         "dt_rank": -(-u // 16), "heads": heads,
         "kv_heads": int(config["num_key_value_heads"]),
         "head_dim": u // heads, "dense": int(config["intermediate_size"]),
         "window": int(config["sliding_window"]),
         "kinds": tuple(config["layer_kinds"]),
         "first": int(config["first_layer"]),
         "layers": int(config["num_hidden_layers"]),
         "eps": float(config["layer_norm_eps"])}
    if len(s["kinds"]) != s["layers"] or not set(s["kinds"]) <= set(KINDS):
        raise ValueError("layer_kinds %r is not num_hidden_layers %d kinds "
                         "of %r" % (s["kinds"], s["layers"], KINDS))
    if not config["tie_word_embeddings"] or config["mlp_bias"]:
        raise ValueError("this reference is of a tied head and an MLP "
                         "without biases")
    return s


def sources(config):
    """``{reader layer: writer layer}``: a gmu reads the last mamba layer
    before it, a cross layer the last full layer before it."""
    kinds = sizes(config)["kinds"]
    out = {}
    for i, kind in enumerate(kinds):
        want = {"gmu": "mamba", "cross_attention": "full_attention"}.get(kind)
        if want:
            out[i] = max(j for j in range(i) if kinds[j] == want)
    return out


def layer_leaves(config, kind):
    """One layer's leaves in the block's construction order."""
    mixer = {"mamba": MAMBA_IN[2:] + MAMBA_SCAN + ("out_weight",),
             "gmu": ("gmu_in_weight", "out_weight")}.get(kind)
    if mixer is None:
        proj = ("q_weight", "q_bias") if kind == "cross_attention" \
            else ("qkv_weight", "qkv_bias")
        mixer = proj + LAMBDAS + ("subln_gamma", "out_weight", "out_bias")
    return ("norm1_gamma", "norm1_beta") + mixer + FFN_LEAVES


def leaf_specs(config):
    """Ordered [(name, shape, init)] of every trainable leaf, in the
    block's construction order.  ``init``: "normal" (std by the name),
    "one", "zero", "conv", "dt_bias", "a_log", "lambda" (see
    :func:`init_weights`)."""
    s = sizes(config)
    u, e, n, r = s["units"], s["inner"], s["state"], s["dt_rank"]
    d, hq, hkv, f = s["head_dim"], s["heads"], s["kv_heads"], s["dense"]
    shape = {"norm1_gamma": (u,), "norm1_beta": (u,), "norm2_gamma": (u,),
             "norm2_beta": (u,), "in_weight": (2 * e, u),
             "conv_weight": (s["conv"], e), "conv_bias": (e,),
             "x_weight": (r + 2 * n, e), "dt_weight": (e, r),
             "dt_bias": (e,), "a_log_weight": (e, n), "d_weight": (e,),
             "gmu_in_weight": (e, u), "qkv_weight": ((hq + 2 * hkv) * d, u),
             "qkv_bias": ((hq + 2 * hkv) * d,), "q_weight": (hq * d, u),
             "q_bias": (hq * d,), "subln_gamma": (2 * d,),
             "out_bias": (u,), "gate_weight": (f, u), "up_weight": (f, u),
             "down_weight": (u, f)}
    shape.update({k: (d,) for k in LAMBDAS})
    init = {"conv_weight": "conv", "conv_bias": "conv", "dt_bias": "dt_bias",
            "a_log_weight": "a_log", "d_weight": "one",
            "dt_weight": "dt", **{k: "lambda" for k in LAMBDAS}}
    specs = [("embed_weight", (s["vocab"], u), "normal")]
    for i, kind in enumerate(s["kinds"]):
        for k in layer_leaves(config, kind):
            sh = (u, e) if k == "out_weight" and kind in ("mamba", "gmu") \
                else (u, hq * d) if k == "out_weight" else shape[k]
            how = init.get(k, "one" if k.endswith("gamma") else
                           "zero" if k.endswith(("beta", "bias"))
                           else "normal")
            specs.append(("l%d_%s" % (i, k), sh, how))
    return specs + [("norm_gamma", (u,), "one"), ("norm_beta", (u,), "zero")]


def init_weights(config, seed):
    """Normal(0, init_std) matrices — Normal(0, residual_init_std) the two
    projections that write into the residual stream — gains 1, biases 0;
    the Mamba family's own draws for its step and state: ``dt_weight``
    Uniform(+-dt_rank^-0.5), ``dt_bias`` the softplus inverse of a step
    drawn log-uniformly in [1e-3, 0.1], ``A_log`` log(1 .. N) in every
    channel, ``D`` 1, the convolution Uniform(+-1/sqrt(taps)) (its bias
    too); the lambda vectors Normal(0, 0.1).  Float32, made on the device
    in ONE jitted call from the seed, read back once."""
    specs = leaf_specs(config)
    s = sizes(config)

    def std(name):
        return float(config["residual_init_std"
                            if name.endswith(RESIDUAL_LEAVES)
                            else "init_std"])

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if init == "normal":
                out[name] = std(name) * jax.random.normal(k, shape)
            elif init == "lambda":
                out[name] = 0.1 * jax.random.normal(k, shape)
            elif init == "dt":
                b = s["dt_rank"] ** -0.5
                out[name] = jax.random.uniform(k, shape, jnp.float32, -b, b)
            elif init == "conv":
                b = 1.0 / math.sqrt(s["conv"])
                out[name] = jax.random.uniform(k, shape, jnp.float32, -b, b)
            elif init == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape) * (
                    math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif init == "a_log":
                out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=jnp.float32)), shape)
            elif init == "one":
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    made = jax.device_get(make(seed_key(seed, WEIGHT_STREAM)))
    return {name: made[name] for name, _shape, _init in specs}  # in order


# -- the pieces ---------------------------------------------------------------
def _mm(a, b, spec, quant):
    if quant:
        return fp8_grad(jnp.einsum(spec, fp8(a), fp8(b), precision=HI))
    return jnp.einsum(spec, a, b, precision=HI)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _ffn(x, p, config, quant):
    """``x`` plus the feed-forward part of its normed state."""
    h = _ln(x, p["norm2_gamma"], p["norm2_beta"], sizes(config)["eps"])
    g = _mm(h, p["gate_weight"], "btu,fu->btf", quant)
    up = _mm(h, p["up_weight"], "btu,fu->btf", quant)
    return x + _mm(jax.nn.silu(g) * up, p["down_weight"], "btf,uf->btu",
                   quant)


def _earlier(a, j):
    if j == 0:
        return a
    return jnp.concatenate([jnp.zeros_like(a[:, :j]), a[:, :-j]], axis=1)


def mamba_in(x, p, config, quant):
    """``(u', delta, B, C, z)`` of a Mamba layer's normed state."""
    s = sizes(config)
    h = _ln(x, p["norm1_gamma"], p["norm1_beta"], s["eps"])
    u, z = jnp.split(_mm(h, p["in_weight"], "btu,ou->bto", quant), 2, -1)
    conv = sum(p["conv_weight"][j] * _earlier(u, j) for j in range(s["conv"]))
    u = jax.nn.silu(conv + p["conv_bias"])
    dbc = _mm(u, p["x_weight"], "bte,oe->bto", quant)
    r, n = s["dt_rank"], s["state"]
    delta = jax.nn.softplus(_mm(dbc[..., :r], p["dt_weight"], "btr,er->bte",
                                quant) + p["dt_bias"])
    return u, delta, dbc[..., r:r + n], dbc[..., r + n:], z


def scan(u, delta, b, c, a_log, d):
    """``y (B, T, E)`` of the recurrence, a ``lax.scan`` over rows."""
    a = -jnp.exp(a_log)

    def row(st, xs):
        dl, uu, bb, cc = xs
        st = jnp.exp(dl[..., None] * a) * st + (dl * uu)[..., None] \
            * bb[:, None, :]
        return st, jnp.sum(st * cc[:, None, :], -1)

    first = lambda v: jnp.swapaxes(v, 0, 1)
    _, y = lax.scan(row, jnp.zeros(u.shape[:1] + a.shape),
                    (first(delta), first(u), first(b), first(c)))
    return jnp.swapaxes(y, 0, 1) + d * u


def mamba_out(x, y, z, p, config, quant):
    x = x + _mm(y * jax.nn.silu(z), p["out_weight"], "bte,ue->btu", quant)
    return _ffn(x, p, config, quant)


def gmu_layer(x, m, p, config, quant):
    s = sizes(config)
    h = _ln(x, p["norm1_gamma"], p["norm1_beta"], s["eps"])
    g = jax.nn.silu(_mm(h, p["gmu_in_weight"], "btu,eu->bte", quant))
    x = x + _mm(g * m, p["out_weight"], "bte,ue->btu", quant)
    return _ffn(x, p, config, quant)


def attn_in(x, p, config, kind, quant):
    """``q (B, T, H, D)`` and, but in a cross layer, ``k``, ``v (B, T,
    Hkv, D)``."""
    s = sizes(config)
    b, t, _u = x.shape
    h = _ln(x, p["norm1_gamma"], p["norm1_beta"], s["eps"])
    hq, hkv, d = s["heads"], s["kv_heads"], s["head_dim"]
    if kind == "cross_attention":
        q = _mm(h, p["q_weight"], "btu,ou->bto", quant) + p["q_bias"]
        return (q.reshape(b, t, hq, d),)
    qkv = _mm(h, p["qkv_weight"], "btu,ou->bto", quant) + p["qkv_bias"]
    q, k, v = jnp.split(qkv, (hq * d, (hq + hkv) * d), -1)
    return (q.reshape(b, t, hq, d), k.reshape(b, t, hkv, d),
            v.reshape(b, t, hkv, d))


def lambda_init(config, i):
    return 0.8 - 0.6 * math.exp(-0.3 * (sizes(config)["first"] + i))


def attn_block(q, k, v, row0, p, config, i, window, quant):
    """The normed difference ``(B, tq, H / 2, 2 D)`` of the query rows
    ``row0 ..``: query pair ``n`` = heads (2 n, 2 n + 1), reading key heads
    (4 (n // 2) .., + 1) — key pair n // 2 — and value head pair n // 2."""
    s = sizes(config)
    b, tq, hq, d = q.shape
    lam0 = lambda_init(config, i)
    kp = k.reshape(b, -1, s["kv_heads"] // 2, 2, d)
    vp = v.reshape(b, -1, s["kv_heads"] // 2, 2 * d)
    qp = q.reshape(b, tq, hq // 4, 2, 2, d)     # (key pair, pair in it, map)
    kpos = jnp.arange(k.shape[1])[None, :]
    qpos = row0 + jnp.arange(tq)[:, None]
    see = kpos <= qpos
    if window is not None:
        see = see & (qpos - kpos < window)
    maps = []
    for m in range(2):
        sc = _mm(qp[:, :, :, :, m], kp[:, :, :, m], "bqjpd,bkjd->bjpqk",
                 quant) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(see, sc, -1e30), axis=-1)
        maps.append(_mm(pr, vp, "bjpqk,bkjd->bqjpd", quant))
    lam = jnp.exp(jnp.sum(p["lambda_q1_weight"] * p["lambda_k1_weight"])) \
        - jnp.exp(jnp.sum(p["lambda_q2_weight"] * p["lambda_k2_weight"])) \
        + lam0
    o = (maps[0] - lam * maps[1]).reshape(b, tq, hq // 2, 2 * d)
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + s["eps"]) \
        * p["subln_gamma"]
    return o * (1.0 - lam0)


def attn_out(x, o, p, config, quant):
    b, t = x.shape[:2]
    x = x + _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu",
                quant) + p["out_bias"]
    return _ffn(x, p, config, quant)


def _window(config, kind):
    return sizes(config)["window"] if kind == "sliding_attention" else None


def _head(x, p, labels, config, quant):
    h = _ln(x, p["norm_gamma"], p["norm_beta"], sizes(config)["eps"])
    logits = _mm(h, p["embed_weight"], "btu,vu->btv", quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels.astype(jnp.int32)[..., None],
                              axis=-1)[..., 0]
    return logits, ce


def layer_params(params, i, config):
    pre = "l%d_" % i
    kind = sizes(config)["kinds"][i]
    return {k: params[pre + k] for k in layer_leaves(config, kind)}


def forward(params, tokens, labels, config, quant=False):
    """``(logits, per-position cross-entropy)``, whole — what the tests
    hold the block's outputs to."""
    s = sizes(config)
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    wrote = {}
    for i, kind in enumerate(s["kinds"]):
        p = layer_params(params, i, config)
        src = sources(config).get(i)
        if kind == "mamba":
            u, delta, b, c, z = mamba_in(x, p, config, quant)
            y = scan(u, delta, b, c, p["a_log_weight"], p["d_weight"])
            wrote[i] = y
            x = mamba_out(x, y, z, p, config, quant)
        elif kind == "gmu":
            x = gmu_layer(x, wrote[src], p, config, quant)
        else:
            qkv = attn_in(x, p, config, kind, quant)
            if kind == "cross_attention":
                qkv = qkv + wrote[src]
            else:
                wrote[i] = qkv[1:]
            o = attn_block(*qkv, 0, p, config, i, _window(config, kind),
                           quant)
            x = attn_out(x, o, p, config, quant)
    return _head(x, params, labels, config, quant)


def loss_fn(params, tokens, labels, config, quant=False):
    return jnp.mean(forward(params, tokens, labels, config, quant)[1])


# -- the same numbers in pieces -----------------------------------------------
def _blocks_of(t, size):
    return [(r, min(size, t - r)) for r in range(0, t, size)]


def _pieces(config, quant):
    """The jitted pieces :func:`loss_and_grads` walks with, each with its
    walk back (``*_bwd(args..., ct)``)."""
    out = {}

    def both(name, fn, static=()):
        out[name] = jax.jit(fn, static_argnums=static)

        def bwd(*args):
            *a, ct = args
            return jax.vjp(lambda *x: fn(*x), *a)[1](ct)
        out[name + "_bwd"] = jax.jit(bwd, static_argnums=static)

    both("mamba_in", lambda x, p: mamba_in(x, p, config, quant))
    both("scan", scan)
    both("mamba_out", lambda x, y, z, p: mamba_out(x, y, z, p, config,
                                                   quant))
    both("gmu", lambda x, m, p: gmu_layer(x, m, p, config, quant))
    for kind in KINDS[1:3] + KINDS[4:]:
        both("attn_in_" + kind,
             lambda x, p, kind=kind: attn_in(x, p, config, kind, quant))
    both("attn_out", lambda x, o, p: attn_out(x, o, p, config, quant))

    def attn(q, k, v, row0, p, i, window):
        return attn_block(q, k, v, row0, p, config, i, window, quant)

    out["attn"] = jax.jit(attn, static_argnums=(5, 6))
    out["attn_bwd"] = jax.jit(
        lambda q, k, v, row0, p, i, window, ct: jax.vjp(
            lambda q_, k_, v_, p_: attn(q_, k_, v_, row0, p_, i, window),
            q, k, v, p)[1](ct), static_argnums=(5, 6))

    def head(x, p, labels, count):
        return jax.value_and_grad(
            lambda x_, p_: jnp.sum(_head(x_, p_, labels, config, quant)[1])
            / count, argnums=(0, 1))(x, p)

    out["head"] = jax.jit(head)
    out["embed_bwd"] = jax.jit(
        lambda g, tokens, ct: g.at[tokens.astype(jnp.int32)].add(ct),
        donate_argnums=0)
    return out


def described_programs(config, sds):
    """``(what, lowered)`` of the largest pieces the walk runs, for
    ``rehearse_compile.py --reference``; ``sds(shape, dtype=float32)``
    makes an argument on the described chip."""
    s = sizes(config)
    b, t = int(config["batch_size"]), int(config["seq_len"])
    u, e, n = s["units"], s["inner"], s["state"]
    rows, eb, qb = min(ROW_BLOCK, t), min(SCAN_BLOCK, e), min(Q_BLOCK, t)
    shapes = {k: sh for k, sh, _i in leaf_specs(config)}
    kinds = s["kinds"]
    layer = lambda kind: {k: sds(shapes["l%d_%s" % (kinds.index(kind), k)])
                          for k in layer_leaves(config, kind)}
    fn = _pieces(config, False)
    mamba, full = layer("mamba"), layer("full_attention")
    print("the walk keeps the state entering each layer and the memory, "
          "keys and values the readers take: %.3f GB beside the weights "
          "and both Adam slots" % (4 * b * t * (s["layers"] * u + e + 2 * u)
                                   / 1e9))
    te = lambda w: sds((b, t, w))
    yield "a Mamba layer's projections and convolution backward", \
        fn["mamba_in_bwd"].lower(te(u), _sub(mamba, MAMBA_IN),
                                 (te(e), te(e), te(n), te(n), te(e)))
    yield "the scan over %d channels backward" % eb, fn["scan_bwd"].lower(
        te(eb), te(eb), te(n), te(n), sds((eb, n)), sds((eb,)), te(eb))
    yield "a Mamba layer's gate, output and SwiGLU over %d rows " \
        "backward" % rows, fn["mamba_out_bwd"].lower(
            sds((b, rows, u)), sds((b, rows, e)), sds((b, rows, e)), mamba,
            sds((b, rows, u)))
    d, hq, hkv = s["head_dim"], s["heads"], s["kv_heads"]
    yield "differential attention of %d query rows backward" % qb, \
        fn["attn_bwd"].lower(sds((b, qb, hq, d)), sds((b, t, hkv, d)),
                             sds((b, t, hkv, d)), sds((), jnp.int32), full,
                             3, None, sds((b, qb, hq // 2, 2 * d)))
    yield "head and loss of %d rows with gradient" % rows, \
        fn["head"].lower(sds((b, rows, u)),
                         {k: sds(shapes[k]) for k in HEAD_LEAVES},
                         sds((b, rows)), sds(()))


def _add(a, b):
    return b if a is None else jax.tree_util.tree_map(jnp.add, a, b)


def _cat(parts):
    return jnp.concatenate(parts, axis=1)


def _sub(p, names):
    return {k: p[k] for k in names}


def _merge(d, extra):
    """``d`` with the gradients of ``extra`` added by leaf."""
    return {k: g + extra[k] if k in extra else g for k, g in d.items()}


def _scan(fn, u, delta, b, c, p):
    return jnp.concatenate(
        [fn["scan"](u[..., e:e + w], delta[..., e:e + w], b, c,
                    p["a_log_weight"][e:e + w], p["d_weight"][e:e + w])
         for e, w in _blocks_of(u.shape[-1], SCAN_BLOCK)], axis=-1)


def _layer_forward(fn, x, p, i, kind, wrote, config):
    """The layer's output and what it writes for a later reader."""
    t = x.shape[1]
    rows = _blocks_of(t, ROW_BLOCK)
    src = sources(config).get(i)
    if kind == "mamba":
        u, delta, b, c, z = fn["mamba_in"](x, _sub(p, MAMBA_IN))
        y = _scan(fn, u, delta, b, c, p)
        out = _cat([fn["mamba_out"](x[:, r:r + n], y[:, r:r + n],
                                    z[:, r:r + n], p) for r, n in rows])
        return out, y
    if kind == "gmu":
        m = wrote[src]
        return _cat([fn["gmu"](x[:, r:r + n], m[:, r:r + n], p)
                     for r, n in rows]), None
    qkv = fn["attn_in_" + kind](x, p)
    if kind == "cross_attention":
        qkv = qkv + wrote[src]
    q, k, v = qkv
    o = _cat([fn["attn"](q[:, r:r + n], k, v, jnp.int32(r), p, i,
                         _window(config, kind))
              for r, n in _blocks_of(t, Q_BLOCK)])
    out = _cat([fn["attn_out"](x[:, r:r + n], o[:, r:r + n], p)
                for r, n in rows])
    return out, (None if kind == "cross_attention" else (k, v))


def loss_and_grads(params, tokens, labels, config, quant=False, pieces=None,
                   sink=None):
    """``(loss, {leaf: gradient})`` — :func:`loss_fn`'s value and
    gradient, a piece of a layer at a time.  ``sink(name, gradient)``, if
    given, takes each leaf's gradient as soon as it is whole (the walk
    then keeps none: at the timed sizes every leaf's gradient beside the
    weights and both Adam slots would not fit the chip)."""
    fn = pieces or _pieces(config, quant)
    s = sizes(config)
    kinds, t = s["kinds"], tokens.shape[1]
    grads = {}
    sink = sink or grads.__setitem__
    rows = _blocks_of(t, ROW_BLOCK)
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    entered, wrote = [], {}
    for i, kind in enumerate(kinds):
        entered.append(x)
        x, w = _layer_forward(fn, x, layer_params(params, i, config), i,
                              kind, wrote, config)
        if w is not None:
            wrote[i] = w
    head_p = _sub(params, HEAD_LEAVES)
    count = jnp.float32(tokens.shape[0] * t)
    loss, cts, d_head = 0.0, [], None
    for r, n in rows:
        part, (ct_b, d_b) = fn["head"](x[:, r:r + n], head_p,
                                       labels[:, r:r + n], count)
        loss, d_head = loss + part, _add(d_head, d_b)
        cts.append(ct_b)
    ct = _cat(cts)
    table = d_head.pop("embed_weight")
    for k, g in d_head.items():
        sink(k, g)
    ct_wrote = {}
    for i in reversed(range(len(kinds))):
        kind, x = kinds[i], entered.pop()
        p = layer_params(params, i, config)
        src = sources(config).get(i)
        d = None
        if kind == "mamba":
            u, delta, b, c, z = fn["mamba_in"](x, _sub(p, MAMBA_IN))
            e_blocks = _blocks_of(u.shape[-1], SCAN_BLOCK)
            y = _scan(fn, u, delta, b, c, p)
            ct_x, ct_y, ct_z = [], [], []
            for r, n in rows:
                cx, cy, cz, d_b = fn["mamba_out_bwd"](
                    x[:, r:r + n], y[:, r:r + n], z[:, r:r + n], p,
                    ct[:, r:r + n])
                ct_x.append(cx)
                ct_y.append(cy)
                ct_z.append(cz)
                d = _add(d, d_b)
            ct_y = _cat(ct_y) + ct_wrote.pop(i, 0.0)
            ct_u, ct_dl, ct_b, ct_c = [], [], 0.0, 0.0
            for e, w in e_blocks:
                cu, cdl, cb, cc, ca, cd = fn["scan_bwd"](
                    u[..., e:e + w], delta[..., e:e + w], b, c,
                    p["a_log_weight"][e:e + w], p["d_weight"][e:e + w],
                    ct_y[..., e:e + w])
                ct_u.append(cu)
                ct_dl.append(cdl)
                ct_b, ct_c = ct_b + cb, ct_c + cc
                d["a_log_weight"] = d["a_log_weight"].at[e:e + w].add(ca)
                d["d_weight"] = d["d_weight"].at[e:e + w].add(cd)
            cx_in, d_in = fn["mamba_in_bwd"](
                x, _sub(p, MAMBA_IN),
                (jnp.concatenate(ct_u, -1), jnp.concatenate(ct_dl, -1),
                 ct_b, ct_c, _cat(ct_z)))
            ct = _cat(ct_x) + cx_in
            d = _merge(d, d_in)
        elif kind == "gmu":
            m, ct_x, ct_m = wrote[src], [], []
            for r, n in rows:
                cx, cm, d_b = fn["gmu_bwd"](x[:, r:r + n], m[:, r:r + n], p,
                                            ct[:, r:r + n])
                ct_x.append(cx)
                ct_m.append(cm)
                d = _add(d, d_b)
            ct = _cat(ct_x)
            ct_wrote[src] = ct_wrote.get(src, 0.0) + _cat(ct_m)
        else:
            qkv = fn["attn_in_" + kind](x, p)
            if kind == "cross_attention":
                qkv = qkv + wrote[src]
            q, k, v = qkv
            o = _cat([fn["attn"](q[:, r:r + n], k, v, jnp.int32(r), p, i,
                                 _window(config, kind))
                      for r, n in _blocks_of(t, Q_BLOCK)])
            ct_x, ct_o = [], []
            for r, n in rows:
                cx, co, d_b = fn["attn_out_bwd"](
                    x[:, r:r + n], o[:, r:r + n], p, ct[:, r:r + n])
                ct_x.append(cx)
                ct_o.append(co)
                d = _add(d, d_b)
            ct_o = _cat(ct_o)
            dq, dk, dv = [], 0.0, 0.0
            for r, n in _blocks_of(t, Q_BLOCK):
                cq, ck, cv, d_b = fn["attn_bwd"](
                    q[:, r:r + n], k, v, jnp.int32(r), p, i,
                    _window(config, kind), ct_o[:, r:r + n])
                dq.append(cq)
                dk, dv = dk + ck, dv + cv
                d = _add(d, d_b)
            if kind == "cross_attention":
                ck, cv = ct_wrote.get(src, (0.0, 0.0))
                ct_wrote[src] = (ck + dk, cv + dv)
                cts_in = (_cat(dq),)
            else:
                ck, cv = ct_wrote.pop(i, (0.0, 0.0))
                cts_in = (_cat(dq), dk + ck, dv + cv)
            cx_in, d_in = fn["attn_in_%s_bwd" % kind](x, p, cts_in)
            ct = _cat(ct_x) + cx_in
            d = _add(d, d_in)
        for k, g in d.items():
            sink("l%d_%s" % (i, k), g)
        if kind == "mamba":
            del u, delta, z, y
    # the tied table: the head's gradient, then the look-up's added in
    sink("embed_weight", fn["embed_bwd"](table, tokens, ct))
    if grads:
        return loss, {k: grads[k] for k in params}
    return loss, None


def train_steps(config, weights, batches, precision="reference",
                rows=None, devices=None):
    """Follow ``len(batches)`` steps of Adam from ``weights``; returns
    ``{"loss": [...], "grad1": {leaf: norm}, "dparam": {leaf: norm}}``.
    ``rows`` (a slice) plants the fault "part of the batch left out".
    Each leaf is updated as soon as its gradient is whole."""
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
        fn = _pieces(config, quant)
        params = {k: jnp.asarray(v) for k, v in weights.items()}
        mean = {k: jnp.zeros(v.shape, jnp.float32)
                for k, v in weights.items()}
        var = {k: jnp.zeros(v.shape, jnp.float32)
               for k, v in weights.items()}
        out = {"loss": [], "grad1": {}}
        for i, (x, y) in enumerate(batches):
            if rows is not None:
                x, y = x[rows], y[rows]
            t = jnp.float32(i + 1)

            def update(k, g, i=i, t=t):
                # the walk reads no leaf again once its gradient is whole
                if i == 0:
                    out["grad1"][k] = float(norm(g))
                params[k], mean[k], var[k] = adam(
                    params[k], mean[k], var[k], g, t,
                    wd * wd_mult(k, config))

            loss, _ = loss_and_grads(params, jnp.asarray(x), jnp.asarray(y),
                                     config, quant, fn, update)
            out["loss"].append(float(loss))
        del mean, var
        out["dparam"] = {k: float(diff(params[k], jnp.asarray(weights[k])))
                         for k in weights}
    return out
