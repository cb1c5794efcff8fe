"""Plain reference: a decoder-only language model of SPARSE-EXPERT layers
under attention of two kinds — three ``sliding_attention`` layers (a
query sees its last ``sliding_window`` keys) to every ``full_attention``
one — trained by Adam on the next token's cross-entropy, float32 at the
highest matmul precision, jax.numpy only.  The model is Mellum 2
(JetBrains, ``Mellum2-12B-A2.5B-Instruct`` ``config.json``) as its
config and the configuration file's ``assumed`` list give it, and it is
handed ONE CHIP'S SHARE of the deployment the configuration states:
experts ``deployment.experts_held = [first, end)`` of the
``published.num_experts`` the router runs over, and the first
``vocab_size`` rows of the vocabulary.

It imports nothing of the program and is handed nothing the program
made: no kernel, no sort, no gather of rows by expert, no grouped
product, no ``checkpoint`` — Python loops.

Layer ``l`` on ``x (B, T, U)``, no bias anywhere::

    h  = RMS(x; g1)
    q, k, v = h Wq', h Wk', h Wv'  -> (T, H, D), (T, Hkv, D), (T, Hkv, D)
    q, k = rope_l(q), rope_l(k)      half-split pairing, positions 0..T-1
    s_ij = q_i . k_j / sqrt(D)  for j <= i and, on a sliding layer,
           i - j < sliding_window;  masked otherwise (from POSITIONS)
    a  = softmax_j(s) v              query head n reads key/value head n // (H / Hkv)
    x  = x + a Wo'
    h2 = RMS(x; g2)
    r  = softmax(h2 Wr')  over ALL published experts
    S  = the top_k largest of r;  w_e = r_e / sum_{e' in S} r_e'  (norm_topk_prob)
    y  = sum_{e in S and held} w_e Wd_e (silu(Wg_e h2) * (Wu_e h2))
    x  = x + y

The expert part is the DENSE MASKED form: every held expert is applied
to every token and weighed by that token's ``w_e``, which is zero where
the expert was not chosen.  ``rope_l``: ``inv_freq_i = theta^(-2i/D)``;
on a layer whose ``rope_parameters`` entry is ``yarn`` (as
``transformers`` computes it) the frequencies are blended with
``inv_freq_i / factor`` by a linear ramp between the dimensions that turn
``beta_fast`` and ``beta_slow`` times over the original context, and cos
and sin are multiplied by ``attention_factor``.  Then
``logits = RMS(x; gf) Wh'`` over the held rows of the vocabulary, loss =
mean over positions of the next token's cross-entropy.

:func:`loss_fn` is the whole of it in one function, for
``jax.value_and_grad`` at sizes where everything fits (the CPU tests).
At the timed sizes one layer's ``(H, T, T)`` float32 probabilities are
8.6 GB, so :func:`loss_and_grads` computes the same numbers IN BLOCKS: a
layer is three pieces — the projections with rotary, attention over
``Q_BLOCK`` query rows at a time, the output projection with the expert
part — and it walks back one piece at a time with ``jax.vjp``.  A test
holds the two to each other.

``precision="fp8"`` is the CONTROL, the step below the bf16 the
configuration states (``reference_common.py``): both operands of every
matrix product rounded to e4m3, the gradient arriving at its output to
e5m2.
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
LAYER_LEAVES = ("norm1_gamma", "q_weight", "k_weight", "v_weight",
                "out_weight", "norm2_gamma", "router_weight", "gate_weight",
                "up_weight", "down_weight")
ATTN_IN_LEAVES = LAYER_LEAVES[:4]
REST_LEAVES = LAYER_LEAVES[4:]
HEAD_LEAVES = ("norm_gamma", "head_weight")
Q_BLOCK = 512       # query rows an attention piece works on


def sizes(config):
    """The sizes the equations read, by name."""
    first, end = (int(e) for e in config["deployment"]["experts_held"])
    s = {"vocab": int(config["vocab_size"]),
         "units": int(config["hidden_size"]),
         "heads": int(config["num_attention_heads"]),
         "kv_heads": int(config["num_key_value_heads"]),
         "head_dim": int(config["head_dim"]),
         "expert_width": int(config["moe_intermediate_size"]),
         "held": (first, end - first),
         "routed": int(config["published"]["num_experts"]),
         "top_k": int(config["num_experts_per_tok"]),
         "layers": int(config["num_hidden_layers"]),
         "window": int(config["sliding_window"]),
         "eps": float(config["rms_norm_eps"])}
    if s["held"][1] != int(config["num_experts"]):
        raise ValueError("deployment.experts_held %s is not num_experts %s"
                         % (config["deployment"]["experts_held"],
                            config["num_experts"]))
    return s


def layer_kinds(config):
    """``layer_types`` of the layers that are run (the first
    ``num_hidden_layers`` of the published list)."""
    return list(config["layer_types"])[:int(config["num_hidden_layers"])]


def leaf_specs(config):
    """Ordered [(name, shape, init)] of every trainable leaf, in the
    block's construction order; a held expert's weights are stacked."""
    s = sizes(config)
    u, d, f, n = s["units"], s["head_dim"], s["expert_width"], s["held"][1]
    shape = {"norm1_gamma": (u,), "q_weight": (s["heads"] * d, u),
             "k_weight": (s["kv_heads"] * d, u),
             "v_weight": (s["kv_heads"] * d, u),
             "out_weight": (u, s["heads"] * d), "norm2_gamma": (u,),
             "router_weight": (s["routed"], u), "gate_weight": (n, f, u),
             "up_weight": (n, f, u), "down_weight": (n, u, f)}
    specs = [("embed_weight", (s["vocab"], u), "normal")]
    for i in range(s["layers"]):
        specs += [("l%d_%s" % (i, k), shape[k],
                   "one" if k.endswith("gamma") else "normal")
                  for k in LAYER_LEAVES]
    return specs + [("norm_gamma", (u,), "one"),
                    ("head_weight", (s["vocab"], u), "normal")]


RESIDUAL_LEAVES = ("out_weight", "down_weight")   # they write the residual


def init_weights(config, seed):
    """Normal(0, init_std) matrices — Normal(0, embed_init_std) the
    embedding, Normal(0, residual_init_std) the two projections that
    write into the residual stream — gains 1, float32; made on the device
    in ONE jitted call from the seed, then read back once."""
    def std(name):
        return float(config[
            "embed_init_std" if name == "embed_weight" else
            "residual_init_std" if name.endswith(RESIDUAL_LEAVES) else
            "init_std"])

    specs = leaf_specs(config)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            if init == "normal":
                out[name] = std(name) * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                out[name] = jnp.ones(shape, jnp.float32)
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


def yarn_correction_range(rope, dim):
    """``(low, high)``: the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original context (``transformers``'
    ``find_correction_range``, not truncated further)."""
    base = float(rope["rope_theta"])
    orig = float(rope["original_max_position_embeddings"])

    def d(beta):
        return dim * math.log(orig / (beta * 2 * math.pi)) \
            / (2 * math.log(base))

    low = math.floor(d(float(rope["beta_fast"])))
    high = math.ceil(d(float(rope["beta_slow"])))
    return max(low, 0), min(high, dim - 1)


def rope_table(rope, dim):
    """``(inv_freq (dim/2,) float64, scale)`` of one ``rope_parameters``
    entry: plain rotary, or YaRN's blended frequencies with the factor
    cos and sin are multiplied by."""
    base = float(rope["rope_theta"])
    inv = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return inv, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError("no rule for rope_type %r" % rope["rope_type"])
    low, high = yarn_correction_range(rope, dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    inv = inv / float(rope["factor"]) * ramp + inv * (1.0 - ramp)
    return inv, float(rope["attention_factor"])


def _rope(x, rope):
    """x: (B, T, H, D); pairs (i, i + D/2) turn by t * inv_freq_i."""
    t, d = x.shape[1], x.shape[-1]
    inv, scale = rope_table(rope, d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * (jnp.cos(emb) * scale) + turned * (jnp.sin(emb) * scale)


def _attn_in(x, p, kind, config, quant):
    """The normed state's three projections, q and k turned."""
    s = sizes(config)
    b, t, _u = x.shape
    h = _rms(x, p["norm1_gamma"], s["eps"])
    rope = config["rope_parameters"][kind]

    def heads(w, n):
        return _mm(h, w, "btu,ou->bto", quant).reshape(b, t, n, s["head_dim"])

    return (_rope(heads(p["q_weight"], s["heads"]), rope),
            _rope(heads(p["k_weight"], s["kv_heads"]), rope),
            heads(p["v_weight"], s["kv_heads"]))


def _attn_block(q, k, v, row0, kind, config, quant):
    """Attention of the query rows ``row0 .. row0 + q.shape[1] - 1``
    over every key, the mask built from positions."""
    s = sizes(config)
    b, tq, hq, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, tq, hkv, hq // hkv, d)    # head n reads kv head n // g
    sc = _mm(qg, k, "bqhgd,bkhd->bhgqk", quant) / math.sqrt(d)
    i = row0 + jnp.arange(tq)[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    see = j <= i
    if kind == "sliding_attention":
        see = jnp.logical_and(see, i - j < s["window"])
    pr = jax.nn.softmax(jnp.where(see[None, None, None], sc, -1e30), axis=-1)
    return _mm(pr, v, "bhgqk,bkhd->bqhgd", quant).reshape(b, tq, hq, d)


def route(h, router_w, config, quant):
    """``(T.., E)`` float32: each token's weight for every published
    expert — softmax over all of them, zero outside its ``top_k``
    largest, those renormalised to sum to 1 (``norm_topk_prob``)."""
    s = sizes(config)
    r = jax.nn.softmax(_mm(h, router_w, "btu,eu->bte", quant), axis=-1)
    kth = lax.top_k(r, s["top_k"])[0][..., -1:]
    w = jnp.where(r >= kth, r, 0.0)
    if config["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w


def experts_dense(h, w, p, config, quant):
    """The held experts' part of the layer, DENSE MASKED: every held
    expert on every token, times the token's weight for it."""
    first, n = sizes(config)["held"]
    g = _mm(h, p["gate_weight"], "btu,efu->btef", quant)
    u = _mm(h, p["up_weight"], "btu,efu->btef", quant)
    y = _mm(jax.nn.silu(g) * u, p["down_weight"], "btef,euf->bteu", quant)
    return jnp.sum(w[..., first:first + n, None] * y, axis=2)


def _rest(x, o, p, config, quant):
    """Attention's output projected and added, then the expert part."""
    s = sizes(config)
    b, t, u = x.shape
    x = x + _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu", quant)
    h = _rms(x, p["norm2_gamma"], s["eps"])
    w = route(h, p["router_weight"], config, quant)
    return x + experts_dense(h, w, p, config, quant)


def _blocks_of(t):
    return [(r, min(Q_BLOCK, t - r)) for r in range(0, t, Q_BLOCK)]


def _layer(x, p, kind, config, quant):
    q, k, v = _attn_in(x, {n: p[n] for n in ATTN_IN_LEAVES}, kind, config,
                       quant)
    o = jnp.concatenate(
        [_attn_block(q[:, r:r + n], k, v, r, kind, config, quant)
         for r, n in _blocks_of(x.shape[1])], axis=1)
    return _rest(x, o, {n: p[n] for n in REST_LEAVES}, config, quant)


def _head(x, p, labels, config, quant):
    """``(logits, per-position cross-entropy)`` over the held rows of
    the vocabulary."""
    h = _rms(x, p["norm_gamma"], sizes(config)["eps"])
    logits = _mm(h, p["head_weight"], "btu,vu->btv", quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels.astype(jnp.int32)[..., None],
                              axis=-1)[..., 0]
    return logits, ce


def _layer_params(params, i):
    pre = "l%d_" % i
    return {k: params[pre + k] for k in LAYER_LEAVES}


def forward(params, tokens, labels, config, quant=False):
    """``(logits, per-position cross-entropy)``, whole — what the tests
    hold the block's outputs to."""
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    for i, kind in enumerate(layer_kinds(config)):
        x = _layer(x, _layer_params(params, i), kind, config, quant)
    return _head(x, params, labels, config, quant)


def loss_fn(params, tokens, labels, config, quant=False):
    return jnp.mean(forward(params, tokens, labels, config, quant)[1])


def expert_layer(h, p, config, quant=False):
    """One layer's expert part alone over normed states ``h (B, T, U)``
    — router, top-k, the held experts — for the test that sums the
    shares."""
    return experts_dense(h, route(h, p["router_weight"], config, quant), p,
                         config, quant)


# -- what the router did (read, printed, not compared) ------------------------
def routing_stats(h, router_w, config):
    """``(rows per held expert (count,), share of (token, slot)
    assignments that differ when router and state are rounded to
    bfloat16)`` of one layer's normed states."""
    s = sizes(config)
    first, n = s["held"]
    w = route(h, router_w, config, False)
    rows = jnp.sum(w[..., first:first + n] > 0, axis=(0, 1))
    low = jnp.einsum("btu,eu->bte", h.astype(jnp.bfloat16),
                     router_w.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    chosen_low = lax.top_k(jax.nn.softmax(low, axis=-1), s["top_k"])[1]
    picked = jnp.take_along_axis(w, chosen_low, axis=-1) > 0
    return rows, 1.0 - jnp.mean(picked.astype(jnp.float32))


# -- the same numbers in blocks ----------------------------------------------
def _blocks(config, quant):
    """The jitted pieces :func:`loss_and_grads` walks with; ``kind`` and
    block sizes are static, a block's first row is data."""
    def attn_in(x, p, kind):
        return _attn_in(x, p, kind, config, quant)

    def attn_in_bwd(x, p, kind, cts):
        return jax.vjp(lambda x_, p_: attn_in(x_, p_, kind), x, p)[1](cts)

    def attn(q, k, v, row0, kind):
        return _attn_block(q, k, v, row0, kind, config, quant)

    def attn_bwd(q, k, v, row0, kind, ct):
        return jax.vjp(lambda q_, k_, v_: attn(q_, k_, v_, row0, kind),
                       q, k, v)[1](ct)

    def rest(x, o, p):
        return _rest(x, o, p, config, quant)

    def rest_bwd(x, o, p, ct):
        return jax.vjp(rest, x, o, p)[1](ct)

    def head(x, p, labels):
        return jax.value_and_grad(
            lambda x_, p_: jnp.mean(_head(x_, p_, labels, config, quant)[1]),
            argnums=(0, 1))(x, p)

    def embed_bwd(shape, tokens, ct):
        return jnp.zeros(shape, jnp.float32).at[
            tokens.astype(jnp.int32)].add(ct)

    def stats(x, o, p):
        s = sizes(config)
        b, t, _u = x.shape
        x = x + _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu",
                    False)
        return routing_stats(_rms(x, p["norm2_gamma"], s["eps"]),
                             p["router_weight"], config)

    return {"attn_in": jax.jit(attn_in, static_argnums=2),
            "attn_in_bwd": jax.jit(attn_in_bwd, static_argnums=2),
            "attn": jax.jit(attn, static_argnums=4),
            "attn_bwd": jax.jit(attn_bwd, static_argnums=4),
            "rest": jax.jit(rest), "rest_bwd": jax.jit(rest_bwd),
            "head": jax.jit(head), "stats": jax.jit(stats),
            "embed_bwd": jax.jit(embed_bwd, static_argnums=0)}


def described_programs(config, sds):
    """``(what, lowered)`` of the largest programs the walk runs, for
    ``rehearse_compile.py --reference``; ``sds(shape, dtype=float32)``
    makes an argument on the described chip."""
    s = sizes(config)
    b, t = int(config["batch_size"]), int(config["seq_len"])
    shapes = {n: sh for n, sh, _i in leaf_specs(config)}
    rest_p = {k: sds(shapes["l0_" + k]) for k in REST_LEAVES}
    head_p = {k: sds(shapes[k]) for k in HEAD_LEAVES}
    x = sds((b, t, s["units"]))
    o = sds((b, t, s["heads"], s["head_dim"]))
    kv = sds((b, t, s["kv_heads"], s["head_dim"]))
    qb = sds((b, min(Q_BLOCK, t), s["heads"], s["head_dim"]))
    fn = _blocks(config, False)
    print("the walk keeps, a layer, the state that enters it and its q, "
          "k, v and attention output: %.3f GB beside parameters and "
          "gradients" % (4 * b * t * (s["units"] + 2 * s["heads"]
                                      * s["head_dim"] + 2 * s["kv_heads"]
                                      * s["head_dim"]) / 1e9))
    yield "attention of %d query rows backward" % qb.shape[1], \
        fn["attn_bwd"].lower(qb, kv, kv, sds((), jnp.int32),
                             "full_attention", qb)
    yield "output projection and expert part backward", \
        fn["rest_bwd"].lower(x, o, rest_p, x)
    yield "head and loss with gradient", \
        fn["head"].lower(x, head_p, sds((b, t)))


def loss_and_grads(params, tokens, labels, config, quant=False, blocks=None,
                   routing=None):
    """``(loss, {leaf: gradient})`` — :func:`loss_fn`'s value and
    gradient, one piece of one layer at a time.  ``routing``, a list,
    gets one :func:`routing_stats` per layer."""
    fn = blocks or _blocks(config, quant)
    kinds = layer_kinds(config)
    layers = [_layer_params(params, i) for i in range(len(kinds))]
    pieces = _blocks_of(tokens.shape[1])
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    kept = []
    for p, kind in zip(layers, kinds):
        p_in = {n: p[n] for n in ATTN_IN_LEAVES}
        p_rest = {n: p[n] for n in REST_LEAVES}
        q, k, v = fn["attn_in"](x, p_in, kind)
        o = jnp.concatenate([fn["attn"](q[:, r:r + n], k, v, jnp.int32(r),
                                        kind) for r, n in pieces], axis=1)
        if routing is not None:
            routing.append(fn["stats"](x, o, p_rest))
        kept.append((x, q, k, v, o))
        x = fn["rest"](x, o, p_rest)
    head_p = {k: params[k] for k in HEAD_LEAVES}
    loss, (ct, d_head) = fn["head"](x, head_p, labels)
    grads = dict(d_head)
    for i in reversed(range(len(kinds))):
        kind, p = kinds[i], layers[i]
        x, q, k, v, o = kept.pop()
        ct_x, ct_o, d_rest = fn["rest_bwd"](
            x, o, {n: p[n] for n in REST_LEAVES}, ct)
        dq, dk, dv = [], jnp.zeros_like(k), jnp.zeros_like(v)
        for r, n in pieces:
            dq_b, dk_b, dv_b = fn["attn_bwd"](
                q[:, r:r + n], k, v, jnp.int32(r), kind, ct_o[:, r:r + n])
            dq.append(dq_b)
            dk, dv = dk + dk_b, dv + dv_b
        ct_in, d_in = fn["attn_in_bwd"](
            x, {n: p[n] for n in ATTN_IN_LEAVES}, kind,
            (jnp.concatenate(dq, axis=1), dk, dv))
        ct = ct_x + ct_in
        for name, g in {**d_in, **d_rest}.items():
            grads["l%d_%s" % (i, name)] = g
    grads["embed_weight"] = fn["embed_bwd"](
        params["embed_weight"].shape, tokens, ct)
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
