"""Plain reference: a sparse-expert language model trained by DIFFUSION
OVER BLOCKS — SDAR-30B-A3B-Chat (JetLM, ``config.json`` ``model_type``
``sdar_moe``; "SDAR: A Synergistic Diffusion-AutoRegression Paradigm",
arXiv:2510.06303) as its config and the configuration file's ``assumed``
list give it, under the objective and training mask of block diffusion
(Arriola et al., "Block Diffusion", arXiv:2503.09573) — float32 at the
highest matmul precision, jax.numpy only, Adam.  It is handed ONE CHIP'S
SHARE of the deployment the configuration states: experts
``deployment.experts_held = [first, end)`` of the
``published.num_experts`` the router runs over, and the first
``vocab_size`` rows of the vocabulary.

It imports nothing of the program and is handed nothing the program
made: no kernel, no sort, no gather of rows by expert, no grouped
product, no ``checkpoint`` — Python loops, and the mask as a boolean
array built from its three-part definition.

One training step on clean ids ``x0 (L,)``, blocks ``b = i // B``::

    t_b, u_i     one draw a block, one a position, integers k / 2^24
                 made from the batch's ids alone (``draws``)
    p_b  = (1 - eps) t_b + eps        on the 2^-24 grid (``noise``)
    m_i  = [u_i < p_b];  x~_i = MASK if m_i else x0_i;  w_i = m_i / p_b
    rows = [x~ ; x0] (2 L)  at positions [0 .. L-1 ; 0 .. L-1]
    row r sees row c  iff  half r = half c and blk r = blk c
                       or  half r = 0, half c = 1 and blk r >  blk c
                       or  half r = 1, half c = 1 and blk r >= blk c
    loss = (1 / L) sum_{i < L} w_i * -log softmax(Wh RMS(x^N_i; gf))[x0_i]

Layer ``l`` on rows ``x (B, R, U)``, no bias anywhere (Qwen3-MoE's
layer)::

    h  = RMS(x; g1)
    q, k, v = h Wq', h Wk', h Wv'  -> (R, H, D), (R, Hkv, D), (R, Hkv, D)
    q, k = RMS(q; gq), RMS(k; gk)    over the D of each head (QK-norm)
    q, k = rope(q), rope(k)          half-split pairing, the ROW'S position
    a  = softmax_c(q_r . k_c / sqrt(D) over the visible c) v
                                     query head n reads key/value head n // (H / Hkv)
    x  = x + a Wo'
    h2 = RMS(x; g2)
    r  = softmax(h2 Wr')  over ALL published experts
    S  = the top_k largest of r;  w_e = r_e / sum_{e' in S} r_e'  (norm_topk_prob)
    y  = sum_{e in S and held} w_e Wd_e (silu(Wg_e h2) * (Wu_e h2))
    x  = x + y

The expert part is the DENSE MASKED form: every held expert on every
row, weighed by that row's ``w_e``, zero where the expert was not chosen.

:func:`loss_fn` is the whole of it in one function, for
``jax.value_and_grad`` at sizes where everything fits (the CPU tests).
At the timed sizes :func:`loss_and_grads` computes the same numbers IN
BLOCKS: a layer is three pieces — the projections with QK-norm and
rotary, attention over ``Q_BLOCK`` query rows at a time against every key
under the mask's rows for them, the output projection with the expert
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
                "q_norm_gamma", "k_norm_gamma", "out_weight", "norm2_gamma",
                "router_weight", "gate_weight", "up_weight", "down_weight")
ATTN_IN_LEAVES = LAYER_LEAVES[:6]
REST_LEAVES = LAYER_LEAVES[6:]
HEAD_LEAVES = ("norm_gamma", "head_weight")
Q_BLOCK = 512       # query rows an attention piece works on
DRAW_BITS = 24      # a draw is k / 2**24


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
         "block": int(config["block_length"]),
         "mask_id": int(config["mask_token_id"]),
         "noise_eps": float(config["noise_eps"]),
         "rope_theta": float(config["rope_theta"]),
         "eps": float(config["rms_norm_eps"])}
    if s["held"][1] != int(config["num_experts"]):
        raise ValueError("deployment.experts_held %s is not num_experts %s"
                         % (config["deployment"]["experts_held"],
                            config["num_experts"]))
    return s


def leaf_specs(config):
    """Ordered [(name, shape, init)] of every trainable leaf, in the
    block's construction order; a held expert's weights are stacked."""
    s = sizes(config)
    u, d, f, n = s["units"], s["head_dim"], s["expert_width"], s["held"][1]
    shape = {"norm1_gamma": (u,), "q_weight": (s["heads"] * d, u),
             "k_weight": (s["kv_heads"] * d, u),
             "v_weight": (s["kv_heads"] * d, u),
             "q_norm_gamma": (d,), "k_norm_gamma": (d,),
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
    made = {name: made[name] for name, _shape, _init in specs}
    _seat_experts(made, config)
    return made     # in construction order


def _seat_experts(weights, config):
    """Order every router's rows so that this chip's load does not hang
    on the seed: of the experts the MASK token routes to it holds
    ``deployment.mask_experts_held`` (the configuration's decision, read
    here and by ``counts/``), and its other held rows get experts of
    AVERAGE popularity among ordinary tokens.

    Every masked position enters the stack as the one MASK embedding, and
    with these weights (a unit-variance embedding under small residual
    writes, ``assumed.init_std``) a row's state stays its own token's
    embedding direction at every router.  So (1) a quarter of the 2 L
    rows — the masked ones — take the same ``top_k`` experts a layer.
    How many of those lie among the held ones would be a hypergeometric
    draw of the seed (0 to 3 of 16 here), each worth 2048 rows of the
    layer's products; and a held one does not stay: 2048 rows with ONE
    state give its router row a gradient of one sign pattern, Adam's
    normalised step moves that state's logit by ``lr x |state|_1``, about
    0.16 a step against a spread of 0.9 over the experts, and the MASK
    token's choice is reshuffled within a dozen steps, differently for
    every seed (on the chip, one held: 274.9 - 280.5 ms a step over 7
    seeds; held firmly, ranked first: 275.5 - 277.9).  Where NO chosen
    expert is held, a masked row's output here is zero whatever the
    router says, the router gets no gradient from it, and the choice
    stays put.  (2) An ordinary expert's load is the share of the
    vocabulary whose embeddings choose it, 384 rows a step give or take
    5 % by the seed, and 384 rows is the edge of the grouped product's
    tile.  The held rows therefore get the ``mask_experts_held`` experts
    the MASK token ranks first and, of the experts it ranks beyond three
    times its ``top_k`` (ordinary tokens' gradients walk its logits 0.005
    a step: they will not come to be chosen), the ones whose count of
    choosing tokens over the held vocabulary is nearest the mean; the
    others keep their order.  A row is an expert's label: permuting the
    rows of an i.i.d. router relabels experts and changes nothing else
    about the model."""
    s = sizes(config)
    first, n = s["held"]
    of_mask = int(config["deployment"]["mask_experts_held"])
    if not 0 <= of_mask <= min(n, s["top_k"]):
        raise ValueError("deployment.mask_experts_held %d of %d held, top-%d"
                         % (of_mask, n, s["top_k"]))
    embed = weights["embed_weight"].astype(np.float32)
    unit = embed / np.sqrt(np.mean(embed * embed, -1, keepdims=True)
                           + s["eps"])
    far = min(3 * s["top_k"], s["routed"] - n)
    for i in range(s["layers"]):
        w = np.array(weights["l%d_router_weight" % i])
        logits = unit @ w.T                                 # (V, routed)
        order = np.argsort(-logits[s["mask_id"]], kind="stable")
        chosen = np.argpartition(-logits, s["top_k"] - 1,
                                 axis=-1)[:, :s["top_k"]]
        count = np.bincount(chosen.ravel(), minlength=s["routed"])
        others = order[far:]
        mine = np.concatenate([order[:of_mask], others[np.argsort(
            np.abs(count[others] - count.mean()),
            kind="stable")][:n - of_mask]])
        rest = iter(np.setdiff1d(np.arange(s["routed"]), mine))
        rows = [mine[j - first] if first <= j < first + n else next(rest)
                for j in range(s["routed"])]
        weights["l%d_router_weight" % i] = w[np.asarray(rows)]


# -- the noise -----------------------------------------------------------------
def draws(ids, block_length):
    """``(position draws (B, L), block draws (B, L // block_length))``,
    int32 in ``[0, 2**24)``, from the batch's clean ids ALONE: a numpy
    ``Generator`` seeded with them.  The benchmark's driver hands the
    program the same (it calls this function), so the step is a function
    of arrays this reference has too."""
    ids = np.asarray(ids)
    rng = np.random.default_rng(ids.astype(np.uint32).ravel())
    b, l = ids.shape
    one = 1 << DRAW_BITS
    return (rng.integers(0, one, (b, l), dtype=np.int64).astype(np.int32),
            rng.integers(0, one, (b, l // int(block_length)),
                         dtype=np.int64).astype(np.int32))


def noise(ids, position_draws, block_draws, config):
    """``(noised ids (B, L) int32, weights (B, L) float32, masked (B, L)
    bool)`` in numpy: the block's rate on the ``2**-24`` grid, ``P_b =
    k_b + (2**24 - k_b) // round(1 / eps)`` (``(1 - eps) t_b + eps`` to
    within ``2**-24``), the position masked where its draw is under it,
    the weight ``m / p``."""
    s = sizes(config)
    one = 1 << DRAW_BITS
    kt = np.asarray(block_draws, np.int64)
    rate = np.repeat(kt + (one - kt) // int(round(1.0 / s["noise_eps"])),
                     s["block"], axis=1)
    masked = np.asarray(position_draws, np.int64) < rate
    weight = np.where(masked, np.float32(one) / rate.astype(np.float32),
                      np.float32(0.0)).astype(np.float32)
    noised = np.where(masked, s["mask_id"], np.asarray(ids)).astype(np.int32)
    return noised, weight, masked


def noised_batch(ids, config):
    """What a step reads of a batch's clean ids: ``(rows (B, 2 L) int32 —
    noised copy, then clean — weights (B, L), clean ids (B, L))``."""
    ids = np.asarray(ids).astype(np.int32)
    u, t = draws(ids, sizes(config)["block"])
    noised, weight, _m = noise(ids, u, t, config)
    return np.concatenate([noised, ids], axis=1), weight, ids


def visible(rows_q, length, block):
    """``(len(rows_q), 2 L)`` bool: which of the ``2 L`` key rows the
    query rows ``rows_q`` (row numbers) see, from the three-part
    definition; ``length`` is L."""
    r = jnp.asarray(rows_q)[:, None]
    c = jnp.arange(2 * length)[None, :]
    half_r, half_c = r >= length, c >= length
    blk_r, blk_c = (r % length) // block, (c % length) // block
    m_bd = jnp.logical_and(half_r == half_c, blk_r == blk_c)
    m_obc = jnp.logical_and(jnp.logical_and(~half_r, half_c), blk_r > blk_c)
    m_bc = jnp.logical_and(jnp.logical_and(half_r, half_c), blk_r >= blk_c)
    return m_bd | m_obc | m_bc


# -- the pieces ---------------------------------------------------------------
def _mm(a, b, spec, quant):
    if quant:
        return fp8_grad(jnp.einsum(spec, fp8(a), fp8(b), precision=HI))
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, 2 L, H, D); pairs (i, i + D/2) turn by pos * theta^(-2i/D),
    pos the row's position: both halves count 0 .. L - 1."""
    r, d = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    pos = jnp.tile(jnp.arange(r // 2, dtype=jnp.float32), 2)
    ang = pos[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + turned * jnp.sin(emb)


def _attn_in(x, p, config, quant):
    """The normed state's three projections, q and k normed over their
    head and then turned."""
    s = sizes(config)
    b, r, _u = x.shape
    h = _rms(x, p["norm1_gamma"], s["eps"])

    def heads(w, n):
        return _mm(h, w, "btu,ou->bto", quant).reshape(b, r, n, s["head_dim"])

    q = _rms(heads(p["q_weight"], s["heads"]), p["q_norm_gamma"], s["eps"])
    k = _rms(heads(p["k_weight"], s["kv_heads"]), p["k_norm_gamma"],
             s["eps"])
    return (_rope(q, s["rope_theta"]), _rope(k, s["rope_theta"]),
            heads(p["v_weight"], s["kv_heads"]))


def _attn_block(q, k, v, row0, config, quant):
    """Attention of the query rows ``row0 .. row0 + q.shape[1] - 1`` over
    every key row, under the block mask's rows for them."""
    s = sizes(config)
    b, tq, hq, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, tq, hkv, hq // hkv, d)    # head n reads kv head n // g
    sc = _mm(qg, k, "bqhgd,bkhd->bhgqk", quant) / math.sqrt(d)
    see = visible(row0 + jnp.arange(tq), k.shape[1] // 2, s["block"])
    pr = jax.nn.softmax(jnp.where(see[None, None, None], sc, -1e30), axis=-1)
    return _mm(pr, v, "bhgqk,bkhd->bqhgd", quant).reshape(b, tq, hq, d)


def route(h, router_w, config, quant):
    """``(R.., E)`` float32: each row's weight for every published
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
    expert on every row, times the row's weight for it."""
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


def _layer(x, p, config, quant):
    q, k, v = _attn_in(x, {n: p[n] for n in ATTN_IN_LEAVES}, config, quant)
    o = jnp.concatenate(
        [_attn_block(q[:, r:r + n], k, v, r, config, quant)
         for r, n in _blocks_of(x.shape[1])], axis=1)
    return _rest(x, o, {n: p[n] for n in REST_LEAVES}, config, quant)


def _head(x, p, weight, clean, config, quant):
    """``(logits, per-position weighted cross-entropy)`` of the NOISED
    half's states over the held rows of the vocabulary: position ``i``
    is held to its own clean token, weighed ``w_i``."""
    length = clean.shape[1]
    h = _rms(x[:, :length], p["norm_gamma"], sizes(config)["eps"])
    logits = _mm(h, p["head_weight"], "btu,vu->btv", quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, clean.astype(jnp.int32)[..., None],
                              axis=-1)[..., 0]
    return logits, weight * ce


def _layer_params(params, i):
    pre = "l%d_" % i
    return {k: params[pre + k] for k in LAYER_LEAVES}


def forward(params, rows, weight, clean, config, quant=False):
    """``(logits (B, L, V) of the noised half, per-position weighted
    cross-entropy (B, L))``, whole — what the tests hold the block's
    outputs to."""
    x = params["embed_weight"][rows.astype(jnp.int32)]
    for i in range(sizes(config)["layers"]):
        x = _layer(x, _layer_params(params, i), config, quant)
    return _head(x, params, weight, clean, config, quant)


def loss_fn(params, rows, weight, clean, config, quant=False):
    return jnp.mean(forward(params, rows, weight, clean, config, quant)[1])


def expert_layer(h, p, config, quant=False):
    """One layer's expert part alone over normed states ``h (B, R, U)``
    — router, top-k, the held experts — for the test that sums the
    shares."""
    return experts_dense(h, route(h, p["router_weight"], config, quant), p,
                         config, quant)


# -- what the router did (read, printed, not compared) ------------------------
def routing_stats(h, router_w, config):
    """``(rows per held expert (count,), share of (row, slot)
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
    """The jitted pieces :func:`loss_and_grads` walks with; block sizes
    are static, a block's first row is data."""
    def attn_in(x, p):
        return _attn_in(x, p, config, quant)

    def attn_in_bwd(x, p, cts):
        return jax.vjp(attn_in, x, p)[1](cts)

    def attn(q, k, v, row0):
        return _attn_block(q, k, v, row0, config, quant)

    def attn_bwd(q, k, v, row0, ct):
        return jax.vjp(lambda q_, k_, v_: attn(q_, k_, v_, row0),
                       q, k, v)[1](ct)

    def rest(x, o, p):
        return _rest(x, o, p, config, quant)

    def rest_bwd(x, o, p, ct):
        return jax.vjp(rest, x, o, p)[1](ct)

    def head(x, p, weight, clean):
        return jax.value_and_grad(
            lambda x_, p_: jnp.mean(_head(x_, p_, weight, clean, config,
                                          quant)[1]),
            argnums=(0, 1))(x, p)

    def embed_bwd(shape, rows, ct):
        return jnp.zeros(shape, jnp.float32).at[
            rows.astype(jnp.int32)].add(ct)

    def stats(x, o, p):
        s = sizes(config)
        b, t, _u = x.shape
        x = x + _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu",
                    False)
        return routing_stats(_rms(x, p["norm2_gamma"], s["eps"]),
                             p["router_weight"], config)

    return {"attn_in": jax.jit(attn_in), "attn_in_bwd": jax.jit(attn_in_bwd),
            "attn": jax.jit(attn), "attn_bwd": jax.jit(attn_bwd),
            "rest": jax.jit(rest), "rest_bwd": jax.jit(rest_bwd),
            "head": jax.jit(head), "stats": jax.jit(stats),
            "embed_bwd": jax.jit(embed_bwd, static_argnums=0)}


def described_programs(config, sds):
    """``(what, lowered)`` of the largest programs the walk runs, for
    ``rehearse_compile.py --reference``; ``sds(shape, dtype=float32)``
    makes an argument on the described chip."""
    s = sizes(config)
    b, r = int(config["batch_size"]), 2 * int(config["seq_len"])
    shapes = {n: sh for n, sh, _i in leaf_specs(config)}
    rest_p = {k: sds(shapes["l0_" + k]) for k in REST_LEAVES}
    head_p = {k: sds(shapes[k]) for k in HEAD_LEAVES}
    x = sds((b, r, s["units"]))
    o = sds((b, r, s["heads"], s["head_dim"]))
    kv = sds((b, r, s["kv_heads"], s["head_dim"]))
    qb = sds((b, min(Q_BLOCK, r), s["heads"], s["head_dim"]))
    fn = _blocks(config, False)
    print("the walk keeps, a layer, the state that enters it and its q, "
          "k, v and attention output over the %d rows: %.3f GB beside "
          "parameters and gradients"
          % (r, 4 * b * r * (s["units"] + 2 * s["heads"] * s["head_dim"]
                             + 2 * s["kv_heads"] * s["head_dim"]) / 1e9))
    yield "attention of %d query rows backward" % qb.shape[1], \
        fn["attn_bwd"].lower(qb, kv, kv, sds((), jnp.int32), qb)
    yield "output projection and expert part backward", \
        fn["rest_bwd"].lower(x, o, rest_p, x)
    yield "head and loss with gradient", \
        fn["head"].lower(x, head_p, sds((b, r // 2)),
                         sds((b, r // 2), jnp.int32))


def loss_and_grads(params, rows, weight, clean, config, quant=False,
                   blocks=None, routing=None):
    """``(loss, {leaf: gradient})`` — :func:`loss_fn`'s value and
    gradient, one piece of one layer at a time.  ``routing``, a list,
    gets one :func:`routing_stats` per layer."""
    fn = blocks or _blocks(config, quant)
    n_layers = sizes(config)["layers"]
    layers = [_layer_params(params, i) for i in range(n_layers)]
    pieces = _blocks_of(rows.shape[1])
    x = params["embed_weight"][rows.astype(jnp.int32)]
    kept = []
    for p in layers:
        p_in = {n: p[n] for n in ATTN_IN_LEAVES}
        p_rest = {n: p[n] for n in REST_LEAVES}
        q, k, v = fn["attn_in"](x, p_in)
        o = jnp.concatenate([fn["attn"](q[:, r:r + n], k, v, jnp.int32(r))
                             for r, n in pieces], axis=1)
        if routing is not None:
            routing.append(fn["stats"](x, o, p_rest))
        kept.append((x, q, k, v, o))
        x = fn["rest"](x, o, p_rest)
    head_p = {k: params[k] for k in HEAD_LEAVES}
    loss, (ct, d_head) = fn["head"](x, head_p, weight, clean)
    grads = dict(d_head)
    for i in reversed(range(n_layers)):
        p = layers[i]
        x, q, k, v, o = kept.pop()
        ct_x, ct_o, d_rest = fn["rest_bwd"](
            x, o, {n: p[n] for n in REST_LEAVES}, ct)
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
    grads["embed_weight"] = fn["embed_bwd"](
        params["embed_weight"].shape, rows, ct)
    return loss, {k: grads[k] for k in params}


def _print_routing(step, routing, masked_share, config):
    s = sizes(config)
    loads = [np.asarray(r).astype(int).tolist() for r, _d in routing]
    rows = np.concatenate(loads)
    through = 2 * int(config["batch_size"]) * int(config["seq_len"])
    print("[perfbench] step %d routing (reference, float32): %.1f%% of "
          "the positions masked; rows per held expert over %d layers "
          "least %d / mean %.1f / most %d, expected %.1f; %.3f%% of (row, "
          "slot) assignments differ when router and state are rounded to "
          "bfloat16 (read, not compared)"
          % (step, 100.0 * masked_share, len(routing), rows.min(),
             rows.mean(), rows.max(), through * s["top_k"] / s["routed"],
             100.0 * float(np.mean([float(d) for _r, d in routing]))),
          file=sys.stderr, flush=True)
    # every load, so that a reader can price any tile's padding
    print("[perfbench] step %d rows per held expert, by layer: %s"
          % (step, loads), file=sys.stderr, flush=True)


def train_steps(config, weights, batches, precision="reference",
                rows=None, devices=None):
    """Follow ``len(batches)`` steps of Adam from ``weights``; returns
    ``{"loss": [...], "grad1": {leaf: norm}, "dparam": {leaf: norm}}``.
    A batch is the traffic's ``(ids, next-token labels)``: the labels
    carry nothing a diffusion objective uses, and the noise is made from
    the ids alone (:func:`noised_batch`).  ``rows`` (a slice) plants the
    fault "part of the batch left out".  It runs on the first of
    ``devices``."""
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
        for i, (x, _y) in enumerate(batches):
            if rows is not None:
                x = x[rows]
            stack, weight, clean = noised_batch(x, config)
            routing = [] if not quant else None
            loss, grads = loss_and_grads(
                params, jnp.asarray(stack), jnp.asarray(weight),
                jnp.asarray(clean), config, quant, fn, routing)
            out["loss"].append(float(loss))
            if routing:
                _print_routing(i + 1, routing, float(np.mean(weight > 0)),
                               config)
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
