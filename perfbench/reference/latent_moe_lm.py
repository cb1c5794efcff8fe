"""Plain reference: a decoder-only language model of LATENT-ATTENTION
layers (MLA) — a leading dense layer, then sparse-expert layers routed
by biased sigmoid scores beside a shared expert — with multi-token-
prediction modules in the objective, trained by Adam, float32 at the
highest matmul precision, jax.numpy only.  The model is JoyAI-LLM-Flash
(``jdopensource/JoyAI-LLM-Flash`` ``config.json``: DeepSeek-V3's layer at
other numbers) as its config and the configuration file's ``assumed``
list give it, handed ONE CHIP'S SHARE of the deployment the
configuration states: experts ``deployment.experts_held = [first, end)``
of the ``published.n_routed_experts`` the router runs over, and the
first ``vocab_size`` rows of the vocabulary.

It imports nothing of the program and is handed nothing the program
made: no kernel, no sort, no gather of rows by expert, no grouped
product, no ``checkpoint`` — Python loops.

Layer ``l`` on ``x (B, T, U)``, no bias in any product::

    h   = RMS(x; g1)
    cq  = RMS(h Wqa'; gq)                         (T, q_lora_rank)
    q   = cq Wqb'  -> (T, H, [nope | rope])
    [ckv | kr] = h Wkva'                          (T, kv_lora_rank + rope)
    [k_nope | v] = RMS(ckv; gkv) Wkvb'  -> (T, H, [nope | v_dim])
    q_rope, kr = rope(q_rope), rope(kr)           ONE kr for all H heads
    s_ij = (q_nope_i . k_nope_j + q_rope_i . kr_j) / sqrt(nope + rope), j <= i
    a   = softmax_j(s) v                          (T, H, v_dim)
    x   = x + a Wo'
    h2  = RMS(x; g2)
    layer 0 .. first_k_dense_replace - 1:  x = x + SwiGLU(h2)
    after them:
    s   = sigmoid(h2 Wr')   over ALL published experts, float32
    S   = the top_k largest of s + b   (b: the HELD selection bias)
    w_e = routed_scaling_factor * s_e / sum_{e' in S} s_e'   (without b)
    x   = x + sum_{e in S and held} w_e SwiGLU_e(h2) + SwiGLU_shared(h2)

``rope``: the rotary dimensions are stored in pairs (2i, 2i + 1)
(``rope_interleave``); as the released code does, they are PERMUTED to
the half-split form ``[x0 x2 .. | x1 x3 ..]`` and turned with
``rotate_half`` — q and k alike, so the scores are those of turning each
pair where it lies.  The expert part is the DENSE MASKED form: every held
expert on every token, weighed by that token's ``w_e``, zero where the
expert was not chosen.

Then ``hN = RMS(x; gf)``, the main term ``CE(hN Wh', t_{i+1})``, and
prediction module ``k = 1 .. D`` (``num_nextn_predict_layers``)::

    x' = [RMS(Emb(t_{i+k}); ge) ; RMS(h_prev; gh)] Wp'      (T, 2U) -> (T, U)
    x' = sparse layer (own weights)(x');   term_k = CE(RMS(x'; gm) Wh', t_{i+1+k})

``h_prev`` is ``hN`` for ``k = 1`` and module ``k - 1``'s ``x'`` after
it.  Position ``i`` of module ``k`` exists for ``i < T - k``; here every
module runs over all ``T`` positions, the last ``k`` reading token 0
(attention is causal and nothing else mixes positions, so no earlier
position sees them) and masked out of the term.  Loss = mean main term +
``mtp_loss_weight / D`` x sum of the modules' means over their own
positions.

:func:`loss_fn` is the whole of it in one function, for
``jax.value_and_grad`` at sizes where everything fits (the CPU tests).
At the timed sizes :func:`loss_and_grads` computes the same numbers IN
BLOCKS, one piece of one layer at a time with ``jax.vjp``, keeping a
layer's input and attention output only.  A test holds the two to each
other.

``precision="fp8"`` is the CONTROL, the step below the bf16 the
configuration states (``reference_common.py``).
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
ATTN_IN_LEAVES = ("norm1_gamma", "q_a_weight", "q_a_norm_gamma",
                  "q_b_weight", "kv_a_weight", "kv_a_norm_gamma",
                  "kv_b_weight")
REST_LEAVES = {
    "dense": ("out_weight", "norm2_gamma", "gate_weight", "up_weight",
              "down_weight"),
    "sparse": ("out_weight", "norm2_gamma", "router_weight", "router_bias",
               "gate_weight", "up_weight", "down_weight",
               "shared_gate_weight", "shared_up_weight",
               "shared_down_weight")}
FRONT_LEAVES = ("embed_norm_gamma", "hidden_norm_gamma", "proj_weight")
HELD_LEAVES = ("router_bias",)      # held, not trained: gradient zero
RESIDUAL_LEAVES = ("out_weight", "down_weight")     # they write the residual
Q_BLOCK = 512       # query rows an attention piece works on


def sizes(config):
    """The sizes the equations read, by name."""
    first, end = (int(e) for e in config["deployment"]["experts_held"])
    s = {"vocab": int(config["vocab_size"]),
         "units": int(config["hidden_size"]),
         "heads": int(config["num_attention_heads"]),
         "q_rank": int(config["q_lora_rank"]),
         "kv_rank": int(config["kv_lora_rank"]),
         "nope": int(config["qk_nope_head_dim"]),
         "rope": int(config["qk_rope_head_dim"]),
         "v": int(config["v_head_dim"]),
         "dense_width": int(config["intermediate_size"]),
         "expert_width": int(config["moe_intermediate_size"]),
         "shared": int(config["n_shared_experts"]),
         "held": (first, end - first),
         "routed": int(config["published"]["n_routed_experts"]),
         "top_k": int(config["num_experts_per_tok"]),
         "route_scale": float(config["routed_scaling_factor"]),
         "layers": int(config["num_hidden_layers"]),
         "dense_layers": int(config["first_k_dense_replace"]),
         "mtp": int(config["num_nextn_predict_layers"]),
         "mtp_weight": float(config["mtp_loss_weight"]),
         "theta": float(config["rope_theta"]),
         "eps": float(config["rms_norm_eps"])}
    if s["held"][1] != int(config["n_routed_experts"]):
        raise ValueError("deployment.experts_held %s is not n_routed_experts "
                         "%s" % (config["deployment"]["experts_held"],
                                 config["n_routed_experts"]))
    if config["scoring_func"] != "sigmoid" or int(config["n_group"]) != 1 \
            or config.get("rope_scaling") is not None:
        raise ValueError("no rule for this scoring / grouping / rope scaling")
    return s


def layer_kinds(config):
    """``dense`` for the leading ``first_k_dense_replace`` layers,
    ``sparse`` after them (``moe_layer_freq`` 1)."""
    s = sizes(config)
    return ["dense" if i < s["dense_layers"] else "sparse"
            for i in range(s["layers"])]


def trunk(config):
    """``[(prefix, kind)]`` of the trunk's layers (a prediction module
    brings one more sparse layer of its own, ``mtp<k>_``)."""
    return [("l%d_" % i, kind) for i, kind in enumerate(layer_kinds(config))]


def leaf_specs(config):
    """Ordered [(name, shape, init)] of every leaf, in the block's
    construction order; a held expert's weights are stacked.  ``init`` is
    ``normal``, ``one`` (a gain) or ``held`` (the selection bias: drawn,
    never trained)."""
    s = sizes(config)
    u, n, f = s["units"], s["held"][1], s["expert_width"]
    qk = s["nope"] + s["rope"]
    shape = {"q_a_weight": (s["q_rank"], u), "q_a_norm_gamma": (s["q_rank"],),
             "q_b_weight": (s["heads"] * qk, s["q_rank"]),
             "kv_a_weight": (s["kv_rank"] + s["rope"], u),
             "kv_a_norm_gamma": (s["kv_rank"],),
             "kv_b_weight": (s["heads"] * (s["nope"] + s["v"]), s["kv_rank"]),
             "out_weight": (u, s["heads"] * s["v"])}
    ffn = {"dense": {"gate_weight": (s["dense_width"], u),
                     "up_weight": (s["dense_width"], u),
                     "down_weight": (u, s["dense_width"])},
           "sparse": {"router_weight": (s["routed"], u),
                      "router_bias": (s["routed"],),
                      "gate_weight": (n, f, u), "up_weight": (n, f, u),
                      "down_weight": (n, u, f),
                      "shared_gate_weight": (s["shared"] * f, u),
                      "shared_up_weight": (s["shared"] * f, u),
                      "shared_down_weight": (u, s["shared"] * f)}}

    def kind_of(name):
        return "one" if name.endswith("gamma") else \
            "held" if name.endswith(HELD_LEAVES) else "normal"

    def layer(prefix, kind):
        names = ATTN_IN_LEAVES + REST_LEAVES[kind]
        # construction order: attention with out_weight, norm2, the FFN
        return [(prefix + k, {**shape, **ffn[kind]}.get(k, (u,)), kind_of(k))
                for k in names]

    specs = [("embed_weight", (s["vocab"], u), "normal")]
    for prefix, kind in trunk(config):
        specs += layer(prefix, kind)
    specs.append(("norm_gamma", (u,), "one"))
    for k in range(s["mtp"]):
        pre = "mtp%d_" % k
        specs += [(pre + "embed_norm_gamma", (u,), "one"),
                  (pre + "hidden_norm_gamma", (u,), "one"),
                  (pre + "proj_weight", (u, 2 * u), "normal")]
        specs += layer(pre, "sparse") + [(pre + "norm_gamma", (u,), "one")]
    return specs + [("head_weight", (s["vocab"], u), "normal")]


def trainable(config):
    """Names of the leaves the optimizer updates."""
    return [n for n, _s, init in leaf_specs(config) if init != "held"]


def _bf16_grid(x):
    """float32 values rounded to the nearest bfloat16 (the program holds
    the selection bias in its compute dtype; so that both choose by the
    same numbers the bias is drawn ON that grid)."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def init_weights(config, seed):
    """Normal(0, init_std) matrices — Normal(0, embed_init_std) the
    embedding, Normal(0, residual_init_std) the projections that write
    into the residual stream — gains 1, the selection bias Normal(0,
    selection_bias_std) on bfloat16's grid; float32; made on the device
    in ONE jitted call from the seed, then read back once."""
    def std(name):
        return float(config[
            "embed_init_std" if name == "embed_weight" else
            "residual_init_std" if name.endswith(RESIDUAL_LEAVES) else
            "selection_bias_std" if name.endswith(HELD_LEAVES) else
            "init_std"])

    specs = leaf_specs(config)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            if init == "one":
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            out[name] = std(name) * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            if init == "held":
                out[name] = _bf16_grid(out[name])
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
    """x: (B, T, H, D) with its pairs interleaved; returns the turned
    vector in the HALF-SPLIT layout: permute, then ``rotate_half``."""
    t, d = x.shape[1], x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + turned * jnp.sin(emb)


def _attn_in(x, p, config, quant):
    """The normed state through both latents: ``q (B, T, H, nope +
    rope)``, ``k`` the same shape — every head's own ``k_nope`` beside
    the ONE turned rotary key, written out — and ``v (B, T, H, v)``."""
    s = sizes(config)
    b, t, _u = x.shape
    h = _rms(x, p["norm1_gamma"], s["eps"])
    cq = _rms(_mm(h, p["q_a_weight"], "btu,ru->btr", quant),
              p["q_a_norm_gamma"], s["eps"])
    q = _mm(cq, p["q_b_weight"], "btr,or->bto", quant).reshape(
        b, t, s["heads"], s["nope"] + s["rope"])
    ckv = _mm(h, p["kv_a_weight"], "btu,ru->btr", quant)
    kv = _mm(_rms(ckv[..., :s["kv_rank"]], p["kv_a_norm_gamma"], s["eps"]),
             p["kv_b_weight"], "btr,or->bto", quant).reshape(
                 b, t, s["heads"], s["nope"] + s["v"])
    kr = _rope(ckv[:, :, None, s["kv_rank"]:], s["theta"])
    q = jnp.concatenate([q[..., :s["nope"]],
                         _rope(q[..., s["nope"]:], s["theta"])], -1)
    k = jnp.concatenate([kv[..., :s["nope"]],
                         jnp.tile(kr, (1, 1, s["heads"], 1))], -1)
    return q, k, kv[..., s["nope"]:]


def _attn_block(q, k, v, row0, config, quant):
    """Attention of the query rows ``row0 .. row0 + q.shape[1] - 1``
    over every key, the mask built from positions; scores over the whole
    ``nope + rope`` key, values of ``v`` dimensions."""
    sc = _mm(q, k, "bqhd,bkhd->bhqk", quant) / math.sqrt(q.shape[-1])
    i = row0 + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    pr = jax.nn.softmax(jnp.where((j <= i)[None, None], sc, -1e30), axis=-1)
    return _mm(pr, v, "bhqk,bkhd->bqhd", quant)


def route(h, router_w, bias, config, quant):
    """``(B, T, E)`` float32: each token's weight for every published
    expert — sigmoid scores, zero outside the ``top_k`` largest of score
    + bias, the chosen scores (without the bias) renormalised to sum to
    1, times the scaling factor."""
    s = sizes(config)
    score = jax.nn.sigmoid(_mm(h, router_w, "btu,eu->bte", quant))
    biased = score + bias
    kth = lax.top_k(biased, s["top_k"])[0][..., -1:]
    w = jnp.where(biased >= kth, score, 0.0)
    if config["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * s["route_scale"]


def _swiglu(h, gate, up, down, quant):
    g = _mm(h, gate, "btu,fu->btf", quant)
    u = _mm(h, up, "btu,fu->btf", quant)
    return _mm(jax.nn.silu(g) * u, down, "btf,uf->btu", quant)


def experts_dense(h, w, p, config, quant):
    """The held experts' part of the layer, DENSE MASKED: every held
    expert on every token, times the token's weight for it."""
    first, n = sizes(config)["held"]
    g = _mm(h, p["gate_weight"], "btu,efu->btef", quant)
    u = _mm(h, p["up_weight"], "btu,efu->btef", quant)
    y = _mm(jax.nn.silu(g) * u, p["down_weight"], "btef,euf->bteu", quant)
    return jnp.sum(w[..., first:first + n, None] * y, axis=2)


def _ffn(h, p, kind, config, quant):
    if kind == "dense":
        return _swiglu(h, p["gate_weight"], p["up_weight"],
                       p["down_weight"], quant)
    w = route(h, p["router_weight"], p["router_bias"], config, quant)
    return experts_dense(h, w, p, config, quant) + _swiglu(
        h, p["shared_gate_weight"], p["shared_up_weight"],
        p["shared_down_weight"], quant)


def _rest(x, o, p, kind, config, quant):
    """Attention's output projected and added, then the feed-forward
    part of the layer's kind."""
    s = sizes(config)
    b, t, _u = x.shape
    x = x + _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu", quant)
    return x + _ffn(_rms(x, p["norm2_gamma"], s["eps"]), p, kind, config,
                    quant)


def _blocks_of(t):
    return [(r, min(Q_BLOCK, t - r)) for r in range(0, t, Q_BLOCK)]


def _split(params, prefix, names):
    return {k: params[prefix + k] for k in names}


def _layer(x, params, prefix, kind, config, quant):
    q, k, v = _attn_in(x, _split(params, prefix, ATTN_IN_LEAVES), config,
                       quant)
    o = jnp.concatenate(
        [_attn_block(q[:, r:r + n], k, v, r, config, quant)
         for r, n in _blocks_of(x.shape[1])], axis=1)
    return _rest(x, o, _split(params, prefix, REST_LEAVES[kind]), kind,
                 config, quant)


def _front(h, embed, nxt, p, config, quant):
    """A prediction module's entry: the next tokens' embeddings and the
    state, each normed, side by side (the embedding first), projected."""
    eps = sizes(config)["eps"]
    both = jnp.concatenate([_rms(embed[nxt], p["embed_norm_gamma"], eps),
                            _rms(h, p["hidden_norm_gamma"], eps)], -1)
    return _mm(both, p["proj_weight"], "btc,uc->btu", quant)


def _ce(h, head_w, labels, quant):
    """``(logits, per-position cross-entropy)`` over the held rows of
    the vocabulary."""
    logits = _mm(h, head_w, "btu,vu->btv", quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return logits, -jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]


def shifted(tokens, labels, k):
    """What prediction module ``k`` (from 1) reads and is held to: the
    tokens ``k`` places on (``labels`` is the tokens one place on) and
    the labels ``k`` places on, zero-filled where the sequence ends, and
    the mask of the positions that have a target."""
    t = tokens.shape[1]
    pad = jnp.zeros((tokens.shape[0], k), jnp.int32)
    nxt = jnp.concatenate([labels.astype(jnp.int32)[:, k - 1:t - 1],
                           pad], axis=1)
    target = jnp.concatenate([labels.astype(jnp.int32)[:, k:], pad], axis=1)
    return nxt, target, (jnp.arange(t) < t - k).astype(jnp.float32)


def forward(params, tokens, labels, config, quant=False):
    """``(states, mtp_states, loss)``: the main final-normed states ``(B,
    T, U)``, the modules' ``(B, D, T, U)`` and the objective, whole —
    what the tests hold the block's outputs to."""
    s = sizes(config)
    tokens = tokens.astype(jnp.int32)
    x = params["embed_weight"][tokens]
    for prefix, kind in trunk(config):
        x = _layer(x, params, prefix, kind, config, quant)
    states = _rms(x, params["norm_gamma"], s["eps"])
    loss = jnp.mean(_ce(states, params["head_weight"], labels, quant)[1])
    h, outs = states, []
    for k in range(1, s["mtp"] + 1):
        pre = "mtp%d_" % (k - 1)
        nxt, target, mask = shifted(tokens, labels, k)
        h = _layer(_front(h, params["embed_weight"], nxt,
                          _split(params, pre, FRONT_LEAVES), config, quant),
                   params, pre, "sparse", config, quant)
        outs.append(_rms(h, params[pre + "norm_gamma"], s["eps"]))
        ce = _ce(outs[-1], params["head_weight"], target, quant)[1]
        loss = loss + s["mtp_weight"] / s["mtp"] * jnp.mean(
            jnp.sum(ce * mask, axis=1) / jnp.sum(mask))
    return states, (jnp.stack(outs, axis=1) if outs else None), loss


def loss_fn(params, tokens, labels, config, quant=False):
    return forward(params, tokens, labels, config, quant)[2]


def expert_layer(h, p, config, quant=False, shared=True):
    """One sparse layer's feed-forward part alone over normed states ``h
    (B, T, U)`` — router, biased top-k, the held experts and, under
    ``shared``, the shared expert — for the test that sums the shares."""
    w = route(h, p["router_weight"], p["router_bias"], config, quant)
    y = experts_dense(h, w, p, config, quant)
    if shared:
        y = y + _swiglu(h, p["shared_gate_weight"], p["shared_up_weight"],
                        p["shared_down_weight"], quant)
    return y


def attention_layer(x, p, config, quant=False):
    """One layer's latent attention alone, ``a Wo'`` over the state ``x``
    (its first norm included) — for the test against the per-head
    form."""
    b, t, _u = x.shape
    q, k, v = _attn_in(x, p, config, quant)
    o = _attn_block(q, k, v, 0, config, quant)
    return _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu", quant)


# -- what the router did (read, printed, not compared) ------------------------
def routing_stats(h, router_w, bias, config):
    """``(rows per held expert (count,), share of (token, slot)
    assignments that differ when router and state are rounded to
    bfloat16)`` of one sparse layer's normed states."""
    s = sizes(config)
    first, n = s["held"]
    w = route(h, router_w, bias, config, False)
    rows = jnp.sum(w[..., first:first + n] > 0, axis=(0, 1))
    low = jnp.einsum("btu,eu->bte", h.astype(jnp.bfloat16),
                     router_w.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    chosen_low = lax.top_k(jax.nn.sigmoid(low) + bias, s["top_k"])[1]
    picked = jnp.take_along_axis(w, chosen_low, axis=-1) > 0
    return rows, 1.0 - jnp.mean(picked.astype(jnp.float32))


# -- the same numbers in blocks ----------------------------------------------
def _blocks(config, quant):
    """The jitted pieces :func:`loss_and_grads` walks with; a layer's
    kind and the block sizes are static, a block's first row is data."""
    s = sizes(config)

    def attn_in(x, p):
        return _attn_in(x, p, config, quant)

    def attn_in_bwd(x, p, cts):
        return jax.vjp(attn_in, x, p)[1](cts)

    def attn(q, k, v, row0):
        return _attn_block(q, k, v, row0, config, quant)

    def attn_bwd(q, k, v, row0, ct):
        return jax.vjp(lambda q_, k_, v_: attn(q_, k_, v_, row0),
                       q, k, v)[1](ct)

    def rest(x, o, p, kind):
        return _rest(x, o, p, kind, config, quant)

    def rest_bwd(x, o, p, kind, ct):
        return jax.vjp(lambda x_, o_, p_: rest(x_, o_, p_, kind),
                       x, o, p)[1](ct)

    def norm(x, g):
        return _rms(x, g, s["eps"])

    def norm_bwd(x, g, ct):
        return jax.vjp(norm, x, g)[1](ct)

    def term(h, head_w, target, mask, weight):
        """``weight`` x the mean over sequences of a term's masked mean,
        with its gradient to the state and the head."""
        def f(h_, w_):
            ce = _ce(h_, w_, target, quant)[1]
            return weight * jnp.mean(jnp.sum(ce * mask, axis=1)
                                     / jnp.sum(mask))
        return jax.value_and_grad(f, argnums=(0, 1))(h, head_w)

    def front(h, embed, nxt, p):
        return _front(h, embed, nxt, p, config, quant)

    def front_bwd(h, embed, nxt, p, ct):
        return jax.vjp(lambda h_, e_, p_: front(h_, e_, nxt, p_),
                       h, embed, p)[1](ct)

    def embed_bwd(shape, tokens, ct):
        return jnp.zeros(shape, jnp.float32).at[tokens].add(ct)

    def stats(x, o, p):
        b, t, _u = x.shape
        x = x + _mm(o.reshape(b, t, -1), p["out_weight"], "bto,uo->btu",
                    False)
        return routing_stats(_rms(x, p["norm2_gamma"], s["eps"]),
                             p["router_weight"], p["router_bias"], config)

    return {"attn_in": jax.jit(attn_in), "attn_in_bwd": jax.jit(attn_in_bwd),
            "attn": jax.jit(attn), "attn_bwd": jax.jit(attn_bwd),
            "rest": jax.jit(rest, static_argnums=3),
            "rest_bwd": jax.jit(rest_bwd, static_argnums=3),
            "norm": jax.jit(norm), "norm_bwd": jax.jit(norm_bwd),
            "term": jax.jit(term), "front": jax.jit(front),
            "front_bwd": jax.jit(front_bwd), "stats": jax.jit(stats),
            "embed_bwd": jax.jit(embed_bwd, static_argnums=0)}


def described_programs(config, sds):
    """``(what, lowered)`` of the largest programs the walk runs, for
    ``rehearse_compile.py --reference``; ``sds(shape, dtype=float32)``
    makes an argument on the described chip."""
    s = sizes(config)
    b, t = int(config["batch_size"]), int(config["seq_len"])
    shapes = {n: sh for n, sh, _i in leaf_specs(config)}
    sparse = "l%d_" % s["dense_layers"]
    qk = s["nope"] + s["rope"]
    x = sds((b, t, s["units"]))
    o = sds((b, t, s["heads"], s["v"]))
    k = sds((b, t, s["heads"], qk))
    qb = sds((b, min(Q_BLOCK, t), s["heads"], qk))
    ob = sds((b, min(Q_BLOCK, t), s["heads"], s["v"]))
    fn = _blocks(config, False)
    print("the walk keeps, a layer, the state that enters it and its "
          "attention output, and while a layer is walked back its q, k and "
          "v: %.3f GB and %.3f GB beside parameters and gradients"
          % (4 * b * t * (s["units"] + s["heads"] * s["v"]) / 1e9,
             4 * b * t * s["heads"] * (2 * qk + s["v"]) / 1e9))
    yield "attention of %d query rows backward" % qb.shape[1], \
        fn["attn_bwd"].lower(qb, k, o, sds((), jnp.int32), ob)
    yield "output projection, router, held and shared experts backward", \
        fn["rest_bwd"].lower(
            x, o, {n: sds(shapes[sparse + n]) for n in REST_LEAVES["sparse"]},
            "sparse", x)
    yield "output projection and dense feed-forward part backward", \
        fn["rest_bwd"].lower(
            x, o, {n: sds(shapes["l0_" + n]) for n in REST_LEAVES["dense"]},
            "dense", x)
    yield "a term through the head with its gradient", \
        fn["term"].lower(x, sds(shapes["head_weight"]),
                         sds((b, t), jnp.int32), sds((t,)), sds(()))


def loss_and_grads(params, tokens, labels, config, quant=False, blocks=None,
                   routing=None):
    """``(loss, {leaf: gradient})`` — :func:`loss_fn`'s value and
    gradient, one piece of one layer at a time; a held leaf's gradient is
    zero.  ``routing``, a list, gets one :func:`routing_stats` per sparse
    layer."""
    s = sizes(config)
    fn = blocks or _blocks(config, quant)
    tokens, t = tokens.astype(jnp.int32), tokens.shape[1]
    pieces = _blocks_of(t)
    grads = {}

    def add(name, g):
        grads[name] = grads[name] + g if name in grads else g

    def layer_fwd(x, prefix, kind):
        p_rest = _split(params, prefix, REST_LEAVES[kind])
        q, k, v = fn["attn_in"](x, _split(params, prefix, ATTN_IN_LEAVES))
        o = jnp.concatenate([fn["attn"](q[:, r:r + n], k, v, jnp.int32(r))
                             for r, n in pieces], axis=1)
        if routing is not None and kind == "sparse":
            routing.append(fn["stats"](x, o, p_rest))
        return fn["rest"](x, o, p_rest, kind), (x, o)

    def layer_bwd(kept, prefix, kind, ct):
        x, o = kept
        p_in = _split(params, prefix, ATTN_IN_LEAVES)
        ct_x, ct_o, d_rest = fn["rest_bwd"](
            x, o, _split(params, prefix, REST_LEAVES[kind]), kind, ct)
        q, k, v = fn["attn_in"](x, p_in)
        dq, dk, dv = [], jnp.zeros_like(k), jnp.zeros_like(v)
        for r, n in pieces:
            dq_b, dk_b, dv_b = fn["attn_bwd"](
                q[:, r:r + n], k, v, jnp.int32(r), ct_o[:, r:r + n])
            dq.append(dq_b)
            dk, dv = dk + dk_b, dv + dv_b
        ct_in, d_in = fn["attn_in_bwd"](
            x, p_in, (jnp.concatenate(dq, axis=1), dk, dv))
        for name, g in {**d_in, **d_rest}.items():
            add(prefix + name, jnp.zeros_like(g)
                if name.endswith(HELD_LEAVES) else g)
        return ct_x + ct_in

    everything = jnp.ones((t,), jnp.float32)
    layers = trunk(config)
    x, kept = params["embed_weight"][tokens], []
    for prefix, kind in layers:
        x, k_ = layer_fwd(x, prefix, kind)
        kept.append(k_)
    x_last = x
    states = fn["norm"](x_last, params["norm_gamma"])
    # the modules, forward: what each was fed, its layer's kept pair and
    # its un-normed output
    h, modules = states, []
    for k in range(1, s["mtp"] + 1):
        pre = "mtp%d_" % (k - 1)
        nxt, target, mask = shifted(tokens, labels, k)
        x_in = fn["front"](h, params["embed_weight"], nxt,
                           _split(params, pre, FRONT_LEAVES))
        x_out, k_ = layer_fwd(x_in, pre, "sparse")
        modules.append((pre, h, nxt, target, mask, k_, x_out))
        h = x_out
    # the terms and the walk back, the last module first
    loss, (ct_states, d_head) = fn["term"](
        states, params["head_weight"], labels.astype(jnp.int32), everything,
        jnp.float32(1.0))
    add("head_weight", d_head)
    ct_next = None          # from module k + 1's entry, to module k's output
    for pre, h_prev, nxt, target, mask, k_, x_out in reversed(modules):
        normed = fn["norm"](x_out, params[pre + "norm_gamma"])
        value, (ct_n, d_head) = fn["term"](
            normed, params["head_weight"], target, mask,
            jnp.float32(s["mtp_weight"] / s["mtp"]))
        loss = loss + value
        add("head_weight", d_head)
        ct, d_gamma = fn["norm_bwd"](x_out, params[pre + "norm_gamma"], ct_n)
        add(pre + "norm_gamma", d_gamma)
        if ct_next is not None:
            ct = ct + ct_next
        ct = layer_bwd(k_, pre, "sparse", ct)
        ct_next, d_embed, d_front = fn["front_bwd"](
            h_prev, params["embed_weight"], nxt,
            _split(params, pre, FRONT_LEAVES), ct)
        add("embed_weight", d_embed)
        for name, g in d_front.items():
            add(pre + name, g)
    if ct_next is not None:
        ct_states = ct_states + ct_next
    ct, d_gamma = fn["norm_bwd"](x_last, params["norm_gamma"], ct_states)
    add("norm_gamma", d_gamma)
    for (prefix, kind), k_ in zip(reversed(layers), reversed(kept)):
        ct = layer_bwd(k_, prefix, kind, ct)
    add("embed_weight", fn["embed_bwd"](params["embed_weight"].shape,
                                        tokens, ct))
    return loss, {k: grads[k] for k in params}


def _print_routing(step, routing, config):
    s = sizes(config)
    loads = [np.asarray(r).astype(int).tolist() for r, _d in routing]
    rows = np.concatenate(loads)
    tokens = int(config["batch_size"]) * int(config["seq_len"])
    print("[perfbench] step %d routing (reference, float32): rows per held "
          "expert over %d sparse layers least %d / mean %.1f / most %d, "
          "expected %.1f; %.3f%% of (token, slot) assignments differ when "
          "router and state are rounded to bfloat16 (read, not compared)"
          % (step, len(routing), rows.min(), rows.mean(), rows.max(),
             tokens * s["top_k"] / s["routed"],
             100.0 * float(np.mean([float(d) for _r, d in routing]))),
          file=sys.stderr, flush=True)
    # every load, so that a reader can price any tile's padding
    print("[perfbench] step %d rows per held expert, by sparse layer: %s"
          % (step, loads), file=sys.stderr, flush=True)


def train_steps(config, weights, batches, precision="reference",
                rows=None, devices=None):
    """Follow ``len(batches)`` steps of Adam from ``weights``; returns
    ``{"loss": [...], "grad1": {leaf: norm}, "dparam": {leaf: norm}}``.
    A held leaf (the selection bias) is not updated: its gradient and its
    change read zero.  ``rows`` (a slice) plants the fault "part of the
    batch left out".  It runs on the first of ``devices``."""
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
    updated = set(trainable(config))

    with jax.default_matmul_precision("highest"):
        fn = _blocks(config, quant)
        params = {k: jnp.asarray(v) for k, v in weights.items()}
        mean = {k: jnp.zeros(weights[k].shape, jnp.float32) for k in updated}
        var = {k: jnp.zeros(weights[k].shape, jnp.float32) for k in updated}
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
                g = grads.pop(k)
                if k in updated:
                    params[k], mean[k], var[k] = adam(
                        params[k], mean[k], var[k], g, t,
                        wd * wd_mult(k, config))
        del mean, var
        out["dparam"] = {k: float(diff(params[k], jnp.asarray(weights[k])))
                         for k in weights}
    return out
