"""Transformer building blocks (long-context model family).

No reference analogue — MXNet 1.2 predates attention (SURVEY.md §5.7:
its long-sequence story was bucketing + fused RNN).  These layers are
the model-level consumers of the TPU-native attention stack:

- single chip: ``F.contrib.flash_attention`` lowers to the Pallas flash
  kernel on TPU (O(T) memory), einsum elsewhere.
- sequence-sharded: the same math runs under
  ``parallel.ring_attention``/``ulysses_attention`` over an ``sp`` mesh
  axis; ``example/long-context/transformer_lm.py`` shows the handoff.

Pre-LN residual blocks (the variant that trains stably without warmup).

Generative serving (``mxnet_tpu.serving.generate``) consumes this file
as the in-tree model stock through two seams:

- :func:`cached_attention_step` / :func:`causal_attention` — the pure
  attention math of the KV-cache decode path: a single-token query
  attends against a preallocated fixed-shape cache with a validity
  mask, so every decode step is ONE compiled program regardless of the
  sequence position (the reference's per-length bucketed executors,
  collapsed to one);
- :meth:`TransformerLM.generative_spec` — the trained block's weights
  extracted as plain device arrays + the architecture config, the feed
  ``serving/generate/model.py`` compiles its prefill/decode programs
  from.
"""
from ..block import HybridBlock
from ..nn import Dense, Dropout, Embedding, HybridSequential, LayerNorm
from ..parameter import DeferredInitializationError

__all__ = ["MultiHeadAttention", "TransformerEncoderCell", "TransformerLM",
           "LoopedLM", "looped_lm_forward", "MoELM", "moe_lm_forward",
           "causal_attention", "cached_attention_step"]


def causal_attention(q, k, v):
    """Pure-jax causal attention over full sequences — the PREFILL
    path's math (einsum + mask formulation, numerically the non-flash
    reference the Pallas kernel is parity-tested against).

    ``q``: ``[B, T, H, D]``; ``k``/``v``: ``[B, T, Hkv, D]`` with
    ``H % Hkv == 0`` (GQA repeats KV head groups).  Returns
    ``[B, T, H, D]``."""
    import jax.numpy as jnp
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, T, Hkv, g, D) * (D ** -0.5)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None, None, None], scores, -1e30)
    p = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(B, T, H, D)


def cached_attention_step(q, k_cache, v_cache, n_valid):
    """One DECODE step against a preallocated KV-cache — the
    fixed-shape program at the heart of incremental generation.

    ``q``: ``[S, H, D]`` (one query token per decode slot);
    ``k_cache``/``v_cache``: ``[S, Hkv, M, D]`` (``M`` = cache
    capacity); ``n_valid``: ``[S]`` int — how many cache positions hold
    real history per slot (the ring's fill level).  Positions
    ``>= n_valid`` are masked out, so the SAME compiled program serves
    every slot at every sequence position; causality is structural (the
    cache only ever holds past tokens plus the current one).  Returns
    ``[S, H, D]``."""
    import jax.numpy as jnp
    S, H, D = q.shape
    Hkv, M = k_cache.shape[1], k_cache.shape[2]
    g = H // Hkv
    qg = q.reshape(S, Hkv, g, D) * (D ** -0.5)
    scores = jnp.einsum("shgd,shmd->shgm", qg, k_cache)
    valid = jnp.arange(M)[None, None, None, :] \
        < n_valid[:, None, None, None]
    scores = jnp.where(valid, scores, -1e30)
    p = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("shgm,shmd->shgd", p, v_cache)
    return out.reshape(S, H, D)


class MultiHeadAttention(HybridBlock):
    """Self-attention with optional GQA (num_kv_heads < num_heads).

    Input (B, T, C); output (B, T, C).
    """

    def __init__(self, units, num_heads, num_kv_heads=None, causal=False,
                 dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise ValueError("units (%d) must divide num_heads (%d)"
                             % (units, num_heads))
        self._units = units
        self._h = num_heads
        self._hkv = num_kv_heads or num_heads
        if self._h % self._hkv:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        self._d = units // num_heads
        self._causal = causal
        with self.name_scope():
            self.q_proj = Dense(self._h * self._d, use_bias=False,
                                flatten=False, prefix="q_")
            self.k_proj = Dense(self._hkv * self._d, use_bias=False,
                                flatten=False, prefix="k_")
            self.v_proj = Dense(self._hkv * self._d, use_bias=False,
                                flatten=False, prefix="v_")
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  prefix="out_")
            self.drop = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        q = self.q_proj(x).reshape((0, 0, self._h, self._d))
        k = self.k_proj(x).reshape((0, 0, self._hkv, self._d))
        v = self.v_proj(x).reshape((0, 0, self._hkv, self._d))
        o = F.contrib.flash_attention(q, k, v, causal=self._causal)
        o = self.out_proj(o.reshape((0, 0, -1)))
        return self.drop(o) if self.drop is not None else o


class TransformerEncoderCell(HybridBlock):
    """Pre-LN block: x + MHA(LN(x)); x + FFN(LN(x))."""

    def __init__(self, units, hidden_size, num_heads, num_kv_heads=None,
                 causal=False, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = LayerNorm()
            self.attn = MultiHeadAttention(units, num_heads,
                                           num_kv_heads=num_kv_heads,
                                           causal=causal, dropout=dropout)
            self.ln2 = LayerNorm()
            self.ffn = HybridSequential(prefix="ffn_")
            with self.ffn.name_scope():
                self.ffn.add(Dense(hidden_size, activation="relu",
                                   flatten=False))
                self.ffn.add(Dense(units, flatten=False))
            self.drop = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.ln1(x))
        h = self.ffn(self.ln2(x))
        if self.drop is not None:
            h = self.drop(h)
        return x + h


class TransformerLM(HybridBlock):
    """Decoder-only causal LM: embed -> N pre-LN blocks -> tied-free head.

    Learned positional embeddings sized to ``max_len``; inputs are
    (B, T) int token ids, outputs (B, T, vocab) logits.
    """

    def __init__(self, vocab_size, units=128, hidden_size=512, num_layers=2,
                 num_heads=4, num_kv_heads=None, max_len=512, dropout=0.0,
                 **kwargs):
        super().__init__(**kwargs)
        self._max_len = max_len
        self._vocab_size = vocab_size
        self._units = units
        self._hidden_size = hidden_size
        self._num_layers = num_layers
        self._num_heads = num_heads
        self._num_kv_heads = num_kv_heads or num_heads
        with self.name_scope():
            self.embed = Embedding(vocab_size, units)
            self.pos_embed = Embedding(max_len, units)
            self.blocks = HybridSequential(prefix="blocks_")
            with self.blocks.name_scope():
                for _ in range(num_layers):
                    self.blocks.add(TransformerEncoderCell(
                        units, hidden_size, num_heads,
                        num_kv_heads=num_kv_heads, causal=True,
                        dropout=dropout))
            self.ln_f = LayerNorm()
            self.head = Dense(vocab_size, flatten=False, prefix="head_")

    def hybrid_forward(self, F, tokens):
        # derive the sequence length from the embedded tokens with
        # slice_like, so pure-Symbol graphs (no shape at trace time)
        # get the right positional window for any T <= max_len
        x = self.embed(tokens)
        pos = F.arange(0, self._max_len)
        pos_e = self.pos_embed(pos).expand_dims(0)
        pos_e = F.slice_like(pos_e, x, axes=(1,))
        x = x + pos_e
        x = self.blocks(x)
        return self.head(self.ln_f(x))

    def generative_spec(self):
        """The decode-path export for ``mxnet_tpu.serving.generate``:
        ``{"config": {...}, "params": {...}}`` with every weight a raw
        device array (the gluon wrapper stripped), so the generative
        engine can jit fixed-shape prefill/decode programs over a plain
        pytree.  Param layout follows the block's own math — Dense
        stores ``(units, in_units)`` (``y = x @ W.T + b``).

        Deferred parameters are materialized by one dummy forward, so
        an untrained (initialized-only) block exports cleanly for
        warmup/benchmark use."""
        from ... import ndarray as _nd

        def _raw(param):
            try:
                return param.data()._data
            except DeferredInitializationError:
                self(_nd.zeros((1, 2)))
                return param.data()._data

        layers = []
        for cell in self.blocks._children.values():
            ffn = list(cell.ffn._children.values())
            layers.append({
                "ln1_g": _raw(cell.ln1.gamma),
                "ln1_b": _raw(cell.ln1.beta),
                "wq": _raw(cell.attn.q_proj.weight),
                "wk": _raw(cell.attn.k_proj.weight),
                "wv": _raw(cell.attn.v_proj.weight),
                "wo": _raw(cell.attn.out_proj.weight),
                "ln2_g": _raw(cell.ln2.gamma),
                "ln2_b": _raw(cell.ln2.beta),
                "w1": _raw(ffn[0].weight), "b1": _raw(ffn[0].bias),
                "w2": _raw(ffn[1].weight), "b2": _raw(ffn[1].bias),
            })
        params = {
            "embed": _raw(self.embed.weight),
            "pos_embed": _raw(self.pos_embed.weight),
            "layers": layers,
            "ln_f_g": _raw(self.ln_f.gamma),
            "ln_f_b": _raw(self.ln_f.beta),
            "head_w": _raw(self.head.weight),
            "head_b": _raw(self.head.bias),
        }
        config = {
            "vocab_size": self._vocab_size,
            "units": self._units,
            "hidden_size": self._hidden_size,
            "num_layers": self._num_layers,
            "num_heads": self._num_heads,
            "num_kv_heads": self._num_kv_heads,
            "max_len": self._max_len,
        }
        return {"config": config, "params": params}


# one looped layer's leaves, in construction order
_LOOPED_LAYER_LEAVES = (
    "norm1_gamma", "q_weight", "k_weight", "v_weight", "out_weight",
    "norm2_gamma", "norm3_gamma", "gate_weight", "up_weight", "down_weight",
    "norm4_gamma")


def looped_lm_forward(params, tokens, *, num_layers, num_heads, num_passes,
                      eps=1e-6, rope_base=1e6, remat=True):
    """The looped LM's forward as ONE pure function of ``(params,
    tokens)`` (ROADMAP D1's shape: :class:`LoopedLM` wraps it; a serving
    path would call the same function with a cache).

    ``params`` maps :class:`LoopedLM`'s short parameter names to jax
    arrays; ``tokens`` is ``(B, T)`` int.  One stack of ``num_layers``
    sandwich-norm layers — ``x + RMS(Attn(RMS(x)))``, ``x +
    RMS(SwiGLU(RMS(x)))``, causal attention with rotary q and k — is
    applied ``num_passes`` times WITH THE SAME WEIGHTS inside one
    ``lax.scan``; after every pass the state is normed (the normed state
    enters the next pass), and read by a one-output exit gate.  With
    ``remat`` each pass of the stack is a ``jax.checkpoint``: the
    backward pass keeps the state that enters a pass and runs the pass
    again, so activations cost one pass, not ``num_passes``.

    Returns ``(logits, states, gates)``, batch-major: the LAST exit's
    logits ``(B, T, V)``, every exit's normed state ``(B, P, T, U)`` and
    gate logit ``(B, P, T)``.  The other exits' logits are the head over
    their state; a loss fuses that product with its cross-entropy
    (``gluon.loss.ExitWeightedCELoss``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ...ops.contrib import (_flash_attention_op, _gated_ffn,
                                _rotary_embedding)
    from ...ops.nn import _rms_norm
    from ...telemetry import phases

    layers = [[params["l%d_%s" % (i, n)] for n in _LOOPED_LAYER_LEAVES]
              for i in range(num_layers)]

    def layer(x, p):
        n1, wq, wk, wv, wo, n2, n3, wg, wu, wd, n4 = p
        b, t, u = x.shape
        h = _rms_norm(x, n1, eps=eps)
        heads = lambda w: jnp.einsum("btu,ou->bto", h, w).reshape(
            b, t, num_heads, u // num_heads)
        q = _rotary_embedding(heads(wq), base=rope_base)
        k = _rotary_embedding(heads(wk), base=rope_base)
        o = _flash_attention_op(q, k, heads(wv), causal=True)
        a = jnp.einsum("btu,ou->bto", o.reshape(b, t, u), wo)
        x = x + _rms_norm(a, n2, eps=eps)
        m = _gated_ffn(_rms_norm(x, n3, eps=eps), wg, wu, wd)
        return x + _rms_norm(m, n4, eps=eps)

    def stack(x, layers_):
        for p in layers_:
            x = layer(x, p)
        return x

    if remat:
        stack = jax.checkpoint(stack)

    def one_pass(h, _):
        with jax.named_scope(phases.LOOP_SCOPE):
            x = stack(h, layers)
        with jax.named_scope(phases.EXIT_SCOPE):
            h = _rms_norm(x, params["norm_gamma"], eps=eps)
            g = jnp.einsum("btu,ou->bto", h, params["exit_weight"])[..., 0] \
                + params["exit_bias"][0]
        return h, (h, g)

    h0 = params["embed_weight"][tokens.astype(jnp.int32)]
    h, (states, gates) = lax.scan(one_pass, h0, None, length=num_passes)
    with jax.named_scope(phases.EXIT_SCOPE):
        logits = jnp.einsum("btu,vu->btv", h, params["head_weight"])
    return logits, jnp.moveaxis(states, 0, 1), jnp.moveaxis(gates, 0, 1)


class LoopedLM(HybridBlock):
    """Looped decoder-only LM: ONE stack of layers applied
    ``num_passes`` times with shared weights, an exit — final norm, LM
    head, one-output gate — after every pass (Ouro, "Scaling Latent
    Reasoning via Looped Language Models", arXiv:2510.25741).

    Sandwich-norm layers (four RMSNorm gains a layer), causal attention
    with rotary positions (half-split pairing), SwiGLU, no biases but
    the gate's, untied head.  Input ``(B, T)`` token ids; outputs
    ``(logits, states, gates)`` as :func:`looped_lm_forward` gives them,
    which this block only wraps: the last exit's logits first, so
    ``ParallelTrainer.forward`` and plain inference read the deepest
    exit, then what ``gluon.loss.ExitWeightedCELoss`` (``exit_loss()``)
    needs of every exit.

    Each pass is rematerialised in the backward pass (the block's own
    property, no option): a weight's gradient is the sum of
    ``num_passes`` contributions, and activations are kept for one pass
    at a time."""

    def __init__(self, vocab_size, units=128, hidden_size=512, num_layers=2,
                 num_heads=4, num_passes=4, epsilon=1e-6, rope_base=1e6,
                 **kwargs):
        super().__init__(**kwargs)
        if units % num_heads or (units // num_heads) % 2:
            raise ValueError("units (%d) must divide into num_heads (%d) "
                             "heads of even size" % (units, num_heads))
        self._config = dict(num_layers=num_layers, num_heads=num_heads,
                            num_passes=num_passes, eps=epsilon,
                            rope_base=rope_base)
        shapes = [("embed_weight", (vocab_size, units))]
        wide = {"gate_weight": (hidden_size, units),
                "up_weight": (hidden_size, units),
                "down_weight": (units, hidden_size)}
        for i in range(num_layers):
            shapes += [("l%d_%s" % (i, n),
                        (units,) if n.endswith("_gamma")
                        else wide.get(n, (units, units)))
                       for n in _LOOPED_LAYER_LEAVES]
        shapes += [("norm_gamma", (units,)), ("head_weight",
                                              (vocab_size, units)),
                   ("exit_weight", (1, units)), ("exit_bias", (1,))]
        with self.name_scope():
            # the initializer reads the suffix: gains 1, the bias 0
            for name, shape in shapes:
                setattr(self, name, self.params.get(name, shape=shape))
        self._export_gauges()

    def _export_gauges(self):
        from ... import telemetry
        c = self._config
        for name, value, what in (
                ("mxnet_loop_passes", c["num_passes"],
                 "times the newest LoopedLM applies its stack"),
                ("mxnet_loop_layers", c["num_layers"],
                 "layers in the newest LoopedLM's shared stack"),
                ("mxnet_loop_layer_applications",
                 c["num_passes"] * c["num_layers"],
                 "layer applications a forward of the newest LoopedLM "
                 "runs (passes x layers)")):
            telemetry.gauge(name, what).set(value)

    def hybrid_forward(self, F, tokens, **params):
        from ...imperative import invoke_fn
        names = list(params)
        config = self._config

        def forward(tokens_, *leaves):
            return looped_lm_forward(dict(zip(names, leaves)), tokens_,
                                     **config)

        return tuple(invoke_fn(forward, [tokens] + [params[n]
                                                    for n in names]))

    def exit_loss(self, beta=0.05, **kwargs):
        """The training objective over this block's outputs, sharing its
        head: ``loss(*net(tokens), labels)``."""
        from ..loss import ExitWeightedCELoss
        return ExitWeightedCELoss(beta=beta, params=self.params, **kwargs)


# one expert layer's leaves, in construction order
_MOE_LAYER_LEAVES = (
    "norm1_gamma", "q_weight", "k_weight", "v_weight", "out_weight",
    "norm2_gamma", "router_weight", "gate_weight", "up_weight",
    "down_weight")
SLIDING, FULL = "sliding_attention", "full_attention"


def moe_lm_forward(params, tokens, *, layer_types, num_heads, num_kv_heads,
                   top_k, held, window, rope, norm_topk=True, eps=1e-6):
    """A sparse-expert LM's trunk as ONE pure function of ``(params,
    tokens)``: the final-normed states ``(B, T, U)`` the head reads.

    ``params`` maps :class:`MoELM`'s short parameter names to jax
    arrays; ``tokens`` is ``(B, T)`` int.  Layer ``i`` is pre-norm,
    ``x + Attn(RMS(x))`` then ``x + Experts(RMS(x))``: grouped-query
    causal attention with rotary q and k — on a ``sliding_attention``
    layer a query sees its last ``window`` keys, on a ``full_attention``
    layer every key before it; ``rope`` maps each kind to ``(base,
    inv_freq or None, scale)`` (:func:`ops.contrib._rotary_embedding`)
    — and one chip's share of a top-``top_k`` routed expert layer
    (:func:`parallel.moe.routed_experts`: the router over ALL published
    experts, ``held = (first, count)`` the ones whose stacked weights
    are here).  Each layer is a ``jax.checkpoint``: the backward pass
    keeps the state that enters a layer and runs the layer again."""
    import jax
    import jax.numpy as jnp

    from ...ops.contrib import _flash_attention_op, _rotary_embedding
    from ...ops.nn import _rms_norm
    from ...parallel.moe import routed_experts
    from ...telemetry import phases

    def layer(x, p, kind):
        n1, wq, wk, wv, wo, n2, wr, wg, wu, wd = p
        b, t, u = x.shape
        base, inv_freq, scale = rope[kind]
        h = _rms_norm(x, n1, eps=eps)
        heads = lambda w, n: jnp.einsum("btu,ou->bto", h, w).reshape(
            b, t, n, w.shape[0] // n)
        with jax.named_scope(phases.ATTN_WINDOW_SCOPE if kind == SLIDING
                             else phases.ATTN_FULL_SCOPE):
            turn = lambda a: _rotary_embedding(a, base=base,
                                               inv_freq=inv_freq, scale=scale)
            o = _flash_attention_op(
                turn(heads(wq, num_heads)), turn(heads(wk, num_kv_heads)),
                heads(wv, num_kv_heads), causal=True,
                window=window if kind == SLIDING else None)
        x = x + jnp.einsum("bto,uo->btu", o.reshape(b, t, -1), wo)
        h = _rms_norm(x, n2, eps=eps)
        y = routed_experts(h.reshape(b * t, u), wr, (wg, wu, wd), top_k,
                           held, norm_topk=norm_topk)
        return x + y.reshape(b, t, u)

    x = params["embed_weight"][tokens.astype(jnp.int32)]
    for i, kind in enumerate(layer_types):
        p = [params["l%d_%s" % (i, n)] for n in _MOE_LAYER_LEAVES]
        x = jax.checkpoint(layer, static_argnums=2)(x, p, kind)
    return _rms_norm(x, params["norm_gamma"], eps=eps)


class MoELM(HybridBlock):
    """Decoder-only LM of sparse-expert layers under attention of two
    kinds: ``layer_types`` names each layer ``sliding_attention`` (a
    query sees its last ``window`` keys) or ``full_attention``; every
    layer's feed-forward part is a top-``top_k`` routed layer of SwiGLU
    experts of which this block HOLDS ``held = (first, count)`` of the
    ``num_routed`` the router runs over (``parallel.moe.routed_experts``:
    one chip's share under expert parallelism; ``(0, num_routed)`` is
    the whole layer).  Pre-norm, two RMSNorm gains a layer and a final
    one, grouped-query heads, rotary positions with a table per layer
    kind (``rope``: kind -> dict with ``rope_theta`` and, for YaRN,
    ``rope_type`` "yarn", ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``, ``attention_factor``), no biases,
    untied head.

    Input ``(B, T)`` token ids; output the final-normed states ``(B, T,
    U)``.  The head is a parameter of this block (``head_weight``) but
    its product is the loss's: ``lm_loss()`` fuses it with its
    cross-entropy (``F.contrib.linear_cross_entropy``) so the float32
    logits are never kept, and ``logits(states)`` gives them where they
    are wanted.  The expert weights are stacked leaves, ``l{i}_gate_weight
    (count, F, U)``.  Each layer is rematerialised in the backward pass
    (the block's own property, no option)."""

    def __init__(self, vocab_size, units=128, expert_width=64,
                 layer_types=(SLIDING, FULL), num_heads=4, num_kv_heads=2,
                 head_dim=None, num_routed=8, held=None, top_k=2,
                 window=32, rope=None, norm_topk=True, epsilon=1e-6,
                 **kwargs):
        super().__init__(**kwargs)
        from ...ops.contrib import yarn_inv_freq
        head_dim = head_dim or units // num_heads
        held = (0, num_routed) if held is None else \
            (int(held[0]), int(held[1]))
        if num_heads % num_kv_heads or head_dim % 2:
            raise ValueError("num_heads (%d) must be a multiple of "
                             "num_kv_heads (%d), heads of even size (%d)"
                             % (num_heads, num_kv_heads, head_dim))
        if any(k not in (SLIDING, FULL) for k in layer_types):
            raise ValueError("layer_types are %r or %r, got %r"
                             % (SLIDING, FULL, list(layer_types)))
        if held[0] < 0 or held[1] < 1 or sum(held) > num_routed \
                or not 1 <= top_k <= num_routed:
            raise ValueError("held experts %r and top_k %d do not fit %d "
                             "routed experts" % (held, top_k, num_routed))
        tables = {}
        for kind in set(layer_types):
            r = dict((rope or {}).get(kind) or {})
            base = float(r.get("rope_theta", 10000.0))
            if r.get("rope_type", "default") == "yarn":
                tables[kind] = (base, tuple(yarn_inv_freq(
                    head_dim, base, float(r["factor"]),
                    float(r["original_max_position_embeddings"]),
                    float(r.get("beta_fast", 32.0)),
                    float(r.get("beta_slow", 1.0)))),
                    float(r.get("attention_factor", 1.0)))
            else:
                tables[kind] = (base, None, 1.0)
        self._config = dict(
            layer_types=tuple(layer_types), num_heads=num_heads,
            num_kv_heads=num_kv_heads, top_k=top_k, held=held,
            window=int(window), rope=tables, norm_topk=bool(norm_topk),
            eps=epsilon)
        self._num_routed = num_routed
        n, f = held[1], expert_width
        shape = {"q_weight": (num_heads * head_dim, units),
                 "k_weight": (num_kv_heads * head_dim, units),
                 "v_weight": (num_kv_heads * head_dim, units),
                 "out_weight": (units, num_heads * head_dim),
                 "router_weight": (num_routed, units),
                 "gate_weight": (n, f, units), "up_weight": (n, f, units),
                 "down_weight": (n, units, f)}
        shapes = [("embed_weight", (vocab_size, units))]
        for i in range(len(layer_types)):
            shapes += [("l%d_%s" % (i, k), shape.get(k, (units,)))
                       for k in _MOE_LAYER_LEAVES]
        shapes += [("norm_gamma", (units,)),
                   ("head_weight", (vocab_size, units))]
        with self.name_scope():
            # the initializer reads the suffix: gains 1
            for name, shp in shapes:
                setattr(self, name, self.params.get(name, shape=shp))
        self._export_gauges()

    def _export_gauges(self):
        from ... import telemetry
        c = self._config
        experts = telemetry.gauge(
            "mxnet_moe_experts", "experts of a layer of the newest MoELM: "
            "the router's width (published) and the ones this block holds "
            "(held)")
        experts.labels(which="published").set(self._num_routed)
        experts.labels(which="held").set(c["held"][1])
        telemetry.gauge("mxnet_moe_top_k", "experts a token is routed to "
                        "in the newest MoELM").set(c["top_k"])
        layers = telemetry.gauge(
            "mxnet_attn_layers", "layers of the newest MoELM by kind of "
            "attention (sliding_attention / full_attention)")
        for kind in (SLIDING, FULL):
            layers.labels(kind=kind).set(c["layer_types"].count(kind))
        telemetry.gauge("mxnet_attn_window", "keys a query of a "
                        "sliding_attention layer of the newest MoELM "
                        "sees").set(c["window"])

    def expected_rows(self, tokens):
        """Rows a step of ``tokens`` tokens sends to this block's held
        experts, a layer, in expectation under a symmetric router —
        exported as ``mxnet_moe_expected_rows`` (what the experts'
        products are sized against).  Beside it the two static shapes
        those rows lie in, of which dispatch and combine fetch the held
        rows alone: ``mxnet_moe_buffer_rows`` (the row buffer, sized for
        every assignment being held) and ``mxnet_moe_slot_rows`` (a row
        a (token, slot) assignment)."""
        from ... import telemetry
        from ...ops.pallas_kernels import GROUPED_TILE_ROWS as tm
        c = self._config
        slots = tokens * c["top_k"]
        rows = slots * c["held"][1] / self._num_routed
        telemetry.gauge("mxnet_moe_expected_rows", "rows a step's tokens "
                        "send to the held experts of one layer of the "
                        "newest MoELM, in expectation under a symmetric "
                        "router").set(rows)
        telemetry.gauge("mxnet_moe_buffer_rows", "rows of the expert "
                        "layer's row buffer in the newest MoELM: whole "
                        "tiles for every assignment being held").set(
                            (-(-slots // tm) + c["held"][1]) * tm)
        telemetry.gauge("mxnet_moe_slot_rows", "(token, slot) "
                        "assignments a step of the newest MoELM routes, "
                        "held or not").set(slots)
        return rows

    def hybrid_forward(self, F, tokens, **params):
        from ...imperative import invoke_fn
        names = [n for n in params if n != "head_weight"]
        config = self._config
        self.expected_rows(tokens.shape[0] * tokens.shape[1])

        def forward(tokens_, *leaves):
            return moe_lm_forward(dict(zip(names, leaves)), tokens_,
                                  **config)

        return invoke_fn(forward, [tokens] + [params[n] for n in names])

    def logits(self, states):
        """The head over final-normed states: ``(B, T, V)``."""
        from ... import ndarray as nd
        return nd.dot(states, self.head_weight.data(), transpose_b=True)

    def lm_loss(self, **kwargs):
        """The training objective over this block's output, sharing its
        head: ``loss(net(tokens), labels)`` — per sequence, the mean
        over positions of the next token's cross-entropy."""
        from ..loss import LinearCELoss
        return LinearCELoss(params=self.params, **kwargs)
