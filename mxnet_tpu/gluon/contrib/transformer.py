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
from typing import NamedTuple

from ..block import HybridBlock
from ..nn import Dense, Dropout, Embedding, HybridSequential, LayerNorm
from ..parameter import DeferredInitializationError

__all__ = ["MultiHeadAttention", "TransformerEncoderCell", "TransformerLM",
           "LoopedLM", "looped_lm_forward", "MoELM", "moe_lm_forward",
           "LatentMoELM", "latent_moe_lm_forward", "latent_attention",
           "SambaYLM", "mamba_mixer", "gated_memory",
           "differential_attention", "causal_attention",
           "cached_attention_step"]


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


def _layer_keeps():
    """The ``policy`` of the ``jax.checkpoint`` that the expert LMs'
    trunk (:func:`_trunk`) wraps each layer in, and
    ``latent_moe_lm_forward`` each prediction module: keep the state
    that enters and, of what the layer computes, two things alone, each
    worth its bytes on the chip.

    * The flash call's output and row statistics
      (``pallas_kernels.FLASH_KEPT``; 67 + 1 MB a layer at 32 x 8192 x
      128 bfloat16, for the 6.7 ms a layer the forward kernel takes) —
      the backward runs the layer again WITHOUT its flash forward, which
      would re-make just those two.  A layer under it tells its
      attention op ``kept=True``, so the statistics are held one float32
      a row; the einsum form (off the TPU) names nothing and is run
      again whole.
    * The routed expert layer's choice and row tables
      (``parallel.moe.ROUTE_KEPT``: the chosen experts and ``_layout``'s
      ``src``, ``dst``, ``is_held``, ``tile_group``, ``used``,
      ``counts``; int32 and bool, about 1 MB a layer at 8192 tokens x 8
      slots, for the 1.5 ms a layer the top-k, the two sorts and the
      tables' gathers take, PERF.md, PR 37) — the layer run again holds
      none of them: only the router's product, the scores and the
      weights read off them at the kept choice, which the router's
      gradient needs.  Forward and backward then agree on the choice
      whatever XLA rounds the recomputed scores to.

    * The selective scan's output and the states entering its chunks
      (``pallas_kernels.SSM_KEPT``; 84 + 21 MB a Mamba layer at 8192 x
      5120 bfloat16 with a state of 16) — the backward kernel reads the
      states, so the layer run again holds no forward scan.

    ``looped_lm_forward``'s pass keeps nothing: what a pass keeps is
    stacked over the ``scan``, and writing it there and reading it back
    cost what the forward kernel took (PERF.md, PR 35)."""
    import jax

    from ...ops.pallas_kernels import FLASH_KEPT, SSM_KEPT
    from ...parallel.moe import ROUTE_KEPT
    return jax.checkpoint_policies.save_only_these_names(
        *FLASH_KEPT, ROUTE_KEPT, *SSM_KEPT)


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
    ``remat`` each pass of the stack is a bare ``jax.checkpoint``: the
    backward pass keeps the state that enters a pass and runs the pass
    again, flash forward and all (:func:`_layer_keeps` says why nothing
    more is kept here), so activations cost one pass, not
    ``num_passes``.

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


SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


# The leaves of the expert LMs' layers, each with its shape in the sizes
# the block names (``_TrunkLM._make_params``).  A layer's leaves are its
# first gain, its attention form's, its second gain and its feed-forward
# form's, in this order (:func:`_layer_leaves`).
def _gqa_leaves(qk_norm=False, cca=False):
    """Grouped-query attention's leaves (:func:`grouped_query_attention`;
    with ``cca``, :func:`compressed_conv_attention`'s): the projections,
    then the two QK-norm gains or the convolutions and the temperature,
    then the output projection."""
    own = ()
    if qk_norm:
        own = (("q_norm_gamma", ("head",)), ("k_norm_gamma", ("head",)))
    if cca:
        own = (("cca_time_weight", ("cca_time", "qk")),
               ("cca_mix_weight", ("qk_heads", "cca_mix", "head", "head")),
               ("cca_temperature_gamma", ("kv_heads",)))
    return ((("q_weight", ("queries", "units")),
             ("k_weight", ("keys", "units")),
             ("v_weight", ("keys", "units"))) + own
            + (("out_weight", ("units", "queries")),))


# latent attention's leaves (:func:`latent_attention`)
_LATENT_LEAVES = (
    ("q_a_weight", ("q_rank", "units")), ("q_a_norm_gamma", ("q_rank",)),
    ("q_b_weight", ("q_b", "q_rank")), ("kv_a_weight", ("kv_a", "units")),
    ("kv_a_norm_gamma", ("kv_rank",)), ("kv_b_weight", ("kv_b", "kv_rank")),
    ("out_weight", ("units", "values")))
# the dense feed-forward form's leaves: one SwiGLU
_DENSE_LEAVES = (("gate_weight", ("dense", "units")),
                 ("up_weight", ("dense", "units")),
                 ("down_weight", ("units", "dense")))


def _routed_leaves(router_layers=0, bias=False, shared=False):
    """The routed feed-forward form's leaves (:func:`routed_ffn`): the
    router (an MLP's first projection where ``router_layers``), the MLP's
    layers, the held selection bias, the held experts' stacked weights,
    the shared expert's."""
    return ((("router_weight", ("router", "units")),)
            + tuple(("router%d_weight" % j,
                     ("routed" if j == router_layers - 1 else "router",
                      "router")) for j in range(router_layers))
            + ((("router_bias", ("routed",)),) if bias else ())
            + (("gate_weight", ("held", "expert", "units")),
               ("up_weight", ("held", "expert", "units")),
               ("down_weight", ("held", "units", "expert")))
            + ((("shared_gate_weight", ("shared", "units")),
                ("shared_up_weight", ("shared", "units")),
                ("shared_down_weight", ("units", "shared"))) if shared
               else ()))


def _layer_leaves(attention, ffn, layer_norm=False):
    """A pre-norm layer's leaves in order: ``norm1_gamma`` (and
    ``norm1_beta`` under ``layer_norm``), the attention form's,
    ``norm2_gamma`` (``norm2_beta``), the feed-forward form's."""
    gain = lambda name: ((name + "_gamma", ("units",)),) + (
        ((name + "_beta", ("units",)),) if layer_norm else ())
    return gain("norm1") + attention + gain("norm2") + ffn


# a Mamba mixer's leaves (:func:`mamba_mixer`)
_MAMBA_LEAVES = (
    ("in_weight", ("inner2", "units")), ("conv_weight", ("conv", "inner")),
    ("conv_bias", ("inner",)), ("x_weight", ("dbc", "inner")),
    ("dt_weight", ("inner", "dt_rank")), ("dt_bias", ("inner",)),
    ("a_log_weight", ("inner", "state")), ("d_weight", ("inner",)),
    ("out_weight", ("units", "inner")))
# a gated memory unit's leaves (:func:`gated_memory`)
_GMU_LEAVES = (("gmu_in_weight", ("inner", "units")),
               ("out_weight", ("units", "inner")))


def _differential_leaves(cross=False):
    """Differential attention's leaves (:func:`differential_attention`):
    the projection of queries, keys and values (of the queries alone in a
    cross-attention layer) with its bias, the four vectors ``lambda`` is
    made of, the heads' norm, the output projection with its bias."""
    proj = (("q_weight", ("queries", "units")), ("q_bias", ("queries",))) \
        if cross else (("qkv_weight", ("qkv", "units")),
                       ("qkv_bias", ("qkv",)))
    return proj + tuple(("lambda_%s_weight" % n, ("head",))
                        for n in ("q1", "k1", "q2", "k2")) \
        + (("subln_gamma", ("pair",)), ("out_weight", ("units", "queries")),
           ("out_bias", ("units",)))


# a multi-token-prediction module's own leaves around its layer
_MTP_FRONT_LEAVES = (("embed_norm_gamma", ("units",)),
                     ("hidden_norm_gamma", ("units",)),
                     ("proj_weight", ("units", "joined")))


def grouped_query_attention(h, p, *, num_heads, num_kv_heads, rope,
                            window=None, qk_norm=False, block_length=None,
                            positions=None, rotary_dim=None, eps=1e-6,
                            kept=False):
    """Grouped-query causal attention with rotary q and k over normed
    states ``h (B, T, U)``: ``(B, T, U)``, the output projection
    included.  ``p`` holds one layer's ``q_weight (H D, U)``, ``k_weight
    (Hkv D, U)``, ``v_weight (Hkv D, U)``, ``out_weight (U, H D)`` and,
    under ``qk_norm``, ``q_norm_gamma`` and ``k_norm_gamma (D,)``: every
    query and key head RMS-normed over its own dimensions BEFORE the
    rotary, as Qwen3's attention does.  ``rope`` is ``(base, inv_freq or
    None, scale)`` (:func:`ops.contrib._rotary_embedding`; only the first
    ``rotary_dim`` dimensions turn where one is given).

    With ``window`` a query sees its last ``window`` keys (scope
    ``mx_attn_window``), without it every key before it
    (``mx_attn_full``); with ``block_length`` the three-part block mask
    of block diffusion (``F.contrib.flash_attention``'s
    ``block_diffusion``, no window; scope ``mx_attn_blockdiff``) over rows
    at ``positions``.  The projections and the flash call are under the
    scope, the output projection is not.  ``kept``: the caller is a layer
    under :func:`_layer_keeps`."""
    import jax
    import jax.numpy as jnp

    from ...ops.contrib import _flash_attention_op, _rotary_embedding
    from ...ops.nn import _rms_norm
    from ...telemetry import phases

    b, t, _u = h.shape
    base, inv_freq, scale = rope
    heads = lambda w, n: jnp.einsum("btu,ou->bto", h, w).reshape(
        b, t, n, w.shape[0] // n)
    if block_length is not None:
        mask, scope = {"block_diffusion": block_length}, \
            phases.ATTN_BLOCKDIFF_SCOPE
    else:
        mask = {"causal": True, "window": window}
        scope = phases.ATTN_FULL_SCOPE if window is None else \
            phases.ATTN_WINDOW_SCOPE
    with jax.named_scope(scope):
        def turned(which, n):
            a = heads(p[which + "_weight"], n)
            if qk_norm:
                a = _rms_norm(a, p[which + "_norm_gamma"], eps=eps)
            return _rotary_embedding(a, positions, base=base,
                                     inv_freq=inv_freq, scale=scale,
                                     rotary_dim=rotary_dim)

        o = _flash_attention_op(
            turned("q", num_heads), turned("k", num_kv_heads),
            heads(p["v_weight"], num_kv_heads), kept=kept, **mask)
    return jnp.einsum("bto,uo->btu", o.reshape(b, t, -1), p["out_weight"])


def compressed_conv_attention(h, p, *, num_heads, num_kv_heads, rope,
                              rotary_dim=None, kept=False):
    """Compressed convolutional attention (CCA; Figliolia et al.,
    arXiv:2510.04476, as ZAYA1 runs it) over normed states ``h (B, T,
    U)``: ``(B, T, U)``, the output projection included.  ``p`` holds one
    layer's ``q_weight (H D, U)``, ``k_weight (Hkv D, U)``, ``v_weight
    (Hkv D, U)``, ``cca_time_weight (K0, (H + Hkv) D)``,
    ``cca_mix_weight (H + Hkv, K1, D, D)``, ``cca_temperature_gamma
    (Hkv,)`` and ``out_weight (U, H D)``; query head ``n`` reads
    key/value head ``n // (H / Hkv)``.  With ``q~ = h Wq'``, ``k~ = h
    Wk'``, ``z = [q~ ; k~]``::

        a_t = sum_j time[j] * z_{t-j}             causal, channel by channel
        b_t = sum_j a_{t-j}[head] @ mix[head, j]  causal, across a head's D
        m_g = (mean of group g's query heads of q~ + k~_g) / 2
        q, k = b's query heads + m_g, b's key heads + m_g
        v_t  = [first Hkv/2 heads of h_t Wv' ; the others of h_{t-1} Wv']
        q, k = temperature_g * q / |q|, k / |k|   each head, float32
        q, k = rope(q), rope(k)                   the first rotary_dim
        o    = causal softmax(q . k) v            no further scale

    (rows before the first are zero).  The projections are the layer's;
    what mixes between them and the flash call is under scope ``mx_cca``,
    the call under ``mx_attn_full``.  ``rope`` is ``(base, inv_freq or
    None, scale)`` (:func:`ops.contrib._rotary_embedding`); ``kept``: the
    caller is a layer under :func:`_layer_keeps`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ...ops.contrib import (_causal_conv, _flash_attention_op,
                                _rotary_embedding)
    from ...telemetry import phases

    b, t, _u = h.shape
    hkv, group = num_kv_heads, num_heads // num_kv_heads
    d = p["q_weight"].shape[0] // num_heads
    base, inv_freq, scale = rope
    proj = lambda w: jnp.einsum("btu,ou->bto", h, w)
    q0, k0, v0 = proj(p["q_weight"]), proj(p["k_weight"]), proj(p["v_weight"])
    with jax.named_scope(phases.CCA_SCOPE):
        z = _causal_conv(_causal_conv(jnp.concatenate([q0, k0], -1),
                                      p["cca_time_weight"]),
                         p["cca_mix_weight"]).astype(jnp.float32)
        mean = 0.5 * (jnp.mean(q0.reshape(b, t, hkv, group, d).astype(
            jnp.float32), axis=3) + k0.reshape(b, t, hkv, d).astype(
                jnp.float32))
        q = z[..., :num_heads * d].reshape(b, t, hkv, group, d) \
            + mean[:, :, :, None]
        k = z[..., num_heads * d:].reshape(b, t, hkv, d) + mean

        def unit(a):
            return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

        temp = p["cca_temperature_gamma"].astype(jnp.float32)
        q = (unit(q) * temp[:, None, None]).reshape(b, t, num_heads, d)
        turn = lambda a: _rotary_embedding(
            a, base=base, inv_freq=inv_freq, scale=scale,
            rotary_dim=rotary_dim).astype(h.dtype)
        q, k = turn(q), turn(unit(k))
        shifted = hkv // 2 * d
        v = jnp.concatenate(
            [v0[..., :shifted],
             jnp.pad(v0[..., shifted:], ((0, 0), (1, 0), (0, 0)))[:, :t]],
            -1).reshape(b, t, hkv, d)
    with jax.named_scope(phases.ATTN_FULL_SCOPE):
        o = _flash_attention_op(q, k, v, causal=True, scale=1.0, kept=kept)
    return jnp.einsum("bto,uo->btu", o.reshape(b, t, -1), p["out_weight"])


class _RouterMLP(NamedTuple):
    """A router given as an MLP over ``weights`` — a bias-free
    down-projection, then layers with an exact GELU before each but the
    first; every product in the weights' dtype with float32
    accumulation.  Called on ``x (T, U)`` it gives the ``(T, E)`` float32
    logits; its ``shape`` is ``(E, U)``, a linear router's over the same
    experts (``parallel.moe.routed_experts`` reads it)."""
    weights: tuple

    @property
    def shape(self):
        return (self.weights[-1].shape[0], self.weights[0].shape[1])

    def __call__(self, x):
        import jax
        import jax.numpy as jnp
        r = x
        for j, w in enumerate(self.weights):
            if j > 1:
                r = jax.nn.gelu(r, approximate=False)
            r = jnp.einsum("ti,oi->to", r.astype(w.dtype), w,
                           preferred_element_type=jnp.float32)
        return r


def routed_ffn(h, p, *, top_k, held, router_layers=0, shared_expert=False,
               **route):
    """The routed feed-forward form over normed states ``h (B, T, U)``:
    one chip's share of a top-``top_k`` routed expert layer
    (:func:`parallel.moe.routed_experts`: the router over ALL published
    experts, ``held = (first, count)`` the ones whose stacked weights
    ``gate_weight``, ``up_weight``, ``down_weight`` are here; ``route``
    its keywords, ``norm_topk``, ``scoring``, ``scale``; the held
    selection bias ``router_bias`` where ``p`` has one).  With
    ``router_layers`` the router is an MLP (:class:`_RouterMLP`):
    ``router_weight`` projects down, then ``router0_weight ..`` give the
    logits.  With ``shared_expert`` the
    SwiGLU every token takes (``shared_*_weight``, scope
    ``mx_shared_expert``) is added: it is this block's and not the routed
    layer's, so summed over the chips' shares it counts once."""
    import jax

    from ...ops.contrib import _gated_ffn
    from ...parallel.moe import routed_experts
    from ...telemetry import phases

    b, t, u = h.shape
    router = p["router_weight"]
    if router_layers:
        router = _RouterMLP((router,) + tuple(
            p["router%d_weight" % j] for j in range(router_layers)))
    if "router_bias" in p:
        route = dict(route, bias=p["router_bias"])
    y = routed_experts(
        h.reshape(b * t, u), router,
        (p["gate_weight"], p["up_weight"], p["down_weight"]), top_k, held,
        **route).reshape(b, t, u)
    if shared_expert:
        with jax.named_scope(phases.SHARED_EXPERT_SCOPE):
            y = y + _gated_ffn(h, p["shared_gate_weight"],
                               p["shared_up_weight"], p["shared_down_weight"])
    return y


def dense_ffn(h, p):
    """The dense feed-forward form: one SwiGLU over ``h (B, T, U)``."""
    from ...ops.contrib import _gated_ffn
    return _gated_ffn(h, p["gate_weight"], p["up_weight"], p["down_weight"])


def mamba_mixer(h, p, *, memory=False):
    """A Mamba mixer (Gu & Dao, arXiv:2312.00752) over normed states ``h
    (B, T, U)``: ``(B, T, U)``, the output projection included, all of it
    under scope ``mx_ssm``.  ``p`` holds one layer's ``in_weight (2 E,
    U)``, ``conv_weight (K, E)`` (tap ``j`` reads the row ``j`` back),
    ``conv_bias (E,)``, ``x_weight (R + 2 N, E)``, ``dt_weight (E, R)``,
    ``dt_bias (E,)``, ``a_log_weight (E, N)``, ``d_weight (E,)`` and
    ``out_weight (U, E)``::

        [u ; z] = W_in h                   no bias
        u'      = silu(conv(u) + b_c)      causal, depthwise
        [dl ; B ; C] = W_x u'              R + N + N
        delta   = softplus(W_dt dl + b_dt) float32
        y       = selective_scan(u', delta, -exp(A_log), B, C, D)
        out     = W_out (y * silu(z))

    (``F.contrib.selective_scan``: the Pallas kernel pair on the TPU).
    With ``memory`` the form returns ``(out, {"memory": y})``: the scan's
    output before its gate is the memory a decoder-hybrid-decoder's gated
    memory units read (:func:`gated_memory`)."""
    import jax
    import jax.numpy as jnp

    from ...ops.contrib import _causal_conv, _selective_scan
    from ...telemetry import phases

    n, r = p["a_log_weight"].shape[1], p["dt_weight"].shape[1]
    with jax.named_scope(phases.SSM_SCOPE):
        u, z = jnp.split(jnp.einsum("btu,ou->bto", h, p["in_weight"]), 2, -1)
        u = jax.nn.silu(_causal_conv(u, p["conv_weight"]) + p["conv_bias"])
        dbc = jnp.einsum("bte,oe->bto", u, p["x_weight"])
        delta = jax.nn.softplus(
            jnp.einsum("btr,er->bte", dbc[..., :r], p["dt_weight"],
                       preferred_element_type=jnp.float32)
            + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["a_log_weight"].astype(jnp.float32))
        y = _selective_scan(u, delta, a, dbc[..., r:r + n],
                            dbc[..., r + n:], p["d_weight"])
        out = jnp.einsum("bte,ue->btu", y * jax.nn.silu(z), p["out_weight"])
    return (out, {"memory": y}) if memory else out


def gated_memory(h, p):
    """A gated memory unit (SambaY, Ren et al., arXiv:2507.06607) over
    normed states ``h (B, T, U)``: ``W_o (silu(W_i h) * m)``, ``m`` the
    memory an earlier Mamba mixer wrote (``p["memory"] (B, T, E)``,
    :func:`mamba_mixer`), ``gmu_in_weight (E, U)`` and ``out_weight (U,
    E)`` without biases; scope ``mx_gmu``."""
    import jax
    import jax.numpy as jnp

    from ...telemetry import phases
    with jax.named_scope(phases.GMU_SCOPE):
        gate = jax.nn.silu(jnp.einsum("btu,eu->bte", h, p["gmu_in_weight"]))
        return jnp.einsum("bte,ue->btu", gate * p["memory"], p["out_weight"])


def differential_attention(h, p, *, num_heads, num_kv_heads, lambda_init,
                           window=None, cross=False, shares=False, eps=1e-5,
                           kept=False):
    """Differential attention (Ye et al., arXiv:2410.05258) over normed
    states ``h (B, T, U)``, causal, with no positional encoding:
    ``(B, T, U)``, the output projection included.  ``p`` holds
    ``qkv_weight ((H + 2 Hkv) D, U)`` with ``qkv_bias`` — in a ``cross``
    layer ``q_weight (H D, U)`` with ``q_bias``, its keys and values
    ``p["shared_k"]``, ``p["shared_v"] (B, T, Hkv, D)`` written by the
    layer that ``shares`` them — ``lambda_{q1,k1,q2,k2}_weight (D,)``,
    ``subln_gamma (2 D,)``, ``out_weight (U, H D)`` and ``out_bias``.
    Query heads pair up (``2 i``, ``2 i + 1``), key heads too, the values
    are ``Hkv / 2`` heads of ``2 D``, and query pair ``i`` reads key/value
    pair ``i // 2``::

        O_i = softmax(Q1_i K1^T / sqrt(D)) V
              - lam softmax(Q2_i K2^T / sqrt(D)) V
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
        O_i = RMS(O_i; subln_gamma) (1 - lambda_init)

    ONE flash call a layer: the query heads are ordered so that head ``n``
    reads key head ``n // 2`` and each value head serves both key heads of
    its pair; ``lam``, the difference and the norm are elementwise after
    it.  Everything before the output projection is under scope
    ``mx_attn_cross`` (``cross``), ``mx_attn_window`` (a ``window``) or
    ``mx_attn_full``.  ``shares``: the form returns ``(out, {"shared_k":
    k, "shared_v": v})``.  ``kept``: the caller is a layer under
    :func:`_layer_keeps`."""
    import jax
    import jax.numpy as jnp

    from ...ops.contrib import _flash_attention_op
    from ...ops.nn import _rms_norm
    from ...telemetry import phases

    b, t, _u = h.shape
    pairs = num_kv_heads // 2
    proj = lambda w: jnp.einsum("btu,ou->bto", h, p[w + "_weight"]) \
        + p[w + "_bias"]
    scope = phases.ATTN_CROSS_SCOPE if cross else \
        phases.ATTN_FULL_SCOPE if window is None else phases.ATTN_WINDOW_SCOPE
    with jax.named_scope(scope):
        if cross:
            q, k, v = proj("q"), p["shared_k"], p["shared_v"]
        else:
            q, k, v = jnp.split(proj("qkv"), (
                num_heads * p["lambda_q1_weight"].shape[0],
                (num_heads + num_kv_heads) * p["lambda_q1_weight"].shape[0]),
                -1)
            k, v = (a.reshape(b, t, num_kv_heads, -1) for a in (k, v))
        d = k.shape[-1]
        # query head 4 j + 2 pair + map  ->  2 (2 j + map) + pair
        q = jnp.swapaxes(q.reshape(b, t, pairs, 2, 2, d), 3, 4).reshape(
            b, t, num_heads, d)
        o = _flash_attention_op(
            q, k, jnp.repeat(v.reshape(b, t, pairs, 2 * d), 2, axis=2),
            causal=True, window=window, kept=kept)
        o = o.reshape(b, t, pairs, 2, 2, 2 * d).astype(jnp.float32)
        f32 = lambda n: p["lambda_%s_weight" % n].astype(jnp.float32)
        lam = jnp.exp(jnp.sum(f32("q1") * f32("k1"))) \
            - jnp.exp(jnp.sum(f32("q2") * f32("k2"))) + lambda_init
        o = _rms_norm(o[:, :, :, 0] - lam * o[:, :, :, 1], p["subln_gamma"],
                      eps=eps) * (1.0 - lambda_init)
    out = jnp.einsum("bto,uo->btu", o.astype(h.dtype).reshape(b, t, -1),
                     p["out_weight"]) + p["out_bias"]
    return (out, {"shared_k": k, "shared_v": v}) if shares else out


def _norm(x, p, which, eps, layer_norm):
    """``x`` under the gain ``which + "_gamma"``: RMSNorm, or LayerNorm
    with the bias ``which + "_beta"`` under ``layer_norm``."""
    from ...ops.nn import _layer_norm, _rms_norm
    if layer_norm:
        return _layer_norm(x, p[which + "_gamma"], p[which + "_beta"],
                           eps=eps)
    return _rms_norm(x, p[which + "_gamma"], eps=eps)


def _layer(x, p, attention, ffn, eps, layer_norm=False):
    """One pre-norm layer, ``x + Attn(N(x))`` then ``x + FFN(N(x))``,
    ``N`` RMSNorm (LayerNorm under ``layer_norm``): ``attention`` and
    ``ffn`` are forms, ``(h, p) -> (B, T, U)``; an attention form may
    return ``(out, wrote)`` instead, ``wrote`` a dict of tensors later
    layers read (:func:`_trunk`).  Returns ``(x, wrote)``."""
    out = attention(_norm(x, p, "norm1", eps, layer_norm), p)
    out, wrote = out if isinstance(out, tuple) else (out, {})
    x = x + out
    return x + ffn(_norm(x, p, "norm2", eps, layer_norm), p), wrote


def _trunk(params, tokens, layers, *, eps, block_diffusion=False,
           layer_norm=False):
    """The expert LMs' one trunk: the embedding's gather of ``tokens (B,
    T)`` (under ``mx_noise`` in a ``block_diffusion`` forward), the
    ``layers`` in turn, the final norm ``norm_gamma`` (with ``norm_beta``:
    every norm a LayerNorm under ``layer_norm``, :func:`_norm`).  Each
    layer is ``(prefix, names, attention, ffn)`` or ``(prefix, names,
    attention, ffn, reads)``: its leaves are ``params[prefix + name]`` in
    the order ``names`` gives, then the tensors an earlier layer WROTE
    under the names ``reads`` gives (a layer's attention form returns
    ``(out, {name: tensor})`` to write them: the state a layer hands to a
    later one that is not its neighbour, whose gradient comes back
    through both checkpoints), the order they are handed to
    its ``jax.checkpoint`` in (:func:`_layer_keeps`: the backward pass
    keeps the state that enters a layer and runs the layer again —
    except the flash call, whose output and row statistics are kept, 67
    + 1 MB a layer at 32 x 8192 x 128, and the expert layer's routing,
    whose choice and row tables are, about 1 MB).  A block-diffusion
    forward runs ``[noised ; clean]`` and returns the noised half alone,
    ``(B, T / 2, U)``.

    A new architecture costs one form — a function ``(h, p, **static) ->
    (B, T, U)`` of normed states and the layer's leaves, like
    :func:`grouped_query_attention`, :func:`compressed_conv_attention`,
    :func:`latent_attention`, :func:`mamba_mixer`, :func:`gated_memory`,
    :func:`differential_attention`, :func:`routed_ffn` or
    :func:`dense_ffn` — and its leaf entry (``_gqa_leaves``,
    ``_LATENT_LEAVES``, ``_MAMBA_LEAVES``, ``_GMU_LEAVES``,
    ``_differential_leaves``, ``_routed_leaves``, ``_DENSE_LEAVES``): the
    block that takes it chooses the form from its arguments and names the
    sizes its shapes read; no new forward."""
    import collections
    import contextlib
    import functools

    import jax
    import jax.numpy as jnp

    from ...telemetry import phases

    with jax.named_scope(phases.NOISE_SCOPE) if block_diffusion \
            else contextlib.nullcontext():
        x = params["embed_weight"][tokens.astype(jnp.int32)]
    written = {}
    for prefix, names, attention, ffn, *reads in layers:
        p = collections.OrderedDict((n, params[prefix + n]) for n in names)
        p.update((n, written[n]) for n in (reads[0] if reads else ()))
        x, wrote = jax.checkpoint(functools.partial(
            _layer, attention=attention, ffn=ffn, eps=eps,
            layer_norm=layer_norm), policy=_layer_keeps())(x, p)
        written.update(wrote)
    if block_diffusion:
        x = x[:, :tokens.shape[1] // 2]
    return _norm(x, params, "norm", eps, layer_norm)


def moe_lm_forward(params, tokens, *, layer_types, num_heads, num_kv_heads,
                   top_k, held, window, rope, norm_topk=True, eps=1e-6,
                   qk_norm=False, block_length=None, cca=False,
                   rotary_dim=None, router_layers=0):
    """A sparse-expert LM's trunk as ONE pure function of ``(params,
    tokens)``: the final-normed states ``(B, T, U)`` the head reads.
    ``params`` maps :class:`MoELM`'s short parameter names to jax
    arrays, ``tokens`` is ``(B, T)`` int, the keywords are the block's
    ``_config``.  Layer ``i`` (:func:`_trunk`) is
    :func:`grouped_query_attention` — ``window`` keys on a
    ``sliding_attention`` layer, ``rope[kind]`` its rotary table — or,
    with ``cca``, :func:`compressed_conv_attention`; then
    :func:`routed_ffn`, its router an MLP of ``router_layers`` layers
    where that is given.

    With ``block_length`` the trunk is trained by diffusion over blocks
    (Arriola et al., arXiv:2503.09573; SDAR, arXiv:2510.06303):
    ``tokens`` is ``(B, 2 L)``, a noised copy of every sequence and then
    its clean copy, both at positions ``0 .. L - 1``; EVERY layer's
    attention runs under the three-part block mask (the layer kind still
    picks the rotary table, a window is not applied), and only the
    noised half's states are returned, ``(B, L, U)`` — the objective
    reads no other (``gluon.loss.BlockDiffusionCELoss``)."""
    import functools

    import jax.numpy as jnp

    names = [n for n, _ in _layer_leaves(_gqa_leaves(qk_norm, cca),
                                         _routed_leaves(router_layers))]
    positions = None if block_length is None else \
        jnp.tile(jnp.arange(tokens.shape[1] // 2, dtype=jnp.int32), 2)
    ffn = functools.partial(routed_ffn, top_k=top_k, held=held,
                            norm_topk=norm_topk, router_layers=router_layers)

    heads = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                 rotary_dim=rotary_dim, kept=True)

    def attention(kind):
        if cca:
            return functools.partial(compressed_conv_attention,
                                     rope=rope[kind], **heads)
        return functools.partial(
            grouped_query_attention, rope=rope[kind],
            window=window if kind == SLIDING else None, qk_norm=qk_norm,
            block_length=block_length, positions=positions, eps=eps, **heads)

    return _trunk(params, tokens,
                  [("l%d_" % i, names, attention(kind), ffn)
                   for i, kind in enumerate(layer_types)],
                  eps=eps, block_diffusion=block_length is not None)


def latent_attention(h, p, *, num_heads, nope_dim, rope_dim, v_dim,
                     rope_base=10000.0, rope_interleaved=True, eps=1e-6,
                     kept=False):
    """Multi-head latent attention over normed states ``h (B, T, U)``:
    ``(B, T, U)``, the output projection included.  ``p`` holds one
    layer's ``q_a_weight (Rq, U)``, ``q_a_norm_gamma``, ``q_b_weight (H
    (nope + rope), Rq)``, ``kv_a_weight (Rkv + rope, U)``,
    ``kv_a_norm_gamma``, ``kv_b_weight (H (nope + v), Rkv)`` and
    ``out_weight (U, H v)``.  Every head's key is ``[k_nope | k_rope]``
    with the ONE rotary key broadcast over the heads; the flash call
    scores over ``nope + rope`` dimensions (scale their ``-0.5`` power)
    and sums values of ``v_dim`` — neither side is padded to the other.
    What runs around the flash call is under scope ``mx_attn_latent``,
    the call and its rotary under ``mx_attn_full``.  ``kept``: the
    caller is a layer under :func:`_layer_keeps`."""
    import jax
    import jax.numpy as jnp

    from ...ops.contrib import _flash_attention_op, _rotary_embedding
    from ...ops.nn import _rms_norm
    from ...telemetry import phases

    b, t, _u = h.shape
    turn = lambda a: _rotary_embedding(a, base=rope_base,
                                       interleaved=rope_interleaved)
    with jax.named_scope(phases.ATTN_LATENT_SCOPE):
        cq = _rms_norm(jnp.einsum("btu,ru->btr", h, p["q_a_weight"]),
                       p["q_a_norm_gamma"], eps=eps)
        q = jnp.einsum("btr,or->bto", cq, p["q_b_weight"]).reshape(
            b, t, num_heads, nope_dim + rope_dim)
        ckv = jnp.einsum("btu,ru->btr", h, p["kv_a_weight"])
        kv = jnp.einsum(
            "btr,or->bto",
            _rms_norm(ckv[..., :-rope_dim], p["kv_a_norm_gamma"], eps=eps),
            p["kv_b_weight"]).reshape(b, t, num_heads, nope_dim + v_dim)
    with jax.named_scope(phases.ATTN_FULL_SCOPE):
        q_rope = turn(q[..., nope_dim:])
        k_rope = turn(ckv[:, :, None, -rope_dim:])
    with jax.named_scope(phases.ATTN_LATENT_SCOPE):
        q = jnp.concatenate([q[..., :nope_dim], q_rope], -1)
        k = jnp.concatenate(
            [kv[..., :nope_dim],
             jnp.broadcast_to(k_rope, (b, t, num_heads, rope_dim))], -1)
    with jax.named_scope(phases.ATTN_FULL_SCOPE):
        o = _flash_attention_op(q, k, kv[..., nope_dim:], causal=True,
                                kept=kept)
    return jnp.einsum("bto,uo->btu", o.reshape(b, t, -1), p["out_weight"])


def latent_moe_lm_forward(params, tokens, *, mlp_layer_types, num_heads,
                          nope_dim, rope_dim, v_dim, top_k, held,
                          scoring="sigmoid", route_scale=1.0, norm_topk=True,
                          shared_expert=True, rope_base=10000.0,
                          rope_interleaved=True, mtp_depth=0, eps=1e-6):
    """A latent-attention sparse-expert LM's trunk as ONE pure function
    of ``(params, tokens)``: the final-normed states ``(B, T, U)`` the
    head reads and, with ``mtp_depth`` prediction modules, their own
    final-normed states ``(B, mtp_depth, T, U)`` beside them.
    ``params`` maps :class:`LatentMoELM`'s short parameter names to jax
    arrays (a leaf the block did not make, as ``l1_router_bias`` without
    a selection bias, is left out), ``tokens`` is ``(B, T)`` int, the
    keywords are the block's ``_config``.  Layer ``i`` (:func:`_trunk`)
    is :func:`latent_attention` (MLA, DeepSeek-V2, arXiv:2405.04434),
    then by ``mlp_layer_types[i]`` :func:`dense_ffn` or
    :func:`routed_ffn` with ``scoring``, ``route_scale`` and, under
    ``shared_expert``, the shared expert.

    Prediction module ``k`` (DeepSeek-V3, arXiv:2412.19437): ``[RMS_e(
    Emb(t_{i+k})) ; RMS_h(h_i)] W`` through one more sparse layer of its
    own and a final norm of its own, ``h`` being the main states after
    their final norm for ``k = 1`` and module ``k - 1``'s un-normed
    output after it; embedding and head are the model's.  The last ``k``
    positions read tokens that wrap round; the loss leaves them out
    (``gluon.loss.MultiTokenCELoss``).  A module is checkpointed as a
    layer is (:func:`_layer_keeps`)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ...ops.nn import _rms_norm
    from ...telemetry import phases

    attention = functools.partial(
        latent_attention, num_heads=num_heads, nope_dim=nope_dim,
        rope_dim=rope_dim, v_dim=v_dim, rope_base=rope_base,
        rope_interleaved=rope_interleaved, eps=eps, kept=True)
    ffn = {DENSE: dense_ffn,
           SPARSE: functools.partial(
               routed_ffn, top_k=top_k, held=held, norm_topk=norm_topk,
               scoring=scoring, scale=route_scale,
               shared_expert=shared_expert)}
    leaves = {DENSE: _layer_leaves(_LATENT_LEAVES, _DENSE_LEAVES),
              SPARSE: _layer_leaves(_LATENT_LEAVES, _routed_leaves(
                  bias=True, shared=True))}

    def names(prefix, kind):
        # the leaves the block made, by name: the order in which this
        # model's layers hand them to their checkpoint (the operands of
        # its barrier in the compiled step)
        return sorted(n for n, _ in leaves[kind] if prefix + n in params)

    def module(h, nxt, p, front):
        e = _rms_norm(params["embed_weight"][nxt],
                      front["embed_norm_gamma"], eps=eps)
        hn = _rms_norm(h, front["hidden_norm_gamma"], eps=eps)
        x = jnp.einsum("btc,uc->btu", jnp.concatenate([e, hn], -1),
                       front["proj_weight"])
        return _layer(x, p, attention, ffn[SPARSE], eps)[0]

    tokens = tokens.astype(jnp.int32)
    states = _trunk(params, tokens,
                    [("l%d_" % i, names("l%d_" % i, kind), attention,
                      ffn[kind]) for i, kind in enumerate(mlp_layer_types)],
                    eps=eps)
    if not mtp_depth:
        return states
    h, outs = states, []
    with jax.named_scope(phases.MTP_SCOPE):
        for k in range(mtp_depth):
            pre = "mtp%d_" % k
            h = jax.checkpoint(module, policy=_layer_keeps())(
                h, jnp.roll(tokens, -(k + 1), axis=1),
                {n: params[pre + n] for n in names(pre, SPARSE)},
                {n: params[pre + n] for n, _ in _MTP_FRONT_LEAVES})
            outs.append(_rms_norm(h, params[pre + "norm_gamma"], eps=eps))
    return states, jnp.stack(outs, axis=1)


def _export_expert_rows(model, tokens, top_k, held, num_routed):
    """Rows a step of ``tokens`` tokens sends to the held experts of one
    layer of ``model``, in expectation under a symmetric router —
    exported as ``mxnet_moe_expected_rows`` (what the experts' products
    are sized against).  Beside it the two static shapes those rows lie
    in, of which dispatch and combine fetch the held rows alone:
    ``mxnet_moe_buffer_rows`` (the row buffer, sized for every
    assignment being held) and ``mxnet_moe_slot_rows`` (a row a (token,
    slot) assignment)."""
    from ... import telemetry
    from ...ops.pallas_kernels import GROUPED_TILE_ROWS as tm
    slots = tokens * top_k
    rows = slots * held[1] / num_routed
    telemetry.gauge("mxnet_moe_expected_rows", "rows a step's tokens "
                    "send to the held experts of one layer of the "
                    "newest %s, in expectation under a symmetric "
                    "router" % model).set(rows)
    telemetry.gauge("mxnet_moe_buffer_rows", "rows of the expert "
                    "layer's row buffer in the newest %s: whole "
                    "tiles for every assignment being held" % model).set(
                        (-(-slots // tm) + held[1]) * tm)
    telemetry.gauge("mxnet_moe_slot_rows", "(token, slot) "
                    "assignments a step of the newest %s routes, "
                    "held or not" % model).set(slots)
    return rows


class _TrunkLM(HybridBlock):
    """What the LMs of the one trunk (:func:`_trunk`) share around it: the
    parameters made in order, the forward through ``invoke_fn`` and the
    head.  A subclass turns its arguments into ``_config``, the leaves and
    the sizes their shapes read, exports its gauges in
    ``_export_gauges`` and calls its pure forward in ``_forward`` (looked
    up when the block runs, not when it is made)."""

    _tied = False

    def _make_params(self, shapes, sizes):
        """Parameters in the order of ``shapes``, ``(name, shape)`` pairs
        whose entries are numbers or names of ``sizes``; then the
        gauges."""
        with self.name_scope():
            # the initializer reads the suffix: gains 1, the bias 0; the
            # selection bias is held, not trained
            for name, shape in shapes:
                held_only = {"grad_req": "null"} \
                    if name.endswith("router_bias") else {}
                setattr(self, name, self.params.get(
                    name, shape=tuple(sizes.get(d, d) for d in shape),
                    **held_only))
        self._export_gauges()

    @staticmethod
    def _export_window(window):
        from ... import telemetry
        telemetry.gauge("mxnet_attn_window", "keys a query of a "
                        "sliding_attention layer of the newest MoELM or "
                        "SambaYLM sees").set(window)

    def hybrid_forward(self, F, tokens, **params):
        from ...imperative import invoke_fn
        names = [n for n in params if n != "head_weight"]

        def forward(tokens_, *leaves):
            return self._forward(dict(zip(names, leaves)), tokens_)

        out = invoke_fn(forward, [tokens] + [params[n] for n in names])
        return tuple(out) if isinstance(out, list) else out

    def _head(self):
        return self.embed_weight if self._tied else self.head_weight

    def logits(self, states):
        """The head over final-normed states: ``(B, T, V)``."""
        from ... import ndarray as nd
        return nd.dot(states, self._head().data(), transpose_b=True)

    def lm_loss(self, **kwargs):
        """The training objective over this block's output, sharing its
        head: ``loss(net(tokens), labels)`` — per sequence, the mean
        over positions of the next token's cross-entropy (the head the
        embedding table where the two are tied)."""
        from ..loss import LinearCELoss
        return LinearCELoss(params=self.params,
                            head="embed_weight" if self._tied
                            else "head_weight", **kwargs)


class _ExpertLM(_TrunkLM):
    """A trunk LM with a routed expert layer: the held experts' check,
    the expert layer's gauges and the rows a step sends to the held
    experts, exported each time the block is traced at a shape."""

    @staticmethod
    def _held_experts(num_routed, held, top_k):
        held = (0, num_routed) if held is None else \
            (int(held[0]), int(held[1]))
        if held[0] < 0 or held[1] < 1 or sum(held) > num_routed \
                or not 1 <= top_k <= num_routed:
            raise ValueError("held experts %r and top_k %d do not fit %d "
                             "routed experts" % (held, top_k, num_routed))
        return held

    def _export_gauges(self):
        """The routed layer, in gauges: the router's width, the experts
        held here, the experts a token takes."""
        from ... import telemetry
        c = self._config
        experts = telemetry.gauge(
            "mxnet_moe_experts", "experts of a layer of the newest MoELM or "
            "LatentMoELM: the router's width (published) and the ones this "
            "block holds (held)")
        experts.labels(which="published").set(self._num_routed)
        experts.labels(which="held").set(c["held"][1])
        telemetry.gauge("mxnet_moe_top_k", "experts a token is routed to in "
                        "the newest MoELM or LatentMoELM").set(c["top_k"])

    def expected_rows(self, tokens):
        """Rows a step of ``tokens`` tokens sends to this block's held
        experts, a layer, in expectation (:func:`_export_expert_rows`:
        exported with the two static shapes those rows lie in)."""
        c = self._config
        return _export_expert_rows(type(self).__name__, tokens, c["top_k"],
                                   c["held"], self._num_routed)

    def hybrid_forward(self, F, tokens, **params):
        self.expected_rows(tokens.shape[0] * tokens.shape[1])
        return super().hybrid_forward(F, tokens, **params)


class MoELM(_ExpertLM):
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
    (the block's own property, no option).

    ``qk_norm``: every query and key head is RMS-normed over its own
    dimensions before the rotary (two ``(head_dim,)`` gains a layer), as
    Qwen3's and SDAR's attention is.

    ``block_length`` makes the block a BLOCK-DIFFUSION model in training
    (Arriola et al., arXiv:2503.09573; SDAR, arXiv:2510.06303) — its
    objective is then ``diffusion_loss()``, not ``lm_loss()``.  A forward
    noises its ``(B, L)`` clean ids (``F.contrib.block_diffusion_noise``:
    one mask rate a block of ``block_length`` positions, ``noise_eps``
    its floor, ``mask_token_id`` in a masked position's place; the draws
    come from the step's key), runs the ``2 L`` rows ``[noised ; clean]``
    through every layer at positions ``[0 .. L - 1 ; 0 .. L - 1]`` under
    the three-part block mask, and returns the NOISED half's
    final-normed states ``(B, L, U)`` with the positions' weights ``m /
    p`` ``(B, L)`` beside them.  A caller that must reproduce a step
    hands the draws in with the batch: an int32 input ``(B, 3, L)`` is
    ``[ids ; the positions' draws ; the blocks' draws]``, integers in
    ``[0, 2**24)``, a block reading the entry at its first position.

    ``cca = (K0, K1)`` makes every layer's attention COMPRESSED
    CONVOLUTIONAL ATTENTION (:func:`compressed_conv_attention`: the
    latent queries and keys mixed by a causal convolution of ``K0`` taps
    over time and one of ``K1`` taps across a head's channels, the q-k
    mean added, half the value heads reading the token before, every
    head L2-normed under a temperature of its key/value head; ZAYA1).
    ``rotary_dim``: only a head's first ``rotary_dim`` dimensions turn.
    ``router_hidden`` with ``router_layers``: the router is an MLP — a
    bias-free projection to ``router_hidden``, then ``router_layers``
    layers (GELU between them) to the ``num_routed`` logits
    (``l{i}_router_weight``, ``l{i}_router0_weight`` ...).
    ``tie_embeddings``: the head IS the embedding table (no
    ``head_weight``): the loss reads ``embed_weight``, and its gradient
    is the sum of the look-up's and the head's."""

    def __init__(self, vocab_size, units=128, expert_width=64,
                 layer_types=(SLIDING, FULL), num_heads=4, num_kv_heads=2,
                 head_dim=None, num_routed=8, held=None, top_k=2,
                 window=32, rope=None, norm_topk=True, epsilon=1e-6,
                 qk_norm=False, block_length=None, mask_token_id=None,
                 noise_eps=1e-3, cca=None, rotary_dim=None,
                 router_hidden=None, router_layers=0, tie_embeddings=False,
                 **kwargs):
        super().__init__(**kwargs)
        from ...ops.contrib import yarn_inv_freq
        head_dim = head_dim or units // num_heads
        if num_heads % num_kv_heads or head_dim % 2:
            raise ValueError("num_heads (%d) must be a multiple of "
                             "num_kv_heads (%d), heads of even size (%d)"
                             % (num_heads, num_kv_heads, head_dim))
        rotary = head_dim if rotary_dim is None else int(rotary_dim)
        if rotary % 2 or not 0 < rotary <= head_dim:
            raise ValueError("rotary_dim (%d) must be even and at most the "
                             "head size (%d)" % (rotary, head_dim))
        if cca is not None and (len(cca) != 2 or min(cca) < 1 or qk_norm
                                or block_length is not None
                                or num_kv_heads % 2 or SLIDING in layer_types):
            raise ValueError(
                "compressed convolutional attention takes two kernel "
                "lengths of at least 1 and an even number of key/value "
                "heads, full attention layers only, and no QK-norm or block "
                "diffusion beside it; got cca=%r, %d key/value heads"
                % (cca, num_kv_heads))
        if (router_hidden is None) != (not router_layers):
            raise ValueError("a router MLP takes router_hidden and at least "
                             "one layer; got %r and %r"
                             % (router_hidden, router_layers))
        if any(k not in (SLIDING, FULL) for k in layer_types):
            raise ValueError("layer_types are %r or %r, got %r"
                             % (SLIDING, FULL, list(layer_types)))
        held = self._held_experts(num_routed, held, top_k)
        if block_length is not None and (
                int(block_length) < 1 or mask_token_id is None
                or not 0 <= int(mask_token_id) < vocab_size):
            raise ValueError(
                "a block-diffusion model needs a block length of at least "
                "1 and a mask_token_id among its %d vocabulary rows, got "
                "%r and %r" % (vocab_size, block_length, mask_token_id))
        tables = {}
        for kind in set(layer_types):
            r = dict((rope or {}).get(kind) or {})
            base = float(r.get("rope_theta", 10000.0))
            if r.get("rope_type", "default") == "yarn":
                tables[kind] = (base, tuple(yarn_inv_freq(
                    rotary, base, float(r["factor"]),
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
            eps=epsilon, qk_norm=bool(qk_norm),
            block_length=None if block_length is None else int(block_length),
            cca=cca is not None,
            rotary_dim=None if rotary == head_dim else rotary,
            router_layers=int(router_layers))
        self._cca = (0, 0) if cca is None else (int(cca[0]), int(cca[1]))
        self._rotary = rotary
        self._tied = bool(tie_embeddings)
        self._noise = None if block_length is None else dict(
            block_length=int(block_length), mask_id=int(mask_token_id),
            eps=float(noise_eps))
        self._num_routed = num_routed
        sizes = dict(
            units=units, head=head_dim, queries=num_heads * head_dim,
            keys=num_kv_heads * head_dim, kv_heads=num_kv_heads,
            qk_heads=num_heads + num_kv_heads,
            qk=(num_heads + num_kv_heads) * head_dim,
            cca_time=self._cca[0], cca_mix=self._cca[1],
            router=router_hidden or num_routed, routed=num_routed,
            held=held[1], expert=expert_width)
        layer = _layer_leaves(_gqa_leaves(qk_norm, cca is not None),
                              _routed_leaves(router_layers))
        shapes = [("embed_weight", (vocab_size, units))]
        for i in range(len(layer_types)):
            shapes += [("l%d_%s" % (i, n), shape) for n, shape in layer]
        shapes.append(("norm_gamma", (units,)))
        if not self._tied:
            shapes.append(("head_weight", (vocab_size, units)))
        self._make_params(shapes, sizes)

    def _forward(self, params, tokens):
        return moe_lm_forward(params, tokens, **self._config)

    def _export_gauges(self):
        from ... import telemetry
        super()._export_gauges()
        c = self._config
        layers = telemetry.gauge(
            "mxnet_attn_layers", "layers of the newest MoELM by kind of "
            "attention (sliding_attention / full_attention)")
        for kind in (SLIDING, FULL):
            layers.labels(kind=kind).set(c["layer_types"].count(kind))
        self._export_window(c["window"])
        telemetry.gauge(
            "mxnet_diffusion_block_length", "positions of a block of the "
            "newest MoELM trained by diffusion over blocks (0: a causal "
            "model)").set(c["block_length"] or 0)
        taps = telemetry.gauge(
            "mxnet_cca_kernel", "taps of the causal convolutions of the "
            "newest MoELM's compressed convolutional attention (conv=time: "
            "over time, channel by channel; conv=mix: across a head's "
            "channels); 0 without it")
        for conv, k in zip(("time", "mix"), self._cca):
            taps.labels(conv=conv).set(k)
        telemetry.gauge("mxnet_rotary_dims", "dimensions of a head the "
                        "newest MoELM turns by rotary position").set(
                            self._rotary)

    def _noised_rows(self, F, tokens):
        """``(rows (B, 2 L), weight (B, L))`` of a block-diffusion
        forward: the noised copy of every sequence, then the clean one."""
        import jax

        from ... import telemetry
        from ...imperative import invoke_fn
        from ...telemetry import phases
        length = self._noise["block_length"]
        with jax.named_scope(phases.NOISE_SCOPE):
            draws = []
            if tokens.ndim == 3:
                tokens, *draws = invoke_fn(
                    lambda t: (t[:, 0], t[:, 1], t[:, 2, ::length]),
                    [tokens])
            noised, weight = F.contrib.block_diffusion_noise(
                tokens, *draws, **self._noise)
            rows = F.concat(noised, tokens.astype("int32"), dim=1)
        telemetry.gauge(
            "mxnet_diffusion_stack_rows", "rows a step of the newest "
            "MoELM trained by diffusion over blocks sends through every "
            "layer: a noised and a clean copy of every position").set(
                rows.shape[0] * rows.shape[1])
        return rows, weight

    def hybrid_forward(self, F, tokens, **params):
        if self._noise is None:
            return super().hybrid_forward(F, tokens, **params)
        rows, weight = self._noised_rows(F, tokens)
        return super().hybrid_forward(F, rows, **params), weight

    def diffusion_loss(self, **kwargs):
        """The training objective of a block-diffusion model
        (``block_length``) over this block's outputs, sharing its head:
        ``loss(*net(tokens), tokens)`` — per sequence, the mean over
        positions of ``weight_i`` times the cross-entropy of the CLEAN
        token at the noised copy's position ``i``, no shift
        (``gluon.loss.BlockDiffusionCELoss``)."""
        from ..loss import BlockDiffusionCELoss
        return BlockDiffusionCELoss(params=self.params, **kwargs)


class LatentMoELM(_ExpertLM):
    """Decoder-only LM of latent-attention layers (MLA: queries, keys
    and values through low-rank latents, one rotary key shared by all
    heads, scores over ``nope_dim + rope_dim`` dimensions and values of
    ``v_dim`` — the value head size differs from the key's) whose
    feed-forward part is, layer by layer (``mlp_layer_types``), "dense"
    — one SwiGLU of ``dense_width`` — or "sparse": a top-``top_k`` routed
    layer of SwiGLU experts of ``expert_width`` of which this block HOLDS
    ``held = (first, count)`` of the ``num_routed`` the router runs over
    (``parallel.moe.routed_experts``; sigmoid or softmax ``scoring``, a
    held selection bias ``router_bias`` — ``grad_req`` null: the step
    does not update it — under ``selection_bias``, weights times
    ``route_scale``), beside ``shared_experts`` shared ones (one SwiGLU
    of ``shared_experts x expert_width``) that every token takes.  With
    ``mtp_depth`` multi-token-prediction modules after the trunk, each
    one more sparse layer between a projection of ``[embedding ;
    state]`` and a final norm of its own, sharing embedding and head
    (DeepSeek-V3 / JoyAI-LLM-Flash).  Pre-norm, RMSNorm, no biases,
    untied head.

    Input ``(B, T)`` token ids; output the final-normed states ``(B, T,
    U)`` and, with prediction modules, theirs ``(B, mtp_depth, T, U)``
    beside them (:func:`latent_moe_lm_forward`, which this block only
    wraps).  The head is a parameter of this block (``head_weight``) but
    its product is the loss's: ``lm_loss()`` sends every term through it
    fused with its cross-entropy.  Expert weights are stacked leaves,
    ``l{i}_gate_weight (count, F, U)``.  Each layer and module is
    rematerialised in the backward pass (the block's own property, no
    option)."""

    def __init__(self, vocab_size, units=128, dense_width=256,
                 expert_width=64, mlp_layer_types=(DENSE, SPARSE),
                 num_heads=4, q_rank=48, kv_rank=32, nope_dim=16,
                 rope_dim=8, v_dim=16, num_routed=8, held=None, top_k=2,
                 shared_experts=1, scoring="sigmoid", selection_bias=True,
                 route_scale=1.0, norm_topk=True, rope_base=10000.0,
                 rope_interleaved=True, mtp_depth=0, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        if rope_dim % 2:
            raise ValueError("rope_dim (%d) must be even" % rope_dim)
        if any(k not in (DENSE, SPARSE) for k in mlp_layer_types):
            raise ValueError("mlp_layer_types are %r or %r, got %r"
                             % (DENSE, SPARSE, list(mlp_layer_types)))
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError("scoring is sigmoid or softmax, got %r"
                             % (scoring,))
        held = self._held_experts(num_routed, held, top_k)
        self._config = dict(
            mlp_layer_types=tuple(mlp_layer_types), num_heads=num_heads,
            nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim, top_k=top_k,
            held=held, scoring=scoring, route_scale=float(route_scale),
            norm_topk=bool(norm_topk), shared_expert=bool(shared_experts),
            rope_base=float(rope_base),
            rope_interleaved=bool(rope_interleaved),
            mtp_depth=int(mtp_depth), eps=epsilon)
        self._num_routed = num_routed
        sizes = dict(
            units=units, q_rank=q_rank, q_b=num_heads * (nope_dim + rope_dim),
            kv_rank=kv_rank, kv_a=kv_rank + rope_dim,
            kv_b=num_heads * (nope_dim + v_dim), values=num_heads * v_dim,
            dense=dense_width, router=num_routed, routed=num_routed,
            held=held[1], expert=expert_width,
            shared=shared_experts * expert_width, joined=2 * units)
        layer = {DENSE: _layer_leaves(_LATENT_LEAVES, _DENSE_LEAVES),
                 SPARSE: _layer_leaves(_LATENT_LEAVES, _routed_leaves(
                     bias=selection_bias, shared=shared_experts))}
        prefixed = lambda pre, leaves: [(pre + n, s) for n, s in leaves]
        shapes = [("embed_weight", (vocab_size, units))]
        for i, kind in enumerate(mlp_layer_types):
            shapes += prefixed("l%d_" % i, layer[kind])
        shapes.append(("norm_gamma", (units,)))
        for k in range(mtp_depth):
            shapes += prefixed("mtp%d_" % k, _MTP_FRONT_LEAVES + layer[SPARSE]
                               + (("norm_gamma", ("units",)),))
        shapes.append(("head_weight", (vocab_size, units)))
        self._make_params(shapes, sizes)

    def _forward(self, params, tokens):
        return latent_moe_lm_forward(params, tokens, **self._config)

    def _export_gauges(self):
        from ... import telemetry
        super()._export_gauges()
        c = self._config
        scoring = telemetry.gauge(
            "mxnet_moe_scoring", "1 at the scoring (sigmoid / softmax) the "
            "newest LatentMoELM's routers choose by, 0 at the other")
        for kind in ("sigmoid", "softmax"):
            scoring.labels(scoring=kind).set(int(kind == c["scoring"]))
        layers = telemetry.gauge(
            "mxnet_mlp_layers", "layers of the newest LatentMoELM's trunk "
            "by kind of feed-forward part (dense / sparse)")
        for kind in (DENSE, SPARSE):
            layers.labels(kind=kind).set(c["mlp_layer_types"].count(kind))
        telemetry.gauge("mxnet_mtp_depth", "multi-token-prediction modules "
                        "of the newest LatentMoELM").set(c["mtp_depth"])

    def lm_loss(self, mtp_weight=0.3, **kwargs):
        """The training objective over this block's outputs, sharing its
        head: ``loss(*net(tokens), labels)`` — per sequence the mean
        next-token cross-entropy, plus ``mtp_weight`` times the mean of
        the prediction modules' (each over the positions it has)."""
        from ..loss import MultiTokenCELoss
        if not self._config["mtp_depth"]:
            return super().lm_loss(**kwargs)
        return MultiTokenCELoss(mtp_weight=mtp_weight, params=self.params,
                                **kwargs)


MAMBA, GMU, CROSS = "mamba", "gmu", "cross_attention"


class SambaYLM(_TrunkLM):
    """A decoder-hybrid-decoder LM (SambaY; Ren et al., arXiv:2507.06607,
    as Phi-4-mini-flash runs it): every layer's mixer is, by
    ``layer_types``, a Mamba mixer (``mamba``, :func:`mamba_mixer`: a
    state of ``state_size`` a channel over ``expand x units`` channels, a
    causal convolution of ``conv_kernel`` taps),
    differential attention over a ``window`` of keys (``sliding_attention``)
    or over every key before (``full_attention``,
    :func:`differential_attention`), a gated memory unit (``gmu``,
    :func:`gated_memory`) or cross-attention (``cross_attention``); every
    feed-forward part one SwiGLU of ``hidden_size`` (:func:`dense_ffn`).
    A ``gmu`` layer reads the scan output of the last ``mamba`` layer
    before it, a ``cross_attention`` layer the keys and values of the last
    ``full_attention`` layer before it: those layers write them
    (:func:`_trunk`) and their gradients come back from every reader.
    Pre-LayerNorm with biases (``epsilon``), a final LayerNorm, no
    positional encoding, biased attention projections of ``units //
    num_heads`` a head, steps of rank ``ceil(units / 16)``, no experts;
    the head is the embedding table.  ``first_layer``: the published
    index of the first layer (``lambda_init`` of a differential layer
    reads its own).

    Input ``(B, T)`` token ids; output the final-normed states ``(B, T,
    U)``, whose head ``lm_loss()`` fuses with the cross-entropy.  Each
    layer is rematerialised in the backward pass (the block's own
    property, no option)."""

    _tied = True

    def __init__(self, vocab_size, units=128, hidden_size=512,
                 layer_types=(MAMBA, SLIDING, MAMBA, FULL, GMU, CROSS),
                 num_heads=4, num_kv_heads=2, window=32, state_size=16,
                 conv_kernel=4, expand=2, first_layer=0, epsilon=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        import functools
        import math
        kinds = (MAMBA, SLIDING, FULL, GMU, CROSS)
        if any(k not in kinds for k in layer_types):
            raise ValueError("layer_types are among %r, got %r"
                             % (kinds, list(layer_types)))
        head_dim, dt_rank = units // num_heads, -(-units // 16)
        if num_kv_heads % 2 or num_heads != 2 * num_kv_heads:
            raise ValueError("differential attention pairs its heads: it "
                             "takes an even number of key/value heads and "
                             "twice as many query heads, got %d and %d"
                             % (num_heads, num_kv_heads))
        inner = expand * units
        writes, reads = set(), []
        for i, kind in enumerate(layer_types):
            source = {GMU: MAMBA, CROSS: FULL}.get(kind)
            if source is None:
                reads.append(())
                continue
            j = max((j for j in range(i) if layer_types[j] == source),
                    default=None)
            if j is None:
                raise ValueError("a %s layer reads a %s layer before it; "
                                 "layer %d has none" % (kind, source, i))
            writes.add(j)
            reads.append(("memory",) if kind == GMU
                         else ("shared_k", "shared_v"))
        self._kinds = tuple(layer_types)
        self._window = int(window)
        self._state = int(state_size)
        lam = lambda i: 0.8 - 0.6 * math.exp(-0.3 * (first_layer + i))
        heads = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                     eps=epsilon, kept=True)
        mixers = {MAMBA: lambda i: functools.partial(
                      mamba_mixer, memory=i in writes),
                  SLIDING: lambda i: functools.partial(
                      differential_attention, lambda_init=lam(i),
                      window=self._window, **heads),
                  FULL: lambda i: functools.partial(
                      differential_attention, lambda_init=lam(i),
                      shares=i in writes, **heads),
                  GMU: lambda i: gated_memory,
                  CROSS: lambda i: functools.partial(
                      differential_attention, lambda_init=lam(i),
                      cross=True, **heads)}
        leaves = {MAMBA: _MAMBA_LEAVES, SLIDING: _differential_leaves(),
                  FULL: _differential_leaves(), GMU: _GMU_LEAVES,
                  CROSS: _differential_leaves(cross=True)}
        sizes = dict(
            units=units, inner=inner, inner2=2 * inner, conv=conv_kernel,
            dbc=dt_rank + 2 * state_size, dt_rank=dt_rank,
            state=state_size, head=head_dim, pair=2 * head_dim,
            queries=num_heads * head_dim,
            qkv=(num_heads + 2 * num_kv_heads) * head_dim,
            dense=hidden_size)
        shapes, self._layers = [("embed_weight", (vocab_size, units))], []
        for i, kind in enumerate(layer_types):
            layer = _layer_leaves(leaves[kind], _DENSE_LEAVES,
                                  layer_norm=True)
            shapes += [("l%d_%s" % (i, n), shape) for n, shape in layer]
            self._layers.append(("l%d_" % i, [n for n, _ in layer],
                                 mixers[kind](i), dense_ffn, reads[i]))
        shapes += [("norm_gamma", ("units",)), ("norm_beta", ("units",))]
        self._eps = epsilon
        self._make_params(shapes, sizes)

    def _forward(self, params, tokens):
        return _trunk(params, tokens, self._layers, eps=self._eps,
                      layer_norm=True)

    def _export_gauges(self):
        from ... import telemetry
        layers = telemetry.gauge(
            "mxnet_hybrid_layers", "layers of the newest SambaYLM by kind "
            "of mixer (mamba / sliding_attention / full_attention / gmu / "
            "cross_attention)")
        for kind in (MAMBA, SLIDING, FULL, GMU, CROSS):
            layers.labels(kind=kind).set(self._kinds.count(kind))
        self._export_window(self._window)
        telemetry.gauge("mxnet_ssm_state_size", "state values a channel "
                        "of the newest SambaYLM's Mamba mixers carries").set(
                            self._state)
