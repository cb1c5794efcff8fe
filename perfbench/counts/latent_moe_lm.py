"""Operations and bytes a latent-attention sparse-expert language model's
training step needs, from shapes alone — for ONE CHIP'S SHARE of the
deployment the configuration states: ``n_routed_experts`` held experts a
sparse layer under a router over ``published.n_routed_experts``,
``vocab_size`` held rows of the vocabulary, ``num_hidden_layers`` trunk
layers (the first ``first_k_dense_replace`` dense) and
``num_nextn_predict_layers`` prediction modules, each one more sparse
layer.

- ``parameters`` counts what the chip holds and the optimizer sweeps
  (``sweep_bytes``); ``published_parameters`` the whole published model
  (without its prediction module, as the model's name counts it;
  ``with_mtp=True`` with).  The selection bias (256 held values a sparse
  layer) is no parameter: it is not trained.
- ``step_flops``, ``attention_flops`` and ``expert_flops`` count the work
  the mathematics needs, whatever implements it.

FLOPs: 2 per multiply-add of every matrix product of the forward pass
(both latents' down- and up-projections, the output projection, the
dense feed-forward part, the router, the shared expert, the routed
experts' three products over the rows ROUTED to the held experts in
expectation under a symmetric router, a module's entry projection, the
head over the held rows once a term) and of attention (scores over
``qk_nope_head_dim + qk_rope_head_dim`` dimensions and values over
``v_head_dim``, ``T^2 / 2`` visible pairs a head as the other cells
count a causal layer); the backward pass is twice the forward.  A
prediction module is counted over all ``T`` positions (one of them has
no target).  The embedding look-up, RMSNorm, rotary, the sigmoid, top-k,
the rows' sort, gather and combine, the loss, the optimizer, PADDING of
any kind and ANYTHING RECOMPUTED are not counted.
"""


def _sizes(config):
    return {"u": int(config["hidden_size"]),
            "h": int(config["num_attention_heads"]),
            "qr": int(config["q_lora_rank"]),
            "kvr": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "vd": int(config["v_head_dim"]),
            "fd": int(config["intermediate_size"]),
            "f": int(config["moe_intermediate_size"]),
            "shared": int(config["n_shared_experts"]),
            "held": int(config["n_routed_experts"]),
            "routed": int(config["published"]["n_routed_experts"]),
            "top_k": int(config["num_experts_per_tok"]),
            "v": int(config["vocab_size"]),
            "n": int(config["num_hidden_layers"]),
            "dense": int(config["first_k_dense_replace"]),
            "mtp": int(config["num_nextn_predict_layers"]),
            "b": int(config["batch_size"]), "t": int(config["seq_len"])}


def attention_matrices(config):
    """A layer's latent attention without its two latent gains: q_a ``u
    x q_rank``, q_b ``q_rank x heads (nope + rope)``, kv_a ``u x (kv_rank
    + rope)``, kv_b ``kv_rank x heads (nope + v)``, out ``heads v x u``."""
    s = _sizes(config)
    return s["u"] * s["qr"] + s["qr"] * s["h"] * (s["nope"] + s["rope"]) \
        + s["u"] * (s["kvr"] + s["rope"]) \
        + s["kvr"] * s["h"] * (s["nope"] + s["vd"]) + s["h"] * s["vd"] * s["u"]


def attention_parameters(config):
    """The five matrices and the two latents' gains."""
    s = _sizes(config)
    return attention_matrices(config) + s["qr"] + s["kvr"]


def expert_parameters(config):
    """One routed expert: gate, up, down."""
    s = _sizes(config)
    return 3 * s["u"] * s["f"]


def shared_parameters(config):
    s = _sizes(config)
    return 3 * s["u"] * s["f"] * s["shared"]


def dense_layer_parameters(config):
    """Attention, two gains, one SwiGLU of the dense width."""
    s = _sizes(config)
    return attention_parameters(config) + 2 * s["u"] + 3 * s["u"] * s["fd"]


def sparse_layer_parameters(config, experts=None):
    """Attention, two gains, the router over every published expert, the
    shared expert and ``experts`` routed ones (default: the held)."""
    s = _sizes(config)
    experts = s["held"] if experts is None else experts
    return attention_parameters(config) + 2 * s["u"] \
        + s["routed"] * s["u"] + shared_parameters(config) \
        + experts * expert_parameters(config)


def mtp_parameters(config, experts=None):
    """One prediction module: the entry projection ``2u x u``, three
    gains (embedding, state, its own final norm) and a sparse layer."""
    s = _sizes(config)
    return 2 * s["u"] * s["u"] + 3 * s["u"] \
        + sparse_layer_parameters(config, experts)


def parameters(config):
    """Every trained leaf the chip holds: embedding and head over the
    held rows, the trunk with its held experts, the final gain, the
    prediction modules."""
    s = _sizes(config)
    return 2 * s["v"] * s["u"] + s["dense"] * dense_layer_parameters(config) \
        + (s["n"] - s["dense"]) * sparse_layer_parameters(config) + s["u"] \
        + s["mtp"] * mtp_parameters(config)


def published_parameters(config, with_mtp=False):
    """The whole published model: every layer, expert and vocabulary
    row; with ``with_mtp`` its prediction modules too."""
    s, pub = _sizes(config), config["published"]
    n = int(pub["num_hidden_layers"])
    total = 2 * int(pub["vocab_size"]) * s["u"] \
        + s["dense"] * dense_layer_parameters(config) \
        + (n - s["dense"]) * sparse_layer_parameters(config, s["routed"]) \
        + s["u"]
    return total + (s["mtp"] * mtp_parameters(config, s["routed"])
                    if with_mtp else 0)


def rows_per_step(config):
    """Tokens per step."""
    s = _sizes(config)
    return s["b"] * s["t"]


def attention_layers(config):
    """Layers that run attention: the trunk's and one a module."""
    s = _sizes(config)
    return s["n"] + s["mtp"]


def sparse_layers(config):
    s = _sizes(config)
    return s["n"] - s["dense"] + s["mtp"]


def expected_expert_rows(config):
    """(token, slot) assignments a step sends to the held experts of ONE
    sparse layer, in expectation under a symmetric router."""
    s = _sizes(config)
    return rows_per_step(config) * s["top_k"] * s["held"] / s["routed"]


def attention_macs_forward(config):
    """Scores over ``nope + rope`` dimensions and values over ``v``, the
    causal half of the pairs, every head, whole batch, every layer."""
    s = _sizes(config)
    pairs = s["t"] * s["t"] // 2    # as the other cells halve a causal layer
    return attention_layers(config) * s["b"] * pairs * s["h"] \
        * (s["nope"] + s["rope"] + s["vd"])


def expert_macs_forward(config):
    """The three routed products over the rows routed to the held
    experts, every sparse layer."""
    return sparse_layers(config) * expected_expert_rows(config) \
        * expert_parameters(config)


def matmul_macs_per_token(config):
    """What every token takes: latent attention's five matrices a layer,
    the dense feed-forward part, router and shared expert of every sparse
    layer, a module's entry projection, the head over the held rows once
    a term (the routed experts are counted by rows, not tokens)."""
    s = _sizes(config)
    return attention_layers(config) * attention_matrices(config) \
        + s["dense"] * 3 * s["u"] * s["fd"] \
        + sparse_layers(config) * (s["routed"] * s["u"]
                                   + shared_parameters(config)) \
        + s["mtp"] * 2 * s["u"] * s["u"] \
        + (1 + s["mtp"]) * s["v"] * s["u"]


def step_flops(config):
    return 3 * 2 * (rows_per_step(config) * matmul_macs_per_token(config)
                    + expert_macs_forward(config)
                    + attention_macs_forward(config))


def attention_flops(config):
    """FLOPs the flash kernels' work needs per step (forward and
    backward, every layer once): 192 dimensions for the scores, 128 for
    the values at the published sizes."""
    return 3 * 2 * attention_macs_forward(config)


def expert_flops(config):
    """FLOPs the routed experts' products need per step (forward and
    backward): 3 x 2 x 3 x hidden x expert width x rows x sparse layers."""
    return 3 * 2 * expert_macs_forward(config)


def sweep_bytes(config, chips):
    passes = {"sgd": 5, "adam": 7}[config["optimizer"]["name"]]
    return passes * 4 * parameters(config) / chips
