"""Operations and bytes the training step of a sparse-expert language
model of COMPRESSED CONVOLUTIONAL ATTENTION needs, from shapes alone —
for ONE CHIP'S SHARE of the deployment the configuration states:
``num_experts`` held experts a layer under a router over
``published.num_experts``, ``vocab_size`` held rows of the table that is
both the embedding and the head, ``num_hidden_layers`` layers.

- ``parameters`` counts what the chip holds and the optimizer sweeps
  (``sweep_bytes``); ``published_parameters`` the whole published model.
- ``step_flops``, ``attention_flops`` and ``expert_flops`` count the work
  the mathematics needs, whatever implements it.

FLOPs: 2 per multiply-add of every matrix product of the forward pass
(the latent q and k, v, out, the convolution across a head's channels,
the router's down-projection and MLP, the experts' three products over
the rows ROUTED to the held experts in expectation under a symmetric
router, the head over the held rows) and of attention (scores and values
of the 8 query heads over ``T^2 / 2`` pairs, as the other cells count a
causal layer); the backward pass is twice the forward.  The embedding
look-up, the convolution over time (one multiply-add a channel and tap),
the q-k mean, the norms, rotary, softmax, top-k, the rows' sort, gather
and combine, the loss, the optimizer, PADDING of any kind and ANYTHING
RECOMPUTED (each layer's forward is run again in the backward pass; flash
attention's backward recomputes the scores) are not counted.
"""


def _sizes(config):
    d = int(config["head_dim"])
    return {"u": int(config["hidden_size"]), "d": d,
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "hd": int(config["num_attention_heads"]) * d,
            "kvd": int(config["num_key_value_heads"]) * d,
            "k0": int(config["cca_time0"]), "k1": int(config["cca_time1"]),
            "f": int(config["moe_intermediate_size"]),
            "held": int(config["num_experts"]),
            "routed": int(config["published"]["num_experts"]),
            "top_k": int(config["num_experts_per_tok"]),
            "hr": int(config["router_hidden_size"]),
            "rl": int(config["assumed"]["router_mlp_layers"]),
            "v": int(config["vocab_size"]),
            "n": int(config["num_hidden_layers"]),
            "b": int(config["batch_size"]), "t": int(config["seq_len"])}


def attention_parameters(config):
    """q and out ``u x heads*d``, k and v ``u x kv_heads*d``."""
    s = _sizes(config)
    return 2 * s["u"] * s["hd"] + 2 * s["u"] * s["kvd"]


def cca_mix_macs_per_token(config):
    """The convolution across each head's channels: ``cca_time1`` taps
    of ``d x d`` for each of the query and key heads."""
    s = _sizes(config)
    return (s["heads"] + s["kv_heads"]) * s["k1"] * s["d"] * s["d"]


def cca_parameters(config):
    """The convolution over time (a weight a channel and tap), the one
    across a head's channels, a temperature a key/value head."""
    s = _sizes(config)
    return s["k0"] * (s["hd"] + s["kvd"]) + cca_mix_macs_per_token(config) \
        + s["kv_heads"]


def router_parameters(config):
    """The down-projection ``u -> router_hidden_size`` and the MLP's
    layers, the last to every published expert."""
    s = _sizes(config)
    return s["u"] * s["hr"] + (s["rl"] - 1) * s["hr"] * s["hr"] \
        + s["routed"] * s["hr"]


def expert_parameters(config):
    """One expert: gate, up, down."""
    s = _sizes(config)
    return 3 * s["u"] * s["f"]


def layer_parameters(config, experts=None):
    """Attention with its convolutions, two gains, the router, and
    ``experts`` experts (default: the held ones)."""
    s = _sizes(config)
    experts = s["held"] if experts is None else experts
    return attention_parameters(config) + cca_parameters(config) \
        + 2 * s["u"] + router_parameters(config) \
        + experts * expert_parameters(config)


def parameters(config):
    """Every leaf the chip holds: the tied table over the held rows
    (once), the layers with their held experts, the final gain."""
    s = _sizes(config)
    return s["v"] * s["u"] + s["n"] * layer_parameters(config) + s["u"]


def published_parameters(config):
    """The whole published model: every layer, expert and vocabulary
    row."""
    s, pub = _sizes(config), config["published"]
    return int(pub["vocab_size"]) * s["u"] \
        + int(pub["num_hidden_layers"]) * layer_parameters(
            config, experts=s["routed"]) + s["u"]


def rows_per_step(config):
    """Tokens per step."""
    s = _sizes(config)
    return s["b"] * s["t"]


def expected_expert_rows(config):
    """(token, slot) assignments a step sends to the held experts of ONE
    layer, in expectation under a symmetric router."""
    s = _sizes(config)
    return rows_per_step(config) * s["top_k"] * s["held"] / s["routed"]


def attention_macs_forward(config):
    """Scores and values over ``T^2 / 2`` pairs, every query head, whole
    batch, every layer."""
    s = _sizes(config)
    return 2 * s["b"] * s["n"] * (s["t"] * s["t"] // 2) * s["hd"]


def expert_macs_forward(config):
    """The three expert products over the rows routed to the held
    experts, every layer."""
    s = _sizes(config)
    return s["n"] * expected_expert_rows(config) * expert_parameters(config)


def matmul_macs_per_token(config):
    """Attention's projections, the convolution across the heads'
    channels and the router of every layer, the head over the held rows
    (the experts are counted by rows, not tokens)."""
    s = _sizes(config)
    router = router_parameters(config)
    return s["n"] * (attention_parameters(config)
                     + cca_mix_macs_per_token(config) + router) \
        + s["v"] * s["u"]


def step_flops(config):
    return 3 * 2 * (rows_per_step(config) * matmul_macs_per_token(config)
                    + expert_macs_forward(config)
                    + attention_macs_forward(config))


def attention_flops(config):
    """FLOPs the flash kernels' work needs per step (forward and
    backward, every layer once)."""
    return 3 * 2 * attention_macs_forward(config)


def expert_flops(config):
    """FLOPs the experts' products need per step (forward and backward):
    3 x 2 x 3 x hidden x expert width x rows x layers."""
    return 3 * 2 * expert_macs_forward(config)


def sweep_bytes(config, chips):
    passes = {"sgd": 5, "adam": 7}[config["optimizer"]["name"]]
    return passes * 4 * parameters(config) / chips
