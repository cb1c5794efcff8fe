"""Operations and bytes a sparse-expert language model's training step
needs, from shapes alone — for ONE CHIP'S SHARE of the deployment the
configuration states: ``num_experts`` held experts a layer under a
router over ``published.num_experts``, ``vocab_size`` held rows of the
vocabulary, ``num_hidden_layers`` layers whose kinds are the first
entries of ``layer_types``.

- ``parameters`` counts what the chip holds and the optimizer sweeps
  (``sweep_bytes``); ``published_parameters`` the whole published model.
- ``step_flops``, ``attention_flops`` and ``expert_flops`` count the work
  the mathematics needs, whatever implements it.

FLOPs: 2 per multiply-add of every matrix product of the forward pass
(q, k, v, out, the router, the experts' three products over the rows
ROUTED to the held experts in expectation under a symmetric router, the
head over the held rows) and of attention (scores and values over the
VISIBLE query-key pairs: ``W (W + 1) / 2 + (T - W) W`` on a
sliding-window layer, ``T^2 / 2`` as the other cells count a causal
layer on a full one); the backward pass is twice the forward.  The
embedding look-up, RMSNorm, rotary, softmax, top-k, the rows' sort,
gather and combine, the loss, the optimizer, PADDING of any kind and
ANYTHING RECOMPUTED (each layer's forward is run again in the backward
pass; flash attention's backward recomputes the scores) are not counted.
"""


def _sizes(config):
    return {"u": int(config["hidden_size"]),
            "hd": int(config["num_attention_heads"])
            * int(config["head_dim"]),
            "kvd": int(config["num_key_value_heads"])
            * int(config["head_dim"]),
            "f": int(config["moe_intermediate_size"]),
            "held": int(config["num_experts"]),
            "routed": int(config["published"]["num_experts"]),
            "top_k": int(config["num_experts_per_tok"]),
            "v": int(config["vocab_size"]),
            "n": int(config["num_hidden_layers"]),
            "w": int(config["sliding_window"]),
            "b": int(config["batch_size"]), "t": int(config["seq_len"])}


def attention_parameters(config):
    """q and out ``u x heads*d``, k and v ``u x kv_heads*d``."""
    s = _sizes(config)
    return 2 * s["u"] * s["hd"] + 2 * s["u"] * s["kvd"]


def expert_parameters(config):
    """One expert: gate, up, down."""
    s = _sizes(config)
    return 3 * s["u"] * s["f"]


def layer_parameters(config, experts=None):
    """Attention, two gains, the router over every published expert, and
    ``experts`` experts (default: the held ones)."""
    s = _sizes(config)
    experts = s["held"] if experts is None else experts
    return attention_parameters(config) + 2 * s["u"] \
        + s["routed"] * s["u"] + experts * expert_parameters(config)


def parameters(config):
    """Every leaf the chip holds: embedding and head over the held rows,
    the layers with their held experts, the final gain."""
    s = _sizes(config)
    return 2 * s["v"] * s["u"] + s["n"] * layer_parameters(config) + s["u"]


def published_parameters(config):
    """The whole published model: every layer, expert and vocabulary
    row."""
    s, pub = _sizes(config), config["published"]
    return 2 * int(pub["vocab_size"]) * s["u"] \
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


def attention_pairs(config, kind):
    """Visible (query, key) pairs of one sequence in a layer of ``kind``."""
    s = _sizes(config)
    t, w = s["t"], min(s["w"], s["t"])
    if kind == "sliding_attention":
        return w * (w + 1) // 2 + (t - w) * w
    if kind == "full_attention":
        return t * t // 2       # as the other cells halve a causal layer
    raise ValueError("no rule for layer type %r" % kind)


def layer_kinds(config):
    return list(config["layer_types"])[:int(config["num_hidden_layers"])]


def attention_macs_forward(config):
    """Scores and values over the visible pairs, every query head, whole
    batch, every layer."""
    s = _sizes(config)
    pairs = sum(attention_pairs(config, k) for k in layer_kinds(config))
    return 2 * s["b"] * pairs * s["hd"]


def expert_macs_forward(config):
    """The three expert products over the rows routed to the held
    experts, every layer."""
    s = _sizes(config)
    return s["n"] * expected_expert_rows(config) * expert_parameters(config)


def matmul_macs_per_token(config):
    """Attention's projections and the router of every layer, the head
    over the held rows (the experts are counted by rows, not tokens)."""
    s = _sizes(config)
    return s["n"] * (attention_parameters(config) + s["routed"] * s["u"]) \
        + s["v"] * s["u"]


def step_flops(config):
    return 3 * 2 * (rows_per_step(config) * matmul_macs_per_token(config)
                    + expert_macs_forward(config)
                    + attention_macs_forward(config))


def attention_flops(config):
    """FLOPs the flash kernels' work needs per step (forward and
    backward, window-aware, every layer once)."""
    return 3 * 2 * attention_macs_forward(config)


def expert_flops(config):
    """FLOPs the experts' products need per step (forward and backward):
    3 x 2 x 3 x hidden x expert width x rows x layers."""
    return 3 * 2 * expert_macs_forward(config)


def sweep_bytes(config, chips):
    passes = {"sgd": 5, "adam": 7}[config["optimizer"]["name"]]
    return passes * 4 * parameters(config) / chips
