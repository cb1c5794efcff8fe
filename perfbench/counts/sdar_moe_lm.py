"""Operations and bytes a block-diffusion sparse-expert language model's
training step needs, from shapes alone — for ONE CHIP'S SHARE of the
deployment the configuration states: ``num_experts`` held experts a layer
under a router over ``published.num_experts``, ``vocab_size`` held rows
of the vocabulary, ``num_hidden_layers`` layers.

A step of ``seq_len`` = L clean tokens sends ``2 L`` ROWS through every
layer — a noised copy and a clean copy — under the three-part block mask,
and reads the head over the L noised rows alone.

- ``parameters`` counts what the chip holds and the optimizer sweeps
  (``sweep_bytes``); ``published_parameters`` the whole published model.
- ``step_flops``, ``attention_flops`` and ``expert_flops`` count the work
  the mathematics needs, whatever implements it.

FLOPs: 2 per multiply-add of every matrix product of the forward pass
(q, k, v, out and the router over the 2 L rows of EVERY layer — the last
layer's clean half too, which feeds nothing after its keys and values:
the released training code runs it and so does the program; the experts'
three products over the rows ROUTED to the held experts in expectation
(``expected_expert_rows``: the masked rows all take the MASK token's
experts); the head over the L noised rows and the held
vocabulary rows) and of attention (scores and values over the VISIBLE
query-key pairs: ``L^2 + L B`` of the ``4 L^2``, B the block length);
the backward pass is twice the forward.  The embedding look-up, RMSNorm
and QK-norm, rotary, softmax, the noising, top-k, the rows' sort, gather
and combine, the loss, the optimizer, PADDING of any kind (a masked tile's
invisible pairs among it) and ANYTHING RECOMPUTED (each layer's forward is
run again in the backward pass; flash attention's backward recomputes the
scores) are not counted.
"""


def _sizes(config):
    return {"u": int(config["hidden_size"]),
            "d": int(config["head_dim"]),
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
            "block": int(config["block_length"]),
            "b": int(config["batch_size"]), "t": int(config["seq_len"])}


def attention_parameters(config):
    """q and out ``u x heads*d``, k and v ``u x kv_heads*d``, the two
    QK-norm gains ``d`` each."""
    s = _sizes(config)
    return 2 * s["u"] * s["hd"] + 2 * s["u"] * s["kvd"] + 2 * s["d"]


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


def tokens_per_step(config):
    """Clean tokens a step: what the head and the loss read."""
    s = _sizes(config)
    return s["b"] * s["t"]


def rows_per_step(config):
    """Rows through every layer: a noised and a clean copy a token."""
    return 2 * tokens_per_step(config)


def masked_share(config):
    """Share of the L positions that are masked, in expectation: the
    mean of the rate ``(1 - eps) t + eps`` over a uniform ``t``."""
    return (1.0 + float(config["noise_eps"])) / 2.0


def expected_expert_rows(config):
    """(row, slot) assignments a step sends to the held experts of ONE
    layer, in expectation: a symmetric router's share of the ORDINARY
    rows — the clean copy and the unmasked positions of the noised one —
    and every MASKED row once for each of the MASK token's own ``top_k``
    experts the chip holds, ``deployment.mask_experts_held`` (the
    configuration's decision; the reference seats the router's rows by
    the same key).  The cell's chip holds none: 6142 rows a layer, 384
    an expert, where a symmetric router over all 8192 rows would send
    512 — as the group's mean chip, which holds one, sees them: 15
    experts at 384 and one at 384 + 2050."""
    s = _sizes(config)
    masked = tokens_per_step(config) * masked_share(config)
    ordinary = rows_per_step(config) - masked
    return ordinary * s["top_k"] * s["held"] / s["routed"] \
        + masked * int(config["deployment"]["mask_experts_held"])


def attention_pairs(config):
    """Visible (query, key) pairs of one sequence's 2 L rows in a layer:
    ``L B`` noised-noised (a block sees itself), ``L (L - B) / 2``
    noised-clean (the clean blocks before), ``L (L + B) / 2`` clean-clean
    (block-causal) — ``L^2 + L B`` together."""
    s = _sizes(config)
    t, blk = s["t"], s["block"]
    return t * blk + t * (t - blk) // 2 + t * (t + blk) // 2


def attention_macs_forward(config):
    """Scores and values over the visible pairs, every query head, whole
    batch, every layer."""
    s = _sizes(config)
    return 2 * s["b"] * s["n"] * attention_pairs(config) * s["hd"]


def expert_macs_forward(config):
    """The three expert products over the rows routed to the held
    experts, every layer."""
    s = _sizes(config)
    return s["n"] * expected_expert_rows(config) * expert_parameters(config)


def matmul_macs_per_row(config):
    """Attention's projections and the router of every layer, a row of
    the 2 L (the experts are counted by routed rows, the head by tokens,
    the QK-norm gains multiply nothing)."""
    s = _sizes(config)
    return s["n"] * (2 * s["u"] * s["hd"] + 2 * s["u"] * s["kvd"]
                     + s["routed"] * s["u"])


def head_macs_per_token(config):
    """The head over the held rows of the vocabulary, a clean token (its
    noised copy's state is what is read)."""
    s = _sizes(config)
    return s["v"] * s["u"]


def step_flops(config):
    return 3 * 2 * (rows_per_step(config) * matmul_macs_per_row(config)
                    + tokens_per_step(config) * head_macs_per_token(config)
                    + expert_macs_forward(config)
                    + attention_macs_forward(config))


def attention_flops(config):
    """FLOPs the flash kernels' work needs per step (forward and
    backward, over the visible pairs, every layer once)."""
    return 3 * 2 * attention_macs_forward(config)


def expert_flops(config):
    """FLOPs the experts' products need per step (forward and backward):
    3 x 2 x 3 x hidden x expert width x rows x layers."""
    return 3 * 2 * expert_macs_forward(config)


def sweep_bytes(config, chips):
    passes = {"sgd": 5, "adam": 7}[config["optimizer"]["name"]]
    return passes * 4 * parameters(config) / chips
