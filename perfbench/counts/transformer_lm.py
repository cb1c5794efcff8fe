"""Operations and bytes a transformer-LM training step needs, from
shapes alone.

FLOPs: 2 per multiply-add of every matrix product of the forward pass
(q, k, v, out, the two FFN products, the head) and of causal attention
(scores and values, HALVED for the causal mask); the backward pass is
twice the forward.  Embedding look-ups, LayerNorm, softmax, the loss,
the optimizer and anything recomputed (flash attention's backward
recomputes the scores) are not counted.
"""


def _sizes(config):
    return (int(config["hidden_size"]), int(config["ffn_dim"]),
            int(config["vocab_size"]), int(config["num_hidden_layers"]),
            int(config["batch_size"]), int(config["seq_len"]))


def parameters(config):
    u, f, v, n, _b, _t = _sizes(config)
    per_layer = 4 * u * u + 2 * u * f + f + u + 4 * u
    return (v * u + int(config["max_position_embeddings"]) * u
            + n * per_layer + 2 * u + v * u + v)


def matmul_macs_per_token(config):
    u, f, v, n, _b, _t = _sizes(config)
    return n * (4 * u * u + 2 * u * f) + v * u


def attention_macs_forward(config):
    """Causal attention's multiply-adds, forward, whole batch: scores
    and values are B*T*T*u each in full, half of that under the mask."""
    u, _f, _v, n, b, t = _sizes(config)
    return n * b * t * t * u


def rows_per_step(config):
    """Tokens per step."""
    _u, _f, _v, _n, b, t = _sizes(config)
    return b * t


def step_flops(config):
    tokens = rows_per_step(config)
    return 3 * 2 * (tokens * matmul_macs_per_token(config)
                    + attention_macs_forward(config))


def attention_flops(config):
    """FLOPs the flash kernels' work needs per step (forward and
    backward, causal)."""
    return 3 * 2 * attention_macs_forward(config)


def sweep_bytes(config, chips):
    passes = {"sgd": 5, "adam": 7}[config["optimizer"]["name"]]
    return passes * 4 * parameters(config) / chips
