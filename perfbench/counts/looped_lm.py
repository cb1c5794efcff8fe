"""Operations and bytes a LOOPED language model's training step needs,
from shapes alone.

One stack of ``num_hidden_layers`` layers is applied ``total_ut_steps``
times with the same weights, and every pass ends in an exit that reads
the LM head.  So:

- ``parameters`` counts a shared weight ONCE — it is what the optimizer
  sweeps (``sweep_bytes``) and what the chip holds;
- ``step_flops`` and ``attention_flops`` count EVERY pass and every
  exit's head — the work the mathematics needs, whatever implements it.

FLOPs: 2 per multiply-add of every matrix product of the forward pass
(q, k, v, out, the gated FFN's three products, each exit's head) and of
causal attention (scores and values, HALVED for the mask); the backward
pass is twice the forward.  The embedding look-up, RMSNorm, rotary, the
one-output gates, the loss, the optimizer and ANYTHING RECOMPUTED (each
pass's forward is run again in the backward pass; flash attention's
backward recomputes the scores) are not counted.
"""


def _sizes(config):
    return (int(config["hidden_size"]), int(config["intermediate_size"]),
            int(config["vocab_size"]), int(config["num_hidden_layers"]),
            int(config["total_ut_steps"]), int(config["batch_size"]),
            int(config["seq_len"]))


def layer_parameters(config):
    """Four square projections, three FFN matrices, four gains."""
    u, f, _v, _n, _p, _b, _t = _sizes(config)
    return 4 * u * u + 3 * u * f + 4 * u


def parameters(config):
    """Every leaf once: embedding, the shared stack, the final norm,
    the untied head, the gate and its bias."""
    u, _f, v, n, _p, _b, _t = _sizes(config)
    return v * u + n * layer_parameters(config) + u + v * u + u + 1


def layer_applications(config):
    _u, _f, _v, n, p, _b, _t = _sizes(config)
    return n * p


def matmul_macs_per_token(config):
    """Every pass of the stack and every exit's head."""
    u, f, v, n, p, _b, _t = _sizes(config)
    return p * (n * (4 * u * u + 3 * u * f) + v * u)


def attention_macs_forward(config):
    """Causal attention's multiply-adds, forward, whole batch, every
    layer application: scores and values are B*T*T*u each in full, half
    of that under the mask."""
    u, _f, _v, _n, _p, b, t = _sizes(config)
    return layer_applications(config) * b * t * t * u


def rows_per_step(config):
    """Tokens per step."""
    _u, _f, _v, _n, _p, b, t = _sizes(config)
    return b * t


def step_flops(config):
    tokens = rows_per_step(config)
    return 3 * 2 * (tokens * matmul_macs_per_token(config)
                    + attention_macs_forward(config))


def attention_flops(config):
    """FLOPs the flash kernels' work needs per step (forward and
    backward, causal, every layer application once)."""
    return 3 * 2 * attention_macs_forward(config)


def sweep_bytes(config, chips):
    passes = {"sgd": 5, "adam": 7}[config["optimizer"]["name"]]
    return passes * 4 * parameters(config) / chips
