"""Operations and bytes the training step of a DECODER-HYBRID-DECODER
language model (SambaY: Mamba mixers, differential window / full /
cross attention, gated memory units, SwiGLU, LayerNorm, a tied head)
needs, from shapes alone, for the layers the configuration runs
(``layer_kinds``) and the ``vocab_size`` held rows of the table that is
both the embedding and the head.

- ``parameters`` counts what the chip holds and the optimizer sweeps
  (``sweep_bytes``); ``published_parameters`` the whole published model.
- ``step_flops`` and ``attention_flops`` count the work the mathematics
  needs, whatever implements it; ``ssm_bytes`` the least bytes the
  selective scan's kernels must move.

FLOPs: 2 per multiply-add of every matrix product of the forward pass
(a Mamba mixer's input, step and output projections, the gated memory
unit's two, attention's projections — the cross layers' queries alone —
and output, the SwiGLU's three, the head over the held rows) and of
attention: each differential layer's TWO maps, two score products of
width ``D`` and two value products of width ``2 D`` for each of the
``H / 2`` query pairs, over ``T^2 / 2`` pairs in a causal layer (full and
cross) and ``T W - W^2 / 2`` under a window of ``W``; the backward pass
is twice the forward.  The embedding look-up, the causal convolution (a
multiply-add a channel and tap), the selective scan's elementwise
recurrence (its state update and read-out are not matrix products),
the norms, the softmax, the maps' difference, the loss, the optimizer,
PADDING and ANYTHING RECOMPUTED (each layer's forward is run again in the
backward pass) are not counted.
"""
import math

SSM_CHUNK = 128     # rows the scan kernels carry the state through at once


def _sizes(config):
    a, u = config["assumed"], int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return {"u": u, "e": int(a["mamba_expand"]) * u,
            "n": int(a["mamba_d_state"]), "k": int(a["mamba_d_conv"]),
            "r": math.ceil(u / 16), "heads": heads,
            "kv_heads": int(config["num_key_value_heads"]),
            "d": u // heads, "f": int(config["intermediate_size"]),
            "v": int(config["vocab_size"]),
            "w": int(config["sliding_window"]),
            "kinds": list(config["layer_kinds"]),
            "b": int(config["batch_size"]), "t": int(config["seq_len"])}


def mixer_parameters(config, kind):
    """A layer's mixer: its leaves as the block holds them."""
    s = _sizes(config)
    u, e, n, r, d = s["u"], s["e"], s["n"], s["r"], s["d"]
    if kind == "mamba":
        return 2 * e * u + s["k"] * e + e + (r + 2 * n) * e + r * e + e \
            + e * n + e + u * e
    if kind == "gmu":
        return 2 * e * u
    qd = s["heads"] * d
    proj = qd if kind == "cross_attention" else qd + 2 * s["kv_heads"] * d
    return proj * u + proj + 4 * d + 2 * d + u * qd + u


def layer_parameters(config, kind):
    """The mixer, the SwiGLU, two LayerNorms."""
    s = _sizes(config)
    return mixer_parameters(config, kind) + 3 * s["u"] * s["f"] + 4 * s["u"]


def parameters(config):
    """Every leaf the chip holds: the tied table over the held rows
    (once), the layers, the final LayerNorm."""
    s = _sizes(config)
    return s["v"] * s["u"] + sum(layer_parameters(config, k)
                                 for k in s["kinds"]) + 2 * s["u"]


def published_kinds(config):
    """The published layers' kinds: the self-decoder's Mamba / window
    pairs, the boundary Mamba and full layers, the cross-decoder's GMU /
    cross pairs."""
    layers = int(config["published"]["num_hidden_layers"])
    half = layers // 2
    return ["mamba" if i % 2 == 0 else "sliding_attention"
            for i in range(half)] + ["mamba", "full_attention"] \
        + ["gmu" if i % 2 == 0 else "cross_attention"
           for i in range(layers - half - 2)]


def published_parameters(config):
    """The whole published model: every layer and vocabulary row."""
    s = _sizes(config)
    return int(config["published"]["vocab_size"]) * s["u"] + sum(
        layer_parameters(config, k) for k in published_kinds(config)) \
        + 2 * s["u"]


def rows_per_step(config):
    s = _sizes(config)
    return s["b"] * s["t"]


def mixer_macs_per_token(config, kind):
    s = _sizes(config)
    u, e, d = s["u"], s["e"], s["d"]
    if kind == "mamba":
        return 2 * e * u + (s["r"] + 2 * s["n"]) * e + s["r"] * e + u * e
    if kind == "gmu":
        return 2 * e * u
    qd = s["heads"] * d
    proj = qd if kind == "cross_attention" else qd + 2 * s["kv_heads"] * d
    return proj * u + u * qd


def matmul_macs_per_token(config):
    s = _sizes(config)
    return sum(mixer_macs_per_token(config, k) + 3 * s["u"] * s["f"]
               for k in s["kinds"]) + s["v"] * s["u"]


def visible_pairs(config, kind):
    """(query, key) pairs a differential layer's map sees a sequence."""
    s = _sizes(config)
    t, w = s["t"], s["w"]
    if kind == "sliding_attention" and w < t:
        return t * w - w * w // 2
    return t * t // 2


def attention_macs_forward(config):
    """Both maps of every query pair: scores of width D and values of
    width 2 D, every attention layer, whole batch."""
    s = _sizes(config)
    per_pair = s["heads"] * (s["d"] + 2 * s["d"])
    return s["b"] * per_pair * sum(
        visible_pairs(config, k) for k in s["kinds"]
        if k in ("sliding_attention", "full_attention", "cross_attention"))


def step_flops(config):
    return 3 * 2 * (rows_per_step(config) * matmul_macs_per_token(config)
                    + attention_macs_forward(config))


def attention_flops(config):
    """FLOPs the flash kernels' work needs per step (forward and
    backward, every attention layer once)."""
    return 3 * 2 * attention_macs_forward(config)


def ssm_bytes(config):
    """The least bytes the scan kernels move a step, over every Mamba
    layer: forward, ``u``, ``delta`` read and ``y`` written, backward
    ``u``, ``delta``, ``dy`` read and ``du``, ``ddelta`` written — (T, E)
    each at 2 bytes an element — ``B``, ``C`` read (and their gradients
    written) at 2 bytes, ``A`` and ``D`` (and theirs) at 4, the states
    entering the chunks written once and read once at 4."""
    s = _sizes(config)
    rows, e, n = rows_per_step(config), s["e"], s["n"]
    te, tn, en = rows * e, rows * n, e * n
    states = 4 * s["b"] * math.ceil(s["t"] / SSM_CHUNK) * n * e
    fwd = 2 * 3 * te + 2 * 2 * tn + 4 * (en + e) + states
    bwd = 2 * 5 * te + 2 * 4 * tn + 2 * 4 * (en + e) + states
    return s["kinds"].count("mamba") * (fwd + bwd)


def sweep_bytes(config, chips):
    passes = {"sgd": 5, "adam": 7}[config["optimizer"]["name"]]
    return passes * 4 * parameters(config) / chips
