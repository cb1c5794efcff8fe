"""Operations and bytes a ResNet training step needs, from shapes alone.

FLOPs: 2 per multiply-add of every convolution and of the classifier,
forward; the backward pass is twice the forward (one product for the
input's gradient, one for the weight's); BatchNorm, ReLU, pooling, the
loss, the optimizer and anything recomputed are not counted.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import loader  # noqa: E402

_ref = loader.load_module(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "reference", "resnet.py"))


def _out(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def forward_macs_per_image(config):
    """Multiply-adds of one image's forward pass."""
    plan = _ref._plan(config)
    h = int(config["image_shape"][1])
    macs = 0

    def conv(spec, size):
        _name, cout, cin, k, stride, pad, _bias = spec
        out = _out(size, k, stride, pad)
        return cout * cin * k * k * out * out, out

    m, h = conv(plan["stem"]["conv"], h)
    macs += m
    if plan["stem"]["pool"]:
        h = _out(h, 3, 2, 1)
    for u in plan["units"]:
        size, h_in = h, h
        for role, spec in _ref._unit_layers(u, plan["bottle"],
                                            plan["gluon"]):
            if role == "conv":
                m, size = conv(spec, size)
                macs += m
            elif role == "sc":
                macs += conv(spec, h_in)[0]
        h = size
    macs += plan["fc_in"] * int(config["num_classes"])
    return macs


def parameters(config):
    n = 0
    for _name, shape, _init in _ref.leaf_specs(config):
        k = 1
        for s in shape:
            k *= s
        n += k
    return n


def step_flops(config):
    """FLOPs of one training step over the whole batch (all chips)."""
    return 3 * 2 * forward_macs_per_image(config) * int(config["batch_size"])


def rows_per_step(config):
    return int(config["batch_size"])


def sweep_bytes(config, chips):
    """Bytes one chip's optimizer sweep must move, float32: SGD with
    momentum reads p, g, m and writes p, m (5 passes); Adam reads p, g,
    m, v and writes p, m, v (7).  Under ZeRO each chip sweeps its own
    1/chips shard."""
    passes = {"sgd": 5, "adam": 7}[config["optimizer"]["name"]]
    return passes * 4 * parameters(config) / chips
