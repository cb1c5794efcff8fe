"""Plain reference: ResNet (He et al., arXiv:1512.03385) trained by SGD
with momentum, float32 at the highest matmul precision, jax.numpy only.

It imports nothing of the program and is handed nothing the program
made: the weights come from :func:`init_weights` (the seed), the
batches from the benchmark's traffic generator.  The program gets the
same two.  :func:`train_steps` follows the first steps of training and
returns what ``checks/train_steps.py`` compares: each step's loss, the
norm of every leaf's first gradient, and the norm of every leaf's
change after the steps.

The configuration says which build it is (``build``):

- ``symbol_v1`` — ``example/image-classification/symbols/resnet.py``
  ``version=1``: conv-BN-ReLU units, the stride on the 3x3 conv, no
  conv bias, BN eps 2e-5, names ``stage1_unit1_conv1_weight`` ….
- ``gluon_v1`` — ``gluon.model_zoo.vision.resnet50_v1``: the stride on
  the first 1x1 conv, a bias on the 1x1 convs of the body, a projected
  shortcut only where the width changes, BN eps 1e-5; the leaves keep
  the names above, in the block's construction order, and the driver
  matches them to the block's parameters by order and shape.

Each residual unit is rematerialised (``jax.checkpoint``) so that batch
256 at 224x224 in float32 fits the chip once the program is gone; that
changes memory, not arithmetic.

``precision="fp8"`` is the CONTROL: the same mathematics computed in
float8, the step below the bf16 the configuration states — the operands
of every convolution and of the classifier rounded to e4m3 on the way
forward, and the gradient arriving at their outputs rounded to e5m2 on
the way back (the two formats of fp8 training), each scaled per tensor.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference_common import (WEIGHT_STREAM, fp8, fp8_grad, seed_key,  # noqa: F401
                              wd_mult)

HI = lax.Precision.HIGHEST
STAGE_PLAN = {18: ([2, 2, 2, 2], False), 34: ([3, 4, 6, 3], False),
              50: ([3, 4, 6, 3], True), 101: ([3, 4, 23, 3], True)}


# ---------------------------------------------------------------------------
# the layer list: one walk that both init_weights and forward follow
# ---------------------------------------------------------------------------
def _plan(config):
    """The stem, the residual units and the classifier's width, in
    forward order, for the configuration's depth and build."""
    units, bottle = STAGE_PLAN[int(config["num_layers"])]
    filters = [64, 256, 512, 1024, 2048] if bottle else \
        [64, 64, 128, 256, 512]
    gluon = config["build"] == "gluon_v1"
    plan = {"units": [], "bottle": bottle, "gluon": gluon}
    cin = int(config["image_shape"][0])
    big = int(config["image_shape"][1]) > 32
    k = 7 if big else 3
    plan["stem"] = {"conv": ("conv0", filters[0], cin, k, 2 if big else 1,
                             3 if big else 1, False),
                    "bn": "bn0", "pool": big}
    cin = filters[0]
    for i, n in enumerate(units):
        for j in range(n):
            stride = 1 if (i == 0 or j > 0) else 2
            name = "stage%d_unit%d" % (i + 1, j + 1)
            # the symbol build projects the shortcut of every stage's
            # first unit; the model zoo only where the width changes
            match = j > 0 or (gluon and cin == filters[i + 1])
            plan["units"].append({"name": name, "cin": cin,
                                  "cout": filters[i + 1], "stride": stride,
                                  "match": match})
            cin = filters[i + 1]
    plan["fc_in"] = cin
    return plan


def _unit_layers(u, bottle, gluon):
    """[(role, conv spec or bn name)] of one unit, in construction order.
    conv spec: (name, cout, cin, kernel, stride, pad, bias)."""
    n, cin, cout, s = u["name"], u["cin"], u["cout"], u["stride"]
    out = []
    if bottle:
        mid = cout // 4
        s1, s2 = (s, 1) if gluon else (1, s)
        out += [("conv", (n + "_conv1", mid, cin, 1, s1, 0, gluon)),
                ("bn", n + "_bn1"),
                ("conv", (n + "_conv2", mid, mid, 3, s2, 1, False)),
                ("bn", n + "_bn2"),
                ("conv", (n + "_conv3", cout, mid, 1, 1, 0, gluon)),
                ("bn", n + "_bn3")]
    else:
        out += [("conv", (n + "_conv1", cout, cin, 3, s, 1, False)),
                ("bn", n + "_bn1"),
                ("conv", (n + "_conv2", cout, cout, 3, 1, 1, False)),
                ("bn", n + "_bn2")]
    if not u["match"]:
        out += [("sc", (n + "_sc", cout, cin, 1, s, 0, False)),
                ("scbn", n + "_sc_bn")]
    return out


def leaf_specs(config):
    """Ordered [(name, shape, init)] of every trainable leaf."""
    plan = _plan(config)
    specs = []

    def conv(spec):
        name, cout, cin, k, _s, _p, bias = spec
        specs.append((name + "_weight", (cout, cin, k, k), "he"))
        if bias:
            specs.append((name + "_bias", (cout,), "zero"))

    def add_bn(name, c):
        specs.append((name + "_gamma", (c,), "one"))
        specs.append((name + "_beta", (c,), "zero"))

    conv(plan["stem"]["conv"])
    add_bn(plan["stem"]["bn"], plan["stem"]["conv"][1])
    for u in plan["units"]:
        last_c = None
        for role, spec in _unit_layers(u, plan["bottle"], plan["gluon"]):
            if role in ("conv", "sc"):
                conv(spec)
                last_c = spec[1]
            else:
                add_bn(spec, last_c)
    specs.append(("fc1_weight", (int(config["num_classes"]),
                                 plan["fc_in"]), "he"))
    specs.append(("fc1_bias", (int(config["num_classes"]),), "zero"))
    return specs


def bn_names(config):
    """Names of the BatchNorm layers (for the program's moving stats)."""
    return [n[:-len("_gamma")] for n, _s, _i in leaf_specs(config)
            if n.endswith("_gamma")]


def init_weights(config, seed):
    """He-normal convolutions and classifier (std sqrt(2 / fan_in), as
    MXNet's Xavier(gaussian, in, 2) that ``fit.py`` uses), gamma 1,
    beta and biases 0, float32 — made on the device in one jitted call
    from the seed, then read back once: the program and the reference
    are both handed these host arrays."""
    specs = leaf_specs(config)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            if init == "he":
                std = math.sqrt(2.0 / int(np.prod(shape[1:])))
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                out[name] = jnp.full(shape, 1.0 if init == "one" else 0.0,
                                     jnp.float32)
        return out

    made = jax.device_get(make(seed_key(seed, WEIGHT_STREAM)))
    return {name: made[name] for name, _shape, _init in specs}  # in order


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _bf16(x):
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + lax.stop_gradient(q - x)


def _round(x, quant):
    """``quant``: False (float32), "fp8" (the control) or "bf16" (what
    the configuration states; read once for PERF.md, not compared)."""
    return {False: lambda a: a, "fp8": fp8, "bf16": _bf16}[quant](x)


def _round_back(y, quant):
    return fp8_grad(y) if quant == "fp8" else y


def _conv(x, p, spec, quant):
    name, _cout, _cin, _k, stride, pad, bias = spec
    w = p[name + "_weight"]
    x, w = _round(x, quant), _round(w, quant)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HI)
    y = _round_back(y, quant)
    if bias:
        y = y + p[name + "_bias"][None, :, None, None]
    return y


def _bn(x, p, name, eps):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    g = p[name + "_gamma"][None, :, None, None]
    b = p[name + "_beta"][None, :, None, None]
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def _unit(x, p, layers, eps, quant):
    body = [l for l in layers if l[0] in ("conv", "bn")]
    short = x
    y = x
    for i, (role, spec) in enumerate(body):
        if role == "conv":
            y = _conv(y, p, spec, quant)
        else:
            y = _bn(y, p, spec, eps)
            if i < len(body) - 1:
                y = jax.nn.relu(y)
    for role, spec in layers:
        if role == "sc":
            short = _conv(x, p, spec, quant)
        elif role == "scbn":
            short = _bn(short, p, spec, eps)
    return jax.nn.relu(y + short)


def loss_fn(params, x, y, config, quant=False):
    """Mean softmax cross-entropy of the batch (x NCHW float32, y class
    ids)."""
    plan = _plan(config)
    eps = float(config["bn_eps"])
    h = _conv(x, params, plan["stem"]["conv"], quant)
    h = jax.nn.relu(_bn(h, params, plan["stem"]["bn"], eps))
    if plan["stem"]["pool"]:
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
    for u in plan["units"]:
        layers = _unit_layers(u, plan["bottle"], plan["gluon"])
        sub = {n: v for n, v in params.items()
               if n.startswith(u["name"] + "_")}
        h = jax.checkpoint(
            lambda h_, sub_, layers=layers: _unit(h_, sub_, layers, eps,
                                                  quant))(h, sub)
    h = jnp.mean(h, axis=(2, 3))
    w = params["fc1_weight"]
    h, w = _round(h, quant), _round(w, quant)
    logits = _round_back(jnp.dot(h, w.T, precision=HI), quant) \
        + params["fc1_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None], axis=1)
    return -jnp.mean(picked)


# ---------------------------------------------------------------------------
# the first steps of training
# ---------------------------------------------------------------------------
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def train_steps(config, weights, batches, precision="reference",
                rows=None, devices=None):
    """Follow ``len(batches)`` steps of SGD with momentum from
    ``weights``.  Returns ``{"loss": [...], "grad1": {leaf: norm},
    "dparam": {leaf: norm}}`` as Python floats.  ``rows`` (a slice)
    plants the fault "part of the batch left out, the mean taken over
    the rest".  With several ``devices`` the batch's rows are spread
    over them so that a four-chip cell's batch fits (the same program:
    BatchNorm's means still run over the whole batch)."""
    opt = config["optimizer"]
    lr, mom, wd = (float(opt["learning_rate"]), float(opt["momentum"]),
                   float(opt["wd"]))
    if precision not in ("reference", "fp8", "bf16"):
        raise ValueError("unknown precision %r" % precision)
    quant = False if precision == "reference" else precision
    wds = {k: wd * wd_mult(k, config) for k in weights}

    @jax.jit
    def step(params, moms, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, config,
                                                  quant)
        new_m = {k: mom * moms[k] - lr * (grads[k] + wds[k] * params[k])
                 for k in params}
        new_p = {k: params[k] + new_m[k] for k in params}
        return new_p, new_m, loss, _leaf_norms(grads)

    @jax.jit
    def change(p, p0):
        return _leaf_norms({k: p[k] - p0[k] for k in p})

    put_rows = put_all = jnp.asarray
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("rows",))
        put_rows = lambda a: jax.device_put(a, NamedSharding(mesh, P("rows")))
        put_all = lambda a: jax.device_put(a, NamedSharding(mesh, P()))

    with jax.default_matmul_precision("highest"):
        p0 = {k: put_all(v) for k, v in weights.items()}
        params = p0
        moms = {k: jnp.zeros_like(v) for k, v in p0.items()}
        out = {"loss": []}
        for i, (x, y) in enumerate(batches):
            if rows is not None:
                x, y = x[rows], y[rows]
            params, moms, loss, gn = step(params, moms, put_rows(x),
                                          put_rows(y))
            out["loss"].append(float(loss))
            if i == 0:
                out["grad1"] = {k: float(v) for k, v in gn.items()}
        out["dparam"] = {k: float(v) for k, v in change(params, p0).items()}
    return out
