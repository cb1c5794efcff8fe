"""Capture a device trace of the full fused train step (fwd+bwd+SGD)
on the live chip and dump per-op time attribution.

Usage:  python _prof_trace.py [outdir]   (default /tmp/jaxtrace)

Produces:
- <outdir>/plugins/profile/... xplane protos (jax.profiler.trace)
- stdout: step timing + top-k op/fusion table parsed from the xplane via
  tensorboard_plugin_profile (framework_op_stats), the data backing the
  docs/faq/perf.md roofline attribution.
"""
import glob
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision as models
from mxnet_tpu.parallel import pure_block_apply
from mxnet_tpu import random as mxrandom

OUT = sys.argv[1] if len(sys.argv) > 1 else "/tmp/jaxtrace"
B = 256

net = models.resnet50_v1(classes=1000)
net.initialize(mx.init.Xavier())
net(mx.nd.ones((1, 3, 224, 224)))
params = {k: p.data()._data.astype(jnp.bfloat16)
          for k, p in net.collect_params().items()}
apply_fn = pure_block_apply(net, list(params), is_train=True)
key = mxrandom.next_key()
x = jnp.asarray(np.random.rand(B, 3, 224, 224), jnp.bfloat16)
y = jnp.asarray(np.random.randint(0, 1000, B))


def loss_fn(p, x, y):
    logits = apply_fn(p, key, x).astype(jnp.float32)
    return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(B), y])


@jax.jit
def train_step(p, mom, x, y):
    loss, g = jax.value_and_grad(loss_fn)(p, x, y)
    new_mom = {k: 0.9 * mom[k] + g[k].astype(jnp.float32) for k in g}
    new_p = {k: (p[k].astype(jnp.float32) - 0.01 * new_mom[k]).astype(p[k].dtype)
             for k in p}
    return loss, new_p, new_mom


mom = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
loss, params, mom = train_step(params, mom, x, y)  # compile
jax.block_until_ready(loss)

# steady-state wall timing around block_until_ready; the xplane's
# XLA-module duration below is the number of record (whether the two
# agree on this chip has not been checked — PERF.md open questions).
t0 = time.time()
N = 20
for _ in range(N):
    loss, params, mom = train_step(params, mom, x, y)
jax.block_until_ready(loss)
dt = (time.time() - t0) / N
print("fused step (wall): %.2f ms  (%.0f img/s)" % (dt * 1e3,
                                                                B / dt))

with jax.profiler.trace(OUT):
    for _ in range(5):
        loss, params, mom = train_step(params, mom, x, y)
    jax.block_until_ready(loss)
print("trace written to", OUT)

# ---- parse the xplane into a per-category table ----
# (tensorboard_plugin_profile's converter predates the installed tf's
# _pywrap_profiler ABI; the tf.tsl xplane proto parses the file fine)
try:
    import collections

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xplanes = sorted(glob.glob(os.path.join(
        OUT, "plugins", "profile", "*", "*.xplane.pb")))
    if not xplanes:
        raise RuntimeError("no xplane.pb found under %s" % OUT)
    xs = xplane_pb2.XSpace()
    with open(xplanes[-1], "rb") as f:
        xs.ParseFromString(f.read())
    plane = [p for p in xs.planes if "TPU" in p.name or "device" in p.name][0]
    emeta = {m.id: m for m in plane.event_metadata.values()}
    smeta = {m.id: m.name for m in plane.stat_metadata.values()}
    cat = collections.Counter()
    total = 0.0
    steps = 5  # traced above
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            m = emeta[ev.metadata_id]
            stats = {}
            for s in list(ev.stats) + list(m.stats):
                stats[smeta.get(s.metadata_id, "?")] = \
                    s.str_value or s.int64_value or s.double_value or ""
            tf_op = str(stats.get("tf_op", ""))
            d = ev.duration_ps / 1e9 / steps  # ms per step
            total += d
            if "conv_general_dilated" in tf_op:
                c = ("conv bwd" if "transpose(jvp" in tf_op else "conv fwd")
            elif "reduce_sum" in tf_op or "reduce_max" in tf_op:
                c = "reductions (BN stats, loss)"
            elif "select_and_scatter" in tf_op:
                c = "maxpool bwd"
            elif "reduce_window" in tf_op:
                c = "pool fwd"
            elif any(k in tf_op for k in ("/add", "/max", "/mul", "/sub",
                                          "/div", "convert", "rsqrt",
                                          "select")):
                c = "elementwise/residual/BN apply"
            elif "dot" in tf_op:
                c = "dense matmul"
            else:
                c = "other"
            cat[c] += d
    print("device ms/step by category (total %.1f):" % total)
    for c, d in cat.most_common():
        print("  %-34s %7.2f ms  (%4.1f%%)" % (c, d, 100 * d / total))
except Exception as e:  # pragma: no cover - tooling-dependent
    print("xplane parse failed (%s); raw trace still on disk" % e)
