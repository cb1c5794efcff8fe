"""Multi-process dist_sync kvstore correctness.

Reference analogue: tests/nightly/dist_sync_kvstore.py launched as N
local processes via tools/launch.py --launcher local
(docs/faq/distributed_training.md:218-233).  Here: spawn 2 worker
subprocesses with the DMLC_* env the launcher exports; each pushes
rank-dependent gradients into create("dist_sync") and asserts the
all-reduced result, rank-0 init broadcast, updater semantics, and
barrier().
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
import numpy as np
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, %(repo)r)
import mxnet_tpu as mx
from mxnet_tpu import nd

kv = mx.kv.create("dist_sync")
rank, nw = kv.rank, kv.num_workers
assert nw == 2, nw

# init broadcast: every process passes a DIFFERENT value; all must end
# up with rank 0's
kv.init("w", nd.array(np.full((4, 3), float(rank + 1), np.float32)))
out = nd.zeros((4, 3))
kv.pull("w", out=out)
assert np.allclose(out.asnumpy(), 1.0), out.asnumpy()

# push sums across processes (no updater -> store += sum)
kv.push("w", nd.array(np.full((4, 3), float(rank + 1), np.float32)))
kv.pull("w", out=out)
# 1 (init) + (1+2) (summed push) = 4
assert np.allclose(out.asnumpy(), 4.0), out.asnumpy()

# per-device list push: local reduce then global reduce
kv.push("w", [nd.ones((4, 3)), nd.ones((4, 3))])
kv.pull("w", out=out)
assert np.allclose(out.asnumpy(), 8.0), out.asnumpy()

# updater semantics on the globally-summed gradient
kv2_key = "u"
kv._set_updater(lambda key, grad, weight: weight.__isub__(0.1 * grad))
kv.init(kv2_key, nd.zeros((2, 2)))
kv.push(kv2_key, nd.ones((2, 2)) * (rank + 1))
o2 = nd.zeros((2, 2))
kv.pull(kv2_key, out=o2)
assert np.allclose(o2.asnumpy(), -0.3), o2.asnumpy()  # -0.1 * (1+2)

kv.barrier()

# failure detection: both workers heartbeat during pushes, so none dead
assert kv.get_num_dead_node(timeout_sec=300) == 0
print("WORKER_OK rank=%%d" %% rank)
"""


@pytest.mark.slow
def test_dist_sync_kvstore_two_processes(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER % {"repo": REPO})
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.update({
            "DMLC_ROLE": "worker",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": "9413",
            "DMLC_WORKER_ID": str(rank),
            "DMLC_NUM_WORKER": "2",
            "MXNET_KVSTORE_HEARTBEAT_DIR": str(tmp_path / "hb"),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, "worker %d failed:\n%s" % (rank, out[-3000:])
        assert "WORKER_OK" in out


def test_dist_async_update_on_arrival(tmp_path):
    """dist_async applies pushes the moment they arrive — no pull, no
    step barrier (reference kvstore_dist_server.h:282 async branch)."""
    import time
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    os.environ["MXNET_KVSTORE_ASYNC_DIR"] = str(tmp_path)
    try:
        kv = mx.kv.create("dist_async")
        assert type(kv).__name__ == "KVStoreDistAsync"
        arrivals = []

        def updater(key_int, grad, weight):
            arrivals.append(float(grad.asnumpy()[0, 0]))
            weight -= 0.1 * grad

        kv._set_updater(updater)
        kv.init("w", nd.zeros((2, 2)))
        # two pushes, NO pull in between: a sync store would buffer or
        # apply at the pull barrier; async must apply both on arrival
        kv.push("w", nd.array(np.full((2, 2), 1.0, np.float32)))
        kv.push("w", nd.array(np.full((2, 2), 2.0, np.float32)))
        deadline = time.time() + 10
        while len(arrivals) < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert arrivals == [1.0, 2.0], arrivals  # arrival order
        out = nd.zeros((2, 2))
        kv.pull("w", out=out)
        assert np.allclose(out.asnumpy(), -0.3), out.asnumpy()
        kv.close()
    finally:
        os.environ.pop("MXNET_KVSTORE_ASYNC_DIR", None)


def test_dist_async_two_processes(tmp_path):
    """A second worker process spools pushes; the coordinator applies
    them on arrival and the worker pulls the updated weights."""
    import time
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    env = dict(os.environ)
    env.update({"MXNET_KVSTORE_ASYNC_DIR": str(tmp_path),
                "DMLC_WORKER_ID": "1", "DMLC_NUM_WORKER": "2",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", "")})
    worker_src = r"""
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd
kv = mx.kv.create("dist_async")
kv.init("w", nd.zeros((2, 3)))        # adopts coordinator weights
kv.push("w", nd.array(np.full((2, 3), 4.0, np.float32)))
# poll until the coordinator's update is visible
import time
deadline = time.time() + 20
out = nd.zeros((2, 3))
while time.time() < deadline:
    kv.pull("w", out=out)
    if abs(float(out.asnumpy()[0, 0]) - 1.0) < 1e-6:
        print("WORKER_SAW_UPDATE")
        break
    time.sleep(0.05)
else:
    raise SystemExit("worker never saw the update")
"""
    os.environ["MXNET_KVSTORE_ASYNC_DIR"] = str(tmp_path)
    os.environ["DMLC_WORKER_ID"] = "0"
    os.environ["DMLC_NUM_WORKER"] = "2"
    try:
        kv = mx.kv.create("dist_async")
        kv._set_updater(lambda i, g, w: w.__isub__(0.25 * g))
        kv.init("w", nd.array(np.full((2, 3), 2.0, np.float32)))
        proc = subprocess.Popen([sys.executable, "-c", worker_src],
                                env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        out_text, _ = proc.communicate(timeout=120)
        assert "WORKER_SAW_UPDATE" in out_text, out_text[-2000:]
        # coordinator applied on arrival: 2.0 - 0.25*4.0 = 1.0
        got = nd.zeros((2, 3))
        kv.pull("w", out=got)
        assert np.allclose(got.asnumpy(), 1.0), got.asnumpy()
        kv.close()
    finally:
        for var in ("MXNET_KVSTORE_ASYNC_DIR", "DMLC_WORKER_ID",
                    "DMLC_NUM_WORKER"):
            os.environ.pop(var, None)


STAGING_WORKER = r"""
import os, sys
import numpy as np
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, %(repo)r)
import mxnet_tpu as mx
from mxnet_tpu import nd

kv = mx.kv.create("dist_sync")
rank = kv.rank
kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))
keys = list(range(3))
shapes = [(64, 8), (128,), (16, 4, 4)]
for k, s in zip(keys, shapes):
    kv.init(k, nd.zeros(s))
grads = [nd.array(np.full(s, float(rank + 1), np.float32)) for s in shapes]
outs = [nd.zeros(s) for s in shapes]

# warmup: compiles stage/reduce/update programs, allocates zero shards
for k, g in zip(keys, grads):
    kv.push(k, g)
for k, o in zip(keys, outs):
    kv.pull(k, out=o)
nd.waitall()

# steady state: count bytes device_put actually moves (non-resident
# operands) using the SAME counter the bandwidth tool ships.  The
# device-resident data plane must move ZERO.
sys.path.insert(0, os.path.join(%(repo)r, "tools"))
from bandwidth import _patch_staging_counter
staged = {"bytes": 0}
unpatch = _patch_staging_counter(staged)
for k, g in zip(keys, grads):
    kv.push(k, g)
for k, o in zip(keys, outs):
    kv.pull(k, out=o)
nd.waitall()
unpatch()

assert staged["bytes"] == 0, "host-staged bytes in steady state: %%d" %% staged["bytes"]
# numerics: two sgd steps on grad summed over ranks (1+2)=3 -> w = -0.6
assert np.allclose(outs[0].asnumpy(), -0.6, atol=1e-5), outs[0].asnumpy()[0, :3]
print("STAGING_OK rank=%%d" %% rank)
"""


@pytest.mark.slow
def test_dist_sync_zero_host_staging(tmp_path):
    """Steady-state dist_sync push moves zero host-staged bytes: the
    lead shard is produced on device, zero shards are persistent, and
    global assembly is metadata-only (VERDICT r3 #3; reference ZPush
    writes into the engine's comm buffer, kvstore_dist.h:387)."""
    script = tmp_path / "staging_worker.py"
    script.write_text(STAGING_WORKER % {"repo": REPO})
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.update({
            "DMLC_ROLE": "worker",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": "9431",
            "DMLC_WORKER_ID": str(rank),
            "DMLC_NUM_WORKER": "2",
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, "worker %d failed:\n%s" % (rank, out[-3000:])
        assert "STAGING_OK" in out


def test_dist_async_spool_bounded_under_stalled_server(tmp_path):
    """With the coordinator's server thread stalled, pushes hit the
    spool capacity and block, then raise after the backpressure timeout.
    The bound is EXACT (r4 VERDICT #7): the capacity scan and the
    publishing rename happen under one spool lockfile, so even
    concurrent pushers cannot land cap + k files (the r4 bound was
    cap + workers - 1 from the unlocked check-then-write)."""
    import glob
    import threading
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.base import MXNetError

    os.environ["MXNET_KVSTORE_ASYNC_DIR"] = str(tmp_path)
    os.environ["MXNET_KVSTORE_ASYNC_MAX_PENDING"] = "3"
    os.environ["MXNET_KVSTORE_ASYNC_BACKPRESSURE_TIMEOUT"] = "1.5"
    try:
        kv = mx.kv.create("dist_async")
        kv.init("w", nd.zeros((2, 2)))
        # stall the server: stop the thread after init's publish
        kv._stop.set()
        kv._server.join(timeout=5)
        g = nd.array(np.ones((2, 2), np.float32))
        # 4 concurrent pushers all racing the capacity check — every
        # one must eventually raise, and the spool must hold EXACTLY
        # the cap, not cap + (pushers - 1)
        errors = []

        def _spam():
            try:
                for _ in range(5):
                    kv.push("w", g)
            except MXNetError as e:
                errors.append(str(e))

        threads = [threading.Thread(target=_spam) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(errors) == 4, \
            "every blocked pusher must raise: %d/4" % len(errors)
        assert all("backpressure" in e or "server thread" in e
                   for e in errors)
        spooled = glob.glob(str(tmp_path / "push" / "*.npz"))
        assert len(spooled) == 3, \
            "spool must hold exactly the cap: %d" % len(spooled)
    finally:
        for var in ("MXNET_KVSTORE_ASYNC_DIR",
                    "MXNET_KVSTORE_ASYNC_MAX_PENDING",
                    "MXNET_KVSTORE_ASYNC_BACKPRESSURE_TIMEOUT"):
            os.environ.pop(var, None)
