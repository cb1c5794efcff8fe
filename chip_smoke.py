#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once at the full width of ResNet-50,
through the entry points a user calls, on ONE TPU chip:

1. **kernels** — each of the 12 Pallas kernels compiled natively once
   at a shape a supported model produces and compared with its jnp
   reference (sweeps sgd / sgd-momentum / adam over a 4 Mi-element and
   a ragged bucket; softmax fwd / bias / bwd at 256x1000 in f32 and
   bf16; scale-bias-relu at ResNet-50's widest eval shape; layernorm
   fwd/bwd; causal bf16 flash fwd/bwd at T=2048, D=64 and 128).
2. **train** — ``train_imagenet.py --benchmark 1 --kv-store tpu
   --network resnet --num-layers 50 --batch-size 256 --dtype bfloat16``
   called in-process (``common/fit.py`` -> ``Module.fit`` ->
   ``KVStoreTPU`` -> ``Executor.install_fused_update``) for a few steps
   on one fixed synthetic batch: the fused step is installed, the
   one-sweep optimizer and the Pallas softmax are the native kernels
   running, nothing compiles after the warm steps, the loss is finite
   at every step and falls.
3. **eval** — ``get_params`` (finite), ``score`` on one batch (the eval
   graph with the BN+ReLU peephole), ``predict`` as the reference.
4. **serve** — ``export_serving`` into ``ModelServer(max_batch=16)``,
   two MORE fused steps (they delete the buffers the export read: the
   donation check), ``warmup``, one solo request and a burst that
   coalesces into a bucket above 1, every answer compared with
   ``predict`` (bf16/f32 tolerance) and with an f32 inference Module
   over the exported weights (tight); no executor-cache miss after
   warmup.

``--four-chip`` runs the multi-chip mode instead (needs the four-chip
host): gluon ``resnet50_v1`` under ``ParallelTrainer(mesh=dp4, zero=2,
dtype=bfloat16)`` — loss parity with a 1-device trainer of the same
seed at global batch 256, then global batch 1024 with the sharding,
memory-spread and native shard_map'd-sweep checks.

The script refuses to run without a TPU (no ``JAX_PLATFORMS`` default,
no CPU branch), lets every exception propagate, and prints as its LAST
stdout line ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}`` only if every phase passed.  The persistent compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (mxnet_tpu/compile_cache.py); run it twice in
one place and the second run reports cache hits and a shorter set-up.

``tests/test_chip_smoke.py`` runs the same phases at toy width on the
CPU through :func:`run` (``platform="cpu"``) — the only non-TPU entry.
"""
import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "example", "image-classification"))

# widths are the model's own; only step counts are cut for time.
# 64 steps, not 16: eval-mode BatchNorm reads the MOVING statistics,
# and on the chip those lag the weights so far for the first ~50 steps
# of this run that every eval row saturates to the same exact one-hot
# (observed: 1 distinct top-1 after 8/16/32 steps, 218 after 64) — a
# served-vs-predict comparison of such rows would pass on anything.
FULL = {
    "seed": 21,
    # train_imagenet.py flags (the BENCH_r05 configuration)
    "num_layers": 50, "num_classes": 1000, "image_shape": (3, 224, 224),
    "batch": 256, "steps": 64, "warm_steps": 3, "min_top1": 32,
    "serve_max_batch": 16, "serve_burst": 11,
    # kernel shapes
    "sweep_sizes": (4 * 1024 * 1024, 1000003),
    "softmax_shape": (256, 1000),
    "sbr_shapes": ((16 * 7 * 7, 2048), (256 * 56 * 56, 64)),
    "layernorm_shape": (2048, 1024),
    "flash": {"bh": 4, "t": 2048, "dims": (64, 128)},
    # four-chip mode
    "gluon_net": "resnet50_v1", "mc_image": (3, 224, 224),
    "mc_parity_batch": 256, "mc_batch": 1024, "mc_steps": 3,
}
TOY = {
    "seed": 21,
    "num_layers": 20, "num_classes": 10, "image_shape": (3, 32, 32),
    "batch": 8, "steps": 8, "warm_steps": 3, "min_top1": 2,
    "serve_max_batch": 4, "serve_burst": 3,
    "sweep_sizes": (40000, 12345),
    "softmax_shape": (24, 1000),
    "sbr_shapes": ((784, 256), (1024, 64)),
    "layernorm_shape": (40, 256),
    "flash": {"bh": 1, "t": 256, "dims": (64,)},
    "gluon_net": "resnet18_v1", "mc_image": (3, 32, 32),
    "mc_parity_batch": 16, "mc_batch": 32, "mc_steps": 2,
}

# tolerances, from the dtypes.  bf16 has 8 mantissa bits (eps 2^-8);
# TPU matmuls/convs of f32 operands run bf16 passes by default, so an
# "f32" program there is only bf16-accurate per contraction.
TOL = {
    # |x - ref| <= atol + rtol*|ref| for the elementwise/reduction kernels
    "f32": (1e-5, 1e-5),
    "bf16": (2e-2, 2e-2),
    # flash vs dense attention: bf16 operands, f32 accumulation, T=2048;
    # the backward sums T products of two such rounded factors
    "flash": (3e-2, 3e-2),
    "flash_bwd": (1.2e-1, 1.2e-1),
    # served answers (an f32 program) against two references, as
    # (max |dp|, max |dlog p| over classes the reference gives >= 1e-4;
    # below that bf16 activations do not resolve the logit — 0.79 seen).
    # vs Module.predict of the bf16 training module, 50 layers of bf16
    # activations apart: 1.4e-2 / 0.24 observed on the v5e
    "serve_bf16": (4e-2, 0.6),
    # vs an f32 inference Module over the same weights — the same
    # program at the top bucket (0 observed), another tiling at bucket
    # 1 (6.5e-4 / 0.02 observed)
    "serve_f32": (5e-3, 0.1),
    # dp4 vs dp1 loss in bf16, relative: the first loss is the same
    # forward in another reduction order; later ones sit on two
    # trajectories that each round differently
    "mc_loss_first": 2e-2,
    "mc_loss": 1e-1,
}


def _log(msg):
    print("[chip_smoke] " + msg, flush=True)


class _Phase:
    """Times one phase and prints its verdict; exceptions propagate."""

    def __init__(self, name, seconds):
        self.name, self.seconds = name, seconds

    def __enter__(self):
        _log("---- %s ----" % self.name)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, etype, exc, tb):
        dt = time.perf_counter() - self.t0
        self.seconds[self.name] = round(dt, 2)
        _log("%s %s in %.1fs" % (self.name,
                                 "FAILED" if etype else "passed", dt))
        return False


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _close(got, ref, tol, what):
    """max |got-ref| within atol + rtol*|ref|; returns the max abs err."""
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    _check(got.shape == ref.shape, "%s: shape %s vs reference %s"
           % (what, got.shape, ref.shape))
    _check(np.isfinite(got).all(), "%s: non-finite values" % what)
    atol, rtol = tol
    err = np.abs(got - ref)
    bad = err > atol + rtol * np.abs(ref)
    _check(not bad.any(), "%s: %d of %d elements off by up to %.3g "
           "(atol %g rtol %g)" % (what, int(bad.sum()), bad.size,
                                  float(err.max()), atol, rtol))
    return float(err.max())


def _kernel_count(name):
    from mxnet_tpu import telemetry
    return telemetry.counter("mxnet_pallas_kernel_calls_total").labels(
        kernel=name).value


def _compiles():
    from mxnet_tpu import telemetry
    return telemetry.scalar_totals().get("mxnet_xla_compiles_total", 0)


def _jit_compiles():
    """Every program jax compiled or loaded, whichever layer dispatched
    it: ``_compiles()`` counts at the executor's dispatch alone and
    never sees a ``ParallelTrainer``."""
    from mxnet_tpu import telemetry
    return telemetry.scalar_totals().get("mxnet_jit_compiles_total", 0)


# ---------------------------------------------------------------------------
# phase 0: the device
# ---------------------------------------------------------------------------
def describe_device(platform):
    """Print what jax sees and refuse anything but ``platform``."""
    import jax
    import jaxlib
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    _log("device: platform=%(platform)s kind=%(kind)s count=%(count)d"
         % dev)
    _log("versions: python %s jax %s jaxlib %s libtpu %s"
         % (sys.version.split()[0], jax.__version__, jaxlib.__version__,
            libtpu))
    if dev["platform"] != platform:
        sys.stderr.write(
            "chip_smoke: needs platform %r but jax reports %r (%s x%d); "
            "nothing was run\n" % (platform, dev["platform"],
                                   dev["kind"], dev["count"]))
        raise SystemExit(2)
    return dev


def place_cache():
    from mxnet_tpu import compile_cache
    compile_cache.ensure_initialized()
    st = compile_cache.stats()
    _log("compile cache: dir=%s (placed by %s) entries=%d size=%.1f MiB"
         % (st["dir"], compile_cache.placement()[1], st["entries"],
            st["size_bytes"] / 2 ** 20))
    return st


# ---------------------------------------------------------------------------
# phase 1: the 12 kernels against their references
# ---------------------------------------------------------------------------
def _ulps(a, b):
    """Largest distance in f32 units-in-the-last-place."""
    import numpy as np
    ia = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    ib = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    ia = np.where(ia < 0, np.int64(-2 ** 31) - ia, ia)
    ib = np.where(ib < 0, np.int64(-2 ** 31) - ib, ib)
    return int(np.abs(ia - ib).max())


def _rand(rng, shape, dtype=None, scale=1.0):
    import jax.numpy as jnp
    import numpy as np
    a = jnp.asarray((rng.randn(*shape) * scale).astype(np.float32))
    return a if dtype is None else a.astype(dtype)


def kernels_sweeps(cfg, rng):
    """sgd / sgd-momentum / adam sweeps vs the per-array tree_map
    oracle.  ``PureSGD/PureAdam.apply(flat=True)`` hands each flat
    bucket to ONE sweep kernel; ``flat=False`` is the per-array path
    the repo holds as the oracle.  The counters prove which one ran."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.optimizer import PureAdam, PureSGD

    sizes = {"b%d" % i: n for i, n in enumerate(cfg["sweep_sizes"])}
    params = {k: _rand(rng, (n,)) for k, n in sizes.items()}
    grads = {k: _rand(rng, (n,)) for k, n in sizes.items()}
    worst_ulp = 0
    for name, opt in (
            ("fused_sgd", PureSGD(0.1, wd=1e-4)),
            ("fused_sgd_momentum", PureSGD(0.1, momentum=0.9, wd=1e-4)),
            ("fused_adam", PureAdam(1e-3, wd=1e-4))):
        state = opt.init(params)
        for slot in ("mom", "mean", "var"):
            if slot in state:
                state[slot] = {k: _rand(rng, (n,)) for k, n in sizes.items()}
        if "var" in state:
            state["var"] = {k: jnp.abs(v) for k, v in state["var"].items()}
        before = _kernel_count(name)
        got = jax.jit(lambda p, g, s, opt=opt: opt.apply(
            p, g, s, flat=True))(params, grads, state)
        ref = jax.jit(lambda p, g, s, opt=opt: opt.apply(
            p, g, s))(params, grads, state)
        jax.block_until_ready((got, ref))
        _check(_kernel_count(name) == before + len(sizes),
               "%s: the sweep kernel did not run for every bucket" % name)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            if a.ndim:
                _close(a, b, TOL["f32"], name)
                worst_ulp = max(worst_ulp, _ulps(a, b))
    _log("sweeps sgd/sgd-momentum/adam over buckets of %s elements: "
         "worst distance from the tree_map oracle %d ulp%s"
         % (list(cfg["sweep_sizes"]), worst_ulp,
            " (bit-identical)" if worst_ulp == 0 else ""))


def kernels_softmax(cfg, rng):
    """softmax fwd / +bias / bwd at ragged C (1000 -> the 1024 pad
    path), f32 and bf16."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    shape = cfg["softmax_shape"]
    for dt, tol in ((jnp.float32, TOL["f32"]), (jnp.bfloat16, TOL["bf16"])):
        x = _rand(rng, shape, dt, scale=3.0)
        bias = _rand(rng, shape)
        do = _rand(rng, shape, dt)
        x32, do32 = x.astype(jnp.float32), do.astype(jnp.float32)
        _close(jax.jit(pk.fused_bias_softmax)(x),
               jax.nn.softmax(x32, axis=-1), tol,
               "fused_softmax_fwd %s" % dt.__name__)
        _close(jax.jit(pk.fused_bias_softmax)(x, bias),
               jax.nn.softmax(x32 + bias, axis=-1), tol,
               "fused_softmax_fwd+bias %s" % dt.__name__)
        dx = jax.jit(lambda x, do: jax.vjp(
            pk.fused_bias_softmax, x)[1](do)[0])(x, do)
        dref = jax.jit(lambda x, do: jax.vjp(
            lambda t: jax.nn.softmax(t, axis=-1), x)[1](do)[0])(x32, do32)
        _close(dx, dref, tol, "fused_softmax_bwd %s" % dt.__name__)


def kernels_scale_bias_relu(cfg, rng):
    """The inference BN+ReLU epilogue at ResNet-50's eval shapes."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    for (n, c) in cfg["sbr_shapes"]:
        for dt, tol in ((jnp.float32, TOL["f32"]),
                        (jnp.bfloat16, TOL["bf16"])):
            x = _rand(rng, (n, c), dt)
            sc = jnp.abs(_rand(rng, (c,))) + 0.5
            bi = _rand(rng, (c,))
            y = jax.jit(pk.fused_scale_bias_relu)(x, sc, bi)
            _close(y, jnp.maximum(x.astype(jnp.float32) * sc + bi, 0.0),
                   tol, "fused_scale_bias_relu (%d,%d) %s"
                   % (n, c, dt.__name__))


def kernels_layernorm(cfg, rng):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    r, c = cfg["layernorm_shape"]

    def ln_ref(x, gam, bet):
        x = x.astype(jnp.float32)
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * gam + bet

    for dt, tol in ((jnp.float32, (1e-4, 1e-4)),
                    (jnp.bfloat16, TOL["bf16"])):
        x = _rand(rng, (r, c), dt)
        gam = jnp.abs(_rand(rng, (c,))) + 0.5
        bet = _rand(rng, (c,))
        do = _rand(rng, (r, c), dt)
        out, vjp = jax.vjp(lambda x, g, b: pk.fused_layernorm(
            x, g, b, 1e-5), x, gam, bet)
        rout, rvjp = jax.vjp(ln_ref, x, gam, bet)
        _close(out, rout, tol, "fused_layernorm_fwd %s" % dt.__name__)
        # dgamma/dbeta are sums over r rows: the absolute part scales
        gtol = (tol[0] * math.sqrt(r), tol[1])
        for nm, a, b, t in zip(("dx", "dgamma", "dbeta"), vjp(do),
                               rvjp(do.astype(jnp.float32)),
                               (tol, gtol, gtol)):
            _close(a, b, t, "fused_layernorm_bwd %s %s"
                   % (nm, dt.__name__))


def kernels_flash(cfg, rng):
    """Causal bf16 flash attention fwd/bwd vs dense attention."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    fl = cfg["flash"]
    bh, t = fl["bh"], fl["t"]
    for d in fl["dims"]:
        q, k, v, do = (_rand(rng, (bh, t, d), jnp.bfloat16)
                       for _ in range(4))

        def dense(q, k, v, d=d):
            q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
            s = jnp.einsum("bqd,bkd->bqk", q, k,
                           precision="highest") / math.sqrt(d)
            mask = jnp.tril(jnp.ones((t, t), bool))
            p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, v, precision="highest")

        o, vjp = jax.vjp(lambda q, k, v: pk.flash_attention(
            q, k, v, True), q, k, v)
        ro, rvjp = jax.vjp(dense, q, k, v)
        _close(o, ro, TOL["flash"], "flash_attention_fwd D=%d" % d)
        for nm, a, b in zip(("dq", "dk", "dv"), vjp(do),
                            rvjp(do.astype(jnp.float32))):
            _close(a, b, TOL["flash_bwd"],
                   "flash_attention_bwd %s D=%d" % (nm, d))


KERNEL_FAMILIES = (kernels_sweeps, kernels_softmax, kernels_scale_bias_relu,
                   kernels_layernorm, kernels_flash)


def phase_kernels(cfg, native):
    import numpy as np

    from mxnet_tpu.ops import pallas_kernels as pk

    _check(pk._interpret() is (not native),
           "pallas interpret mode is %s on this platform"
           % pk._interpret())
    rng = np.random.RandomState(cfg["seed"])
    for family in KERNEL_FAMILIES:
        family(cfg, rng)
    for kname in ("fused_sgd", "fused_sgd_momentum", "fused_adam",
                  "fused_softmax_fwd", "fused_softmax_bwd",
                  "fused_scale_bias_relu", "fused_layernorm_fwd",
                  "fused_layernorm_bwd", "flash_attention_fwd",
                  "flash_attention_bwd"):
        _check(_kernel_count(kname) >= 1,
               "kernel %s was never instantiated" % kname)
    _log("12 kernels (%s) match their references"
         % ("native Mosaic" if native else "interpret mode"))


# ---------------------------------------------------------------------------
# phase 2: train through train_imagenet.py
# ---------------------------------------------------------------------------
def phase_train(cfg, platform, seconds):
    import numpy as np

    import mxnet_tpu as mx
    import train_imagenet
    from mxnet_tpu.ops import pallas_kernels as pk

    np.random.seed(cfg["seed"])
    mx.random.seed(cfg["seed"])
    steps = []        # (loss, compiles so far, seconds since fit began)
    seen = {}

    def on_batch(param):
        mod = param.locals["self"]
        batch = param.locals["data_batch"]
        probs = mod.get_outputs()[0].asnumpy().astype(np.float32)
        label = batch.label[0].asnumpy().astype(np.int64)
        loss = float(-np.log(np.maximum(
            probs[np.arange(len(label)), label], 1e-30)).mean())
        steps.append((loss, _compiles(), time.perf_counter() - t0))
        seen["train_data"] = param.locals["train_data"]
        _log("step %2d loss %.4f (%.2fs)" % (len(steps), loss,
                                             steps[-1][2]))

    argv = ["--benchmark", "1", "--kv-store", "tpu",
            "--network", "resnet", "--num-layers", str(cfg["num_layers"]),
            "--num-classes", str(cfg["num_classes"]),
            "--image-shape", ",".join(map(str, cfg["image_shape"])),
            "--batch-size", str(cfg["batch"]), "--dtype", "bfloat16",
            "--num-epochs", "1", "--num-batches", str(cfg["steps"]),
            "--disp-batches", "4"]
    t0 = time.perf_counter()
    mod = train_imagenet.main(argv, batch_end_callback=on_batch)
    mod.get_outputs()[0].wait_to_read()
    seconds["train_first_step"] = round(steps[0][2], 2)

    exe = mod._exec_group.execs[0]
    _check(mod._fused_exec_update is True,
           "Executor.install_fused_update did not install the fused step")
    _check(exe._sweep is not None and exe._sweep["kind"] == "sgd",
           "the one-sweep optimizer plan is missing (per-array fallback)")
    _check(pk._interpret() is (platform != "tpu"), "interpret mode wrong")
    for kname in ("fused_sgd_momentum", "fused_softmax_fwd"):
        _check(_kernel_count(kname) >= 1,
               "train step never instantiated Pallas kernel %s" % kname)
    losses = [s[0] for s in steps]
    _check(len(losses) == cfg["steps"], "ran %d of %d steps"
           % (len(losses), cfg["steps"]))
    _check(all(math.isfinite(l) for l in losses),
           "non-finite loss: %s" % losses)
    _check(losses[-1] < losses[0], "loss did not fall on the fixed "
           "batch: first %.4f last %.4f" % (losses[0], losses[-1]))
    warm = cfg["warm_steps"]
    _check(steps[-1][1] == steps[warm - 1][1],
           "XLA compiled after the %d warm steps: %d -> %d programs"
           % (warm, steps[warm - 1][1], steps[-1][1]))
    out = mod.get_outputs()[0]._data
    _check({d.platform for d in out.devices()} == {platform},
           "outputs live on %s" % out.devices())
    _log("fused step installed, %d sweep bucket(s), loss %.4f -> %.4f, "
         "%d XLA programs, none after step %d"
         % (len(exe._sweep["plan"]), losses[0], losses[-1], steps[-1][1],
            warm))
    return mod, seen["train_data"]


# ---------------------------------------------------------------------------
# phase 3 + 4: eval, export, serve
# ---------------------------------------------------------------------------
def phase_eval(cfg, mod, train_data, seconds):
    import numpy as np

    t0 = time.perf_counter()
    arg_params, aux_params = mod.get_params()
    for name, arr in list(arg_params.items()) + list(aux_params.items()):
        _check(np.isfinite(arr.asnumpy().astype(np.float32)).all(),
               "parameter %s is not finite after training" % name)
    score = mod.score(train_data, "acc", num_batch=1)
    seconds["eval_first_forward"] = round(time.perf_counter() - t0, 2)
    _check(_kernel_count("fused_scale_bias_relu") >= 1,
           "the eval graph never instantiated the BN+ReLU Pallas "
           "epilogue (fused_scale_bias_relu)")
    ref = mod.predict(train_data, num_batch=1)
    ref.wait_to_read()
    ref = ref.asnumpy().astype(np.float32)
    _check(ref.shape == (cfg["batch"], cfg["num_classes"]),
           "predict returned %s" % (ref.shape,))
    _check(np.isfinite(ref).all(), "predict is not finite")
    _check(np.allclose(ref.sum(-1), 1.0, atol=2e-2),
           "predict rows are not probability vectors")
    # the reference must be worth comparing against (see FULL)
    top1 = len(set(ref.argmax(-1).tolist()))
    onehot = int((ref.max(-1) >= 1.0).sum())
    _check(top1 >= cfg["min_top1"] and onehot == 0,
           "eval output is degenerate: %d distinct top-1 classes over %d "
           "rows, %d rows saturated to an exact one-hot"
           % (top1, len(ref), onehot))
    _log("%d parameters finite, score %s, predict %s with %d distinct "
         "top-1 classes" % (len(arg_params) + len(aux_params), score,
                            ref.shape, top1))
    return ref


def phase_serve(cfg, mod, train_data, ref, seconds):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.serving import ModelServer

    srv = ModelServer(max_batch=cfg["serve_max_batch"], queue_depth=256,
                      default_timeout_ms=600000.0)
    mod.export_serving("resnet", srv)
    # training goes on after the export: the fused step DELETES the
    # weight buffers it is handed, so an export that aliased them
    # would now be serving freed memory ("Array has been deleted")
    train_data.reset()
    for _ in range(2):
        batch = next(train_data)
        mod.forward_backward(batch)
        mod.update()
    mod.get_outputs()[0].wait_to_read()

    x = train_data.data.asnumpy()
    n_req = 1 + cfg["serve_burst"]
    srv.start()
    try:
        t0 = time.perf_counter()
        warmed = srv.warmup("resnet")
        seconds["serve_warmup"] = round(time.perf_counter() - t0, 2)
        _check(len(warmed) == len(srv.stats()["buckets"]),
               "warmup covered %d of %d buckets"
               % (len(warmed), len(srv.stats()["buckets"])))
        before = srv.stats()
        answers = [srv.infer("resnet", {"data": x[0:1]})[0]]
        futs = [srv.infer_async("resnet", {"data": x[i:i + 1]})
                for i in range(1, n_req)]
        answers += [f.result()[0] for f in futs]
        after = srv.stats()
    finally:
        srv.stop(drain=False)

    _check(after["executor_cache"]["misses"]
           == before["executor_cache"]["misses"],
           "ExecutorCache missed after warmup: %s -> %s"
           % (before["executor_cache"], after["executor_cache"]))
    occ0 = before["batches"]["occupancy"]
    deep = {b: o["rows"] - occ0.get(b, {"rows": 0})["rows"]
            for b, o in after["batches"]["occupancy"].items() if b > 1}
    _check(sum(deep.values()) >= 2,
           "no request was served from a bucket above 1: %s" % deep)
    _check(after["requests"]["failed"] == 0
           and after["requests"]["expired"] == 0,
           "serving ledger: %s" % after["requests"])

    # the same weights in an f32 inference Module, bound at the top
    # bucket: what the server's program is, dtype for dtype
    top = cfg["serve_max_batch"]
    exported = srv.registry.get("resnet")
    m32 = mx.mod.Module(mod.symbol, context=mod._context)
    m32.bind(data_shapes=[("data", (top,) + x.shape[1:])],
             for_training=False)
    m32.set_params(exported.arg_params, exported.aux_params)
    ref32 = m32.predict(mx.io.NDArrayIter(x[:top], None, batch_size=top))
    ref32 = ref32.asnumpy().astype(np.float32)

    got = np.stack([np.asarray(a, np.float32).reshape(-1) for a in answers])
    _check(got.shape == (n_req, cfg["num_classes"])
           and np.isfinite(got).all(), "bad answers %s" % (got.shape,))
    worst = {}
    for name, want in (("serve_bf16", ref[:n_req]),
                       ("serve_f32", ref32[:n_req])):
        tol_p, tol_lp = TOL[name]
        dp = np.abs(got - want)
        dlp = np.where(want >= 1e-4, np.abs(
            np.log(np.maximum(got, 1e-30)) - np.log(np.maximum(want, 1e-30))),
            0.0)
        worst[name] = (float(dp.max()), float(dlp.max()))
        _check(dp.max() <= tol_p and dlp.max() <= tol_lp,
               "%s: answers differ from the reference by |dp| %.3g "
               "(bound %g), |dlog p| %.3g (bound %g); per request %s"
               % (name, dp.max(), tol_p, dlp.max(), tol_lp,
                  np.round(dlp.max(-1), 3).tolist()))
        # same top-1, unless the reference is within tolerance of a tie
        picked = want[np.arange(n_req), got.argmax(-1)]
        _check((picked >= want.max(-1) - tol_p).all(),
               "%s: served top-1 %s, reference top-1 %s"
               % (name, got.argmax(-1).tolist(),
                  want.argmax(-1).tolist()))
    _log("%d requests answered (rows by bucket above 1: %s); worst "
         "(|dp|, |dlog p|) vs Module.predict bf16 %s, vs an f32 Module "
         "%s; no cache miss after warmup"
         % (n_req, deep, "(%.2g, %.2g)" % worst["serve_bf16"],
            "(%.2g, %.2g)" % worst["serve_f32"]))


# ---------------------------------------------------------------------------
# four-chip mode
# ---------------------------------------------------------------------------
def phase_four_chip(cfg, platform):
    import gc

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh

    devs = jax.devices()
    _check(len(devs) == 4, "four-chip mode needs 4 devices, jax has %d"
           % len(devs))
    _check(pk._sweep_shard_verdict() is True,
           "graftkern did not prove the sweep shard-safe: the "
           "multi-chip step would fall back to tree_map")

    np.random.seed(cfg["seed"])
    mx.random.seed(cfg["seed"])
    net = vision.get_model(cfg["gluon_net"], classes=cfg["num_classes"])
    # parameters materialize on the host: an eager shape-inference
    # forward on the chip would compile every layer as its own program
    with mx.cpu():
        net.initialize(mx.init.Xavier(), ctx=mx.cpu())
        net(nd.ones((1,) + tuple(cfg["mc_image"]), ctx=mx.cpu()))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = {"learning_rate": 0.01, "momentum": 0.9, "wd": 1e-4}

    def trainer(mesh):
        return ParallelTrainer(net, loss_fn, "sgd", dict(opt), mesh=mesh,
                               zero=2, dtype="bfloat16")

    def batch(n, mesh):
        rng = np.random.RandomState(cfg["seed"] + n)
        ns = NamedSharding(mesh, P(("dp", "fsdp")))
        x = rng.uniform(-1, 1, (n,) + tuple(cfg["mc_image"]))
        y = rng.randint(0, cfg["num_classes"], (n,))
        return (jax.device_put(x.astype(np.float32), ns),
                jax.device_put(y.astype(np.float32), ns))

    compiled = []       # programs compiled so far, after each step

    def run(tr, x, y, steps):
        out = []
        for _ in range(steps):
            out.append(float(tr.step(nd.NDArray(x), nd.NDArray(y))
                             .asnumpy()))
            compiled.append(_jit_compiles())
        return out

    mesh4 = make_mesh(dp=4)
    mesh1 = make_mesh(dp=1, devices=devs[:1])
    t4, t1 = trainer(mesh4), trainer(mesh1)

    # -- loss parity, 4 devices vs 1, same seed, global batch that fits one
    nb = cfg["mc_parity_batch"]
    l4 = run(t4, *batch(nb, mesh4), steps=cfg["mc_steps"])
    l1 = run(t1, *batch(nb, mesh1), steps=cfg["mc_steps"])
    _log("loss dp4 %s" % ["%.4f" % l for l in l4])
    _log("loss dp1 %s" % ["%.4f" % l for l in l1])
    _check(all(math.isfinite(l) for l in l4 + l1), "non-finite loss")
    for i, (a, b) in enumerate(zip(l4, l1)):
        tol = TOL["mc_loss" if i else "mc_loss_first"]
        _check(abs(a - b) <= tol * max(1.0, abs(b)),
               "step %d: dp4 loss %.4f vs dp1 %.4f beyond bf16 "
               "tolerance %g" % (i + 1, a, b, tol))
    del t1
    gc.collect()

    # -- full width: global batch 1024 over 4 chips --------------------------
    x, y = batch(cfg["mc_batch"], mesh4)
    before = _jit_compiles()
    lw = run(t4, x, y, steps=cfg["mc_steps"])
    _check(all(math.isfinite(l) for l in lw), "non-finite loss %s" % lw)
    wide = compiled[-cfg["mc_steps"]:]
    _check(wide[0] > before, "the new batch shape compiled nothing that "
           "mxnet_jit_compiles_total saw (%d programs)" % before)
    _check(wide[-1] == wide[0], "the dp4 step compiled after the first "
           "step at its shape: %s programs after each step" % wide)
    _log("%d program(s) at the global batch's first step, none after"
         % (wide[0] - before))
    _log("loss dp4 global batch %d: %s"
         % (cfg["mc_batch"], ["%.4f" % l for l in lw]))
    leaves = (jax.tree_util.tree_leaves(t4._params)
              + jax.tree_util.tree_leaves(t4._opt_state)
              + jax.tree_util.tree_leaves(t4._resids) + [x, y])
    for leaf in leaves:
        _check(len(leaf.sharding.device_set) == 4,
               "a %s %s array lives on %d device(s)"
               % (leaf.dtype, leaf.shape, len(leaf.sharding.device_set)))
    slots = [l for l in jax.tree_util.tree_leaves(t4._opt_state["fused"])
             if l.ndim == 1]
    _check(slots and all(
        l.sharding.shard_shape(l.shape)[0] * 4 == l.shape[0]
        for l in slots), "ZeRO-2 slots are not 1/4 per chip")
    _check(pk._interpret() is (platform != "tpu"), "interpret mode wrong")
    _check(_kernel_count("fused_sgd_momentum") >= 1,
           "the shard_map'd fused_sgd_momentum sweep never ran")
    stats = [d.memory_stats() for d in devs]
    if all(s and "bytes_in_use" in s for s in stats):
        used = [s["bytes_in_use"] for s in stats]
        _log("bytes in use per device: %s" % used)
        _check(max(used) <= 2 * min(used),
               "memory piled on one device: %s" % used)
    else:
        _check(platform != "tpu", "memory_stats() unavailable on tpu")
        _log("memory_stats() not reported on %s: spread not checked"
             % platform)

    t4.sync_to_block()
    out = t4.forward(nd.NDArray(x))
    out.wait_to_read()
    _check(out.shape == (cfg["mc_batch"], cfg["num_classes"])
           and np.isfinite(out.asnumpy()).all(), "forward after "
           "sync_to_block: %s" % (out.shape,))
    _log("dp4 zero-2 bf16: parity with dp1, 4-device shardings, "
         "shard_map'd sweep counted, sync_to_block + forward all hold")


# ---------------------------------------------------------------------------
def run(cfg, platform="tpu", four_chip=False):
    """Every phase in order; returns the device dict.  ``platform`` is
    what jax must report — anything else exits 2 before any work.
    ``platform="cpu"`` is the tier-1 test entry (toy ``cfg``, kernels in
    interpret mode via ``MXNET_PALLAS_*=1``)."""
    t_start = time.perf_counter()
    dev = describe_device(platform)

    from mxnet_tpu import compile_cache, telemetry
    telemetry.enable()
    place_cache()
    seconds = {}
    if four_chip:
        with _Phase("four-chip", seconds):
            phase_four_chip(cfg, platform)
    else:
        with _Phase("kernels", seconds):
            phase_kernels(cfg, native=platform == "tpu")
        with _Phase("train", seconds):
            mod, train_data = phase_train(cfg, platform, seconds)
        with _Phase("eval", seconds):
            ref = phase_eval(cfg, mod, train_data, seconds)
        with _Phase("serve", seconds):
            phase_serve(cfg, mod, train_data, ref, seconds)
    st = compile_cache.stats()
    seconds["total"] = round(time.perf_counter() - t_start, 2)
    # set-up = the walls that contain compilation
    setup = {k: seconds[k] for k in
             ("kernels", "train_first_step", "eval_first_forward",
              "serve_warmup", "four-chip") if k in seconds}
    _log("compile cache: dir=%s hits=%d misses=%d requests=%d entries=%d "
         "size=%.1f MiB" % (st["dir"], st["hits"], st["misses"],
                            st["requests"], st["entries"],
                            st["size_bytes"] / 2 ** 20))
    _log("summary " + json.dumps({
        "phases_s": seconds, "setup_s": round(sum(setup.values()), 2),
        "setup_parts_s": setup,
        "compile_cache": {k: st[k] for k in
                          ("dir", "hits", "misses", "requests",
                           "entries", "size_bytes")}}))
    return dev


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run the multi-chip mode (needs 4 TPU devices)")
    args = ap.parse_args(argv)
    dev = run(FULL, platform="tpu", four_chip=args.four_chip)
    # only reached when every phase passed
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
