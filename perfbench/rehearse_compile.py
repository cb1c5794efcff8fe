#!/usr/bin/env python3
"""Compile a cell's step for a DESCRIBED v5e (no chip attached) and print
``memory_analysis()`` — how a configuration's depth and batch are chosen
before any chip call, and what the chip's compiler refuses, for free.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile.py --workload <cell> \
        [--set key=value ...] [--reference]

A script for a builder's hands, not a test and not a measurement: nothing
runs, no time comes out of it.  ``--reference`` compiles the plain
reference's step instead of the program's (does it fit the chip once the
program is gone?).  ``--set num_hidden_layers=8 --set batch_size=4``
tries other sizes without touching the configuration's file.

The program builds its mesh from real devices and places its own
parameters, so for the described chip this script stands in for
``jax.device_put`` while the trainer is constructed (shapes with
shardings instead of arrays) and tells the Pallas wrappers that the
target is a TPU.  Only ``parallel_trainer`` cells and references are
covered; the ``module_fit`` step binds through ``mx.tpu()`` contexts
that cannot be described (PERF.md, Open questions).
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import loader  # noqa: E402


def described(chips):
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return list(topo.devices)[:chips]


def report(compiled, what):
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({
        "what": what,
        "temp_gb": m.temp_size_in_bytes / 1e9,
        "argument_gb": m.argument_size_in_bytes / 1e9,
        "output_gb": m.output_size_in_bytes / 1e9,
        "alias_gb": m.alias_size_in_bytes / 1e9,
        "code_gb": m.generated_code_size_in_bytes / 1e9,
        "total_gb_per_device": total / 1e9}))
    return compiled


def compile_reference(cell, config, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    ref = cell.reference()
    one = SingleDeviceSharding(devices[0])
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
              for n, s, _i in ref.leaf_specs(config)}
    import numpy as np
    import traffic
    feed = traffic.Feed(cell.traffic, config, 0)
    x, y = feed._draw()
    xs = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
    ys = jax.ShapeDtypeStruct(y.shape, y.dtype, sharding=one)

    def grad(p, x, y):
        return jax.value_and_grad(ref.loss_fn)(p, x, y, config, False)

    with jax.default_matmul_precision("highest"):
        c = jax.jit(grad).lower(params, xs, ys).compile()
    n = sum(int(np.prod(s)) for _n, s, _i in ref.leaf_specs(config))
    print("reference: loss and gradients only; add its optimizer state, "
          "%.2f GB for SGD's one slot or %.2f GB for Adam's two"
          % (4 * n / 1e9, 8 * n / 1e9))
    return report(c, "reference loss+grad, float32 highest")


def compile_parallel_trainer(cell, config, devices):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.parallel import mesh as mesh_mod

    class Abstract(jax.ShapeDtypeStruct):
        @property
        def nbytes(self):
            return int(np.prod(self.shape)) * self.dtype.itemsize

        @property
        def ndim(self):
            return len(self.shape)

    real_put = jax.device_put

    def fake_put(x, sharding=None, **kw):
        if sharding is None or not hasattr(sharding, "mesh"):
            return real_put(x, sharding, **kw)
        return Abstract(x.shape, x.dtype, sharding=sharding)

    driver = cell.driver().Driver(config, devices, rehearse=True)
    weights = {n: np.zeros(s, np.float32)
               for n, s, _i in cell.reference().leaf_specs(config)}
    jax.device_put = fake_put
    try:
        driver.build(weights)
    finally:
        jax.device_put = real_put
    tr = driver.trainer
    pk._on_tpu = lambda: True       # the target, not this host
    import traffic
    x, y = traffic.Feed(cell.traffic, config, 0)._draw()
    jit_step, args = tr.step_callable(x.shape, y.shape, dtype=x.dtype)
    args = list(args)
    key = args[5]
    args[5] = jax.ShapeDtypeStruct(key.shape, key.dtype,
                                   sharding=NamedSharding(tr.mesh, P()))
    with mesh_mod.mesh_scope(tr.mesh):
        c = jit_step.lower(*args).compile()
    text = c.as_text()
    print("pallas kernels in the program: %d tpu_custom_call; collectives: "
          "%d all-reduce, %d reduce-scatter, %d all-gather"
          % (text.count("tpu_custom_call"), text.count(" all-reduce("),
             text.count(" reduce-scatter("), text.count(" all-gather(")))
    return report(c, "ParallelTrainer step, %d chip(s)" % len(devices))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="key=value")
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        sys.exit("rehearse_compile: run with JAX_PLATFORMS=cpu (it must "
                 "never take a chip)")
    os.environ.setdefault("MXNET_COMPILE_CACHE_DIR", "")
    bench = loader.Bench(ROOT)
    cell = bench.cell(args.workload)
    config = cell.config_for()
    for kv in args.set:
        k, v = kv.split("=", 1)
        config[k] = json.loads(v)
    devices = described(cell.chips)
    t0 = time.time()
    if args.reference:
        compile_reference(cell, config, devices)
    elif config["driver"] == "parallel_trainer":
        compile_parallel_trainer(cell, config, devices)
    else:
        sys.exit("rehearse_compile: no way to describe a chip to driver %r"
                 % config["driver"])
    print("compiled in %.0fs on this host (not a device time)"
          % (time.time() - t0))


if __name__ == "__main__":
    main()
