#!/usr/bin/env python3
"""Compile a cell's step for a DESCRIBED v5e (no chip attached) and print
``memory_analysis()`` — how a configuration's depth and batch are chosen
before any chip call, and what the chip's compiler refuses, for free.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile.py --workload <cell> \
        [--set key=value ...] [--reference] [--count] [--dump FILE]

A script for a builder's hands, not a test and not a measurement: nothing
runs, no time comes out of it.  ``--reference`` compiles the plain
reference instead of the program (does it fit the chip once the program
is gone?): its whole loss and gradient, or, where the reference walks
its model in blocks, the programs its ``described_programs`` names.
``--set num_hidden_layers=8 --set batch_size=4`` tries other sizes
without touching the configuration's file.  ``--count`` counts in the
optimized module what the per-layer readers will look for — the
instructions under each class of each of the program's maps
(``telemetry.phases`` ``instruction_*``) and the opcode histogram, which
holds the ``while`` loops and every Pallas call by its kernel's name;
``--dump`` writes the module's text.

The program builds its mesh from real devices and places its own
parameters, so for the described chip this script stands in for
``jax.device_put`` while the trainer is constructed (shapes with
shardings instead of arrays), tells the Pallas wrappers that the target
is a TPU, and answers for ``parallel/attention.py``, which asks
``jax.default_backend()`` before it takes the flash kernels and would
hear "cpu".  Any cell whose driver's class derives from
``drivers/parallel_trainer.py``'s ``Driver`` is covered, and every
reference; the ``module_fit`` step binds through ``mx.tpu()`` contexts
that cannot be described (PERF.md, Open questions).
"""
import argparse
import collections
import json
import os
import re
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
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    ref = cell.reference()
    one = SingleDeviceSharding(devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    n = sum(int(np.prod(s)) for _n, s, _i in ref.leaf_specs(config))
    print("reference: beside what is compiled here it holds its optimizer "
          "state, %.2f GB for SGD's one slot or %.2f GB for Adam's two"
          % (4 * n / 1e9, 8 * n / 1e9))
    with jax.default_matmul_precision("highest"):
        if hasattr(ref, "described_programs"):  # it walks its model in blocks
            for what, lowered in ref.described_programs(config, sds):
                report(lowered.compile(), "reference: %s, float32 highest"
                       % what)
            return
        params = {name: sds(s) for name, s, _i in ref.leaf_specs(config)}
        import traffic
        x, y = traffic.Feed(cell.traffic, config, 0)._draw()

        def grad(p, x, y):
            return jax.value_and_grad(ref.loss_fn)(p, x, y, config, False)

        c = jax.jit(grad).lower(params, sds(x.shape, x.dtype),
                                sds(y.shape, y.dtype)).compile()
    return report(c, "reference loss+grad, float32 highest")


def derives_from_parallel_trainer(driver_cls):
    """``loader.load_module`` gives every import of a file a module of
    its own, so the base is known by the name it is loaded under."""
    return any(c.__name__ == "Driver"
               and c.__module__ == "perfbench_drivers_parallel_trainer"
               for c in driver_cls.__mro__)


def flash_on_the_described_chip():
    """``parallel/attention.py`` ``_flash_eligible`` without its question
    to ``jax.default_backend()``: what it answers on the chip."""
    from mxnet_tpu.ops.pallas_kernels import flash_seq_ok
    from mxnet_tpu.parallel import attention

    def eligible(q, k, causal, q_offset, kv_offset):
        if causal and (q_offset != 0 or kv_offset != 0):
            return False
        return flash_seq_ok(q.shape[1], q.dtype) \
            and flash_seq_ok(k.shape[1], k.dtype)

    attention._flash_eligible = eligible


def count(text):
    """What the per-layer readers will find in the optimized module."""
    import trace_reduce
    from mxnet_tpu.telemetry import phases
    for name in sorted(n for n in dir(phases) if n.startswith("instruction_")):
        classes = collections.Counter(
            str(c) for c in getattr(phases, name)(text).values())
        print("%s: %s" % (name, json.dumps(dict(sorted(classes.items())))))
    lines = [m.group(0).strip() for m in re.finditer(
        r"^\s+(?:ROOT )?%[\w.\-]+ = .*$", text, re.M)]
    opcodes = collections.Counter(trace_reduce.opcode(line) for line in lines)
    # ``while`` counts the loops, ``custom-call:_<kernel>`` the Pallas calls
    print("opcodes (%d instructions): %s"
          % (len(lines), json.dumps(dict(sorted(opcodes.items())))))


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

    flash_on_the_described_chip()
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
    ap.add_argument("--count", action="store_true",
                    help="count scopes, while loops, Pallas calls, opcodes")
    ap.add_argument("--dump", default=None,
                    help="write the optimized module's text here")
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
    elif derives_from_parallel_trainer(cell.driver().Driver):
        text = compile_parallel_trainer(cell, config, devices).as_text()
        if args.count:
            count(text)
        if args.dump:
            with open(args.dump, "w") as f:
                f.write(text)
    else:
        sys.exit("rehearse_compile: no way to describe a chip to driver %r"
                 % config["driver"])
    print("compiled in %.0fs on this host (not a device time)"
          % (time.time() - t0))


if __name__ == "__main__":
    main()
