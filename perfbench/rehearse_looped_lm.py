#!/usr/bin/env python3
"""Compile the looped LM's cell for a DESCRIBED v5e (no chip attached):
how ``num_hidden_layers`` and ``batch_size`` of ``ouro-2.6b-loop4`` were
chosen, and whether the reference's blocks fit once the program is gone.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_looped_lm.py \
        [--set num_hidden_layers=9 ...] [--reference] [--dump FILE]

It calls ``rehearse_compile.py``'s ``compile_parallel_trainer`` (that
file's ``main`` knows one driver's name) after steering one switch the
described chip cannot answer: ``parallel/attention.py`` asks
``jax.default_backend()`` before it takes the flash kernels, and here
that is the CPU.  Then it counts, in the optimized module, what the
per-layer metrics will look for: the ``mx_loop`` / ``mx_exit`` scopes,
jax's ``rematted_computation`` mark, ``while`` loops and Pallas calls.
A script for a builder's hands: nothing runs, no time comes out of it.
"""
import argparse
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import loader  # noqa: E402
import rehearse_compile  # noqa: E402

CELL = "ouro2p6b-train-s2048"


def flash_on_the_described_chip():
    from mxnet_tpu.ops.pallas_kernels import flash_seq_ok
    from mxnet_tpu.parallel import attention

    def eligible(q, k, causal, q_offset, kv_offset):
        if causal and (q_offset != 0 or kv_offset != 0):
            return False
        return flash_seq_ok(q.shape[1], q.dtype) \
            and flash_seq_ok(k.shape[1], k.dtype)

    attention._flash_eligible = eligible


def compile_reference_blocks(cell, config, devices):
    """The reference walks one layer application and one exit at a
    time; its two largest programs are their backward blocks."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    ref = cell.reference()
    one = SingleDeviceSharding(devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    b, t, u = int(config["batch_size"]), int(config["seq_len"]), \
        int(config["hidden_size"])
    shapes = {n: s for n, s, _i in ref.leaf_specs(config)}
    layer = {k: sds(shapes["l0_" + k]) for k in ref.LAYER_LEAVES}
    exit_p = {k: sds(shapes[k]) for k in ref.EXIT_LEAVES}
    x, bt = sds((b, t, u)), sds((b, t))
    fn = ref._blocks(config, False)
    with jax.default_matmul_precision("highest"):
        rehearse_compile.report(
            fn["layer_bwd"].lower(x, layer, x).compile(),
            "reference: one layer application backward, float32 highest")
        rehearse_compile.report(
            fn["exit_bwd"].lower(x, exit_p, bt, (x, bt, bt)).compile(),
            "reference: one exit backward, float32 highest")
    n = sum(math.prod(s) for s in shapes.values())
    passes = int(config["total_ut_steps"])
    print("beside them the reference holds parameters, gradients and "
          "Adam's two slots: %.2f GB, and %d states of %.3f GB"
          % (16 * n / 1e9, passes * (int(config["num_hidden_layers"]) + 1),
             4 * b * t * u / 1e9))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--set", action="append", default=[],
                    metavar="key=value")
    ap.add_argument("--reference", action="store_true")
    ap.add_argument("--dump", default=None,
                    help="write the optimized module's text here")
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        sys.exit("rehearse_looped_lm: run with JAX_PLATFORMS=cpu (it must "
                 "never take a chip)")
    os.environ.setdefault("MXNET_COMPILE_CACHE_DIR", "")
    cell = loader.Bench(ROOT).cell(CELL)
    config = cell.config_for()
    for kv in args.set:
        k, v = kv.split("=", 1)
        config[k] = json.loads(v)
    devices = rehearse_compile.described(cell.chips)
    t0 = time.time()
    if args.reference:
        compile_reference_blocks(cell, config, devices)
    else:
        flash_on_the_described_chip()
        compiled = rehearse_compile.compile_parallel_trainer(
            cell, config, devices)
        text = compiled.as_text()
        from mxnet_tpu.telemetry import phases
        found = phases.instruction_loop_parts(text)
        count = lambda pred: sum(1 for v in found.values() if pred(v))
        print("instructions: %d under mx_loop, %d under mx_exit, %d "
              "rematerialised; %d while loops; %d layers x %d passes"
              % (count(lambda v: v[0] == phases.LOOP),
                 count(lambda v: v[0] == phases.EXIT),
                 count(lambda v: v[1]), text.count(" while("),
                 int(config["num_hidden_layers"]),
                 int(config["total_ut_steps"])))
        if args.dump:
            with open(args.dump, "w") as f:
                f.write(text)
    print("compiled in %.0fs on this host (not a device time)"
          % (time.time() - t0))


if __name__ == "__main__":
    main()
