"""Benchmark: ResNet-50 training throughput through the north-star entry
script (example/image-classification/train_imagenet.py --kv-store tpu).

Baseline (BASELINE.md / docs/faq/perf.md:185): 181.53 img/s training
ResNet-50 batch 32 on 1x P100.  Runs on a TPU and nowhere else: the
ONE JSON line carries the device the training child reported
(platform, device_kind, count) and the run fails unless that platform
is ``tpu``.

Methodology matches the reference's perf.md benchmark: synthetic data
(--benchmark 1), Speedometer samples/sec readings, first reading
discarded (contains compile time), median of the rest reported.
The whole train step — fwd + bwd + SGD-momentum update — is ONE donated
XLA program (executor fused step, kvstore=tpu), bf16 compute / fp32
master params.

One process for each chip: a chip belongs to one process at a time, so
this parent NEVER imports jax (nor mxnet_tpu) and runs its children one
at a time — each child has the chip to itself and has exited before the
next starts.  The CPU-only children (the static cost trace, the
sharded-sweep microbench) are pinned to ``JAX_PLATFORMS=cpu``.

Robustness contract (VERDICT r2 item 1): this script never hangs.
Every subprocess runs in its own session under a hard wall-clock limit
with a process-group kill, and a child that was killed or exited
non-zero is a failed leg whatever it printed first.  On any failure the
output is still ONE JSON line — with an ``error`` field and a non-zero
exit — never an rc=124 with an empty tail.
"""
import json
import os
import re
import sys

from _proc_util import run_bounded as _run_bounded

BASELINE_IMG_S = 181.53
BATCH = 256
SPEED_RE = re.compile(r"Speed:\s*([0-9.]+)\s*samples/sec")
# common/fit.py's header line: what jax gave the training child
DEVICE_RE = re.compile(
    r"device platform=(\S+) kind=(.+?) count=(\d+)\s*$", re.M)
HARD_TIMEOUT_S = 900  # healthy run finishes in ~3-4 min incl. compiles
HERE = os.path.dirname(os.path.abspath(__file__))


def _fail(reason, code):
    print(json.dumps({
        "metric": "resnet50_train_img_per_sec",
        "value": 0.0,
        "unit": "img/s",
        "vs_baseline": 0.0,
        "error": reason,
    }))
    sys.stdout.flush()
    raise SystemExit(code)



def _measure(num_batches, disp_batches, timeout_s, extra_env=None):
    """One bounded training run.

    Returns (median img/s, device, None) on success — ``device`` is the
    ``{"platform", "kind", "count"}`` the child logged — else
    (None, None, (message, rc)): rc 3 for crash/timeout, rc 5 for "ran
    but no Speedometer output", rc 4 for "did not run on a TPU"
    (distinct codes the harness diagnostics key on).
    """
    script = os.path.join(HERE, "example", "image-classification",
                          "train_imagenet.py")
    cmd = [sys.executable, "-u", script,
           "--benchmark", "1", "--kv-store", "tpu",
           "--network", "resnet", "--num-layers", "50",
           "--batch-size", str(BATCH), "--dtype", "bfloat16",
           "--num-epochs", "1", "--num-batches", str(num_batches),
           "--disp-batches", str(disp_batches)]
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    rc, text = _run_bounded(cmd, env, timeout_s, cwd=HERE)
    speeds = [float(m.group(1)) for m in SPEED_RE.finditer(text)]
    expected = num_batches // disp_batches
    if rc != 0:
        # killed or crashed: whatever it printed first, a child that
        # did not run to a clean end is not a measurement
        sys.stderr.write(text[-4000:])
        how = ("exceeded %ds wall clock (killed)" % timeout_s
               if rc is None else "exited rc=%s" % rc)
        return None, None, (
            "train_imagenet.py %s with %d/%d Speedometer readings"
            % (how, len(speeds), expected), 3)
    if not speeds:
        sys.stderr.write(text[-4000:])
        return None, None, ("no Speedometer output parsed", 5)
    m = DEVICE_RE.search(text)
    if m is None:
        sys.stderr.write(text[-4000:])
        return None, None, ("the training child logged no device line", 4)
    device = {"platform": m.group(1), "kind": m.group(2),
              "count": int(m.group(3))}
    if device["platform"] != "tpu":
        return None, None, (
            "the training child ran on %(platform)s (%(kind)s x%(count)d)"
            ", not on a TPU; no number is reported" % device, 4)
    steady = sorted(speeds[1:] if len(speeds) > 1 else speeds)
    return steady[len(steady) // 2], device, None


def _ir_cost_columns():
    """Static price of the measured step program (graftir cost model,
    ``mxnet_tpu/analysis/ir/bench.py``): the resnet50 b256 bf16 fused
    step is abstractly traced ON CPU in a bounded subprocess (nothing
    compiles, the chip is left to the training child) and its
    predicted flops/bytes ride the primary JSON line next to the
    measured img/s — a regression in either column points at the
    other.  Runs BEFORE the measurement, and a trace that fails fails
    the run: a silently missing column is how this leg stayed broken
    for a jax upgrade."""
    # same truthiness set as config.py's registered bool (base._TRUE):
    # MXNET_IR=off/no must skip here too, not only in lint --all
    if os.environ.get("MXNET_IR", "1") not in ("1", "true", "True",
                                               "yes", "on"):
        return {"ir_skipped": "MXNET_IR off"}
    cmd = [sys.executable, "-m", "mxnet_tpu.analysis.ir.bench"]
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # a trace needs no chip
    rc, text = _run_bounded(cmd, env, 240, cwd=HERE)
    if rc == 0:
        for line in reversed(text.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                doc = json.loads(line)
                if "ir_predicted_flops" in doc:
                    return {k: doc[k] for k in
                            ("ir_predicted_flops", "ir_predicted_bytes",
                             "ir_program") if k in doc}
                break
    sys.stderr.write(text[-4000:])
    _fail("static cost trace (mxnet_tpu.analysis.ir.bench) rc=%s with "
          "no cost columns" % (rc,), 6)


_SHARDED_SWEEP_SRC = r"""
import json, os, time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.optimizer import PureAdam

mesh = make_mesh(dp=8)
ns = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
rng = np.random.RandomState(9)
sizes = [8 * 8192, 8 * 4096]
mk = lambda: {"b%d" % i: jax.device_put(
                  jnp.asarray(rng.randn(n).astype(np.float32)), ns)
              for i, n in enumerate(sizes)}
params, grads = mk(), mk()
opt = PureAdam(1e-3, wd=0.01)
state = opt.init(params, {k: ns for k in params})

def bench(knob, mesh_arg, iters=20):
    os.environ["MXNET_PALLAS_FUSED_OPT"] = knob
    step = jax.jit(lambda p, g, s: opt.apply(p, g, s, flat=True,
                                             mesh=mesh_arg))
    p, s = step(params, grads, state)          # compile outside timing
    jax.block_until_ready(p)
    t0 = time.perf_counter()
    for _ in range(iters):
        p, s = step(p, grads, s)
    jax.block_until_ready(p)
    return (time.perf_counter() - t0) / iters * 1e6

us_f = bench("1", mesh)    # shard_map-wrapped fused sweep
us_t = bench("0", None)    # per-array tree_map oracle
print(json.dumps({"sharded_sweep_platform": jax.devices()[0].platform,
                  "sharded_fused_us_per_step": round(us_f, 1),
                  "sharded_treemap_us_per_step": round(us_t, 1),
                  "sharded_treemap_vs_fused": round(us_t / us_f, 3)}))
"""


def _sharded_sweep_rider(timeout_s):
    """The ZeRO sharded-sweep A/B: dp8 shard_map-wrapped fused
    optimizer vs the tree_map oracle, a bounded CPU microbench.  The
    imagenet workload trains through kvstore/Module.fit, not
    ``ParallelTrainer``, so the multi-chip sweep (graftkern-gated,
    ``mesh_sweep_safe``) cannot ride the img/s legs — this measures it
    directly on an 8-device virtual mesh.  Bit-parity is the drill's
    bar (``fault/drill.py fused_sweep_parity_drill``); this leg records
    the timing ratio."""
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    rc, text = _run_bounded([sys.executable, "-c", _SHARDED_SWEEP_SRC],
                            env, timeout_s, cwd=HERE)
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                break
    return {"sharded_sweep_error": "microbench rc=%s with no JSON tail"
                                   % (rc,)}


def _run_tune_sweep(journal, db_dir=None, measure_timeout=240.0):
    """The grafttune sweep behind ``bench.py --tune`` — split out so
    the plumbing tests can stub the whole driver and exercise only the
    BENCH_TUNE.json contract."""
    sys.path.insert(0, HERE)
    from mxnet_tpu.tune import (default_context, default_space,
                                measure_candidate, run_sweep)
    space = default_space()
    context = default_context()
    return run_sweep(
        space, context, journal=journal, db_dir=db_dir,
        measure=lambda cand: measure_candidate(
            cand, space=space, timeout=measure_timeout))


def tune_main():
    """``bench.py --tune``: a budgeted grafttune sweep on the reference
    deployment context -> ``BENCH_TUNE.json`` (default-vs-tuned step
    time, proposed/pruned/measured counts, the prune-rule histogram)
    plus ONE stdout JSON line.  Candidate budget and seed ride the
    registered ``MXNET_TUNE_BUDGET``/``MXNET_TUNE_SEED`` knobs; the
    wall bound is ``MXNET_BENCH_SECONDARY_BUDGET_S`` (the leg is
    skipped, not killed, when it cannot fit)."""
    try:
        budget_s = float(os.environ.get(
            "MXNET_BENCH_SECONDARY_BUDGET_S", "600"))
    except ValueError:
        budget_s = 600.0
    path = os.path.join(HERE, "BENCH_TUNE.json")
    if budget_s < 60:
        out = {"tune_skipped": "secondary wall budget exhausted"}
    else:
        journal = os.path.join(HERE, "BENCH_TUNE.journal.jsonl")
        summary = _run_tune_sweep(
            journal=journal, measure_timeout=min(240.0, budget_s))
        out = {k: summary[k] for k in
               ("proposed", "pruned", "admissible", "measured",
                "failed", "duplicates", "budget", "seed")}
        out["prune_rules"] = dict(summary["prune_rules"])
        default_us = summary.get("default_us_per_step")
        out["default_us_per_step"] = default_us
        winner = summary.get("winner")
        if winner is not None:
            # where the clock ran: a CPU/interpreter time is not a
            # device metric (mxnet_tpu/tune/measure.py)
            out["measured_on"] = {"platform": winner.get("platform"),
                                  "interpret": winner.get("interpret")}
            out["tuned_us_per_step"] = winner["us_per_step"]
            out["tuned_candidate"] = winner["candidate"]
            out["stored"] = summary.get("stored")
            if default_us:
                out["tuned_vs_default"] = round(
                    winner["us_per_step"] / default_us, 3)
    # side file first, then the one stdout line — same ordering
    # discipline as the primary leg
    with open(path, "w") as f:
        json.dump(out, f)
    print(json.dumps(out))
    sys.stdout.flush()


def main():
    import time

    # telemetry rides the primary leg: the training subprocess emits
    # per-step JSONL and writes a Prometheus exposition at exit, so every
    # BENCH capture carries the why (compiles, transfer bytes, io stalls)
    # alongside the img/s.  Near-zero overhead: host-side counters only.
    for stale in ("BENCH_STEPS.jsonl", "BENCH_TELEMETRY.prom"):
        try:
            os.unlink(os.path.join(HERE, stale))
        except OSError:
            pass
    telemetry_env = {
        "MXNET_TELEMETRY": "1",
        "MXNET_TELEMETRY_STEP_LOG": os.path.join(HERE,
                                                 "BENCH_STEPS.jsonl"),
        "MXNET_TELEMETRY_STEP_INTERVAL": "1",
        "MXNET_TELEMETRY_PROM_FILE": os.path.join(HERE,
                                                  "BENCH_TELEMETRY.prom"),
    }
    # static cost columns are computed BEFORE the measurement (CPU
    # subprocess, bounded, ended before the chip's child starts): a
    # wedged trace burns budget up front, but the measurement -> print
    # gap below stays immediate
    ir_cols = _ir_cost_columns()
    img_s, device, err = _measure(210, 20, HARD_TIMEOUT_S,
                                  extra_env=telemetry_env)
    if err is not None:
        _fail(err[0], err[1])
    # the ONE stdout JSON line goes out IMMEDIATELY: nothing that runs
    # after this (the layout experiments) can void a successful
    # primary measurement
    out = {
        "metric": "resnet50_train_img_per_sec",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "device": device,
    }
    out.update(ir_cols)
    print(json.dumps(out))
    sys.stdout.flush()
    # secondary: the layout/MFU experiment legs (docs/faq/perf.md) run
    # after it, one child at a time, recorded INCREMENTALLY to side
    # files so stdout stays one line and a mid-leg kill loses at most
    # one leg.  A total wall budget bounds the invocation under any
    # external cap (the r2 driver kill was an rc=124): legs that no
    # longer fit are marked skipped — the session-measured values stay
    # in git history either way.
    try:
        budget = float(os.environ.get(
            "MXNET_BENCH_SECONDARY_BUDGET_S", "600"))
    except ValueError:
        budget = 600.0  # malformed knob must not void the secondaries
    t_secondary = time.time()  # budget covers SECONDARY legs only
    # a leg needs at least this much of the budget left to start (a
    # healthy leg finishes well within it), and its subprocess timeout
    # is clamped to what remains so the whole invocation stays bounded
    MIN_LEG_S = 120

    def leg_timeout():
        left = budget - (time.time() - t_secondary)
        return left if left >= MIN_LEG_S else None

    if os.environ.get("MXNET_BENCH_SKIP_NHWC") != "1":
        ab = {"nchw_img_per_sec": round(img_s, 2)}
        to = leg_timeout()
        if to is not None:
            nhwc, _dev, nhwc_err = _measure(
                110, 20, to, extra_env={"MXNET_CONV_LAYOUT": "NHWC"})
            if nhwc is not None:
                ab["nhwc_img_per_sec"] = round(nhwc, 2)
                ab["nhwc_vs_nchw"] = round(nhwc / img_s, 3)
            else:
                ab["nhwc_error"] = nhwc_err[0]
        else:
            ab["nhwc_skipped"] = "secondary wall budget exhausted"
        with open(os.path.join(HERE, "BENCH_NHWC.json"), "w") as f:
            json.dump(ab, f)
    if os.environ.get("MXNET_BENCH_SKIP_RIDERS") != "1":
        riders = {"baseline_img_per_sec": round(img_s, 2)}
        riders_path = os.path.join(HERE, "BENCH_RIDERS.json")
        for name, env in (
                # pallas A/B: primary leg runs with the mega-kernel
                # pass ON (default); this leg turns the whole family
                # off — fused-vs-unfused is value/pallas_unfused
                ("pallas_unfused", {"MXNET_PALLAS_FUSED_OPT": "0",
                                    "MXNET_PALLAS_NORM": "0",
                                    "MXNET_PALLAS_SOFTMAX": "0",
                                    "MXNET_PALLAS_BN_RELU": "0"}),
                ("stem_s2d", {"MXNET_STEM_SPACE_TO_DEPTH": "1"}),
                ("unfused_metric", {"MXNET_FUSED_METRIC": "0"})):
            to = leg_timeout()
            if to is None:
                riders[name + "_skipped"] = \
                    "secondary wall budget exhausted"
            else:
                v, _dev, v_err = _measure(110, 20, to, extra_env=env)
                if v is not None:
                    riders[name + "_img_per_sec"] = round(v, 2)
                    riders[name + "_vs_baseline"] = round(v / img_s, 3)
                else:
                    riders[name + "_error"] = v_err[0]
            # one incremental write per leg: a mid-run kill loses at
            # most the in-flight leg, skip markers included
            with open(riders_path, "w") as f:
                json.dump(riders, f)
        # sharded-sweep leg: not an img/s run — the trainer here goes
        # through kvstore, so the ZeRO shard_map sweep gets its own
        # bounded dp8 CPU microbench (fused vs tree_map step time)
        to = leg_timeout()
        if to is None:
            riders["sharded_sweep_skipped"] = \
                "secondary wall budget exhausted"
        else:
            riders.update(_sharded_sweep_rider(min(to, 300)))
        with open(riders_path, "w") as f:
            json.dump(riders, f)


if __name__ == "__main__":
    if "--tune" in sys.argv[1:]:
        tune_main()
    else:
        main()
