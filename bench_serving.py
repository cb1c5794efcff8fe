"""Benchmark: serving throughput/latency through mxnet_tpu.serving.

The ISSUE-2 artifact of record: requests/s and p99 latency at client
concurrency 1 / 8 / 64 against the model-zoo ResNet
(example/image-classification/symbols/resnet.py, cifar-style
ResNet-20), compared to the SEQUENTIAL single-request ``Predictor``
baseline — the deployment surface this subsystem replaces.  The
acceptance bar is batched throughput >= 2x sequential at concurrency
64; the win comes entirely from the micro-batcher filling deep shape
buckets while the baseline runs 1-row programs back-to-back.

Since ISSUE 6 the harness also measures the restart story: a
**warm-restart leg** runs ``warmup()`` in two fresh subprocesses
(``--warmup-probe``) sharing one persistent compile cache dir + warmup
manifest — the first cold (empty cache), the second warm (pre-
populated, manifest-replayed) — and records ``warmup_cold_s`` /
``warmup_warm_s`` as first-class fields (acceptance: warm <= 0.5x
cold on the 5-bucket ladder).  A chip belongs to one process at a
time, so this leg runs FIRST, before the parent touches jax: each
probe child has the device to itself and has exited before the next
process needs it.  Their shared cache is one fixed-name directory
under the cache root (``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``), emptied at the start of the cold leg — the
path is part of jax's cache key, so a temp name could never hit.

Since ISSUE 15 there is also a **multi-tenant leg** (``--multitenant``
runs it standalone and merges into BENCH_SERVING.json): two tenant
models under skewed load with per-model quotas and one injected-POISON
canary (``fault.drill.multitenant_soak`` — the NaN fault kind at
``serving.canary.execute`` scoped to the victim), recording per-tenant
throughput/p99, the canary rollback latency, and the isolation
evidence (zero cross-tenant evictions, per-tenant exactly-once
ledgers, quotas respected).

Since ISSUE 18 a **tracing A/B leg** (``--tracing`` standalone)
measures the graftrace request-tracing cost: the same concurrency-8
burst with tracing disarmed vs armed at the default tail-sample rate,
recording both throughputs and asserting the armed overhead stays
within 3% req/s (the disarmed path is one boolean check per seam).

Methodology: warmup excluded from measurement (every
bucket compiled by ``warmup()`` before the clock starts), ONE JSON
line on stdout win or lose, details written incrementally to
BENCH_SERVING.json.  The stdout line and the JSON file carry the
device jax reported (platform, device_kind, count): a number taken on
``cpu`` is a host number, never a device metric, and only the relative
claim (batched vs sequential on the SAME device) carries across
platforms.  Small hosts are noisy (the capture box has 2
cores shared by 64 client threads), so each number is a multi-pass
reading: the sequential baseline is the median of 3 passes, each
serving leg the better of 2 (first pass carries thread/cache
warm-in); all passes are recorded in the JSON.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "example", "image-classification",
                                "symbols"))

NUM_CLASSES = 10
IMAGE_SHAPE = (3, 32, 32)
NUM_LAYERS = 20           # cifar-style model-zoo ResNet-20
MAX_BATCH = 16
SEQ_REQUESTS = 64
PER_CLIENT = {1: 64, 8: 32, 64: 8}   # requests per client thread
OUT_PATH = os.path.join(HERE, "BENCH_SERVING.json")


def _fail(reason, code):
    print(json.dumps({
        "metric": "serving_resnet_req_per_sec_c64",
        "value": 0.0,
        "unit": "req/s",
        "vs_sequential": 0.0,
        "error": reason,
    }))
    sys.stdout.flush()
    raise SystemExit(code)


def _device():
    """The device every number of this process was taken on."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _build_model():
    """Model-zoo ResNet-20 with randomly initialized params (synthetic
    weights: serving throughput does not depend on what the weights
    converged to)."""
    import resnet as resnet_zoo

    import mxnet_tpu as mx
    symb = resnet_zoo.get_symbol(NUM_CLASSES, NUM_LAYERS,
                                 ",".join(str(d) for d in IMAGE_SHAPE))
    arg_shapes, _, aux_shapes = symb.infer_shape(
        data=(1,) + IMAGE_SHAPE)
    rng = np.random.RandomState(0)
    arg_params, aux_params = {}, {}
    for name, shp in zip(symb.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith(("_gamma",)):
            arr = np.ones(shp, np.float32)
        elif name.endswith(("_beta", "_bias")):
            arr = np.zeros(shp, np.float32)
        else:
            arr = (rng.randn(*shp) * 0.05).astype(np.float32)
        arg_params[name] = mx.nd.array(arr)
    for name, shp in zip(symb.list_auxiliary_states(), aux_shapes):
        arr = np.ones(shp, np.float32) if name.endswith("_moving_var") \
            else np.zeros(shp, np.float32)
        aux_params[name] = mx.nd.array(arr)
    return symb, arg_params, aux_params


def _percentile(lat_ms, q):
    return round(float(np.percentile(np.asarray(lat_ms), q)), 2)


def _measure_sequential(symb, arg_params, aux_params):
    """The pre-serving deployment path: one Predictor, one request at a
    time, batch 1 — what c_predict_api callers do today."""
    import mxnet_tpu as mx
    pred = mx.Predictor.from_parts(symb, arg_params, aux_params,
                                   {"data": (1,) + IMAGE_SHAPE})
    rng = np.random.RandomState(1)
    x = rng.rand(1, *IMAGE_SHAPE).astype(np.float32)
    for _ in range(3):                       # compile + settle
        pred.forward(data=x)
        pred.get_output(0).asnumpy()
    lat = []
    t0 = time.perf_counter()
    for _ in range(SEQ_REQUESTS):
        t1 = time.perf_counter()
        pred.forward(data=x)
        pred.get_output(0).asnumpy()
        lat.append((time.perf_counter() - t1) * 1000.0)
    wall = time.perf_counter() - t0
    pred.free()
    return {"requests": SEQ_REQUESTS,
            "req_per_sec": round(SEQ_REQUESTS / wall, 2),
            "p50_ms": _percentile(lat, 50), "p99_ms": _percentile(lat, 99),
            "wall_s": round(wall, 2)}


def _measure_concurrency(srv, concurrency, per_client):
    lat, errors = [], []
    lock = threading.Lock()
    barrier = threading.Barrier(concurrency + 1)

    def client(tid):
        rng = np.random.RandomState(1000 + tid)
        mine = []
        barrier.wait()
        for _ in range(per_client):
            x = rng.rand(1, *IMAGE_SHAPE).astype(np.float32)
            t1 = time.perf_counter()
            try:
                srv.infer("resnet", {"data": x}, timeout_ms=300000.0)
            except Exception as exc:   # noqa: BLE001 — recorded, not fatal
                with lock:
                    errors.append(repr(exc))
                return
            mine.append((time.perf_counter() - t1) * 1000.0)
        with lock:
            lat.extend(mine)

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(concurrency)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        return {"concurrency": concurrency, "error": errors[0]}
    total = concurrency * per_client
    return {"concurrency": concurrency, "requests": total,
            "req_per_sec": round(total / wall, 2),
            "p50_ms": _percentile(lat, 50), "p99_ms": _percentile(lat, 99),
            "wall_s": round(wall, 2)}


def _warmup_probe():
    """Child mode: time ONE warmup() in a fresh process.

    The parent points MXNET_COMPILE_CACHE_DIR / _MANIFEST at a shared
    temp location; run 1 (empty cache) is the cold restart, run 2
    (populated cache + manifest replay) is the warm restart.  Prints
    one JSON line and exits — model build and jax import stay OUTSIDE
    the timed window, exactly like the parent's warmup_s."""
    from mxnet_tpu import compile_cache
    from mxnet_tpu.serving import ModelServer

    symb, arg_params, aux_params = _build_model()
    srv = ModelServer(max_batch=MAX_BATCH, queue_depth=1024,
                      default_timeout_ms=300000.0)
    srv.add_model("resnet", symb, arg_params, aux_params,
                  {"data": (1,) + IMAGE_SHAPE})
    t0 = time.perf_counter()
    warmed = srv.warmup_from_manifest("resnet")
    source = "manifest"
    if not warmed:               # first boot: no manifest yet
        warmed = srv.warmup("resnet")
        source = "ladder"
    wall = time.perf_counter() - t0
    print(json.dumps({
        "warmup_s": round(wall, 3),
        "device": _device(),
        "warmed": len(warmed),
        "source": source,
        "compile_cache": compile_cache.stats(),
    }))
    sys.stdout.flush()


def _measure_warm_restart():
    """Parent side of the warm-restart leg: two fresh subprocesses
    sharing one compile cache dir + manifest.  Must run before this
    process touches jax (module docstring)."""
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(HERE, ".jax_cache")
    tmp = os.path.join(root, "bench_serving_restart")
    shutil.rmtree(tmp, ignore_errors=True)      # the cold leg starts empty
    env = dict(os.environ)
    # placed from outside, as far as the children can tell
    env["JAX_COMPILATION_CACHE_DIR"] = tmp
    env["MXNET_COMPILE_CACHE_MANIFEST"] = os.path.join(tmp, "warmup.json")
    legs = {}
    try:
        for leg in ("cold", "warm"):
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--warmup-probe"],
                env=env, capture_output=True, text=True, timeout=900)
            if proc.returncode != 0:
                raise RuntimeError(
                    "%s probe failed rc=%d: %s"
                    % (leg, proc.returncode, proc.stderr[-800:]))
            legs[leg] = json.loads(proc.stdout.strip().splitlines()[-1])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return legs


def _measure_multitenant():
    """The ISSUE-15 leg: the multi-tenant soak drill IS the
    measurement — small models (throughput numbers are about the
    batcher/quota/canary machinery, not conv flops), skewed load (3
    victim clients vs 1 bystander), tenant-scoped faults and one
    NaN-poisoned canary."""
    from mxnet_tpu.fault.drill import multitenant_soak
    return multitenant_soak(duration_s=8.0)


def _multitenant_only():
    """--multitenant: run just the multi-tenant leg and merge it into
    an existing BENCH_SERVING.json (or a fresh skeleton)."""
    try:
        with open(OUT_PATH) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    leg = _measure_multitenant()
    result["multitenant"] = leg
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({
        "metric": "serving_multitenant_rollback_s",
        "device": _device(),
        "value": leg["canary"]["rollback_wall_s"],
        "unit": "s",
        "victim_req_per_sec": leg["per_tenant"]["tenantA"]["req_per_sec"],
        "bystander_req_per_sec":
            leg["per_tenant"]["tenantB"]["req_per_sec"],
        "bystander_p99_ms": leg["per_tenant"]["tenantB"]["p99_ms"],
        "faults_injected": leg["faults_injected"]["total"],
    }))
    sys.stdout.flush()


def _measure_tracing_ab(symb, arg_params, aux_params):
    """The ISSUE-18 leg: the same concurrency-8 burst against the
    bench's model of record with tracing disarmed vs armed
    (tail-sampled at the default rate, spans exported between passes).
    Acceptance: armed throughput within 3% of disarmed — the off path
    is one boolean per seam, and the armed per-request bookkeeping
    must disappear into real model time."""
    from mxnet_tpu.serving import ModelServer
    from mxnet_tpu.telemetry import tracing

    srv = ModelServer(max_batch=MAX_BATCH, queue_depth=1024,
                      default_timeout_ms=300000.0)
    srv.add_model("resnet", symb, arg_params, aux_params,
                  {"data": (1,) + IMAGE_SHAPE})
    srv.start()
    srv.warmup("resnet")

    conc, per_client, passes = 8, 16, 3

    def burst():
        lat = []
        lock = threading.Lock()
        barrier = threading.Barrier(conc + 1)

        def client(tid):
            crng = np.random.RandomState(2000 + tid)
            mine = []
            barrier.wait()
            for _ in range(per_client):
                x = crng.rand(1, *IMAGE_SHAPE).astype(np.float32)
                t1 = time.perf_counter()
                srv.infer("resnet", {"data": x}, timeout_ms=300000.0)
                mine.append((time.perf_counter() - t1) * 1000.0)
            with lock:
                lat.extend(mine)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(conc)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return {"req_per_sec": round(conc * per_client / wall, 2),
                "p99_ms": _percentile(lat, 99)}

    trace_dir = tempfile.mkdtemp(prefix="mxnet-bench-trace-")
    legs = {"off": [], "on": []}
    try:
        burst()                          # warm-in pass, discarded
        for _ in range(passes):          # interleaved A/B: shared drift
            tracing.disable()
            legs["off"].append(burst())
            tracing.reset()
            tracing.enable(trace_dir=trace_dir)  # default tail sample
            legs["on"].append(burst())
            tracing.export_jsonl()
        sample = tracing.stats()["sample"]
    finally:
        tracing.disable()
        tracing.reset()
        srv.stop(drain=False)
        srv.cache.clear()
        shutil.rmtree(trace_dir, ignore_errors=True)
    best_off = max(p["req_per_sec"] for p in legs["off"])
    best_on = max(p["req_per_sec"] for p in legs["on"])
    overhead = round((best_off - best_on) / best_off * 100.0, 2)
    leg = {
        "concurrency": conc,
        "requests_per_pass": conc * per_client,
        "sample": sample,
        "off": {"req_per_sec": best_off,
                "p99_ms": min(p["p99_ms"] for p in legs["off"]),
                "passes": [p["req_per_sec"] for p in legs["off"]]},
        "on": {"req_per_sec": best_on,
               "p99_ms": min(p["p99_ms"] for p in legs["on"]),
               "passes": [p["req_per_sec"] for p in legs["on"]]},
        "overhead_pct": overhead,
        "bound_pct": 3.0,
        "ok": overhead <= 3.0,
    }
    if not leg["ok"]:
        raise AssertionError(
            "tracing overhead %.2f%% exceeds the 3%% bar: off %.2f "
            "req/s vs on %.2f req/s" % (overhead, best_off, best_on))
    return leg


def _tracing_only():
    """--tracing: run just the tracing A/B leg and merge it into an
    existing BENCH_SERVING.json (or a fresh skeleton)."""
    try:
        with open(OUT_PATH) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    leg = _measure_tracing_ab(*_build_model())
    result["tracing_ab"] = leg
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({
        "metric": "serving_tracing_overhead_pct",
        "device": _device(),
        "value": leg["overhead_pct"],
        "unit": "%",
        "off_req_per_sec": leg["off"]["req_per_sec"],
        "on_req_per_sec": leg["on"]["req_per_sec"],
        "ok": leg["ok"],
    }))
    sys.stdout.flush()


def _measure_generative():
    """The ISSUE-17 leg: generative serving through
    ``serving/generate`` — decode throughput, TTFT percentiles under
    mixed short/long traffic, and the three hard proofs: (1)
    no-convoy — with one 512-token generation in flight, concurrent
    16-token requests' TTFT p99 stays within 3x their solo baseline;
    (2) jit-cache flatness — zero recompiles (executor-cache misses
    AND decode/admit jit variants) across >= 1000 steady-state decode
    steps; (3) per-tenant exactly-once ledgers balance."""
    import numpy as np
    from mxnet_tpu.gluon.contrib.transformer import TransformerLM
    from mxnet_tpu.serving import ModelServer

    rng = np.random.RandomState(17)
    blk = TransformerLM(vocab_size=128, units=64, hidden_size=128,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_len=512)
    blk.initialize()
    srv = ModelServer(cache_size=64)
    sched = srv.add_generative_model("lm", blk, slots=8, max_len=512,
                                     prefill_batch=4)
    t0 = time.perf_counter()
    warmed = srv.warmup_generative()["lm"]
    warmup_s = time.perf_counter() - t0

    def _prompt(n):
        return rng.randint(1, 127, size=n).astype(np.int32)

    def _ttfts(streams):
        for s in streams:
            s.result(timeout=300)
        return [s.ttft_s * 1000.0 for s in streams]

    def _short_wave():
        return [srv.infer_stream("lm", _prompt(12), max_new_tokens=16,
                                 priority=0, tenant="short")
                for _ in range(4)]

    # -- solo baseline: the same short traffic (waves of 4) with the
    # pool to itself — the mixed phase below replays this shape with a
    # 512-token generation in flight, so the two p99s are comparable
    solo = []
    for _ in range(8):
        solo.extend(_ttfts(_short_wave()))
    solo_p99 = float(np.percentile(solo, 99))

    # -- steady-state marker: everything below must not compile
    miss0 = srv.cache.misses
    jit0 = sched.model.compile_stats()
    steps0 = sched.stats()["steps"]

    # -- mixed phase: one 512-token generation + waves of shorts
    t0 = time.perf_counter()
    long_st = srv.infer_stream("lm", _prompt(32), max_new_tokens=512,
                               priority=1, tenant="long")
    mixed_streams = []
    waves = 0
    while not long_st.done() and waves < 12:
        wave = _short_wave()
        for s in wave:
            s.result(timeout=300)
        mixed_streams.extend(wave)
        waves += 1
    convoy_window = not long_st.done()   # shorts really overlapped it
    long_tokens = len(long_st.result(timeout=600))
    mixed_wall = time.perf_counter() - t0
    mixed = [s.ttft_s * 1000.0 for s in mixed_streams]
    mixed_p99 = float(np.percentile(mixed, 99))
    mixed_tokens = long_tokens + sum(s.n_tokens for s in mixed_streams)

    # -- fill to >= 1000 steady-state decode steps for the flatness bar
    while sched.stats()["steps"] - steps0 < 1000:
        srv.infer_stream("lm", _prompt(24), max_new_tokens=256,
                         priority=1, tenant="long").result(timeout=600)
    steps = sched.stats()["steps"] - steps0
    recompiles = srv.cache.misses - miss0
    jit1 = sched.model.compile_stats()
    ledgers = sched.ledgers()
    srv.stop(drain=False)
    srv.cache.clear()

    if recompiles or jit1 != jit0:
        raise AssertionError(
            "steady-state decode recompiled: cache misses +%d, jit "
            "variants %r -> %r over %d steps"
            % (recompiles, jit0, jit1, steps))
    for tenant, led in ledgers.items():
        settled = (led["served"] + led["failed"] + led["expired"]
                   + led["shed"])
        if led["submitted"] != settled:
            raise AssertionError(
                "ledger imbalance for %r: %r" % (tenant, led))
    no_convoy = mixed_p99 <= 3.0 * solo_p99
    return {
        "model": "transformer_lm(64u/2L/4h, vocab 128)",
        "slots": 8, "max_len": 512,
        "warmup": {"prefill_cells": warmed, "seconds": round(warmup_s, 3)},
        "decode_tokens_per_sec": round(mixed_tokens / mixed_wall, 1),
        "ttft_ms": {
            "solo_p50": round(float(np.percentile(solo, 50)), 3),
            "solo_p99": round(solo_p99, 3),
            "mixed_p50": round(float(np.percentile(mixed, 50)), 3),
            "mixed_p99": round(mixed_p99, 3),
            "mixed_over_solo_p99": round(mixed_p99 / solo_p99, 3),
        },
        "no_convoy": {
            "long_tokens": long_tokens,
            "short_requests_overlapped": len(mixed_streams),
            "overlap_confirmed": bool(convoy_window),
            "bound": 3.0,
            "holds": bool(no_convoy),
        },
        "steady_state": {"decode_steps": int(steps),
                         "recompiles": int(recompiles),
                         "jit_variants": jit1},
        "ledgers": ledgers,
    }


def _generative_only():
    """--generative: run just the generative leg and merge it into an
    existing BENCH_SERVING.json (or a fresh skeleton)."""
    try:
        with open(OUT_PATH) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    leg = _measure_generative()
    result["generative"] = leg
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({
        "metric": "serving_generative_decode_tokens_per_sec",
        "device": _device(),
        "value": leg["decode_tokens_per_sec"],
        "unit": "tokens/s",
        "ttft_solo_p99_ms": leg["ttft_ms"]["solo_p99"],
        "ttft_mixed_p99_ms": leg["ttft_ms"]["mixed_p99"],
        "no_convoy": leg["no_convoy"]["holds"],
        "steady_state_recompiles": leg["steady_state"]["recompiles"],
        "decode_steps": leg["steady_state"]["decode_steps"],
    }))
    sys.stdout.flush()


def main():
    result = {"model": "resnet%d_cifar" % NUM_LAYERS,
              "image_shape": list(IMAGE_SHAPE),
              "max_batch": MAX_BATCH}

    def checkpoint():
        with open(OUT_PATH, "w") as f:
            json.dump(result, f, indent=1)

    # warm-restart leg FIRST, while this process has not touched jax:
    # the ISSUE-6 headline — a restarted replica's warmup with a
    # pre-populated persistent compile cache vs cold
    try:
        legs = _measure_warm_restart()
        result["warm_restart"] = legs
        result["warmup_cold_s"] = legs["cold"]["warmup_s"]
        result["warmup_warm_s"] = legs["warm"]["warmup_s"]
        result["warmup_warm_ratio"] = round(
            legs["warm"]["warmup_s"] / legs["cold"]["warmup_s"], 3)
        checkpoint()
    except Exception as exc:   # noqa: BLE001
        _fail("warm-restart leg failed: %r" % (exc,), 6)

    try:
        from mxnet_tpu.serving import ModelServer
        symb, arg_params, aux_params = _build_model()
        result["device"] = _device()
    except Exception as exc:   # noqa: BLE001
        _fail("model build failed: %r" % (exc,), 3)

    try:
        passes = [_measure_sequential(symb, arg_params, aux_params)
                  for _ in range(3)]
        passes.sort(key=lambda p: p["req_per_sec"])
        result["sequential"] = passes[1]          # median of 3
        result["sequential_passes"] = [p["req_per_sec"] for p in passes]
        checkpoint()
    except Exception as exc:   # noqa: BLE001
        _fail("sequential baseline failed: %r" % (exc,), 3)

    srv = ModelServer(max_batch=MAX_BATCH, queue_depth=1024,
                      default_timeout_ms=300000.0)
    srv.add_model("resnet", symb, arg_params, aux_params,
                  {"data": (1,) + IMAGE_SHAPE})
    try:
        srv.start()
        t0 = time.perf_counter()
        srv.warmup("resnet")
        result["warmup_s"] = round(time.perf_counter() - t0, 2)
        result["serving"] = []
        for c in sorted(PER_CLIENT):
            first = _measure_concurrency(srv, c, PER_CLIENT[c])
            second = _measure_concurrency(srv, c, PER_CLIENT[c])
            leg = max((p for p in (first, second) if "error" not in p),
                      key=lambda p: p["req_per_sec"],
                      default=first)     # best of 2 (first is warm-in)
            leg["passes"] = [p.get("req_per_sec", p.get("error"))
                             for p in (first, second)]
            result["serving"].append(leg)
            checkpoint()                 # incremental
        result["stats"] = srv.stats()
        checkpoint()
    except Exception as exc:   # noqa: BLE001
        _fail("serving measurement failed: %r" % (exc,), 3)
    finally:
        srv.stop(drain=False)

    # multi-tenant leg: the ISSUE-15 drill evidence — quotas, a
    # poisoned canary's auto-rollback latency, per-tenant isolation
    try:
        result["multitenant"] = _measure_multitenant()
        checkpoint()
    except Exception as exc:   # noqa: BLE001
        _fail("multi-tenant leg failed: %r" % (exc,), 7)

    # tracing A/B leg: the ISSUE-18 bar — request tracing armed at the
    # default tail-sample rate costs <= 3% req/s vs disarmed
    try:
        result["tracing_ab"] = _measure_tracing_ab(symb, arg_params,
                                                   aux_params)
        checkpoint()
    except Exception as exc:   # noqa: BLE001
        _fail("tracing A/B leg failed: %r" % (exc,), 8)

    seq = result["sequential"]["req_per_sec"]
    c64 = [leg for leg in result["serving"]
           if leg.get("concurrency") == 64]
    if not c64 or "error" in c64[0]:
        _fail("concurrency-64 leg failed: %s"
              % (c64[0].get("error") if c64 else "missing"), 5)
    value = c64[0]["req_per_sec"]
    result["vs_sequential_c64"] = round(value / seq, 3)
    checkpoint()
    print(json.dumps({
        "metric": "serving_resnet_req_per_sec_c64",
        "value": value,
        "unit": "req/s",
        "device": result["device"],
        "p99_ms": c64[0]["p99_ms"],
        "vs_sequential": result["vs_sequential_c64"],
        "warmup_cold_s": result["warmup_cold_s"],
        "warmup_warm_s": result["warmup_warm_s"],
        "multitenant_rollback_s":
            result["multitenant"]["canary"]["rollback_wall_s"],
        "tracing_overhead_pct": result["tracing_ab"]["overhead_pct"],
    }))
    sys.stdout.flush()


if __name__ == "__main__":
    if "--warmup-probe" in sys.argv[1:]:
        _warmup_probe()
    elif "--multitenant" in sys.argv[1:]:
        _multitenant_only()
    elif "--generative" in sys.argv[1:]:
        _generative_only()
    elif "--tracing" in sys.argv[1:]:
        _tracing_only()
    else:
        main()
