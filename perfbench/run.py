#!/usr/bin/env python3
"""perfbench/run.py — the one command of the benchmark of record.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: find the cell's files by name (``BENCHMARK.json`` ->
``configs/``, ``traffic/``, ``drivers/``, ``reference/``, ``counts/``,
``metrics/``, ``limits/``), build the program with weights made from
``--seed``, drive it through its first steps (read for ``correct``),
warm up, measure for ``--seconds``, free the program, run the plain
reference over the same first steps, compare, and print ONE JSON line
last on standard output.  With ``--trace 1`` the window runs under the
jax profiler and the line carries the per-layer metrics.

It fails (exit 2, no result line) unless jax reports a TPU with at
least the cell's chips.  ``--rehearse`` — never passed by the
manifest's command — runs the configuration's ``rehearse`` sizes on the
CPU and names the CPU in ``device``.
"""
import time

T_PROCESS_START = time.time()   # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import loader  # noqa: E402

TRACE_SECONDS = 3.0     # the traced window of a --trace 1 run
WARM_STEPS = 3          # steady steps after the checked ones, before timing
MAX_IN_FLIGHT = 4       # steps dispatched and not yet known to be done


def log(msg):
    print("[perfbench] " + msg, file=sys.stderr, flush=True)


def place_compile_cache():
    """One fixed directory inside the checkout, unless the machine
    placed the cache itself (``JAX_COMPILATION_CACHE_DIR``).  The
    program's own default directory is capped at 128 MiB, less than the
    cells' programs together, so the harness names another through the
    program's knob and lifts the cap: no entry of a cell is evicted."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ.setdefault("MXNET_COMPILE_CACHE_DIR",
                              os.path.join(ROOT, ".perfbench_cache"))
    os.environ.setdefault("MXNET_COMPILE_CACHE_MAX_BYTES", str(8 << 30))


def describe_device(chips, rehearse):
    """The device as jax reports it; refuse anything but a TPU with the
    chips the cell asks for (``--rehearse``: the CPU, named as such)."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    want = "cpu" if rehearse else "tpu"
    if dev["platform"] != want or len(devs) < chips:
        sys.stderr.write(
            "perfbench: the cell needs %d %s device(s) but jax reports "
            "%s %r x%d; nothing was run\n"
            % (chips, want, dev["platform"], dev["kind"], dev["count"]))
        raise SystemExit(2)
    return dev


def memory_peak_bytes(devices):
    """Peak on the fullest device.  On the TPU ``peak_bytes_in_use``
    counts the arrays the process holds (weights, optimizer slots, the
    batch) and leaves out the temporaries of a running program — for a
    training step, the activations kept for the backward pass — which
    the runtime takes from a second, disjoint pool, ``bytes_reserved``.
    The two peaks together are what the chip holds and what agrees with
    the step's ``memory_analysis()`` (PERF.md section 3); where the
    backend reports neither, 0."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_reserved", 0))
                     + int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_window(driver, feed, seconds, spans):
    """Dispatch steps for ``seconds``, then block on the last one.
    Returns (steps, window seconds): the window runs from the first
    timed dispatch to the return of the block on the last step's
    outputs, and every step dispatched in it counts.  The host runs at
    most ``MAX_IN_FLIGHT`` steps ahead of the device (it waits for the
    step dispatched that many before): deep enough that the device never
    waits for a dispatch, bounded so that the window ends within a few
    steps of ``seconds`` whatever the runtime's own queue holds.  ``spans`` gets
    one (start, end) per step's dispatch and a final (start, end,
    "block"), host clock; the same spans go into the profiler's trace
    as ``pb.step`` / ``pb.block`` inside ``pb.window``."""
    from collections import deque
    from jax.profiler import TraceAnnotation
    steps, in_flight = 0, deque()
    with TraceAnnotation("pb.window"):
        t0 = time.perf_counter()
        while True:
            t_a = time.perf_counter()
            if t_a - t0 >= seconds:
                break
            with TraceAnnotation("pb.step"):
                in_flight.append(driver.step(feed.next()))
            spans.append((t_a, time.perf_counter()))
            steps += 1
            if len(in_flight) > MAX_IN_FLIGHT:
                with TraceAnnotation("pb.wait"):
                    in_flight.popleft().block_until_ready()
        t_b = time.perf_counter()
        with TraceAnnotation("pb.block"):
            driver.block()
        t1 = time.perf_counter()
    spans.append((t_b, t1, "block"))
    return steps, t1 - t0


def free_device(driver):
    """Drop the program and every array it left on the devices, so the
    reference has the chip to itself."""
    import jax
    driver.free()
    gc.collect()
    for arr in jax.live_arrays():
        arr.delete()
    gc.collect()


def run_cell(bench, cell, args, dev, devices, driver_cls=None):
    """Everything after the look for a chip.  ``driver_cls`` lets a
    test put a broken driver in the real one's place."""
    from mxnet_tpu import compile_cache, telemetry
    import traffic as traffic_mod
    telemetry.enable()      # trace-time kernel counters need it in set-up
    # the program turns its persistent compile cache on at its first bind;
    # the benchmark's own programs (weights, reference) come before that
    compile_cache.ensure_initialized()

    config = cell.config_for(rehearse=args.rehearse)
    reference, check = cell.reference(), cell.check()
    log("cell %s: config %s, traffic %s, %d chip(s), seed %d"
        % (cell.name, cell.config_name, cell.traffic_name, cell.chips,
           args.seed))
    marks = [("imports", time.time())]
    weights = reference.init_weights(config, args.seed)
    marks.append(("weights", time.time()))
    feed = traffic_mod.Feed(cell.traffic, config, args.seed)
    driver = (driver_cls or cell.driver().Driver)(
        config, devices, rehearse=args.rehearse)
    driver.build(weights)
    feed.place = driver.place
    marks.append(("build", time.time()))

    # -- the first steps, through the window's own call and feed ------------
    got, first_batches = check.probe(driver, feed, weights, config,
                                     reference.wd_mult)
    driver.assert_fast_path()
    marks.append(("first steps", time.time()))
    for _ in range(WARM_STEPS):
        driver.step(feed.next())
    driver.block()
    marks.append(("warm-up", time.time()))
    compiles_before = telemetry.scalar_totals().get(
        "mxnet_xla_compiles_total", 0)
    if not args.trace:
        telemetry.disable()
    setup_s = time.time() - T_PROCESS_START
    log("set-up %.2fs (%s); first losses %s" % (setup_s, ", ".join(
        "%s %.1f" % (name, t - t_prev) for (name, t), t_prev in zip(
            marks, [T_PROCESS_START] + [t for _n, t in marks])),
        got["loss"]))

    # -- the measured window -----------------------------------------------
    spans, rec = [], None
    if args.trace:
        import trace_reduce
        trace_dir = args.trace_dir or os.path.join(ROOT, ".perfbench_trace")
        with trace_reduce.Recording(trace_dir, keep=bool(args.trace_dir)) \
                as rec:
            steps, window_s = run_window(
                driver, feed, min(args.seconds, TRACE_SECONDS), spans)
    else:
        steps, window_s = run_window(driver, feed, args.seconds, spans)
    compiles_in_window = telemetry.scalar_totals().get(
        "mxnet_xla_compiles_total", 0) - compiles_before
    telemetry.disable()
    mem_peak = memory_peak_bytes(devices)
    mem_stats = devices[0].memory_stats() or {}
    log("window %.3fs, %d steps, %.3f ms/step"
        % (window_s, steps, 1e3 * window_s / max(steps, 1)))
    log("memory_stats %s" % json.dumps(mem_stats))

    # -- free the program, then the reference over the same first steps ----
    free_device(driver)
    del driver, feed
    t_ref = time.perf_counter()
    want = reference.train_steps(config, weights, first_batches,
                                 devices=devices)
    verdict = check.judge(got, want, cell.limits())
    log("reference took %.2fs" % (time.perf_counter() - t_ref))

    device = dict(dev, memory_peak_bytes=mem_peak)
    metrics = {}
    result = {"correct": bool(verdict["correct"]) and steps > 0,
              "attempted": steps, "failed": 0}
    if args.trace:
        reduced = trace_reduce.reduce(rec.xplane_path(), chips=cell.chips)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        ctx = {"cell": cell, "config": config, "counts": cell.counts(),
               "peaks": bench.peaks(dev["kind"], rehearse=args.rehearse),
               "steps": steps, "window_s": window_s, "spans": spans,
               "trace": reduced, "chips": cell.chips,
               "memory_peak_bytes": mem_peak, "memory_stats": mem_stats,
               "compiles_in_window": compiles_in_window}
        for spec in cell.per_layer_metrics():
            value = bench.metric_reader(spec["name"]).read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value,
                                         "unit": spec["unit"]}
        result["breakdown"] = reduced["breakdown"]
        rec.cleanup()
    else:
        values = {"train_step_ms": 1e3 * window_s / max(steps, 1),
                  "setup_s": setup_s}
        for spec in cell.end_to_end_metrics():
            metrics[spec["name"]] = {"value": values[spec["name"]],
                                     "unit": spec["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["window"] = {"steps": steps, "seconds": window_s}
    result["read"] = verdict["read"]
    result["compared"] = {k: [v["value"], v["limit"]]
                          for k, v in verdict["compared"].items()}
    for name, value in verdict["read"].items():
        log("read     %-10s %.6g (not compared)" % (name, value))
    for name, row in verdict["compared"].items():
        log("compared %-10s %.6g (limit %.6g) %s%s"
            % (name, row["value"], row["limit"],
               "ok" if row["ok"] else "FAILED",
               "  worst leaf %s" % row["leaf"] if "leaf" in row else ""))
    log("correct: %s" % result["correct"])
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; not a measurement")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's files here (default: a "
                         "directory in the checkout, removed after)")
    args = ap.parse_args(argv)

    bench = loader.Bench(ROOT)
    cell = bench.cell(args.workload)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("MXNET_COMPILE_CACHE_DIR", "")
        if cell.chips > 1:
            os.environ.setdefault(
                "XLA_FLAGS", "--xla_force_host_platform_device_count=%d"
                % cell.chips)
    else:
        place_compile_cache()
    dev = describe_device(cell.chips, args.rehearse)
    import jax
    result = run_cell(bench, cell, args, dev, jax.devices()[:cell.chips])
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
