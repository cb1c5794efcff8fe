"""Set-up from inside: what the program spent compiling and placing
before the window, and whether anything compiled inside it.

The program records, on the ring ``step_host_ms`` reads
(``tracing.snapshot()``, ``t0_ns`` on ``time.perf_counter``, the clock of
``ctx["spans"]``), one span per stage of every program jax compiles —
``xla.trace``, ``xla.lower``, ``xla.compile`` (tags ``program``, and on
the last ``cache`` = ``hit`` / ``miss`` / ``off``) — and one around each
of its own set-up stages (:data:`PLACE`).  :func:`split` cuts them at the
window's first dispatch, ``ctx["spans"][0][0]``: a span that BEGAN before
it is set-up, one that began inside the window is the window's.

Seconds are lengths of UNIONS of intervals, not sums of durations: jax
reports a jitted function traced inside another's trace on its own and
inside the outer one, and a set-up stage holds the compilations it
caused.  ``backend`` is what the ``xla.compile`` spans cover,
``trace_lower`` what ``xla.trace`` / ``xla.lower`` cover beyond that,
``place`` what the set-up stages cover beyond both — so the three share
no second and their sum is wall time.  All of it is PROCESS-wide: jax
reports the harness's own programs (the reference weights' ``make``)
like the program's, and the ``program`` tag is what a later reader
could separate them by.  The set-up stages are host time: the copies a
``trainer.place`` dispatches finish on the device after it returns.

A test hands the spans in as ``ctx["program_spans"]`` (and an eviction
count as ``ctx["program_spans_evicted"]``); a run asks the program.  The
ring forgets its oldest spans when full, and an export under
``MXNET_TRACE_DIR`` drains it: once either has happened (``stats()``'s
``evicted``, ``exported`` or ``dropped`` above 0) what is before the
window is no longer whole and every reader of it gives ``None``, never a
partial sum.  A program from before the
spans (the parent of the PR that added them) gives every reader ``None``.
"""
import sys

import trace_reduce

COMPILE = "xla.compile"
TRACE_LOWER = ("xla.trace", "xla.lower")
PLACE = ("trainer.place", "trainer.build", "module.bind",
         "module.init_params", "module.init_optimizer", "module.set_params")


def _recorded(ctx):
    """The program's spans, or None where the record is not whole."""
    if "program_spans" in ctx:
        return None if ctx.get("program_spans_evicted") \
            else ctx["program_spans"]
    try:
        from mxnet_tpu.telemetry import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "evicted"):   # cannot say what it forgot
        return None
    # what left the ring: dropped when full, or drained to a shard
    # (exported or sampled out) under MXNET_TRACE_DIR
    said, rows = tracing.stats(), tracing.snapshot()
    lost = said["evicted"] + said["exported"] + said["dropped"]
    print("[perfbench] the program's ring holds %d spans, %d gone "
          "(%d evicted)" % (len(rows), lost, said["evicted"]),
          file=sys.stderr, flush=True)
    return None if lost else rows


def split(ctx):
    """``{"backend": [...], "trace_lower": [...], "place": [...]}`` —
    ``(start_ns, end_ns)`` of the spans that began before the window —
    with ``"misses"`` (``xla.compile`` spans among them tagged
    ``cache=miss``) and ``"in_window"`` (``xla.compile`` spans that began
    inside it); memoised on ``ctx``.  None without a window or a whole
    record."""
    if "_setup" in ctx:
        return ctx["_setup"]
    ctx["_setup"] = None
    window = ctx.get("spans") or []
    rows = _recorded(ctx) if window else None
    if rows is None:
        return None
    lo, hi = window[0][0] * 1e9, window[-1][1] * 1e9
    out = {"backend": [], "trace_lower": [], "place": [], "misses": 0,
           "in_window": 0}
    caches = {}
    for r in rows:
        t0 = r.get("t0_ns")
        if t0 is None:
            continue
        span = (t0, t0 + r["dur_ms"] * 1e6)
        if r["name"] == COMPILE:
            if t0 < lo:
                out["backend"].append(span)
                cache = (r.get("tags") or {}).get("cache")
                caches[cache] = caches.get(cache, 0) + 1
            elif t0 <= hi:
                out["in_window"] += 1
        elif t0 < lo and r["name"] in TRACE_LOWER:
            out["trace_lower"].append(span)
        elif t0 < lo and r["name"] in PLACE:
            out["place"].append(span)
    out["misses"] = caches.get("miss", 0)
    print("[perfbench] before the window: %d programs' backend stages "
          "(%s), %d trace / lower spans, %d set-up stages; in the window "
          "%d compiles"
          % (len(out["backend"]),
             ", ".join("%s %d" % kv for kv in sorted(
                 caches.items(), key=lambda kv: str(kv[0]))) or "none",
             len(out["trace_lower"]), len(out["place"]), out["in_window"]),
          file=sys.stderr, flush=True)
    ctx["_setup"] = out
    return out


def _beyond(spans, under):
    """Seconds ``spans`` cover that ``under`` does not."""
    return 1e-9 * (trace_reduce.union_length(spans + under)
                   - trace_reduce.union_length(under))


def backend_s(ctx):
    """Seconds inside backend stages (XLA compiles and cache loads
    together) before the window, or None."""
    cut = split(ctx)
    if not cut or not cut["backend"]:
        return None
    return _beyond(cut["backend"], [])


def trace_lower_s(ctx):
    """Seconds of tracing and lowering before the window — Python, paid
    at every restart whatever the cache holds — or None."""
    cut = split(ctx)
    if not cut or not cut["trace_lower"]:
        return None
    return _beyond(cut["trace_lower"], cut["backend"])


def place_s(ctx):
    """Seconds inside the program's own set-up stages before the window,
    less what compiled inside them, or None."""
    cut = split(ctx)
    if not cut or not cut["place"]:
        return None
    return _beyond(cut["place"], cut["trace_lower"] + cut["backend"])


def cache_misses(ctx):
    """Backend stages before the window that went to the persistent
    cache, came back empty and compiled; None where none was observed."""
    cut = split(ctx)
    if not cut or not cut["backend"]:
        return None
    return float(cut["misses"])


def compiles_in_window(ctx):
    """Backend stages of ANY program that began inside the window.  None
    where the program reports no stage at all: a count of 0 is a reading
    only from an observer that saw set-up compile."""
    cut = split(ctx)
    if not cut or not (cut["backend"] or cut["in_window"]):
        return None
    return float(cut["in_window"])


def import_s(ctx):
    """The program's gauge ``mxnet_import_seconds``, or None."""
    if "program_totals" in ctx:
        totals = ctx["program_totals"]
    else:
        try:
            from mxnet_tpu import telemetry
        except ImportError:
            return None
        totals = telemetry.scalar_totals()
    value = totals.get("mxnet_import_seconds")
    return None if value is None else float(value)
