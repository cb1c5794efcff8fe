"""Persistent XLA compile cache — warm-start executors across restarts.

Reference precedent: the TensorFlow paper's serving story and TVM's
reuse of ahead-of-time compiled artifacts — a compiled executable is a
deterministic function of (program, shapes, dtypes, backend) and
should be cached on disk, not rebuilt per process.  Today every
process pays the full compile bill from scratch (BENCH_SERVING.json:
5.08 s of ``warmup()`` for five shape buckets); at fleet scale,
restarts and autoscaling make those cold-start recompiles the dominant
tail-latency event.

This module wires jax's persistent compilation cache
(``jax_compilation_cache_dir`` + thresholds) behind the
``MXNET_COMPILE_CACHE_*`` knobs, initialized once from the executor's
bind path so EVERY jit in the stack — executor fwd/train/fused-step,
kvstore reduce, serving binds — reads and writes one shared on-disk
cache.

Placement (:func:`placement`), first match wins:

1. ``JAX_COMPILATION_CACHE_DIR`` set — the cache was placed from
   outside (a machine that keeps it between runs).  jax reads that
   variable itself; this module sets NO directory of its own, it only
   attaches thresholds, hygiene, counters and ``stats()["dir"]`` to it.
2. ``MXNET_COMPILE_CACHE_DIR`` set — that directory; set to the empty
   string it turns the cache off (what tier-1's conftest does, so the
   CPU test suite does not fill the checkout).
3. neither — ``<checkout>/.jax_cache``: one fixed, git-ignored path.
   The directory is part of jax's cache key, so a temp name, pid or
   timestamp would never hit; its size cap is clamped to
   ``_DEFAULT_DIR_MAX_BYTES`` so the checkout stays copyable.

On top of the raw wiring it adds what jax leaves out:

- **hygiene** — a size cap (``MXNET_COMPILE_CACHE_MAX_BYTES``) with
  LRU eviction by recency (jax touches a ``-atime`` sibling per read;
  its mtime is the recency signal, falling back to the entry's own
  mtime), swept at initialization and on demand (:func:`sweep`);
- **degradation, never crashes** — an unwritable cache dir disables
  the cache with one warning; a corrupted/truncated entry falls back
  to a cold compile (``jax_raise_persistent_cache_errors`` is forced
  off) and is counted, not raised;
- **telemetry** — ``mxnet_compile_cache_{hits,misses,evictions,
  errors}_total`` counters + a ``mxnet_compile_cache_size_bytes``
  gauge, recorded via jax's monitoring events so the numbers are the
  cache's own truth, not a parallel guess;
- **every program's compilation** — the same listeners see each jitted
  program's trace, lowering and backend stage (a compile or a cache
  load) with its name, whichever layer dispatched it: the executor's
  ``fbu``, ``ParallelTrainer``'s ``step``, eager one-op programs.  With
  telemetry on each stage lands on the span ring as ``xla.trace`` /
  ``xla.lower`` / ``xla.compile`` (tags ``program``; ``cache`` =
  ``hit`` / ``miss`` / ``off`` and ``load_s`` on the last), and each
  backend stage counts in ``mxnet_jit_compiles_total``.  The spans are
  the record of the seconds; the hit / miss split is the span's tag and
  ``mxnet_compile_cache_hits_total``.  A function traced inside another
  stage (another's trace, a lowering) is that stage's time: only the
  outermost trace leaves a span.  jax reports only on a COMPILING
  dispatch: a cached dispatch runs none of this.  A fault in the
  recording is logged once and never reaches the compiling call.

Multi-process sharing is safe by construction: jax commits entries by
write-to-temp + rename, readers of a just-evicted entry degrade to a
miss, and the cache key includes the backend, so heterogeneous
replicas can share one directory (caveats: docs/faq/compile_cache.md).

The serving layer pairs this with a warmup manifest
(``mxnet_tpu.serving.WarmupManifest``): the compile cache remembers
the *executables*, the manifest remembers *which* (model, bucket)
programs a replica needs — together a restarted server's ``warmup()``
replays the manifest against the disk cache and starts hot.
"""
from __future__ import annotations

import logging
import os
import threading

__all__ = ["ensure_initialized", "configure", "placement", "enabled",
           "cache_dir", "stats", "sweep", "reset", "DEFAULT_DIR"]

_EXTERNAL_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
# the in-checkout default is copied along with the checkout by
# whatever ships it to a machine; hold it well under such a copy's
# budget whatever MXNET_COMPILE_CACHE_MAX_BYTES says
_DEFAULT_DIR_MAX_BYTES = 128 * 1024 * 1024

_LOCK = threading.Lock()
_INIT_LOCK = threading.Lock()   # serializes first-time configuration so
#                               # a concurrent bind WAITS instead of
#                               # compiling cold before the cache is on
_STATE = {                      # guarded-by: _LOCK
    "checked": False,           # configuration committed (terminal)
    "enabled": False,
    "dir": None,
    "max_bytes": 0,
    "entries": 0,               # as of the last sweep()/stats(refresh=True)
    "size_bytes": 0,            # as of the last sweep()/stats(refresh=True)
    "hooks": False,             # error-accounting wrappers installed
}
_COUNTS = {"requests": 0, "hits": 0, "misses": 0, "errors": 0,
           "evictions": 0}
#                               # guarded-by: _LOCK

_HELP = {
    "requests": "compile requests that consulted the persistent cache; "
                "requests - hits == real compiles (robust to the "
                "min-compile-time/entry-size persist thresholds, which "
                "suppress the miss event but never this one)",
    "hits": "persistent compile-cache hits (an XLA executable "
            "deserialized from disk instead of compiled)",
    "misses": "persistent compile-cache misses that then populated the "
              "cache (compiles below the persist thresholds count in "
              "requests - hits but not here)",
    "errors": "persistent compile-cache failures (unreadable dir, "
              "corrupt entry, failed write) — every one degraded to a "
              "cold compile, never an exception",
    "evictions": "compile-cache entries LRU-evicted by the size cap "
                 "(MXNET_COMPILE_CACHE_MAX_BYTES)",
}


# the three stages jax reports of every program it compiles
# (jax/_src/dispatch.py), each as a duration and as a (start, end) span:
# event -> span name
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lower",
    "/jax/core/compile/backend_compile_duration": "xla.compile",
}
_JIT_COMPILES = (
    "mxnet_jit_compiles_total",
    "backend stages of jitted programs as jax reports them, any layer's "
    "(executor, trainer, eager ops): XLA compiles and persistent-cache "
    "loads together (mxnet_compile_cache_hits_total counts the loads)")
_RECORD_FAILED = [False]        # _on_jax_time_span logged its fault
# per thread (jax fires its events on the thread that compiles): what
# the cache said inside the backend stage now running (``cache``,
# ``load_s``) and how many stages are open (``open``)
_tls = threading.local()


def _declare_counters():
    """Create every mxnet_compile_cache_*_total family up front so the
    exposition shows an explicit 0 from the moment the cache is
    configured — a scraper must be able to tell "zero misses" (warm
    restart) from "cache off" (family absent) — and
    ``mxnet_jit_compiles_total`` with them."""
    from . import telemetry
    if not telemetry.enabled():
        return
    for kind in _COUNTS:
        telemetry.counter("mxnet_compile_cache_%s_total" % kind,
                          _HELP[kind])
    telemetry.counter(*_JIT_COMPILES)


def _set_size_gauge(total):
    from . import telemetry
    if telemetry.enabled():
        telemetry.gauge(
            "mxnet_compile_cache_size_bytes",
            "bytes of committed entries in the persistent compile cache "
            "directory (updated by hygiene sweeps)").set(total)


def _bump(kind, n=1):
    if not n:
        return
    with _LOCK:
        _COUNTS[kind] += n
    from . import telemetry
    if telemetry.enabled():
        telemetry.counter("mxnet_compile_cache_%s_total" % kind,
                          _HELP[kind]).inc(n)


def _on_jax_event(event, **kwargs):
    # fires only on compiling dispatches — never on the cached hot path
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _bump("requests")
    elif event == "/jax/compilation_cache/cache_hits":
        _bump("hits")
        _note(cache="hit")
    elif event == "/jax/compilation_cache/cache_misses":
        # fired as the compiled program is WRITTEN: one the thresholds
        # keep out of the cache compiles every time and is no miss
        _bump("misses")
        _note(cache="miss")


def _on_jax_duration(event, duration_secs, **kwargs):
    if event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _note(load_s=duration_secs)


def _note(**said):
    """Keep what the cache said for the ``xla.compile`` span around it."""
    from . import telemetry
    if telemetry.enabled():
        _tls.__dict__.update(said)


def _on_jax_scalar(event, value, **kwargs):
    # jax reports a stage's start as a scalar.  A jitted function traced
    # while another stage is open on the thread — inside another's trace
    # (a symbol's step holds one per operator), inside a lowering (the
    # random bits' rule traces its arithmetic) — is reported on its own
    # AND inside the outer one, thousands a program: the depth, kept
    # telemetry on or off so that it stays balanced, tells them apart
    if event in _STAGES:
        _tls.open = getattr(_tls, "open", 0) + 1


def _on_jax_time_span(event, start_time, end_time, fun_name=None, **kwargs):
    """One stage of one program's compilation, as jax reports it at the
    stage's end: a span on the ring, and the counter for a backend stage.
    A trace inside another stage is that stage's time and leaves none (a
    program lowered and compiled inside one — an eager operation on a
    constant while another traces — keeps those two)."""
    span = _STAGES.get(event)
    if span is None:
        return
    said = _tls.__dict__
    said["open"] = depth = max(said.get("open", 1) - 1, 0)
    if depth and span == "xla.trace":
        return
    from . import telemetry
    if not telemetry.enabled():
        return
    try:
        _record_stage(telemetry, span, start_time, end_time, fun_name, said)
    except Exception:       # jax calls this inside the user's jit call
        if not _RECORD_FAILED[0]:
            _RECORD_FAILED[0] = True
            logging.exception(
                "compile observer: recording %s of %r failed; the "
                "compilation goes on (logged once)", span, fun_name)


def _record_stage(telemetry, span, start_time, end_time, fun_name, said):
    # jax takes both ends from time.time(), which can step back
    seconds = max(0.0, end_time - start_time)
    # jax names the trace after the function and the later stages after
    # the module it made of it ("jit(step)"): one name for the three
    program = str(fun_name)
    if program.startswith("jit(") and program.endswith(")"):
        program = program[4:-1]
    tags = {"program": program}
    if span == "xla.compile":
        tags["cache"] = said.pop("cache", "off")
        if "load_s" in said:
            tags["load_s"] = said.pop("load_s")
        telemetry.counter(*_JIT_COMPILES).inc()
    tracing = telemetry.tracing
    tracing.add_span(span, tracing.process_root(), start_time,
                     1e3 * seconds, **tags)


def _install_listeners():
    """Called once, at import and not at configuration: the stages are
    reported of every program, cache on or off, executor bound or not."""
    from jax._src import monitoring
    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    monitoring.register_event_time_span_listener(_on_jax_time_span)
    monitoring.register_scalar_listener(_on_jax_scalar)


def _install_error_hooks():
    """Count read/write failures at the cache boundary.

    jax handles them (warn + cold compile when
    ``raise_persistent_cache_errors`` is off) but exposes no counter;
    wrapping the two entry points gives exact error accounting without
    changing behavior — exceptions are re-raised for jax's own
    handling."""
    with _LOCK:
        if _STATE["hooks"]:
            return
        _STATE["hooks"] = True
    from jax._src import compilation_cache as _cc

    def _wrap(orig):
        def wrapper(*args, **kwargs):
            try:
                return orig(*args, **kwargs)
            except Exception:
                _bump("errors")
                raise
        wrapper._mxnet_compile_cache_hook = True
        return wrapper

    for name in ("get_executable_and_time", "put_executable_and_time"):
        orig = getattr(_cc, name)
        if not getattr(orig, "_mxnet_compile_cache_hook", False):
            setattr(_cc, name, _wrap(orig))


def _reset_jax_cache():
    """Drop jax's in-memory handle on the cache dir so a config change
    takes effect (jax latches the directory on first use)."""
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()


def placement():
    """``(directory, source)`` of the cache as the environment places
    it right now (module docstring): source ``"external"``
    (``JAX_COMPILATION_CACHE_DIR`` — the directory is read back from
    jax, which owns it), ``"knob"`` (``MXNET_COMPILE_CACHE_DIR``; the
    empty string gives directory None, cache off) or ``"default"``
    (:data:`DEFAULT_DIR`)."""
    if os.environ.get(_EXTERNAL_ENV):
        import jax
        return jax.config.jax_compilation_cache_dir, "external"
    from . import config as _config
    knob = _config.get("MXNET_COMPILE_CACHE_DIR")
    if knob is None:
        return DEFAULT_DIR, "default"
    return (os.path.abspath(knob) if knob else None), "knob"


def ensure_initialized():
    """Place the cache (:func:`placement`) and wire thresholds,
    hygiene and counters to it, once per process — called from the
    executor's bind path, so the first bind of anything (trainer,
    server, kvstore) turns the cache on for every jit after it.
    Returns whether the cache is enabled.  After the first call this is
    one dict read; concurrent first binds WAIT on the init lock instead
    of racing ahead and compiling cold before the cache config lands."""
    if _STATE["checked"]:
        return _STATE["enabled"]
    with _INIT_LOCK:
        if _STATE["checked"]:
            return _STATE["enabled"]
        return configure(placement()[0])


def _commit_disabled(external):
    import jax
    if not external:
        jax.config.update("jax_compilation_cache_dir", None)
        _reset_jax_cache()
    with _LOCK:        # checked last: it is the commit marker the
        _STATE["enabled"] = False      # lock-free fast path trusts
        _STATE["dir"] = None
        _STATE["checked"] = True
    return False


def configure(directory, min_compile_secs=None, min_entry_bytes=None,
              max_bytes=None):
    """Point jax's persistent compile cache at ``directory`` (None/empty
    disables).  Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was
    placed from outside: ``directory`` is ignored, jax's own setting is
    left untouched and everything below attaches to that directory.
    Unset thresholds come from the ``MXNET_COMPILE_CACHE_*`` knobs.  A
    directory that cannot be created or written disables the cache with
    a warning — a bad cache mount must degrade a replica to cold
    compiles, never crash it.  Returns whether the cache is on."""
    import jax
    from . import config as _config
    external = bool(os.environ.get(_EXTERNAL_ENV))
    if external:
        directory = jax.config.jax_compilation_cache_dir
    if not directory:
        return _commit_disabled(external)
    directory = os.path.abspath(directory)
    if min_compile_secs is None:
        min_compile_secs = _config.get("MXNET_COMPILE_CACHE_MIN_COMPILE_SECS")
    if min_entry_bytes is None:
        min_entry_bytes = _config.get("MXNET_COMPILE_CACHE_MIN_ENTRY_BYTES")
    if max_bytes is None:
        max_bytes = _config.get("MXNET_COMPILE_CACHE_MAX_BYTES")
    if directory == DEFAULT_DIR:
        max_bytes = min(int(max_bytes), _DEFAULT_DIR_MAX_BYTES) \
            if int(max_bytes) > 0 else _DEFAULT_DIR_MAX_BYTES
    try:
        os.makedirs(directory, exist_ok=True)
        probe = os.path.join(directory, ".mxnet-cache-probe-%d" % os.getpid())
        with open(probe, "wb") as f:
            f.write(b"probe")
        os.remove(probe)
    except OSError as exc:
        _bump("errors")
        logging.warning(
            "compile cache disabled: %r is not a writable directory (%s); "
            "every process will pay cold compiles", directory, exc)
        return _commit_disabled(external)
    if not external:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      int(min_entry_bytes))
    # corruption/IO errors must degrade to a cold compile, not raise
    jax.config.update("jax_raise_persistent_cache_errors", False)
    if not external:
        _reset_jax_cache()
    _declare_counters()
    _install_error_hooks()
    with _LOCK:
        _STATE["enabled"] = True
        _STATE["dir"] = directory
        _STATE["max_bytes"] = int(max_bytes)
        _STATE["checked"] = True
    sweep()
    return True


def enabled():
    return _STATE["enabled"]


def cache_dir():
    return _STATE["dir"]


def _entries(directory):
    """[(cache_path, atime_path_or_None, size, recency)] for each
    committed entry; recency is the ``-atime`` sibling's mtime (jax
    touches it per read) falling back to the entry's own mtime."""
    out = []
    try:
        names = os.listdir(directory)
    except OSError:
        return out
    present = set(names)
    for name in names:
        if not name.endswith("-cache"):
            continue
        path = os.path.join(directory, name)
        atime_name = name[:-len("-cache")] + "-atime"
        atime_path = (os.path.join(directory, atime_name)
                      if atime_name in present else None)
        try:
            size = os.path.getsize(path)
            recency = os.path.getmtime(atime_path or path)
        except OSError:
            continue        # concurrently evicted by another process
        out.append((path, atime_path, size, recency))
    return out


def sweep(max_bytes=None):
    """Enforce the size cap: evict least-recently-used entries until
    the cache fits.  Concurrent processes may race the unlink — a
    reader of an evicted entry degrades to a miss, so the race is
    benign.  Returns the number of entries evicted."""
    with _LOCK:
        directory = _STATE["dir"]
        if max_bytes is None:
            max_bytes = _STATE["max_bytes"]
    if not directory:
        return 0
    entries = _entries(directory)
    total = sum(size for _p, _a, size, _r in entries)
    evicted = 0
    if max_bytes and max_bytes > 0 and total > max_bytes:
        entries.sort(key=lambda e: e[3])        # oldest recency first
        for path, atime_path, size, _recency in entries:
            if total <= max_bytes:
                break
            try:
                os.remove(path)
            except OSError:
                continue    # another process won the eviction race
            if atime_path is not None:
                try:
                    os.remove(atime_path)
                except OSError:
                    pass
            total -= size
            evicted += 1
    with _LOCK:
        _STATE["entries"] = len(entries) - evicted
        _STATE["size_bytes"] = total
    _bump("evictions", evicted)
    _set_size_gauge(total)
    return evicted


def stats(refresh=True):
    """Snapshot for /stats surfaces and the bench harness.

    ``refresh=True`` rescans the cache directory so ``entries`` /
    ``size_bytes`` reflect what is on disk right now — O(entries)
    stat calls, fine for a bench probe or a debugger.  ``refresh=
    False`` is the cheap form for hot monitoring paths (the serving
    ``stats()`` poll): counters plus the sizes recorded by the last
    :func:`sweep`, zero disk I/O — on a network-mounted cache dir a
    per-scrape directory walk is exactly the kind of repeated remote
    I/O the cache exists to avoid."""
    with _LOCK:
        snap = dict(_COUNTS)
        snap["enabled"] = _STATE["enabled"]
        snap["dir"] = _STATE["dir"]
        snap["max_bytes"] = _STATE["max_bytes"]
        snap["entries"] = _STATE["entries"]
        snap["size_bytes"] = _STATE["size_bytes"]
    if refresh and snap["dir"]:
        entries = _entries(snap["dir"])
        snap["entries"] = len(entries)
        snap["size_bytes"] = sum(size for _p, _a, size, _r in entries)
        with _LOCK:
            _STATE["entries"] = snap["entries"]
            _STATE["size_bytes"] = snap["size_bytes"]
        _set_size_gauge(snap["size_bytes"])
    return snap


def reset():
    """Test hook: disable the cache and zero the counters so the next
    :func:`ensure_initialized` re-reads the environment.  An externally
    placed cache (``JAX_COMPILATION_CACHE_DIR``) is jax's to keep: only
    this module's view of it is forgotten."""
    if not os.environ.get(_EXTERNAL_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", None)
        _reset_jax_cache()
    with _LOCK:
        _STATE["checked"] = False
        _STATE["enabled"] = False
        _STATE["dir"] = None
        _STATE["entries"] = 0
        _STATE["size_bytes"] = 0
        for k in _COUNTS:
            _COUNTS[k] = 0


_install_listeners()
