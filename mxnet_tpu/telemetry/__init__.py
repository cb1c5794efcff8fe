"""Runtime telemetry — the process-wide metrics registry and its hooks.

The hot-path costs that decide TPU step time — XLA recompiles,
host<->device transfers, input-pipeline stalls, kvstore traffic — are
recorded here by the executor, ndarray, io, kvstore, and serving
layers, and read back three ways:

- ``snapshot()`` — one JSON view of every series;
- ``prometheus_text()`` / ``write_prometheus()`` — text exposition for
  scrapers (format-checked by ``validate_exposition``);
- ``StepLogger`` — per-step JSONL with counter deltas, installed by
  ``module.fit`` when ``MXNET_TELEMETRY_STEP_LOG`` is set, which also
  bridges counters into the profiler's chrome-trace stream as ``'C'``
  events.

Gating: instrumentation in training hot paths (executor dispatch,
``asnumpy``, iterator ``next``, kvstore push/pull) only records when
``enabled()`` — one boolean check on the disabled fast path, toggled by
``MXNET_TELEMETRY`` or ``enable()``/``disable()``.  The serving layer
records unconditionally: its ``stats()`` surface always existed and the
registry is simply its new backing store.  The graftsan sanitizers
(``analysis/sanitizers/``) record unconditionally too — their
``mxnet_sanitizer_findings_total{rule=...}`` /
``mxnet_sanitizer_overhead_seconds`` series only move while a
``MXNET_SAN*`` knob is armed, and ride the same scalar-totals bridge
into chrome traces as every other family.
"""
from __future__ import annotations

import atexit

from .registry import (Counter, Gauge, Histogram, MetricFamily,
                       MetricsRegistry, exponential_buckets,
                       validate_exposition)
from .step_logger import StepLogger
from . import tracing, flight, phases
from .phases import program_hlo, register_program

__all__ = ["Counter", "Gauge", "Histogram", "MetricFamily",
           "MetricsRegistry", "StepLogger", "counter", "gauge",
           "histogram", "get_registry", "snapshot", "snapshot_json",
           "prometheus_text", "write_prometheus", "validate_exposition",
           "exponential_buckets", "enabled", "enable", "disable",
           "reset", "scalar_totals", "publish_to_profiler",
           "chrome_counter_events", "note_import_seconds", "tracing",
           "flight", "phases",
           "register_program", "program_hlo"]

_REGISTRY = MetricsRegistry()
_ENABLED = [False]
_IMPORT_SECONDS = [None]    # note_import_seconds()


def get_registry():
    """The process-wide registry every subsystem records into."""
    return _REGISTRY


def counter(name, help=""):
    return _REGISTRY.counter(name, help)


def gauge(name, help=""):
    return _REGISTRY.gauge(name, help)


def histogram(name, help="", buckets=None):
    return _REGISTRY.histogram(name, help, buckets=buckets)


def snapshot():
    return _REGISTRY.snapshot()


def snapshot_json(**kwargs):
    return _REGISTRY.snapshot_json(**kwargs)


def prometheus_text():
    return _REGISTRY.prometheus_text()


def scalar_totals():
    return _REGISTRY.scalar_totals()


def reset():
    _REGISTRY.reset()


def enabled():
    """Is hot-path instrumentation on?  (One list read — the cost the
    disabled fast path pays.)"""
    return _ENABLED[0]


def enable(on=True):
    """Hot-path instrumentation on (or off): the counters, and the
    program's spans (``tracing.arm_ring`` — ring and profiler
    annotation, no exporter).  Off takes back only what on armed: a
    process tracing under ``MXNET_TRACE`` keeps tracing."""
    _ENABLED[0] = bool(on)
    if on:
        tracing.arm_ring()
        _export_import_seconds()
    else:
        tracing.disarm_ring()


def note_import_seconds(seconds):
    """``mxnet_tpu/__init__.py``'s last line: what importing the package
    took.  Kept until telemetry is on (now, under ``MXNET_TELEMETRY``),
    then exported as gauge ``mxnet_import_seconds``."""
    _IMPORT_SECONDS[0] = float(seconds)
    if _ENABLED[0]:
        _export_import_seconds()


def _export_import_seconds():
    if _IMPORT_SECONDS[0] is not None:
        gauge("mxnet_import_seconds",
              "seconds spent importing the mxnet_tpu package's own "
              "modules, first line to last of its __init__ (whatever "
              "was imported before it, jax for one, is not in it)"
              ).set(_IMPORT_SECONDS[0])


def disable():
    enable(False)


def write_prometheus(path=None):
    """Write the exposition to ``path`` (default:
    ``MXNET_TELEMETRY_PROM_FILE``); returns the path written or None."""
    if path is None:
        from .. import config as _config
        path = _config.get("MXNET_TELEMETRY_PROM_FILE")
    if not path:
        return None
    with open(path, "w") as f:
        f.write(prometheus_text())
    return path


def chrome_counter_events(ts=None):
    """The registry's scalar metrics as chrome-trace ``'C'`` counter
    events (profiler.dumps appends these so a dumped trace carries the
    final counter totals alongside its spans)."""
    if ts is None:
        import time
        ts = time.perf_counter_ns() / 1000.0
    return [{"name": name, "cat": "telemetry", "ph": "C", "ts": ts,
             "pid": 0, "tid": 0, "args": {name: value}}
            for name, value in _REGISTRY.scalar_totals().items()]


def publish_to_profiler():
    """Record one ``'C'`` sample per scalar metric into a RUNNING
    profiler trace (no-op otherwise) — the per-step time-series feed."""
    from .. import profiler
    if not profiler.is_running():
        return
    for name, value in _REGISTRY.scalar_totals().items():
        profiler._record(name, "telemetry", "C", args={name: value})


def _atexit_write():
    try:
        write_prometheus()
    except Exception:
        pass


atexit.register(_atexit_write)

# honor the env knob at import so subprocesses (bench legs) need no code
from .. import config as _config  # noqa: E402

_REGISTRY.set_label_cap(_config.get("MXNET_TELEMETRY_LABEL_CAP"))

if _config.get("MXNET_TELEMETRY"):
    enable()

if _config.get("MXNET_TRACE"):
    tracing.enable()
