"""Environment-variable configuration registry.

Reference: the ``dmlc::GetEnv`` sites across the C++ tree plus their
documentation page (``docs/faq/env_var.md``) — every knob the runtime
honors, with type, default, and description, discoverable in one place.

TPU-native: variables are declared with ``register_env`` and read with
``config.get``; ``list_env()`` renders the registry as the env_var.md
table.  Unknown ``MXNET_*`` variables found in the process environment
are reported by ``check_unknown()`` so typos fail loudly instead of
silently configuring nothing.
"""
from __future__ import annotations

import os
from collections import OrderedDict

from .base import getenv

__all__ = ["register_env", "get", "list_env", "check_unknown", "EnvVar"]


class EnvVar:
    __slots__ = ("name", "typ", "default", "description")

    def __init__(self, name, typ, default, description):
        self.name = name
        self.typ = typ
        self.default = default
        self.description = description


_REGISTRY = OrderedDict()


def register_env(name, typ=str, default=None, description=""):
    """Declare a configuration variable (reference: the dmlc::GetEnv
    call-site + env_var.md doc-entry pair)."""
    _REGISTRY[name] = EnvVar(name, typ, default, description)
    return _REGISTRY[name]


def get(name):
    """Read a registered variable with its declared type/default."""
    if name not in _REGISTRY:
        raise KeyError("unregistered env var %r; declare it with "
                       "register_env" % name)
    var = _REGISTRY[name]
    return getenv(name, var.default, var.typ)


def list_env():
    """The registry as a markdown table (reference: docs/faq/env_var.md)."""
    lines = ["| variable | type | default | description |",
             "| --- | --- | --- | --- |"]
    for var in _REGISTRY.values():
        lines.append("| %s | %s | %r | %s |" % (
            var.name, var.typ.__name__, var.default, var.description))
    return "\n".join(lines)


def check_unknown(prefix="MXNET_"):
    """MXNET_* variables set in the environment but never registered —
    likely typos."""
    return sorted(k for k in os.environ
                  if k.startswith(prefix) and k not in _REGISTRY)


# ---------------------------------------------------------------------------
# the variables this runtime honors
# ---------------------------------------------------------------------------
register_env("MXNET_PROFILER_AUTOSTART", bool, False,
             "start the profiler at import (reference: src/profiler)")
register_env("MXNET_PROFILER_MODE", int, 0,
             "profiler instrumentation mode bitmask")
register_env("MXNET_ENGINE_TYPE", str, "XLA",
             "accepted for compatibility; scheduling is XLA async "
             "dispatch, so engine selection is a no-op")
register_env("MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
             "accepted for compatibility; op bulking corresponds to jit "
             "boundaries here")
register_env("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
             "size above which dist kvstore treats an array as big "
             "(sharding hint)")
register_env("MXNET_CPU_WORKER_NTHREADS", int, 4,
             "default preprocess/decode worker count for data iterators")
register_env("MXNET_BACKWARD_DO_MIRROR", bool, False,
             "gradient checkpointing (jax.checkpoint) in the fused "
             "training step")
register_env("MXNET_IMAGE_PREFETCH_BUFFER", int, 4,
             "ImageRecordIter ready-batch queue depth")
register_env("MXNET_NATIVE_DISABLE", bool, False,
             "skip the C++ data-pipeline core even when buildable")
register_env("MXNET_KVSTORE_HEARTBEAT_DIR", str, None,
             "shared directory for dist-kvstore worker heartbeats "
             "(enables get_num_dead_node)")
register_env("MXNET_KVSTORE_ASYNC_DIR", str, None,
             "shared spool directory for the dist_async parameter "
             "server (coordinator applies pushes on arrival)")
register_env("MXNET_KVSTORE_ASYNC_MAX_PENDING", int, 64,
             "dist_async spool capacity: push blocks while this many "
             "spooled gradients await the server (bounds staleness and "
             "spool growth; 0 disables backpressure)")
register_env("MXNET_KVSTORE_ASYNC_BACKPRESSURE_TIMEOUT", float, 120.0,
             "seconds a dist_async push may block on a full spool "
             "before raising (a dead server thread, not staleness)")
register_env("MXNET_SERVING_MAX_BATCH", int, 8,
             "largest serving shape bucket; the micro-batcher coalesces "
             "concurrent requests up to this many rows per dispatch")
register_env("MXNET_SERVING_QUEUE_DEPTH", int, 256,
             "bounded serving request queue; submissions beyond this "
             "depth are rejected with QueueFull (explicit backpressure)")
register_env("MXNET_SERVING_BATCH_WAIT_MS", float, 2.0,
             "how long the micro-batcher holds a head-of-line request "
             "for co-batchable arrivals before dispatching a partial "
             "bucket")
register_env("MXNET_SERVING_DEFAULT_TIMEOUT_MS", float, 5000.0,
             "per-request serving deadline when infer() passes none; "
             "expired requests fail with DeadlineExceeded and are "
             "skipped by the batcher")
register_env("MXNET_SERVING_EXECUTOR_CACHE", int, 16,
             "LRU capacity of the serving executor cache, in bound "
             "(model, version, bucket) programs; misses are recompiles")
register_env("MXNET_TELEMETRY", bool, False,
             "master switch for hot-path metrics instrumentation "
             "(XLA compiles, device->host transfers, io fetch latency, "
             "kvstore traffic); the registry itself is always live")
register_env("MXNET_TELEMETRY_STEP_LOG", str, None,
             "path for per-step JSONL emitted during fit() — one JSON "
             "object per step with samples/sec and counter deltas")
register_env("MXNET_TELEMETRY_STEP_INTERVAL", int, 1,
             "emit a step-JSONL record every N batches")
register_env("MXNET_TELEMETRY_PROM_FILE", str, None,
             "write the registry's Prometheus text exposition to this "
             "path at process exit (telemetry.write_prometheus)")
register_env("MXNET_GLUON_REPO", str, None,
             "override source for gluon model-zoo checkpoints: a local "
             "staging directory or an apache-mxnet-style base URL "
             "(gluon/model_zoo/model_store.py)")
register_env("MXNET_CKPT_DIR", str, None,
             "checkpoint directory; when set, fit() checkpoints into it "
             "via a CheckpointManager and Module.save_checkpoint mirrors "
             "saves there (docs/faq/checkpoint.md)")
register_env("MXNET_CKPT_PERIOD_STEPS", int, 0,
             "save a checkpoint every N training batches during fit() "
             "(0 disables step-periodic saves)")
register_env("MXNET_CKPT_PERIOD_EPOCHS", int, 1,
             "save a checkpoint every N epochs at epoch end during "
             "fit() (0 disables epoch-periodic saves)")
register_env("MXNET_CKPT_KEEP_LAST", int, 5,
             "retention: keep this many most-recent complete "
             "checkpoints (<= 0 keeps everything)")
register_env("MXNET_CKPT_KEEP_EVERY", int, 0,
             "retention: additionally pin every checkpoint whose step "
             "id divides by K, forever (0 disables)")
register_env("MXNET_CKPT_ASYNC", bool, True,
             "serialize checkpoints on a background worker (at most one "
             "in flight); 0 saves synchronously on the training thread")
register_env("MXNET_CKPT_ON_SIGTERM", bool, True,
             "during fit(), SIGTERM triggers one final synchronous "
             "checkpoint before exiting (preemption grace-window save)")
register_env("MXNET_CKPT_WATCH_INTERVAL_S", float, 10.0,
             "poll period of serving ModelRegistry.watch_checkpoints "
             "for newly committed checkpoint versions")
register_env("MXNET_COMPILE_CACHE_DIR", str, None,
             "directory for the persistent XLA compile cache: compiled "
             "executables are cached on disk and a restarted process "
             "warm-starts instead of recompiling.  jax's own "
             "JAX_COMPILATION_CACHE_DIR wins over it; unset = "
             "<checkout>/.jax_cache; empty = cache off "
             "(docs/faq/compile_cache.md)")
register_env("MXNET_COMPILE_CACHE_MIN_COMPILE_SECS", float, 0.0,
             "only compiles at least this slow are persisted (0 caches "
             "everything — serving warmup wants every bucket back)")
register_env("MXNET_COMPILE_CACHE_MIN_ENTRY_BYTES", int, 0,
             "only serialized executables at least this large are "
             "persisted (0 caches everything)")
register_env("MXNET_COMPILE_CACHE_MAX_BYTES", int, 1073741824,
             "compile-cache size cap; hygiene sweeps LRU-evict by "
             "recency until the cache fits (<= 0 disables the cap)")
register_env("MXNET_COMPILE_CACHE_MANIFEST", str, None,
             "path of the serving warmup manifest: ModelServer records "
             "its (model, bucket) executor key set there and a "
             "restarted replica replays it so warmup re-binds hit the "
             "persisted executables (docs/faq/compile_cache.md)")
register_env("MXNET_PARALLEL_BUCKET_BYTES", int, 4194304,
             "gradient-collective bucket size cap for ParallelTrainer: "
             "replicated params are fused into flat buckets of at most "
             "this many bytes so each bucket's reduce can overlap the "
             "remaining backward (docs/faq/parallel.md); <= 0 puts "
             "everything in one monolithic bucket")
register_env("MXNET_PARALLEL_BUCKET_FIRST_BYTES", int, 1048576,
             "size cap of the FIRST bucket (the output-side params whose "
             "gradients finish earliest in backward); smaller than "
             "MXNET_PARALLEL_BUCKET_BYTES so the first collective "
             "launches as early as possible")
register_env("MXNET_PARALLEL_ZERO", int, 0,
             "default ZeRO stage for ParallelTrainer: 0 replicates "
             "optimizer state (monolithic all-reduce), 1 shards "
             "optimizer slots 1/mesh (full-gradient all-reduce), 2 also "
             "reduce-scatters gradients into the shards "
             "(docs/faq/parallel.md)")
register_env("MXNET_PARALLEL_COMPRESSION", str, None,
             "default gradient-compression codec for ParallelTrainer "
             "bucket reductions: 2bit (reference kvstore quantizer), "
             "bf16, or fp8 — all with error-feedback residuals carried "
             "in trainer state; unset sends fp32")
register_env("MXNET_PARALLEL_COMPRESSION_THRESHOLD", float, 0.5,
             "quantization threshold of the 2bit codec (reference "
             "gradient_compression.cc pos/neg threshold)")
register_env("MXNET_SAN", bool, False,
             "master switch arming all four graftsan runtime "
             "sanitizers (recompile, host-sync, lock-order, donation); "
             "each is also individually switchable — see "
             "docs/faq/static_analysis.md")
register_env("MXNET_SAN_RECOMPILE", bool, False,
             "graftsan recompile sanitizer: XLA compiles observed "
             "inside a steady-state region (after serving warmup / "
             "after fit's first step) become san-recompile findings "
             "carrying the re-traced shape signature")
register_env("MXNET_SAN_HOST_SYNC", bool, False,
             "graftsan host-sync sanitizer: asnumpy/asscalar/item/"
             "wait_to_read in a steady-state region must be claimed by "
             "a static suppression or baseline entry, else they become "
             "san-host-sync findings")
register_env("MXNET_SAN_LOCK_ORDER", bool, False,
             "graftsan lock-order sanitizer: tracked locks build a "
             "runtime acquisition-order graph; a cycle (potential "
             "deadlock) is reported with both witness stacks")
register_env("MXNET_SAN_DONATION", bool, False,
             "graftsan donation sanitizer: buffers consumed by a "
             "donated XLA dispatch are registered and any later use "
             "is reported with the declaring bind site")
register_env("MXNET_SAN_REPORT", str, None,
             "path for the graftsan findings/claim-statistics JSON "
             "report written at process exit when any sanitizer is "
             "armed")
register_env("MXNET_PLAN_HBM_BYTES", int, 0,
             "per-chip memory budget (bytes) for graftplan's oom-risk "
             "checker: configurations whose predicted per-chip peak "
             "(params + ZeRO-sharded optimizer slots + activation "
             "liveness + collective staging) exceeds it fail "
             "tools/lint.py --plan; 0 disables the budget gate")
register_env("MXNET_PLAN_BUCKET_FILL_MIN", float, 0.6,
             "minimum predicted per-rung fill of a serving bucket "
             "ladder (uniform-arrival model) before graftplan's "
             "bucket-plan-waste checker flags the rung as padding "
             "waste")
register_env("MXNET_IR", bool, True,
             "graftir master switch: include the jaxpr-level IR leg "
             "(donation/dtype/collective/Pallas verification + cost "
             "model, analysis/ir/) in tools/lint.py --all runs and "
             "the bench cost columns; tools/lint.py --ir always runs "
             "(explicit request wins)")
register_env("MXNET_IR_F64_ALLOWLIST", str, None,
             "comma-separated substrings naming DELIBERATE f64 sites "
             "(matched against the eqn's name-stack/primitive) that "
             "graftir's ir-dtype-drift skips — e.g. fp32-master "
             "accumulators promoted on purpose; unset allows none")
register_env("MXNET_IR_COST_REPORT", str, None,
             "path where tools/lint.py --ir/--all writes the traced "
             "catalog's static CostReports (flops/bytes/op-mix per "
             "program) as JSON, next to graftplan's memory numbers")
register_env("MXNET_KERN", bool, True,
             "graftkern master switch: include the kernel analysis leg "
             "(grid coverage / VMEM budget / retrace hazard / "
             "shard_map safety over the Pallas kernel catalog, "
             "analysis/kern/) in tools/lint.py --all runs; "
             "tools/lint.py --kern always runs (explicit request "
             "wins).  The mesh_sweep_safe shard-safety verdict is "
             "computed regardless — this knob only gates the lint leg")
register_env("MXNET_KERN_VMEM_BYTES", int, 16 * 1024 * 1024,
             "per-core VMEM budget (bytes) for graftkern's "
             "kern-vmem-budget checker: a kernel whose per-program-"
             "instance residency (operand blocks x dtypes + scratch) "
             "exceeds it fails tools/lint.py --kern; default 16 MiB "
             "(v5e-class core)")
register_env("MXNET_PALLAS_FUSED_OPT", str, "auto",
             "one-sweep Pallas optimizer (ParallelTrainer ZeRO sweep; "
             "executor fused step, where only the 1-D leaves — biases, "
             "norm gammas/betas — ride the flat buckets and every N-D "
             "weight is updated per array in its own layout; "
             "fused_sgd_momentum/fused_adam): "
             "auto = on where the kernels compile natively (TPU), 1 = "
             "force on anywhere (interpret mode — how CPU tier-1 "
             "exercises the kernels), 0 = off; the per-array tree_map "
             "path is the fallback, bit-parity oracle and bench A/B "
             "leg")
register_env("MXNET_PALLAS_NORM", str, "auto",
             "fused Pallas last-axis LayerNorm (fwd + custom_vjp bwd): "
             "auto = native TPU only, 1 = force (interpret), 0 = off "
             "(jnp reduction chain)")
register_env("MXNET_PALLAS_SOFTMAX", str, "auto",
             "fused Pallas bias+softmax (SoftmaxOutput core, non-flash "
             "attention probabilities): auto = native TPU only, 1 = "
             "force (interpret), 0 = off (jax.nn.softmax)")
register_env("MXNET_PALLAS_BN_RELU", str, "auto",
             "executor eval-graph peephole: inference BatchNorm(+ReLU) "
             "as one fused_scale_bias_relu pass: auto = native TPU "
             "only, 1 = force (interpret), 0 = off (per-op path)")
register_env("MXNET_PALLAS_OPT_BLOCK_ELEMS", int, 0,
             "elements per grid step of the fused optimizer sweep "
             "kernels (rounded to whole (8,128) fp32 tiles); 0 picks "
             "the 128Ki-element default")
register_env("MXNET_PALLAS_NORM_BLOCK_ROWS", int, 0,
             "rows per grid step of the fused layernorm kernels; 0 "
             "sizes blocks to ~512 KiB of VMEM per operand")
register_env("MXNET_PALLAS_SOFTMAX_BLOCK_ROWS", int, 0,
             "rows per grid step of the fused softmax kernels; 0 "
             "sizes blocks to ~512 KiB of VMEM per operand")
register_env("MXNET_PALLAS_OPT_BUCKET_BYTES", int, 0,
             "bucket size cap for the executor fused step's optimizer "
             "sweep (the 1-D leaves of each (lr_mult, wd_mult) group "
             "concatenated into contiguous fp32 buckets; N-D weights "
             "are never bucketed); <= 0 sweeps each group as one "
             "monolithic bucket")
register_env("MXNET_FAULT_PLAN", str, None,
             "deterministic fault-injection schedule (graftfault): "
             "inline JSON or @/path/to/plan.json; armed at import, "
             "every instrumented site then consults it "
             "(docs/faq/fault_tolerance.md has the site catalog and "
             "rule vocabulary); unset = one boolean per site")
register_env("MXNET_FAULT_RETRIES", int, 3,
             "default retry budget of the shared BackoffPolicy "
             "(fault/backoff.py): elastic training restarts, watcher "
             "transient reads, kvstore weight reads, serving submit "
             "retries; per-call-site overrides win")
register_env("MXNET_FAULT_BACKOFF_BASE_S", float, 0.5,
             "first-retry delay of the shared BackoffPolicy; "
             "subsequent delays multiply by 2 up to "
             "MXNET_FAULT_BACKOFF_MAX_S")
register_env("MXNET_FAULT_BACKOFF_MAX_S", float, 30.0,
             "cap on any single BackoffPolicy delay")
register_env("MXNET_FAULT_BACKOFF_JITTER", float, 0.25,
             "jitter fraction of BackoffPolicy delays (each delay is "
             "scaled by a seeded uniform draw from [1-j, 1+j]) so a "
             "preempted fleet does not retry in lockstep")
register_env("MXNET_SERVING_SUBMIT_RETRIES", int, 0,
             "opt-in client-side retry budget for serving submissions "
             "rejected with QueueFull: infer()/infer_async() re-submit "
             "up to this many times, sleeping the error's retry_after_s "
             "hint with BackoffPolicy jitter; 0 (default) surfaces "
             "QueueFull to the caller unchanged")
register_env("MXNET_SERVING_MODEL_QUEUE_DEPTH", int, 0,
             "default per-model queue quota: at most this many requests "
             "of one model queued at once, rejected with that model's "
             "own QueueFull/retry_after_s beyond it (0 = no per-model "
             "cap; the global MXNET_SERVING_QUEUE_DEPTH always applies); "
             "ModelServer.set_quota overrides per model")
register_env("MXNET_SERVING_MODEL_INFLIGHT", int, 0,
             "default per-model cap on accepted-but-unresolved requests "
             "(queued + executing); 0 = no cap; set_quota overrides")
register_env("MXNET_SERVING_PRIORITY_CLASSES", int, 3,
             "number of serving priority classes (0 = most important, "
             "N-1 = first shed under brownout)")
register_env("MXNET_SERVING_DEFAULT_PRIORITY", int, 1,
             "priority class assigned to requests that pass none")
register_env("MXNET_SERVING_BROWNOUT_HIGH", float, 0.75,
             "queue-fill fraction (of MXNET_SERVING_QUEUE_DEPTH) at "
             "which the server enters declared brownout: hold-open "
             "window skipped, dispatch shrunk to "
             "MXNET_SERVING_BROWNOUT_MAX_BATCH, priority classes >= "
             "MXNET_SERVING_BROWNOUT_REJECT_CLASS shed")
register_env("MXNET_SERVING_BROWNOUT_LOW", float, 0.25,
             "queue-fill fraction at which brownout exits (hysteresis: "
             "must be below MXNET_SERVING_BROWNOUT_HIGH)")
register_env("MXNET_SERVING_BROWNOUT_MAX_BATCH", int, 0,
             "dispatch-size cap while in brownout (smaller programs "
             "turn the queue over faster); 0 keeps the ladder max")
register_env("MXNET_SERVING_BROWNOUT_REJECT_CLASS", int, 2,
             "lowest priority class still ADMITTED during brownout: "
             "classes >= this are rejected at submit and shed from the "
             "queue, counted per model+class in "
             "mxnet_serving_sheds_total")
register_env("MXNET_SERVING_CANARY_FRACTION", float, 0.0,
             "staged-promotion traffic fraction: watcher-promoted "
             "checkpoint versions serve only this fraction of the "
             "model's unversioned traffic until the health gate "
             "decides promotion vs rollback; 0 (default) promotes "
             "directly (the PR 5 behavior)")
register_env("MXNET_SERVING_CANARY_MIN_REQUESTS", int, 20,
             "canary completions required before the health gate "
             "decides (the evidence budget; the non-finite sentinel "
             "rolls back immediately regardless)")
register_env("MXNET_SERVING_CANARY_MAX_ERROR_RATE", float, 0.05,
             "canary failed/completed ratio above which the gate rolls "
             "back")
register_env("MXNET_SERVING_CANARY_P99_FACTOR", float, 3.0,
             "rollback when canary p99 latency exceeds this multiple "
             "of the baseline version's p99 over the same window")
register_env("MXNET_SERVING_GEN_SLOTS", int, 8,
             "decode slots per generative model: the fixed lane count "
             "of the continuous-batching pool (KV-cache is "
             "preallocated for all slots at add_generative_model)")
register_env("MXNET_SERVING_GEN_MAX_LEN", int, 0,
             "KV-cache window per decode slot in tokens; prompts "
             "longer than the window are rejected and generations "
             "past it attend to the most recent window (ring "
             "wrap-around); 0 uses the model's positional-table size")
register_env("MXNET_SERVING_GEN_MAX_NEW_TOKENS", int, 64,
             "default generation budget when infer_stream passes no "
             "max_new_tokens; a slot always frees at EOS or budget")
register_env("MXNET_SERVING_GEN_PREFILL_BATCH", int, 4,
             "max prompts coalesced into one prefill program; sets "
             "the batch axis of the prefill (batch, length) grid, so "
             "raising it multiplies warmup compiles by one more rung")
register_env("MXNET_SERVING_GEN_QUEUE_DEPTH", int, 128,
             "pending generative requests per model beyond which "
             "submits are rejected with QueueFull/retry_after_s")
register_env("MXNET_SERVING_GEN_SLOT_QUOTA", int, 0,
             "default per-tenant cap on concurrently held decode "
             "slots (0 = no cap); DecodeScheduler.set_slot_quota "
             "overrides per tenant — a tenant at its cap queues even "
             "when slots are free")
register_env("MXNET_SERVING_GEN_BROWNOUT_MS", float, 0.0,
             "generative brownout budget: when (remaining in-flight "
             "tokens + queued token demand) x the live per-token "
             "median predicts a drain time above this, queued "
             "requests of class >= MXNET_SERVING_BROWNOUT_REJECT_CLASS "
             "are shed (hysteresis exits at half the budget); 0 "
             "disables token-priced brownout")
register_env("MXNET_SERVING_CANARY_TIMEOUT_S", float, 600.0,
             "canary decision budget: a canary that cannot gather "
             "min_requests within this window is decided on whatever "
             "evidence exists (healthy -> promote, zero traffic -> "
             "rollback)")
register_env("MXNET_TRANSPORT_SEND_RETRIES", int, 4,
             "at-least-once resend budget of "
             "SpoolTransport.send_reliable (parallel/transport.py): "
             "link faults (partition, lost ack) are retried this many "
             "times on the shared BackoffPolicy, reusing one message "
             "id so the receiver's dedup keeps delivery exactly-once")
register_env("MXNET_TRANSPORT_POLL_S", float, 0.005,
             "SpoolTransport receive poll interval: how often "
             "recv_wait re-scans the inbox while empty")
register_env("MXNET_FLEET_HEALTH_INTERVAL_S", float, 0.2,
             "replica health-beat period: each fleet replica reports "
             "its ledger/latency/non-finite evidence to the front "
             "door this often (serving/fleet.py); the front door "
             "treats a replica silent for several periods as dead")
register_env("MXNET_FLEET_PROBE_RETRIES", int, 5,
             "re-admission probe budget for an ejected fleet replica: "
             "the front door probes it on BackoffPolicy delays this "
             "many times before declaring it dead for good")
register_env("MXNET_FLEET_SUBMIT_RETRIES", int, 3,
             "front-door resubmit budget per request: replica death, "
             "link failure or remote QueueFull re-route the SAME "
             "request id to another replica up to this many times "
             "(honoring the remote retry_after_s hint); the ledger "
             "dedups, so a client never sees a duplicate")
register_env("MXNET_TRACE", bool, False,
             "master switch for graftrace request tracing + the flight "
             "recorder (telemetry/tracing.py): off, every span call "
             "site costs one boolean check; on, request-scoped spans "
             "land in the per-process ring and cross process "
             "boundaries as _trace headers on transport frames")
register_env("MXNET_TRACE_SAMPLE", float, 0.01,
             "tail-sampling keep rate for HEALTHY traces at export; "
             "anomalous traces (shed, failed, deadline-exceeded, "
             "canary-routed, fault-injected, resubmitted, "
             "p99-exceeding) are always retained regardless")
register_env("MXNET_TRACE_SEED", int, 0,
             "seed of the per-trace sampling hash — the keep decision "
             "is pure in (seed, trace_id), so runs and processes agree "
             "on which healthy traces survive")
register_env("MXNET_TRACE_RING", int, 4096,
             "finished-span ring capacity per process; spans of traces "
             "whose root has not finished stay ringed until flush, "
             "oldest spill first")
register_env("MXNET_TRACE_DIR", str, None,
             "directory for JSONL trace shards (trace-<pid>.jsonl, "
             "appended by flush()/atexit) and flight-recorder incident "
             "dumps; unset disables export but not in-ring tracing")
register_env("MXNET_TRACE_P99_FACTOR", float, 3.0,
             "a finished root span slower than this multiple of its "
             "name's running p99 estimate marks the trace anomalous "
             "(p99_exceeded) for tail retention")
register_env("MXNET_TRACE_FLIGHT_RING", int, 512,
             "flight-recorder ring capacity: last N control-plane "
             "events (shed/brownout transitions, canary decisions, "
             "quota rejections, fault injections, elastic retries) "
             "kept for incident dumps")
register_env("MXNET_TRACE_FLIGHT_DUMPS", int, 8,
             "max flight-recorder incident dumps per process — a "
             "crash-looping trigger cannot fill the disk")
register_env("MXNET_TELEMETRY_LABEL_CAP", int, 256,
             "label-cardinality cap per metric family: past this many "
             "distinct label sets, new ones collapse into the "
             "__overflow__ child and "
             "mxnet_telemetry_label_overflow_total{metric=...} counts "
             "the spill (0 = uncapped)")
