"""graftrace (PR 18) — request tracing, tail sampling, the incident
flight recorder, and the cross-process merge path.

Fast legs: context minting/propagation, the one-boolean off path (by
identity AND by a timed bound), ring bounds, tail-sampled JSONL export
merged by ``tools/trace.py``, p99 anomaly marking, histogram
exemplars, the telemetry label-cardinality guard, flight-recorder
record/incident semantics, and the span-discipline checker's two
directions on inline ASTs.  The capstone is the 2-process fleet drill:
SIGKILL a replica mid-request and assert the MERGED trace shows
route -> death -> resubmit -> serve stitched across pids.
"""
import ast
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 — platform init before subprocesses
from mxnet_tpu.serving import ServingError
from mxnet_tpu.serving.fleet import FleetFrontDoor, spawn_replica
from mxnet_tpu.telemetry import flight, tracing
from mxnet_tpu.telemetry.registry import (Histogram, MetricsRegistry,
                                          OVERFLOW_LABEL,
                                          validate_exposition)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_tracing():
    """No armed tracing state may leak across tests."""
    yield
    tracing.disable()
    tracing.reset()
    flight.reset()


def _load_trace_tool():
    spec = importlib.util.spec_from_file_location(
        "trace_tool", os.path.join(REPO, "tools", "trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# context + propagation
# ---------------------------------------------------------------------------
def test_mint_use_and_span_parentage(tmp_path):
    tracing.reset()
    tracing.enable(sample=1.0, trace_dir=None, p99_factor=1e9)
    ctx = tracing.mint(tenant="a", priority=2)
    assert ctx.span_id is None and ctx.baggage == {"tenant": "a",
                                                  "priority": 2}
    with tracing.use(ctx):
        assert tracing.current() is ctx
        with tracing.span("outer") as outer:
            with tracing.span("inner") as inner:
                assert inner.trace_id == ctx.trace_id
                assert inner.parent_id == outer.span_id
    assert tracing.current() is None
    recs = {r["name"]: r for r in tracing.snapshot()}
    assert recs["outer"]["parent"] is None          # root of the trace
    assert recs["inner"]["parent"] == recs["outer"]["span"]
    assert recs["inner"]["baggage"] == {"tenant": "a", "priority": 2}
    # use(None) is a no-op (extraction misses stay cheap)
    with tracing.use(None):
        assert tracing.current() is None


def test_inject_extract_roundtrip():
    tracing.reset()
    tracing.enable(sample=1.0, trace_dir=None)
    ctx = tracing.mint(tenant="a").child("span-7")
    meta = tracing.inject({"id": "req-1"}, ctx)
    assert meta["id"] == "req-1"                    # payload untouched
    back = tracing.extract(meta)
    assert back.trace_id == ctx.trace_id
    assert back.span_id == "span-7"
    assert back.baggage == {"tenant": "a"}
    assert tracing.extract({"id": "req-1"}) is None  # no header
    assert tracing.extract(None) is None
    tracing.disable()
    # disarmed inject leaves meta alone entirely
    m2 = tracing.inject({"id": "x"}, ctx)
    assert "_trace" not in m2


def test_off_path_is_the_shared_noop_singleton():
    tracing.disable()
    s1 = tracing.span("a", rows=3)
    s2 = tracing.start_span("b")
    assert s1 is s2 is tracing._NOOP                # zero allocation
    with s1 as inside:
        assert inside is tracing._NOOP
    assert s1.finish(status="boom") is None
    assert s1.ctx is None
    tracing.mark("ignored")                         # all no-ops
    tracing.add_span("x", tracing.mint(), time.time(), 1.0)
    assert tracing.snapshot() == [] and tracing.anomalous() == {}
    # the timed bound the docstring promises: one boolean per call
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        tracing.span("hot")
    assert time.perf_counter() - t0 < 2.0           # ~50 ns/call real


def test_ring_bounded_and_finish_idempotent():
    tracing.reset()
    tracing.enable(sample=1.0, ring=16, trace_dir=None, p99_factor=1e9)
    ctx = tracing.mint()
    for i in range(40):
        tracing.span("s%d" % i, ctx=ctx).finish()
    assert len(tracing.snapshot()) == 16            # bounded, oldest out
    sp = tracing.start_span("once", ctx=tracing.mint())
    sp.finish(status="boom")
    sp.finish()                                     # first call won
    recs = [r for r in tracing.snapshot() if r["name"] == "once"]
    assert len(recs) == 1 and recs[0]["status"] == "boom"
    assert tracing.anomalous()[sp.trace_id] == "boom"


def test_ambient_background_trace_per_thread():
    tracing.reset()
    tracing.enable(sample=1.0, trace_dir=None)
    tracing.span("bg.work").finish()                # no context anywhere
    rec = tracing.snapshot()[-1]
    assert rec["trace"].startswith("bg-")
    tids = []

    def worker():
        with tracing.span("bg.other") as sp:
            tids.append(sp.trace_id)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert tids[0].startswith("bg-") and tids[0] != rec["trace"]


# ---------------------------------------------------------------------------
# tail sampling + export + merge
# ---------------------------------------------------------------------------
def test_keep_verdicts_are_seeded_and_anomaly_wins():
    tracing.reset()
    tracing.enable(sample=0.0, seed=3, trace_dir=None)
    assert tracing.keep("t-healthy") is False       # sampled out
    tracing.mark("shed", tracing.TraceContext("t-bad"))
    assert tracing.keep("t-bad") is True            # anomaly always kept
    tracing.enable(sample=1.0, seed=3, trace_dir=None)
    assert tracing.keep("t-healthy") is True
    # pure in (seed, trace_id): reproducible across calls, and a seed
    # change reshuffles which healthy traces survive
    tracing.enable(sample=0.5, seed=3, trace_dir=None)
    first = [tracing.keep("t-%d" % i) for i in range(64)]
    assert [tracing.keep("t-%d" % i) for i in range(64)] == first
    assert any(first) and not all(first)            # rate really applies
    tracing.enable(sample=0.5, seed=4, trace_dir=None)
    assert [tracing.keep("t-%d" % i) for i in range(64)] != first


def test_export_jsonl_tail_sampling_and_inflight_stay(tmp_path):
    tracing.reset()
    tracing.enable(sample=0.0, trace_dir=str(tmp_path), p99_factor=1e9)
    healthy = tracing.start_span("req", ctx=tracing.mint(kind="healthy"))
    healthy.finish()
    bad = tracing.start_span("req", ctx=tracing.mint(kind="bad"))
    bad.finish(status="shed")
    inflight_ctx = tracing.mint(kind="inflight")
    tracing.add_span("child", inflight_ctx.child("s1"), time.time(), 1.0)
    wrote = tracing.export_jsonl()
    assert wrote == 1                               # only the anomaly
    shard = tracing.shard_path()
    with open(shard) as f:
        recs = [json.loads(line) for line in f]
    assert [r["trace"] for r in recs] == [bad.trace_id]
    assert recs[0]["anomaly"] == "shed"
    st = tracing.stats()
    assert st["exported"] == 1 and st["dropped"] == 1
    # the in-flight trace's span re-parked for the next flush
    assert [r["trace"] for r in tracing.snapshot()] \
        == [inflight_ctx.trace_id]
    # chrome events mirror the ring
    evs = tracing.chrome_events()
    assert evs and evs[0]["ph"] == "X" \
        and evs[0]["args"]["trace"] == inflight_ctx.trace_id


def test_merge_joins_shards_and_survives_torn_lines(tmp_path):
    tracing.reset()
    tracing.enable(sample=1.0, trace_dir=str(tmp_path), p99_factor=1e9)
    root = tracing.start_span("fleet.infer", ctx=tracing.mint())
    tid = root.trace_id
    root.finish(status="replica_dead")
    tracing.export_jsonl()
    # a second process's shard: one span of the SAME trace + a torn
    # tail (SIGKILLed writer) + an unrelated healthy trace
    other = os.path.join(str(tmp_path), "trace-99999.jsonl")
    with open(other, "w") as f:
        f.write(json.dumps({"trace": tid, "span": "r1", "parent": None,
                            "name": "replica.serve", "ts": time.time(),
                            "dur_ms": 2.0, "status": "ok",
                            "pid": 99999}) + "\n")
        f.write('{"trace": "t-torn", "name": "half')   # no newline: torn
    tool = _load_trace_tool()
    traces, bad = tool.load_shards([str(tmp_path)])
    assert bad == 1
    assert {r["name"] for r in traces[tid]} \
        == {"fleet.infer", "replica.serve"}
    assert {r["pid"] for r in traces[tid]} == {os.getpid(), 99999}
    tree = tool.format_tree(tid, traces[tid])
    assert "replica.serve" in tree and "replica_dead" in tree
    out = str(tmp_path / "merged.json")
    assert tool.main(["merge", str(tmp_path), "--out", out]) == 0
    with open(out) as f:
        doc = json.load(f)
    assert doc["bad_lines"] == 1
    assert doc["anomalous"][tid] == "replica_dead"
    chrome = str(tmp_path / "chrome.json")
    assert tool.main(["merge", str(tmp_path), "--chrome", chrome,
                      "--trace", tid]) == 0
    with open(chrome) as f:
        lanes = {e["tid"] for e in json.load(f)["traceEvents"]}
    assert len(lanes) == 1                          # one lane per trace


def test_root_slower_than_p99_threshold_is_marked():
    tracing.reset()
    tracing.enable(sample=1.0, trace_dir=None, p99_factor=2.0)
    for _ in range(16):                             # seed the window
        sp = tracing.start_span("op", ctx=tracing.mint())
        sp._t0 = time.perf_counter() - 0.001        # ~1 ms roots
        sp.finish()
    assert not any(r == "p99_exceeded"
                   for r in tracing.anomalous().values())
    slow = tracing.start_span("op", ctx=tracing.mint())
    slow._t0 = time.perf_counter() - 0.5            # 500 ms >> 2*p99
    slow.finish()
    assert tracing.anomalous()[slow.trace_id] == "p99_exceeded"


# ---------------------------------------------------------------------------
# histogram exemplars + label-cardinality guard
# ---------------------------------------------------------------------------
def test_histogram_exemplars_keep_worst_per_bucket():
    h = Histogram([0.1, 1.0])
    h.observe(0.05, exemplar="t-small")
    h.observe(0.09, exemplar="t-worse")             # same bucket, worse
    h.observe(0.07, exemplar="t-better")            # not retained
    h.observe(5.0, exemplar="t-inf")
    ex = h.exemplars()
    assert ex[0.1] == {"value": 0.09, "trace": "t-worse"}
    assert ex["+Inf"] == {"value": 5.0, "trace": "t-inf"}
    reg = MetricsRegistry()
    fam = reg.histogram("t_latency_seconds", buckets=[0.1, 1.0])
    fam.observe(0.09, exemplar="t-abc")
    snap = reg.snapshot()["t_latency_seconds"]["values"][0]
    assert snap["exemplars"][0.1]["trace"] == "t-abc"
    # exemplars ride snapshot() only; the text exposition stays valid
    validate_exposition(reg.prometheus_text())


def test_label_cardinality_guard_spills_to_overflow_child():
    reg = MetricsRegistry()
    reg.set_label_cap(3)
    fam = reg.counter("t_requests_total", "per-tenant")
    for i in range(5):
        fam.labels(tenant="t%d" % i).inc()
    kids = dict((tuple(sorted(k.items())), c) for k, c in fam.items())
    keys = {dict(k)["tenant"] for k in kids}
    assert keys == {"t0", "t1", "t2", OVERFLOW_LABEL}
    assert kids[(("tenant", OVERFLOW_LABEL),)].value == 2
    # known label sets keep routing to their own child past the cap
    fam.labels(tenant="t0").inc()
    assert kids[(("tenant", "t0"),)].value == 2
    # one spill counted per collapsed set, labeled by family
    spill = reg.counter("mxnet_telemetry_label_overflow_total")
    assert spill.labels(metric="t_requests_total").value == 2
    # the unlabeled () child is exempt (no labels to attack with)
    fam.inc()
    assert fam.value == 1
    validate_exposition(reg.prometheus_text())


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_record_gated_and_never_raises(tmp_path):
    tracing.reset()
    flight.reset()
    tracing.disable()
    flight.record("shed", tenant="a")
    assert flight.events() == []                    # disarmed: free
    tracing.enable(sample=1.0, trace_dir=str(tmp_path))
    flight.reset()

    class Hostile:
        def __str__(self):
            raise ValueError("unprintable")

    flight.record("shed", tenant="a", obj=object(), ts="caller-lie")
    flight.record("shed", bad=Hostile())            # swallowed, no raise
    evs = flight.events()
    assert len(evs) == 1                            # hostile one dropped
    assert evs[0]["kind"] == "shed"
    assert isinstance(evs[0]["ts"], float)          # reserved key wins
    assert evs[0]["obj"].startswith("<object object")


def test_flight_incident_dump_is_self_contained(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_FLIGHT_DUMPS", "2")
    tracing.reset()
    flight.reset()
    tracing.enable(sample=1.0, trace_dir=str(tmp_path), p99_factor=1e9)
    bad = tracing.start_span("serving.request", ctx=tracing.mint(),
                             model="tenantA")
    bad.finish(status="shed")
    flight.record("shed", tenant="tenantA", depth=9)
    path = flight.incident("unit_probe", note="n1")
    assert path and os.path.exists(path)
    with open(path) as f:
        dump = json.load(f)
    assert dump["incident"] == "unit_probe"
    assert dump["detail"] == {"note": "n1"}
    assert [e["kind"] for e in dump["events"]] == ["shed"]
    assert dump["anomalous"][bad.trace_id] == "shed"
    spans = dump["traces"][bad.trace_id]
    assert spans[0]["tags"]["model"] == "tenantA"
    # the dump cap holds (MXNET_TRACE_FLIGHT_DUMPS=2): third is refused
    assert flight.incident("unit_probe") is not None
    assert flight.incident("unit_probe") is None
    assert flight.dumps_written() == 2
    # no trace dir -> no dump, never an error
    tracing._STATE["dir"] = None
    assert flight.incident("unit_probe") is None
    tracing._STATE["dir"] = str(tmp_path)


# ---------------------------------------------------------------------------
# span-discipline checker (both directions, inline ASTs)
# ---------------------------------------------------------------------------
class _Ctx:
    def __init__(self, catalog=()):
        self.root = "/nonexistent"
        self.memo = {"span-discipline-catalog": set(catalog)}
        self.project = None


def _discipline(src, catalog=()):
    from mxnet_tpu.analysis.checkers.span_discipline import \
        SpanDisciplineChecker
    tree = ast.parse(src)
    return SpanDisciplineChecker().check(
        "x.py", "mxnet_tpu/x.py", src, tree, _Ctx(catalog))


def test_span_discipline_flags_leaks_and_dropped_handles():
    leaky = _discipline(
        "def f():\n"
        "    sp = start_span('a')\n"
        "    do_work()\n")
    assert len(leaky) == 1 and "leaks open" in leaky[0].message
    dropped = _discipline(
        "def f():\n"
        "    start_span('a')\n")
    assert len(dropped) == 1 and "dropped" in dropped[0].message


def test_span_discipline_accepts_closed_and_escaped_spans():
    ok_finally = (
        "def f():\n"
        "    sp = start_span('a')\n"
        "    try:\n"
        "        work()\n"
        "    finally:\n"
        "        sp.finish()\n")
    ok_with = (
        "def f():\n"
        "    sp = start_span('a')\n"
        "    with sp:\n"
        "        work()\n")
    ok_escape = (
        "def f(self):\n"
        "    sp = start_span('a')\n"
        "    self.pending.append(sp)\n")   # ownership transferred
    for src in (ok_finally, ok_with, ok_escape):
        assert _discipline(src) == []


def test_span_discipline_bare_finish_outside_finally_is_flagged():
    # a finish() outside any finally is not leak-proof: the statement
    # above it can raise past the close
    found = _discipline(
        "def f():\n"
        "    sp = start_span('a')\n"
        "    work_that_can_raise()\n"
        "    sp.finish()\n")
    assert len(found) == 1 and found[0].message.startswith("span 'sp'")


def test_span_discipline_untraced_cataloged_fires():
    catalog = {"serving.cache.get"}
    bare = _discipline(
        "def f(hooks, m):\n"
        "    hooks.fire('serving.cache.get', model=m)\n", catalog)
    assert len(bare) == 1 and "outside any tracing span" in bare[0].message
    traced = _discipline(
        "def f(hooks, m):\n"
        "    with _trace.span('exec.bind'):\n"
        "        hooks.fire('serving.cache.get', model=m)\n", catalog)
    assert traced == []
    multi_item = _discipline(
        "def f(hooks, m, lock):\n"
        "    with lock, _span('exec.bind'):\n"
        "        hooks.fire('serving.cache.get', model=m)\n", catalog)
    assert multi_item == []                 # helper *span callees count
    uncataloged = _discipline(
        "def f(hooks):\n"
        "    hooks.fire('training.step')\n", catalog)
    assert uncataloged == []                # not drillable, not required
    prefix = _discipline(
        "def f(hooks, op):\n"
        "    hooks.fire('serving.' + op)\n", catalog)
    assert len(prefix) == 1                 # prefix pattern matches


# ---------------------------------------------------------------------------
# the capstone: 2-process fleet, SIGKILL mid-request, merged trace
# ---------------------------------------------------------------------------
VICTIM_DELAY_PLAN = {
    "seed": 5,
    "rules": [
        # every batch on the victim stalls ~1.5 s inside
        # serving.worker, guaranteeing the SIGKILL lands while the
        # routed request is in the victim's hands
        {"site": "serving.worker", "kind": "delay", "delay_s": 1.5,
         "p": 1.0, "times": 0},
    ],
}


def test_fleet_sigkill_resubmit_stitches_one_merged_trace(tmp_path):
    """Front door (this process) + two ``spawn_replica`` subprocesses,
    all tracing at sample 1.0 into one shard directory.  SIGKILL the
    replica holding the traced request; the request resubmits and
    serves on the survivor, and the MERGED shards show one trace with
    route(dead) -> route(ok) -> replica.serve(resubmits=1) spanning at
    least two pids — with exactly ONE replica.serve (the victim's ring
    died unflushed: exactly-once in the trace, not just the ledger)."""
    trace_dir = str(tmp_path / "traces")
    fleet_root = str(tmp_path / "fleet")
    os.makedirs(trace_dir)
    os.makedirs(fleet_root)
    env = dict(os.environ)
    env.pop("MXNET_FAULT_PLAN", None)
    env.update({"MXNET_TRACE": "1", "MXNET_TRACE_DIR": trace_dir,
                "MXNET_TRACE_SAMPLE": "1.0", "JAX_PLATFORMS": "cpu"})
    tracing.reset()
    flight.reset()
    tracing.enable(sample=1.0, trace_dir=trace_dir, p99_factor=1e9)
    fd = FleetFrontDoor(fleet_root, 3, request_timeout_s=30.0,
                        health_interval_s=0.1)
    x = np.random.RandomState(0).randn(1, 6).astype(np.float32)
    victim = None
    closed = False
    try:
        fd.add_replica(spawn_replica(fleet_root, 1, 3, env=env))
        deadline = time.monotonic() + 180
        up = False
        while time.monotonic() < deadline:      # survivor boot (jax...)
            try:
                fd.infer("m", x)
                up = True
                break
            except ServingError:
                time.sleep(0.2)
        assert up, "survivor replica never came up: %r" \
            % (fd.replica_status(),)
        victim = fd.add_replica(spawn_replica(
            fleet_root, 2, 3, env=env, fault_plan=VICTIM_DELAY_PLAN))
        # steer round-robin so the NEXT pick is the victim (rid 2):
        # live=[1,2], _pick returns live[(_rr+1) % 2]
        if [1, 2][(fd._rr + 1) % 2] != 2:
            fd.infer("m", x)                    # burns one pick on rid 1
        result = {}

        def client():
            result["out"] = fd.infer("m", x)

        t = threading.Thread(target=client, daemon=True)
        t.start()
        time.sleep(0.6)       # frame sent; victim boots or holds it
        victim.kill()         # SIGKILL mid-request — the host-death move
        t.join(timeout=60)
        assert not t.is_alive() and "out" in result
        assert result["out"][0].shape == (1, 4)
        st = fd.stats()
        assert st["resubmitted"] >= 1
        assert fd.ledger_balanced()
        assert st["replicas"][2][0] in ("ejected", "dead")
        # the survivor flushes its shard right after answering; wait
        # for the write to land before tearing the process down
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not any(
                n.startswith("trace-") and n.endswith(".jsonl")
                for n in os.listdir(trace_dir)):
            time.sleep(0.05)
        fd.close()
        closed = True
        tracing.export_jsonl()

        tool = _load_trace_tool()
        traces, _bad = tool.load_shards([trace_dir])
        # the resubmitted request's trace: the parent marked it when
        # the first route attempt closed "replica_dead"
        tids = [tid for tid, reason in tracing.anomalous().items()
                if reason == "replica_dead"]
        assert len(tids) == 1, tracing.anomalous()
        spans = traces[tids[0]]
        by_name = {}
        for rec in spans:
            by_name.setdefault(rec["name"], []).append(rec)
        root = by_name["fleet.infer"][0]
        assert root["parent"] is None and root["status"] == "ok"
        assert root["pid"] == os.getpid()
        routes = {r["status"] for r in by_name["fleet.route"]}
        assert "replica_dead" in routes and "ok" in routes
        dead_route = [r for r in by_name["fleet.route"]
                      if r["status"] == "replica_dead"][0]
        assert dead_route["tags"]["rid"] == 2
        # exactly ONE serve, on the survivor, carrying the resubmit
        serves = by_name["replica.serve"]
        assert len(serves) == 1
        assert serves[0]["pid"] != os.getpid()
        assert serves[0]["tags"]["resubmits"] == 1
        assert serves[0]["status"] == "ok"
        assert serves[0]["tags"]["req"] == root["tags"]["req"]
        # the survivor's ModelServer JOINED the trace (no fresh mint)
        assert any(r["pid"] == serves[0]["pid"]
                   for r in by_name.get("serving.request", []))
        assert len({r["pid"] for r in spans}) >= 2
        # both processes marked it anomalous; either reason retains it
        anomalies = {r.get("anomaly") for r in spans} - {None}
        assert anomalies & {"replica_dead", "resubmitted"}
        tree = tool.format_tree(tids[0], spans)
        assert "replica.serve" in tree
    finally:
        if victim is not None:
            victim.kill()
        if not closed:
            fd.close()
