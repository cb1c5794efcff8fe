"""Multi-host fleet seam (PR 16) — SpoolTransport network faults, the
FleetFrontDoor exactly-once ledger, and the tier-1 2-process smoke
drill.

Fast legs only: every network fault kind (``partition``, ``slow_link``,
``lost_ack``, ``reorder``) driven through the transport's named
injection sites, backpressure (``InboxFull`` is terminal, never
retried), epoch-based dedup across sender incarnations, trace/replay
identity of a seeded network plan, front-door routing + resubmission +
probe re-admission + remote ``retry_after_s`` hints, and the 2-process
dist_async smoke (this process as coordinator, one ``--kv-worker``
subprocess under seeded lost_ack/reorder weather).  The long
multi-process soak lives in ``tests/test_fault.py`` behind the ``slow``
marker.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault, nd, sym
from mxnet_tpu.fault import BackoffPolicy, FaultPlan
from mxnet_tpu.parallel.transport import InboxFull, SpoolTransport
from mxnet_tpu.serving import (ModelNotFound, ModelServer, QueueFull,
                               ServingError)
from mxnet_tpu.serving.fleet import (FleetFrontDoor, ReplicaHandle,
                                     decode_error, encode_error,
                                     local_replica)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IN_DIM = 6
HID = 4


@pytest.fixture(autouse=True)
def _disarm():
    """No plan leaks across tests."""
    yield
    fault.uninstall()


def _pair(root):
    return SpoolTransport(root, 0, 2), SpoolTransport(root, 1, 2)


def _drain(t, n, timeout_s=5.0):
    got = []
    deadline = time.monotonic() + timeout_s
    while len(got) < n and time.monotonic() < deadline:
        got += t.recv()
        time.sleep(0.005)
    return got


# ---------------------------------------------------------------------------
# transport: framing + the four network fault kinds
# ---------------------------------------------------------------------------

def test_transport_roundtrip_order_and_payload(tmp_path):
    a, b = _pair(str(tmp_path))
    a.send(1, "x", meta={"tag": "first"}, arrays={"v": np.arange(3.0)})
    a.send(1, "x", meta={"tag": "second"})
    got = _drain(b, 2)
    assert [m.meta["tag"] for m in got] == ["first", "second"]
    assert got[0].sender == 0 and got[0].kind == "x"
    np.testing.assert_array_equal(got[0].arrays["v"], np.arange(3.0))
    assert b.stats()["received"] == 2 and a.stats()["sent"] == 2


def test_partition_drops_at_send_per_peer(tmp_path):
    a, b = _pair(str(tmp_path))
    with fault.active_plan({"seed": 3, "rules": [
            {"site": "transport.send", "kind": "partition", "times": 0,
             "where": {"peer": "1"}}]}):
        with pytest.raises(ConnectionError, match="peer 1"):
            a.send(1, "x")
    assert b.recv() == []                       # nothing landed
    assert a.stats()["send_failures"] == 1
    a.send(1, "x", meta={"i": 1})               # link healed
    assert _drain(b, 1)[0].meta["i"] == 1


def test_slow_link_delays_but_delivers(tmp_path):
    a, b = _pair(str(tmp_path))
    with fault.active_plan({"seed": 3, "rules": [
            {"site": "transport.send", "kind": "slow_link",
             "delay_s": 0.05, "times": 1}]}):
        t0 = time.monotonic()
        a.send(1, "x")
        assert time.monotonic() - t0 >= 0.05
    assert len(_drain(b, 1)) == 1


def test_lost_ack_resend_dedups_to_exactly_once(tmp_path):
    """The lost_ack drill: the message LANDS, the ack does not — the
    reliable sender resends under the SAME id and the receiver absorbs
    the duplicates.  Exactly-once on top of an at-least-once link."""
    a, b = _pair(str(tmp_path))
    with fault.active_plan({"seed": 5, "rules": [
            {"site": "transport.send.ack", "kind": "lost_ack",
             "times": 2}]}):
        a.send_reliable(1, "grad", meta={"n": 1})
    got = b.recv()
    assert len(got) == 1 and got[0].meta["n"] == 1
    s = b.stats()
    assert s["received"] == 1 and s["duplicates_dropped"] == 2
    assert a.stats()["resent"] == 2


def test_reorder_swaps_adjacent_sends(tmp_path):
    a, b = _pair(str(tmp_path))
    with fault.active_plan({"seed": 7, "rules": [
            {"site": "transport.send", "kind": "reorder", "times": 1}]}):
        a.send(1, "x", meta={"i": 1})           # parked, not published
        assert b.recv() == []
        a.send(1, "x", meta={"i": 2})           # overtakes, then flushes
    got = _drain(b, 2)
    assert [m.meta["i"] for m in got] == [2, 1]
    assert a.stats()["reordered"] == 1


def test_reorder_on_last_send_is_flushed_not_lost(tmp_path):
    a, b = _pair(str(tmp_path))
    with fault.active_plan({"seed": 7, "rules": [
            {"site": "transport.send", "kind": "reorder", "times": 1}]}):
        a.send(1, "x", meta={"i": 1})
        assert b.recv() == []                   # still parked
        a.close()                               # drain path flushes
    assert _drain(b, 1)[0].meta["i"] == 1


def test_recv_side_reorder_skips_one_scan(tmp_path):
    a, b = _pair(str(tmp_path))
    a.send(1, "x", meta={"i": 1})
    a.send(1, "x", meta={"i": 2})
    with fault.active_plan({"seed": 1, "rules": [
            {"site": "transport.recv", "kind": "reorder", "times": 1}]}):
        first = b.recv()                        # msg 1 skipped this scan
        assert [m.meta["i"] for m in first] == [2]
        assert [m.meta["i"] for m in b.recv()] == [1]


def test_recv_partition_leaves_messages_spooled(tmp_path):
    a, b = _pair(str(tmp_path))
    a.send(1, "x", meta={"i": 1})
    a.send(1, "x", meta={"i": 2})
    with fault.active_plan({"seed": 1, "rules": [
            {"site": "transport.recv", "kind": "partition",
             "times": 1}]}):
        assert b.recv() == []                   # poll broke immediately
        assert b.pending() == 2                 # nothing lost
        assert [m.meta["i"] for m in b.recv()] == [1, 2]


def test_inbox_cap_backpressure_is_terminal(tmp_path):
    """A full inbox raises ``InboxFull`` after the admission timeout,
    and ``send_reliable`` does NOT burn its retry budget on it —
    admission already waited, a receiver that far behind is dead."""
    a = SpoolTransport(str(tmp_path), 0, 2, cap=1, admit_timeout=0.2)
    SpoolTransport(str(tmp_path), 1, 2)         # create the inbox
    a.send(1, "x")
    with pytest.raises(InboxFull, match="backpressure"):
        a.send(1, "x")
    with pytest.raises(InboxFull):
        a.send_reliable(1, "x", retries=5)
    assert a.stats()["resent"] == 0             # no retry consumed


def test_epoch_distinguishes_restarted_sender(tmp_path):
    """A SIGKILLed + respawned rank restarts its seq counter at 1; its
    messages must NOT dedup against its dead predecessor's."""
    root = str(tmp_path)
    b = SpoolTransport(root, 1, 2)
    SpoolTransport(root, 0, 2, epoch=1).send(1, "x", meta={"gen": 1})
    SpoolTransport(root, 0, 2, epoch=2).send(1, "x", meta={"gen": 2})
    got = _drain(b, 2)
    assert sorted(m.meta["gen"] for m in got) == [1, 2]
    assert {(m.sender, m.seq) for m in got} == {(0, 1)}  # same id, twice
    assert b.stats()["duplicates_dropped"] == 0


def test_network_plan_trace_replays_identically(tmp_path):
    """ACCEPTANCE: given the hit sequence, the injected fault timeline
    is a pure function of the (plan, seed) — the witness every soak
    report carries."""
    plan = FaultPlan({"seed": 11, "rules": [
        {"site": "transport.send", "kind": "partition", "p": 0.2,
         "times": 0},
        {"site": "transport.send", "kind": "slow_link",
         "delay_s": 0.0, "p": 0.2, "times": 0},
        {"site": "transport.send.ack", "kind": "lost_ack", "p": 0.2,
         "times": 0},
        {"site": "transport.recv", "kind": "reorder", "p": 0.2,
         "times": 0}]}, trace=True)
    a, b = _pair(str(tmp_path))
    with fault.active_plan(plan):
        for i in range(40):
            try:
                a.send(1, "x", meta={"i": i})
            except ConnectionError:
                pass
            b.recv()
        b.recv()
    injected = plan.stats()["injected"]
    assert {i["kind"] for i in injected} == {"partition", "slow_link",
                                            "lost_ack", "reorder"}
    assert plan.replay() == injected


# ---------------------------------------------------------------------------
# fleet front door
# ---------------------------------------------------------------------------

def _model_server(seed=0):
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=HID, name="fc")
    out = sym.softmax(fc, name="prob")
    rng = np.random.RandomState(seed)
    params = {"fc_weight": nd.array(rng.randn(HID, IN_DIM)
                                    .astype(np.float32)),
              "fc_bias": nd.array(rng.randn(HID).astype(np.float32))}
    srv = ModelServer(max_batch=8, batch_wait_ms=1.0, queue_depth=64,
                      default_timeout_ms=30000.0)
    srv.add_model("m", out, params, {}, {"data": (1, IN_DIM)})
    srv.start()
    return srv


def test_fleet_routes_round_robin_and_balances_ledger(tmp_path):
    fd = FleetFrontDoor(str(tmp_path), 3, request_timeout_s=10.0,
                        health_interval_s=5.0)
    servers = [_model_server(), _model_server()]
    try:
        for rid, srv in enumerate(servers, start=1):
            fd.add_replica(local_replica(str(tmp_path), rid, 3, srv))
        x = np.zeros((1, IN_DIM), np.float32)
        outs = [fd.infer("m", {"data": x}) for _ in range(6)]
        assert all(o[0].shape == (1, HID) for o in outs)
        # identical seed => identical function: routing is invisible
        assert all(np.allclose(o[0], outs[0][0]) for o in outs)
        # round-robin: both replicas actually served
        assert all(s.stats()["requests"]["served"] >= 1
                   for s in servers)
        st = fd.stats()
        assert st["submitted"] == 6 and st["served"] == 6
        assert fd.ledger_balanced()
    finally:
        fd.close()
        for s in servers:
            s.stop(drain=False)
            s.cache.clear()


def test_replica_death_resubmits_same_id_no_duplicates(tmp_path):
    """A request routed to a dead replica is resubmitted (same id) to
    the next healthy one: the ledger records the ejection and the
    resubmission, and every request still reaches exactly ONE terminal
    outcome."""
    root = str(tmp_path)
    fd = FleetFrontDoor(root, 4, request_timeout_s=15.0,
                        health_interval_s=5.0)   # no auto-eject: the
    srv = _model_server()                        # infer path must do it
    corpse = threading.Thread(target=lambda: None)
    corpse.start()
    corpse.join()
    try:
        fd.add_replica(ReplicaHandle(1, thread=corpse))  # dead on arrival
        fd.add_replica(local_replica(root, 2, 4, srv))
        x = np.zeros((1, IN_DIM), np.float32)
        for _ in range(4):
            assert fd.infer("m", {"data": x})[0].shape == (1, HID)
        st = fd.stats()
        assert st["submitted"] == 4 and st["served"] == 4
        assert st["resubmitted"] >= 1 and st["ejections"] >= 1
        assert fd.ledger_balanced()
        assert fd.replica_status()[1][0] in ("ejected", "dead")
    finally:
        fd.close()
        srv.stop(drain=False)
        srv.cache.clear()


class _HintedServer:
    """Fake backend: rejects with a hinted ``QueueFull`` twice, then
    serves — the remote-hint path in one deterministic object."""

    def __init__(self):
        self.calls = 0

    def infer(self, name, inputs, timeout_ms=None, priority=None):
        self.calls += 1
        if self.calls <= 2:
            raise QueueFull("replica saturated", retry_after_s=0.123)
        return [np.ones((1, HID), np.float32)]


def test_remote_retry_after_hint_floors_client_backoff(tmp_path):
    """Satellite: a ``QueueFull`` raised on a REMOTE replica crosses
    the wire typed, and the front door's retry sleeps at least the
    replica's live ``retry_after_s`` hint — same contract as the
    in-process serving client."""
    sleeps = []
    bo = BackoffPolicy(retries=5, base_s=1e-4, max_s=2e-4, jitter=0.0,
                       seed=0, sleep=sleeps.append)
    fd = FleetFrontDoor(str(tmp_path), 2, request_timeout_s=10.0,
                        submit_retries=3, health_interval_s=5.0,
                        submit_backoff=bo)
    try:
        fd.add_replica(local_replica(str(tmp_path), 1, 2,
                                     _HintedServer()))
        out = fd.infer("m", np.zeros((1, IN_DIM), np.float32))
        np.testing.assert_allclose(out[0], 1.0)
        # two remote rejections -> two floored sleeps
        assert len(sleeps) == 2
        assert all(s >= 0.123 for s in sleeps)
        st = fd.stats()
        assert st["retried"] == 2 and st["hint_floors"] == 2
        assert st["last_retry_after_s"] == pytest.approx(0.123)
        assert st["served"] == 1 and fd.ledger_balanced()
    finally:
        fd.close()


def test_error_codec_roundtrip():
    e = decode_error(encode_error(QueueFull("busy", retry_after_s=0.5)))
    assert isinstance(e, QueueFull) and e.retry_after_s == 0.5
    assert isinstance(decode_error(encode_error(ModelNotFound("nope"))),
                      ModelNotFound)
    # unknown types degrade to the taxonomy root, never crash the demux
    assert type(decode_error(encode_error(ValueError("boom")))) \
        is ServingError


def test_ejected_replica_readmitted_by_probe(tmp_path):
    fd = FleetFrontDoor(str(tmp_path), 2, health_interval_s=0.05,
                        probe_retries=5)
    srv = _model_server()
    try:
        fd.add_replica(local_replica(str(tmp_path), 1, 2, srv))
        fd._eject(1, "drill")
        assert fd.replica_status()[1][0] == "ejected"
        deadline = time.monotonic() + 10
        while fd.replica_status()[1][0] != "healthy" \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fd.replica_status()[1] == ("healthy", None)
        assert fd.stats()["readmissions"] == 1
    finally:
        fd.close()
        srv.stop(drain=False)
        srv.cache.clear()


def test_probe_budget_exhaustion_marks_dead(tmp_path):
    fd = FleetFrontDoor(str(tmp_path), 2, health_interval_s=0.03,
                        probe_retries=1, probe_timeout_s=0.05)
    stop = threading.Event()
    silent = threading.Thread(target=stop.wait, daemon=True)
    silent.start()                      # alive, but never answers
    try:
        fd.add_replica(ReplicaHandle(1, thread=silent, stop_event=stop))
        fd._eject(1, "drill")
        deadline = time.monotonic() + 10
        while fd.replica_status()[1][0] != "dead" \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fd.replica_status()[1] == ("dead", "drill")
    finally:
        fd.close()


# ---------------------------------------------------------------------------
# tier-1 2-process smoke drill (the fast leg of the chaos soak)
# ---------------------------------------------------------------------------

SMOKE_PLAN = {
    "seed": 13,
    "rules": [
        {"site": "transport.send.ack", "kind": "lost_ack", "p": 0.35,
         "times": 0},
        {"site": "transport.send", "kind": "slow_link",
         "delay_s": 0.001, "p": 0.3, "times": 0},
        {"site": "transport.send", "kind": "reorder", "p": 0.2,
         "times": 0},
    ],
}


def test_two_process_smoke_drill(tmp_path):
    """Coordinator (this process) + one ``--kv-worker`` subprocess
    under seeded lost_ack/reorder weather: every acked gradient applied
    exactly once, the worker's own replay witness holds, and the whole
    drill fits the tier-1 budget."""
    pushes = 8
    report = str(tmp_path / "kv-report.json")
    env = dict(os.environ)
    env.update({"MXNET_KVSTORE_ASYNC_DIR": str(tmp_path),
                "DMLC_WORKER_ID": "1", "DMLC_NUM_WORKER": "2",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", ""),
                "MXNET_FAULT_PLAN": json.dumps(SMOKE_PLAN)})
    os.environ["MXNET_KVSTORE_ASYNC_DIR"] = str(tmp_path)
    os.environ["DMLC_WORKER_ID"] = "0"
    os.environ["DMLC_NUM_WORKER"] = "2"
    kv = None
    try:
        kv = mx.kv.create("dist_async")
        kv._set_updater(lambda i, g, w: w.__isub__(0.1 * g))
        kv.init("w", nd.zeros((4,)))
        proc = subprocess.run(
            [sys.executable, "-u", "-m", "mxnet_tpu.fault.drill",
             "--kv-worker", "--pushes", str(pushes), "--report",
             report],
            env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(report) as f:
            rec = json.load(f)
        assert rec["final"] and rec["acked"] + rec["failed"] == pushes
        assert rec.get("injected", 0) >= 1          # weather really hit
        assert rec.get("replay_identical") is True  # seeded timeline
        assert kv.wait_to_drain(timeout=30)
        deadline = time.monotonic() + 10            # server thread lag
        while time.monotonic() < deadline and \
                kv._transport.stats()["received"] > len(kv._applied_log):
            time.sleep(0.02)
        ids = [i for _k, i in kv._applied_log]
        applied = len(ids)
        assert len(set(ids)) == applied             # exactly-once
        assert rec["acked"] <= applied <= rec["acked"] + rec["failed"]
        got = nd.zeros((4,))
        kv.pull("w", out=got)
        np.testing.assert_allclose(got.asnumpy(), -0.1 * applied,
                                   rtol=1e-6)
    finally:
        if kv is not None:
            kv.close()
        for var in ("MXNET_KVSTORE_ASYNC_DIR", "DMLC_WORKER_ID",
                    "DMLC_NUM_WORKER"):
            os.environ.pop(var, None)
