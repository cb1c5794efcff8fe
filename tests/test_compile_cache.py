"""Persistent compile cache + warmup manifest (ISSUE 6).

The acceptance pins: a process with a pre-populated cache dir re-binds
from disk (hits, zero misses); every failure path DEGRADES — corrupted
entries fall back to a cold compile, an unwritable dir disables the
cache with a warning, concurrent processes share one dir without
corrupting each other; hygiene evicts LRU by recency under the size
cap; the serving warmup manifest round-trips atomically and replays a
prior process's working set; and the PR 2 invariant — zero
steady-state recompiles after warmup — survives with the cache ON
(the cache makes the first compile per process cheap, never adds new
ones).
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, nd, sym, telemetry
from mxnet_tpu.serving import ExecutorCache, ModelServer, WarmupManifest

IN_DIM = 6
HID = 4


@pytest.fixture(autouse=True)
def _isolated_cache():
    """Every test starts with the cache disabled and zeroed counters,
    and leaves no process-global jax cache config behind."""
    compile_cache.reset()
    yield
    compile_cache.reset()


def _make_model(seed=0):
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=HID, name="fc")
    out = sym.softmax(fc, name="prob")
    rng = np.random.RandomState(seed)
    args = {"fc_weight": nd.array(rng.randn(HID, IN_DIM).astype(np.float32)),
            "fc_bias": nd.array(rng.randn(HID).astype(np.float32))}
    return out, args


def _jit_once(scale):
    """Compile a fresh program (new lambda => new trace, so the only
    in-process shortcut is the DISK cache)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x * scale + 1.0))
    return np.asarray(f(jnp.ones((32, 32), jnp.float32)))


# -- wiring + knobs ----------------------------------------------------------
def test_knobs_registered_and_documented():
    from mxnet_tpu.analysis.checkers.env_knobs import drift_report
    rep = drift_report(prefix="MXNET_COMPILE_CACHE")
    assert rep["used"], "no MXNET_COMPILE_CACHE_* uses found"
    assert rep["unregistered"] == []
    assert rep["undocumented"] == []


def test_configure_populates_and_rehits_from_disk(tmp_path):
    d = tmp_path / "cc"
    assert compile_cache.configure(str(d)) is True
    assert compile_cache.enabled() and compile_cache.cache_dir() == str(d)
    _jit_once(2.0)
    s1 = compile_cache.stats()
    assert s1["misses"] >= 1 and s1["entries"] >= 1
    assert s1["size_bytes"] > 0
    assert [f for f in os.listdir(str(d)) if f.endswith("-cache")]
    # a structurally identical fresh program must deserialize from disk
    _jit_once(2.0)
    s2 = compile_cache.stats()
    assert s2["hits"] > s1["hits"]
    assert s2["misses"] == s1["misses"], \
        "re-compile of an identical program must be a disk hit"


def test_executor_bind_initializes_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path / "env_cc"))
    compile_cache.reset()
    symb, args = _make_model()
    pred = mx.Predictor.from_parts(symb, args, {}, {"data": (1, IN_DIM)})
    pred.forward(data=np.zeros((1, IN_DIM), np.float32))
    pred.get_output(0).asnumpy()
    pred.free()
    assert compile_cache.enabled()
    assert compile_cache.stats()["entries"] >= 1, \
        "the bind path must have wired the env-configured cache"


# -- failure paths degrade, never crash --------------------------------------
def test_corrupted_entry_falls_back_to_cold_compile(tmp_path):
    d = tmp_path / "cc"
    compile_cache.configure(str(d))
    want = _jit_once(3.0)
    for name in os.listdir(str(d)):
        if name.endswith("-cache"):
            with open(os.path.join(str(d), name), "r+b") as f:
                f.write(b"\x00corrupt\x00" * 4)     # truncate-ish garbage
    before = compile_cache.stats()
    with pytest.warns(UserWarning, match="persistent compilation cache"):
        got = _jit_once(3.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    after = compile_cache.stats()
    assert after["errors"] > before["errors"], \
        "a corrupt entry must be counted, not hidden"


def test_unwritable_dir_degrades_to_disabled(tmp_path, caplog):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("a file where the cache dir should be")
    import logging
    with caplog.at_level(logging.WARNING):
        ok = compile_cache.configure(str(blocker / "cache"))
    assert ok is False and not compile_cache.enabled()
    assert compile_cache.stats()["errors"] >= 1
    assert any("compile cache disabled" in r.message for r in caplog.records)
    # and jits still run — cold
    out = _jit_once(4.0)
    assert np.isfinite(out).all()


def test_sweep_evicts_lru_by_read_recency(tmp_path):
    d = tmp_path / "cc"
    d.mkdir()
    now = time.time()
    # entry A: recently WRITTEN but long-unread (stale atime sibling);
    # entry B: old write, recently read.  LRU by read recency evicts A.
    for name, atime_age in (("progA", 9000.0), ("progB", 10.0)):
        cache = d / (name + "-cache")
        atime = d / (name + "-atime")
        cache.write_bytes(b"x" * 100)
        atime.write_bytes(b"")
        os.utime(str(atime), (now - atime_age, now - atime_age))
    assert compile_cache.configure(str(d), max_bytes=150) is True
    names = set(os.listdir(str(d)))
    assert "progB-cache" in names and "progA-cache" not in names
    assert "progA-atime" not in names, "evicted entries drop the sibling"
    st = compile_cache.stats()
    assert st["evictions"] == 1 and st["entries"] == 1


# -- warmup manifest ---------------------------------------------------------
def test_manifest_roundtrip_atomic_and_corrupt_tolerant(tmp_path):
    from mxnet_tpu.serving.registry import ModelVersion
    symb, args = _make_model()
    entry = ModelVersion("m", 1, symb, args, {}, {"data": (1, IN_DIM)})
    path = tmp_path / "warmup.json"
    man = WarmupManifest(str(path))
    assert man.record(entry, 4, backend="cpu") is True
    assert man.record(entry, 4, backend="cpu") is False      # dedupe
    assert man.record(entry, 8, backend="cpu") is True
    assert not [f for f in os.listdir(str(tmp_path))
                if f.startswith(".")], "no temp litter after commits"
    # fresh reader sees the committed key set, keyed by PROGRAM identity
    man2 = WarmupManifest(str(path))
    assert man2.buckets_for("m", entry.symbol_sha) == [4, 8]
    assert man2.buckets_for("m", "0" * 64) == []
    # same architecture under a new version: no new entries
    entry_v2 = ModelVersion("m", 2, symb, args, {}, {"data": (1, IN_DIM)})
    assert entry_v2.symbol_sha == entry.symbol_sha
    man2.record(entry_v2, 4, backend="cpu")
    assert len(man2) == 2
    # corruption degrades to empty-with-warning, never a crash
    path.write_text("{ not json !!!")
    man3 = WarmupManifest(str(path))
    assert len(man3) == 0 and man3.buckets_for("m", entry.symbol_sha) == []
    # valid JSON that is not a manifest object (foreign file) too
    path.write_text("[1, 2, 3]")
    man4 = WarmupManifest(str(path))
    assert len(man4) == 0
    # ... and a manifest-shaped doc with garbage entries
    path.write_text('{"schema": 1, "entries": ["x", 7]}')
    man5 = WarmupManifest(str(path))
    assert len(man5) == 0


def test_server_records_manifest_and_replays_it(tmp_path):
    symb, args = _make_model()
    manifest = str(tmp_path / "warmup.json")
    srv = ModelServer(max_batch=4, manifest_path=manifest)
    srv.add_model("m", symb, args, {}, {"data": (1, IN_DIM)})
    warmed = srv.warmup("m")
    assert [b for (_n, _v, b) in warmed] == [1, 2, 4]
    doc = json.loads(open(manifest).read())
    assert sorted(e["bucket"] for e in doc["entries"]) == [1, 2, 4]
    assert all(e["backend"] for e in doc["entries"])
    # a "restarted" server replays exactly that working set
    srv2 = ModelServer(max_batch=4, manifest_path=manifest)
    srv2.add_model("m", symb, args, {}, {"data": (1, IN_DIM)})
    replayed = srv2.warmup_from_manifest()
    assert [b for (_n, _v, b) in replayed] == [1, 2, 4]
    assert srv2.cache.stats()["misses"] == 3
    # live traffic through an unwarmed bucket records into the manifest
    # via the executor-cache miss hook (not only warmup)
    srv3 = ModelServer(max_batch=8, manifest_path=manifest)
    srv3.add_model("m", symb, args, {}, {"data": (1, IN_DIM)})
    srv3.start()
    try:
        srv3.infer("m", {"data": np.zeros((5, IN_DIM), np.float32)},
                   timeout_ms=60000.0)
    finally:
        srv3.stop(drain=False)
    man = WarmupManifest(manifest)
    entry = srv3.registry.get("m")
    assert 8 in man.buckets_for("m", entry.symbol_sha)
    stats = srv3.stats()
    assert stats["warmup_manifest"]["entries"] == len(man)
    assert "compile_cache" in stats


def test_manifest_off_ladder_buckets_skipped(tmp_path):
    symb, args = _make_model()
    manifest = str(tmp_path / "warmup.json")
    srv = ModelServer(max_batch=16, manifest_path=manifest)
    srv.add_model("m", symb, args, {}, {"data": (1, IN_DIM)})
    srv.warmup("m", buckets=[16])
    # a later config shrinks the ladder: recorded 16 no longer exists
    srv2 = ModelServer(max_batch=4, manifest_path=manifest)
    srv2.add_model("m", symb, args, {}, {"data": (1, IN_DIM)})
    assert srv2.warmup_from_manifest() == []
    assert srv2.cache.stats()["misses"] == 0


def test_watcher_warms_new_version_before_promoting(tmp_path, monkeypatch):
    from mxnet_tpu.checkpoint import CheckpointManager

    X = np.random.RandomState(0).rand(32, IN_DIM).astype(np.float32)
    y = (np.arange(32) % 2).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=8)
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=2, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(mx.init.Xavier())
    mgr = CheckpointManager(directory=str(tmp_path / "ckpts"),
                            async_save=False)
    mgr.save_module(mod, epoch=0, nbatch=1)

    manifest = str(tmp_path / "warmup.json")
    srv = ModelServer(max_batch=2, manifest_path=manifest)
    events = []
    real_warm = srv.warmup_version
    monkeypatch.setattr(
        srv, "warmup_version",
        lambda name, version, **kw: (events.append(("warm", version)),
                                     real_warm(name, version, **kw))[1])
    real_promote = srv.registry.set_default
    monkeypatch.setattr(
        srv.registry, "set_default",
        lambda name, version: (events.append(("promote", version)),
                               real_promote(name, version))[1])
    watcher = srv.watch_checkpoints(str(tmp_path / "ckpts"), "clf",
                                    start=False)
    step1 = watcher.poll_once()
    assert step1 is not None
    assert events == [("warm", step1), ("promote", step1)], \
        "a hot swap must warm the new version BEFORE promoting it"
    # no manifest history for this program yet -> full ladder warmed
    assert srv.cache.stats()["misses"] == 2
    # second commit of the same architecture: warms again (new version
    # = new executor keys) but the manifest stays deduped by symbol sha
    mgr.save_module(mod, epoch=0, nbatch=2)
    step2 = watcher.poll_once()
    assert step2 is not None and step2 > step1
    assert srv.registry.get("clf").version == step2
    assert srv.cache.stats()["misses"] == 4
    man = WarmupManifest(manifest)
    assert len(man) == 2, "same program, new version: no manifest growth"


# -- serving executor-cache eviction mirror ----------------------------------
def test_serving_cache_evictions_mirrored_to_registry():
    symb, args = _make_model()
    from mxnet_tpu.serving.registry import ModelVersion
    entry = ModelVersion("m", 1, symb, args, {}, {"data": (1, IN_DIM)})
    fam = telemetry.counter(
        "mxnet_serving_cache_evictions_total",
        "bound executors dropped by LRU capacity pressure; a "
        "rising rate means the (model, version, bucket) working "
        "set exceeds MXNET_SERVING_EXECUTOR_CACHE and steady-state "
        "traffic is recompiling")
    before = fam.labels().value
    cache = ExecutorCache(capacity=1)
    cache.get(entry, 1)
    cache.get(entry, 2)        # capacity 1: evicts the bucket-1 entry
    assert cache.stats()["evictions"] == 1
    assert fam.labels().value == before + 1, \
        "per-instance eviction count must mirror into the registry"


# -- telemetry: warm vs cold warmup -----------------------------------------
def test_warmup_seconds_histogram_warm_and_cold(tmp_path):
    compile_cache.configure(str(tmp_path / "cc"))
    symb, args = _make_model()
    srv = ModelServer(max_batch=2)
    srv.add_model("m", symb, args, {}, {"data": (1, IN_DIM)})
    srv.warmup("m")            # cold: populates the disk cache
    srv2 = ModelServer(max_batch=2)
    srv2.add_model("m", symb, args, {}, {"data": (1, IN_DIM)})
    srv2.warmup("m")           # warm: every bind a disk hit
    text = telemetry.prometheus_text()
    assert 'mxnet_serving_warmup_seconds_count{mode="cold"}' in text
    assert 'mxnet_serving_warmup_seconds_count{mode="warm"}' in text


# -- tier-1 guard: the PR 2 invariant survives the cache ---------------------
def test_steady_state_zero_recompiles_with_cache_enabled(tmp_path):
    """Regression fence: with the persistent cache ON, a served model's
    mxnet_xla_compiles_total stays FLAT after warmup — the cache
    changes where the first compile comes from, never whether
    steady-state traffic compiles."""
    compile_cache.configure(str(tmp_path / "cc"))
    symb, args = _make_model()
    srv = ModelServer(max_batch=8, batch_wait_ms=1.0,
                      default_timeout_ms=30000.0,
                      manifest_path=str(tmp_path / "warmup.json"))
    srv.add_model("m", symb, args, {}, {"data": (1, IN_DIM)})
    telemetry.enable()
    try:
        srv.start()
        srv.warmup("m")
        after_warmup = telemetry.scalar_totals().get(
            "mxnet_xla_compiles_total", 0)
        rng = np.random.RandomState(5)
        futs = []
        for _ in range(60):
            rows = int(rng.randint(1, 9))
            x = rng.rand(rows, IN_DIM).astype(np.float32)
            futs.append((srv.infer_async("m", {"data": x}), rows))
        for f, rows in futs:
            assert f.result()[0].shape == (rows, HID)
        assert telemetry.scalar_totals().get(
            "mxnet_xla_compiles_total", 0) == after_warmup, \
            "steady-state traffic recompiled with the cache enabled"
        assert srv.cache.stats()["misses"] == 4
    finally:
        telemetry.disable()
        srv.stop(drain=False)


# -- multi-process sharing ---------------------------------------------------
_CHILD = textwrap.dedent("""
    import sys, json
    from mxnet_tpu import compile_cache
    import jax, jax.numpy as jnp
    compile_cache.configure(sys.argv[1])
    f = jax.jit(lambda x: jnp.tanh(x @ x + 7.0))
    f(jnp.ones((48, 48), jnp.float32)).block_until_ready()
    print(json.dumps(compile_cache.stats()))
""")


def test_two_processes_share_one_cache_dir(tmp_path):
    """Two concurrent processes compiling the SAME program into one
    cache dir must both succeed (rename-commit races are benign), and
    a third process must then hit what they wrote."""
    d = str(tmp_path / "shared")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run():
        return subprocess.run([sys.executable, "-c", _CHILD, d],
                              capture_output=True, text=True, timeout=300,
                              env=env)

    results = [None, None]
    threads = [threading.Thread(
        target=lambda i=i: results.__setitem__(i, run())) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in results:
        assert r is not None and r.returncode == 0, \
            (r.stdout if r else "") + (r.stderr if r else "")
    # the dir holds committed entries, not torn temp files
    assert [f for f in os.listdir(d) if f.endswith("-cache")]
    third = run()
    assert third.returncode == 0, third.stderr
    stats = json.loads(third.stdout.strip().splitlines()[-1])
    assert stats["hits"] >= 1 and stats["misses"] == 0, \
        "a fresh process must warm-start from what the racers wrote"


# -- placement: outside > knob > fixed in-checkout default --------------------
_BIND_CHILD = textwrap.dedent("""
    import json, os
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache, nd, sym
    before = jax.config.jax_compilation_cache_dir
    data = sym.Variable("data")
    out = sym.softmax(sym.FullyConnected(data, num_hidden=4, name="fc"))
    args = {"fc_weight": nd.array(np.ones((4, 6), np.float32)),
            "fc_bias": nd.array(np.zeros((4,), np.float32))}
    pred = mx.Predictor.from_parts(out, args, {}, {"data": (1, 6)})
    pred.forward(data=np.zeros((1, 6), np.float32))
    pred.get_output(0).asnumpy()
    print(json.dumps({"before": before,
                      "after": jax.config.jax_compilation_cache_dir,
                      "placement": list(compile_cache.placement()),
                      "stats": compile_cache.stats()}))
""")


def _child_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "MXNET_COMPILE_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_externally_placed_cache_survives_the_first_bind(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: after the first bind jax still
    points at it, an entry landed there, stats() names it — and the
    MXNET knob, set to somewhere else, was ignored."""
    ext, other = str(tmp_path / "outside"), str(tmp_path / "knob")
    proc = subprocess.run(
        [sys.executable, "-c", _BIND_CHILD], capture_output=True,
        text=True, timeout=300,
        env=_child_env(JAX_COMPILATION_CACHE_DIR=ext,
                       MXNET_COMPILE_CACHE_DIR=other))
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["before"] == ext and doc["after"] == ext
    assert doc["placement"] == [ext, "external"]
    assert doc["stats"]["enabled"] and doc["stats"]["dir"] == ext
    assert doc["stats"]["entries"] >= 1
    assert [f for f in os.listdir(ext) if f.endswith("-cache")]
    assert not os.path.exists(other)


def test_default_placement_is_one_fixed_path_in_the_checkout(tmp_path):
    """Neither variable set: two processes started in different
    directories agree on <checkout>/.jax_cache — no temp name, pid or
    timestamp in it.  (placement() only: the suite must not fill the
    checkout.)"""
    src = ("from mxnet_tpu import compile_cache as c; "
           "print(c.placement()[0]); print(c.placement()[1])")
    procs = [subprocess.Popen([sys.executable, "-c", src], cwd=cwd,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=_child_env())
             for cwd in (str(tmp_path), os.getcwd())]
    seen = []
    for proc in procs:
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err[-2000:]
        seen.append(out.split())
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert seen[0] == seen[1] == [os.path.join(root, ".jax_cache"),
                                  "default"]
    assert compile_cache.DEFAULT_DIR == seen[0][0]
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_empty_knob_turns_the_cache_off(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", "")
    assert compile_cache.placement() == (None, "knob")
    assert compile_cache.ensure_initialized() is False
    assert compile_cache.stats()["dir"] is None


# -- bench plumbing ----------------------------------------------------------
@pytest.mark.slow
def test_bench_warmup_probe_emits_parseable_json(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=str(tmp_path / "cc"),
               MXNET_COMPILE_CACHE_MANIFEST=str(tmp_path / "warmup.json"))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench_serving.py"),
         "--warmup-probe"],
        capture_output=True, text=True, timeout=600, env=env, cwd=root)
    assert proc.returncode == 0, proc.stderr[-800:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["warmed"] == 5 and doc["warmup_s"] > 0
    assert doc["source"] == "ladder"
    assert doc["compile_cache"]["misses"] >= 5
