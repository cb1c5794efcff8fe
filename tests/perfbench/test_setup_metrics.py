"""The six readers PR 36 added beside the harness (CPU, quick, no chip):
``perfbench/setup_reduce.py`` and the metric files over it.

- each over a hand-made list of the program's spans: sums by hand, a
  trace nested in a trace and a compile nested in a set-up stage counted
  once, a span at the window's edge on the side it began on;
- ``None`` on an empty ``ctx``, on spans that hold none of theirs (the
  parent program) and on a ring that has evicted;
- the manifest lists the six at the end of ``per_layer`` under the five
  cells, and every manifest rule of every test file here holds over the
  tree as it now stands.
"""
import argparse
import importlib.util
import inspect
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PB = os.path.join(ROOT, "perfbench")
READERS = ("setup_trace_lower_s", "setup_backend_s", "setup_cache_misses",
           "setup_place_s", "setup_import_s", "jit_compiles_in_window")
CELLS = ["resnet50-train-b256", "opt1p3b-train-s2048",
         "ouro2p6b-train-s2048", "mellum2-12b-train-s8192",
         "joyai-flash-train-s8192"]


@pytest.fixture(scope="module")
def pb():
    sys.path.insert(0, PB)
    try:
        import loader
        import phase_reduce
        import run
        import trace_reduce
        import traffic
        yield argparse.Namespace(
            loader=loader, traffic=traffic, trace_reduce=trace_reduce,
            run=run, phase_reduce=phase_reduce, bench=loader.Bench(ROOT))
    finally:
        sys.path.remove(PB)


def _span(name, t0_s, dur_s, **tags):
    rec = {"name": name, "t0_ns": int(t0_s * 1e9), "dur_ms": dur_s * 1e3}
    if tags:
        rec["tags"] = tags
    return rec


def _ctx():
    """A window of two steps from t = 100 s to 100.4 s (and a block to
    100.5 s) after a set-up of: one stage ``trainer.place`` 10 s - 16 s
    holding an eager program (trace 11 - 11.5, lower 11.5 - 12, compile
    12 - 13, a hit) — the step's program 20 s - 30 s: its trace 20 - 23
    holding a nested trace 21 - 22, lower 23 - 24, compile 24 - 30, a
    miss, all inside ``trainer.step`` which is no set-up stage — a
    ``trainer.build`` of 0.25 s inside that step — and a compile that
    began 0.5 s before the window and ended inside it."""
    spans = [
        _span("trainer.place", 10.0, 6.0, param_bytes=1 << 20),
        _span("xla.trace", 11.0, 0.5, program="copy"),
        _span("xla.lower", 11.5, 0.5, program="copy"),
        _span("xla.compile", 12.0, 1.0, program="copy", cache="hit",
              load_s=0.9),
        _span("trainer.step", 19.0, 12.0),
        _span("trainer.build", 19.5, 0.25),
        _span("xla.trace", 21.0, 1.0, program="inner"),
        _span("xla.trace", 20.0, 3.0, program="step"),
        _span("xla.lower", 23.0, 1.0, program="step"),
        _span("xla.compile", 24.0, 6.0, program="step", cache="miss"),
        _span("xla.compile", 99.5, 1.0, program="late", cache="off"),
        # the window's own
        _span("trainer.step", 100.0, 0.2),
        _span("xla.trace", 100.25, 0.01, program="drift"),
        _span("xla.lower", 100.26, 0.01, program="drift"),
        _span("xla.compile", 100.27, 0.1, program="drift", cache="miss"),
        _span("trainer.step", 100.2, 0.2),
        # after the block returned: the harness's own, not the window's
        _span("xla.compile", 100.6, 1.0, program="reference",
              cache="miss"),
    ]
    return {"spans": [(100.0, 100.2), (100.2, 100.4),
                      (100.4, 100.5, "block")],
            "steps": 2, "program_spans": spans,
            "program_totals": {"mxnet_import_seconds": 2.5,
                               "mxnet_xla_compiles_total": 7}}


BY_HAND = {
    "setup_backend_s": 1.0 + 6.0 + 1.0,
    # 11 - 12 and 20 - 24: the nested trace inside the outer one, once
    "setup_trace_lower_s": 1.0 + 4.0,
    # place 6 s less the 2 s that compiled in it, and the build
    "setup_place_s": (6.0 - 2.0) + 0.25,
    "setup_cache_misses": 1.0,
    "setup_import_s": 2.5,
    "jit_compiles_in_window": 1.0,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_over_handmade_spans(pb, name):
    reader = pb.bench.metric_reader(name)
    assert reader.read(_ctx()) == pytest.approx(BY_HAND[name], abs=1e-6)
    assert isinstance(reader.read(_ctx()), float)


def test_the_four_set_up_seconds_share_none(pb):
    """place + trace_lower + backend is the length of everything the
    spans cover: wall time, whatever nests in whatever."""
    ctx = _ctx()
    total = sum(pb.bench.metric_reader(n).read(ctx) for n in (
        "setup_place_s", "setup_trace_lower_s", "setup_backend_s"))
    assert total == pytest.approx(6.0 + 0.25 + 10.0 + 1.0, abs=1e-6)


def test_a_span_at_the_edge_belongs_to_the_side_it_began_on(pb):
    ctx = _ctx()
    ctx["program_spans"] = [
        _span("xla.compile", 99.999999, 0.5, program="a", cache="miss"),
        _span("xla.compile", 100.0, 0.5, program="b", cache="miss"),
        _span("xla.compile", 100.5, 0.5, program="c", cache="miss"),
        _span("xla.compile", 100.500001, 0.5, program="d", cache="miss"),
    ]
    read = {n: pb.bench.metric_reader(n).read(ctx) for n in READERS}
    assert read["setup_backend_s"] == pytest.approx(0.5, abs=1e-6)
    assert read["setup_cache_misses"] == 1.0
    assert read["jit_compiles_in_window"] == 2.0      # b and c, not d
    assert read["setup_trace_lower_s"] is None
    assert read["setup_place_s"] is None


def test_a_clean_window_reads_zero_not_none(pb):
    ctx = _ctx()
    ctx["program_spans"] = [r for r in ctx["program_spans"]
                            if (r.get("tags") or {}).get("program")
                            != "drift"]
    assert pb.bench.metric_reader("jit_compiles_in_window").read(ctx) == 0.0
    ctx = _ctx()
    ctx["program_spans"] = [r for r in ctx["program_spans"]
                            if (r.get("tags") or {}).get("cache") != "miss"]
    assert pb.bench.metric_reader("setup_cache_misses").read(ctx) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_where_there_is_nothing_to_read(pb, name):
    reader = pb.bench.metric_reader(name)
    # no window
    assert reader.read({"program_spans": [], "program_totals": {}}) is None
    # the parent program: step spans only, no gauge
    ctx = dict(_ctx(), program_totals={"mxnet_xla_compiles_total": 7},
               program_spans=[_span("trainer.step", 19.0, 12.0),
                              _span("trainer.dispatch", 19.0, 11.9),
                              _span("trainer.step", 100.0, 0.2)])
    assert reader.read(ctx) is None
    if name == "setup_import_s":
        return      # a gauge: the ring's state is nothing to it
    # a ring that has forgotten: no partial sum
    assert reader.read(dict(_ctx(), program_spans_evicted=3)) is None
    # a span from before the clock was carried over
    ctx = _ctx()
    ctx["program_spans"] = [{"name": "xla.compile", "dur_ms": 5.0}]
    assert reader.read(ctx) is None


def test_readers_ask_the_program_when_no_spans_are_handed_in(pb):
    """Over the live ring: a compile before and a compile inside a
    window made by hand, on the spans' own clock."""
    import time
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import tracing
    tracing.reset()
    telemetry.enable()
    try:
        x = jnp.ones((8,), jnp.float32)
        jax.jit(lambda x: x * 11.0 + 1.0)(x).block_until_ready()
        t0 = time.perf_counter()
        jax.jit(lambda x: x * 13.0 - 1.0)(x).block_until_ready()
        t1 = time.perf_counter()
        ctx = {"spans": [(t0, t1)], "steps": 1}
        read = {n: pb.bench.metric_reader(n).read(dict(ctx))
                for n in READERS}
        assert read["jit_compiles_in_window"] == 1.0
        assert read["setup_backend_s"] > 0
        assert read["setup_trace_lower_s"] > 0
        assert read["setup_cache_misses"] == 0.0        # the cache is off
        assert read["setup_place_s"] is None
        assert read["setup_import_s"] > 0
        # a ring too small for the record: nothing, not the newest part
        tracing.enable(ring=16)
        for _ in range(20):
            with tracing.span("filler"):
                pass
        assert tracing.evicted() > 0
        for name in READERS[:4] + READERS[5:]:
            assert pb.bench.metric_reader(name).read(dict(ctx)) is None, name
    finally:
        tracing.enable(ring=4096)
        tracing.disable()
        telemetry.disable()
        telemetry.reset()
        tracing.reset()


def test_a_ring_drained_to_a_shard_is_no_whole_record(pb, tmp_path):
    """An export takes the process root's spans out of the ring with
    ``evicted()`` still 0: the readers give None all the same."""
    import time
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import tracing
    tracing.reset()
    telemetry.enable()
    try:
        x = jnp.ones((8,), jnp.float32)
        jax.jit(lambda x: x * 17.0 + 2.0)(x).block_until_ready()
        t0 = time.perf_counter()
        ctx = {"spans": [(t0, t0 + 1.0)], "steps": 1}
        reader = pb.bench.metric_reader("setup_backend_s")
        assert reader.read(dict(ctx)) > 0
        tracing.export_jsonl(str(tmp_path / "shard.jsonl"))
        said = tracing.stats()      # written, or sampled out: gone
        assert said["exported"] + said["dropped"] >= 3
        assert tracing.evicted() == 0 and not tracing.snapshot()
        for name in READERS[:4] + READERS[5:]:
            assert pb.bench.metric_reader(name).read(dict(ctx)) is None, name
    finally:
        tracing.disable()
        telemetry.disable()
        telemetry.reset()
        tracing.reset()


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------
# a rule on ``pb.bench`` (any checkout's), which an addition keeps
# (tests/perfbench/test_manifest_addition.py runs it over one)
@pytest.mark.parametrize("name", READERS)
def test_the_manifest_lists_the_setup_readers(pb, name):
    """Each of the six lists AT LEAST the five cells PR 36 gave it, in
    the manifest's order, and in every cell they stand in their order
    among that cell's readers, after the readers that were there."""
    m = pb.bench.manifest
    specs = {s["name"]: s for s in m["per_layer"]}
    spec = specs[name]
    assert spec["workloads"][:5] == CELLS
    assert spec["better"] == "lower"
    assert spec["moves"] == ("train_step_ms"
                             if name == "jit_compiles_in_window"
                             else "setup_s")
    assert spec["source"] == ("program_counter" if name == "setup_import_s"
                              else "program_span")
    assert set(spec) == {"name", "unit", "better", "source", "layer",
                         "moves", "workloads"}
    for cell in spec["workloads"]:
        names = [s["name"] for s in pb.bench.cell(cell).per_layer_metrics()]
        rest = iter(names)
        assert all(r in rest for r in READERS), cell
        assert names.index("compiles_in_window") < names.index(READERS[0])
    assert os.path.isfile(pb.bench.path("metrics", name + ".py"))


def test_every_rule_of_the_addition_test_holds_over_the_tree_as_it_stands(
        pb):
    """What ``test_manifest_addition.py`` runs over a copy with one more
    cell, run here over this checkout itself — with its own way of
    finding the rules and unrolling their cases."""
    spec = importlib.util.spec_from_file_location(
        "standing_addition", os.path.join(HERE, "test_manifest_addition.py"))
    addition = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(addition)
    fixtures = {"bench": pb.bench, "pb": pb}
    ran = []
    for fname in sorted(os.listdir(HERE)):
        if not (fname.startswith("test_") and fname.endswith(".py")) \
                or fname in ("test_manifest_addition.py",
                             os.path.basename(__file__)):
            continue
        mod = addition._load(os.path.join(HERE, fname))
        for name, fn in sorted(vars(mod).items()):
            if not (name.startswith("test_") and inspect.isfunction(fn)
                    and ("manifest" in name or "every_cell" in name)):
                continue
            for kwargs in addition._cases(fn, fixtures):
                fn(**kwargs)
                ran.append("%s::%s" % (fname, name))
    assert len(ran) >= 20 and any(
        "test_manifest_names_and_units" in c for c in ran), ran
