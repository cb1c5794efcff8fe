"""graftkern — static Pallas kernel verification (PR 19).

Proof obligations:

1. each ``kern-*`` rule catches its seeded bad-kernel fixture (an
   overlapping index map, an unmasked padded tail, an over-budget
   block, a closure-constant lr, a cross-block read on the sharded
   dim) with ``jax.jit`` fully poisoned — the judging path is pure
   data;
2. the in-tree catalog gate (tier-1): every kernel in
   ``ops/pallas_kernels.py`` analyzes clean, ALSO with ``jax.jit``
   poisoned — building the plans and evaluating the index maps never
   traces or compiles anything;
3. the ``kern-shard-safety`` verdict is load-bearing:
   ``sweep_shard_verdict()`` proves the sweep family block-local,
   ``mesh_sweep_safe`` consumes the verdict (no hardcoded flag), and
   the multi-chip dp8 fused sweep is BITWISE the ``tree_map`` oracle,
   with graftir finding the ``pallas_call`` inside the ``shard_map``
   body (``ir-pallas-presence``'s blind spot closed);
4. the four ``kern-*`` rule ids ride the SARIF reporter and the
   stale-suppression hygiene like every other rule, and ``--changed``
   maps kernel-plan edits to a kern re-run.
"""
import json
import os
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mxnet_tpu import analysis, parallel
from mxnet_tpu.analysis import rule_ids, sarif_report
from mxnet_tpu.analysis.checkers.kern_rules import (
    KERN_RULES, SCHEDULE_HYPERPARAMS, coverage_problems,
    run_kern_checkers, shard_safety, vmem_bytes)
from mxnet_tpu.analysis.kern import (kernel_reports, sweep_reports,
                                     sweep_shard_verdict)
from mxnet_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(ROOT, "tests", "fixtures")


def _poison_jit(monkeypatch):
    def boom(*_a, **_k):
        raise AssertionError(
            "jax.jit reached from the graftkern static path")
    monkeypatch.setattr(jax, "jit", boom)


def _fixture_reports():
    doc = json.load(open(os.path.join(FIX, "analysis",
                                      "kern_bad_kernels.json")))
    return doc["reports"]


# ---------------------------------------------------------------------------
# 1. seeded bad kernels — pure data, jax.jit fully poisoned
# ---------------------------------------------------------------------------

def test_fixture_kernels_with_jit_poisoned(monkeypatch):
    """ACCEPTANCE: every kern-* rule catches its seeded report without
    compiling anything — the checkers never leave pure data."""
    _poison_jit(monkeypatch)
    seen = set()
    for entry in _fixture_reports():
        findings = run_kern_checkers([entry["report"]])
        rules = {f.rule for f in findings}
        assert entry["expect_rule"] in rules, \
            (entry["report"]["name"], rules)
        for f in findings:
            assert f.path == "mxnet_tpu/ops/pallas_kernels.py"
            assert f.symbol == entry["report"]["name"]
        seen.add(entry["expect_rule"])
    assert seen == set(KERN_RULES)


def test_fixture_failure_modes_are_specific(monkeypatch):
    """The seeded defects are the advertised ones: the overlap fixture
    reports BOTH the race and the gap; the cross-read fixture's shard
    verdict is candidate-but-unsafe with the offending operand named."""
    _poison_jit(monkeypatch)
    by_name = {e["report"]["name"]: e["report"]
               for e in _fixture_reports()}
    overlap = by_name["_seed_overlap_kernel"]
    out = next(o for o in overlap["operands"] if o["role"] == "out")
    problems = coverage_problems(out, overlap["grid"])
    assert any("never written" in p for p in problems)
    assert any("race" in p for p in problems)
    cross = by_name["_seed_cross_read_kernel"]
    verdict = shard_safety(cross)
    assert verdict["candidate"] and not verdict["safe"]
    assert verdict["grid_dim"] is None
    assert any("g:" in r for r in verdict["reasons"])
    fat = by_name["_seed_fat_block_kernel"]
    assert vmem_bytes(fat) == 2 * 4096 * 4096 * 4


# ---------------------------------------------------------------------------
# 2. the in-tree catalog gate (tier-1)
# ---------------------------------------------------------------------------

def test_in_tree_catalog_clean_with_jit_poisoned(monkeypatch):
    """ACCEPTANCE: the whole kernel catalog analyzes with ZERO findings
    and jax.jit poisoned — abstract interpretation of the shared plan
    objects, nothing traces, nothing compiles."""
    _poison_jit(monkeypatch)
    reports = kernel_reports()
    names = {r["name"] for r in reports}
    assert {"_sgd_kernel", "_sgd_mom_kernel", "_adam_kernel",
            "_flash_fwd_kernel", "_flash_bwd_dq_kernel",
            "_flash_bwd_dkv_kernel", "_scale_bias_relu_kernel",
            "_layernorm_fwd_kernel", "_layernorm_bwd_kernel",
            "_softmax_fwd_kernel", "_softmax_bias_fwd_kernel",
            "_softmax_bwd_kernel"} <= names
    findings = run_kern_checkers(reports)
    assert findings == [], [(f.rule, f.symbol, f.message)
                            for f in findings]
    for r in reports:
        assert r["vmem"]["bytes_per_instance"] <= r["vmem"]["budget"], \
            r["name"]
        assert r["tail"]["masked"], r["name"]


def test_catalog_respects_vmem_budget_knob(monkeypatch):
    """A tightened MXNET_KERN_VMEM_BYTES turns real kernels into
    kern-vmem-budget findings — the budget is the knob, not a constant
    baked into the checker."""
    _poison_jit(monkeypatch)
    reports = sweep_reports()
    findings = run_kern_checkers(reports, ctx={"vmem_budget": 1024})
    assert {f.rule for f in findings} == {"kern-vmem-budget"}
    assert len(findings) == len(reports)


# ---------------------------------------------------------------------------
# 3. the verdict is load-bearing
# ---------------------------------------------------------------------------

def test_sweep_shard_verdict_proves_block_local():
    verdict = sweep_shard_verdict()
    assert verdict["safe"] is True
    assert set(verdict["kernels"]) == {"_sgd_kernel", "_sgd_mom_kernel",
                                       "_adam_kernel"}
    for name, v in verdict["kernels"].items():
        assert v["candidate"] and v["safe"], name
        assert v["grid_dim"] == 0, name


def test_mesh_sweep_safe_derives_from_verdict(monkeypatch):
    """mesh_sweep_safe is the verdict, not a hardcoded flag: on a
    native (non-interpret) backend multi-chip is allowed iff graftkern
    proves the sweep kernels block-local."""
    import mxnet_tpu.analysis.kern as kern_mod
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "_SWEEP_SHARD_VERDICT", None)
    assert pk.mesh_sweep_safe(1) is True          # single chip: no wrap
    assert pk.mesh_sweep_safe(8) is True          # proof present
    monkeypatch.setattr(pk, "_SWEEP_SHARD_VERDICT", None)
    monkeypatch.setattr(kern_mod, "sweep_shard_verdict",
                        lambda: {"safe": False, "kernels": {}})
    assert pk.mesh_sweep_safe(8) is False         # proof absent
    assert pk.mesh_sweep_safe(1) is True          # single chip still ok
    monkeypatch.setattr(pk, "_SWEEP_SHARD_VERDICT", None)
    monkeypatch.setattr(kern_mod, "sweep_shard_verdict",
                        lambda: (_ for _ in ()).throw(RuntimeError()))
    assert pk.mesh_sweep_safe(8) is False         # verdict errors: safe


def test_multichip_fused_sweep_bitwise_vs_treemap(monkeypatch):
    """ACCEPTANCE (dp8): the shard_map-wrapped fused sweep over
    1/mesh-sharded flat buckets is BITWISE the per-array tree_map
    oracle — params and slots — for SGD+momentum and Adam."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.optimizer import PureAdam, PureSGD
    mesh = parallel.make_mesh(dp=8)
    ns = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    rng = np.random.RandomState(7)

    def buckets(sizes):
        return {"b%d" % i: jax.device_put(
                    jnp.asarray(rng.randn(n).astype(np.float32)), ns)
                for i, n in enumerate(sizes)}

    sizes = [8 * 1024, 4096]
    for opt in (PureSGD(0.1, momentum=0.9, wd=0.01,
                        clip_gradient=0.05),
                PureAdam(1e-3, wd=0.01)):
        params = buckets(sizes)
        grads = [buckets(sizes) for _ in range(3)]
        shardings = {k: ns for k in params}

        def drive(knob, mesh_arg):
            monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", knob)
            step = jax.jit(lambda p, g, s: opt.apply(
                p, g, s, flat=True, mesh=mesh_arg))
            p, s = dict(params), opt.init(params, shardings)
            for g in grads:
                p, s = step(p, g, s)
            return p, s

        pf, sf = drive("1", mesh)     # fused, shard_map-wrapped
        pu, su = drive("0", None)     # tree_map oracle
        for k in params:
            assert np.array_equal(np.asarray(pf[k]),
                                  np.asarray(pu[k])), (type(opt), k)
        for a, b in zip(jax.tree_util.tree_leaves(sf),
                        jax.tree_util.tree_leaves(su)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_sharded_sweep_requires_mesh_divisible_buckets():
    """The bucket plan pads every bucket to a multiple of mesh.size
    (parallel/collectives.py); the sharded sweep enforces that
    contract instead of silently re-padding unevenly."""
    mesh = parallel.make_mesh(dp=8)
    w = jnp.ones(8 * 100 + 3, jnp.float32)
    with pytest.raises(ValueError, match="mesh"):
        pk.fused_sgd_momentum(w, w, None, lr=0.1, momentum=0.0,
                              mesh=mesh)


def test_ir_finds_pallas_inside_shard_map(monkeypatch):
    """Satellite: graftir's fact walk descends shard_map/pjit
    sub-jaxprs, so ir-pallas-presence sees the kernels of the
    multi-chip fused step (trace-only; compile poisoned)."""
    from jax._src.interpreters import pxla
    from mxnet_tpu.analysis.ir.trace import collect_facts
    mesh = parallel.make_mesh(dp=8)
    w = jnp.ones(8 * 1024, jnp.float32)

    def step(w, g):
        nw, _ = pk.fused_sgd_momentum(w, g, None, lr=0.1, momentum=0.0,
                                      mesh=mesh)
        return nw

    traced = jax.jit(step).trace(w, w)

    def boom(*_a, **_k):
        raise AssertionError("XLA compile reached from abstract path")

    monkeypatch.setattr(pxla.MeshComputation, "compile", boom)
    facts = collect_facts(traced.jaxpr)
    assert "_sgd_kernel" in facts["pallas"]


# ---------------------------------------------------------------------------
# 4. reporter / hygiene / CLI plumbing
# ---------------------------------------------------------------------------

def test_sarif_coverage_of_kern_rules():
    findings = run_kern_checkers([e["report"]
                                  for e in _fixture_reports()])
    sarif = json.loads(sarif_report(findings))
    ids = {r["id"] for r in sarif["runs"][0]["tool"]["driver"]["rules"]}
    assert ids == set(KERN_RULES)
    for res in sarif["runs"][0]["results"]:
        assert res["partialFingerprints"]["graftlintFingerprint/v1"]
        assert res["locations"][0]["physicalLocation"][
            "artifactLocation"]["uri"] == "mxnet_tpu/ops/pallas_kernels.py"
    assert set(rule_ids()) >= ids


def test_stale_suppression_handles_kern_rules(tmp_path):
    (tmp_path / "m.py").write_text(textwrap.dedent("""
        def f(x):
            return x  # graftlint: disable=kern-shard-safety
    """))
    findings = analysis.run([str(tmp_path)], root=str(tmp_path))
    stale = [f for f in findings if f.rule == "stale-suppression"]
    assert len(stale) == 1 and "kern-shard-safety" in stale[0].message


def test_changed_maps_kernel_edits_to_kern_run():
    """Satellite: the --changed fast path re-runs kern exactly when the
    kernel plans, the analysis engine, or the knob registry changed."""
    from mxnet_tpu.analysis.cli import _kern_relevant
    assert _kern_relevant(["mxnet_tpu/ops/pallas_kernels.py"])
    assert _kern_relevant(["mxnet_tpu/config.py"])
    assert _kern_relevant(["mxnet_tpu/analysis/kern/catalog.py"])
    assert _kern_relevant(["mxnet_tpu/analysis/checkers/kern_rules.py"])
    assert not _kern_relevant(["docs/faq/perf.md",
                               "mxnet_tpu/parallel/trainer.py"])


def test_schedule_hyperparams_vocabulary():
    """The retrace vocabulary matches the sweep kernels' scalar-prefetch
    names (exact-name matching: structural constants like use_clip,
    eps, scale, causal must stay clean)."""
    for r in sweep_reports():
        assert r["hyper"]["transport"] == "scalar_prefetch"
        for pc in r["python_constants"]:
            assert pc["name"] not in SCHEDULE_HYPERPARAMS, r["name"]
    assert "lr" in SCHEDULE_HYPERPARAMS
    assert "use_clip" not in SCHEDULE_HYPERPARAMS
    assert "eps" not in SCHEDULE_HYPERPARAMS


def test_grouped_product_is_in_the_catalog_at_a_representative_table(
        monkeypatch):
    """The grouped product's index maps read a prefetched table (row
    tile -> group), so its three instantiations are analysed at one
    table — an empty group and unused tiles in it — with jit poisoned:
    forward and the rows' gradient write every output tile once a
    contraction step, the weights' gradient stays on a group's block
    over that group's run of tiles."""
    from mxnet_tpu.analysis.kern import grouped_matmul_reports
    _poison_jit(monkeypatch)
    reports = grouped_matmul_reports()
    assert [r["name"] for r in reports] == [
        "_grouped_matmul_kernel", "_grouped_matmul_kernel",
        "_grouped_matmul_dw_kernel"]
    assert run_kern_checkers(reports) == []
    fwd, _dx, dw = reports
    x, w, y = fwd["operands"][1:]
    # tiles past the 7 used ones hold at the last used tile's blocks
    assert x["index"][7 * 3 - 1] == x["index"][-1] == [6, 2]
    assert w["index"][-1] == [3, 0, 2]
    assert y["index"][-1] == [13, 0]       # 3840 / 384 + 4 groups: 14 tiles
    out = dw["operands"][-1]
    assert out["revisit"] == "runs"
    assert [i[0] for i in out["index"][:14]] \
        == [0, 0, 0, 1, 2, 2] + [3] * 8
    for r in reports:
        assert r["vmem"]["bytes_per_instance"] <= r["vmem"]["budget"]


def test_row_movers_are_in_the_catalog_at_a_representative_table(
        monkeypatch):
    """The expert layer's movers — the buffer-side one plain and with
    its factor and row dots, the token-side one, the pass that makes
    rows fetchable — analysed at one table with jit poisoned: the source
    a kernel fetches from by row DMA is an un-blocked HBM operand, every
    operand blocked by buffer tile holds at the last used tile, and the
    landing buffers keep an instance under the VMEM budget."""
    from mxnet_tpu.analysis.kern import kernel_reports, moe_mover_reports
    _poison_jit(monkeypatch)
    reports = moe_mover_reports()
    assert [r["name"] for r in reports] == [
        "_moe_rows_kernel", "_moe_rows_kernel", "_moe_slots_kernel",
        "_moe_words_kernel"]
    assert run_kern_checkers(reports) == []
    assert {r["name"] for r in reports} <= {
        r["name"] for r in kernel_reports()}
    rows, rows_bwd, slots, words = reports
    for r, source in ((rows, "x_words"), (rows_bwd, "g_words"),
                      (slots, "y_words")):
        (hbm,) = [o for o in r["operands"] if o["name"] == source]
        assert hbm["block"] is None and hbm["index"] is None
        assert hbm["dtype"] == "uint32" and hbm["shape"][1:] == [1, 1152]
    # 8192 / 384 + 4 groups: 26 tiles, 7 used; past them the blocked
    # operands stay on tile 6
    assert [o["name"] for o in words["operands"][1:]] \
        == ["g_gate", "g_up", "g_words"]
    for op in rows_bwd["operands"][2:] + words["operands"][1:]:
        assert [i[0] for i in op["index"]] == list(range(7)) + [6] * 19
    assert [o["revisit"] for o in rows_bwd["operands"][-2:]] \
        == ["used", "used"]
    assert [i[0] for i in slots["operands"][-1]["index"]] == list(range(8))
    assert slots["scratch"] == [{"shape": [8 * 128, 1, 1152],
                                 "dtype": "uint32"}]
    for r in reports:
        assert r["vmem"]["bytes_per_instance"] <= r["vmem"]["budget"]


def test_a_used_prefix_is_written_once_and_in_order():
    """``revisit: used`` admits a prefix of the blocks written once each
    with the grid held at the last of them, and refuses a gap, a block
    written twice before the last, and a grid that comes back."""
    op = {"name": "rows", "role": "out", "dtype": "float32",
          "block": [8, 128], "shape": [40, 128], "revisit": "used",
          "index": [[0, 0], [1, 0], [2, 0], [2, 0], [2, 0]]}
    assert coverage_problems(op, [5]) == []
    assert coverage_problems(
        dict(op, index=[[i, 0] for i in range(5)]), [5]) == []
    gap = dict(op, index=[[0, 0], [2, 0], [2, 0], [2, 0], [2, 0]])
    assert any("prefix" in p for p in coverage_problems(gap, [5]))
    twice = dict(op, index=[[0, 0], [0, 0], [1, 0], [2, 0], [2, 0]])
    assert any("written 2 times" in p for p in coverage_problems(twice, [5]))
    back = dict(op, index=[[0, 0], [1, 0], [0, 0], [1, 0], [1, 0]])
    assert any("prefix" in p for p in coverage_problems(back, [5]))


def test_a_run_of_revisits_may_not_come_back_to_a_block():
    """``revisit: runs`` admits runs of any length and refuses a block
    the grid leaves and returns to (its accumulation would be written
    back in between)."""
    op = {"name": "dw", "role": "out", "dtype": "float32",
          "block": [None, 8, 128], "shape": [3, 8, 128], "revisit": "runs",
          "index": [[0, 0, 0], [0, 0, 0], [1, 0, 0], [2, 0, 0], [2, 0, 0]]}
    assert coverage_problems(op, [5]) == []
    back = dict(op, index=[[0, 0, 0], [1, 0, 0], [0, 0, 0], [2, 0, 0],
                           [2, 0, 0]])
    assert any("revisited" in p for p in coverage_problems(back, [5]))
    gap = dict(op, index=[[0, 0, 0]] * 3 + [[2, 0, 0]] * 2)
    assert any("never written" in p for p in coverage_problems(gap, [5]))


def test_the_head_kernels_are_in_the_catalog_with_a_ragged_vocabulary(
        monkeypatch):
    """The fused head's pair at ZAYA1's shape, with jit poisoned: every
    row block's statistics and rows' gradient written once over its run
    of vocabulary blocks, ``d`` once a grid step, the last vocabulary
    block ragged (32784 columns in blocks of 512) under a masking
    contract, an instance under the VMEM budget."""
    from mxnet_tpu.analysis.kern import head_ce_reports, kernel_reports
    _poison_jit(monkeypatch)
    reports = head_ce_reports()
    assert [r["name"] for r in reports] == ["_head_ce_fwd_kernel",
                                            "_head_ce_bwd_kernel"]
    assert {r["name"] for r in kernel_reports()} >= {
        "_head_ce_fwd_kernel", "_head_ce_bwd_kernel"}
    assert run_kern_checkers(reports) == []
    for r in reports:
        assert r["grid"][1] == -(-32784 // 512)
        assert r["tail"]["padded_elems"] > r["tail"]["logical_elems"]
        assert r["vmem"]["bytes_per_instance"] <= r["vmem"]["budget"]
    d = reports[1]["operands"][-1]
    assert d["name"] == "d" and d["block"] == [256, 512]
    assert d["index"][:2] == [[0, 0], [0, 1]]


@pytest.mark.parametrize("mask", ["causal", "window", "block_diffusion"])
@pytest.mark.parametrize("group", [4, 8])
def test_grouped_flash_plans_are_in_the_catalog(group, mask, monkeypatch):
    """The flash kernels with ``group`` query heads a key/value head,
    with jit poisoned: clean under every mask, dK/dV's blocks each
    revisited over the group's visits (the coverage verdict's uniform
    revisit count) and declared the sum over the group's query heads.
    A plan that drops the group's last member — its dK/dV still writes
    every block equally often — or that reads a head of another group
    is refused."""
    from mxnet_tpu.analysis.kern import flash_group_reports
    _poison_jit(monkeypatch)
    which = ["causal", "window", "block_diffusion"].index(mask)
    reports = flash_group_reports()[(3 * [4, 8].index(group) + which) * 3:][:3]
    assert [r["name"] for r in reports] == [
        "_flash_fwd_kernel", "_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"]
    assert run_kern_checkers(reports) == []
    fwd, _dq, dkv = reports
    ops = {o["name"]: o for o in dkv["operands"]}
    nq = 4
    assert dkv["grid"] == [2, 4, group * nq]
    assert ops["k"]["shape"] == ops["dk"]["shape"] == [2, 512, 64]
    assert ops["q"]["shape"] == [2 * group, 512, 64]
    assert ops["dk"]["sums"] == {"of": "q", "heads": group}
    assert {tuple(i) for i in ops["dk"]["index"][:group * nq]} == {(0, 0, 0)}
    assert [o["shape"][0] for o in fwd["operands"]] == [2 * group, 2, 2,
                                                        2 * group, 2 * group]

    def cut(report, keep):
        """The report as a plan that visits only the grid points where
        ``keep(point)`` holds along a shortened minor axis."""
        grid = report["grid"]
        points = [p for p in np.ndindex(*grid)]
        kept = [n for n, p in enumerate(points) if keep(p)]
        out = dict(report, grid=grid[:2] + [grid[2] - nq])
        out["operands"] = [dict(o, index=[o["index"][n] for n in kept])
                           if o.get("index") else o
                           for o in report["operands"]]
        return out

    dropped = cut(dkv, lambda p: p[2] < (group - 1) * nq)
    out = next(o for o in dropped["operands"] if o["name"] == "dk")
    assert coverage_problems(out, dropped["grid"]) == []
    findings = run_kern_checkers([dropped])
    assert {f.rule for f in findings} == {"kern-grid-coverage"}
    assert any("never read head" in f.message for f in findings)
    stray = json.loads(json.dumps(dkv))
    for o in stray["operands"]:
        if o["name"] in ("q", "do", "lse", "delta"):
            o["index"] = [[(i[0] + group) % (2 * group)] + i[1:]
                          for i in o["index"]]
    assert any("another group" in f.message
               for f in run_kern_checkers([stray]))
