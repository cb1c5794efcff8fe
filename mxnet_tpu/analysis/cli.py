"""graftlint CLI — shared by ``python -m mxnet_tpu.analysis`` and
``tools/lint.py``.

Exit status: 0 when every finding is baselined (or none), 1 when new
findings exist, 2 on usage errors.  ``--update-baseline`` rewrites the
committed baseline from the current run and exits 0 — the triage
workflow is: run, fix the true positives, suppress or baseline the
deliberate remainder, ``--update-baseline``, commit.

Incremental runs: the CLI keeps a content-hash cache at
``.graftlint-cache.json`` (``--no-cache`` to disable, ``--cache`` to
relocate), so a warm re-lint only re-analyzes edited files.
``--changed`` derives the path set from git (worktree changes by
default, ``--changed REF`` to diff against a ref) — the pre-push
habit: ``tools/lint.py --changed``.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

from . import baseline as baseline_mod
from .core import C_API_BASENAMES, repo_root, rule_ids, run
from .reporters import human_report, json_report, sarif_report

__all__ = ["main"]


def _changed_paths(root, ref):
    """Lintable files git reports as changed: worktree+index vs HEAD
    (plus untracked) when ``ref`` is None, else ``git diff REF``."""
    def git(*args):
        out = subprocess.run(["git", "-C", root] + list(args),
                             capture_output=True, text=True)
        if out.returncode != 0:
            raise RuntimeError(out.stderr.strip()
                               or "git %s failed" % (args,))
        return [l for l in out.stdout.splitlines() if l.strip()]

    if ref is None:
        names = set(git("diff", "--name-only", "HEAD", "--"))
        names.update(git("ls-files", "--others", "--exclude-standard"))
    else:
        names = set(git("diff", "--name-only", ref, "--"))
    picked = []
    analysis_dir = os.path.join(root, "mxnet_tpu", "analysis")

    for rel in sorted(names):
        rel_n = rel.replace(os.sep, "/")
        # analysis fixtures (plan-spec corpora, checker inputs) under
        # tests/fixtures/ feed the checker tests' lint paths: a
        # fixture-only edit re-lints the analysis package instead of
        # being silently dropped as "no changed lintable files"
        if rel_n.startswith("tests/fixtures/"):
            if os.path.isdir(analysis_dir) \
                    and analysis_dir not in picked:
                picked.append(analysis_dir)
            continue
        if not (rel.endswith(".py")
                or os.path.basename(rel) in C_API_BASENAMES):
            continue
        # graftlint's scope is the package: its checkers (and the
        # suppression scanner, which reads raw text) are calibrated
        # for mxnet_tpu sources, not for test files full of fixture
        # snippets embedded in strings
        if not rel_n.startswith("mxnet_tpu/"):
            continue
        full = os.path.join(root, rel)
        if os.path.exists(full):        # deletions need no lint
            picked.append(full)
    return picked


def _bad_rules(rules):
    """True (after printing the usage error) when --rule names an
    unregistered id — shared by the --plan/--ir/--all modes."""
    unknown = set(rules or ()) - set(rule_ids())
    if unknown:
        print("graftlint: unknown rule ids: %s" % sorted(unknown),
              file=sys.stderr)
    return bool(unknown)


def _load_plan(configs=None):
    """Analyze the plan catalog with the configured knobs applied;
    ``configs`` reuses an already-built live catalog (``--all``)."""
    from mxnet_tpu import config as _config

    from .plan.configs import catalog_reports
    budget = int(_config.get("MXNET_PLAN_HBM_BYTES") or 0) or None
    fill_min = float(_config.get("MXNET_PLAN_BUCKET_FILL_MIN"))
    reports, verify_problems = catalog_reports(fill_min=fill_min,
                                               configs=configs)
    for r in reports:
        if r.get("hbm_budget") is None:
            r["hbm_budget"] = budget
    return reports, verify_problems


def _plan(args):
    """``--plan``: run graftplan over the in-tree configuration
    catalog (analysis/plan/configs.py) — like ``--audit-suppressions``
    this imports and instantiates the package (jax required; trainers
    are built, never stepped — nothing XLA-compiles), then gates the
    plan findings through the same baseline as the static rules and
    verifies the closed loop: predicted optimizer-state and collective
    bytes must equal the live objects' measurements exactly."""
    import json

    from .checkers.plan_rules import run_plan_checkers

    plan_rules = {"spmd-divisibility", "collective-mismatch",
                  "oom-risk", "bucket-plan-waste"}
    if _bad_rules(args.rules):
        return 2
    reports, verify_problems = _load_plan()
    findings = run_plan_checkers(reports)
    if args.rules:
        findings = [f for f in findings if f.rule in set(args.rules)]
    baseline_path = args.baseline or baseline_mod.default_path(repo_root())
    if args.update_baseline:
        # same restricted-merge semantics as the static path: a --plan
        # update re-derives only the plan rules' findings (narrowed
        # further by --rule), so every other entry — and any plan entry
        # outside the --rule scope — is preserved, with audit
        # annotations carried over for unchanged fingerprints
        return _restricted_update(findings, baseline_path, plan_rules,
                                  narrowed=args.rules)
    known = {} if args.no_baseline else baseline_mod.load(baseline_path)
    new, old = baseline_mod.filter_new(findings, known)
    if args.sarif:
        doc = json.loads(sarif_report(new, old))
        doc["runs"][0]["properties"] = {
            "graftplan": {"configs": [r["name"] for r in reports],
                          "verify_problems": verify_problems}}
        print(json.dumps(doc, indent=1))
    elif args.json:
        doc = json.loads(json_report(new, old))
        doc["plan"] = {"reports": reports,
                       "verify_problems": verify_problems}
        print(json.dumps(doc, indent=1))
    else:
        for r in reports:
            mem = r.get("memory")
            comm = r.get("comm")
            bits = []
            if mem:
                bits.append("per-chip %d B (params %d, opt %d, "
                            "staging %d, act %s)"
                            % (mem["total"], mem["params"],
                               mem["opt_state"], mem["staging"],
                               mem["activations"]))
            if comm:
                bits.append("%d wire B/step" % comm["total_bytes"])
            if r.get("ladder"):
                fills = [x["fill"] for x in r["ladder"]["rungs"]]
                bits.append("ladder fill %s" % fills)
            print("plan %-32s %s" % (r["name"], "; ".join(bits)))
        for p in verify_problems:
            print("PREDICTION MISMATCH: %s" % p)
        print(human_report(new, old, show_baselined=args.show_baselined))
        agreed = len(reports) - len(verify_problems)
        print("graftplan: %d configuration%s analyzed, predictions "
              "match measurements on %d"
              % (len(reports), "s" if len(reports) != 1 else "",
                 agreed))
    return 1 if (new or verify_problems) else 0


def _ir_cost_line(report):
    cost = report.get("cost") or {}
    return ("ir %-36s %d eqns, %d flops, %d traffic B%s"
            % (report["name"], cost.get("eqns", 0),
               cost.get("flops", 0), cost.get("bytes", 0),
               " (est)" if cost.get("estimated") else ""))


def _load_ir(live_configs=None):
    """Trace the catalog (jax required; tracing/lowering only, nothing
    compiles or dispatches) and run the IR checkers."""
    from .checkers.ir_rules import run_ir_checkers
    from .ir.catalog import catalog_reports
    reports = catalog_reports(live_configs=live_configs)
    return reports, run_ir_checkers(reports)


def _write_cost_report(reports):
    """Honor MXNET_IR_COST_REPORT: the per-program CostReports as one
    JSON file next to graftplan's memory numbers."""
    import json

    from mxnet_tpu import config as _config
    path = _config.get("MXNET_IR_COST_REPORT")
    if not path:
        return None
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"programs": [
            {"name": r["name"], "kind": r["kind"],
             "origin": r["origin"], "cost": r["cost"]}
            for r in reports]}, f, indent=1)
        f.write("\n")
    return path


def _restricted_update(findings, baseline_path, scope, narrowed=None):
    """The --plan/--ir baseline refresh: re-derive only ``scope``'s
    rules (narrowed further by --rule), preserve every other entry,
    carry audit annotations for unchanged fingerprints."""
    scope = set(narrowed) & set(scope) if narrowed else set(scope)
    entries = {f.fingerprint: f.to_dict() for f in findings}
    kept = 0
    for fp, e in baseline_mod.load(baseline_path).items():
        if fp in entries:
            if "audit" in e:
                entries[fp]["audit"] = e["audit"]
            continue
        if e.get("rule") not in scope:
            entries[fp] = e
            kept += 1
    baseline_mod.save_entries(list(entries.values()), baseline_path)
    print("graftlint: wrote %d finding%s to %s"
          % (len(entries), "s" if len(entries) != 1 else "",
             baseline_path)
          + (" (%d out-of-scope entr%s preserved)"
             % (kept, "ies" if kept != 1 else "y") if kept else ""))
    return 0


def _ir(args):
    """``--ir``: graftir over the traced in-tree program catalog —
    donation aliasing, dtype drift, dead outputs, the collective
    schedule vs plan/schedule.py, Pallas presence, and the static cost
    model — gated through the same committed baseline as every other
    rule.  Like ``--plan`` this imports and instantiates the package
    (jax required) but NOTHING compiles: abstract tracing + lowering
    only."""
    import json

    from .checkers.ir_rules import IR_RULES

    if _bad_rules(args.rules):
        return 2
    reports, findings = _load_ir()
    if args.rules:
        findings = [f for f in findings if f.rule in set(args.rules)]
    cost_path = _write_cost_report(reports)
    baseline_path = args.baseline or baseline_mod.default_path(repo_root())
    if args.update_baseline:
        return _restricted_update(findings, baseline_path, IR_RULES,
                                  narrowed=args.rules)
    known = {} if args.no_baseline else baseline_mod.load(baseline_path)
    new, old = baseline_mod.filter_new(findings, known)
    if args.sarif:
        doc = json.loads(sarif_report(new, old))
        doc["runs"][0]["properties"] = {
            "graftir": {"programs": [r["name"] for r in reports]}}
        print(json.dumps(doc, indent=1))
    elif args.json:
        doc = json.loads(json_report(new, old))
        doc["ir"] = {"reports": reports}
        print(json.dumps(doc, indent=1))
    else:
        for r in reports:
            print(_ir_cost_line(r))
        if cost_path:
            print("graftir: cost report written to %s" % cost_path)
        print(human_report(new, old, show_baselined=args.show_baselined))
        exact = sum(1 for r in reports
                    if sorted(map(tuple, r.get("schedule_expect") or []))
                    == sorted(map(tuple, r.get("schedule_actual") or [])))
        print("graftir: %d program%s traced, collective schedule "
              "matches the plan on %d"
              % (len(reports), "s" if len(reports) != 1 else "", exact))
    return 1 if new else 0


def _load_kern():
    """Build the kernel catalog (jax required for BlockSpec
    construction, but nothing traces or compiles — index maps are
    evaluated with plain ints) and run the kern checkers."""
    from .checkers.kern_rules import run_kern_checkers
    from .kern.catalog import kernel_reports
    reports = kernel_reports()
    return reports, run_kern_checkers(reports)


def _kern_line(report):
    vmem = report.get("vmem") or {}
    shard = report.get("shard")
    verdict = ""
    if shard is not None:
        verdict = (", shard-safe (grid dim %s walks axis %s)"
                   % (shard.get("grid_dim"), shard.get("axis"))
                   if shard.get("safe")
                   else ", NOT provably shard-safe")
    return ("kern %-26s grid %s, vmem %d B of %d B budget%s"
            % (report["name"], tuple(report["grid"]),
               vmem.get("bytes_per_instance", 0),
               vmem.get("budget", 0), verdict))


def _kern(args):
    """``--kern``: graftkern over the in-tree Pallas kernel catalog —
    grid coverage, VMEM budgets, scalar-prefetch transport, shard_map
    safety — by abstract interpretation of the kernels' own
    grid/BlockSpec plans (ops/pallas_kernels.py builds them for its
    dispatch; the catalog re-reads the same objects).  Like --plan
    this imports the package (jax required) but NOTHING traces or
    compiles — index maps are evaluated with plain Python ints.  The
    per-kernel VMEM predictions print beside the plan leg's HBM
    numbers under --all (one byte story per step: HBM from graftplan,
    VMEM from graftkern)."""
    import json

    from .checkers.kern_rules import KERN_RULES

    if _bad_rules(args.rules):
        return 2
    reports, findings = _load_kern()
    if args.rules:
        findings = [f for f in findings if f.rule in set(args.rules)]
    baseline_path = args.baseline or baseline_mod.default_path(repo_root())
    if args.update_baseline:
        return _restricted_update(findings, baseline_path, KERN_RULES,
                                  narrowed=args.rules)
    known = {} if args.no_baseline else baseline_mod.load(baseline_path)
    new, old = baseline_mod.filter_new(findings, known)
    if args.sarif:
        doc = json.loads(sarif_report(new, old))
        doc["runs"][0]["properties"] = {
            "graftkern": {"kernels": [r["name"] for r in reports]}}
        print(json.dumps(doc, indent=1))
    elif args.json:
        doc = json.loads(json_report(new, old))
        doc["kern"] = {"reports": reports}
        print(json.dumps(doc, indent=1))
    else:
        for r in reports:
            print(_kern_line(r))
        print(human_report(new, old, show_baselined=args.show_baselined))
        cands = [r for r in reports if r.get("shard") is not None]
        safe = sum(1 for r in cands if r["shard"].get("safe"))
        print("graftkern: %d kernel%s analyzed, %d of %d shard_map "
              "candidate%s provably safe"
              % (len(reports), "s" if len(reports) != 1 else "",
                 safe, len(cands), "s" if len(cands) != 1 else ""))
    return 1 if new else 0


def _kern_relevant(paths):
    """Whether a --changed path set can affect the kernel catalog:
    the kernels themselves (ops/pallas_kernels.py), anything in the
    analysis package (checkers/catalog/engine), or config.py (the
    VMEM budget and family knobs feed the reports)."""
    for p in paths:
        rel = p.replace(os.sep, "/")
        if rel.endswith("ops/pallas_kernels.py") \
                or rel.endswith("mxnet_tpu/config.py") \
                or "mxnet_tpu/analysis" in rel:
            return True
    return False


def _all(args):
    """``--all``: lint + plan + ir + kern in ONE process with one
    merged baseline pass and one exit code — the single entry point
    tier-1 and CI call instead of four.  The plan's closed-loop
    verification still fails the run even when its findings are
    baselined; the IR leg honors the MXNET_IR master switch and the
    kern leg honors MXNET_KERN."""
    import json

    from mxnet_tpu import config as _config

    from .checkers.plan_rules import run_plan_checkers

    if _bad_rules(args.rules):
        return 2
    root = repo_root()
    cache = None
    if not args.no_cache:
        from . import cache as cache_mod
        cache = args.cache or cache_mod.default_path(root)
    static = run([os.path.join(root, "mxnet_tpu")], rules=args.rules,
                 cache=cache)

    # ONE live catalog (4 trainers + serving + bound program on the
    # virtual mesh) shared by the plan and IR legs
    from .plan.configs import in_tree_live
    live = in_tree_live()
    plan_reports, verify_problems = _load_plan(
        configs=[(s, m) for s, m, _l in live])
    plan_findings = run_plan_checkers(plan_reports)

    ir_reports, ir_findings = [], []
    ir_on = bool(_config.get("MXNET_IR"))
    if ir_on:
        ir_reports, ir_findings = _load_ir(live_configs=live)
        _write_cost_report(ir_reports)

    kern_reports, kern_findings = [], []
    kern_on = bool(_config.get("MXNET_KERN"))
    if kern_on:
        kern_reports, kern_findings = _load_kern()

    findings = (list(static) + list(plan_findings) + list(ir_findings)
                + list(kern_findings))
    if args.rules:
        wanted = set(args.rules)
        findings = [f for f in findings
                    if f.rule in wanted or f.rule == "parse-error"]
    baseline_path = args.baseline or baseline_mod.default_path(root)
    if args.update_baseline:
        # full-scope merge: every leg re-derived in this run, so only
        # audit annotations need carrying over (narrowed --rule runs
        # still preserve out-of-scope entries).  A skipped IR/kern leg
        # (MXNET_IR=0 / MXNET_KERN=0) re-derived nothing — its rules
        # leave the scope so accepted entries are preserved, not
        # silently dropped
        from .checkers.ir_rules import IR_RULES
        from .checkers.kern_rules import KERN_RULES
        scope = set(rule_ids()) | {"parse-error", "stale-suppression"}
        if not ir_on:
            scope -= set(IR_RULES)
        if not kern_on:
            scope -= set(KERN_RULES)
        return _restricted_update(findings, baseline_path, scope,
                                  narrowed=args.rules)
    known = {} if args.no_baseline else baseline_mod.load(baseline_path)
    new, old = baseline_mod.filter_new(findings, known)
    if args.sarif:
        doc = json.loads(sarif_report(new, old))
        doc["runs"][0]["properties"] = {
            "graftlintAll": {
                "plan_configs": [r["name"] for r in plan_reports],
                "verify_problems": verify_problems,
                "ir_programs": [r["name"] for r in ir_reports],
                "ir_enabled": ir_on,
                "kern_kernels": [r["name"] for r in kern_reports],
                "kern_enabled": kern_on}}
        print(json.dumps(doc, indent=1))
    elif args.json:
        doc = json.loads(json_report(new, old))
        doc["plan"] = {"reports": plan_reports,
                       "verify_problems": verify_problems}
        doc["ir"] = {"enabled": ir_on, "reports": ir_reports}
        doc["kern"] = {"enabled": kern_on, "reports": kern_reports}
        print(json.dumps(doc, indent=1))
    else:
        for p in verify_problems:
            print("PREDICTION MISMATCH: %s" % p)
        if not ir_on:
            print("graftir: skipped (MXNET_IR=0)")
        if not kern_on:
            print("graftkern: skipped (MXNET_KERN=0)")
        else:
            # VMEM predictions beside the plan leg's HBM numbers —
            # one byte story per step
            for r in kern_reports:
                print(_kern_line(r))
        print(human_report(new, old, show_baselined=args.show_baselined))
        print("graftlint --all: %d static + %d plan + %d ir + %d kern "
              "findings before baseline; %d plan config%s, %d traced "
              "program%s, %d kernel%s"
              % (len(static), len(plan_findings), len(ir_findings),
                 len(kern_findings), len(plan_reports),
                 "s" if len(plan_reports) != 1 else "",
                 len(ir_reports), "s" if len(ir_reports) != 1 else "",
                 len(kern_reports),
                 "s" if len(kern_reports) != 1 else ""))
    return 1 if (new or verify_problems) else 0


def _audit_suppressions(args):
    """``--audit-suppressions``: the one mode that executes the package
    (everything else is stdlib AST) — run the built-in workload under
    all four graftsan sanitizers and gate on the verdicts."""
    import json

    from .core import Finding
    from .sanitizers import run_audit
    rep = run_audit()
    if args.sarif:
        # findings travel as SARIF results (CI annotation); the
        # suppression verdicts ride in run properties
        findings = [Finding(d["rule"], d["severity"], d["path"],
                            d["line"], d["message"], d.get("symbol", ""))
                    for d in rep["findings"]]
        sarif = json.loads(sarif_report(findings))
        sarif["runs"][0]["properties"] = {
            "graftsanAudit": {k: rep[k] for k in
                              ("summary", "suppressions", "baseline")}}
        print(json.dumps(sarif, indent=1))
    elif args.json:
        print(json.dumps(rep, indent=1))
    else:
        for row in rep["suppressions"]:
            print("%s:%d [%s] %s — %s"
                  % (row["path"], row["line"], ",".join(row["rules"]),
                     row["verdict"], row["evidence"]))
        for row in rep["baseline"]:
            print("baseline %s (%s %s) %s — %s"
                  % (row["fingerprint"], row["path"], row["symbol"],
                     row["verdict"], row["evidence"]))
        for d in rep["findings"]:
            print("UNCLAIMED %s:%d [%s] %s"
                  % (d["path"], d["line"], d["rule"], d["message"]))
        s = rep["summary"]
        print("graftsan audit: %d suppressions + %d baseline entries — "
              "%d runtime-confirmed, %d never-exercised, "
              "%d justified-unreachable, %d contradicted; "
              "%d unclaimed runtime finding%s"
              % (s["suppressions"], s["baseline_entries"],
                 s["runtime_confirmed"], s["never_exercised"],
                 s.get("justified_unreachable", 0),
                 s["contradicted"], s["unclaimed_findings"],
                 "s" if s["unclaimed_findings"] != 1 else ""))
    return 0 if rep["ok"] else 1


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="graftlint",
        description="AST static analysis with TPU/JAX-aware checkers "
                    "(rule catalog: docs/faq/static_analysis.md)")
    parser.add_argument(
        "paths", nargs="*",
        help="files or directories to lint (default: the mxnet_tpu "
             "package)")
    parser.add_argument(
        "--json", action="store_true",
        help="emit a machine-readable JSON report instead of text")
    parser.add_argument(
        "--sarif", action="store_true",
        help="emit a SARIF 2.1.0 report (CI diff annotation)")
    parser.add_argument(
        "--changed", nargs="?", const="WORKTREE", default=None,
        metavar="REF",
        help="lint only files git reports changed (worktree vs HEAD, "
             "or vs REF when given)")
    parser.add_argument(
        "--cache", metavar="PATH",
        help="incremental cache file (default: <repo>/.graftlint-"
             "cache.json)")
    parser.add_argument(
        "--no-cache", action="store_true",
        help="analyze every file from scratch")
    parser.add_argument(
        "--stale", action="store_true",
        help="list stale suppression comments as a removal worklist "
             "and exit (1 when any exist)")
    parser.add_argument(
        "--plan", action="store_true",
        help="run graftplan (static shape/sharding/memory analysis) "
             "over the in-tree configuration catalog and gate the "
             "spmd-divisibility / collective-mismatch / oom-risk / "
             "bucket-plan-waste findings; also verifies predicted "
             "optimizer-state and collective bytes against the live "
             "measurements.  NOTE: imports and instantiates the "
             "package (jax required), but nothing XLA-compiles")
    parser.add_argument(
        "--ir", action="store_true",
        help="run graftir (jaxpr-level verification of the compiled "
             "step: donation aliasing, dtype drift, dead outputs, "
             "collective schedule vs plan/schedule.py, Pallas "
             "presence, static cost model) over the traced in-tree "
             "program catalog and gate the ir-* findings.  NOTE: "
             "imports and instantiates the package (jax required), "
             "but only traces/lowers — nothing XLA-compiles")
    parser.add_argument(
        "--kern", action="store_true",
        help="run graftkern (static Pallas kernel verification: grid "
             "coverage, VMEM budget vs MXNET_KERN_VMEM_BYTES, "
             "scalar-prefetch retrace hazards, shard_map safety) over "
             "the in-tree kernel catalog and gate the kern-* "
             "findings.  NOTE: imports the package (jax required) but "
             "nothing traces or compiles — index maps are evaluated "
             "with plain ints")
    parser.add_argument(
        "--all", action="store_true", dest="all_modes",
        help="lint + plan + ir + kern in one process with one merged "
             "baseline pass and one exit code (the tier-1/CI entry "
             "point); the ir leg honors MXNET_IR, the kern leg "
             "MXNET_KERN")
    parser.add_argument(
        "--audit-suppressions", action="store_true",
        help="run the graftsan workload (runtime sanitizers + line "
             "probe) and classify every inline suppression and "
             "baseline entry as runtime-confirmed / never-exercised / "
             "contradicted; exits 1 on contradictions or unclaimed "
             "runtime findings.  NOTE: unlike every other mode this "
             "imports and RUNS the package (jax required)")
    parser.add_argument(
        "--rule", action="append", dest="rules", metavar="RULE",
        help="restrict to RULE (repeatable); see --list-rules")
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the registered rule ids and exit")
    parser.add_argument(
        "--baseline", metavar="PATH",
        help="baseline file (default: <repo>/%s)"
             % baseline_mod.BASELINE_NAME)
    parser.add_argument(
        "--update-baseline", action="store_true",
        help="rewrite the baseline from this run's findings and exit 0")
    parser.add_argument(
        "--no-baseline", action="store_true",
        help="gate on every finding, ignoring the baseline")
    parser.add_argument(
        "--show-baselined", action="store_true",
        help="also list baselined findings in the text report")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule in rule_ids():
            print(rule)
        return 0

    if args.audit_suppressions:
        return _audit_suppressions(args)

    if args.changed is not None and (args.plan or args.ir or args.kern
                                     or args.all_modes):
        # the catalog analyses are whole-program (IR facts and plan
        # predictions don't decompose per file), so --changed acts as
        # the pre-push fast path: nothing relevant changed -> skip the
        # catalog entirely; anything changed -> full run
        if args.paths:
            print("graftlint: --changed derives the path set from git; "
                  "drop the explicit paths", file=sys.stderr)
            return 2
        try:
            changed = _changed_paths(
                repo_root(),
                None if args.changed == "WORKTREE" else args.changed)
        except RuntimeError as exc:
            print("graftlint: %s" % exc, file=sys.stderr)
            return 2
        if not changed:
            print("graftlint: no changed lintable files")
            return 0
        if args.kern and not args.all_modes and not _kern_relevant(changed):
            # the kern catalog is derived solely from the kernel plans
            # (plus the analysis engine and the knob registry); edits
            # anywhere else cannot change a kern verdict
            print("graftlint: no changed files affect the kernel "
                  "catalog; skipping kern run")
            return 0

    if args.all_modes:
        if args.plan or args.ir or args.kern:
            print("graftlint: --all already includes --plan, --ir "
                  "and --kern", file=sys.stderr)
            return 2
        return _all(args)

    if args.plan:
        return _plan(args)

    if args.ir:
        return _ir(args)

    if args.kern:
        return _kern(args)

    root = repo_root()
    if args.changed is not None:
        if args.paths:
            print("graftlint: --changed derives the path set from git; "
                  "drop the explicit paths", file=sys.stderr)
            return 2
        try:
            paths = _changed_paths(
                root, None if args.changed == "WORKTREE" else args.changed)
        except RuntimeError as exc:
            print("graftlint: %s" % exc, file=sys.stderr)
            return 2
        if not paths:
            print("graftlint: no changed lintable files")
            return 0
    else:
        paths = args.paths or [os.path.join(root, "mxnet_tpu")]
    for p in paths:
        if not os.path.exists(p):
            print("graftlint: no such path: %s" % p, file=sys.stderr)
            return 2
    cache = None
    if not args.no_cache:
        from . import cache as cache_mod
        cache = args.cache or cache_mod.default_path(root)
    try:
        findings = run(paths, rules=args.rules, cache=cache)
    except ValueError as exc:       # unknown --rule
        print("graftlint: %s" % exc, file=sys.stderr)
        return 2

    if args.stale:
        stale = [f for f in findings if f.rule == "stale-suppression"]
        for f in stale:
            print("%s:%d: remove the suppression comment (%s)"
                  % (f.path, f.line, f.message.split(" — ")[0]))
        print("graftlint: %d stale suppression%s"
              % (len(stale), "s" if len(stale) != 1 else ""))
        return 1 if stale else 0

    baseline_path = args.baseline or baseline_mod.default_path(root)
    if args.update_baseline:
        # a restricted run (--rule / explicit paths / --changed) only
        # re-derives the findings in its scope: out-of-scope baseline
        # entries are preserved, not silently dropped (a --rule update
        # must not un-baseline every other rule's deliberate findings,
        # and `--changed --update-baseline` must not un-baseline every
        # UNCHANGED file's)
        entries = {f.fingerprint: f.to_dict() for f in findings}
        # audit verdicts annotated onto baseline entries (the
        # --audit-suppressions workflow) survive a refresh of an
        # unchanged finding — only a changed fingerprint re-opens one
        for fp, e in baseline_mod.load(baseline_path).items():
            if fp in entries and "audit" in e:
                entries[fp]["audit"] = e["audit"]
        restricted_rules = set(args.rules) if args.rules else None
        restricted_paths = None
        if args.paths or args.changed is not None:
            restricted_paths = [
                os.path.relpath(os.path.abspath(p), root).replace(
                    os.sep, "/")
                for p in paths]
        kept = 0
        if restricted_rules or restricted_paths:
            for fp, e in baseline_mod.load(baseline_path).items():
                if fp in entries:
                    continue
                in_rules = (restricted_rules is None
                            or e["rule"] in restricted_rules)
                in_paths = restricted_paths is None or any(
                    e["path"] == p or e["path"].startswith(p + "/")
                    for p in restricted_paths)
                if not (in_rules and in_paths):
                    entries[fp] = e
                    kept += 1
        baseline_mod.save_entries(list(entries.values()), baseline_path)
        print("graftlint: wrote %d finding%s to %s"
              % (len(entries), "s" if len(entries) != 1 else "",
                 baseline_path)
              + (" (%d out-of-scope entr%s preserved)"
                 % (kept, "ies" if kept != 1 else "y") if kept else ""))
        return 0

    known = {} if args.no_baseline else baseline_mod.load(baseline_path)
    new, old = baseline_mod.filter_new(findings, known)
    if args.sarif:
        print(sarif_report(new, old))
    elif args.json:
        print(json_report(new, old))
    else:
        print(human_report(new, old, show_baselined=args.show_baselined))
    return 1 if new else 0
