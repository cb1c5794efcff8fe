"""Kernel checkers — graftkern verdicts as graftlint rules.

Four rules consuming :mod:`mxnet_tpu.analysis.kern` kernel reports
(pure data) instead of source files: ``check()`` is inert in the
file-walk pass (``suffixes = ()``), and ``check_kern(report, ctx)``
runs under ``tools/lint.py --kern`` / ``--all`` (and the tier-1 gate in
``tests/test_kern.py``) over the abstractly-interpreted in-tree kernel
catalog.  Same :class:`~..core.Finding` machinery — fingerprints,
SARIF, committed baseline (``--kern --update-baseline`` is the
acceptance path for a deliberate finding); findings anchor to
``ops/pallas_kernels.py`` with the kernel name as the enclosing symbol.

| rule | catches |
|---|---|
| ``kern-grid-coverage``  | output blocks the index maps never write, write unevenly (overlap), or write out of range — plus a padded tail with no masking contract (injectivity + surjectivity of grid -> output blocks, modulo declared sequential revisits) — and an output block declared the sum over a group of heads whose steps leave a member of the group unread, or read a head outside it |
| ``kern-vmem-budget``    | per-program-instance VMEM residency (block shapes x dtypes + scratch) over ``MXNET_KERN_VMEM_BYTES`` |
| ``kern-retrace-hazard`` | schedule-varying hyperparameters (lr/momentum/betas/wd/clip) baked into the kernel as Python-level constants instead of riding the scalar-prefetch operand — the lr-schedule retrace class made structural |
| ``kern-shard-safety``   | a shard_map-candidate kernel whose index maps are NOT provably block-local along the sharded axis (cross-block reads/writes on that dim) — the verdict ``ops/pallas_kernels.py mesh_sweep_safe`` consumes |

The helpers here (:func:`shard_safety`, :func:`vmem_bytes`,
:func:`coverage_problems`, :func:`group_problems`) are pure functions
of a report dict, shared with the catalog (``analysis/kern/catalog.py``)
and with ``mesh_sweep_safe``'s cached verdict — one implementation of every
judgement.
"""
from __future__ import annotations

import itertools

from ..core import Checker, Finding, register

__all__ = ["KernGridCoverageChecker", "KernVmemBudgetChecker",
           "KernRetraceHazardChecker", "KernShardSafetyChecker",
           "kern_checkers", "run_kern_checkers", "KERN_RULES",
           "shard_safety", "vmem_bytes", "coverage_problems",
           "group_problems", "SCHEDULE_HYPERPARAMS"]

KERN_RULES = frozenset((
    "kern-grid-coverage", "kern-vmem-budget", "kern-retrace-hazard",
    "kern-shard-safety"))

# hyperparameters that change with the training schedule — these MUST
# travel as scalar-prefetch VALUES; baked in as Python constants every
# schedule step becomes a retrace + recompile.  Architecture constants
# (a layernorm eps, an attention scale, a causal flag, block sizes)
# are legitimately structural and stay out of this set.
SCHEDULE_HYPERPARAMS = frozenset((
    "lr", "lr_eff", "learning_rate", "momentum", "wd", "weight_decay",
    "beta1", "beta2", "rescale", "rescale_grad", "clip",
    "clip_gradient"))

_DTYPE_BYTES = {"float64": 8, "float32": 4, "int32": 4, "uint32": 4,
                "bfloat16": 2, "float16": 2, "int16": 2, "int8": 1,
                "uint8": 1, "bool": 1}


def _dtype_bytes(name):
    return _DTYPE_BYTES.get(str(name), 4)


def grid_points(grid):
    """Row-major enumeration of the grid — the order every report's
    per-operand ``index`` table follows."""
    return list(itertools.product(*[range(int(g)) for g in grid]))


def _block_extent(block, dim):
    b = block[dim]
    return 1 if b is None else int(b)


def operand_blocks(op):
    """Blocks per dimension of an operand's padded shape under its
    block shape (``None`` block dims are size-1 squeezed blocks)."""
    return tuple(-(-int(s) // _block_extent(op["block"], d))
                 for d, s in enumerate(op["shape"]))


def block_bytes(op):
    """VMEM bytes of one operand's per-step block."""
    total = _dtype_bytes(op.get("dtype"))
    for b in op["block"]:
        total *= 1 if b is None else int(b)
    return total


def vmem_bytes(report):
    """Per-program-instance VMEM residency: every in/out operand's
    block plus declared scratch.  Scalar-prefetch operands live in
    SMEM and do not count."""
    total = 0
    for op in report.get("operands", ()):
        if op.get("role") == "scalar_prefetch" or op.get("block") is None:
            continue
        total += block_bytes(op)
    for s in report.get("scratch", ()):
        b = _dtype_bytes(s.get("dtype"))
        for d in s["shape"]:
            b *= int(d)
        total += b
    return total


def _affecting_dims(pts, table, ndims):
    """Grid dimensions whose coordinate changes the operand's block
    index — the complement's sizes multiply into the legal sequential
    revisit count (accumulate-in-scratch schedules re-visit an output
    block once per unused grid step)."""
    affect = set()
    for d in range(ndims):
        first = {}
        for pt, idx in zip(pts, table):
            key = pt[:d] + pt[d + 1:]
            if first.setdefault(key, idx) != idx:
                affect.add(d)
                break
    return affect


def coverage_problems(op, grid):
    """Pure coverage verdict for one output operand: list of problem
    strings (empty == every block written exactly once per sequential
    revisit, nothing out of range)."""
    pts = grid_points(grid)
    table = [tuple(int(v) for v in row) for row in op.get("index") or ()]
    if len(table) != len(pts):
        return ["index table covers %d of %d grid points"
                % (len(table), len(pts))]
    blocks = operand_blocks(op)
    expected = set(itertools.product(*[range(b) for b in blocks]))
    counts = {}
    for t in table:
        counts[t] = counts.get(t, 0) + 1
    problems = []
    oob = sorted(set(counts) - expected)
    if oob:
        problems.append(
            "index map escapes the %s-block output (first out-of-range "
            "block %s)" % ("x".join(map(str, blocks)), oob[0]))
    missing = sorted(expected - set(counts))
    if op.get("revisit") == "used":
        # a data-dependent PREFIX of the blocks is written, once each
        # and in order, and the grid holds at the last of them for the
        # steps past it (which write nothing: the block is not written
        # back until the grid ends); the blocks after are left as they
        # are, and whoever reads the output skips them by the same count
        order = sorted(expected)
        seen = [t for i, t in enumerate(table) if i == 0 or t != table[i - 1]]
        if seen != order[:len(seen)]:
            problems.append(
                "the written blocks are not a prefix in grid order "
                "(first blocks %s)" % (seen[:3],))
        early = [t for t in seen[:-1] if counts[t] != 1]
        if early:
            problems.append(
                "block %s is written %d times before the grid holds at "
                "its last block" % (early[0], counts[early[0]]))
        return problems
    if missing:
        problems.append(
            "%d of %d output blocks are never written (first gap %s)"
            % (len(missing), len(expected), missing[0]))
    if op.get("revisit") == "runs":
        # a block the grid stays on over a data-dependent RUN of
        # consecutive steps (a group's row tiles, the reduction
        # innermost): no race iff every block's visits are one unbroken
        # run in grid order — however long each run is
        closed, prev = set(), None
        for t in table:
            if t != prev:
                if t in closed:
                    problems.append(
                        "block %s is revisited after the grid left it — "
                        "its accumulation is written back in between"
                        % (t,))
                    break
                closed.add(prev)
                prev = t
        return problems
    revisit = 1
    affect = _affecting_dims(pts, table, len(grid))
    for d, g in enumerate(grid):
        if d not in affect:
            revisit *= int(g)
    uneven = sorted(t for t in counts
                    if t in expected and counts[t] != revisit)
    if uneven:
        t = uneven[0]
        problems.append(
            "block %s is written %d times where the grid implies %d — "
            "overlapping index maps race on the block"
            % (t, counts[t], revisit))
    return problems


def group_problems(report, op):
    """Problems of an output operand that declares ``sums: {"of":
    operand, "heads": g}`` — each of its blocks is the sum over the
    ``g`` heads of a group (grouped-query attention's dK / dV: the query
    heads that share a key/value head).  The grid steps that write block
    ``t`` must read operand ``of`` at every head ``t[0] g .. t[0] g + g
    - 1`` (its first index) and at no other: a plan that drops a member
    of the group writes every block as often as one that keeps it, which
    the coverage verdict cannot tell."""
    sums = op.get("sums")
    if not sums:
        return []
    heads = int(sums["heads"])
    source = next((o for o in report.get("operands", ())
                   if o["name"] == sums["of"]), None)
    if source is None or not source.get("index"):
        return ["sums over the heads of %r, which the report does not "
                "index" % (sums["of"],)]
    read = {}
    for t, src in zip(op["index"], source["index"]):
        read.setdefault(tuple(t), set()).add(int(src[0]))
    for t in sorted(read):
        group = set(range(t[0] * heads, (t[0] + 1) * heads))
        if read[t] != group:
            missing, stray = sorted(group - read[t]), sorted(read[t] - group)
            return ["block %s sums the %d heads %d..%d of %s, but its "
                    "steps %s" % (
                        t, heads, min(group), max(group), sums["of"],
                        "read head %s of another group" % stray[0] if stray
                        else "never read head %s" % missing[0])]
    return []


def shard_safety(report):
    """The ``kern-shard-safety`` verdict as pure data.

    A kernel is provably safe to wrap in ``shard_map`` along the
    declared axis when ONE grid dimension walks that axis identically
    for every sharded operand: block index along the axis equals that
    grid coordinate at every grid point, and the dimension's extent
    equals the operand's block count along the axis.  Splitting the
    buffers 1/mesh then splits exactly that grid dimension — each
    shard's kernel reads and writes only its own blocks, so the wrap
    (which must pass ``check_vma=False``: pallas_call has no
    replication rule) cannot change any result.

    Returns ``{"candidate", "safe", "grid_dim", "reasons"}``.
    """
    shard = report.get("shard") or None
    if not shard:
        return {"candidate": False, "safe": False, "grid_dim": None,
                "reasons": ["not a shard candidate"]}
    axis = int(shard["axis"])
    sharded = set(shard.get("operands") or ())
    grid = [int(g) for g in report.get("grid") or ()]
    pts = grid_points(grid)
    reasons = []
    candidates = None
    for op in report.get("operands", ()):
        if op.get("role") == "scalar_prefetch" \
                or op["name"] not in sharded:
            continue        # replicated operands are shard-invariant
        blocks = operand_blocks(op)
        nblocks = blocks[axis]
        table = [tuple(int(v) for v in row)
                 for row in op.get("index") or ()]
        if len(table) != len(pts):
            reasons.append("%s: index table does not cover the grid"
                           % op["name"])
            candidates = set()
            continue
        mine = {g for g in range(len(grid))
                if grid[g] == nblocks
                and all(idx[axis] == pt[g]
                        for pt, idx in zip(pts, table))}
        if not mine:
            reasons.append(
                "%s: block index along sharded axis %d is not the "
                "identity of any grid dimension — a cross-block "
                "access on the dim the mesh would split" % (op["name"],
                                                            axis))
        candidates = mine if candidates is None else candidates & mine
    if candidates is None:
        reasons.append("no sharded operands declared")
        candidates = set()
    safe = bool(candidates)
    if not safe and not reasons:
        reasons.append("operands disagree on which grid dimension "
                       "walks the sharded axis")
    return {"candidate": True, "safe": safe,
            "grid_dim": min(candidates) if candidates else None,
            "reasons": reasons}


class _KernChecker(Checker):
    """Base: inert in the file walk, active in the kern pass."""

    suffixes = ()

    def check(self, path, relpath, text, tree, ctx):
        return []

    def _finding(self, report, message):
        return Finding(self.rule, self.severity, report["origin"], 1,
                       message, symbol=report["name"])

    def check_kern(self, report, ctx):
        raise NotImplementedError


@register
class KernGridCoverageChecker(_KernChecker):
    rule = "kern-grid-coverage"
    severity = "error"

    def check_kern(self, report, ctx):
        out = []
        grid = report.get("grid") or []
        for op in report.get("operands", ()):
            if op.get("role") != "out":
                continue
            for problem in coverage_problems(op, grid) \
                    + group_problems(report, op):
                out.append(self._finding(
                    report,
                    "output %s: %s — the grid must write every output "
                    "block exactly once (modulo declared sequential "
                    "revisits)" % (op["name"], problem)))
        tail = report.get("tail") or {}
        if tail.get("padded_elems", 0) > tail.get("logical_elems", 0) \
                and not tail.get("masked"):
            out.append(self._finding(
                report,
                "padded tail (%d of %d elements are padding) has no "
                "masking contract — pad lanes feed real outputs; "
                "declare the identity-fill/slice-away scheme or mask "
                "in-kernel" % (tail["padded_elems"]
                               - tail["logical_elems"],
                               tail["padded_elems"])))
        return out


@register
class KernVmemBudgetChecker(_KernChecker):
    rule = "kern-vmem-budget"
    severity = "error"

    def check_kern(self, report, ctx):
        budget = (ctx or {}).get("vmem_budget")
        if budget is None:
            from ... import config as _config
            budget = _config.get("MXNET_KERN_VMEM_BYTES")
        budget = int(budget)
        total = vmem_bytes(report)
        if total <= budget:
            return []
        return [self._finding(
            report,
            "per-instance VMEM residency %d B (operand blocks + "
            "scratch) exceeds MXNET_KERN_VMEM_BYTES=%d — the kernel "
            "will spill or fail to fit a core's VMEM; shrink the block "
            "shapes or raise the budget" % (total, budget))]


@register
class KernRetraceHazardChecker(_KernChecker):
    rule = "kern-retrace-hazard"
    severity = "warning"

    def check_kern(self, report, ctx):
        out = []
        hyper = report.get("hyper") or {}
        if hyper.get("names") \
                and hyper.get("transport") != "scalar_prefetch":
            out.append(self._finding(
                report,
                "hyperparameters %s travel by %s — route them through "
                "ONE scalar-prefetch operand so a schedule change is a "
                "new argument value, not a new program"
                % (", ".join(hyper["names"]),
                   hyper.get("transport") or "closure")))
        for pc in report.get("python_constants", ()):
            if pc.get("name") in SCHEDULE_HYPERPARAMS:
                out.append(self._finding(
                    report,
                    "schedule-varying hyperparameter %r is baked into "
                    "the kernel as a Python constant (%s) — every "
                    "schedule change retraces and recompiles the "
                    "program; move the value onto the scalar-prefetch "
                    "operand" % (pc["name"],
                                 pc.get("detail") or "closure constant")))
        return out


@register
class KernShardSafetyChecker(_KernChecker):
    rule = "kern-shard-safety"
    severity = "error"

    def check_kern(self, report, ctx):
        verdict = shard_safety(report)
        if not verdict["candidate"] or verdict["safe"]:
            return []
        shard = report.get("shard") or {}
        return [self._finding(
            report,
            "shard_map candidate along axis %s is NOT provably "
            "block-local: %s — the verdict stays unsafe, so "
            "mesh_sweep_safe keeps multi-chip runs on the tree_map "
            "path" % (shard.get("axis"),
                      "; ".join(verdict["reasons"])))]


def kern_checkers():
    """The registered checkers that implement a kern pass."""
    from ..core import checkers
    return [cls() for cls in checkers()
            if issubclass(cls, _KernChecker)]


def run_kern_checkers(reports, ctx=None):
    """All kern findings over ``reports``, sorted and fingerprint-
    deduplicated the same way ``core.run`` does."""
    findings = []
    for checker in kern_checkers():
        for report in reports:
            findings.extend(checker.check_kern(report, ctx))
    findings.sort(key=Finding.sort_key)
    counts = {}
    for f in findings:
        key = (f.rule, f.path, f.symbol, f.message)
        f._dup = counts.get(key, 0)
        counts[key] = f._dup + 1
    return findings
