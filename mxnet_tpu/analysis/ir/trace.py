"""Abstract tracing + jaxpr fact extraction for graftir.

Everything here is capture: :func:`trace_program` traces a jitted
callable over abstract arguments (``jax.jit(fn).trace`` — the aot API;
nothing compiles, nothing dispatches) and distills the closed jaxpr +
lowered StableHLO into ONE pure-data report dict.  The ``ir-*``
checkers (``checkers/ir_rules.py``) consume only these dicts, so the
seeded-misconfiguration tests can run them with ``jax.jit`` fully
poisoned, exactly like graftplan's.

Fact channels:

- **collectives** — explicit collective primitives (``psum`` /
  ``all_gather`` / ``reduce_scatter`` / ``ppermute`` — shard_map
  programs) plus the trainer's TAGGED sharding-constraint sites:
  ``ParallelTrainer`` wraps each collective-implying
  ``with_sharding_constraint`` in ``jax.named_scope("mx_coll:<kind>:
  b<bucket>")``, and the eqn's name stack carries the scope through
  trace AND transpose — so the reduce-scatter a ``custom_vjp`` tap
  attaches inside the backward stream is found where it actually
  lives.  A refactor that drops the constraint drops the eqn, and
  ``ir-collective-schedule`` fires.
- **dtype drift** — tracing runs under ``jax.enable_x64``
  so an injected f64 is representable instead of silently truncated;
  forward bf16→f32 converts are promotions unless scoped deliberate
  (``DELIBERATE_CAST_SCOPES`` — the codec decode, the amp fp32-master
  loss cast) or sitting in a transpose region (cotangent upcasts are
  the amp master-grad design).
- **dead eqns** — the traced jaxpr is NOT dead-code-eliminated, so
  computed-but-unused work (a dropped residual/output) is visible as
  an eqn whose results reach no output; only flop-bearing eqns are
  reported (dead converts/broadcasts are trace lint, not lost work).
- **pallas** — ``pallas_call`` kernel names (the call's ``name``),
  found through wrapper sub-jaxprs too (``shard_map``/``pjit`` descend
  explicitly in ``_subjaxprs`` — the multi-chip fused sweep's kernels
  live inside a ``shard_map`` body).
- **donation** — declared-donated leaves (``args_info.donated``)
  checked against the ``tf.aliasing_output`` / ``jax.buffer_donor``
  attributes of the lowered module's kept args: a declared donation
  the lowering dropped (DCE'd arg, no alias attr) is exactly the
  silent un-alias ``ir-donation-lost`` exists for.
- **cost** — per-eqn flops/bytes rows folded by :mod:`.cost`.
"""
from __future__ import annotations

import re

from .cost import cost_report, eqn_bytes, eqn_flops

__all__ = ["COLLECTIVE_SCOPE_PREFIX", "DELIBERATE_CAST_SCOPES",
           "collect_facts", "trace_program", "abstract_args"]

# the trainer's collective-site tag convention:
#   jax.named_scope("mx_coll:<kind>:b<bucket>")
COLLECTIVE_SCOPE_PREFIX = "mx_coll"
_COLL_RE = re.compile(r"mx_coll:([a-z_]+):b(-?\d+)")

# name-stack scopes marking a dtype cast as deliberate (codec decode,
# fp32-master loss cast) — ir-dtype-drift skips converts under them
DELIBERATE_CAST_SCOPES = ("mx_decode_fp32", "mx_master_fp32")

# explicit collective primitives (shard_map-style programs)
_COLLECTIVE_PRIMS = {
    "psum": "all_reduce", "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter", "ppermute": "ppermute",
    "all_to_all": "all_to_all",
}


def _subjaxprs(eqn):
    """``(jaxpr, scale, estimated)`` children of one eqn.  scan bodies
    multiply by trip count; while/cond bodies count once (estimate)."""
    import jax.extend
    name = eqn.primitive.name
    if name == "pallas_call":
        # the kernel body runs once per grid step; charging it flat
        # would miscount — the wrapper eqn itself is costed instead
        return []
    out = []
    if name == "scan":
        length = int(eqn.params.get("length", 1) or 1)
        out.append((eqn.params["jaxpr"], length, False))
        return out
    if name == "while":
        out.append((eqn.params["cond_jaxpr"], 1, True))
        out.append((eqn.params["body_jaxpr"], 1, True))
        return out
    if name == "cond":
        for br in eqn.params.get("branches", ()):
            out.append((br, 1, True))
        return out
    if name in ("shard_map", "pjit"):
        # explicit, not left to the generic fallback: the per-shard /
        # inner program is where shard_map-wrapped Pallas kernels live
        # (the fused optimizer sweep on a multi-chip mesh), and
        # ir-pallas-presence must see through the wrapper (shard_map
        # carries a Jaxpr, pjit a ClosedJaxpr)
        body = eqn.params.get("jaxpr")
        if body is not None:
            out.append((body, 1, False))
            return out
    for v in eqn.params.values():
        if isinstance(v, (jax.extend.core.ClosedJaxpr,
                          jax.extend.core.Jaxpr)):
            out.append((v, 1, False))
    return out


def _inner(jaxpr):
    import jax.extend
    return jaxpr.jaxpr if isinstance(jaxpr, jax.extend.core.ClosedJaxpr) \
        else jaxpr


def _body_flops(children):
    """Total flops of an eqn's sub-jaxprs (scan bodies scaled) — dead
    WRAPPER eqns are priced by the work their body wastes, not by
    their (often scalar) output element count."""
    total = 0
    for child, s, _est in children:
        jx = _inner(child)
        for e in jx.eqns:
            cc = _subjaxprs(e)
            total += (_body_flops(cc) if cc else eqn_flops(e)) * s
    return total


def _live_eqn_flags(jaxpr):
    """Per-eqn liveness at ONE jaxpr level: an eqn is live when any
    output (transitively) reaches the jaxpr outputs or it has
    effects."""
    live = set()
    for v in jaxpr.outvars:
        if hasattr(v, "count"):         # skip Literals
            live.add(v)
    flags = [False] * len(jaxpr.eqns)
    for i in range(len(jaxpr.eqns) - 1, -1, -1):
        eqn = jaxpr.eqns[i]
        is_live = bool(eqn.effects) or any(
            o in live for o in eqn.outvars)
        flags[i] = is_live
        if is_live:
            for v in eqn.invars:
                if hasattr(v, "count"):
                    live.add(v)
    return flags


def _aval_shape(v):
    aval = getattr(v, "aval", None)
    return tuple(int(s) for s in getattr(aval, "shape", ()) or ())


def _aval_dtype(v):
    aval = getattr(v, "aval", None)
    return str(getattr(aval, "dtype", ""))


def _sharding_axes(sharding):
    """Flatten a NamedSharding's PartitionSpec into the mesh-axis
    names it uses (tag sites with a replicated target report none)."""
    spec = getattr(sharding, "spec", None)
    axes = []
    for entry in (spec or ()):
        if entry is None:
            continue
        if isinstance(entry, (list, tuple)):
            axes.extend(str(a) for a in entry)
        else:
            axes.append(str(entry))
    return axes


def _elems(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def _user_site(eqn):
    """The user-code ``file:line`` an eqn traces to (jax-internal
    frames filtered), repo-relative when possible — dead eqns are
    aggregated per site so one dropped expression is one finding, not
    one per primitive it expanded into."""
    try:
        from jax._src import source_info_util
        fr = source_info_util.user_frame(eqn.source_info)
        if fr is not None:
            fname = str(fr.file_name).replace("\\", "/")
            if "/mxnet_tpu/" in fname:
                fname = "mxnet_tpu/" + fname.split("/mxnet_tpu/", 1)[1]
            else:
                fname = fname.rsplit("/", 1)[-1]
            return "%s:%d" % (fname, fr.start_line)
    except Exception:
        pass
    stack = str(eqn.source_info.name_stack)
    return stack or eqn.primitive.name


def collect_facts(closed_jaxpr, f64_allow=(), deliberate=None):
    """Walk a closed jaxpr (recursively) and return the pure-data fact
    dict trace_program folds into its report."""
    deliberate = tuple(deliberate if deliberate is not None
                       else DELIBERATE_CAST_SCOPES)
    f64_allow = tuple(f64_allow or ())
    facts = {"collectives": [], "pallas": [], "f64": [],
             "promotions": [], "dead": [], "cost_rows": []}
    seen_pallas = set()
    dead_sites = {}

    def visit(jaxpr, scale, estimated):
        jx = _inner(jaxpr)
        flags = _live_eqn_flags(jx)
        for eqn, live in zip(jx.eqns, flags):
            name = eqn.primitive.name
            stack = str(eqn.source_info.name_stack)
            flops = eqn_flops(eqn)
            children = _subjaxprs(eqn)
            if not children:
                # wrapper eqns (pjit/scan/while/cond/custom_vjp/...)
                # are priced by their recursed bodies; charging the
                # wrapper too would double-count every nested program
                facts["cost_rows"].append(
                    (name, flops, eqn_bytes(eqn), scale, estimated))

            if name == "pallas_call":
                # every in-tree pallas_call passes its kernel's name
                kernel = str(eqn.params["name"])
                if kernel not in seen_pallas:
                    seen_pallas.add(kernel)
                    facts["pallas"].append(kernel)

            if name in _COLLECTIVE_PRIMS:
                axes = eqn.params.get("axis_name",
                                      eqn.params.get("axes", ()))
                if not isinstance(axes, (list, tuple)):
                    axes = (axes,)
                facts["collectives"].append({
                    "kind": _COLLECTIVE_PRIMS[name],
                    "axes": [str(a) for a in axes], "bucket": None,
                    "elems": _elems(_aval_shape(eqn.invars[0])),
                    "dtype": _aval_dtype(eqn.invars[0]),
                    "site": stack or name})
            elif name == "sharding_constraint":
                m = _COLL_RE.search(stack)
                if m:
                    facts["collectives"].append({
                        "kind": m.group(1),
                        "axes": _sharding_axes(
                            eqn.params.get("sharding")),
                        "bucket": int(m.group(2)),
                        "elems": _elems(_aval_shape(eqn.outvars[0])),
                        "dtype": _aval_dtype(eqn.outvars[0]),
                        "site": stack})

            if name == "convert_element_type":
                src = _aval_dtype(eqn.invars[0])
                dst = _aval_dtype(eqn.outvars[0])
                if src == "bfloat16" and dst == "float32" \
                        and "transpose" not in stack \
                        and not any(s in stack for s in deliberate):
                    facts["promotions"].append({
                        "from": src, "to": dst,
                        "shape": list(_aval_shape(eqn.invars[0])),
                        "site": stack})

            for v in eqn.outvars:
                dt = _aval_dtype(v)
                if dt in ("float64", "complex128"):
                    where = stack or name
                    if not any(a and a in (where + " " + name)
                               for a in f64_allow):
                        facts["f64"].append({
                            "prim": name, "dtype": dt,
                            "shape": list(_aval_shape(v)),
                            "site": where})
                    break

            # dead detection DOES judge wrapper eqns: a dropped pjit's
            # body is locally live (it feeds the body's outputs), so
            # the deadness is only visible at the wrapper — priced by
            # the body's wasted work, not the wrapper's output size
            if not live and children:
                flops = _body_flops(children)
            if not live and flops > 0:
                site = _user_site(eqn)
                slot = dead_sites.get(site)
                if slot is None:
                    slot = dead_sites[site] = {
                        "site": site, "flops": 0, "eqns": 0,
                        "prims": [],
                        "shape": list(_aval_shape(eqn.outvars[0]))
                        if eqn.outvars else []}
                    facts["dead"].append(slot)
                slot["flops"] += int(flops * scale)
                slot["eqns"] += 1
                if name not in slot["prims"]:
                    slot["prims"].append(name)

            for child, s, est in children:
                visit(child, scale * s, estimated or est)

    visit(closed_jaxpr, 1, False)
    facts["pallas"].sort()
    return facts


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------
_MAIN_RE = re.compile(r"func\.func public @main\((.*?)\)\s*->", re.S)


def _aliased_positions(stablehlo_text):
    """Module-arg positions carrying an aliasing/donor attribute, or
    None when the signature cannot be parsed (skip, don't lie)."""
    m = _MAIN_RE.search(stablehlo_text)
    if m is None:
        return None
    out = set()
    for chunk in m.group(1).split("%arg")[1:]:
        try:
            pos = int(chunk.split(":", 1)[0])
        except ValueError:
            return None
        if "tf.aliasing_output" in chunk or "jax.buffer_donor" in chunk:
            out.add(pos)
    return out


def _donation_facts(traced, lowered):
    """Declared-vs-aliased ledger from the traced/lowered pair."""
    import jax
    flat, _tree = jax.tree_util.tree_flatten_with_path(traced.args_info)
    declared = [(i, jax.tree_util.keystr(path))
                for i, (path, info) in enumerate(flat)
                if getattr(info, "donated", False)]
    facts = {"declared": len(declared), "checked": False,
             "aliased": 0, "lost": []}
    if not declared:
        return facts
    try:
        kept = lowered._lowering.compile_args.get("kept_var_idx")
    except AttributeError:
        kept = None
    kept = sorted(kept) if kept is not None else list(range(len(flat)))
    aliased = _aliased_positions(lowered.as_text())
    if aliased is None:
        return facts
    facts["checked"] = True
    pos_of = {flat_idx: pos for pos, flat_idx in enumerate(kept)}
    for flat_idx, path in declared:
        pos = pos_of.get(flat_idx)
        if pos is None:
            facts["lost"].append({
                "path": path,
                "reason": "donated input pruned from the lowered "
                          "program (dead arg — nothing aliases it)"})
        elif pos not in aliased:
            facts["lost"].append({
                "path": path,
                "reason": "no aliasing attribute on the lowered "
                          "argument (lowering dropped the donation)"})
        else:
            facts["aliased"] += 1
    return facts


# ---------------------------------------------------------------------------
# program capture
# ---------------------------------------------------------------------------
def abstract_args(tree):
    """ShapeDtypeStruct mirror of a pytree of arrays, shardings kept
    (the step's in_shardings must resolve against them)."""
    import jax

    def one(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        sharding = getattr(leaf, "sharding", None)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=sharding)

    return jax.tree_util.tree_map(one, tree)


def trace_program(jit_fn, args, name, kind="program", origin="",
                  f64_allow=None, x64=True, kwargs=None):
    """Trace ``jit_fn(*args)`` abstractly and return the graftir
    report dict (pure data; see the module docstring for channels).

    ``f64_allow`` defaults from ``MXNET_IR_F64_ALLOWLIST``; lowering
    (for the donation ledger) only happens when donations are
    declared."""
    import contextlib

    import jax

    if f64_allow is None:
        from ... import config as _config
        raw = _config.get("MXNET_IR_F64_ALLOWLIST") or ""
        f64_allow = tuple(s.strip() for s in raw.split(",") if s.strip())
    ctx = jax.enable_x64(True) if x64 else contextlib.nullcontext()
    with ctx:
        traced = jit_fn.trace(*args, **(kwargs or {}))
        facts = collect_facts(traced.jaxpr, f64_allow=f64_allow)
        donation = {"declared": 0, "checked": False, "aliased": 0,
                    "lost": []}
        if any(getattr(info, "donated", False) for info in
               jax.tree_util.tree_leaves(traced.args_info)):
            import warnings
            with warnings.catch_warnings():
                # the donated-but-unused warning is exactly what the
                # ledger below reports as a finding
                warnings.simplefilter("ignore")
                donation = _donation_facts(traced, traced.lower())
    return {
        "name": str(name), "kind": str(kind), "origin": str(origin),
        "collectives": facts["collectives"],
        "pallas_found": facts["pallas"],
        "f64": facts["f64"],
        "promotions": facts["promotions"],
        "dead": facts["dead"],
        "donation": donation,
        "cost": cost_report(facts["cost_rows"]),
    }
