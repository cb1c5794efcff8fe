"""The in-tree program catalog graftir traces — what ``tools/lint.py
--ir`` and the tier-1 gate actually verify.

One traced program per claim the tree makes: every trainer
configuration of ``plan/configs.py`` (the step program, donations and
collectives included), the bound convnet program (train and fused-step
forms), and the serving warmup ladder (one eval program per rung).
Each report pairs the IR-extracted facts with the plan model's
expectations:

- ``schedule_expect`` — ``plan/schedule.py``'s static collective
  schedule, canonicalized to a ``(kind, axes, bytes)`` multiset;
- ``schedule_actual`` — the SAME multiset derived from the traced
  jaxpr: explicitly tagged collective sites (``mx_coll:*`` scopes, see
  ``trace.py``) for the ZeRO paths, plus the GSPMD-implied per-bucket
  all-reduces of the zero-0 path, which are only credited when the IR
  shows their preconditions (batch input actually sharded over the
  mesh, params replicated) — un-shard the batch and the implied
  entries vanish, so the mismatch fires;
- ``pallas`` — kernels found in the jaxpr vs the expectation each
  ``MXNET_PALLAS_*`` knob + program structure resolves to.

Like ``plan/configs.py`` this module instantiates live objects (jax +
the virtual mesh required); everything it RETURNS is pure data, so the
``ir-*`` checkers and their seeded-misconfiguration tests run with
``jax.jit`` poisoned.  Nothing here compiles or dispatches — tracing
and lowering only.
"""
from __future__ import annotations

__all__ = ["catalog_reports", "schedule_multiset", "actual_multiset",
           "pallas_families", "family_expectations", "finish_report"]

# knob -> (family, kernel basenames as they appear in pallas_call's
# name_and_src_info).  flash attention has its own impl= gate and no
# tri-state knob, so it is not judged here.
PALLAS_FAMILIES = {
    "MXNET_PALLAS_FUSED_OPT": (
        "fused-opt", ("_sgd_kernel", "_sgd_mom_kernel", "_adam_kernel")),
    "MXNET_PALLAS_NORM": (
        "norm", ("_layernorm_fwd_kernel", "_layernorm_bwd_kernel")),
    "MXNET_PALLAS_SOFTMAX": (
        "softmax", ("_softmax_fwd_kernel", "_softmax_bias_fwd_kernel",
                    "_softmax_bwd_kernel")),
    "MXNET_PALLAS_BN_RELU": ("bn-relu", ("_scale_bias_relu_kernel",)),
}

_DATA_SHAPE = (16, 3, 8, 8)      # catalog net input; 16 divides dp8


def pallas_families():
    return dict(PALLAS_FAMILIES)


# ---------------------------------------------------------------------------
# schedule multisets
# ---------------------------------------------------------------------------
def schedule_multiset(spec):
    """plan/schedule.py's prediction as a sorted ``(kind, axes,
    bytes)`` multiset — the ir-collective-schedule reference side."""
    from ..plan.schedule import build_schedule
    return sorted((e["kind"], tuple(e["axes"]), int(e["bytes"]))
                  for e in build_schedule(spec))


def actual_multiset(report, spec):
    """The traced program's collective multiset, in the same
    canonical form.  Tagged sites carry kind/bucket/element counts out
    of the jaxpr; wire bytes are recomputed with the SAME codec + ring
    model the schedule uses (``plan/schedule.py``), so equality means
    "the collectives in the program match the plan", not "two copies
    of one formula agree about nothing".  A site's ``elems`` is the
    constrained buffer's element count whatever its rank: a native
    bucket's ``(rows, C)`` counts as its flat form would."""
    from ..plan.schedule import (codec_wire_bytes, ring_all_reduce_bytes,
                                 ring_shard_bytes)
    mesh = spec.mesh
    n = mesh.size if mesh is not None else 1
    mesh_axes = tuple(mesh.names) if mesh is not None else ()
    out = []
    for c in report.get("collectives", ()):
        kind = c["kind"]
        elems = int(c["elems"])
        axes = tuple(c.get("axes") or ()) or mesh_axes
        if kind == "all_gather":
            nbytes = ring_shard_bytes(4 * elems, n)
        elif kind == "reduce_scatter":
            nbytes = ring_shard_bytes(
                codec_wire_bytes(spec.codec, elems), n)
        elif kind == "all_reduce":
            nbytes = ring_all_reduce_bytes(
                codec_wire_bytes(spec.codec, elems), n)
        else:                      # ppermute/all_to_all: payload bytes
            nbytes = elems * 4
        out.append((kind, axes, int(nbytes)))
    # zero-0 bucket reductions are GSPMD-inserted at compile time, not
    # jaxpr eqns; credit them only when the IR shows the preconditions
    # that force them
    if (spec.kind == "trainer" and spec.zero == 0
            and report.get("batch_sharded")
            and report.get("params_replicated", True)):
        for b in spec.buckets:
            wire = codec_wire_bytes(spec.codec, int(b["padded_n"]))
            out.append(("all_reduce", mesh_axes,
                        ring_all_reduce_bytes(wire, n)))
        from ..plan.schedule import _sharded_pairs
        for local, repl in _sharded_pairs(spec):
            if repl > 1:
                out.append(("all_reduce", ("dp",),
                            ring_all_reduce_bytes(local, repl)))
    return sorted(out)


# ---------------------------------------------------------------------------
# pallas expectations
# ---------------------------------------------------------------------------
def family_expectations(spec=None, graph_ops=(), fused_sweep=None):
    """``{knob: {"family", "kernels", "enabled", "expected"}}`` for one
    program.  ``expected`` True = the kernels MUST be in the trace,
    False = MUST NOT, None = presence optional (but still forbidden
    when the family is disabled)."""
    from ...ops.pallas_kernels import family_enabled
    ops = set(graph_ops or ())
    out = {}
    for knob, (family, kernels) in PALLAS_FAMILIES.items():
        enabled = bool(family_enabled(knob))
        expected = None
        if knob == "MXNET_PALLAS_FUSED_OPT":
            if fused_sweep is not None:
                expected = bool(fused_sweep) and enabled
            elif spec is not None and spec.kind == "trainer":
                expected = bool(spec.optimizer.get("fused_sweep"))
        elif knob == "MXNET_PALLAS_SOFTMAX":
            if ops:
                expected = enabled and bool(
                    ops & {"SoftmaxOutput", "Softmax"})
        elif knob == "MXNET_PALLAS_NORM":
            if ops:
                expected = enabled and "LayerNorm" in ops
        # bn-relu's eval peephole has bind-time structure conditions
        # the graph op-set alone cannot decide — judged only in the
        # forbidden-when-off direction
        out[knob] = {"family": family, "kernels": list(kernels),
                     "enabled": enabled, "expected": expected}
    return out


def _graph_ops(spec):
    graph = getattr(spec, "graph", None)
    if not graph:
        return set()
    return {n.get("op") for n in graph.get("nodes", ())
            if n.get("op") and n.get("op") != "null"}


def finish_report(report, spec, pallas_expect, batch_sharded=None,
                  params_replicated=True):
    """Attach the plan-side expectations to a raw trace report (kept
    separate so fixture tests can build reports as pure data)."""
    if batch_sharded is not None:
        report["batch_sharded"] = bool(batch_sharded)
    report["params_replicated"] = bool(params_replicated)
    report["schedule_expect"] = schedule_multiset(spec)
    report["schedule_actual"] = actual_multiset(report, spec)
    report["pallas"] = {"found": list(report.pop("pallas_found", ())),
                        "families": pallas_expect}
    return report


# ---------------------------------------------------------------------------
# live capture
# ---------------------------------------------------------------------------
def _batch_axes(sds):
    from .trace import _sharding_axes
    return _sharding_axes(getattr(sds, "sharding", None))


def trainer_report(trainer, spec, data_shape=_DATA_SHAPE,
                   label_shape=None):
    """Trace one live ParallelTrainer's compiled step abstractly."""
    from .trace import trace_program
    jit_fn, args = trainer.step_callable(data_shape=data_shape,
                                         label_shape=label_shape)
    report = trace_program(jit_fn, args, name="ir:%s" % spec.name,
                           kind="trainer", origin=spec.origin)
    x = args[3]
    batch_sharded = bool(set(_batch_axes(x))
                         & set(spec.mesh.names if spec.mesh else ()))
    replicated = all(not any(p.get("spec") or ())
                     for p in spec.params if p.get("trainable", True))
    return finish_report(
        report, spec, family_expectations(spec=spec),
        batch_sharded=batch_sharded, params_replicated=replicated)


def program_report(exe, spec, mode="train", name=None):
    """Trace a bound Executor program (train fwd+bwd, eval, or the
    donated fused step)."""
    from .trace import trace_program
    jit_fn, args = exe.step_callable(mode=mode)
    fused_sweep = (getattr(exe, "_sweep", None) is not None
                   if mode == "fused" else False)
    report = trace_program(
        jit_fn, args, name=name or "ir:%s/%s" % (spec.name, mode),
        kind=spec.kind, origin=spec.origin)
    return finish_report(
        report, spec,
        family_expectations(spec=spec, graph_ops=_graph_ops(spec),
                            fused_sweep=fused_sweep))


def _ladder_reports(spec):
    """One eval program per serving-ladder rung: the shape-bucketed
    executors a warmed replica actually serves, traced like any other
    program (cost per rung; pallas families judged in eval mode)."""
    from ..plan.configs import convnet_symbol
    from ..plan.spec import PlanSpec
    from .trace import trace_program
    reports = []
    sym = convnet_symbol()
    for rung in spec.ladder or ():
        exe = sym.simple_bind(grad_req="null",
                              data=(int(rung), 3, 16, 16))
        rung_spec = PlanSpec.from_executor(
            exe, name="%s/b%d" % (spec.name, int(rung)))
        rung_spec.origin = spec.origin
        jit_fn, args = exe.step_callable(mode="eval")
        report = trace_program(
            jit_fn, args, name="ir:%s/b%d" % (spec.name, int(rung)),
            kind="serving", origin=spec.origin)
        reports.append(finish_report(
            report, rung_spec,
            family_expectations(spec=rung_spec,
                                graph_ops=_graph_ops(rung_spec))))
    return reports


def _fused_step_report():
    """The executor fused train step (fwd+bwd+optimizer, donated) —
    the program behind kvstore=tpu and the bench hot path: donation
    aliasing and the one-sweep Pallas expectation both live here."""
    from ... import optimizer as opt_mod
    from ..plan.configs import convnet_symbol
    from ..plan.spec import PlanSpec
    sym = convnet_symbol()
    exe = sym.simple_bind(data=(8, 3, 16, 16))
    opt = opt_mod.SGD(learning_rate=0.1, momentum=0.9)
    installed = exe.install_fused_update(opt)
    spec = PlanSpec.from_executor(exe, name="program/convnet-fused")
    if not installed:               # pragma: no cover - SGD always fuses
        return program_report(exe, spec, mode="train",
                              name="ir:program/convnet-fused")
    return program_report(exe, spec, mode="fused",
                          name="ir:program/convnet-fused")


def catalog_reports(width=None, live_configs=None):
    """Trace the whole in-tree catalog; returns pure-data reports.
    ``live_configs`` reuses a caller's ``in_tree_live`` result (the
    ``--all`` mode builds the live catalog ONCE for both legs)."""
    from ..plan.configs import in_tree_live
    reports = []
    if live_configs is None:
        live_configs = in_tree_live(width=width)
    for spec, _measured, live in live_configs:
        if spec.kind == "trainer":
            reports.append(trainer_report(live, spec))
        elif spec.kind == "program":
            reports.append(program_report(live, spec, mode="train"))
        elif spec.kind == "serving":
            reports.extend(_ladder_reports(spec))
    reports.append(_fused_step_report())
    return reports
