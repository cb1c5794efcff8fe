"""The step measured from inside (PR 25): the phase scopes in the two
step programs, ``telemetry.register_program`` / ``program_hlo``, and the
program's own step spans armed by ``telemetry.enable()``.

- ``phases.phase_of`` / ``instruction_phases`` on hand-written HLO,
  a metadata-less ``copy`` that inherits included;
- a tiny ``Module`` fused step and a tiny ``ParallelTrainer`` step carry
  ``jvp(mx_fwd)`` / ``transpose(jvp(mx_fwd))`` / ``mx_update/flatten`` /
  ``mx_update/unflatten`` through the compiler, and ``program_hlo``
  returns the text of the jit's own lowering;
- the scopes change no computation: with ``jax.named_scope`` patched to
  a null context the StableHLO text is the same;
- the fused step never re-lays a weight (PR 26): only rank-1 operands
  enter ``mx_update/flatten``, and an N-D weight reaches its output
  through elementwise ops alone;
- the ZeRO step never re-lays a native bucket (PR 30): only the flat
  tail enters ``mx_update/flatten`` / ``unflatten``, a matrix reaches
  its output through no layout op, and every sweep ``pallas_call``
  keeps its kernel's name under ``mx_update/sweep``;
- ``telemetry.enable()`` arms the spans, ``telemetry.disable()`` takes
  back only that.
"""
import contextlib
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, telemetry
from mxnet_tpu.telemetry import phases, tracing


@pytest.fixture(autouse=True)
def _clean():
    yield
    telemetry.disable()
    tracing.disable()
    tracing.reset()
    phases._PROGRAMS.clear()


# ---------------------------------------------------------------------------
# the two pure functions
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op_name, phase", [
    ("jit(fbu)/jvp(mx_fwd)/conv0/conv_general_dilated", "fwd"),
    ("jit(step)/jvp(mx_fwd)/mx_loss/reduce_sum", "fwd"),
    ("jit(fbu)/transpose(jvp(mx_fwd))/conv0/conv_general_dilated", "bwd"),
    ("jit(step)/transpose(jvp(mx_fwd))/mx_loss/mul;"
     "jit(step)/jvp(mx_fwd)/mx_loss/exp", "bwd"),
    ("jit(fbu)/mx_update/flatten/reshape", "update"),
    ("jit(step)/transpose(jvp(mx_update))/unflatten/pad", "update"),
    ("jit(fbu)/mx_codec/convert_element_type", "update"),
    ("jit(step)/mx_update/mx_coll:all_gather:b0/sharding_constraint",
     "collective"),
    ("jit(step)/transpose(jvp(mx_fwd))/mx_coll:reduce_scatter:b1/x",
     "collective"),
    ("jit(fbu)/jit(_threefry_fold_in)/xor", "other"),
    ("w", "other"),
    ("", "other"),
    (None, "other"),
])
def test_phase_of(op_name, phase):
    assert phases.phase_of(op_name) == phase


HLO = """HloModule jit_fbu, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%param_0), metadata={op_name="jit(fbu)/transpose(jvp(mx_fwd))/neg" stack_frame_id=3}
}

ENTRY %main.9 (w.1: f32[4], x.1: f32[4]) -> (f32[4], f32[]) {
  %w.1 = f32[4]{0} parameter(0), metadata={op_name="w"}
  %x.1 = f32[4]{0} parameter(1), metadata={op_name="x"}
  %copy-start.1 = (f32[4]{0:S(1)}, f32[4]{0}, u32[]) copy-start(%w.1)
  %copy-done.1 = f32[4]{0:S(1)} copy-done(%copy-start.1)
  %copy.0 = f32[4]{0} copy(%x.1), metadata={op_name="x"}
  %fusion.1 = f32[4]{0} fusion(%copy-done.1, %copy.0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(fbu)/jvp(mx_fwd)/mul" stack_frame_id=1}, backend_config={"x":{"y":"%not_an_operand"}}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(fbu)/transpose(jvp(mx_fwd))/neg" stack_frame_id=3}
  %copy.3 = f32[4]{0} copy(%fusion.2)
  %reshape.4 = f32[4]{0} reshape(%copy.3), metadata={op_name="jit(fbu)/mx_update/flatten/reshape"}
  %_sgd_mom_kernel.5 = f32[4]{0} custom-call(%reshape.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(fbu)/mx_update/sweep/pallas_call"}
  %copy.6 = f32[4]{0} copy(%_sgd_mom_kernel.5)
  %xor.7 = u32[] constant(1), metadata={op_name="jit(fbu)/xor"}
  %bitcast.8 = u32[] bitcast(%xor.7)
  ROOT %tuple.9 = (f32[4]{0}, u32[]) tuple(%copy.6, %bitcast.8)
}
"""


def test_instruction_phases_on_a_handwritten_module():
    got = phases.instruction_phases(HLO)
    assert got["fusion.1"] == "fwd" and got["fusion.2"] == "bwd"
    assert got["reshape.4"] == "update"
    assert got["_sgd_mom_kernel.5"] == "update"
    # a parameter's op_name names no scope and hands nothing on ...
    assert got["w.1"] == "other" and got["xor.7"] == "other"
    # ... so the prefetch of the weights takes the phase of what it feeds
    assert got["copy-start.1"] == "fwd" and got["copy-done.1"] == "fwd"
    # as does a layout change that carries its argument's name
    assert got["x.1"] == "other" and got["copy.0"] == "fwd"
    # a metadata-less copy takes the phase of what it reads
    assert got["copy.3"] == "bwd" and got["copy.6"] == "update"
    # nothing with a phase on either side: unattributed
    assert got["bitcast.8"] == "other"
    assert got["neg.1"] == "bwd"            # fused computations too
    assert "not_an_operand" not in got and "fused_computation" not in got


LOOP_HLO = """HloModule jit_step, is_scheduled=true

%body.1 (arg.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%arg.1), index=1
  %mul.1 = f32[4]{0} multiply(%gte.1, %gte.1), metadata={op_name="jit(step)/jvp(mx_fwd)/while/body/mx_loop/mul"}
  %exp.1 = f32[4]{0} exponential(%mul.1), metadata={op_name="jit(step)/jvp(mx_fwd)/while/body/mx_exit/exp"}
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%gte.1, %exp.1)
}

%cond.1 (arg.2: (s32[], f32[4])) -> pred[] {
  %arg.2 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.2 (w.1: f32[4]) -> f32[4] {
  %w.1 = f32[4]{0} parameter(0), metadata={op_name="w"}
  %copy-start.1 = (f32[4]{0:S(1)}, f32[4]{0}, u32[]) copy-start(%w.1)
  %copy-done.1 = f32[4]{0:S(1)} copy-done(%copy-start.1)
  %tuple.2 = (s32[], f32[4]{0:S(1)}) tuple(%copy-done.1)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.2), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/jvp(mx_fwd)/mx_loop/while"}
  %gte.2 = f32[4]{0} get-tuple-element(%while.1), index=1
  %gte.3 = s32[] get-tuple-element(%while.1), index=0
  %call.1 = f32[4]{0} call(%gte.2), to_apply=%elsewhere.1, metadata={op_name="jit(step)/transpose(jvp(mx_fwd))/mx_loop/checkpoint/rematted_computation/call"}
  %call.2 = f32[4]{0} call(%call.1), to_apply=%elsewhere.2
  ROOT %neg.2 = f32[4]{0} negate(%call.2), metadata={op_name="jit(step)/mx_update/neg"}
}
"""


def test_a_control_instruction_is_control_only_over_a_body_in_the_text():
    got = phases.instruction_phases(LOOP_HLO)
    # the loop's event spans its body's, which carry their own phases
    assert got["while.1"] == "control"
    assert got["mul.1"] == "fwd" and got["exp.1"] == "fwd"
    # what only the loop names takes the class of the loop's own name;
    # another neighbour's goes first
    assert got["gte.3"] == "fwd" and got["gte.2"] == "bwd"
    # no body in the text, so no events under it: its time counts once,
    # for the phase of its own name ...
    assert got["call.1"] == "bwd"
    # ... and unnamed it inherits like a copy: from what it reads, else
    # from what it feeds, else it is unattributed, where the guard sees it
    assert got["call.2"] == "bwd"
    bare = LOOP_HLO.replace("(%call.1), to_apply", "(%w.1), to_apply")
    assert phases.instruction_phases(bare)["call.2"] == "update"
    bare = bare.replace("negate(%call.2)", "negate(%w.1)")
    assert phases.instruction_phases(bare)["call.2"] == "other"
    parts = phases.instruction_loop_parts(LOOP_HLO)
    assert parts["while.1"] == (None, False)        # its body's events count
    assert parts["mul.1"] == ("loop", False)
    assert parts["exp.1"] == ("exit", False)
    assert parts["call.1"] == ("loop", True)
    assert parts["neg.2"] == (None, False)


# ---------------------------------------------------------------------------
# the two step programs
# ---------------------------------------------------------------------------
def _toy_module(monkeypatch, optimizer="sgd", optimizer_params=None):
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")   # the sweep's path
    data = mx.sym.var("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                             name="c1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(mx.sym.Pooling(net, global_pool=True,
                                        pool_type="avg", kernel=(1, 1)))
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    x = np.random.rand(16, 1, 8, 8).astype(np.float32)
    y = np.random.randint(0, 10, (16,)).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer=optimizer,
                       optimizer_params=optimizer_params or
                       {"learning_rate": 0.1, "momentum": 0.9})
    assert mod._exec_group.execs[0]._sweep is not None
    return mod, next(iter(it))


def _toy_trainer(monkeypatch):
    import jax
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, in_units=8, activation="relu"),
            gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh(dp=2, devices=jax.devices()[:2])
    return parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh, zero=2,
        bucket_bytes=4096)


def _module_step(monkeypatch):
    mod, _batch = _toy_module(monkeypatch)
    return mod._exec_group.execs[0].step_callable("fused"), None


def _trainer_step(monkeypatch):
    tr = _toy_trainer(monkeypatch)
    return tr.step_callable((8, 8)), tr


STEPS = pytest.mark.parametrize("build", [_module_step, _trainer_step],
                                ids=["module_fused", "parallel_trainer"])


@STEPS
def test_step_program_carries_the_phase_scopes(build, monkeypatch):
    (jit_fn, args), tr = build(monkeypatch)
    with parallel.mesh.mesh_scope(tr.mesh) if tr is not None \
            else contextlib.nullcontext():
        lowered = jit_fn.lower(*args)
        text = lowered.compile().as_text()
    # what the program hands the compiler ...
    handed = lowered.as_text(debug_info=True)
    for scope in ("jvp(mx_fwd)", "transpose(jvp(mx_fwd))",
                  "mx_update/flatten", "mx_update/unflatten",
                  "mx_update/sweep"):
        assert scope in handed, scope
    if tr is not None:
        assert "mx_fwd)/mx_loss" in handed
    # ... and what comes out of it (the CPU compiler folds this toy's
    # flatten into bitcasts; the TPU's keeps it: perfbench/testdata)
    for scope in ("jvp(mx_fwd)", "transpose(jvp(mx_fwd))", "mx_update/"):
        assert scope in text, scope
    found = set(phases.instruction_phases(text).values())
    assert {"fwd", "bwd", "update"} <= found


@STEPS
def test_scopes_change_no_computation(build, monkeypatch):
    """Metadata only: the StableHLO without debug info is the same text
    with ``jax.named_scope`` patched to a null context."""
    import jax

    def stablehlo():
        (jit_fn, args), tr = build(monkeypatch)
        with parallel.mesh.mesh_scope(tr.mesh) if tr is not None \
                else contextlib.nullcontext():
            text = jit_fn.lower(*args).as_text()
        # gluon numbers its blocks process-wide: dense2_weight, dense4_...
        return re.sub(r"dense\d+_", "dense_", text)

    scoped = stablehlo()
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope",
                  lambda name: contextlib.nullcontext())
        bare = stablehlo()
    assert "mx_fwd" not in scoped       # debug info is not in this text
    assert scoped == bare


_OP = re.compile(r"^\s*(%\w+)(?::\d+)? = \"?([\w.]+)\"?(.*) loc\((#loc\d+)\)$")
_LAYOUT_OPS = {"reshape", "concatenate", "slice", "dynamic_slice", "pad",
               "transpose", "gather", "dynamic_update_slice"}


def _main_ops(handed):
    """``{ssa: (op, operands, operand ranks, scope)}`` of @main in a
    StableHLO text with debug info, and the values it returns."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', handed, re.M))
    body = handed[handed.index("func.func public @main"):]
    ops, returned = {}, None
    for ln in body.splitlines():
        if ln.startswith("    return "):
            returned = re.findall(r"%\w+", ln.split(" : ")[0])
            break
        m = _OP.match(ln)
        if m is None:
            continue
        ssa, op, rest, loc = m.groups()
        sig = rest.rsplit(" : ", 1)
        types = sig[1].split(" -> ")[0] if len(sig) == 2 else ""
        ranks = [t.count("x") for t in re.findall(r"tensor<([^>]*)>", types)]
        ops[ssa] = (op.split(".")[-1], re.findall(r"%\w+", sig[0]), ranks,
                    names.get(loc, ""))
    return ops, returned


@pytest.mark.parametrize("optimizer, optimizer_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_fused_step_never_relays_a_weight(monkeypatch, optimizer,
                                          optimizer_params):
    """What the chip gain of PR 26 rests on, read off the program handed
    to the compiler: flattening is a concatenation of rank-1 leaves, and
    the conv and dense weights go from argument to output through
    elementwise ops in their own shape."""
    mod, _batch = _toy_module(monkeypatch, optimizer, optimizer_params)
    exe = mod._exec_group.execs[0]
    jit_fn, args = exe.step_callable("fused")
    ops, returned = _main_ops(jit_fn.lower(*args).as_text(debug_info=True))
    flat = [v for v in ops.values() if "/mx_update/flatten/" in v[3]]
    assert len(flat) >= 2   # weights, gradients (a one-leaf bucket has none)
    for op, _operands, ranks, _scope in flat:
        assert op == "concatenate" and set(ranks) == {1}, (op, ranks)
    shapes = [exe.arg_dict[exe.arg_names[i]].shape for i in exe._diff_idx]
    nd = [j for j, shape in enumerate(shapes) if len(shape) > 1]
    assert nd == exe._sweep["rest"] and len(nd) == 2
    for j in nd:
        # outputs are (outs, new_diff, ...): the toy has one output
        seen, todo, reached = set(), [returned[1 + j]], False
        while todo:
            v = todo.pop()
            if v == "%%arg%d" % j:
                reached = True
            if v in seen or v not in ops or "/mx_update/" not in ops[v][3]:
                continue
            seen.add(v)
            assert ops[v][0] not in _LAYOUT_OPS or max(ops[v][2]) <= 1, \
                (exe.arg_names[exe._diff_idx[j]], ops[v])
            todo.extend(ops[v][1])
        assert reached and len(seen) >= 3, (j, seen)


def _toy_lm_trainer(monkeypatch, optimizer, ndev=1):
    import jax
    from mxnet_tpu.gluon.contrib.transformer import TransformerLM
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")
    lm = TransformerLM(vocab_size=256, units=128, hidden_size=256,
                       num_layers=1, num_heads=2, max_len=16, dropout=0.0)
    lm.initialize(mx.init.Xavier())
    lm(mx.nd.zeros((2, 16)))            # materialise the deferred shapes
    opt = {"learning_rate": 0.01}
    if optimizer == "sgd":
        opt["momentum"] = 0.9
    return parallel.ParallelTrainer(
        lm, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer, opt,
        mesh=parallel.make_mesh(dp=ndev, devices=jax.devices()[:ndev]),
        zero=2, bucket_bytes=2048, first_bucket_bytes=1024)


def _pallas_calls(jaxpr, stack=""):
    """``[(kernel name, name stack)]`` of every ``pallas_call`` in a
    jaxpr, through pjit / shard_map / custom_vjp bodies."""
    import jax
    found = []
    for eqn in jaxpr.eqns:
        here = stack + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], here))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub, here)
    return found


@pytest.mark.parametrize("ndev", [1, 2])
@pytest.mark.parametrize("optimizer, kernel", [("adam", "_adam_kernel"),
                                               ("sgd", "_sgd_mom_kernel")])
def test_zero_step_never_relays_a_native_bucket(monkeypatch, optimizer,
                                                kernel, ndev):
    """What the chip gain of PR 30 rests on, read off the program handed
    to the compiler for a small LM: nothing under the ``flatten`` /
    ``unflatten`` scopes touches a native bucket (only the flat tail's
    instructions remain there), every matrix goes from argument to
    output through no layout op at all, and the sweep is one
    ``pallas_call`` a bucket, under ``mx_update/sweep`` and under its
    kernel's own name."""
    tr = _toy_lm_trainer(monkeypatch, optimizer, ndev)
    plan = tr.bucket_plan
    native = [b for b in plan if b.layout == "native"]
    flat = [b for b in plan if b.layout == "flat"]
    # every matrix keeps its layout; LayerNorm vectors and biases ride flat
    assert sorted(n for b in native for n in b.names) == sorted(
        n for n, v in tr.params.items() if v.ndim == 2)
    assert flat and all(len(s) == 1 for b in flat for s in b.shapes)
    jit_fn, args = tr.step_callable((2, 16), (2, 16))
    with parallel.mesh.mesh_scope(tr.mesh):
        traced = jit_fn.trace(*args)
        ops, returned = _main_ops(traced.lower().as_text(debug_info=True))
    scoped = [v for v in ops.values()
              if "/flatten/" in v[3] or "/unflatten/" in v[3]]
    assert scoped and all("mx_update" in v[3] for v in scoped)
    assert all(max(ranks, default=0) <= 1 for _op, _in, ranks, _s in scoped)
    # count them: a flat bucket cuts each leaf back out twice (for the
    # forward, and after the sweep) by a slice, unless the leaf is the
    # whole buffer — with the matrices native, the tail's leaves only
    cut = sum(1 for b in flat for sz in b.sizes if sz != b.padded_n)
    assert cut >= 4
    assert sum(op == "slice" for op, _in, _ranks, _s in scoped) == 2 * cut
    # each matrix: from its argument to its output through the update,
    # no layout op between (the sweep's call is one equation here)
    jaxpr = traced.jaxpr.jaxpr
    made_by = {v: e for e in jaxpr.eqns for v in e.outvars}
    names = sorted(tr.params)       # a dict flattens in key order
    for b in native:
        j = names.index(b.names[0])
        seen, todo, reached = set(), [jaxpr.outvars[j]], False
        while todo:
            v = todo.pop()
            reached = reached or v is jaxpr.invars[j]
            eqn = made_by.get(v)
            if eqn is None or id(eqn) in seen \
                    or "mx_update" not in str(eqn.source_info.name_stack):
                continue
            seen.add(id(eqn))
            assert eqn.primitive.name not in _LAYOUT_OPS | {
                "squeeze", "expand_dims", "broadcast_in_dim"}, \
                (b.names[0], eqn)
            # the matrices' path (the hyperparameter vector is stacked)
            todo.extend(x for x in eqn.invars
                        if getattr(getattr(x, "aval", None), "ndim", 0) > 1)
        assert reached and seen, b.names[0]
    calls = _pallas_calls(traced.jaxpr.jaxpr)
    sweeps = [c for c in calls if "kernel" in c[0] and "sweep" in c[1]]
    assert [c[0] for c in sweeps] == [kernel] * len(plan)
    assert all("mx_update/sweep" in c[1] for c in sweeps)
    assert not [c for c in calls if c[0] in (
        "_adam_kernel", "_sgd_mom_kernel", "_sgd_kernel")
        and c not in sweeps]


def _instruction_lines(text):
    return [ln.split(", metadata=")[0] for ln in text.splitlines()
            if " = " in ln]


def test_program_hlo_is_the_fused_steps_own_lowering(monkeypatch):
    mod, batch = _toy_module(monkeypatch)
    mod.forward_backward(batch)         # telemetry off: nothing registered
    assert telemetry.program_hlo("fbu") is None
    telemetry.enable()
    for _ in range(2):
        mod.forward_backward(batch)
        mod.update()
    exe = mod._exec_group.execs[0]
    ent = phases._PROGRAMS["fbu"]
    assert ent[0] is exe._jit_fbu and ent[3] is None    # nothing compiled
    import jax
    assert not any(isinstance(leaf, jax.Array) and not
                   isinstance(leaf, jax.core.Tracer) and leaf.size > 8
                   for leaf in jax.tree_util.tree_leaves(ent[1]))
    text = telemetry.program_hlo("fbu")
    assert telemetry.program_hlo("fbu") is text         # memoised
    assert ent[0] is None       # and the program is let go of
    jit_fn, args = exe.step_callable("fused")
    own = jit_fn.lower(*args).compile().as_text()
    assert _instruction_lines(text) == _instruction_lines(own)
    assert text.startswith("HloModule jit_fbu")


def test_program_hlo_is_the_trainers_own_lowering(monkeypatch):
    tr = _toy_trainer(monkeypatch)
    telemetry.enable()
    x = mx.nd.array(np.random.rand(8, 8).astype(np.float32))
    y = mx.nd.array(np.random.randint(0, 4, (8,)).astype(np.float32))
    for _ in range(2):
        tr.step(x, y)
    assert phases._PROGRAMS["step"][3] is None
    text = telemetry.program_hlo("step")
    jit_fn, args = tr.step_callable((8, 8))
    with parallel.mesh.mesh_scope(tr.mesh):
        own = jit_fn.lower(*args).compile().as_text()
    assert _instruction_lines(text) == _instruction_lines(own)
    assert text.startswith("HloModule jit_step")
    # the newest registration of a name replaces the last
    tr2 = _toy_trainer(monkeypatch)
    tr2.step(x, y)
    assert phases._PROGRAMS["step"][0] is tr2._jit_step
    assert phases.program_names() == ["step"]


# ---------------------------------------------------------------------------
# the program's own step spans
# ---------------------------------------------------------------------------
def _nested(child, parent):
    return parent["t0_ns"] <= child["t0_ns"] and \
        child["t0_ns"] + child["dur_ms"] * 1e6 \
        <= parent["t0_ns"] + parent["dur_ms"] * 1e6 + 1e3


def test_enable_arms_the_module_spans_and_disable_takes_them_back(
        monkeypatch):
    mod, batch = _toy_module(monkeypatch)
    metric = mx.metric.create("acc")
    assert tracing.span("off") is tracing._NOOP
    telemetry.enable()
    assert tracing.ACTIVE[0]
    for _ in range(3):
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(metric, batch.label)
    telemetry.disable()
    assert tracing.span("off") is tracing._NOOP
    mod.forward_backward(batch)         # records nothing
    recs = tracing.snapshot()
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    assert len(by_name["module.forward_backward"]) == 3
    assert len(by_name["module.update_metric"]) == 3
    assert "module.update" not in by_name   # the fused step already did it
    t0s = [r["t0_ns"] for r in by_name["module.forward_backward"]]
    assert t0s == sorted(t0s) and len(set(t0s)) == 3
    for name in ("executor.hyper", "executor.dispatch"):
        for child, parent in zip(by_name[name],
                                 by_name["module.forward_backward"]):
            assert child["parent"] == parent["span"]
            assert _nested(child, parent)
    # one clock for the chrome dump: perf_counter microseconds
    ev = tracing.chrome_events()[0]
    assert ev["ts"] == recs[0]["t0_ns"] / 1e3


def test_enable_arms_the_trainer_spans(monkeypatch):
    tr = _toy_trainer(monkeypatch)
    x = mx.nd.array(np.random.rand(8, 8).astype(np.float32))
    y = mx.nd.array(np.random.randint(0, 4, (8,)).astype(np.float32))
    telemetry.enable()
    tr.step(x, y)
    tr.step(x, y)
    telemetry.disable()
    recs = tracing.snapshot()
    steps = [r for r in recs if r["name"] == "trainer.step"]
    inner = [r for r in recs if r["name"] == "trainer.dispatch"]
    assert len(steps) == len(inner) == 2
    assert steps[0]["t0_ns"] < steps[1]["t0_ns"]
    for child, parent in zip(inner, steps):
        assert child["parent"] == parent["span"] and _nested(child, parent)


def test_fit_step_is_the_parent_of_the_module_spans(monkeypatch):
    mod, _batch = _toy_module(monkeypatch)
    x = np.random.rand(32, 1, 8, 8).astype(np.float32)
    y = np.random.randint(0, 10, (32,)).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=16, label_name="softmax_label")
    telemetry.enable()
    mod.fit(it, num_epoch=1, kvstore="tpu", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    telemetry.disable()
    recs = tracing.snapshot()
    fit = [r for r in recs if r["name"] == "fit.step"]
    assert len(fit) == 2
    assert len([r for r in recs if r["name"] == "fit.data_wait"]) == 2
    for name in ("module.forward_backward", "module.update_metric"):
        kids = [r for r in recs if r["name"] == name]
        assert [k["parent"] for k in kids] == [f["span"] for f in fit]


def test_an_explicitly_traced_process_stays_armed():
    tracing.enable(sample=1.0, trace_dir=None)
    telemetry.enable()
    telemetry.disable()
    assert tracing.ACTIVE[0] and tracing.span("x") is not tracing._NOOP
    tracing.disable()
    # and the other order: enable() after telemetry armed owns the switch
    telemetry.enable()
    tracing.enable(sample=1.0, trace_dir=None)
    telemetry.disable()
    assert tracing.ACTIVE[0]
