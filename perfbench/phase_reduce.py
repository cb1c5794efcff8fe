"""The step from inside: device time by phase of the step program, and
the program's own host spans.

The device events of the xplane (``XLA Ops``) carry no scope, only the
name of their HLO instruction; the optimized module's text carries, on
that instruction, the ``jax.named_scope``s the program wrapped its parts
in (``mxnet_tpu/telemetry/phases.py``: ``mx_fwd``, ``mx_update`` ...).
:func:`join` is the one walk between the two — event -> the program it
ran in (the ``XLA Modules`` event around it) -> leading ``%instruction``
-> its class in one of the program's maps, handed in with the key the
result is memoised under.  :func:`phases` sums it under
``instruction_phases`` for the metric files ``fwd_ms``, ``bwd_ms``,
``update_ms`` and ``phase_unattributed_share``; ``loop_reduce.parts``
under ``instruction_loop_parts``; a reader of a scope a later PR adds is
that PR's map from the program and a metric file that sums
``join(ctx, map, key)`` over the class it wants.  ``step_host_ms`` reads
the ring of the program's spans (``tracing.snapshot()``), whose
``t0_ns`` is on ``time.perf_counter``, the clock of ``ctx["spans"]``: no
offset is needed to lay them on the window.

A test or a recorded trace hands the module texts in as
``ctx["program_hlo"]`` (a list) and the spans as ``ctx["program_spans"]``;
a run asks the program.  A program without the scopes or the registry
(the parent of the PR that added them) gives every reader ``None``.
"""
import bisect
import sys
import time
import traceback

import trace_reduce

STEP_SPANS = ("module.forward_backward", "module.update",
              "module.update_metric", "trainer.step")
UNATTRIBUTED = "other"      # phases.OTHER, and what no map knows
CONTROL = "control"         # phases.CONTROL: a while / conditional / call


def program():
    """The program's ``telemetry.phases`` module; None for a program from
    before the scopes."""
    try:
        from mxnet_tpu.telemetry import phases
    except ImportError:
        return None
    return phases


def _program_hlo(ctx):
    """The optimized module texts of the programs registered with
    telemetry — compiled here, after the window, a persistent-cache hit."""
    if "program_hlo" in ctx:
        return ctx["program_hlo"]
    registry, texts = program(), []
    for name in registry.program_names() if registry else ():
        t0 = time.perf_counter()
        try:
            texts.append(registry.program_hlo(name))
        except Exception:       # a reader reports, the run goes on
            traceback.print_exc()
            continue
        print("[perfbench] program_hlo(%r): %.2fs, %d bytes"
              % (name, time.perf_counter() - t0, len(texts[-1])),
              file=sys.stderr, flush=True)
    return texts


def _module_name(hlo_text):
    head = hlo_text.split("\n", 1)[0]       # "HloModule jit_fbu, is_..."
    return head.split()[1].rstrip(",") if head.startswith("HloModule") \
        else None


def join(ctx, classify, key):
    """``{device: [(class, start_ns, end_ns)]}``: every device event of
    the traced window under the class its instruction has in
    ``classify(optimized module text)`` — one of the program's maps from
    instruction name to class — and None where the module the event ran
    in has no such instruction or is no registered program.  The ONE walk
    event -> ``XLA Modules`` event around it -> leading ``%instruction``
    -> class; memoised on ``ctx[key]``.  None where there is no trace,
    no classifier (a program from before it) or no module text."""
    if key in ctx:
        return ctx[key]
    ctx[key] = None
    trace = ctx.get("trace") or {}
    ops = trace.get("ops_by_device")
    texts = _program_hlo(ctx) if ops and classify is not None else None
    if not texts:
        return None
    maps = {_module_name(t): classify(t) for t in texts}
    joined = {}
    for dev, events in ops.items():
        mods = sorted((s, e, name.split("(", 1)[0]) for name, s, e in
                      trace.get("modules_by_device", {}).get(dev, ()))
        starts = [m[0] for m in mods]
        rows = joined[dev] = []
        for name, s, e in events:
            mid = (s + e) / 2.0
            i = bisect.bisect_right(starts, mid) - 1
            inside = mods[i][2] if i >= 0 and mid <= mods[i][1] else None
            instruction = name.split(" = ", 1)[0].strip().lstrip("%")
            rows.append((maps.get(inside, {}).get(instruction), s, e))
    ctx[key] = joined
    return joined


def control_cover(control, rest):
    """``(seconds inside the ``control`` intervals, of which other events
    cover)`` of one device, both lists ``[(start_ns, end_ns)]``.  A
    ``while`` has an event of its own that spans its body's; the
    program's map classes it ``control`` and the body's events carry the
    phases, so only what they leave uncovered is the loop's own."""
    if not control:
        return 0.0, 0.0
    rest = sorted(rest)
    starts = [s for s, _e in rest]
    covered = 0
    for s, e in trace_reduce.merged(control):       # a loop in a loop: once
        i = bisect.bisect_left(starts, s)
        j = bisect.bisect_left(starts, e)
        covered += trace_reduce.union_length(
            [(a, min(b, e)) for a, b in rest[i:j]])
    return (trace_reduce.union_length(control) * 1e-9, covered * 1e-9)


def phases(ctx):
    """``{"seconds": {phase: s}, "busy_s": s}`` per chip (mean over the
    chips) over the traced window, memoised on ``ctx``; None where the
    program names no phase.  A ``control`` event's time is its body's
    events' over again, so it is no phase's: what of it NO other event
    covers goes to ``other``, where ``phase_unattributed_share`` guards
    every reader of the join (``fwd_ms`` ... and ``loop_ms`` ...)."""
    if "_phases" in ctx:
        return ctx["_phases"]
    ctx["_phases"] = None
    joined = join(ctx, getattr(program(), "instruction_phases", None),
                  "_phase_events")
    if not joined:
        return None
    seconds, whole, covered = {}, 0.0, 0.0
    for events in joined.values():
        control, rest = [], []
        for phase, s, e in events:
            if phase == CONTROL:
                control.append((s, e))
                continue
            rest.append((s, e))
            phase = phase or UNATTRIBUTED
            seconds[phase] = seconds.get(phase, 0.0) + (e - s) * 1e-9
        if control:
            w, c = control_cover(control, rest)
            seconds[UNATTRIBUTED] = seconds.get(UNATTRIBUTED, 0.0) + (w - c)
            whole, covered = whole + w, covered + c
    if not any(t for p, t in seconds.items() if p != UNATTRIBUTED):
        return None
    print("[perfbench] control events (while ...) %.4fs, their bodies' "
          "events inside them %.4fs (%s); the rest counts as unattributed"
          % (whole, covered,
             "%.3f%%" % (100.0 * covered / whole) if whole else "none"),
          file=sys.stderr, flush=True)
    ctx["_phases"] = {
        "seconds": {p: t / len(joined) for p, t in seconds.items()},
        "busy_s": ctx["trace"]["busy_s"]}
    return ctx["_phases"]


def phase_ms(ctx, phase):
    """Device time per step (ms) of one phase, or None."""
    joined = phases(ctx)
    if joined is None or not ctx.get("steps"):
        return None
    return 1e3 * joined["seconds"].get(phase, 0.0) / ctx["steps"]


def unattributed_share(ctx):
    """Device time no phase claims, over busy time (%), or None."""
    joined = phases(ctx)
    if joined is None or not joined["busy_s"]:
        return None
    return 100.0 * joined["seconds"].get(UNATTRIBUTED, 0.0) \
        / joined["busy_s"]


def step_host_ms(ctx):
    """Host time per step (ms) inside the program's own top-level step
    spans that began inside the window, or None."""
    window = ctx.get("spans") or []
    if not window or not ctx.get("steps"):
        return None
    if "program_spans" in ctx:
        recorded = ctx["program_spans"]
    else:
        from mxnet_tpu.telemetry import tracing
        recorded = tracing.snapshot()
    lo, hi = window[0][0] * 1e9, window[-1][1] * 1e9
    inside = [r["dur_ms"] for r in recorded
              if r["name"] in STEP_SPANS and lo <= r.get("t0_ns", -1) <= hi]
    if not inside:
        return None
    return sum(inside) / ctx["steps"]
