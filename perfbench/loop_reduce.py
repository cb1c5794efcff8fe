"""A looped model's step from inside: device time under each pass of
the shared stack, under the exits, and in forwards run again for the
backward pass.

The join is ``phase_reduce.py``'s — device event -> the program it ran
in -> leading ``%instruction`` -> the scopes that instruction carries in
the optimized module — with another map:
``mxnet_tpu.telemetry.phases.instruction_loop_parts`` gives every
instruction ``(part, recomputed)``, ``part`` being ``loop`` (scope
``mx_loop``), ``exit`` (``mx_exit``: an exit's norm, gate, head and
cross-entropy; the inner scope wins) or None, and ``recomputed`` whether
jax marked it as a rematerialised forward (``rematted_computation`` in
its name stack).  The metric files ``loop_ms``, ``exit_ms`` and
``recompute_ms`` read the result.

A program without that function (the parent of the PR that added it), or
whose step carries neither scope (every cell but a looped model's),
gives every reader ``None``.
"""
import bisect
import sys

import phase_reduce
import trace_reduce

CONTROL_OPCODES = ("while", "conditional", "call")


def control_cover(events):
    """``(control seconds, of which covered by other events)`` of one
    device's ``[(name, start, end)]``.  A ``while`` has an event of its
    own that spans its body's; the program's maps class it ``control``
    and count the body's events, which is sound only while the two
    numbers agree — every traced run prints them."""
    control = [(s, e) for name, s, e in events
               if trace_reduce.opcode(name) in CONTROL_OPCODES]
    if not control:
        return 0.0, 0.0
    rest = sorted((s, e) for name, s, e in events
                  if trace_reduce.opcode(name) not in CONTROL_OPCODES)
    starts = [s for s, _e in rest]
    covered = 0
    for s, e in trace_reduce.merged(control):       # a loop in a loop: once
        i = bisect.bisect_left(starts, s)
        j = bisect.bisect_left(starts, e)
        covered += trace_reduce.union_length(
            [(a, min(b, e)) for a, b in rest[i:j]])
    return (trace_reduce.union_length(control) * 1e-9, covered * 1e-9)


def parts(ctx):
    """``{"loop": s, "exit": s, "recompute": s}`` per chip (mean over
    the chips) over the traced window, memoised on ``ctx``; None where
    the program names no pass and no exit.  ``recompute`` overlaps the
    other two: it is the rematerialised share of both."""
    if "_loop_parts" in ctx:
        return ctx["_loop_parts"]
    ctx["_loop_parts"] = None
    try:
        from mxnet_tpu.telemetry import phases as program
    except ImportError:
        return None
    classify = getattr(program, "instruction_loop_parts", None)
    trace = ctx.get("trace") or {}
    ops = trace.get("ops_by_device")
    if classify is None or not ops:
        return None
    texts = phase_reduce._program_hlo(ctx, program)
    if not texts:
        return None
    maps = {phase_reduce._module_name(t): classify(t) for t in texts}
    seconds = {"loop": 0.0, "exit": 0.0, "recompute": 0.0}
    for dev, events in ops.items():
        mods = sorted((s, e, name.split("(", 1)[0]) for name, s, e in
                      trace.get("modules_by_device", {}).get(dev, ()))
        starts = [m[0] for m in mods]
        for name, s, e in events:
            mid = (s + e) / 2.0
            i = bisect.bisect_right(starts, mid) - 1
            inside = mods[i][2] if i >= 0 and mid <= mods[i][1] else None
            instruction = name.split(" = ", 1)[0].strip().lstrip("%")
            part, again = maps.get(inside, {}).get(instruction,
                                                   (None, False))
            if part is not None:
                seconds[part] += (e - s) * 1e-9
            if again:
                seconds["recompute"] += (e - s) * 1e-9
    if not (seconds["loop"] or seconds["exit"]):
        return None
    control, covered = map(sum, zip(*map(control_cover, ops.values())))
    if control:
        print("[perfbench] control events (while ...) %.4fs, their bodies' "
              "events inside them %.4fs (%.3f%%)"
              % (control, covered, 100.0 * covered / control),
              file=sys.stderr, flush=True)
    ctx["_loop_parts"] = {p: t / len(ops) for p, t in seconds.items()}
    return ctx["_loop_parts"]


def part_ms(ctx, part):
    """Device time per step (ms) of one part, or None."""
    joined = parts(ctx)
    if joined is None or not ctx.get("steps"):
        return None
    return 1e3 * joined[part] / ctx["steps"]
