"""A looped model's step from inside: device time under each pass of
the shared stack, under the exits, and in forwards run again for the
backward pass.

The join is ``phase_reduce.join`` — device event -> the program it ran
in -> leading ``%instruction`` -> its class — under another of the
program's maps:
``mxnet_tpu.telemetry.phases.instruction_loop_parts`` gives every
instruction ``(part, recomputed)``, ``part`` being ``loop`` (scope
``mx_loop``), ``exit`` (``mx_exit``: an exit's norm, gate, head and
cross-entropy; the inner scope wins) or None, and ``recomputed`` whether
jax marked it as a rematerialised forward (``rematted_computation`` in
its name stack).  The metric files ``loop_ms``, ``exit_ms`` and
``recompute_ms`` read the result.  A ``while``'s own event has the class
``(None, False)`` here: its time lies in its body's events, and what they
leave uncovered ``phase_reduce.phases`` counts as unattributed.

A program without that function (the parent of the PR that added it), or
whose step carries neither scope (every cell but a looped model's),
gives every reader ``None``.
"""
import phase_reduce


def parts(ctx):
    """``{"loop": s, "exit": s, "recompute": s}`` per chip (mean over
    the chips) over the traced window, memoised on ``ctx``; None where
    the program names no pass and no exit.  ``recompute`` overlaps the
    other two: it is the rematerialised share of both."""
    if "_loop_parts" in ctx:
        return ctx["_loop_parts"]
    ctx["_loop_parts"] = None
    joined = phase_reduce.join(
        ctx, getattr(phase_reduce.program(), "instruction_loop_parts", None),
        "_loop_events")
    if not joined:
        return None
    seconds = {"loop": 0.0, "exit": 0.0, "recompute": 0.0}
    for events in joined.values():
        for found, s, e in events:
            part, again = found or (None, False)
            if part is not None:
                seconds[part] += (e - s) * 1e-9
            if again:
                seconds["recompute"] += (e - s) * 1e-9
    if not (seconds["loop"] or seconds["exit"]):
        return None
    ctx["_loop_parts"] = {p: t / len(joined) for p, t in seconds.items()}
    return ctx["_loop_parts"]


def part_ms(ctx, part):
    """Device time per step (ms) of one part, or None."""
    joined = parts(ctx)
    if joined is None or not ctx.get("steps"):
        return None
    return 1e3 * joined[part] / ctx["steps"]
