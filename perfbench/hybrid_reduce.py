"""A decoder-hybrid-decoder step from inside: device time under its Mamba
mixers, its gated memory units and its cross-attention.

The join is ``phase_reduce.join`` under another of the program's maps:
``mxnet_tpu.telemetry.phases.instruction_hybrid_parts`` gives every
instruction ``(part, recomputed)``, ``part`` being ``ssm`` (scope
``mx_ssm``: a whole Mamba mixer, input projection to output projection),
``gmu`` (``mx_gmu``), ``attn_cross`` (``mx_attn_cross``: a cross layer's
query projection, flash call, maps' difference and norm) or None.
Forward, the forward run again for the backward pass and the backward
all count.  The metric files ``ssm_ms``, ``gmu_ms`` and ``attn_cross_ms``
read the result.

A program without that function (the parent of the PR that added it), or
whose step carries none of the scopes, gives every reader ``None``.
"""
import phase_reduce

PARTS = ("ssm", "gmu", "attn_cross")


def part_ms(ctx, name):
    """Device time per step (ms) of the named part, mean over the chips,
    or None; the join is memoised on ``ctx``."""
    if "_hybrid_parts" not in ctx:
        ctx["_hybrid_parts"] = None
        joined = phase_reduce.join(
            ctx, getattr(phase_reduce.program(), "instruction_hybrid_parts",
                         None), "_hybrid_events")
        if joined:
            seconds = dict.fromkeys(PARTS, 0.0)
            for events in joined.values():
                for found, s, e in events:
                    part = (found or (None, False))[0]
                    if part in seconds:
                        seconds[part] += (e - s) * 1e-9
            if any(seconds.values()):
                ctx["_hybrid_parts"] = {p: t / len(joined)
                                        for p, t in seconds.items()}
    parts = ctx["_hybrid_parts"]
    if parts is None or not parts[name] or not ctx.get("steps"):
        return None
    return 1e3 * parts[name] / ctx["steps"]
