"""A sparse-expert model's step from inside: device time under the
expert part of its layers — its routing and its experts' products apart
— and under attention of each kind.

The join is ``phase_reduce.join`` under another of the program's maps:
``mxnet_tpu.telemetry.phases.instruction_block_parts`` gives every
instruction ``(part, recomputed)``, ``part`` being ``experts`` (scope
``mx_moe_experts``: the grouped products and their activation),
``route`` (``mx_moe`` outside it: router, top-k, sort, gather, combine),
``attn_window`` / ``attn_full`` (rotary and attention of a layer of that
kind) or None, and ``recomputed`` whether jax marked it as a
rematerialised forward (``rematted_computation`` in its name stack).
Forward, the forward run again for the backward pass and the backward
itself all count under a part: recomputed products are time and not
work.  ``recompute`` is the forward run again wherever it lies — in a
part or in none (a layer's projections and norms): a share of the step
beside the parts, not a fifth part.  The metric files ``moe_ms``,
``moe_route_ms``, ``expert_roofline``, ``attn_window_ms``,
``attn_full_ms`` and ``block_recompute_ms`` read the result.

A program without that function (the parent of the PR that added it), or
whose step carries none of the scopes, gives every reader ``None``.
"""
import phase_reduce

PARTS = ("route", "experts", "attn_window", "attn_full")


def parts(ctx):
    """``{part: seconds}`` per chip (mean over the chips) over the traced
    window, with ``recompute`` beside the parts, memoised on ``ctx``;
    None where the program names no part."""
    if "_block_parts" in ctx:
        return ctx["_block_parts"]
    ctx["_block_parts"] = None
    joined = phase_reduce.join(
        ctx, getattr(phase_reduce.program(), "instruction_block_parts", None),
        "_block_events")
    if not joined:
        return None
    seconds = dict.fromkeys(PARTS + ("recompute",), 0.0)
    for events in joined.values():
        for found, s, e in events:
            part, again = found or (None, False)
            if part in PARTS:
                seconds[part] += (e - s) * 1e-9
            if again:
                seconds["recompute"] += (e - s) * 1e-9
    if not any(seconds[p] for p in PARTS):
        return None
    ctx["_block_parts"] = {p: t / len(joined) for p, t in seconds.items()}
    return ctx["_block_parts"]


def part_ms(ctx, *names):
    """Device time per step (ms) of the named parts together, or None."""
    joined = parts(ctx)
    if joined is None or not ctx.get("steps"):
        return None
    return 1e3 * sum(joined[n] for n in names) / ctx["steps"]
