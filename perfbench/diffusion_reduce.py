"""A block-diffusion model's step from inside: device time of attention
under the three-part block mask and of the noising.

The join is ``phase_reduce.join`` under another of the program's maps:
``mxnet_tpu.telemetry.phases.instruction_diffusion_parts`` gives every
instruction ``(part, recomputed)``, ``part`` being ``attn_blockdiff``
(scope ``mx_attn_blockdiff``: QK-norm, rotary at the rows' positions, the
K/V repeat of grouped-query heads, the flash kernels over the rows
``[noised ; clean]``), ``noise`` (``mx_noise``: the noising operator, the
stack of the two copies and the embedding's gather of the 2 L rows) or
None.  Forward, the forward run again for the backward pass and the
backward all count under a part.  The metric files ``attn_blockdiff_ms``
and ``noise_ms`` read the result.

A program without that function (the parent of the PR that added it), or
whose step carries neither scope, gives every reader ``None``.
"""
import phase_reduce

PARTS = ("attn_blockdiff", "noise")


def parts(ctx):
    """``{part: seconds}`` per chip (mean over the chips) over the traced
    window, memoised on ``ctx``; None where the program names no part."""
    if "_diffusion_parts" in ctx:
        return ctx["_diffusion_parts"]
    ctx["_diffusion_parts"] = None
    joined = phase_reduce.join(
        ctx, getattr(phase_reduce.program(), "instruction_diffusion_parts",
                     None), "_diffusion_events")
    if not joined:
        return None
    seconds = dict.fromkeys(PARTS, 0.0)
    for events in joined.values():
        for found, s, e in events:
            part = (found or (None, False))[0]
            if part in PARTS:
                seconds[part] += (e - s) * 1e-9
    if not any(seconds.values()):
        return None
    ctx["_diffusion_parts"] = {p: t / len(joined)
                               for p, t in seconds.items()}
    return ctx["_diffusion_parts"]


def part_ms(ctx, name):
    """Device time per step (ms) of the named part, or None."""
    joined = parts(ctx)
    if joined is None or not ctx.get("steps"):
        return None
    return 1e3 * joined[name] / ctx["steps"]
