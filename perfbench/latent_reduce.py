"""A latent-attention model's step from inside: device time of what
latent attention runs around its flash call, of the shared expert, and of
the multi-token-prediction modules.

The join is ``phase_reduce.join`` under another of the program's maps:
``mxnet_tpu.telemetry.phases.instruction_latent_parts`` gives every
instruction ``(part, in_mtp)``, ``part`` being ``attn_latent`` (scope
``mx_attn_latent``: both latents' down- and up-projections, their norms,
the split and the shared rotary key's broadcast — the flash call and its
rotary are ``mx_attn_full``, read by ``attn_full_ms``), ``shared_expert``
(``mx_shared_expert``) or None, and ``in_mtp`` whether a prediction
module runs it (``mx_mtp``: its entry, its layer — whose attention and
experts carry their own scopes inside it — its norm and its term through
the head).  ``mtp`` therefore CROSSES the parts, here and in
``moe_reduce.py``: a share of the step beside them, not one more part.
Forward, the forward run again for the backward pass and the backward
all count.  The metric files ``attn_latent_ms``, ``shared_expert_ms`` and
``mtp_ms`` read the result.

A program without that function (the parent of the PR that added it), or
whose step carries none of the scopes, gives every reader ``None``.
"""
import phase_reduce

PARTS = ("attn_latent", "shared_expert")


def parts(ctx):
    """``{part: seconds}`` per chip (mean over the chips) over the traced
    window, with ``mtp`` beside the parts, memoised on ``ctx``; None
    where the program names none of them."""
    if "_latent_parts" in ctx:
        return ctx["_latent_parts"]
    ctx["_latent_parts"] = None
    joined = phase_reduce.join(
        ctx, getattr(phase_reduce.program(), "instruction_latent_parts",
                     None), "_latent_events")
    if not joined:
        return None
    seconds = dict.fromkeys(PARTS + ("mtp",), 0.0)
    for events in joined.values():
        for found, s, e in events:
            part, in_mtp = found or (None, False)
            if part in PARTS:
                seconds[part] += (e - s) * 1e-9
            if in_mtp:
                seconds["mtp"] += (e - s) * 1e-9
    if not any(seconds.values()):
        return None
    ctx["_latent_parts"] = {p: t / len(joined) for p, t in seconds.items()}
    return ctx["_latent_parts"]


def part_ms(ctx, name):
    """Device time per step (ms) of the named part, or None."""
    joined = parts(ctx)
    if joined is None or not ctx.get("steps"):
        return None
    return 1e3 * joined[name] / ctx["steps"]
