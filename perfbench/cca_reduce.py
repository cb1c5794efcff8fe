"""A step of compressed convolutional attention from inside: device time
of what mixes the latent queries and keys between their projections and
the flash call.

The join is ``phase_reduce.join`` under another of the program's maps:
``mxnet_tpu.telemetry.phases.instruction_cca_parts`` gives every
instruction ``(part, recomputed)``, ``part`` being ``cca`` (scope
``mx_cca``: the causal convolution over time, the one across a head's
channels, the q-k mean, the values' shift, the QK norm with its
temperature and the partial rotary) or None.  Forward, the forward run
again for the backward pass and the backward all count.  The metric file
``cca_mix_ms`` reads the result.

A program without that function (the parent of the PR that added it), or
whose step carries no such scope, gives the reader ``None``.
"""
import phase_reduce


def cca_seconds(ctx):
    """Seconds per chip (mean over the chips) under ``mx_cca`` over the
    traced window, memoised on ``ctx``; None where the program names no
    such part."""
    if "_cca_seconds" in ctx:
        return ctx["_cca_seconds"]
    ctx["_cca_seconds"] = None
    joined = phase_reduce.join(
        ctx, getattr(phase_reduce.program(), "instruction_cca_parts", None),
        "_cca_events")
    if not joined:
        return None
    seconds = sum((e - s) * 1e-9 for events in joined.values()
                  for found, s, e in events
                  if (found or (None, False))[0] == "cca")
    if seconds:
        ctx["_cca_seconds"] = seconds / len(joined)
    return ctx["_cca_seconds"]
