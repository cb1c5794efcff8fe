"""block_recompute_ms (ms): device time per step of forward instructions
run AGAIN for the backward pass in a program whose layers route tokens
to experts — ``XLA Ops`` events whose HLO instruction carries jax's
``rematted_computation`` in its name stack, whatever part of a layer it
belongs to (every layer of the block is a ``jax.checkpoint``) — mean over
the chips (``moe_reduce.py``).  It is a share of ``bwd_ms``, overlapping
``moe_ms`` and the attention readers, not beside them: work ``step_mfu``
does not count.  (``recompute_ms`` reads the same mark through the looped
model's scopes and finds none here.)  No expert scope in the program:
nothing returned."""
import moe_reduce


def read(ctx):
    return moe_reduce.part_ms(ctx, "recompute")
