"""recompute_ms (ms): device time per step of forward instructions run
AGAIN for the backward pass — ``XLA Ops`` events whose HLO instruction
carries jax's ``rematted_computation`` in its name stack (a pass of the
looped stack, an exit's logits) — mean over the chips
(``loop_reduce.py``).  It is a share of ``loop_ms`` + ``exit_ms``, not
beside them: work ``step_mfu`` does not count.  A program that names no
pass and no exit: nothing returned."""
import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, "recompute")
