"""fwd_ms (ms): device time per step of the step program's forward
instructions, the loss included — ``XLA Ops`` events whose HLO
instruction carries ``jvp(mx_fwd)`` and no ``transpose(`` — mean over
the chips (``phase_reduce.py``).  No phase named by the program: nothing
returned."""
import phase_reduce


def read(ctx):
    return phase_reduce.phase_ms(ctx, "fwd")
