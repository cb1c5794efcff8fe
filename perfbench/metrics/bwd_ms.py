"""bwd_ms (ms): device time per step of the step program's backward
instructions — ``XLA Ops`` events whose HLO instruction carries
``transpose(jvp(mx_fwd))`` — mean over the chips (``phase_reduce.py``).
No phase named by the program: nothing returned."""
import phase_reduce


def read(ctx):
    return phase_reduce.phase_ms(ctx, "bwd")
