"""gmu_ms (ms): device time per step under the scope ``mx_gmu`` — the
gated memory units, their two projections and the gate over the memory
— forward, recomputed forward and backward, mean over the chips
(``hybrid_reduce.py``).  No such scope in the program: nothing
returned."""
import hybrid_reduce


def read(ctx):
    return hybrid_reduce.part_ms(ctx, "gmu")
