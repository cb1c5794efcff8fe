"""loop_ms (ms): device time per step under the looped stack — ``XLA
Ops`` events whose HLO instruction carries the scope ``mx_loop`` (and not
``mx_exit``): every pass's forward, the same forward run again for the
backward pass, and the backward itself — mean over the chips
(``loop_reduce.py``).  No such scope in the program: nothing returned."""
import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, "loop")
