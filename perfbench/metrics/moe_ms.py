"""moe_ms (ms): device time per step under the expert part of the layers
— ``XLA Ops`` events whose HLO instruction carries the scope ``mx_moe``:
router, top-k, sort, dispatch, the experts' grouped products, combine;
forward, the forward run again for the backward pass, and backward —
mean over the chips (``moe_reduce.py``).  No such scope in the program:
nothing returned."""
import moe_reduce


def read(ctx):
    return moe_reduce.part_ms(ctx, "route", "experts")
