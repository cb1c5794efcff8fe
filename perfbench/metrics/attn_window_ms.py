"""attn_window_ms (ms): device time per step under the scope
``mx_attn_window`` — rotary and attention of the sliding-window layers,
forward, recomputed forward and backward — mean over the chips
(``moe_reduce.py``).  No such scope in the program: nothing returned."""
import moe_reduce


def read(ctx):
    return moe_reduce.part_ms(ctx, "attn_window")
