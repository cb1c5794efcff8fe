"""attn_full_ms (ms): device time per step under the scope
``mx_attn_full`` — rotary and attention of the full-attention layers,
forward, recomputed forward and backward — mean over the chips
(``moe_reduce.py``).  No such scope in the program: nothing returned."""
import moe_reduce


def read(ctx):
    return moe_reduce.part_ms(ctx, "attn_full")
