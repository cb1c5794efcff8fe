"""attn_cross_ms (ms): device time per step under the scope
``mx_attn_cross`` — the cross-attention layers' query projection, flash
call, the difference of their two maps and its norm — forward,
recomputed forward and backward, mean over the chips
(``hybrid_reduce.py``).  No such scope in the program: nothing
returned."""
import hybrid_reduce


def read(ctx):
    return hybrid_reduce.part_ms(ctx, "attn_cross")
