"""update_ms (ms): device time per step of the optimizer's instructions
(``mx_update`` / ``mx_codec``) — the flat buckets' flatten and
unflatten, the sweep kernel, the casts, a gradient codec's round trip —
mean over the chips (``phase_reduce.py``).  No phase named by the
program: nothing returned."""
import phase_reduce


def read(ctx):
    return phase_reduce.phase_ms(ctx, "update")
