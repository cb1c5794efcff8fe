"""exit_ms (ms): device time per step under the exits — ``XLA Ops``
events whose HLO instruction carries the scope ``mx_exit``: each pass's
final norm and gate, each exit's head fused with its cross-entropy
(forward, the logits recomputed in the backward pass, backward) — mean
over the chips (``loop_reduce.py``).  No such scope in the program:
nothing returned."""
import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, "exit")
