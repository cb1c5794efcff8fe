"""expert_roofline (%): the experts' products' share of the chip's bf16
peak (compute bound).

FLOPs the three expert products need per step, forward and backward,
over the rows routed to the held experts in expectation
(counts/<family>.py ``expert_flops``: padding is not work, and neither
is the forward run again), over the peak, divided by the device time per
step under the scope ``mx_moe_experts`` (``moe_reduce.py``) — whatever
implements the products.  No such scope, or no such count: nothing
returned.
"""
import moe_reduce


def read(ctx):
    counts = ctx.get("counts")
    if ctx.get("peaks") is None or not hasattr(counts, "expert_flops"):
        return None
    ms = moe_reduce.part_ms(ctx, "experts")
    if not ms:
        return None
    least = counts.expert_flops(ctx["config"]) / ctx["chips"] \
        / ctx["peaks"]["bf16_flops"]
    return 100.0 * least / (1e-3 * ms)
