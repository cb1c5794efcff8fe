"""flash_roofline (%): the flash-attention kernels' share of the chip's
bf16 peak (compute bound).

FLOPs causal attention needs per step, forward and backward, from
shapes (counts/<family>.py ``attention_flops``: scores and values,
halved for the mask; the backward's recomputation of the scores is not
counted), over the peak, divided by the summed device time per step of
the kernels' events, found by the ``name=`` of their ``pallas_call``
(``_flash_fwd_kernel``, ``_flash_bwd_dq_kernel``,
``_flash_bwd_dkv_kernel``).  No event found: nothing returned.
"""
import re

import trace_reduce

KERNELS = re.compile(r"_flash_fwd_kernel|_flash_bwd_dq_kernel|"
                     r"_flash_bwd_dkv_kernel")


def read(ctx):
    counts = ctx["counts"]
    if ctx["peaks"] is None or not ctx["steps"] \
            or not hasattr(counts, "attention_flops"):
        return None
    seconds, n = trace_reduce.event_seconds(ctx["trace"], KERNELS)
    if not n or not seconds:
        return None
    least = counts.attention_flops(ctx["config"]) / ctx["chips"] \
        / ctx["peaks"]["bf16_flops"]
    return 100.0 * least / (seconds / ctx["steps"])
