"""ssm_roofline (%): the selective scan's kernels' share of the HBM
roofline.

Bytes the scan must move per step (counts/<family>.py ``ssm_bytes``: its
(T, E) operands and results at 2 bytes an element, B, C, A, D and the
chunks' states) over the chip's HBM bandwidth, divided by the summed
device time per step of the kernels' events, found by the ``name=`` of
their ``pallas_call`` (``_ssm_fwd_kernel``, ``_ssm_bwd_kernel``).  No
event found, or counts without ``ssm_bytes``: nothing returned.
"""
import re

import trace_reduce

KERNELS = re.compile(r"_ssm_fwd_kernel|_ssm_bwd_kernel")


def read(ctx):
    counts = ctx["counts"]
    if ctx["peaks"] is None or not ctx["steps"] \
            or not hasattr(counts, "ssm_bytes"):
        return None
    seconds, n = trace_reduce.event_seconds(ctx["trace"], KERNELS)
    if not n or not seconds:
        return None
    least = counts.ssm_bytes(ctx["config"]) / ctx["chips"] \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / ctx["steps"])
