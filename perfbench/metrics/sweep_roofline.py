"""sweep_roofline (%): the one-sweep optimizer kernels' share of the
HBM roofline (bandwidth bound).

Bytes the update must move per step and chip, from shapes
(counts/<family>.py ``sweep_bytes``: SGD with momentum reads p, g, m and
writes p, m; Adam reads p, g, m, v and writes p, m, v; float32; each
chip its own ZeRO shard), over the chip's HBM bandwidth, divided by the
summed device time of the kernels' events per step.  The kernels are
found by the ``name=`` their ``pallas_call`` carries
(``ops/pallas_kernels.py``: ``_sgd_mom_kernel``, ``_sgd_kernel``,
``_adam_kernel``).  No event found: nothing returned.
"""
import re

import trace_reduce

KERNELS = re.compile(r"_sgd_mom_kernel|_sgd_kernel|_adam_kernel")


def read(ctx):
    if ctx["peaks"] is None or not ctx["steps"]:
        return None
    seconds, n = trace_reduce.event_seconds(ctx["trace"], KERNELS)
    if not n or not seconds:
        return None
    least = ctx["counts"].sweep_bytes(ctx["config"], ctx["chips"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / ctx["steps"])
