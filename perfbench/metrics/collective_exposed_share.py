"""collective_exposed_share (%): device time of collective operations
during which no compute operation runs on that device, over the traced
window; the worst device.

Collectives are the events of the ``XLA Ops`` line whose HLO opcode is
an all-reduce, reduce-scatter, all-gather, all-to-all or
collective-permute (``-start`` / ``-done`` forms included); every other
event of that line is compute.  Exposed = the collectives' intervals
minus the union of the compute intervals.  One chip, or no collective
found: nothing returned.
"""
import re

import trace_reduce

COLLECTIVE = re.compile(r"\b(all-reduce|reduce-scatter|all-gather|"
                        r"all-to-all|collective-permute)(-start|-done)?\(")


def _exposed(events):
    coll, comp = [], []
    for name, s, e in events:
        (coll if COLLECTIVE.search(name) else comp).append((s, e))
    if not coll:
        return None
    comp = trace_reduce.merged(comp)
    exposed = 0
    for s, e in trace_reduce.merged(coll):
        covered = 0
        for cs, ce in comp:
            lo, hi = max(s, cs), min(e, ce)
            if hi > lo:
                covered += hi - lo
        exposed += (e - s) - covered
    return exposed


def read(ctx):
    t = ctx["trace"]
    if ctx["chips"] < 2 or not t["window_s"]:
        return None
    worst = None
    for _dev, events in t["ops_by_device"].items():
        x = _exposed(events)
        if x is not None and (worst is None or x > worst):
            worst = x
    if worst is None:
        return None
    return 100.0 * worst * 1e-9 / t["window_s"]
