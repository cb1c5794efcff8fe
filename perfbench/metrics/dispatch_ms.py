"""dispatch_ms (ms): host time per step — the benchmark's own span
around each step's calls into the program (``forward_backward`` +
``update`` + ``update_metric``, or ``ParallelTrainer.step``), host
clock, no block: the sum of the spans over the steps."""


def read(ctx):
    steps = [s for s in ctx["spans"] if len(s) == 2]
    if not steps:
        return None
    return 1e3 * sum(e - s for s, e in steps) / len(steps)
