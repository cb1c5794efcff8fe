"""step_host_ms (ms): host time per step by the program's own spans —
the summed duration of its top-level step spans (``module.*`` or
``trainer.step``) that began inside the window (``phase_reduce.py``).
``dispatch_ms`` is the benchmark's span around the same calls."""
import phase_reduce


def read(ctx):
    return phase_reduce.step_host_ms(ctx)
