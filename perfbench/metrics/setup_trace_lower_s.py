"""setup_trace_lower_s (s): what the program's ``xla.trace`` and
``xla.lower`` spans that began before the window cover, beyond the
backend stages (``setup_reduce.py``) — Python, paid at every restart
whatever the compile cache holds.  Process-wide: the harness's own
programs (the reference weights' ``make``) are in it beside the
program's.  Moves ``setup_s``."""
import setup_reduce


def read(ctx):
    return setup_reduce.trace_lower_s(ctx)
