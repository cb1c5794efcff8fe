"""setup_backend_s (s): what the program's ``xla.compile`` spans that
began before the window cover (``setup_reduce.py``) — XLA compiles and
persistent-cache loads together; ``setup_cache_misses`` says how many
were compiles.  Process-wide: the harness's own programs (the
reference weights' ``make``) are in it beside the program's.  Moves
``setup_s``."""
import setup_reduce


def read(ctx):
    return setup_reduce.backend_s(ctx)
