"""setup_cache_misses (count): ``xla.compile`` spans before the window
tagged ``cache=miss`` (``setup_reduce.py``) — 0 in a warm run; what
separates a first run's set-up from a warm one's, and an evicted cache
from a slow machine.  Process-wide: a miss of the harness's own
programs (the reference weights' ``make``) counts too.  Moves
``setup_s``."""
import setup_reduce


def read(ctx):
    return setup_reduce.cache_misses(ctx)
