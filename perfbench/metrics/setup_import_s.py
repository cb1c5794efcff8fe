"""setup_import_s (s): the program's gauge ``mxnet_import_seconds`` —
its package's own modules, first line of its ``__init__`` to the last;
jax, which the harness imports first, is not in it
(``setup_reduce.py``).  Moves ``setup_s``."""
import setup_reduce


def read(ctx):
    return setup_reduce.import_s(ctx)
