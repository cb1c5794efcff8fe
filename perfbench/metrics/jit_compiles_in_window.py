"""jit_compiles_in_window (count): ``xla.compile`` spans of ANY program
that began inside the window (``setup_reduce.py``) — jax's own report,
so the trainer's step and the kernel wrappers count as the executor's
programs do in ``compiles_in_window``.  Expected 0."""
import setup_reduce


def read(ctx):
    return setup_reduce.compiles_in_window(ctx)
