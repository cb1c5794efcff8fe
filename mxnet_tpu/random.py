"""Global RNG state.

Reference: ``python/mxnet/random.py`` (mx.random.seed) backed by per-device
RNG resources (src/common/random_generator.h, ResourceManager kRandom).

TPU-native: a host-side (seed, counter) chain whose bits ARE the
threefry key — deriving a key never dispatches a device program (see
next_key).  Eager random ops consume keys from here; executors draw
per-step keys from the same chain (the fused train step then advances
its key on-device); results are reproducible under ``mx.random.seed(n)``
in both modes.

Thread safety: the chain is consumed from worker threads too (the
serving batcher's forward path draws dropout keys, prefetch producers
run transforms), so the counter bump is a lock-guarded RMW — an
unguarded ``count += 1`` can hand two threads the SAME key, which is
correlated randomness, the silent kind of wrong (found by graftlint's
``unguarded-global-mutation`` pass).  The trace-key stack is
*thread-local*: a trace running on the batcher thread must consume its
own traced key, never interleave with a main-thread trace's counters.
"""
from __future__ import annotations

import threading

import numpy as np

import jax

_STATE_LOCK = threading.Lock()
_STATE = {"seed": 0, "count": 0}    # guarded-by: _STATE_LOCK

# graftsan lock-order sanitizer swap list: the RNG chain lock is taken
# from worker threads too (see the thread-safety note above), so it
# belongs in the runtime acquisition-order graph
__san_locks__ = ("_STATE_LOCK",)


def seed(seed_state=0, ctx="all"):
    """Reference: python/mxnet/random.py:28 (mx.random.seed)."""
    with _STATE_LOCK:
        _STATE["seed"] = int(seed_state)
        _STATE["count"] = 0


def get_state():
    """The full RNG chain position as a plain dict — because the chain
    is host-side ``(seed, count)``, this pair IS the complete generator
    state (checkpoint capture serializes it; no device read needed)."""
    with _STATE_LOCK:
        return {"seed": int(_STATE["seed"]), "count": int(_STATE["count"])}


def set_state(state):
    """Restore a :func:`get_state` snapshot: every subsequent
    ``next_key`` draw equals the uninterrupted run's draw (checkpoint
    resume's bit-identical-RNG contract)."""
    with _STATE_LOCK:
        _STATE["seed"] = int(state["seed"])
        _STATE["count"] = int(state["count"])


def next_key():
    """A fresh subkey off the global chain (runtime internal).

    The chain is COUNTER-BASED ON HOST: the key bits are (seed, count)
    assembled in numpy and reinterpreted via ``wrap_key_data`` — no
    device program runs.  Deriving keys with ``jax.random.split`` would
    dispatch a tiny kernel per step, which serializes against an
    in-flight train step.  Threefry guarantees independent
    streams for distinct key bits, so uniqueness == independence.

    Inside a jit trace (hybridized blocks), keys must derive from the
    traced key argument — a concrete key would bake one fixed mask into
    the compiled program.  ``trace_key_scope`` pushes the traced key."""
    stack = _trace_stack()
    if stack:
        base, counter = stack[-1]
        stack[-1] = (base, counter + 1)
        return jax.random.fold_in(base, counter)
    return jax.random.wrap_key_data(jax.numpy.asarray(next_key_data()),
                                    impl="threefry2x32")


def next_key_data():
    """Like next_key but returns the RAW uint32[2] threefry key bits as
    host numpy — for programs that wrap the key inside the jit boundary
    (executor fused step: its key chain crosses the program boundary
    as plain uint32 data, so the donated step needs no extended-dtype
    input)."""
    with _STATE_LOCK:
        _STATE["count"] += 1
        seed = _STATE["seed"]
        count = _STATE["count"]
    # mix the high seed bits down so 64-bit seeds keep their entropy in
    # the 32-bit word (seed=2**32 must differ from seed=0)
    mixed = (seed ^ (seed >> 32)) & 0xFFFFFFFF
    return np.array([mixed, count], np.uint32)


# per-thread trace-key stacks: a trace is a per-thread activity, and
# its counter chain must not bleed into (or race with) another thread's
_TRACE = threading.local()


def _trace_stack():
    stack = getattr(_TRACE, "stack", None)
    if stack is None:
        stack = _TRACE.stack = []
    return stack


class trace_key_scope:
    """Route next_key() through a traced base key while active."""

    def __init__(self, key):
        # deliberate tracer capture: the scope exists only for the
        # duration of the trace that created it — the key never
        # outlives the compiled region
        self._key = key  # graftlint: disable=tracer-escape

    def __enter__(self):
        _trace_stack().append((self._key, 0))
        return self

    def __exit__(self, *args):
        _trace_stack().pop()
