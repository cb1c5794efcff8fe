"""Device mesh management.

The TPU-native replacement for the reference's device topology handling
(kvstore device lists, ``group2ctx`` model-parallel context maps —
src/executor/graph_executor.cc AssignContext).  A named
``jax.sharding.Mesh`` over {dp, tp, pp, sp, ep} axes is the single
source of truth for every parallelism strategy; collectives ride ICI
inside a slice and DCN across slices (axis order puts dp outermost so
its all-reduce maps to the slowest network, per the scaling-book recipe).
"""
from __future__ import annotations

import math
from contextlib import contextmanager

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["make_mesh", "current_mesh", "mesh_scope", "replicated",
           "batch_sharded", "P", "NamedSharding", "Mesh"]

AXES = ("dp", "fsdp", "tp", "pp", "sp", "ep")

_CURRENT = []


def make_mesh(dp=None, tp=1, pp=1, sp=1, ep=1, fsdp=1, devices=None):
    """Build a named mesh over the available devices.

    Unspecified ``dp`` absorbs all remaining devices, so
    ``make_mesh()`` is pure data parallelism over every chip (the
    reference's kvstore=device default)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    fixed = tp * pp * sp * ep * fsdp
    if dp is None:
        if n % fixed != 0:
            raise MXNetError(
                "mesh axes tp*pp*sp*ep*fsdp=%d do not divide device count %d"
                % (fixed, n))
        dp = n // fixed
    if dp * fixed != n:
        raise MXNetError("mesh size %d != device count %d" % (dp * fixed, n))
    shape = dict(dp=dp, fsdp=fsdp, tp=tp, pp=pp, sp=sp, ep=ep)
    dims = [shape[a] for a in AXES]
    arr = np.asarray(devices).reshape(dims)
    return Mesh(arr, AXES)


def current_mesh():
    if _CURRENT:
        return _CURRENT[-1]
    return None


@contextmanager
def mesh_scope(mesh):
    _CURRENT.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _CURRENT.pop()


def replicated(mesh):
    """Sharding for fully-replicated arrays (params in pure DP)."""
    return NamedSharding(mesh, P())


def batch_sharded(mesh, axis=0, axes=("dp",)):
    """Sharding that splits dim `axis` across the given mesh axes."""
    spec = [None] * (axis + 1)
    spec[axis] = axes if len(axes) > 1 else axes[0]
    return NamedSharding(mesh, P(*spec))
