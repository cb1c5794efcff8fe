"""Pipeline parallelism over the "pp" mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.14 — only the
`PartialForward` staging hook, graph_executor.cc:85, and manual
`group2ctx` device placement).  This is the TPU-native expression of
layer-wise model parallelism: stages live on different devices of the
"pp" axis and microbatches stream through a GPipe schedule compiled as
ONE XLA program — `shard_map` over "pp", `lax.scan` over the
M + S - 1 schedule steps, `lax.ppermute` moving activations to the next
stage over ICI.  Backward is jax autodiff through the scan/ppermute
(the transpose of a ppermute is the reverse ppermute), i.e. the 1F1B
bubble structure falls out of XLA's scheduling rather than a hand-built
runtime.

Constraints (the classic homogeneous-pipeline contract): every stage
maps activations of one fixed shape to the same shape, and stage
parameters are stacked on a leading stage axis (use ``stack_stages``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..base import MXNetError

__all__ = ["pipeline_apply", "stack_stages"]


def stack_stages(param_trees):
    """Stack per-stage (or per-expert — moe.py aliases this) parameter
    pytrees on a new leading axis."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *param_trees)


def pipeline_apply(stage_fn, stacked_params, x, mesh, num_microbatches=None,
                   axis="pp"):
    """Run ``x`` through S pipeline stages sharded over ``axis``.

    stage_fn(params, act) -> act : one stage, shape-preserving.
    stacked_params: pytree with leading stage dim S == mesh.shape[axis].
    x: (B, ...) global batch; B must divide into ``num_microbatches``
    (default S) equal microbatches.

    Returns the (B, ...) output after all S stages, replicated.
    """
    S = mesh.shape[axis]
    M = int(num_microbatches or S)
    B = x.shape[0]
    if B % M:
        raise MXNetError("batch %d not divisible into %d microbatches"
                         % (B, M))
    mbs = x.reshape((M, B // M) + x.shape[1:])

    def per_stage(params, mbs):
        params = jax.tree.map(lambda a: a[0], params)  # local stage slice
        idx = lax.axis_index(axis)
        mb_shape = mbs.shape[1:]
        perm = [(i, i + 1) for i in range(S - 1)]

        def body(carry, t):
            buf, outs = carry
            # stage 0 feeds microbatch t while t < M; later stages take
            # the activation handed over by ppermute last step
            feed = lax.dynamic_index_in_dim(mbs, jnp.clip(t, 0, M - 1), 0,
                                            keepdims=False)
            inp = jnp.where(idx == 0, feed, buf)
            out = stage_fn(params, inp)
            # the last stage retires microbatch t-(S-1) at step t
            pos = t - (S - 1)
            cpos = jnp.clip(pos, 0, M - 1)
            write = (idx == S - 1) & (pos >= 0)
            cur = lax.dynamic_index_in_dim(outs, cpos, 0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, out, cur), cpos, 0)
            buf = lax.ppermute(out, axis, perm)
            return (buf, outs), None

        # the carry is device-varying under shard_map (each stage holds
        # different activations), so the init must be typed as such
        init = (lax.pcast(jnp.zeros(mb_shape, x.dtype), axis, to="varying"),
                lax.pcast(jnp.zeros(mbs.shape, x.dtype), axis, to="varying"))
        (_, outs), _ = lax.scan(body, init, jnp.arange(M + S - 1))
        # result lives on the last stage only; psum replicates it (and
        # transposes to an identity-on-last-stage in backward)
        return lax.psum(jnp.where(idx == S - 1, outs, 0), axis)

    spec_params = jax.tree.map(lambda _: P(axis), stacked_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(spec_params, P()), out_specs=P())
    out = fn(stacked_params, mbs)
    return out.reshape((B,) + out.shape[2:])
