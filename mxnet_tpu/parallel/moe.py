"""Expert parallelism (MoE) over the "ep" mesh axis.

The reference has no mixture-of-experts (SURVEY.md §2.14).  This is the
TPU-native switch-routing layer: experts are sharded over "ep", tokens
are routed top-1 with a capacity limit, and the dispatch/return trips
are `lax.all_to_all` collectives inside `shard_map` — the canonical
expert-parallel pattern (Switch Transformer / GShard), compiled into the
surrounding step.

Routing math (per source device, capacity C):
  gate      = softmax(x @ gate_w)              (T_local, E)
  expert_id = argmax(gate)                     top-1 switch routing
  position  = rank of the token within its expert's queue; tokens
              beyond C are dropped (their combine weight is zero)
  dispatch  : scatter tokens into an (E, C, D) send buffer ->
              all_to_all -> each device holds its E/ep experts' queues
              from every source
  combine   : all_to_all back, gather each token's expert output,
              scale by its gate probability.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from .pipeline import stack_stages as stack_experts  # same stacking helper

__all__ = ["switch_moe", "stack_experts"]


def switch_moe(x, gate_w, expert_params, expert_fn, mesh,
               capacity_factor=2.0, axis="ep"):
    """Top-1 routed mixture of experts, experts sharded over ``axis``.

    x: (T, D) tokens (shard tokens over ep); gate_w: (D, E) replicated;
    expert_params: pytree with leading expert dim E == ep * E_local;
    expert_fn(params, tokens) -> tokens, vmapped over local experts.

    Returns (T, D) combined outputs; dropped (over-capacity) tokens
    contribute zero, exactly like capacity-limited switch routing.
    """
    ep = mesh.shape[axis]
    E = gate_w.shape[1]
    if E % ep:
        raise MXNetError("num experts %d not divisible by ep=%d" % (E, ep))
    T = x.shape[0]
    if T % ep:
        raise MXNetError("token count %d not divisible by ep=%d" % (T, ep))
    T_local = T // ep
    # per-(expert, source-device) queue capacity
    C = max(int(capacity_factor * T_local / E), 1)

    def per_device(x_l, gate_w, params_l):
        # params_l leaves arrive as the (E_local, ...) shard of this device
        D = x_l.shape[-1]
        logits = x_l @ gate_w
        probs = jax.nn.softmax(logits, axis=-1)
        eid = jnp.argmax(probs, axis=-1)                      # (T_l,)
        gate = jnp.take_along_axis(probs, eid[:, None], 1)[:, 0]
        onehot = jax.nn.one_hot(eid, E, dtype=jnp.int32)      # (T_l, E)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)           # rank in queue
        pos_t = jnp.sum(pos * onehot, axis=-1)                # (T_l,)
        keep = pos_t < C
        slot = jnp.clip(pos_t, 0, C - 1)
        send = jnp.zeros((E, C, D), x_l.dtype).at[eid, slot].add(
            x_l * keep[:, None])
        # (E, C, D) -> (E_local, ep*C, D): device d keeps its E_local
        # experts, receiving each expert's queue from every source
        recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=1,
                              tiled=True)
        y = jax.vmap(expert_fn)(params_l, recv)               # (E_l, ep*C, D)
        back = lax.all_to_all(y, axis, split_axis=1, concat_axis=0,
                              tiled=True)                     # (E, C, D)
        out = back[eid, slot] * (gate * keep)[:, None]
        return out

    spec_params = jax.tree.map(lambda _: P(axis), expert_params)
    fn = jax.shard_map(per_device, mesh=mesh,
                       in_specs=(P(axis), P(), spec_params),
                       out_specs=P(axis))
    return fn(x, gate_w, expert_params)
