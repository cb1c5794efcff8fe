"""Expert parallelism (MoE): the switch layer over the "ep" mesh axis,
and :func:`routed_experts`, one chip's share of a top-k expert layer.

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

from ..telemetry import phases as _phases

__all__ = ["switch_moe", "stack_experts", "routed_experts"]


def _route_top_k(x, router_w, top_k, norm_topk=True):
    """``(weights, experts)``, both ``(T, top_k)``: the router's product
    ``x @ router_w.T`` accumulated in float32, a float32 softmax over ALL
    ``router_w.shape[0]`` experts, then each token's ``top_k`` largest,
    renormalised to sum to 1 under ``norm_topk``."""
    logits = jnp.einsum("tu,eu->te", x, router_w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts


def _layout(experts, held, tm):
    """Where every (token, slot) assignment goes.  The assignments whose
    expert is held, sorted by expert, fill a row buffer in which every
    expert's rows start at a multiple of ``tm``: an expert's end is
    padded to its tile with zero rows (never to a capacity: an expert
    takes as many tiles as its rows need, one if it has none), and the
    tiles after the last expert's are unused.  The buffer is sized for
    the worst case, every assignment held: ``ceil(T k / tm) + count``
    tiles.  Returns int32 arrays: ``src (P,)`` the flat assignment a
    row holds, ``valid (P,)``, ``dst (T, k)`` an assignment's row (0
    where it is not held), ``is_held (T, k)``, ``tile_group (tiles,)``
    and ``used (1,)``."""
    first, count = held
    t, k = experts.shape
    a = t * k
    tiles = -(-a // tm) + count
    local = experts - first
    is_held = jnp.logical_and(local >= 0, local < count)
    key = jnp.where(is_held, local, count).reshape(a).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)       # the inverse
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes                # in sorted order
    group_tiles = jnp.maximum(-(-sizes // tm), 1)
    tile_ends = jnp.cumsum(group_tiles)
    row0 = (tile_ends - group_tiles) * tm             # in the buffer
    used = tile_ends[-1:]
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_ends, jnp.arange(tiles, dtype=jnp.int32),
                         side="right"), count - 1).astype(jnp.int32)
    p = jnp.arange(tiles * tm, dtype=jnp.int32)
    g = tile_group[p // tm]
    off = p - row0[g]
    valid = jnp.logical_and(p // tm < used[0], off < sizes[g])
    src = order[jnp.clip(starts[g] + off, 0, a - 1)]
    g_a = jnp.minimum(key, count - 1)
    dst = jnp.where(is_held.reshape(a), row0[g_a] + rank - starts[g_a], 0)
    return (src, valid, dst.reshape(t, k), is_held, tile_group,
            used.astype(jnp.int32))


@jax.custom_vjp
def _dispatch(x, src_token, valid, dst, is_held):
    """``(P, U)``: row ``p`` is token ``src_token[p]``'s state, zero
    where the row is padding.  Its transpose is a gather too: a token's
    cotangent is the sum over its held slots of their rows'."""
    return jnp.where(valid[:, None], x[src_token], 0).astype(x.dtype)


def _dispatch_fwd(x, src_token, valid, dst, is_held):
    return _dispatch(x, src_token, valid, dst, is_held), (dst, is_held)


def _dispatch_bwd(res, g):
    dst, is_held = res
    dx = jnp.sum(jnp.where(is_held[..., None], g[dst], 0)
                 .astype(jnp.float32), axis=1).astype(g.dtype)
    return dx, None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _weighted_sum(w, rows):
    return jnp.sum(w[..., None] * rows.astype(jnp.float32), axis=1)


@jax.custom_vjp
def _combine(y, w, src, valid, dst):
    """``(T, U)``: token ``t`` gets ``sum_s w[t, s] y[dst[t, s]]``
    (``w`` is zero on a slot that is not held), summed in float32.
    Transposed by gathers: row ``p``'s cotangent is its assignment's
    weight times its token's cotangent."""
    return _weighted_sum(w, y[dst]).astype(y.dtype)


def _combine_fwd(y, w, src, valid, dst):
    rows = y[dst]           # kept: the weights' cotangent reads them again
    return _weighted_sum(w, rows).astype(y.dtype), (rows, w, src, valid)


def _combine_bwd(res, g):
    rows, w, src, valid = res
    k = w.shape[1]
    gy = jnp.where(valid[:, None],
                   w.reshape(-1)[src][:, None] * g[src // k], 0)
    gw = jnp.sum(g[:, None, :].astype(jnp.float32)
                 * rows.astype(jnp.float32), axis=-1)
    return gy.astype(rows.dtype), gw.astype(w.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _tile_einsum(x, w, tile_group, used, tm):
    """:func:`pallas_kernels.grouped_matmul` in plain form: one batched
    einsum over the tiles with each tile's weight gathered."""
    on = (jnp.arange(tile_group.shape[0]) < used[0])[:, None, None]
    y = jnp.einsum("itc,ioc->ito", x.reshape(-1, tm, x.shape[1]),
                   w.astype(x.dtype)[tile_group],
                   preferred_element_type=jnp.float32)
    return jnp.where(on, y, 0).astype(x.dtype).reshape(x.shape[0], -1)


def _tile_product(x, w, tile_group, used, tm):
    """``x``'s row tile ``i`` times ``w[tile_group[i]].T``: the Pallas
    grouped product on the TPU, the plain form off it."""
    from ..ops import pallas_kernels as pk
    if pk._on_tpu():
        return pk.grouped_matmul(x, w, tile_group, used, tm)
    return _tile_einsum(x, w, tile_group, used, tm)


def routed_experts(x, router_w, experts, top_k, held, norm_topk=True):
    """One chip's share of a top-``top_k`` routed expert layer.

    ``x (T, U)`` tokens; ``router_w (E, U)`` the bias-free router over
    ALL ``E`` published experts; ``experts = (gate, up, down)`` the HELD
    experts' SwiGLU weights stacked, ``(count, F, U)``, ``(count, F,
    U)``, ``(count, U, F)``; ``held = (first, count)``: this chip holds
    experts ``first .. first + count - 1``.  Every token is routed over
    all ``E`` (float32 logits and softmax, then the ``top_k`` largest,
    renormalised under ``norm_topk``); the assignments whose expert is
    held are sorted by expert and the three products run as grouped
    products over the sorted rows; each token gets the weighted sum of
    ITS held experts' outputs — the partial result expert parallelism
    would exchange, which on one chip simply goes on.  No assignment is
    ever dropped and nothing is padded to a capacity: the row buffer is
    sized for every assignment being held (:func:`_layout`), and the
    tiles an imbalance leaves unused are skipped by the product.
    ``held = (0, E)`` is the whole layer.  Returns ``(T, U)``."""
    from ..ops import pallas_kernels as pk
    gate, up, down = experts
    first, count = int(held[0]), int(held[1])
    if gate.shape[0] != count or first < 0 \
            or first + count > router_w.shape[0]:
        raise MXNetError(
            "held experts [%d, %d) do not fit %d stacked experts under a "
            "router over %d" % (first, first + count, gate.shape[0],
                                router_w.shape[0]))
    with jax.named_scope(_phases.MOE_SCOPE):
        weights, chosen = _route_top_k(x, router_w, top_k, norm_topk)
        tm = pk.GROUPED_TILE_ROWS
        src, valid, dst, is_held, tile_group, used = _layout(
            chosen, (first, count), tm)
        rows = _dispatch(x, src // top_k, valid, dst, is_held)
        with jax.named_scope(_phases.MOE_EXPERTS_SCOPE):
            product = lambda a, w: _tile_product(a, w, tile_group, used, tm)
            h = jax.nn.silu(product(rows, gate)) * product(rows, up)
            y = product(h, down)
        w = jnp.where(is_held, weights, 0).astype(jnp.float32)
        return _combine(y, w, src, valid, dst)


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
