"""Expert parallelism (MoE): the switch layer over the "ep" mesh axis,
and :func:`routed_experts`, one chip's share of a top-k expert layer
(router, a row buffer sorted by expert, grouped products between a
dispatch and a combine that move only the rows this chip holds).

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

import re

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from .pipeline import stack_stages as stack_experts  # same stacking helper

from ..telemetry import phases as _phases

__all__ = ["switch_moe", "stack_experts", "routed_experts"]

# The name routing's integer results carry (:func:`routed_experts`): the
# chosen experts and the row tables made from them.  An identity until a
# checkpoint's policy asks for it: a rematerialised layer of
# ``gluon.contrib.transformer`` keeps them across its ``jax.checkpoint``
# (about 1.4 MB a layer at 8192 tokens x 8 slots), and the layer run again
# in the backward pass then holds no top-k, no sort and no table work.
ROUTE_KEPT = "moe_route"

# a routing pass in an optimized module: its ``lax.top_k`` under the
# expert layer's scope (``.../mx_moe/top_k``, or ``.../jvp(mx_moe)/top_k``
# where the scope is the outermost under a transform) — a ``sort`` on
# the TPU, a ``TopK`` call on the CPU, a ``topk`` where the backend has
# the instruction
_ROUTE_PASS = re.compile(
    r' (?:(?:sort|topk)\(|custom-call\(.*custom_call_target="TopK")'
    r'.*op_name="[^"]*\b%s\)*/top_k"' % _phases.MOE_SCOPE)


def _kept(a):
    """``a`` under the name ``ROUTE_KEPT``, held FLAT behind a barrier,
    so that what a checkpoint keeps is ``a``'s own bytes.  A name alone
    is an identity to the compiler, which then keeps what suits its
    fusions — the whole ``(T, E)`` index array the top-k's sort wrote,
    sliced to ``(T, k)`` where it is read — and a ``(T, k)`` array in
    the TPU's tiles is padded from ``k`` to 128 lanes."""
    flat = lax.optimization_barrier(a.reshape(-1))
    return checkpoint_name(flat, ROUTE_KEPT).reshape(a.shape)


# an XLA gather under the expert layer's scope, its experts' included
_ROUTE_GATHER = re.compile(r' gather\(.*op_name="[^"]*\b%s\)*/'
                           % _phases.MOE_SCOPE)


def export_route_passes(program, hlo_text):
    """``mxnet_moe_route_passes{program}``: the routing passes — top-k,
    the two sorts, the tables — in a program's OPTIMIZED module
    (``telemetry.program_hlo``), counted by their top-k.  One a routed
    layer where the layer's checkpoint keeps ``ROUTE_KEPT``, two where
    the backward pass routes the tokens again.  Beside it, from the same
    text, ``mxnet_moe_route_gathers{program}``: the XLA ``gather``
    instructions under the expert layer's scope — none, where every
    table, weight and row is moved by a sort, a shift or a select."""
    from .. import telemetry
    if not telemetry.enabled():
        return
    lines = hlo_text.splitlines()
    telemetry.gauge(
        "mxnet_moe_route_passes",
        "top-k instructions under the expert layer's scope in the "
        "optimized module of a registered program: one a routed layer "
        "where the layer's checkpoint keeps the choice and the row "
        "tables, two where the backward pass routes again").labels(
            program=program).set(
                sum(1 for line in lines if _ROUTE_PASS.search(line)))
    telemetry.gauge(
        "mxnet_moe_route_gathers",
        "gather instructions under the expert layer's scope in the "
        "optimized module of a registered program: zero where its "
        "tables, weights and rows move by sort, shift and "
        "select").labels(program=program).set(
            sum(1 for line in lines if _ROUTE_GATHER.search(line)))


def _route_top_k(x, router_w, top_k, norm_topk=True, scoring="softmax",
                 bias=None, scale=1.0):
    """``(weights, experts)``, both ``(T, top_k)``: the router's product
    ``x @ router_w.T`` accumulated in float32 — or ``router_w(x)``, the
    ``(T, E)`` float32 logits of a router given as a function (an MLP) —
    float32 scores over ALL ``E`` experts — their softmax, or under ``scoring``
    "sigmoid" each expert's own sigmoid — then each token's ``top_k``
    largest, renormalised to sum to 1 under ``norm_topk`` and multiplied
    by ``scale``.  A ``bias (E,)`` is added to the scores for the CHOICE
    alone: the weights are the chosen experts' scores without it.

    The weights are always the scores AT the choice, and the choice
    carries the name ``ROUTE_KEPT``: the same numbers as ``lax.top_k``'s
    values bit for bit and the same gradient, so a layer whose
    checkpoint keeps the name runs no top-k again for the sake of its
    values.  They are taken by a masked sum over the experts, not by
    ``take_along_axis``: one fused pass of ``T x top_k x E`` selects
    (0.2 ms at 8192 x 8 x 256 on a v5e) where XLA's gather of 65,536
    elements takes 0.67 ms, and its transpose is the same pass where
    the gather's is a scatter."""
    if scoring not in ("softmax", "sigmoid"):
        raise MXNetError("router scoring is softmax or sigmoid, got %r"
                         % (scoring,))
    # a router given as a function is a Python callable, never a traced
    # array: the test is of its type, static at trace time
    if callable(router_w):
        logits = router_w(x)
    else:
        logits = jnp.einsum("tu,eu->te", x, router_w.astype(x.dtype),
                            preferred_element_type=jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    experts = _kept(lax.top_k(
        scores if bias is None else scores + bias.astype(jnp.float32),
        top_k)[1])
    chosen = experts[..., None] == jnp.arange(scores.shape[-1],
                                              dtype=experts.dtype)
    weights = jnp.sum(jnp.where(chosen, scores[:, None, :], 0), axis=-1)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts


def _select(e_of, table):
    """``table[e_of]`` for a short ``table`` — one value an expert — by
    compare and select, one fused elementwise pass (an XLA gather of
    65,536 elements takes 0.67 ms on a v5e); zero where ``e_of`` names
    no entry."""
    out = jnp.zeros(e_of.shape, table.dtype)
    for e in range(table.shape[0]):
        out = jnp.where(e_of == e, table[e], out)
    return out


def _to_buffer(v, runs, rows):
    """``v (A,)`` in sorted order, laid out as the buffer's ``rows``:
    expert ``e``'s run ``v[starts[e] : starts[e] + sizes[e]]`` from row
    ``row0[e]`` on, zero on the padding rows.  A run is ``v`` shifted
    right by ``row0[e] - starts[e]`` (less than ``rows - A``): one
    ``dynamic_slice`` an expert of ``v`` padded on both sides, selected
    where the row lies in the run — no gather."""
    starts, row0, sizes = runs
    pad = jnp.zeros((rows - v.shape[0],), v.dtype)
    wide = jnp.concatenate([pad, v, pad])
    p = jnp.arange(rows, dtype=jnp.int32)
    out = jnp.zeros((rows,), v.dtype)
    for e in range(starts.shape[0]):
        run = lax.dynamic_slice(wide, (pad.shape[0] - row0[e] + starts[e],),
                                (rows,))
        out = jnp.where((p >= row0[e]) & (p < row0[e] + sizes[e]), run, out)
    return out


def _from_buffer(u, runs, a):
    """:func:`_to_buffer` undone: the buffer's rows ``u`` back in sorted
    order, ``(a,)``, each run shifted left by ``row0[e] - starts[e]``;
    zero past the held assignments.  ``u`` is held flat behind a
    barrier: the row mover writes it as a ``(P, 1)`` column, and XLA
    would otherwise slice the column, each ``(a, 1)`` slice padded to
    128 lanes on the TPU (33.5 MB at 65,536 rows)."""
    starts, row0, sizes = runs
    u = lax.optimization_barrier(u)
    q = jnp.arange(a, dtype=jnp.int32)
    out = jnp.zeros((a,), u.dtype)
    for e in range(starts.shape[0]):
        run = lax.dynamic_slice(u, (row0[e] - starts[e],), (a,))
        out = jnp.where((q >= starts[e]) & (q < starts[e] + sizes[e]), run,
                        out)
    return out


def _to_places(at, v):
    """``out[at[j]] = v[j]`` for a permutation ``at``: one sort of ``v``
    keyed by ``at`` (about 0.09 ms at 65,536 keys on a v5e), where an XLA
    gather by the inverse permutation takes 0.67."""
    return lax.sort((at, v), num_keys=1)[1]


def _layout(experts, held, tm):
    """Where every (token, slot) assignment goes.  The assignments whose
    expert is held, sorted by expert, fill a row buffer in which every
    expert's rows start at a multiple of ``tm``: an expert's end is
    padded to its tile with zero rows (never to a capacity: an expert
    takes as many tiles as its rows need, one if it has none), and the
    tiles after the last expert's are unused.  The buffer is sized for
    the worst case, every assignment held: ``ceil(T k / tm) + count``
    tiles.  Returns int32 arrays: ``src (P,)`` the flat assignment a
    row holds (0 on padding), ``valid (P,)``, ``dst (T, k)`` an
    assignment's row (0 where it is not held), ``is_held (T, k)``,
    ``tile_group (tiles,)``, ``used (1,)``, ``counts (tiles,)``: a
    tile's valid rows are its first ``counts[i]`` (none past the used
    tiles); ``order (T k,)`` the assignments in sorted order, ``rank (T
    k,)`` its inverse, and ``runs (3, count)``: each expert's ``starts``
    in sorted order, ``row0`` in the buffer, ``sizes``.

    No table is read by a gather: the per-expert ones by compare and
    select over the ``count`` experts (:func:`_select`), ``order`` into
    the buffer by one shift an expert (:func:`_to_buffer`)."""
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
    runs = jnp.stack([starts, row0, sizes]).astype(jnp.int32)
    i = jnp.arange(tiles, dtype=jnp.int32)
    tile_group = jnp.minimum(jnp.sum(i[:, None] >= tile_ends, axis=1,
                                     dtype=jnp.int32), count - 1)
    counts = jnp.where(i < used[0], jnp.clip(
        _select(tile_group, row0 + sizes) - i * tm, 0, tm), 0)
    src = _to_buffer(order, runs, tiles * tm)
    valid = _to_buffer(jnp.ones((a,), jnp.int32), runs, tiles * tm) > 0
    dst = jnp.where(is_held.reshape(a), rank + _select(key, row0 - starts),
                    0)
    return (src, valid, dst.reshape(t, k), is_held, tile_group,
            used.astype(jnp.int32), counts.astype(jnp.int32), order, rank,
            runs)


def _gather_rows(x, src_token, counts, used, tm, scale=None, y=None):
    """:func:`pallas_kernels.moe_rows` in plain form: one gather over
    the whole static buffer.  (It writes the tiles past ``used`` too, as
    zeros; nothing reads them.)"""
    p = src_token.shape[0]
    valid = jnp.arange(p, dtype=jnp.int32) % tm < jnp.repeat(counts, tm)
    rows = jnp.where(valid[:, None], x[src_token], 0)
    out = rows if scale is None else scale[:, None] * rows
    if y is None:
        return out.astype(x.dtype)
    return out.astype(x.dtype), jnp.sum(
        rows.astype(jnp.float32) * y.astype(jnp.float32), axis=-1)


def _gather_slots(ys, dst, w, is_held, used, tm):
    """:func:`pallas_kernels.moe_slots` in plain form: the buffers ``ys``
    (a tuple) summed over their whole static shape, every slot's row
    gathered, ``(T, k, U)``, and summed over the slots in float32."""
    y = sum(ys[1:], ys[0])
    rows = jnp.where(is_held[..., None], y[dst], 0).astype(jnp.float32)
    return jnp.sum(w[..., None] * rows, axis=1).astype(y.dtype)


def _movers_run(units, dtype):
    from ..ops import pallas_kernels as pk
    return pk._on_tpu() and pk.row_words_ok(units, dtype)


def _move_rows(x, *args, **kwargs):
    """Tokens' rows into the buffer: the Pallas row mover on the TPU
    (only the valid rows of the used tiles are fetched), the plain
    gather off it."""
    from ..ops import pallas_kernels as pk
    if _movers_run(x.shape[1], x.dtype):
        return pk.moe_rows(x, *args, **kwargs)
    return _gather_rows(x, *args, **kwargs)


def _move_slots(ys, *args):
    """The rows of the buffer ``sum(ys)`` (a tuple) back to their
    tokens: the Pallas slot mover on the TPU (only the held slots' rows
    are fetched), the plain gather off it."""
    from ..ops import pallas_kernels as pk
    if _movers_run(ys[0].shape[1], ys[0].dtype):
        return pk.moe_slots(ys, *args)
    return _gather_slots(ys, *args)


@jax.custom_vjp
def _dispatch(x, src_token, counts, used, dst, is_held):
    """``(P, U)``, handed out TWICE (one array: the gate's and the up
    product's operand): row ``p`` is token ``src_token[p]``'s state,
    zero where the row is padding (a tile's rows past its ``counts``).
    Its transpose moves rows too: a token's cotangent is the sum over
    its held slots of their rows' — and the two products' cotangents
    come back apart, to be summed where the mover reads them, over the
    used tiles alone, where jax would add the two static buffers whole."""
    tm = src_token.shape[0] // counts.shape[0]
    rows = _move_rows(x, src_token, counts, used, tm)
    return rows, rows


def _dispatch_fwd(x, src_token, counts, used, dst, is_held):
    return (_dispatch(x, src_token, counts, used, dst, is_held),
            (dst, is_held, used, counts))


def _dispatch_bwd(res, gs):
    dst, is_held, used, counts = res
    dx = _move_slots(tuple(gs), dst, is_held.astype(jnp.float32), is_held,
                     used, gs[0].shape[0] // counts.shape[0])
    return dx, None, None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, w, src, counts, used, dst, is_held, order, rank, runs):
    """``(T, U)``: token ``t`` gets ``sum_s w[t, s] y[dst[t, s]]`` over
    its held slots, summed in float32.  Transposed by moving rows the
    other way: row ``p``'s cotangent is its assignment's weight times
    its token's cotangent, and a weight's is the dot of its row with its
    token's cotangent (taken on the buffer's side, a number a row).  The
    weights reach the rows, and the dots the assignments, by a sort
    (``order``, ``rank``) and a shift an expert (``runs``), no gather."""
    return _move_slots((y,), dst, w, is_held, used,
                       y.shape[0] // counts.shape[0])


def _combine_fwd(y, w, src, counts, used, dst, is_held, order, rank, runs):
    return (_combine(y, w, src, counts, used, dst, is_held, order, rank,
                     runs),
            (y, w, src, counts, used, dst, is_held, order, rank, runs))


def _combine_bwd(res, g):
    y, w, src, counts, used, dst, is_held, order, rank, runs = res
    t, k = w.shape
    scale = _to_buffer(_to_places(rank, w.reshape(-1)), runs, y.shape[0])
    gy, dots = _move_rows(g.astype(y.dtype), src // k, counts, used,
                          y.shape[0] // counts.shape[0], scale=scale, y=y)
    gw = _to_places(order, _from_buffer(dots, runs, t * k)).reshape(t, k)
    return (gy, jnp.where(is_held, gw, 0).astype(w.dtype)) + (None,) * 8


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


def routed_experts(x, router_w, experts, top_k, held, norm_topk=True,
                   scoring="softmax", bias=None, scale=1.0):
    """One chip's share of a top-``top_k`` routed expert layer.

    ``x (T, U)`` tokens; ``router_w (E, U)`` the bias-free router over
    ALL ``E`` published experts, or a function of ``x`` that gives their
    ``(T, E)`` float32 logits and has that ``shape (E, U)`` (a router
    that is an MLP: it runs under this layer's scope); ``experts =
    (gate, up, down)`` the HELD
    experts' SwiGLU weights stacked, ``(count, F, U)``, ``(count, F,
    U)``, ``(count, U, F)``; ``held = (first, count)``: this chip holds
    experts ``first .. first + count - 1``.  Every token is routed over
    all ``E`` (float32 logits and scores — ``scoring`` "softmax" over
    all of them or each expert's "sigmoid" — then the ``top_k`` largest,
    chosen by score plus ``bias (E,)`` where one is given and weighed by
    the score without it, renormalised under ``norm_topk``, times
    ``scale``: :func:`_route_top_k`); the assignments whose expert is
    held are sorted by expert and the three products run as grouped
    products over the sorted rows; each token gets the weighted sum of
    ITS held experts' outputs — the partial result expert parallelism
    would exchange, which on one chip simply goes on.  No assignment is
    ever dropped and nothing is padded to a capacity: the row buffer is
    sized for every assignment being held (:func:`_layout`), and the
    tiles an imbalance leaves unused are skipped by the product — and,
    on the TPU, by dispatch and combine too: a chip that holds a quarter
    of the experts fills a quarter of the buffer and a quarter of a
    token's slots, and the row movers (``pallas_kernels.moe_rows`` /
    ``moe_slots``) fetch those rows alone, forward and backward, where a
    gather copies the whole static shape.  They leave the unused tiles
    UNWRITTEN: whatever reads the buffer skips them by ``used``.
    ``held = (0, E)`` is the whole layer.  Returns ``(T, U)``.

    Routing's integer results — the choice and, of :func:`_layout`'s
    tables, the ones used here — carry the name ``ROUTE_KEPT``, and the
    weights are the scores at the named choice (:func:`_route_top_k`):
    under a ``jax.checkpoint`` whose policy
    keeps the name, the layer run again in the backward pass reads them
    and runs no top-k, no sort and no table work; under any other
    caller the names are identities."""
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
        weights, chosen = _route_top_k(x, router_w, top_k, norm_topk,
                                       scoring, bias, float(scale))
        tm = pk.GROUPED_TILE_ROWS
        src, _, *tables = _layout(chosen, (first, count), tm)
        src, dst, is_held, tile_group, used, counts, order, rank, runs = (
            _kept(a) for a in (src, *tables))
        # lax's own operations on the kept values, not jnp's jitted
        # wrappers: a checkpoint that keeps a jitted function's operand
        # keeps its result beside it
        rows, rows_again = _dispatch(x, lax.div(src, jnp.int32(top_k)),
                                     counts, used, dst, is_held)
        with jax.named_scope(_phases.MOE_EXPERTS_SCOPE):
            product = lambda a, w: _tile_product(a, w, tile_group, used, tm)
            h = jax.nn.silu(product(rows, gate)) * product(rows_again, up)
            y = product(h, down)
        w = lax.select(is_held, weights,
                       jnp.zeros_like(weights)).astype(jnp.float32)
        return _combine(y, w, src, counts, used, dst, is_held, order,
                        rank, runs)


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
