"""Pallas TPU kernels for hot paths where XLA fusion is not enough.

SURVEY.md §2.5/§7 names these the north star for the operator library's
hot paths — the MPK mega-kernel thesis (PAPERS.md) applied to this
tree's step function.  The catalog (docs/faq/perf.md has the
when-does-it-fuse table and the ``MXNET_PALLAS_*`` knobs):

- ``flash_attention`` — blockwise online-softmax attention (forward and
  backward), the kernel behind long-context attention: O(T) memory
  instead of XLA's materialized (T, T) logits.  The score blocks are
  chosen per kernel from the shape (``_flash_blocks``: the largest
  whole-tile divisors of T, up to 1024 rows a side, that fit the
  scoped-VMEM budget); the causal schedule fetches and runs nothing
  for a block above the diagonal, masks only the blocks the diagonal
  crosses, and in the backward works a square diagonal block in slabs,
  so its upper half costs no product and no exponential.  With a
  ``window`` (a query sees its last ``window`` keys) the visible band has
  a lower edge too: a block wholly below it is neither fetched nor run
  either, and only the blocks an edge crosses build a mask.  This is the
  per-device block kernel of ring/Ulysses sequence parallelism
  (parallel/attention.py); reference long-sequence analogue: the fused
  cuDNN RNN workspace kernels (src/operator/cudnn_rnn-inl.h).
- ``grouped_matmul`` — the sparse experts' product: rows sorted by
  group, each group's rows starting at a row tile (``parallel/moe.py``
  lays them out), so a tile belongs to one group and its weight block
  is named by a scalar-prefetched table, tile -> group; forward, the
  rows' gradient and the weights' gradient (a group's block resident
  over its run of tiles).  Tiles past the used ones run nothing.
- ``moe_rows`` / ``moe_slots`` — the sparse experts' dispatch and
  combine as row movers: one DMA for each row that is HELD (a valid row
  of a used tile; a held slot of a token), none for the rest of the
  static worst-case shapes an XLA gather would copy whole; the
  token-side one sums a token's rows in float32 as they land, the
  buffer-side one can scale a row and dot it with the matching row of a
  second buffer (the combine's transpose).  ``_moe_words_kernel`` lays a
  source out as 32-bit words a row first, since Mosaic slices a DMA
  along whole tiles only.
- ``head_cross_entropy`` — the fused head, a projection with its
  softmax cross-entropy: a (row block, vocabulary block) of the logits
  lives in VMEM, never in HBM.  The forward keeps an online row max and
  sum of exponentials and picks the label's logit; the backward makes
  the block again from ``lse``, sums the softmax's gradient times the
  vocabulary block into the rows' gradient and writes that gradient
  once, in the weights' dtype, for the weights' product.  Taken by
  ``F.contrib.linear_cross_entropy`` unweighted on the TPU.
- ``fused_scale_bias_relu`` — the inference BatchNorm + ReLU epilogue as
  one VMEM-resident pass (reference: the BN+Activation fusion MKL-DNN
  does on CPU, nn/mkldnn/mkldnn_base-inl.h).  Call sites: the
  ``_contrib_fused_bn_relu`` operator and the executor's inference
  BatchNorm→Activation peephole (symbol.py ``build_graph_fn``).
- ``fused_sgd_momentum`` / ``fused_adam`` — the one-sweep fused
  optimizer: an ENTIRE bucket (params, grads and optimizer slots as
  same-layout buffers: a flat 1-D fusion of small leaves, or one
  matrix in its own ``(rows, C)`` layout) updated in one VMEM-resident
  pass, grid over row blocks.  Hyperparameters (lr/momentum/betas/wd/
  clip) ride ONE scalar-prefetch operand, so an lr-schedule change is a
  new argument value, not a new XLA program.  The kernel math mirrors
  ``parallel/optimizer.py`` / ``ops/optimizer_ops.py`` expression by
  expression — the per-array ``tree_map`` path is the bit-parity oracle
  (tests/test_pallas.py asserts exact equality, padded tails included).
- ``fused_layernorm`` — mean/var/normalize/affine in one pass per row
  block (vs XLA's multi-kernel reduction chain), custom_vjp backward
  with the dx kernel fused the same way.
- ``fused_bias_softmax`` — additive-bias (mask) + max + exp + normalize
  in one pass; forward of the non-flash attention path and the
  SoftmaxOutput core, custom_vjp backward fused as well.

All kernels run natively on TPU and in `interpret=True` mode everywhere
else (CPU tests exercise the same kernel code paths); every wrapper
counts into ``mxnet_pallas_kernel_calls_total{kernel=...}`` (counted at
trace/call time — inside jit a kernel is traced once per program, then
replayed by XLA with no Python in the loop).

Layout note: per-row softmax stats (m, l, lse, delta) are stored with a
trailing 128-lane dim, every lane holding the same value — the Mosaic
tiling constraint (last two block dims divisible by (8, 128)) forbids
1-D row vectors, and this is the same convention jax's in-tree flash
kernel uses.  The dK/dV kernel alone reads ``lse`` and ``delta`` as
lane-dense rows, one (1, bq) row a Q block: it holds its score tile
transposed.
"""
from __future__ import annotations

import functools
import logging
import math
import re

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _count(kernel):
    """Advance ``mxnet_pallas_kernel_calls_total{kernel=...}``.

    Trace-time accounting: under jit each wrapper runs once per traced
    program (XLA replays the kernel with no Python after that), eagerly
    once per call — either way the counter says which kernels a run
    actually instantiated, the observability leg of the mega-kernel
    claim (docs/faq/perf.md)."""
    from .. import telemetry
    if telemetry.enabled():
        telemetry.counter(
            "mxnet_pallas_kernel_calls_total",
            "Pallas kernel instantiations by kernel name (trace/call "
            "time: one per traced program under jit, one per call "
            "eagerly)").labels(kernel=kernel).inc()


def _knob(name):
    from .. import config as _config
    return _config.get(name)


def family_enabled(knob):
    """Resolve a tri-state ``MXNET_PALLAS_*`` family knob.

    ``auto`` (the default) enables the family only where the kernels
    compile natively — on TPU; everywhere else the XLA-fused fallback
    paths are already the fast form and routing them through the
    ``interpret=True`` emulation would be a hot-path regression (the
    same backend gate flash attention's ``impl="auto"`` applies).
    ``1`` forces the family on anywhere (how CPU tier-1 exercises the
    kernel code paths in interpret mode); ``0`` disables it."""
    v = _knob(knob)
    if v is None or str(v).lower() in ("", "auto"):
        return _on_tpu()
    return str(v).lower() not in ("0", "false")


_SWEEP_SHARD_VERDICT = None


def _sweep_shard_verdict():
    """Cached graftkern ``kern-shard-safety`` verdict for the sweep
    family (analysis/kern/): True only when every sweep kernel's index
    maps are provably block-local along the sharded rows axis, i.e.
    wrapping the sweep in ``shard_map`` cannot read or write across
    shards.  Unprovable (or any analysis failure) degrades to False —
    the tree_map fallback, never an unsound fused path."""
    global _SWEEP_SHARD_VERDICT
    if _SWEEP_SHARD_VERDICT is None:
        try:
            from ..analysis.kern import sweep_shard_verdict
            _SWEEP_SHARD_VERDICT = bool(sweep_shard_verdict()["safe"])
        except Exception:
            # an analysis crash is reported, not swallowed: the price
            # of the fallback is every multi-chip bucket updating
            # through tree_map
            logging.getLogger(__name__).exception(
                "graftkern sweep-shard analysis failed; multi-chip "
                "fused sweep disabled (tree_map fallback)")
            _SWEEP_SHARD_VERDICT = False
    return _SWEEP_SHARD_VERDICT


def mesh_sweep_safe(mesh_size):
    """Whether the one-sweep optimizer may run over buffers sharded
    across ``mesh_size`` devices.  The native Mosaic custom call has NO
    GSPMD partitioning rule — inside a multi-chip pjit step XLA would
    all-gather every bucket to full size per chip (or fail to lower),
    forfeiting the ZeRO 1/mesh contract.  The multi-chip answer is the
    ``shard_map`` wrap in :func:`_sweep_call` (each chip sweeps its
    contiguous 1/mesh shard), which is sound exactly when graftkern's
    ``kern-shard-safety`` verdict proves the kernels block-local along
    the sharded rows axis — so multi-chip is allowed iff that verdict
    holds, not by a hardcoded flag."""
    return _interpret() or int(mesh_size) <= 1 \
        or _sweep_shard_verdict()


def _on_tpu():
    return jax.default_backend() == "tpu"


def _interpret():
    return not _on_tpu()


NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------
# One schedule for the three kernels.  The score matrix of a (batch, head)
# is cut into (bq, bk) blocks chosen from the shape (``_flash_blocks``); a
# grid step owns one block.  Under the causal mask a block lies wholly
# under the diagonal (no mask built), across it (the only blocks that
# build one) or wholly above it: those steps run no body and, because the
# index maps clamp to the last block the row (column) needs, fetch
# nothing — consecutive steps name the same block and Pallas skips the
# copy.
#
# What the backward reads of the forward is ``(q, k, v, o, lse)``.  The
# forward kernel's two results carry names (``FLASH_KEPT``), identities
# until a checkpoint's policy asks for them: a rematerialised layer of
# ``gluon.contrib.transformer`` keeps them across its ``jax.checkpoint``
# and runs everything else again, so the layer run again holds no flash
# forward — the kernel would only re-make ``o`` and ``lse``.  Such a
# caller says ``kept=True`` and ``lse`` is held as ONE float32 a row,
# ``(BH, T)``, not as the kernel's lane-broadcast ``(BH, T, 128)``: at
# 32 x 8192 that is 1 MB beside the output's 67 MB, where the broadcast
# is 134 MB; dQ is then handed a broadcast of it, as it is of ``delta``.
# Every other caller holds the kernel's own output, which dQ reads as it
# is: the broadcast is a pass a layer (1 ms a step of the looped LM's 36
# layer applications, PERF.md, PR 35) that only a kept ``lse`` pays for.

# index-map arithmetic that serves traced grid indices and the plain
# Python ints graftkern evaluates the same maps with (analysis/kern/)
def _idiv(a, b):
    return a // b if isinstance(a, int) else jax.lax.div(a, b)


def _imin(a, b):
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _imax(a, b):
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _isel(cond, a, b):
    return (a if cond else b) if isinstance(cond, bool) \
        else jnp.where(cond, a, b)


def _iand(a, b):
    return (a and b) if isinstance(a, bool) and isinstance(b, bool) \
        else jnp.logical_and(a, b)


def _causal_last_k(i, bq, bk):
    """Last K block a causal Q block ``i`` has a visible key in."""
    return _idiv(i * bq + bq - 1, bk)


def _causal_first_q(j, bq, bk):
    """First Q block that sees a key of causal K block ``j``."""
    return _idiv(j * bk, bq)


def _window_first_k(i, bq, bk, window):
    """First K block in which the first row of causal Q block ``i``
    still sees a key, under a window of ``window`` keys a query."""
    return _idiv(_imax(i * bq - (window - 1), 0), bk)


def _window_last_q(j, bq, bk, window):
    """Last Q block in which a query still sees the last key of K
    block ``j`` (the caller holds it under the number of Q blocks)."""
    return _idiv(j * bk + bk - 1 + window - 1, bq)


_DIAG = "diag"        # mask tag: the slab's own square lies on the diagonal


# The block-diffusion mask (Arriola et al., arXiv:2503.09573, section 4):
# the T = 2 L rows are a noised copy of a sequence and then its clean
# copy, both cut into blocks of ``length`` positions.  A noised row sees
# its own noised block (both ways) and the clean blocks BEFORE its own; a
# clean row sees the clean blocks up to and with its own; no clean row
# sees a noised one.  With square tiles of ``b`` rows that divide L and
# hold whole blocks, ``n = L // b`` tiles a half, a Q tile's visible keys
# are NOT one band of K tiles: noised Q tile i has its own tile (a
# block diagonal: _BD_SELF) and the clean tiles n .. n + i, the last of
# them cut by the block diagonal (_BD_DIAG, strictly below it); clean Q
# tile n + i has the clean tiles n .. n + i (_BD_DIAG, the diagonal's
# blocks with it).  So the grid's minor axis counts VISITS, not tiles:
# ``_bd_q_visit`` / ``_bd_k_visit`` name the tile a step visits and what
# lies in it, and hold at the last visited tile once a row (column) has
# none left — no body, no fetch.  n (n + 2) of the (2 n)^2 tiles run.
_BD_NONE, _BD_FULL, _BD_SELF, _BD_DIAG = 0, 1, 2, 3


class _BlockMask:
    """Mask tag of a square that lies on the block-diffusion mask's
    diagonal: query row r sees key c iff ``r // length - c // length``
    is 0 (``lo`` None: a block sees itself, both ways) or at least
    ``lo`` (0: the blocks up to its own; 1: the blocks before it)."""

    def __init__(self, length, lo):
        self.length, self.lo = length, lo

    def blocks(self, a):
        length = self.length
        if length & (length - 1) == 0:
            return jax.lax.shift_right_logical(a, length.bit_length() - 1)
        return jax.lax.div(a, length)


def _bd_q_visit(i, s, n):
    """``(K tile, case, lo)`` of visit ``s`` (of ``n + 1``) of Q tile
    ``i`` under the block-diffusion mask, ``n`` tiles a half.  A noised
    tile visits its own tile first — every row has a key there, so the
    forward's running maximum is finite from the first visit on."""
    noised = i < n
    p = _isel(noised, i, i - n)
    c = _isel(noised, s - 1, s)         # the clean tile this visit is for
    own = _iand(noised, s == 0)
    tile = _isel(own, i, n + _imin(_imax(c, 0), p))
    case = _isel(own, _BD_SELF,
                 _isel(c < p, _BD_FULL, _isel(c == p, _BD_DIAG, _BD_NONE)))
    return tile, case, _isel(noised, 1, 0)


def _bd_k_visit(j, s, n):
    """``(Q tile, case, lo)`` of visit ``s`` (of ``2 n``) of K tile
    ``j``: a noised tile is seen by its own Q tile alone; clean tile
    ``n + p`` by the noised Q tiles ``p .. n - 1`` and then by the clean
    ones ``n + p .. 2 n - 1``, the first of each cut by the diagonal."""
    p = j - n
    m = n - p                           # Q tiles of each half that see it
    first = s < m
    tile = _isel(j < n, j,
                 _isel(first, p + s, _imin(n + p + s - m, 2 * n - 1)))
    case = _isel(
        j < n, _isel(s == 0, _BD_SELF, _BD_NONE),
        _isel(first, _isel(s == 0, _BD_DIAG, _BD_FULL),
              _isel(s < 2 * m, _isel(s == m, _BD_DIAG, _BD_FULL),
                    _BD_NONE)))
    return tile, case, _isel(first, 1, 0)


def _bd_tiles(n, length, b):
    """``(tiles run, tiles that hold a visible pair)`` of one (batch,
    head) under the block-diffusion mask — the same for the three
    kernels.  They differ only where a tile is one block: the tile
    strictly below the diagonal is then visited and empty."""
    run = n * (n + 2)
    return run, run - (n if b <= length else 0)


def _flash_block_diffusion_cases(case, lo, b, length, update, k_major=False,
                                 slabs=True):
    """Drive ``update(q_rows, k_rows, mask)`` over one visited tile of
    the block-diffusion mask (``b`` rows square, whole blocks of
    ``length``).  _BD_FULL: the whole tile, no mask.  _BD_SELF (noised
    rows against their own noised tile): only the blocks on the diagonal
    are visible, so the tile is worked in squares of one lane tile along
    it — an eighth of a 1024-row tile's products — each under a block
    mask.  _BD_DIAG (the clean tile at a row's own position): the slabs
    of :func:`_flash_block_cases`' diagonal, the slab's own square under
    the block mask ``lo``."""
    full = slice(None)
    pl.when(case == _BD_FULL)(lambda: update(full, full, None))

    @pl.when(case == _BD_SELF)
    def _self():
        r = LANES if b % LANES == 0 and LANES % length == 0 else b
        for i in range(b // r):
            own = slice(i * r, (i + 1) * r)
            update(own, own, _BlockMask(length, None))

    @pl.when(case == _BD_DIAG)
    def _diagonal():
        n = _flash_slabs(b) if slabs else 1
        n = n if (b // n) % length == 0 else 1
        r = b // n
        for i in range(n):
            own = slice(i * r, (i + 1) * r)
            if k_major:
                update(slice(i * r, b), own, _BlockMask(length, lo))
            else:
                update(own, slice(0, (i + 1) * r), _BlockMask(length, lo))


def _flash_slabs(b):
    """Slabs the backward kernels work a diagonal block of ``b`` rows
    in (below): four or two, each a whole number of lane tiles, else
    the block whole.  On the chip at 1024 rows four beat two beat one
    by a tenth each time for dQ and dK/dV; the forward, which carries
    its row statistics through every slab, is fastest with the block
    whole (PERF.md, PR 28)."""
    return 4 if b % (4 * LANES) == 0 else 2 if b % (2 * LANES) == 0 else 1


def _flash_block_cases(causal, qi, ki, bq, bk, update, k_major=False,
                       slabs=True, window=None):
    """Drive ``update(q_rows, k_rows, mask)`` over score block (qi, ki).

    Every key visible (no mask, or the block wholly under the
    diagonal): one update of the whole block, ``mask`` None.  The block
    wholly above the diagonal: nothing.  The diagonal crosses it: the
    only updates that build a mask.  A square block on the diagonal
    (bq == bk: its offset is 0, known at trace time) is, with
    ``slabs``, worked in slabs — of Q rows against the keys up to the
    slab's own square (of K rows against the queries from it on, for
    dK/dV: ``k_major``) — so the half above the diagonal costs no
    product and no exponential, and only the slab's square (``mask``
    _DIAG) is masked.  Any other
    crossing block takes the whole-block update with the diagonal's
    offset as ``mask``: query row r sees key c iff r - c >= mask.

    Under a ``window`` (causal, each query sees its last ``window``
    keys, itself among them) the visible band has a lower edge too:
    query row r sees key c iff ``shift <= r - c <= shift + window - 1``.
    A block wholly inside the band takes the unmasked update, one wholly
    outside it (above the diagonal or below the window) nothing, any
    other the whole-block update with ``mask`` the pair of edges."""
    full = slice(None)
    if not causal:
        update(full, full, None)
        return
    shift = ki * bk - qi * bq
    if window is not None:
        inside = jnp.logical_and(shift + bk - 1 <= 0, shift >= bq - window)
        outside = jnp.logical_or(shift > bq - 1,
                                 shift + bk - 1 + window - 1 < 0)
        pl.when(inside)(lambda: update(full, full, None))
        pl.when(jnp.logical_not(jnp.logical_or(inside, outside)))(
            lambda: update(full, full, (shift, shift + window - 1)))
        return
    pl.when(shift + bk - 1 <= 0)(lambda: update(full, full, None))
    crossing = jnp.logical_and(shift + bk - 1 > 0, shift <= bq - 1)
    if bq != bk:
        pl.when(crossing)(lambda: update(full, full, shift))
        return

    @pl.when(crossing)
    def _diagonal():
        n = _flash_slabs(bq) if slabs else 1
        r = bq // n
        for i in range(n):
            own = slice(i * r, (i + 1) * r)
            if k_major:
                update(slice(i * r, bq), own, _DIAG)
            else:
                update(own, slice(0, (i + 1) * r), _DIAG)


def _flash_scores(a, b, scale, mask, rows_are_q):
    """float32 scores ``a @ b.T * scale`` of a block or slab, the
    invisible keys at NEG_INF.  Rows are queries and columns keys, or
    the transpose (dK/dV).  ``mask``: None, the diagonal's offset, a
    pair ``(lo, hi)`` — a window's band, query row r sees key c iff
    ``lo <= r - c <= hi`` — or _DIAG: the square at the end of a Q
    slab's keys (at the start of a K slab's queries) is the one on the
    diagonal; a :class:`_BlockMask` is that square under the
    block-diffusion mask's rule."""
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask is None:
        return s
    if isinstance(mask, tuple):
        r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        d = r - c if rows_are_q else c - r
        return jnp.where(jnp.logical_and(d >= mask[0], d <= mask[1]),
                         s, NEG_INF)
    n = s.shape[0]
    blocks = isinstance(mask, _BlockMask)
    sq = s if not (blocks or mask is _DIAG) \
        else s[:, -n:] if rows_are_q else s[:, :n]
    r = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 1)
    if blocks:
        # the square's origin holds whole blocks on both sides
        r, c = mask.blocks(r), mask.blocks(c)
    d = r - c if rows_are_q else c - r
    sq = jnp.where(d == 0 if blocks and mask.lo is None else
                   d >= (mask.lo if blocks else 0 if mask is _DIAG else mask),
                   sq, NEG_INF)
    if sq.shape == s.shape:
        return sq
    return jnp.concatenate([s[:, :-n], sq] if rows_are_q
                           else [sq, s[:, n:]], axis=1)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, *, scale, causal, bq, bk, nk, window=None,
                      blocks=None):
    """Grid (BH, nQ, nK); accumulate across the sequential nK dimension in
    VMEM scratch, finalize on the last K step (the canonical online-
    softmax schedule).  ``blocks``: ``(length, tiles a half)`` of the
    block-diffusion mask, under which the minor axis counts a Q tile's
    visits (``nk`` of them)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _update(qs, ks, mask):
        v = v_ref[ks]
        s = _flash_scores(q_ref[qs], k_ref[ks], scale, mask, True)
        m_prev = m_ref[qs, :1]                          # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)                 # (rows, 1)
        l_new = l_ref[qs, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[qs] = acc_ref[qs] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[qs] = jnp.broadcast_to(m_new, (m_new.shape[0], LANES))
        l_ref[qs] = jnp.broadcast_to(l_new, (l_new.shape[0], LANES))

    if blocks is not None:
        _tile, case, lo = _bd_q_visit(qi, ki, blocks[1])
        _flash_block_diffusion_cases(case, lo, bq, blocks[0], _update,
                                     slabs=False)
    else:
        _flash_block_cases(causal, qi, ki, bq, bk, _update, slabs=False,
                           window=window)

    @pl.when(ki == nk - 1)
    def _final():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[:] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, scale, causal, bq, bk, nk,
                         window=None, blocks=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _update(qs, ks, mask):
        k = k_ref[ks]
        s = _flash_scores(q_ref[qs], k, scale, mask, True)
        p = jnp.exp(s - lse_ref[qs, :1])
        dp = jax.lax.dot_general(do_ref[qs], v_ref[ks],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[qs, :1]) * scale
        acc_ref[qs] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if blocks is not None:
        _tile, case, lo = _bd_q_visit(qi, ki, blocks[1])
        _flash_block_diffusion_cases(case, lo, bq, blocks[0], _update)
    else:
        _flash_block_cases(causal, qi, ki, bq, bk, _update, window=window)

    @pl.when(ki == nk - 1)
    def _final():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                          bq, bk, nq, window=None, blocks=None, group=1):
    """Grid (BH, nK, nQ) — under the block-diffusion mask ``blocks`` the
    minor axis counts a K tile's ``nq`` visits; with ``group`` query
    heads a key/value head, grid (BH / group, nK, group nQ): the minor
    axis visits each query head of the group in turn, ``nq`` visits a
    head, all summed into the one float32 accumulator.  The block is
    held transposed, (bk, bq):
    ``lse`` and ``delta`` arrive as rows (1, bq), so the scores, ``p``
    and ``ds`` come out of plain products in the orientation the dV and
    dK products consume — no transpose of a score tile."""
    ki = pl.program_id(1)
    visit = pl.program_id(2)
    qi = visit if group == 1 else jax.lax.rem(visit, nq)

    @pl.when(visit == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _update(qs, ks, mask):
        q = q_ref[qs]
        do = do_ref[qs]
        st = _flash_scores(k_ref[ks], q, scale, mask, False)
        pt = jnp.exp(st - lse_ref[:, qs])               # (rows of K, Q)
        dv_acc[ks] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[ks], do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[:, qs]) * scale
        dk_acc[ks] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if blocks is not None:
        _tile, case, lo = _bd_k_visit(ki, qi, blocks[1])
        _flash_block_diffusion_cases(case, lo, bq, blocks[0], _update,
                                     k_major=True)
    else:
        _flash_block_cases(causal, qi, ki, bq, bk, _update, k_major=True,
                           window=window)

    @pl.when(visit == group * nq - 1)
    def _final():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _pick_block(t, pref):
    b = min(pref, t)
    while t % b:
        b //= 2
    return max(b, 1)


def _sublane(dtype):
    """Rows of one native VMEM tile: 8 for 4-byte elements, 16 for
    bf16/f16, 32 for 1-byte types (narrow types pack along sublanes).
    A row-block must be a multiple of it or span the whole array."""
    return 32 // jnp.dtype(dtype).itemsize


def flash_seq_ok(t, dtype, pref=128):
    """Whether :func:`flash_attention` can tile a length-``t`` sequence
    of ``dtype`` natively: the halving block choice must land on whole
    sublane tiles (or on the whole sequence)."""
    b = _pick_block(t, pref)
    return b == t or b % _sublane(dtype) == 0


def _export_flash_gauges(kernel, bq, bk, grid, window=None,
                         block_diffusion=None, group=1):
    """What the newest instantiation of ``kernel`` was tiled into (trace
    time, beside ``_count``), and how many query heads it gave each
    key/value head (``group``); a windowed instantiation is kept apart
    from a full one by a ``window`` label of its own, one under the
    block-diffusion mask by ``mask`` — and that one says how many tiles a
    (batch, head) runs and how many of them hold a visible pair."""
    from .. import telemetry
    if not telemetry.enabled():
        return
    own = {} if window is None else {"window": int(window)}
    if block_diffusion is not None:
        own = {"mask": "block_diffusion"}
        run, seen = _bd_tiles(grid[1] // 2, block_diffusion, bq)
        tiles = telemetry.gauge(
            "mxnet_flash_mask_tiles",
            "score tiles of one (batch, head) of the newest flash-"
            "attention kernel instantiation under a mask that names its "
            "tiles, by kernel: the whole square (all), the tiles its grid "
            "runs (run) and those of them that hold a visible pair "
            "(visible)")
        for which, count in (("all", grid[1] ** 2), ("run", run),
                             ("visible", seen)):
            tiles.labels(kernel=kernel, which=which, **own).set(count)
    rows = telemetry.gauge(
        "mxnet_flash_block_rows",
        "rows of the score block the newest flash-attention kernel "
        "instantiation works on, by kernel (fwd / dq / dkv) and side "
        "(q / k)")
    rows.labels(kernel=kernel, side="q", **own).set(bq)
    rows.labels(kernel=kernel, side="k", **own).set(bk)
    telemetry.gauge(
        "mxnet_flash_kv_group",
        "query heads that share one key/value head in the newest "
        "flash-attention kernel instantiation, by kernel: 1 where every "
        "query head has its own (MHA); the kernels index the shared head, "
        "nothing is repeated").labels(kernel=kernel, **own).set(group)
    telemetry.gauge(
        "mxnet_flash_grid_steps",
        "grid steps of the newest flash-attention kernel instantiation "
        "(batch x heads x Q blocks x K blocks), by kernel").labels(
            kernel=kernel, **own).set(math.prod(grid))


_FLASH_FWD_CALL = re.compile(
    r' custom-call\(.*op_name="[^"]*\b_flash_fwd_kernel\b')


def export_flash_fwd_calls(program, hlo_text):
    """``mxnet_flash_fwd_calls{program}``: the forward kernel's custom
    calls in a program's OPTIMIZED module (``telemetry.program_hlo``).
    The ``flash_attention_fwd`` counter counts traces and cannot tell a
    layer that keeps ``FLASH_KEPT`` across its checkpoint (one call)
    from one that runs the kernel again in the backward pass (two)."""
    from .. import telemetry
    if not telemetry.enabled():
        return
    telemetry.gauge(
        "mxnet_flash_fwd_calls",
        "_flash_fwd_kernel custom calls in the optimized module of a "
        "registered program: one an attention layer where the layer's "
        "checkpoint keeps the kernel's results, two where the backward "
        "pass runs it again").labels(program=program).set(
            sum(1 for line in hlo_text.splitlines()
                if _FLASH_FWD_CALL.search(line)))


# one operand block, counted at the f32 width the kernels compute in:
# in + out blocks double-buffered stay a small fraction of the 16 MiB
# of scoped VMEM whatever the channel count
_BLOCK_BYTES = 512 * 1024


def _row_block(n, c, dtype, pref):
    """Rows per grid step of a row-blocked pass over an (n, c) array:
    at most ``pref`` and at most ``_BLOCK_BYTES`` worth of ``c``-wide
    rows, in whole sublane tiles of ``dtype``.  An array that fits one
    block is taken whole (always a legal block shape); otherwise the
    largest tile-multiple divisor of ``n`` within 8x of the cap (no
    padding), else the cap itself and the caller pads the rows."""
    sub = _sublane(dtype)
    cap = max(sub, min(int(pref), _BLOCK_BYTES // (4 * c)) // sub * sub)
    if n <= cap:
        return n
    for b in range(cap, max(cap // 8, sub) - 1, -sub):
        if n % b == 0:
            return b
    return cap


# Kernel plans: each family's grid / BlockSpecs / operand shapes as one
# declarative dict, built by the SAME function the dispatch consumes —
# graftkern (analysis/kern/) abstractly interprets these plans, so the
# verifier checks exactly the grid and index maps the kernel runs (no
# drift by construction).  Shapes are the PADDED shapes the pallas_call
# sees; "scratch" lists fp32 VMEM scratch shapes; a flash plan also
# names its operands' "dtypes" (inputs, then outputs) and the (shape,
# dtype) score "tiles" a step holds besides.

def _flash_tiles(kernel, bq, bk, dtype):
    """Score tiles a grid step keeps beside its operand blocks: the
    float32 scores (``p`` overwrites them) and one float32 temporary —
    the exponential's argument, ``dp`` / ``ds`` — and, in the backward,
    ``p`` / ``ds`` again in the operand dtype for their products."""
    shape = (bk, bq) if kernel == "dkv" else (bq, bk)
    tiles = [(shape, "float32")] * 2
    if kernel != "fwd":
        tiles.append((shape, jnp.dtype(dtype).name))
    return tiles


_FLASH_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _flash_q_major_specs(d, dv, bq, bk, causal, window=None, half=None,
                         group=1):
    """Specs of the kernels on grid (BH, nQ, nK): the Q side follows
    i; the K side follows j, held at the last block row i needs under
    the causal mask — and at the first one under a window — so the
    masked steps fetch nothing.  Under the block-diffusion mask (``half``
    tiles a half) j counts row i's visits and the K side is the tile
    ``_bd_q_visit`` names.  The K side names the key/value head of
    query head b: the heads fold batch-major, ``B x Hq`` rows over ``B x
    Hkv`` with ``Hq = group Hkv``, so row b reads row ``b // group``.
    ``(q, k, lse, v, o)``: the values and the output are ``dv`` wide."""
    head = (lambda b: b) if group == 1 else (lambda b: _idiv(b, group))

    def kmap(b, i, j):
        if half is not None:
            return (head(b), _bd_q_visit(i, j, half)[0], 0)
        if not causal:
            return (head(b), j, 0)
        j = _imin(j, _causal_last_k(i, bq, bk))
        if window is not None:
            j = _imax(j, _window_first_k(i, bq, bk, window))
        return (head(b), j, 0)
    return (pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), kmap),
            pl.BlockSpec((None, bq, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, dv), kmap),
            pl.BlockSpec((None, bq, dv), lambda b, i, j: (b, i, 0)))


def _bd_half(tq, tk, bq, bk, causal, window, length):
    """Tiles a half of a plan under the block-diffusion mask of
    ``length``-position blocks (None without one): the rows are two
    halves of whole square tiles of whole blocks, and the mask stands
    in no other's stead."""
    if length is None:
        return None
    if causal or window is not None:
        raise ValueError("the block-diffusion mask is a mask of its own: "
                         "neither causal nor a window goes with it")
    if tq != tk or bq != bk or tq % (2 * bq) or bq % length:
        raise ValueError(
            "the block-diffusion mask needs rows [noised ; clean] of two "
            "equal halves in square tiles of whole blocks: got %d x %d "
            "rows in %d x %d tiles, blocks of %d"
            % (tq, tk, bq, bk, length))
    return tq // (2 * bq)


def flash_fwd_plan(bh, tq, tk, d, bq, bk, causal=False,
                   dtype=jnp.float32, window=None, dv=None,
                   block_diffusion=None, group=1):
    """Plan of the flash-attention forward kernel (q, k, v -> o, lse);
    ``dv``: the head size of ``v`` and ``o`` where it is not ``d``;
    ``block_diffusion``: the block length of that mask, under which the
    grid's minor axis is a Q tile's visits; ``group``: query heads a
    key/value head (``bh`` counts the query heads)."""
    dv = d if dv is None else dv
    bkv = bh // group
    half = _bd_half(tq, tk, bq, bk, causal, window, block_diffusion)
    qspec, kspec, lmspec, vspec, ospec = _flash_q_major_specs(
        d, dv, bq, bk, causal, window, half, group)
    return {
        "grid": (bh, tq // bq, tk // bk if half is None else half + 1),
        "in_specs": [qspec, kspec, vspec],
        "in_shapes": [(bh, tq, d), (bkv, tk, d), (bkv, tk, dv)],
        "out_specs": [ospec, lmspec],
        "out_shapes": [(bh, tq, dv), (bh, tq, LANES)],
        "scratch": [(bq, dv), (bq, LANES), (bq, LANES)],
        "dtypes": [dtype] * 4 + [jnp.float32],
        "tiles": _flash_tiles("fwd", bq, bk, dtype),
    }


def flash_bwd_dq_plan(bh, tq, tk, d, bq, bk, causal=False,
                      dtype=jnp.float32, window=None, dv=None,
                      block_diffusion=None, group=1):
    """Plan of the dq backward kernel
    (q, k, v, do, lse, delta -> dq); the forward's grid."""
    dv = d if dv is None else dv
    bkv = bh // group
    half = _bd_half(tq, tk, bq, bk, causal, window, block_diffusion)
    qspec, kspec, lmspec, vspec, ospec = _flash_q_major_specs(
        d, dv, bq, bk, causal, window, half, group)
    return {
        "grid": (bh, tq // bq, tk // bk if half is None else half + 1),
        "in_specs": [qspec, kspec, vspec, ospec, lmspec, lmspec],
        "in_shapes": [(bh, tq, d), (bkv, tk, d), (bkv, tk, dv),
                      (bh, tq, dv), (bh, tq, LANES), (bh, tq, LANES)],
        "out_specs": [qspec],
        "out_shapes": [(bh, tq, d)],
        "scratch": [(bq, d)],
        "dtypes": [dtype] * 4 + [jnp.float32] * 2 + [dtype],
        "tiles": _flash_tiles("dq", bq, bk, dtype),
    }


def flash_bwd_dkv_plan(bh, tq, tk, d, bq, bk, causal=False,
                       dtype=jnp.float32, window=None, dv=None,
                       block_diffusion=None, group=1):
    """Plan of the dk/dv backward kernel — grid (BH, nK, nQ), so the
    q-side specs transpose their two minor grid coordinates, and under
    the causal mask hold at the first Q block column j needs (under a
    window at the last one too); under the block-diffusion mask the
    minor axis is a K tile's visits (``_bd_k_visit``).  ``lse`` and
    ``delta`` are rows here: (BH, nQ, 1, bq), one row a Q block.

    With ``group`` query heads a key/value head the grid is (BH / group,
    nK, group nQ): visit ``s`` of key/value head b reads query head ``b
    group + s // nQ`` at the Q block that visit ``s mod nQ`` names
    with one head a group, and the K / V blocks, whose index does not move
    along the minor axis, stay in VMEM across the whole group."""
    nq = tq // bq
    dv = d if dv is None else dv
    bkv = bh // group
    half = _bd_half(tq, tk, bq, bk, causal, window, block_diffusion)

    def qblock(j, i):
        if half is not None:
            return _bd_k_visit(j, i, half)[0]
        if not causal:
            return i
        last = nq - 1 if window is None else \
            _imin(_window_last_q(j, bq, bk, window), nq - 1)
        return _imin(_imax(i, _causal_first_q(j, bq, bk)), last)

    def qside(b, j, s):
        """(query head, Q block) of visit ``s`` of K block j."""
        if group == 1:
            return b, qblock(j, s)
        m = _idiv(s, nq)
        return b * group + m, qblock(j, s - m * nq)
    qspec_t = pl.BlockSpec((None, bq, d),
                           lambda b, j, s: (*qside(b, j, s), 0))
    kspec_t = pl.BlockSpec((None, bk, d), lambda b, j, s: (b, j, 0))
    dospec_t = pl.BlockSpec((None, bq, dv),
                            lambda b, j, s: (*qside(b, j, s), 0))
    vspec_t = pl.BlockSpec((None, bk, dv), lambda b, j, s: (b, j, 0))
    rowspec_t = pl.BlockSpec((None, None, 1, bq),
                             lambda b, j, s: (*qside(b, j, s), 0, 0))
    return {
        # a clean K tile's visits: up to nq a query head of the group
        "grid": (bkv, tk // bk, group * nq),
        "in_specs": [qspec_t, kspec_t, vspec_t, dospec_t, rowspec_t,
                     rowspec_t],
        "in_shapes": [(bh, tq, d), (bkv, tk, d), (bkv, tk, dv),
                      (bh, tq, dv), (bh, nq, 1, bq), (bh, nq, 1, bq)],
        "out_specs": [kspec_t, vspec_t],
        "out_shapes": [(bkv, tk, d), (bkv, tk, dv)],
        "scratch": [(bk, d), (bk, dv)],
        "dtypes": [dtype] * 4 + [jnp.float32] * 2 + [dtype] * 2,
        "tiles": _flash_tiles("dkv", bq, bk, dtype),
    }


# rows a side: the sweep on the chip (PERF.md, PR 28) has every kernel
# fastest at 1024 x 1024 or within a few per cent of it
_FLASH_MAX_ROWS = 1024

_FLASH_PLANS = {"fwd": flash_fwd_plan, "dq": flash_bwd_dq_plan,
                "dkv": flash_bwd_dkv_plan}


def _flash_vmem_bytes(plan):
    """What one grid step of a flash plan holds in VMEM: its operand
    blocks double-buffered, its float32 scratch twice (the product
    added to an accumulator is as large), its score tiles.  Held
    against every verdict of the chip's compiler over head sizes
    64-256, bf16 and float32, blocks 512-1024: the compiler refuses
    nothing this puts under the budget (PERF.md, PR 28)."""
    def nbytes(shape, dtype):
        return math.prod(b for b in shape if b is not None) \
            * jnp.dtype(dtype).itemsize
    specs = plan["in_specs"] + plan["out_specs"]
    return (2 * sum(nbytes(sp.block_shape, t)
                    for sp, t in zip(specs, plan["dtypes"]))
            + 2 * sum(nbytes(sh, jnp.float32) for sh in plan["scratch"])
            + sum(nbytes(sh, t) for sh, t in plan["tiles"]))


def _block_choices(t, sub):
    """Legal row blocks of a length-``t`` side, largest first: the
    whole side when it is short, then the divisors of ``t`` in whole
    lane tiles (a block is the lane dimension of a score tile too),
    else in whole sublane tiles; the halving choice where none of those
    exists (interpret mode only: ``flash_seq_ok`` is the native gate)."""
    cap = _FLASH_MAX_ROWS
    whole = [t] if t <= cap else []
    for step in (LANES, sub):
        divs = [b for b in range(min(cap, t) // step * step, 0, -step)
                if t % b == 0 and b != t]
        if divs:
            return whole + divs
    return whole or [_pick_block(t, cap)]


def _flash_blocks(tq, tk, d, dtype, kernel, dv=None, block_diffusion=None):
    """(bq, bk) for one flash kernel from what the call can see: the
    largest blocks, in whole tiles and dividing T, whose plan fits the
    scoped-VMEM budget graftkern holds the plans to
    (``MXNET_KERN_VMEM_BYTES``).  Among equal areas the squarer pair
    wins (a square block on the diagonal is worked in slabs), then the
    wider Q side.  Under the block-diffusion mask the tiles are square,
    divide a HALF of the rows and hold whole blocks
    (:func:`flash_block_diffusion_ok` says whether such a tile exists)."""
    from .. import config as _config
    budget = int(_config.get("MXNET_KERN_VMEM_BYTES"))
    sub = _sublane(dtype)
    if block_diffusion is not None:
        pairs = [(b, b) for b in _block_choices(tq // 2, sub)
                 if b % block_diffusion == 0]
        if not pairs:
            raise ValueError(
                "no row block divides a half of %d rows in whole blocks of "
                "%d positions" % (tq, block_diffusion))
    else:
        pairs = [(bq, bk) for bq in _block_choices(tq, sub)
                 for bk in _block_choices(tk, sub)]
    fits = [(bq * bk, -abs(bq - bk), bq, bk) for bq, bk in pairs
            if _flash_vmem_bytes(_FLASH_PLANS[kernel](
                1, tq, tk, d, bq, bk, dtype=dtype, dv=dv,
                block_diffusion=block_diffusion)) < budget]
    # nothing fits: the smallest blocks of both sides
    return max(fits)[2:] if fits else pairs[-1]


def flash_block_diffusion_ok(t, length, dtype):
    """Whether the kernels can tile ``t`` rows ``[noised ; clean]``
    under the block-diffusion mask of ``length``-position blocks: two
    halves of whole blocks, and a legal row block that divides a half
    and holds whole blocks."""
    return t % (2 * length) == 0 and any(
        b % length == 0 for b in _block_choices(t // 2, _sublane(dtype)))


def _flash_plan(kernel, q, k, causal, block_q, block_k, window=None,
                dv=None, block_diffusion=None):
    """(bq, bk, plan) of one kernel for this call: a caller's explicit
    blocks are honoured (halved until they divide T, as ever), a side
    left None is picked from the shape; the choice is exported.  ``dv``
    is the values' head size (None: the keys').  Under the
    block-diffusion mask the tile is square: the smaller explicit side,
    halved until it divides a half of the rows."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    group = _flash_group(q, k)
    bq, bk = _flash_blocks(tq, tk, d, q.dtype, kernel, dv, block_diffusion)
    if block_diffusion is not None:
        given = [b for b in (block_q, block_k) if b is not None]
        if given:
            bq = bk = _pick_block(tq // 2, min(given))
    else:
        if block_q is not None:
            bq = _pick_block(tq, block_q)
        if block_k is not None:
            bk = _pick_block(tk, block_k)
    plan = _FLASH_PLANS[kernel](bh, tq, tk, d, bq, bk, causal, q.dtype,
                                window, dv, block_diffusion, group)
    _export_flash_gauges(kernel, bq, bk, plan["grid"], window,
                         block_diffusion, group)
    return bq, bk, plan


def _flash_group(q, k):
    """Query heads a key/value head, from the folded operands' leading
    sides: ``B x Hq`` over ``B x Hkv``."""
    if q.shape[0] % k.shape[0]:
        raise ValueError(
            "grouped-query attention needs the query heads a whole "
            "multiple of the key/value heads: %d folded query heads over "
            "%d" % (q.shape[0], k.shape[0]))
    return q.shape[0] // k.shape[0]


def _flash_mask_kwargs(plan, window, block_diffusion):
    """The mask as a kernel takes it: the window, or the block length
    with the tiles a half the plan's grid was laid out for."""
    if block_diffusion is None:
        return {"window": window}
    return {"blocks": (int(block_diffusion), plan["grid"][1] // 2)}


def _flash_call(kernel, plan, *operands):
    """One flash ``pallas_call`` off its plan, named after its kernel
    (the benchmark's trace readers find the three by name)."""
    n_in = len(plan["in_specs"])
    return pl.pallas_call(
        kernel,
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"],
        out_shape=[jax.ShapeDtypeStruct(s, t) for s, t in zip(
            plan["out_shapes"], plan["dtypes"][n_in:])],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32)
                        for s in plan["scratch"]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_FLASH_SEMANTICS),
        name=kernel.func.__name__,
        interpret=_interpret(),
    )(*operands)


def _flash_window(window, causal, tq, tk):
    """The window as the kernels take it: None where it hides nothing
    the causal mask shows (so the call is the causal call, program and
    all), else a whole number of keys, at least one."""
    if window is None:
        return None
    window = int(window)
    if window < 1:
        raise ValueError("attention window must be at least 1 key, got %d"
                         % window)
    if not causal:
        raise ValueError("an attention window needs causal=True: a query "
                         "sees its last `window` keys, itself among them")
    return None if window >= max(tq, tk) else window


# the forward kernel's results by the names a checkpoint policy keeps
# them under: the output, the row statistics
FLASH_KEPT = ("flash_out", "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, window=None, kept=False,
                    block_diffusion=None):
    """Blockwise online-softmax attention.

    q: (BH, T, D), k: (BKV, T, D), v: (BKV, T, Dv) — fold batch and
    heads into the leading dim, batch-major; the values' head size may
    differ from the keys' (latent attention scores over 192 dimensions
    and sums values of 128).  Grouped-query attention: ``g = BH / BKV``
    query heads share a key/value head (folded query head ``n`` reads
    key/value head ``n // g``); the kernels index the shared head — K
    and V are never repeated — and dK / dV come out at BKV heads, the
    group's sum taken in the dK/dV kernel's float32 accumulator.
    Returns (BH, T, Dv).  O(T) memory.  The (block_q, block_k) score
    blocks are chosen per kernel from the shape — the largest whole-
    tile divisors of T that fit the scoped-VMEM budget
    (``_flash_blocks``); an explicit ``block_q`` / ``block_k`` is
    honoured.  Causal masking skips the blocks above the diagonal
    entirely (no body, no fetch), builds a mask only in the blocks the
    diagonal crosses, and in the backward skips the upper half of a
    square diagonal block slab by slab.  ``window`` (with ``causal``):
    a query sees its last ``window`` keys, itself among them; a block
    wholly below the window is neither fetched nor run either, and only
    the blocks an edge of the band crosses build a mask.  ``kept``: the
    caller is a layer whose ``jax.checkpoint`` keeps ``FLASH_KEPT``, so
    the backward's ``lse`` is held one float32 a row (the section's
    header); no number moves.

    ``block_diffusion`` (an int ``B``; neither ``causal`` nor ``window``
    goes with it): the mask block diffusion trains under (Arriola et
    al., arXiv:2503.09573, section 4).  The ``T = 2 L`` rows are a
    noised copy of a sequence followed by its clean copy, both in blocks
    of ``B`` positions; with ``half(r) = [r >= L]`` and ``blk(r) = (r mod
    L) // B`` query row r sees key row c iff ``half r = half c and blk r
    = blk c`` (a block sees itself, both ways), or ``half r = 0, half c
    = 1 and blk r > blk c`` (a noised block sees the clean blocks before
    it), or ``half r = half c = 1 and blk r >= blk c`` (clean rows are
    block-causal): ``L^2 + L B`` of the ``4 L^2`` pairs.  The tiles are
    square, divide L and hold whole blocks; a tile with no visible pair
    is neither fetched nor run in any of the three grids, whose minor
    axis counts a row's (column's) visits — ``n (n + 2)`` of ``(2
    n)^2`` tiles at ``n`` tiles a half, 24 of 64 at L = 4096.
    """
    o, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, window,
                      block_diffusion)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, window=None,
               block_diffusion=None):
    """``(o, lse)`` off the forward kernel; ``lse`` lane-broadcast,
    ``(BH, T, LANES)`` float32, as the kernel writes it."""
    _count("flash_attention_fwd")
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    window = _flash_window(window, causal, q.shape[1], k.shape[1])
    bq, bk, plan = _flash_plan("fwd", q, k, causal, block_q, block_k, window,
                               v.shape[-1], block_diffusion)
    return _flash_call(
        functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=plan["grid"][2],
                          **_flash_mask_kwargs(plan, window,
                                               block_diffusion)),
        plan, q, k, v)


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, window, kept,
                    block_diffusion):
    o, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, window,
                        block_diffusion)
    # named BEFORE they become residuals: whatever the backward reads of
    # the two derives from a value a policy can keep
    o = checkpoint_name(o, FLASH_KEPT[0])
    lse = checkpoint_name(lse[:, :, 0] if kept else lse, FLASH_KEPT[1])
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, window, kept,
                    block_diffusion, res, do):
    _count("flash_attention_bwd")
    q, k, v, o, lse = res
    bh, tq, d = q.shape
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    window = _flash_window(window, causal, tq, k.shape[1])
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # dQ reads a row statistic over the lanes, dK/dV as rows
    lanes = lambda a: jnp.broadcast_to(a[..., None], (bh, tq, LANES))
    lse_lanes, lse = (lanes(lse), lse) if kept else (lse, lse[:, :, 0])
    bq, bk, plan = _flash_plan("dq", q, k, causal, block_q, block_k, window,
                               v.shape[-1], block_diffusion)
    dq, = _flash_call(
        functools.partial(_flash_bwd_dq_kernel, scale=s, causal=causal,
                          bq=bq, bk=bk, nk=plan["grid"][2],
                          **_flash_mask_kwargs(plan, window,
                                               block_diffusion)),
        plan, q, k, v, do, lse_lanes, lanes(delta))
    bq, bk, plan = _flash_plan("dkv", q, k, causal, block_q, block_k, window,
                               v.shape[-1], block_diffusion)
    rows = (bh, tq // bq, 1, bq)
    dk, dv = _flash_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=s, causal=causal,
                          bq=bq, bk=bk, nq=tq // bq,
                          group=_flash_group(q, k),
                          **_flash_mask_kwargs(plan, window,
                                               block_diffusion)),
        plan, q, k, v, do, lse.reshape(rows), delta.reshape(rows))
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# Selective scan (Mamba's state-space recurrence)
# ---------------------------------------------------------------------------
# ``s_t = exp(delta_t A) * s_{t-1} + (delta_t u_t) B_t``, ``y_t = s_t C_t +
# D u_t`` with a state of N values for each of E channels.  A grid step owns
# (a sequence, a block of channels, a chunk of rows); the chunk axis is the
# grid's sequential one, and the (N, channels) float32 state rides it in
# VMEM scratch, N on the sublanes and the channels on the lanes, stepped once
# a row.  B_t and C_t come in transposed, (N, rows): a row's column is
# picked out of the chunk's lane-dense block by its lane index.  The forward
# writes ``y`` and the state ENTERING every chunk; the backward visits the
# chunks last first, runs a chunk's rows again from that state (holding the
# state before every row in VMEM), then walks them back with the state's
# adjoint carried in scratch across chunks.  The state is never written to
# HBM a row: T x E x N float32 is 2.7 GB at 8192 x 5120 x 16.
SSM_CHUNK_ROWS = 128      # rows a grid step carries the state through
_SSM_SUB = 16             # rows a loop iteration loads (a bf16 tile)
_SSM_CHANNELS = {"fwd": 512, "bwd": 256}   # channels a grid step holds
_SSM_SEMANTICS = ("parallel", "parallel", "arbitrary")
# the forward kernel's results by the names a checkpoint policy keeps them
# under: the output and the states entering the chunks
SSM_KEPT = ("ssm_out", "ssm_states")


def ssm_blocks(t, e, kernel):
    """``(rows a chunk, padded rows, channels a block)`` of a scan over
    ``t`` rows and ``e`` channels: chunks of ``SSM_CHUNK_ROWS`` (the whole
    sequence in whole sub-blocks where it is shorter), channel blocks of
    whole lane tiles where ``e`` has them, else every channel."""
    tc = SSM_CHUNK_ROWS if t >= SSM_CHUNK_ROWS \
        else -(-t // _SSM_SUB) * _SSM_SUB
    eb = next((c for c in (_SSM_CHANNELS[kernel], 256, LANES)
               if c <= _SSM_CHANNELS[kernel] and e % c == 0), e)
    return tc, -(-t // tc) * tc, eb


def ssm_scan_ok(e):
    """Whether the scan kernels tile ``e`` channels natively: whole lane
    tiles (interpret mode takes any)."""
    return e % LANES == 0


def _ssm_column(block, lane, t):
    """``block (N, rows)``'s column ``t`` as ``(N, 1)``."""
    return jnp.sum(jnp.where(lane == t, block, 0.0), axis=1, keepdims=True)


def _ssm_fwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, dd_ref, y_ref, s0_ref,
                    s_ref, *, tc):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_ref[:] = jnp.zeros_like(s_ref)

    s0_ref[:] = s_ref[:]
    a, dd = a_ref[:], dd_ref[:]
    bt, ct = b_ref[:].astype(jnp.float32), c_ref[:].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_SSM_SUB, a.shape[1]), 0)

    def rows(i, s):
        r0 = pl.multiple_of(i * _SSM_SUB, _SSM_SUB)
        dl = d_ref[pl.ds(r0, _SSM_SUB), :].astype(jnp.float32)
        uu = u_ref[pl.ds(r0, _SSM_SUB), :].astype(jnp.float32)
        du = dl * uu
        ys = jnp.zeros(row.shape, jnp.float32)
        for r in range(_SSM_SUB):
            s = jnp.exp(dl[r:r + 1] * a) * s \
                + du[r:r + 1] * _ssm_column(bt, lane, r0 + r)
            ys = jnp.where(row == r, jnp.sum(
                s * _ssm_column(ct, lane, r0 + r), axis=0, keepdims=True), ys)
        y_ref[pl.ds(r0, _SSM_SUB), :] = (ys + dd * uu).astype(y_ref.dtype)
        return s

    s_ref[:] = jax.lax.fori_loop(0, tc // _SSM_SUB, rows, s_ref[:])


def _ssm_bwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, dd_ref, s0_ref, dy_ref,
                    du_ref, ddl_ref, db_ref, dc_ref, da_ref, dD_ref, p_ref,
                    g_ref, *, tc):
    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        g_ref[:] = jnp.zeros_like(g_ref)
        da_ref[:] = jnp.zeros_like(da_ref)
        dD_ref[:] = jnp.zeros_like(dD_ref)

    a, dd = a_ref[:], dd_ref[:]
    bt, ct = b_ref[:].astype(jnp.float32), c_ref[:].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_SSM_SUB, a.shape[1]), 0)
    nsub = tc // _SSM_SUB

    def load(r0):
        return (d_ref[pl.ds(r0, _SSM_SUB), :].astype(jnp.float32),
                u_ref[pl.ds(r0, _SSM_SUB), :].astype(jnp.float32))

    def again(i, s):
        # the chunk's rows run again: the state BEFORE each row is held
        r0 = pl.multiple_of(i * _SSM_SUB, _SSM_SUB)
        dl, uu = load(r0)
        du = dl * uu
        for r in range(_SSM_SUB):
            p_ref[r0 + r] = s
            s = jnp.exp(dl[r:r + 1] * a) * s \
                + du[r:r + 1] * _ssm_column(bt, lane, r0 + r)
        return s

    jax.lax.fori_loop(0, nsub, again, s0_ref[:])

    def back(j, carry):
        g, da, dbt, dct = carry
        r0 = pl.multiple_of((nsub - 1 - j) * _SSM_SUB, _SSM_SUB)
        dl, uu = load(r0)
        dy = dy_ref[pl.ds(r0, _SSM_SUB), :].astype(jnp.float32)
        du = dl * uu
        gu = jnp.zeros(row.shape, jnp.float32)
        gd = jnp.zeros(row.shape, jnp.float32)
        for r in reversed(range(_SSM_SUB)):
            t = r0 + r
            bcol = _ssm_column(bt, lane, t)
            sp = p_ref[t]
            decay = jnp.exp(dl[r:r + 1] * a)
            s = decay * sp + du[r:r + 1] * bcol
            g = g + _ssm_column(ct, lane, t) * dy[r:r + 1]
            dct = jnp.where(lane == t, jnp.sum(s * dy[r:r + 1], axis=1,
                                               keepdims=True), dct)
            dbt = jnp.where(lane == t, jnp.sum(g * du[r:r + 1], axis=1,
                                               keepdims=True), dbt)
            gb = jnp.sum(g * bcol, axis=0, keepdims=True)
            w = g * decay * sp
            da = da + w * dl[r:r + 1]
            gu = jnp.where(row == r, gb * dl[r:r + 1], gu)
            gd = jnp.where(row == r, jnp.sum(w * a, axis=0, keepdims=True)
                           + uu[r:r + 1] * gb, gd)
            g = decay * g
        du_ref[pl.ds(r0, _SSM_SUB), :] = (gu + dd * dy).astype(du_ref.dtype)
        ddl_ref[pl.ds(r0, _SSM_SUB), :] = gd.astype(ddl_ref.dtype)
        dD_ref[:] += dy * uu
        return g, da, dbt, dct

    g, da, dbt, dct = jax.lax.fori_loop(
        0, nsub, back, (g_ref[:], jnp.zeros(a.shape, jnp.float32),
                        jnp.zeros(bt.shape, jnp.float32),
                        jnp.zeros(bt.shape, jnp.float32)))
    g_ref[:] = g
    da_ref[:] += da
    db_ref[:] = dbt
    dc_ref[:] = dct


def ssm_scan_plan(bs, t, e, n, kernel, dtypes=(jnp.bfloat16, jnp.float32)):
    """Plan of one scan kernel over ``bs`` sequences of ``t`` (padded)
    rows, ``e`` channels and a state of ``n``: forward ``(u, delta, A (N,
    E), B^T, C^T (bs, N, T), D (1, E)) -> (y, the states entering the
    chunks (bs, chunks, N, E))``; backward the same operands, the states
    and ``dy`` -> ``(du, ddelta, dB^T, dC^T (bs, channel blocks, N, T),
    dA (bs, N, E), dD (bs, sub-block rows, E))``, the chunks visited last
    first.  ``dtypes``: ``u``'s and ``delta``'s (``y`` and ``du`` are
    ``u``'s, ``ddelta`` is ``delta``'s)."""
    tc, tp, eb = ssm_blocks(t, e, kernel)
    nc, ne = tp // tc, e // eb
    rev = (lambda c: c) if kernel == "fwd" else (lambda c: nc - 1 - c)
    rows = pl.BlockSpec((None, tc, eb), lambda b, j, c: (b, rev(c), j))
    chan = pl.BlockSpec((n, eb), lambda b, j, c: (0, j))
    cols = pl.BlockSpec((None, n, tc), lambda b, j, c: (b, 0, rev(c)))
    state = pl.BlockSpec((None, None, n, eb),
                         lambda b, j, c: (b, rev(c), 0, j))
    ins = [rows, rows, chan, cols, cols,
           pl.BlockSpec((1, eb), lambda b, j, c: (0, j))]
    plan = {"grid": (bs, ne, nc), "chunk": tc, "channels": eb,
            "in_specs": ins, "scratch": [(n, eb)]}
    if kernel == "fwd":
        plan.update(out_specs=[rows, state],
                    out_shapes=[(bs, tp, e), (bs, nc, n, e)],
                    dtypes=[dtypes[0], jnp.float32])
        return plan
    plan.update(
        in_specs=ins + [state, rows],
        out_specs=[rows, rows,
                   pl.BlockSpec((None, None, n, tc),
                                lambda b, j, c: (b, j, 0, rev(c))),
                   pl.BlockSpec((None, None, n, tc),
                                lambda b, j, c: (b, j, 0, rev(c))),
                   pl.BlockSpec((None, n, eb), lambda b, j, c: (b, 0, j)),
                   pl.BlockSpec((None, _SSM_SUB, eb),
                                lambda b, j, c: (b, 0, j))],
        out_shapes=[(bs, tp, e), (bs, tp, e), (bs, ne, n, tp),
                    (bs, ne, n, tp), (bs, n, e), (bs, _SSM_SUB, e)],
        dtypes=list(dtypes) + [jnp.float32] * 4,
        scratch=[(tc, n, eb), (n, eb)])
    return plan


def _ssm_call(kernel, bs, t, e, n, *operands):
    plan = ssm_scan_plan(bs, t, e, n, kernel,
                         (operands[0].dtype, operands[1].dtype))
    body = _ssm_fwd_kernel if kernel == "fwd" else _ssm_bwd_kernel
    _export_ssm_gauges(plan)
    return pl.pallas_call(
        functools.partial(body, tc=plan["chunk"]),
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"],
        out_shape=[jax.ShapeDtypeStruct(s, d) for s, d in zip(
            plan["out_shapes"], plan["dtypes"])],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in plan["scratch"]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SSM_SEMANTICS),
        name=body.__name__,
        interpret=_interpret(),
    )(*operands)


def _export_ssm_gauges(plan):
    from .. import telemetry
    if telemetry.enabled():
        telemetry.gauge(
            "mxnet_ssm_scan_chunk_rows",
            "rows a grid step of the newest selective-scan kernel carries "
            "the state through (the chunk)").set(plan["chunk"])


def _ssm_operands(u, delta, a, b, c, d):
    """The kernels' operands: rows padded to whole chunks (a padded row
    has ``delta = 0``: it leaves the state as it is), ``A`` and the
    columns transposed, ``D`` a row."""
    t = u.shape[1]
    tp = ssm_blocks(t, u.shape[2], "fwd")[1]
    pad = lambda x: jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))
    cols = lambda x: jnp.swapaxes(pad(x.astype(jnp.float32)), 1, 2)
    return (pad(u), pad(delta),
            jnp.transpose(a.astype(jnp.float32)), cols(b), cols(c),
            d.astype(jnp.float32).reshape(1, -1))


def _ssm_fwd(u, delta, a, b, c, d):
    _count("ssm_scan_fwd")
    ops = _ssm_operands(u, delta, a, b, c, d)
    bs, tp, e = ops[0].shape
    y, states = _ssm_call("fwd", bs, tp, e, a.shape[1], *ops)
    return y[:, :u.shape[1]], states


@jax.custom_vjp
def selective_scan(u, delta, a, b, c, d):
    """Mamba's selective scan (Gu & Dao, arXiv:2312.00752, Algorithm 2)
    over ``u``, ``delta (Bs, T, E)`` with ``a (E, N)`` (``-exp(A_log)``),
    ``b``, ``c (Bs, T, N)`` and ``d (E,)``::

        s_t = exp(delta_t A) * s_{t-1} + (delta_t u_t) B_t     s_0 = 0
        y_t = s_t C_t + D u_t

    every step in float32 (``u`` and ``delta`` are read in their own
    dtypes); ``y (Bs, T, E)`` in ``u``'s dtype.  The state is
    carried in VMEM (the section's header); the backward returns every
    operand's gradient, ``B``'s and ``C``'s summed over the channel blocks
    and ``A``'s and ``D``'s over the sequences and chunks.  The forward's
    two results carry the names ``SSM_KEPT``: a layer whose checkpoint
    keeps them runs no forward kernel again in the backward pass."""
    return _ssm_fwd(u, delta, a, b, c, d)[0]


def _ssm_fwd_rule(u, delta, a, b, c, d):
    y, states = _ssm_fwd(u, delta, a, b, c, d)
    y = checkpoint_name(y, SSM_KEPT[0])
    states = checkpoint_name(states, SSM_KEPT[1])
    return y, (u, delta, a, b, c, d, states)


def _ssm_bwd_rule(res, dy):
    _count("ssm_scan_bwd")
    u, delta, a, b, c, d, states = res
    t = u.shape[1]
    ops = _ssm_operands(u, delta, a, b, c, d)
    bs, tp, e = ops[0].shape
    dyp = jnp.pad(dy.astype(u.dtype), ((0, 0), (0, tp - t), (0, 0)))
    du, ddl, dbt, dct, da, dD = _ssm_call(
        "bwd", bs, tp, e, a.shape[1], *ops, states, dyp)
    cols = lambda x, like: jnp.swapaxes(jnp.sum(x, axis=1), 1, 2)[
        :, :t].astype(like.dtype)
    return (du[:, :t], ddl[:, :t],
            jnp.transpose(jnp.sum(da, axis=0)).astype(a.dtype),
            cols(dbt, b), cols(dct, c),
            jnp.sum(dD, axis=(0, 1)).astype(d.dtype))


selective_scan.defvjp(_ssm_fwd_rule, _ssm_bwd_rule)


# ---------------------------------------------------------------------------
# Grouped matrix product (sparse experts)
# ---------------------------------------------------------------------------
# ``P`` rows sorted by group, every group's rows starting at a multiple of
# ``tm`` (``parallel/moe.py`` lays them out so: a group's end is padded to
# the tile with ZERO rows, never to a capacity), so a row tile belongs to
# ONE group and the product is a tiled matmul whose weight block is named
# by a scalar-prefetched table, tile -> group.  ``used`` tiles hold rows;
# the tiles past them run no product and fetch nothing (their index maps
# hold at the last used tile's blocks) and are written as zeros.

# Rows of a tile.  A held expert's rows start at a tile and its last tile
# is padded with zero rows, so the tile prices the padding: the one cell
# that runs the product sees about 1024 rows an expert (872 - 1175 over
# 1344 loads), three 384-row tiles on all but 8 of them and 11 % of the
# rows multiplied padding, where 512-row tiles flip between two and
# three (PERF.md, Findings PR 32: 23.7 -> 26.2 % of the peak).  One
# constant until a second load exists.
GROUPED_TILE_ROWS = 384
_GROUPED_MAX_COLS = 1024


def _grouped_cols(n):
    """Columns of a block over a side of ``n``: its largest divisor in
    whole lane tiles up to ``_GROUPED_MAX_COLS``, else the side whole."""
    for b in range(min(_GROUPED_MAX_COLS, n) // LANES * LANES, 0, -LANES):
        if n % b == 0:
            return b
    return n


def grouped_matmul_plan(p, c, o, groups, tm, w_out_in=True,
                        dtype=jnp.bfloat16):
    """Plan of ``y[rows of tile i] = x[rows of tile i] @ W[group(i)]``:
    ``x (P, C)``, ``y (P, O)``, ``W (G, O, C)`` contracted over its last
    side (``w_out_in``: the forward) or ``(G, C, O)`` over its middle
    one (the gradient for ``x``).  Grid (row tiles, O blocks, C blocks),
    the contraction innermost; scalar prefetch: ``tile_group
    (tiles,)`` — held at the last used tile's group past ``used`` —
    and ``used (1,)``."""
    to, tc = _grouped_cols(o), _grouped_cols(c)
    nj, nk = o // to, c // tc

    def xmap(i, j, kk, tg, used):
        on = i < used[0]
        return (_isel(on, i, used[0] - 1), _isel(on, kk, nk - 1))

    def wmap(i, j, kk, tg, used):
        on = i < used[0]
        jj, kc = _isel(on, j, nj - 1), _isel(on, kk, nk - 1)
        return (tg[i], jj, kc) if w_out_in else (tg[i], kc, jj)

    return {
        "grid": (p // tm, nj, nk),
        "num_scalar_prefetch": 2,
        "in_specs": [pl.BlockSpec((tm, tc), xmap),
                     pl.BlockSpec((None, to, tc) if w_out_in
                                  else (None, tc, to), wmap)],
        "in_shapes": [(p, c), (groups, o, c) if w_out_in
                      else (groups, c, o)],
        "out_specs": [pl.BlockSpec((tm, to),
                                   lambda i, j, kk, tg, used: (i, j))],
        "out_shapes": [(p, o)],
        "scratch": [(tm, to)],
        "dtypes": [dtype] * 3,
        "tiles": [((tm, to), "float32")],
    }


def grouped_matmul_dw_plan(p, c, o, groups, tm, dtype=jnp.bfloat16):
    """Plan of ``dW[g] = dy[rows of g].T @ x[rows of g]``, ``(G, O, C)``:
    grid (O blocks, C blocks, row tiles), the rows innermost; a group's
    block stays resident over its (consecutive) tiles and is written
    once, after its last."""
    to, tc = _grouped_cols(o), _grouped_cols(c)

    def rows(side):
        def index(j, kk, i, tg, used):
            return (_imin(i, used[0] - 1), j if side == "o" else kk)
        return index

    return {
        "grid": (o // to, c // tc, p // tm),
        "num_scalar_prefetch": 2,
        "in_specs": [pl.BlockSpec((tm, to), rows("o")),
                     pl.BlockSpec((tm, tc), rows("c"))],
        "in_shapes": [(p, o), (p, c)],
        "out_specs": [pl.BlockSpec((None, to, tc),
                                   lambda j, kk, i, tg, used:
                                   (tg[i], j, kk))],
        "out_shapes": [(groups, o, c)],
        "scratch": [(to, tc)],
        "dtypes": [dtype] * 3,
        "tiles": [((to, tc), "float32")],
    }


def _grouped_matmul_kernel(tg_ref, used_ref, x_ref, w_ref, o_ref, acc_ref,
                           *, nk, w_out_in):
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < used_ref[0])
    def _product():
        acc_ref[:] += jax.lax.dot_general(
            x_ref[:], w_ref[:],
            (((1,), (1 if w_out_in else 0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _final():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _grouped_matmul_dw_kernel(tg_ref, used_ref, dy_ref, x_ref, o_ref,
                              acc_ref, *, tiles):
    i = pl.program_id(2)
    on = i < used_ref[0]
    here = tg_ref[i]
    first = jnp.logical_or(i == 0, tg_ref[jnp.maximum(i - 1, 0)] != here)
    last = jnp.logical_or(i == used_ref[0] - 1,
                          tg_ref[jnp.minimum(i + 1, tiles - 1)] != here)

    @pl.when(jnp.logical_and(on, first))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(on)
    def _product():
        acc_ref[:] += jax.lax.dot_general(
            dy_ref[:], x_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(on, last))
    def _final():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _grouped_call(kernel, plan, tile_group, used, *operands):
    n_in = len(plan["in_specs"])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=plan["num_scalar_prefetch"],
            grid=plan["grid"], in_specs=plan["in_specs"],
            out_specs=plan["out_specs"],
            scratch_shapes=[pltpu.VMEM(sh, jnp.float32)
                            for sh in plan["scratch"]]),
        out_shape=[jax.ShapeDtypeStruct(sh, t) for sh, t in zip(
            plan["out_shapes"], plan["dtypes"][n_in:])],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=kernel.func.__name__,
        interpret=_interpret(),
    )(tile_group, used, *operands)[0]


def _grouped_product(x, w, tile_group, used, tm, w_out_in):
    p, c = x.shape
    groups, o = w.shape[0], w.shape[1 if w_out_in else 2]
    plan = grouped_matmul_plan(p, c, o, groups, tm, w_out_in, x.dtype)
    return _grouped_call(
        functools.partial(_grouped_matmul_kernel, nk=plan["grid"][2],
                          w_out_in=w_out_in),
        plan, tile_group, used, x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_group, used, tm):
    """``y (P, O)``: row tile ``i`` of ``x (P, C)`` times
    ``w[tile_group[i]].T``, ``w (G, O, C)`` stored ``(out, in)`` like
    FullyConnected's; tiles ``i >= used[0]`` come out zero.  Every group
    owns at least one tile and a group's tiles are consecutive; rows
    that pad a group's last tile are zero in ``x`` (and in the
    cotangent), so they add nothing to the weight's gradient.  float32
    accumulation, results in ``x``'s dtype."""
    _count("grouped_matmul_fwd")
    return _grouped_product(x, w.astype(x.dtype), tile_group, used, tm, True)


def _grouped_matmul_fwd_rule(x, w, tile_group, used, tm):
    return grouped_matmul(x, w, tile_group, used, tm), \
        (x, w, tile_group, used)


def _grouped_matmul_bwd_rule(tm, res, dy):
    _count("grouped_matmul_bwd")
    x, w, tile_group, used = res
    dy = dy.astype(x.dtype)
    dx = _grouped_product(dy, w.astype(x.dtype), tile_group, used, tm, False)
    plan = grouped_matmul_dw_plan(x.shape[0], x.shape[1], w.shape[1],
                                  w.shape[0], tm, x.dtype)
    dw = _grouped_call(
        functools.partial(_grouped_matmul_dw_kernel,
                          tiles=plan["grid"][2]),
        plan, tile_group, used, dy, x)
    return dx, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_grouped_matmul_fwd_rule, _grouped_matmul_bwd_rule)


# ---------------------------------------------------------------------------
# Fused head (a projection with its softmax cross-entropy)
# ---------------------------------------------------------------------------
# ``CE_r = lse_r - z_r[y_r]`` of the logits ``z = x @ w.T`` (N rows of U
# over a vocabulary of V) with no (N, V) float32 array in HBM: a grid step
# owns a (tm, tv) block of the logits in VMEM, row block i by vocabulary
# block j, j the reduction axis; the rows of ``x`` stay resident over j.
# The forward keeps an online row max and sum of exponentials in float32
# and picks the label's logit by comparing a column's index with the
# label.  The backward makes the block again from ``lse``, forms ``d = g p
# - g [col = y]`` (the softmax's gradient times the term's cotangent, ``g``
# inside the rounding), rounds it to the weights' dtype, sums ``d w_j``
# into the rows' gradient in a float32 scratch and writes ``d`` once, for
# the weights' gradient ``dW = d^T x`` (one XLA product).  A vocabulary of
# no whole number of blocks (32784, 16160, 18992 in the benchmark) leaves
# the last block ragged: what Pallas reads past an edge is undefined, so
# its columns past V are NEG_INF in the logits and its rows of ``w`` past V
# zero in the backward's product.  Row statistics (the label, ``lse``,
# ``g``) are lane-broadcast ``(N, 128)``, as the flash kernels hold them.

_HEAD_MAX_ROWS = 1024
_HEAD_COLS = (512, 256, 128)
_HEAD_SEMANTICS = ("parallel", "arbitrary")


def head_ce_ok(n, u, dtype):
    """Whether the head kernels tile ``n`` rows of ``u`` in ``dtype``:
    whole sublane tiles of rows, whole lane tiles of units."""
    return n % _sublane(dtype) == 0 and u % LANES == 0


def _head_ragged(v, tv, values, cols, fill):
    """``values`` with the entries past the vocabulary's end (``cols``:
    their vocabulary index) at ``fill``; as they are where ``tv`` divides
    ``v`` (no block is ragged)."""
    if v % tv == 0:
        return values
    return jnp.where(cols < v, values, fill)


def _head_logits(x_ref, w_ref, tv, v):
    """float32 logits of row block i by vocabulary block j, the columns
    past V at NEG_INF, and the columns' vocabulary indices."""
    z = jax.lax.dot_general(x_ref[:], w_ref[:], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    cols = pl.program_id(1) * tv \
        + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    return _head_ragged(v, tv, z, cols, NEG_INF), cols


def _head_ce_fwd_kernel(x_ref, w_ref, y_ref, lse_ref, picked_ref, m_ref,
                        l_ref, *, tv, v, nv):
    """Grid (row blocks, vocabulary blocks): ``lse`` and the label's
    logit of a row block, accumulated over the sequential j."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        picked_ref[:] = jnp.zeros_like(picked_ref)

    z, cols = _head_logits(x_ref, w_ref, tv, v)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=1, keepdims=True))
    l_new = l_ref[:, :1] * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.exp(z - m_new), axis=1, keepdims=True)
    hit = jnp.sum(jnp.where(cols == y_ref[:, :1], z, 0.0), axis=1,
                  keepdims=True)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
    picked_ref[:] += jnp.broadcast_to(hit, picked_ref.shape)

    @pl.when(j == nv - 1)
    def _final():
        lse_ref[:] = m_ref[:] + jnp.log(l_ref[:])


def _head_ce_bwd_kernel(x_ref, w_ref, y_ref, lse_ref, g_ref, dx_ref, d_ref,
                        acc_ref, *, tv, v, nv):
    """Grid (row blocks, vocabulary blocks): the block's ``d`` written
    in the weights' dtype, ``d w_j`` summed into the rows' gradient."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    z, cols = _head_logits(x_ref, w_ref, tv, v)
    g = g_ref[:, :1]
    # today's XLA rule's order: softmax times g, less g at the label
    d = (g * jnp.exp(z - lse_ref[:, :1])
         - jnp.where(cols == y_ref[:, :1], g, 0.0)).astype(d_ref.dtype)
    d_ref[:] = d

    def past_end(w):
        rows = j * tv + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
        return _head_ragged(v, tv, w, rows, jnp.zeros((), w.dtype))
    w = w_ref[:]
    if v % tv:
        # only the last block reaches past V: the select is a pass over
        # the whole (tv, U) block, not paid on the others
        w = jax.lax.cond(j == nv - 1, past_end, lambda w: w, w)
    acc_ref[:] += jax.lax.dot_general(d, w, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(j == nv - 1)
    def _final():
        dx_ref[:] = acc_ref[:].astype(dx_ref.dtype)


def _head_specs(u, tm, tv):
    """``(x, w, row statistic)`` blocks on grid (i, j)."""
    return (pl.BlockSpec((tm, u), lambda i, j: (i, 0)),
            pl.BlockSpec((tv, u), lambda i, j: (j, 0)),
            pl.BlockSpec((tm, LANES), lambda i, j: (i, 0)))


def head_ce_fwd_plan(n, v, u, tm, tv, dtype=jnp.bfloat16):
    """Plan of the fused head's forward kernel (x, w, y -> lse, the
    label's logit)."""
    xspec, wspec, rowspec = _head_specs(u, tm, tv)
    return {
        "grid": (n // tm, pl.cdiv(v, tv)),
        "in_specs": [xspec, wspec, rowspec],
        "in_shapes": [(n, u), (v, u), (n, LANES)],
        "out_specs": [rowspec, rowspec],
        "out_shapes": [(n, LANES), (n, LANES)],
        "scratch": [(tm, LANES), (tm, LANES)],
        "dtypes": [dtype, dtype, jnp.int32, jnp.float32, jnp.float32],
        # the logits and the exponential's argument
        "tiles": [((tm, tv), "float32")] * 2,
    }


def head_ce_bwd_plan(n, v, u, tm, tv, dtype=jnp.bfloat16):
    """Plan of the fused head's backward kernel (x, w, y, lse, g -> dx,
    d); ``d`` (N, V) in the weights' dtype, one block a grid step."""
    xspec, wspec, rowspec = _head_specs(u, tm, tv)
    return {
        "grid": (n // tm, pl.cdiv(v, tv)),
        "in_specs": [xspec, wspec, rowspec, rowspec, rowspec],
        "in_shapes": [(n, u), (v, u)] + [(n, LANES)] * 3,
        "out_specs": [xspec, pl.BlockSpec((tm, tv), lambda i, j: (i, j))],
        "out_shapes": [(n, u), (n, v)],
        "scratch": [(tm, u)],
        "dtypes": [dtype, dtype, jnp.int32, jnp.float32, jnp.float32,
                   dtype, dtype],
        # the logits, the exponential, ``d`` in the operand dtype
        "tiles": [((tm, tv), "float32")] * 2 + [((tm, tv), dtype)],
    }


_HEAD_PLANS = {"fwd": head_ce_fwd_plan, "bwd": head_ce_bwd_plan}


def _head_blocks(n, v, u, dtype, kernel):
    """(tm, tv) of one head kernel from the shape: the largest block of
    the logits whose plan fits the scoped-VMEM budget, the more rows the
    better among equal areas (a vocabulary block is fetched once a row
    block: rows are what a fetched byte of ``w`` is multiplied by).  Rows
    divide N in whole sublane tiles, up to 1024; columns are whole lane
    tiles, or the vocabulary whole where it is narrower."""
    from .. import config as _config
    budget = int(_config.get("MXNET_KERN_VMEM_BYTES"))
    sub = _sublane(dtype)
    rows = [b for b in range(min(n, _HEAD_MAX_ROWS) // sub * sub, 0, -sub)
            if n % b == 0] or [n]
    cols = [c for c in _HEAD_COLS if c <= v] or [v]
    fits = [(tm * tv, tm, tv) for tm in rows for tv in cols
            if _flash_vmem_bytes(_HEAD_PLANS[kernel](
                n, v, u, tm, tv, dtype)) < budget]
    return max(fits)[1:] if fits else (rows[-1], cols[-1])


def _head_call(kernel, n, v, u, dtype, *operands):
    """One head ``pallas_call`` at the blocks chosen for it, named after
    its kernel."""
    tm, tv = _head_blocks(n, v, u, dtype, kernel)
    plan = _HEAD_PLANS[kernel](n, v, u, tm, tv, dtype)
    body = _head_ce_fwd_kernel if kernel == "fwd" else _head_ce_bwd_kernel
    n_in = len(plan["in_specs"])
    return pl.pallas_call(
        functools.partial(body, tv=tv, v=v, nv=plan["grid"][1]),
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"],
        out_shape=[jax.ShapeDtypeStruct(s, t) for s, t in zip(
            plan["out_shapes"], plan["dtypes"][n_in:])],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in plan["scratch"]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_HEAD_SEMANTICS),
        name=body.__name__,
        interpret=_interpret(),
    )(*operands)


def _lanes(a):
    return jnp.broadcast_to(a[:, None], (a.shape[0], LANES))


def _head_ce_fwd(x, w, y):
    """``(terms, lse)``, one float32 each a row."""
    _count("head_ce_fwd")
    (n, u), v = x.shape, w.shape[0]
    lse, picked = _head_call("fwd", n, v, u, w.dtype, x, w, _lanes(y))
    return lse[:, 0] - picked[:, 0], lse[:, 0]


@jax.custom_vjp
def head_cross_entropy(x, w, y):
    """``-log softmax(x @ w.T)[y]`` a row, float32 ``(N,)``, of ``x (N,
    U)`` and ``w (V, U)`` in one dtype, ``y (N,)`` int32 in ``[0, V)``;
    the products in that dtype with float32 accumulation.  Neither
    direction writes the float32 ``(N, V)`` logits: the backward keeps
    ``(x, w, y, lse)`` and makes each block of them again in VMEM.  The
    rows' gradient comes out in the weights' dtype, as the gradient of an
    XLA product in that dtype does."""
    return _head_ce_fwd(x, w, y)[0]


def _head_ce_fwd_rule(x, w, y):
    terms, lse = _head_ce_fwd(x, w, y)
    return terms, (x, w, y, lse)


def _head_ce_bwd_rule(res, g):
    _count("head_ce_bwd")
    x, w, y, lse = res
    (n, u), v = x.shape, w.shape[0]
    dx, d = _head_call("bwd", n, v, u, w.dtype, x, w, _lanes(y), _lanes(lse),
                       _lanes(g.astype(jnp.float32)))
    dw = jnp.einsum("nv,nu->vu", d, x, preferred_element_type=jnp.float32)
    # the rows' gradient leaves with the weights': a loss of several heads
    # (Ouro's four exits) would otherwise run every head's kernel first
    # and hold all their ``d`` at once (201 MB each there)
    dx, dw = jax.lax.optimization_barrier((dx, dw.astype(w.dtype)))
    return dx, dw, None


head_cross_entropy.defvjp(_head_ce_fwd_rule, _head_ce_bwd_rule)


# ---------------------------------------------------------------------------
# Row movers (sparse experts' dispatch and combine)
# ---------------------------------------------------------------------------
# Dispatch and combine fetch ROWS: a token's state into its row of the
# buffer, a row's output back to its token.  The buffer has a static
# worst-case size (``parallel/moe.py`` ``_layout``) of which a chip that
# holds a quarter of the experts fills a quarter, and an XLA gather moves
# the whole static shape; these two kernels start one DMA a row that is
# HELD and none for the rest.  Mosaic slices a DMA only along whole tiles
# of the last two dimensions, so a source is handed over as 32-bit words
# ``(rows, 1, W)`` (``_row_words``: a bfloat16 row's two halves packed
# pairwise, a float32 row as it is): the row is then a LEADING index, its
# words lie together in HBM, and the kernel takes the halves apart again
# with a shift and a mask.

_ROW_DTYPES = ("bfloat16", "float32")


def row_words_ok(units, dtype):
    """Whether a ``(rows, units)`` array of ``dtype`` can be moved row by
    row: bfloat16 or float32, each half of the row whole lane tiles."""
    dtype = jnp.dtype(dtype)
    return dtype.name in _ROW_DTYPES \
        and units % (LANES * (4 // dtype.itemsize)) == 0


def _row_word_count(units, dtype):
    return units * jnp.dtype(dtype).itemsize // 4


def _half_cols(half, chunk, words):
    """The columns of lane chunk ``chunk`` of a row's half ``half``."""
    return pl.ds(pl.multiple_of(half * words + chunk * LANES, LANES), LANES)


def _mover_call(kernel, plan, prefetch, operands):
    """One of the movers' kernels over its plan; the plan's landing
    buffer, where it has one, comes with a DMA semaphore."""
    n_in = len(plan["in_specs"])
    scratch = [pltpu.VMEM(shape, jnp.dtype(kind))
               for shape, kind in plan.get("tiles", ())]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=plan["num_scalar_prefetch"],
            grid=plan["grid"], in_specs=plan["in_specs"],
            out_specs=plan["out_specs"],
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA(())]
            if scratch else []),
        out_shape=[jax.ShapeDtypeStruct(sh, d) for sh, d in zip(
            plan["out_shapes"], plan["dtypes"][n_in:])],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=kernel.func.__name__,
        interpret=_interpret(),
    )(*prefetch, *operands)


_WORD_TILE_ROWS = 256


def moe_words_plan(rows, units, tr, dtype=jnp.bfloat16, sources=1):
    """Plan of the pass that makes rows fetchable: grid over the ``rows /
    tr`` row tiles, scalar prefetch ``used (1,)``; ``sources`` arrays
    ``(rows, units)`` in ``(tr, units)`` blocks, the words of their SUM
    out in ``(tr, 1, W)`` blocks, all held at the last used tile past
    ``used``."""
    words = _row_word_count(units, dtype)

    def tile(rank):
        return lambda i, used: (_imin(i, used[0] - 1),) + (0,) * (rank - 1)

    return {
        "grid": (rows // tr,),
        "num_scalar_prefetch": 1,
        "in_specs": [pl.BlockSpec((tr, units), tile(2))] * sources,
        "in_shapes": [(rows, units)] * sources,
        "out_specs": [pl.BlockSpec((tr, 1, words), tile(3))],
        "out_shapes": [(rows, 1, words)],
        "dtypes": [dtype] * sources + [jnp.uint32],
    }


def _moe_words_kernel(used_ref, *refs, dtype):
    *x_refs, o_ref = refs
    words = o_ref.shape[-1]

    @pl.when(pl.program_id(0) < used_ref[0])
    def _tile():
        def chunk(c, carry):
            def bits(h):
                cols = _half_cols(h, c, words)
                v = x_refs[0][:, cols].astype(jnp.float32)
                for x_ref in x_refs[1:]:
                    # the sum XLA would make in the rows' own dtype: exact
                    # in float32, rounded once
                    v = (v + x_ref[:, cols].astype(jnp.float32)) \
                        .astype(dtype).astype(jnp.float32)
                return pltpu.bitcast(v, jnp.uint32)
            word = bits(0)
            if jnp.dtype(dtype).itemsize == 2:
                # a bfloat16's bits are its float32's upper half
                word = (word >> 16) | bits(1)
            o_ref[:, 0, _half_cols(0, c, words)] = word
            return carry
        jax.lax.fori_loop(0, words // LANES, chunk, 0)


def _row_words(xs, used=None, tr=None):
    """``(rows, 1, W)`` uint32, the rows of the SUM of the arrays ``xs``
    (a tuple, each ``(rows, U)``; one array is itself) as a row DMA can
    fetch them: a float32 row's bits, or word ``j`` of a bfloat16 row
    its elements ``j`` (low half) and ``j + W`` (high); ``used (1,)``:
    only the first ``used[0]`` tiles of ``tr`` rows are read and written
    (all of them without it)."""
    rows, units = xs[0].shape
    dtype = xs[0].dtype
    tr = tr or _pick_block(rows, _WORD_TILE_ROWS)
    if used is None:
        used = jnp.full((1,), rows // tr, jnp.int32)
    return _mover_call(
        functools.partial(_moe_words_kernel, dtype=dtype),
        moe_words_plan(rows, units, tr, dtype, len(xs)), (used,),
        [x.astype(dtype) for x in xs])[0]


def _word_values(words, dtype):
    """The float32 values a block of words holds, one array a half."""
    if jnp.dtype(dtype).itemsize == 4:
        return [pltpu.bitcast(words, jnp.float32)]
    return [pltpu.bitcast(words << 16, jnp.float32),
            pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32)]


_DMA_UNROLL = 8


def _each_row(n, do):
    """``do(r)`` for ``r`` in ``range(n)``, ``n`` a traced scalar: whole
    groups of ``_DMA_UNROLL`` unrolled (a DMA's start or wait is a dozen
    scalar instructions; the loop's own bookkeeping would double them),
    then the rest one by one."""
    groups = n // _DMA_UNROLL

    def group(i, carry):
        for u in range(_DMA_UNROLL):
            do(i * _DMA_UNROLL + u)
        return carry

    def one(r, carry):
        do(r)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)
    jax.lax.fori_loop(groups * _DMA_UNROLL, n, one, 0)


def _landed_rows(buf, row0, rows, chunk):
    """Lane chunk ``chunk`` of ``rows`` consecutive rows from ``row0`` on
    of a landing buffer ``(n, 1, W)``: its rows lie ``W / 128`` sublanes
    apart, so a chunk of many rows is one strided load."""
    per = buf.shape[-1] // LANES
    flat = buf.reshape(buf.shape[0] * per, LANES)
    return flat[pl.ds(row0 * per + chunk, rows, stride=per), :]


def moe_rows_plan(p, t, units, tm, dtype=jnp.bfloat16, scaled=False,
                  dotted=False):
    """Plan of the buffer-side mover: grid over the ``p / tm`` row tiles;
    scalar prefetch ``used (1,)``, ``counts (tiles,)`` — a tile's valid
    rows are its first ``counts[i]`` — and ``src_token (p,)``; the source
    ``(t, 1, W)`` words stay in HBM.  ``scaled``: a float32 factor a row,
    ``(p, 1)``; ``dotted``: the matching tile of ``y (p, units)`` comes
    in and the float32 row dots ``(p, 1)`` go out.  Every blocked
    operand holds at the last used tile past ``used``, so an unused tile
    is neither fetched nor written."""
    words = _row_word_count(units, dtype)

    def tile(i, used, counts, src):
        return (_imin(i, used[0] - 1), 0)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    in_shapes = [(t, 1, words)]
    dtypes = [jnp.uint32]
    if scaled:
        in_specs.append(pl.BlockSpec((tm, 1), tile))
        in_shapes.append((p, 1))
        dtypes.append(jnp.float32)
    if dotted:
        in_specs.append(pl.BlockSpec((tm, units), tile))
        in_shapes.append((p, units))
        dtypes.append(dtype)
    out_specs = [pl.BlockSpec((tm, units), tile)]
    out_shapes = [(p, units)]
    dtypes.append(dtype)
    if dotted:
        out_specs.append(pl.BlockSpec((tm, 1), tile))
        out_shapes.append((p, 1))
        dtypes.append(jnp.float32)
    return {
        "grid": (p // tm,),
        "num_scalar_prefetch": 3,
        "in_specs": in_specs, "in_shapes": in_shapes,
        "out_specs": out_specs, "out_shapes": out_shapes,
        "tiles": [((tm, 1, words), "uint32")],      # the landing buffer
        "dtypes": dtypes,
    }


def _moe_rows_kernel(used_ref, counts_ref, src_ref, x_hbm, *refs, tm,
                     dtype, scaled, dotted):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    y_ref = refs.pop(0) if dotted else None
    o_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    i = pl.program_id(0)
    words = buf.shape[-1]

    @pl.when(i < used_ref[0])
    def _tile():
        n = counts_ref[i]

        _each_row(n, lambda r: pltpu.make_async_copy(
            x_hbm.at[src_ref[i * tm + r]], buf.at[r], sem).start())
        _each_row(n, lambda r: pltpu.make_async_copy(
            x_hbm.at[0], buf.at[r], sem).wait())
        on = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0) < n

        def chunk(c, dot):
            vals = _word_values(_landed_rows(buf, 0, tm, c), dtype)
            for h, v in enumerate(vals):
                # a row past the valid ones was never written: selected
                # away, not multiplied away
                v = jnp.where(on, v, 0.0)
                cols = _half_cols(h, c, words)
                if dotted:
                    dot += jnp.sum(v * y_ref[:, cols].astype(jnp.float32),
                                   axis=1, keepdims=True)
                if scaled:
                    v = v * scale_ref[:]
                o_ref[:, cols] = v.astype(o_ref.dtype)
            return dot

        dot = jax.lax.fori_loop(0, words // LANES, chunk,
                                jnp.zeros((tm, 1), jnp.float32))
        if dotted:
            dot_ref[:] = dot


def moe_rows(x, src_token, counts, used, tm, scale=None, y=None):
    """``rows (P, U)``: row ``r`` of tile ``i`` is ``x[src_token[i tm +
    r]]`` for ``r < counts[i]`` and zero for the tile's other rows — times
    ``scale[i tm + r]`` (float32, one a row) where that is given, rounded
    once.  With ``y (P, U)`` also ``dots (P,)``: the float32 dot of the
    UNSCALED row with ``y``'s row.  Tiles from ``used[0]`` on are not
    written at all: whoever reads the result skips them by ``used``, as
    the grouped product does."""
    _count("moe_rows")
    return _moe_rows(x, src_token, counts, used, tm, scale, y)


# jitted, so that a program's layers — the same shapes each — trace and
# lower a mover ONCE: a kernel's body is some hundred equations, a step
# holds dozens of instances, and a restart pays for each (set-up time)
@functools.partial(jax.jit, static_argnums=(4,))
def _moe_rows(x, src_token, counts, used, tm, scale, y):
    p, (t, units) = src_token.shape[0], x.shape
    plan = moe_rows_plan(p, t, units, tm, x.dtype, scale is not None,
                         y is not None)
    operands = [_row_words((x,))]
    if scale is not None:
        operands.append(scale.astype(jnp.float32).reshape(p, 1))
    if y is not None:
        operands.append(y.astype(x.dtype))
    out = _mover_call(
        functools.partial(_moe_rows_kernel, tm=tm, dtype=x.dtype,
                          scaled=scale is not None, dotted=y is not None),
        plan, (used, counts, src_token), operands)
    return out[0] if y is None else (out[0], out[1].reshape(p))


_SLOT_TILE_TOKENS = 128


def moe_slots_plan(t, k, p, units, bt, dtype=jnp.bfloat16):
    """Plan of the token-side mover: grid over the ``t / bt`` token
    tiles; scalar prefetch ``held (tiles,)``, how many of a tile's ``bt
    k`` assignments are held, and ``fetch (t k,)``, a tile's held
    assignments first, each its row of the buffer and the place its row
    lands in (``row << bits | place``); the rows ``(p, 1, W)`` words
    stay in HBM; ``dst`` (-1 where a slot is not held) and the float32
    weights come in as ``(bt, k)`` blocks, the tokens go out as ``(bt,
    units)``.  The landing buffer is slot-major, ``(k bt, 1, W)``."""
    words = _row_word_count(units, dtype)

    def tile(i, held, fetch):
        return (i, 0)

    return {
        "grid": (t // bt,),
        "num_scalar_prefetch": 2,
        "in_specs": [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec((bt, k), tile),
                     pl.BlockSpec((bt, k), tile)],
        "in_shapes": [(p, 1, words), (t, k), (t, k)],
        "out_specs": [pl.BlockSpec((bt, units), tile)],
        "out_shapes": [(t, units)],
        "tiles": [((k * bt, 1, words), "uint32")],  # the landing buffer
        "dtypes": [jnp.uint32, jnp.int32, jnp.float32, dtype],
    }


def _moe_slots_kernel(held_ref, fetch_ref, y_hbm, dst_ref, w_ref, o_ref,
                      buf, sem, *, bt, k, dtype):
    i = pl.program_id(0)
    words = buf.shape[-1]
    bits = (bt * k - 1).bit_length()

    def copy(q):
        code = fetch_ref[i * bt * k + q]
        return pltpu.make_async_copy(
            y_hbm.at[code >> bits], buf.at[code & ((1 << bits) - 1)], sem)

    _each_row(held_ref[i], lambda q: copy(q).start())
    _each_row(held_ref[i], lambda q: copy(q).wait())
    halves = 4 // jnp.dtype(dtype).itemsize

    def chunk(c, carry):
        acc = [jnp.zeros((bt, LANES), jnp.float32) for _ in range(halves)]
        for s in range(k):
            on = dst_ref[:, s:s + 1] >= 0
            ws = w_ref[:, s:s + 1]
            vals = _word_values(_landed_rows(buf, s * bt, bt, c), dtype)
            for h, v in enumerate(vals):
                # a slot that is not held was never fetched: selected
                # away, not multiplied away
                acc[h] = acc[h] + jnp.where(on, ws * v, 0.0)
        for h, a in enumerate(acc):
            o_ref[:, _half_cols(h, c, words)] = a.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, words // LANES, chunk, 0)


def moe_slots(ys, dst, w, is_held, used, tm):
    """``(T, U)``: token ``t`` gets ``sum_s w[t, s] y[dst[t, s]]`` over
    its HELD slots ``s`` in their order, summed in float32 and rounded
    once; a slot that is not held is neither fetched nor added.  ``y
    (P, U)`` is the sum of the arrays ``ys`` (a tuple), of which only
    the first ``used[0]`` tiles of ``tm`` rows are read (every held
    slot's row lies in one); ``dst``, ``w`` (float32), ``is_held``:
    ``(T, k)``."""
    _count("moe_slots")
    return _moe_slots(tuple(ys), dst, w, is_held, used, tm)


def _slot_fetch(rows, bt, k):
    """``(held (t / bt,), fetch (t / bt, bt k))`` of the slots' rows
    ``rows (t, k)`` (-1 where a slot is not held), a tile of ``bt``
    tokens a row: how many of a tile's slots are held, and those slots
    first, in their (token, slot) order, each its row of the buffer and
    the place its row lands in (``row << bits | place``).  One stable
    sort of each tile's codes keyed by "not held", no gather; what
    follows a tile's held slots is never read."""
    t = rows.shape[0]
    bits = (bt * k - 1).bit_length()
    flat = rows.reshape(t // bt, bt * k)
    q = jnp.arange(bt * k, dtype=jnp.int32)
    place = (q % k) * bt + q // k
    absent = (flat < 0).astype(jnp.int32)
    fetch = jax.lax.sort((absent, (flat << bits) | place), dimension=1,
                         num_keys=1)[1]
    return jnp.sum(1 - absent, axis=1, dtype=jnp.int32), fetch


@functools.partial(jax.jit, static_argnums=(5,))
def _moe_slots(ys, dst, w, is_held, used, tm):
    (p, units), (t, k), dtype = ys[0].shape, dst.shape, ys[0].dtype
    bt = _pick_block(t, _SLOT_TILE_TOKENS)
    bits = (bt * k - 1).bit_length()
    if p >= 1 << (31 - bits):
        raise ValueError("a buffer of %d rows is too long for tiles of %d "
                         "tokens with %d slots each" % (p, bt, k))
    rows = jnp.where(is_held, dst, -1).astype(jnp.int32)
    held, fetch = _slot_fetch(rows, bt, k)
    return _mover_call(
        functools.partial(_moe_slots_kernel, bt=bt, k=k, dtype=dtype),
        moe_slots_plan(t, k, p, units, bt, dtype),
        (held, fetch.reshape(t * k)),
        [_row_words(ys, used, tm), rows, w.astype(jnp.float32)])[0]


# ---------------------------------------------------------------------------
# Fused inference BatchNorm + ReLU epilogue
# ---------------------------------------------------------------------------
def _scale_bias_relu_kernel(x_ref, s_ref, b_ref, o_ref, *, relu):
    y = x_ref[:] * s_ref[:] + b_ref[:]
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[:] = y.astype(o_ref.dtype)


def scale_bias_relu_plan(n, c, bn):
    """Plan of the scale+bias+relu epilogue (x, scale, bias -> y):
    row-blocked x with the (1, C) vectors broadcast to every step."""
    spec = pl.BlockSpec((bn, c), lambda i: (i, 0))
    vspec = pl.BlockSpec((1, c), lambda i: (0, 0))
    return {
        "grid": (n // bn,),
        "in_specs": [spec, vspec, vspec],
        "in_shapes": [(n, c), (1, c), (1, c)],
        "out_specs": [spec],
        "out_shapes": [(n, c)],
        "scratch": [],
    }


def fused_scale_bias_relu(x, scale, bias, relu=True, block=1024):
    """y = relu(x * scale + bias) in one VMEM pass.

    x: (N, C) with per-column scale/bias (callers reshape NCHW to
    (N*H*W, C) layout first).  The inference BatchNorm epilogue:
    scale = gamma/sqrt(var+eps), bias = beta - mean*scale.
    """
    _count("fused_scale_bias_relu")
    n, c = x.shape
    bn = _row_block(n, c, x.dtype, block)
    xp = _pad_rows(x, bn)
    kernel = functools.partial(_scale_bias_relu_kernel, relu=relu)
    plan = scale_bias_relu_plan(xp.shape[0], c, bn)
    out = pl.pallas_call(
        kernel,
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"][0],
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        name="_scale_bias_relu_kernel",
        interpret=_interpret(),
    )(xp, scale.reshape(1, c), bias.reshape(1, c))
    return out[:n]


# ---------------------------------------------------------------------------
# One-sweep fused optimizer over param buckets
# ---------------------------------------------------------------------------
# The trainer's ZeRO path and the executor's fused step hand the update
# fp32 buffers (params / grads / slots in the SAME layout —
# parallel/collectives.py buckets).  A FLAT bucket is a contiguous 1-D
# buffer, viewed here as (rows, 128); a NATIVE bucket is one leaf as it
# lies in HBM, (rows, C) with C a multiple of 128, and reaches the
# kernel with no pad, no reshape and no slice (on the TPU any reshape
# that changes an f32 matrix's minor dimension is an element-wise
# re-layout).  One kernel sweeps a whole bucket: each grid step loads a
# (block_rows, C) tile of every buffer into VMEM, applies the exact
# per-element expressions of the tree_map path, and writes the new tile
# — no per-parameter kernel launches, no HBM round-trips between the
# update's elementwise stages.  Hyperparameters arrive as ONE
# scalar-prefetch vector so schedule changes never retrace.

_OPT_BLOCK_ELEMS = 128 * 1024     # default elems per grid step (auto)


def _block_elems():
    be = _knob("MXNET_PALLAS_OPT_BLOCK_ELEMS")
    be = int(be) if be else 0
    return be if be > 0 else _OPT_BLOCK_ELEMS


def _sweep_layout(n, block_elems):
    """(padded_rows, block_rows): the (rows, LANES) layout of an
    ``n``-element flat buffer, rows padded to a whole number of
    ``block_rows``-row grid steps (block_rows itself a multiple of the
    fp32 sublane tile, 8)."""
    block_rows = max(8, (block_elems // LANES) // 8 * 8)
    rows = -(-n // LANES)
    block_rows = min(block_rows, -(-rows // 8) * 8)
    padded_rows = -(-rows // block_rows) * block_rows
    return padded_rows, block_rows


def sweep_native_rows(shape, shards=1):
    """``(rows, C)`` if the sweep can tile an fp32 leaf of ``shape`` as
    it stands — leading dimensions collapsed into rows, each of
    ``shards`` equal row shards a whole number of (8, 128) tiles, and
    eight rows no more than a grid step's elements — else None (the
    leaf rides a flat bucket)."""
    if len(shape) < 2:
        return None
    c, rows = int(shape[-1]), math.prod(int(d) for d in shape[:-1])
    if c <= 0 or c % LANES or 8 * c > _block_elems() \
            or rows <= 0 or rows % (8 * int(shards)):
        return None
    return rows, c


def _to_rows(flat, padded_rows):
    pad = padded_rows * LANES - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(padded_rows, LANES)


def _hyper_vec(vals):
    """Pack hyperparameters into the ONE scalar-prefetch operand.
    Python floats and traced scalars mix freely; a changed VALUE is a
    new argument, not a new program."""
    return jnp.stack([jnp.asarray(v, jnp.float32) for v in vals])


def _prep_sweep_grad(g, w, h_ref, i_wd, i_rescale, i_clip, use_clip):
    """The shared gradient prologue — same expression (and grouping) as
    ``PureSGD/PureAdam.apply`` and ``optimizer_ops._prep_grad``:
    rescale, optional clip, decoupled-into-gradient weight decay."""
    g = g * h_ref[i_rescale]
    if use_clip:
        c = h_ref[i_clip]
        g = jnp.clip(g, -c, c)
    return g + h_ref[i_wd] * w


def _sgd_kernel(h_ref, w_ref, g_ref, ow_ref, *, use_clip):
    g = _prep_sweep_grad(g_ref[:], w_ref[:], h_ref, 1, 2, 3, use_clip)
    ow_ref[:] = w_ref[:] - h_ref[0] * g


def _sgd_mom_kernel(h_ref, w_ref, g_ref, m_ref, ow_ref, om_ref, *,
                    use_clip):
    g = _prep_sweep_grad(g_ref[:], w_ref[:], h_ref, 2, 3, 4, use_clip)
    nm = h_ref[1] * m_ref[:] - h_ref[0] * g
    ow_ref[:] = w_ref[:] + nm
    om_ref[:] = nm


def _adam_kernel(h_ref, w_ref, g_ref, m_ref, v_ref, ow_ref, om_ref,
                 ov_ref, *, use_clip):
    # h = [lr_eff, beta1, beta2, 1-beta1, 1-beta2, eps, wd, rescale, clip]
    g = _prep_sweep_grad(g_ref[:], w_ref[:], h_ref, 6, 7, 8, use_clip)
    nm = h_ref[1] * m_ref[:] + h_ref[3] * g
    nv = h_ref[2] * v_ref[:] + h_ref[4] * jnp.square(g)
    ow_ref[:] = w_ref[:] - h_ref[0] * nm / (jnp.sqrt(nv) + h_ref[5])
    om_ref[:] = nm
    ov_ref[:] = nv


def sweep_plan(shape, n_ins, n_outs):
    """Plan of one optimizer sweep over buffers of ``shape``: ``(n,)``,
    a flat bucket laid out as (rows, LANES) with a zero-padded tail, or
    ``(rows, C)``, a native bucket swept as it stands (the last block
    may overhang the rows; Pallas clips it at the array's edge).
    Either way a 1-D row-block grid, the ONE block-local spec every
    operand shares, and the scalar-prefetch slot.  Built by the dispatch
    (:func:`_sweep_call`) and abstractly interpreted by graftkern — the
    ``kern-shard-safety`` verdict that unlocks :func:`mesh_sweep_safe`
    reads index maps from THIS plan, so the proof is about the grid the
    kernel actually runs."""
    if len(shape) == 1:
        rows, block_rows = _sweep_layout(int(shape[0]), _block_elems())
        cols = LANES
    else:
        rows_c = sweep_native_rows(shape) if len(shape) == 2 else None
        if rows_c is None:
            raise ValueError(
                "the optimizer sweep takes a flat 1-D buffer or a (rows, "
                "C) buffer of whole (8, 128) tiles; got shape %s"
                % (tuple(shape),))
        rows, cols = rows_c
        block_rows = min(rows, max(8, (_block_elems() // cols) // 8 * 8))
    spec = pl.BlockSpec((block_rows, cols), lambda i, h: (i, 0))
    return {
        "grid": (-(-rows // block_rows),),
        "num_scalar_prefetch": 1,
        "in_specs": [spec] * n_ins,
        "in_shapes": [(rows, cols)] * n_ins,
        "out_specs": [spec] * n_outs,
        "out_shapes": [(rows, cols)] * n_outs,
        "scratch": [],
        "block_rows": block_rows,
    }


def _sweep_call_single(kernel, hyper, *bufs, n_outs):
    """One-device sweep dispatch (also the shard-local body under
    ``shard_map``).  A flat buffer is padded and reshaped to rows, swept
    over the plan's grid, and its logical elements sliced back out; a
    native ``(rows, C)`` buffer goes to the kernel and comes back as it
    is."""
    shape = bufs[0].shape
    plan = sweep_plan(shape, len(bufs), n_outs)
    rows, cols = plan["out_shapes"][0]
    flat = len(shape) == 1
    # a native bucket's weight and slots are the step's own (donated)
    # arrays: each output takes its input's buffer — new w over w, new
    # slot k over slot k (operands: hyper, w, g, slots...) — so nothing
    # is copied back; a block is read whole before it is written
    aliases = {} if flat else \
        {1: 0, **{k + 2: k for k in range(1, n_outs)}}
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=plan["num_scalar_prefetch"],
            grid=plan["grid"],
            in_specs=plan["in_specs"], out_specs=plan["out_specs"]),
        out_shape=[jax.ShapeDtypeStruct((rows, cols),
                                        jnp.float32)] * n_outs,
        input_output_aliases=aliases,
        name=kernel.func.__name__,
        interpret=_interpret(),
    )(hyper, *([_to_rows(f, rows) for f in bufs] if flat else bufs))
    if flat:
        return tuple(o.reshape(-1)[:shape[0]] for o in outs)
    return tuple(outs)


def _sweep_call(kernel, hyper, bufs, n_outs, mesh=None):
    """Dispatch one optimizer-sweep kernel over fp32 bucket buffers
    (flat 1-D or native ``(rows, C)``, all of one shape).

    With a multi-device ``mesh`` the sweep runs under ``shard_map``:
    every chip sweeps its contiguous 1/mesh shard of each buffer —
    dimension 0, elements of a flat bucket and rows of a native one —
    with the same kernel (hyperparameters replicated), the exact ZeRO
    layout the trainer's bucket plan hands in.  ``check_vma=False`` is
    mandatory — pallas_call has no replication rule — which is
    precisely the unproven-safety gap graftkern closes: the
    ``kern-shard-safety`` verdict (block-local index maps along the
    sharded rows axis, analysis/kern/) is the static proof that
    shard-local sweeps touch disjoint data, and zero-padded shard
    tails update to exactly zero just like the global tail."""
    if mesh is not None and getattr(mesh, "size", 1) > 1:
        n = bufs[0].shape[0]
        if n % mesh.size:
            raise ValueError(
                "fused sweep over a %d-device mesh needs the bucket's "
                "leading dimension (%d) to be a mesh multiple — the "
                "bucket plan's pad_multiple contract"
                % (mesh.size, n))
        from jax.sharding import PartitionSpec
        axes = PartitionSpec(tuple(mesh.axis_names))
        local = functools.partial(_sweep_call_single, kernel,
                                  n_outs=n_outs)
        outs = jax.shard_map(
            local, mesh=mesh,
            in_specs=(PartitionSpec(),) + (axes,) * len(bufs),
            out_specs=(axes,) * n_outs,
            check_vma=False)(hyper, *bufs)
        return list(outs)
    return list(_sweep_call_single(kernel, hyper, *bufs, n_outs=n_outs))


def fused_sgd_momentum(w, g, mom=None, lr=0.01, momentum=0.0, wd=0.0,
                       rescale=1.0, clip=None, mesh=None):
    """One-sweep SGD(+momentum) over one fp32 bucket.

    ``w``/``g``/``mom`` are same-layout buffers, flat 1-D or native
    ``(rows, C)`` (:func:`sweep_plan`); returns
    ``(new_w, new_mom)`` (``new_mom`` is None when ``mom`` is None —
    plain SGD carries no slot).  Scalars may be Python floats or traced
    values; all ride the scalar-prefetch operand.  Bit-identical to the
    per-array ``tree_map``/``optimizer_ops`` path by construction (same
    expressions, same grouping); a zero-padded tail stays exactly zero
    (0 - lr*(0 + wd*0) == 0), so bucket padding never perturbs real
    params.  A multi-device ``mesh`` shard_maps the sweep (see
    :func:`_sweep_call`): every update is elementwise, so per-shard
    re-padding changes nothing and the sharded result stays
    bit-identical too."""
    use_clip = clip is not None
    if mom is None:
        _count("fused_sgd")
        hyper = _hyper_vec([lr, wd, rescale] + ([clip] if use_clip else []))
        kernel = functools.partial(_sgd_kernel, use_clip=use_clip)
        (nw,) = _sweep_call(kernel, hyper, [w, g], 1, mesh=mesh)
        return nw, None
    _count("fused_sgd_momentum")
    hyper = _hyper_vec([lr, momentum, wd, rescale]
                       + ([clip] if use_clip else []))
    kernel = functools.partial(_sgd_mom_kernel, use_clip=use_clip)
    nw, nm = _sweep_call(kernel, hyper, [w, g, mom], 2, mesh=mesh)
    return nw, nm


def fused_adam(w, g, mean, var, lr_eff=0.001, beta1=0.9, beta2=0.999,
               epsilon=1e-8, wd=0.0, rescale=1.0, clip=None,
               mesh=None):
    """One-sweep Adam over one fp32 bucket (flat 1-D or native
    ``(rows, C)`` buffers, :func:`sweep_plan`).

    ``lr_eff`` is the EFFECTIVE learning rate — the caller folds in the
    bias-correction factor (``lr * sqrt(1-b2^t)/(1-b1^t)``, computed
    outside so `t` bookkeeping stays wherever the caller keeps it).
    ``beta1``/``beta2`` must be concrete floats: the ``1-beta`` moment
    coefficients are computed HOST-side in double precision, matching
    the per-array path's ``(1 - beta1) * g`` exactly (computing ``1-b``
    from an f32 scalar on device would differ by one ulp and break bit
    parity).  Zero-padded tails: mean/var stay 0 and the weight update
    is -lr*0/(sqrt(0)+eps) == 0.  A multi-device ``mesh`` shard_maps
    the sweep (see :func:`_sweep_call`) with the same bit-parity
    argument as :func:`fused_sgd_momentum`."""
    _count("fused_adam")
    use_clip = clip is not None
    hyper = _hyper_vec(
        [lr_eff, beta1, beta2, 1.0 - float(beta1), 1.0 - float(beta2),
         epsilon, wd, rescale] + ([clip] if use_clip else []))
    kernel = functools.partial(_adam_kernel, use_clip=use_clip)
    nw, nm, nv = _sweep_call(kernel, hyper, [w, g, mean, var], 3,
                             mesh=mesh)
    return nw, nm, nv


# ---------------------------------------------------------------------------
# Fused layernorm (fwd + custom_vjp bwd)
# ---------------------------------------------------------------------------
def _pad_rows(x2, br):
    r = x2.shape[0]
    pad = (-r) % br
    if pad:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((pad, x2.shape[1]), x2.dtype)])
    return x2


def _norm_block_rows(r, c, knob, dtype=jnp.float32):
    sub = _sublane(dtype)
    br = _knob(knob)
    if not br or br <= 0:
        br = min(256, _BLOCK_BYTES // max(4 * c, 1))
    br = max(sub, int(br) // sub * sub)
    return min(br, -(-r // sub) * sub)


def _norm_specs(br, c):
    spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    vspec = pl.BlockSpec((1, c), lambda i: (0, 0))
    sspec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    return spec, vspec, sspec


def layernorm_fwd_plan(rp, c, br):
    """Plan of the layernorm forward kernel
    (x, gamma, beta -> o, mu, rstd) over ``rp`` padded rows."""
    spec, vspec, sspec = _norm_specs(br, c)
    return {
        "grid": (rp // br,),
        "in_specs": [spec, vspec, vspec],
        "in_shapes": [(rp, c), (1, c), (1, c)],
        "out_specs": [spec, sspec, sspec],
        "out_shapes": [(rp, c), (rp, LANES), (rp, LANES)],
        "scratch": [],
    }


def layernorm_bwd_plan(rp, c, br):
    """Plan of the layernorm dx backward kernel
    (x, do, gamma, mu, rstd -> dx)."""
    spec, vspec, sspec = _norm_specs(br, c)
    return {
        "grid": (rp // br,),
        "in_specs": [spec, spec, vspec, sspec, sspec],
        "in_shapes": [(rp, c), (rp, c), (1, c), (rp, LANES),
                      (rp, LANES)],
        "out_specs": [spec],
        "out_shapes": [(rp, c)],
        "scratch": [],
    }


def fused_layernorm_eligible(c):
    """Whether the fused layernorm can run over a ``c``-wide last axis:
    Mosaic wants whole 128-lane minor-dim tiles on real TPU (padding is
    not an option here — pad columns would perturb the row stats);
    interpret mode has no such constraint, so CPU tests cover ragged C."""
    return _interpret() or c % LANES == 0


def _layernorm_fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rs_ref, *,
                          eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o_ref[:] = ((xc * rstd) * g_ref[:] + b_ref[:]).astype(o_ref.dtype)
    mu_ref[:] = jnp.broadcast_to(mu, mu_ref.shape)
    rs_ref[:] = jnp.broadcast_to(rstd, rs_ref.shape)


def _layernorm_bwd_kernel(x_ref, do_ref, g_ref, mu_ref, rs_ref, dx_ref):
    x = x_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    rstd = rs_ref[:, :1]
    xhat = (x - mu_ref[:, :1]) * rstd
    dxh = do * g_ref[:]
    c1 = jnp.mean(dxh, axis=1, keepdims=True)
    c2 = jnp.mean(dxh * xhat, axis=1, keepdims=True)
    dx_ref[:] = (rstd * (dxh - c1 - xhat * c2)).astype(dx_ref.dtype)


def _layernorm_fwd(x, gamma, beta, eps):
    _count("fused_layernorm_fwd")
    c = x.shape[-1]
    x2 = x.reshape(-1, c)
    r = x2.shape[0]
    br = _norm_block_rows(r, c, "MXNET_PALLAS_NORM_BLOCK_ROWS",
                          dtype=x.dtype)
    x2p = _pad_rows(x2, br)
    rp = x2p.shape[0]
    plan = layernorm_fwd_plan(rp, c, br)
    out, mu, rstd = pl.pallas_call(
        functools.partial(_layernorm_fwd_kernel, eps=eps),
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"],
        out_shape=[
            jax.ShapeDtypeStruct((rp, c), x.dtype),
            jax.ShapeDtypeStruct((rp, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rp, LANES), jnp.float32),
        ],
        name="_layernorm_fwd_kernel",
        interpret=_interpret(),
    )(x2p, gamma.reshape(1, c), beta.reshape(1, c))
    return out[:r].reshape(x.shape), (x, gamma, mu[:r], rstd[:r])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layernorm(x, gamma, beta, eps=1e-5):
    """LayerNorm over the LAST axis: mean/var/normalize/affine in one
    VMEM pass per row block (stats in fp32 whatever the input dtype).
    Backward is a fused dx kernel; dgamma/dbeta are plain row
    reductions XLA already does in one pass each."""
    out, _ = _layernorm_fwd(x, gamma, beta, eps)
    return out


def _fused_layernorm_fwd_rule(x, gamma, beta, eps):
    return _layernorm_fwd(x, gamma, beta, eps)


def _fused_layernorm_bwd_rule(eps, res, do):
    x, gamma, mu, rstd = res
    _count("fused_layernorm_bwd")
    c = x.shape[-1]
    x2 = x.reshape(-1, c)
    do2 = do.reshape(-1, c)
    r = x2.shape[0]
    br = _norm_block_rows(r, c, "MXNET_PALLAS_NORM_BLOCK_ROWS",
                          dtype=x.dtype)
    x2p = _pad_rows(x2, br)
    do2p = _pad_rows(do2, br)
    mup = _pad_rows(mu, br)
    rsp = _pad_rows(rstd, br)
    rp = x2p.shape[0]
    plan = layernorm_bwd_plan(rp, c, br)
    dx = pl.pallas_call(
        _layernorm_bwd_kernel,
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"][0],
        out_shape=jax.ShapeDtypeStruct((rp, c), x.dtype),
        name="_layernorm_bwd_kernel",
        interpret=_interpret(),
    )(x2p, do2p, gamma.reshape(1, c), mup, rsp)
    xhat = (x2.astype(jnp.float32) - mu[:, :1]) * rstd[:, :1]
    do32 = do2.astype(jnp.float32)
    dgamma = jnp.sum(do32 * xhat, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(do32, axis=0).astype(gamma.dtype)
    return dx[:r].reshape(x.shape), dgamma, dbeta


fused_layernorm.defvjp(_fused_layernorm_fwd_rule, _fused_layernorm_bwd_rule)


# ---------------------------------------------------------------------------
# Fused bias+softmax(+mask) (fwd + custom_vjp bwd)
# ---------------------------------------------------------------------------
def _softmax_fwd_kernel(x_ref, o_ref):
    s = x_ref[:].astype(jnp.float32)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    o_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _softmax_bias_fwd_kernel(x_ref, b_ref, o_ref):
    s = x_ref[:].astype(jnp.float32) + b_ref[:]
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    o_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _softmax_bwd_kernel(p_ref, do_ref, dx_ref):
    p = p_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    dot = jnp.sum(p * do, axis=-1, keepdims=True)
    dx_ref[:] = (p * (do - dot)).astype(dx_ref.dtype)


def softmax_plan(b, rp, c, n_ops, br, has_bias=False):
    """Plan of one fused-softmax pass over (B, rp, c) operands (plus
    the optional (rp, c) bias shared across B, appended last)."""
    spec = pl.BlockSpec((None, br, c), lambda bi, i: (bi, i, 0))
    ins = [spec] * n_ops
    in_shapes = [(b, rp, c)] * n_ops
    if has_bias:
        ins.append(pl.BlockSpec((br, c), lambda bi, i: (i, 0)))
        in_shapes.append((rp, c))
    return {
        "grid": (b, rp // br),
        "in_specs": ins,
        "in_shapes": in_shapes,
        "out_specs": [spec],
        "out_shapes": [(b, rp, c)],
        "scratch": [],
    }


def _softmax_call(kernel3, ops, col_fill, bias=None):
    """Shared scaffolding of every fused-softmax pass: dispatch
    ``kernel3`` over (B, R, C) operands (+ an optional (R, C) bias
    shared across B, appended last, matching the kernels' ref order).

    The last dim pads to whole 128-lane tiles so the Mosaic minor-dim
    constraint holds for ragged C (e.g. 1000-class logits) on real
    TPU; each operand pads with its own exact-identity ``col_fill``
    value — NEG_INF for logits (their exp underflows to exactly 0, row
    max and sum untouched), 0 for probabilities/cotangents (adds 0 to
    the p·do row dot, dx pad comes out 0).  Rows pad with zeros; pad
    rows and columns are sliced away before returning."""
    b, r, c0 = ops[0].shape
    cpad = (-c0) % LANES
    if cpad:
        ops = [jnp.concatenate(
            [a, jnp.full((b, r, cpad), fill, a.dtype)], axis=2)
            for a, fill in zip(ops, col_fill)]
        if bias is not None:
            bias = jnp.concatenate(
                [bias, jnp.zeros((bias.shape[0], cpad), bias.dtype)],
                axis=1)
    c = c0 + cpad
    br = _norm_block_rows(r, c, "MXNET_PALLAS_SOFTMAX_BLOCK_ROWS",
                          dtype=ops[0].dtype)
    rpad = (-r) % br
    if rpad:
        ops = [jnp.concatenate([a, jnp.zeros((b, rpad, c), a.dtype)],
                               axis=1) for a in ops]
        if bias is not None:
            bias = _pad_rows(bias, br)
    rp = r + rpad
    plan = softmax_plan(b, rp, c, len(ops), br,
                        has_bias=bias is not None)
    args = list(ops)
    if bias is not None:
        args.append(bias)
    out = pl.pallas_call(
        kernel3,
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"][0],
        out_shape=jax.ShapeDtypeStruct((b, rp, c), ops[0].dtype),
        name=kernel3.__name__,
        interpret=_interpret(),
    )(*args)
    return out[:, :r, :c0]


def _softmax_fwd(x, bias):
    _count("fused_softmax_fwd")
    c = x.shape[-1]
    if bias is None:
        p = _softmax_call(_softmax_fwd_kernel, [x.reshape(1, -1, c)],
                          [NEG_INF])
    else:
        if x.ndim < 2 or x.shape[-2] != bias.shape[0]:
            raise ValueError(
                "fused_bias_softmax: bias rows (%d) must equal x's "
                "second-to-last dim (%s)" % (bias.shape[0], x.shape))
        p = _softmax_call(_softmax_bias_fwd_kernel,
                          [x.reshape(-1, bias.shape[0], c)],
                          [NEG_INF], bias=bias.astype(jnp.float32))
    return p.reshape(x.shape)


def _softmax_bwd_dx(p, do):
    _count("fused_softmax_bwd")
    c = p.shape[-1]
    dx = _softmax_call(_softmax_bwd_kernel,
                       [p.reshape(1, -1, c), do.reshape(1, -1, c)],
                       [0.0, 0.0])
    return dx.reshape(p.shape)


@jax.custom_vjp
def _fused_softmax_nobias(x):
    return _softmax_fwd(x, None)


def _fused_softmax_nobias_fwd(x):
    p = _softmax_fwd(x, None)
    return p, p


def _fused_softmax_nobias_bwd(p, do):
    return (_softmax_bwd_dx(p, do),)


_fused_softmax_nobias.defvjp(_fused_softmax_nobias_fwd,
                             _fused_softmax_nobias_bwd)


@jax.custom_vjp
def _fused_softmax_bias(x, bias):
    return _softmax_fwd(x, bias)


def _fused_softmax_bias_fwd(x, bias):
    # zero-size prototype: carries the bias's rows/dtype through the
    # residual pytree as a REAL array (a dtype object leaf would break
    # under jit, same constraint ops/loss.py documents)
    p = _softmax_fwd(x, bias)
    return p, (p, jnp.zeros((bias.shape[0], 0), bias.dtype))


def _fused_softmax_bias_bwd(res, do):
    p, proto = res
    dx = _softmax_bwd_dx(p, do)
    # softmax(x + bias): d/dbias == d/dx summed over the broadcasted
    # leading dims (the bias is shared across them); the cotangent
    # must come back in the bias's own dtype for the vjp aval check
    c = p.shape[-1]
    dbias = jnp.sum(dx.reshape(-1, proto.shape[0], c), axis=0)
    return dx, dbias.astype(proto.dtype)


_fused_softmax_bias.defvjp(_fused_softmax_bias_fwd,
                           _fused_softmax_bias_bwd)


def fused_bias_softmax(x, bias=None):
    """softmax(x + bias) over the LAST axis in one VMEM pass per row
    block (max/exp/normalize fused; stats in fp32).

    ``bias`` is an optional additive (rows, C) mask/bias shared across
    ``x``'s remaining leading dims — the attention-mask form: the
    caller encodes masked positions as a large negative value (use
    ``NEG_INF``, finite, so fully-masked tails underflow to exactly 0
    instead of NaN).  Differentiable via a fused backward kernel; the
    bias cotangent is the dx row-sum over the broadcast dims."""
    if bias is None:
        return _fused_softmax_nobias(x)
    return _fused_softmax_bias(x, bias)
