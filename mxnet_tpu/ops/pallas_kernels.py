"""Pallas TPU kernels for hot paths where XLA fusion is not enough.

SURVEY.md §2.5/§7 names these the north star for the operator library's
hot paths — the MPK mega-kernel thesis (PAPERS.md) applied to this
tree's step function.  The catalog (docs/faq/perf.md has the
when-does-it-fuse table and the ``MXNET_PALLAS_*`` knobs):

- ``flash_attention`` — blockwise online-softmax attention (forward and
  backward), the kernel behind long-context attention: O(T) memory
  instead of XLA's materialized (T, T) logits.  This is the per-device
  block kernel of ring/Ulysses sequence parallelism
  (parallel/attention.py); reference long-sequence analogue: the fused
  cuDNN RNN workspace kernels (src/operator/cudnn_rnn-inl.h).
- ``fused_scale_bias_relu`` — the inference BatchNorm + ReLU epilogue as
  one VMEM-resident pass (reference: the BN+Activation fusion MKL-DNN
  does on CPU, nn/mkldnn/mkldnn_base-inl.h).  Call sites: the
  ``_contrib_fused_bn_relu`` operator and the executor's inference
  BatchNorm→Activation peephole (symbol.py ``build_graph_fn``).
- ``fused_sgd_momentum`` / ``fused_adam`` — the one-sweep fused
  optimizer: an ENTIRE flat 1-D bucket (params, grads and optimizer
  slots as contiguous same-layout buffers) updated in one VMEM-resident
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
kernel uses.
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
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
    # env > tuning DB (MXNET_TUNE; the "pallas-kernels" program) >
    # default — block-size knobs the grafttune sweep won bind here
    # without any env plumbing, while an explicit env var still wins
    from .. import config as _config
    return _config.tuned(name, program="pallas-kernels")


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
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, *, scale, causal, bq, bk, nk):
    """Grid (BH, nQ, nK); accumulate across the sequential nK dimension in
    VMEM scratch, finalize on the last K step (the canonical online-
    softmax schedule)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: skip K blocks entirely above the diagonal
    run = True if not causal else (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[:]                                    # (BQ, D)
        k = k_ref[:]                                    # (BK, D)
        v = v_ref[:]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[:, :1]                           # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)                 # (BQ, 1)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _final():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[:] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, scale, causal, bq, bk, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True if not causal else (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[:, :1])
        dp = jax.lax.dot_general(do_ref[:], v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, :1]) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _final():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                          bq, bk, nq):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True if not causal else (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[:, :1])                      # (BQ, BK)
        do = do_ref[:]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, :1]) * scale             # (BQ, BK)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
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


def _qspec(bq, d):
    return pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0))


def _kspec(bk, d):
    return pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0))


def _lmspec(bq):
    return pl.BlockSpec((None, bq, LANES), lambda b, i, j: (b, i, 0))


# Kernel plans: each family's grid / BlockSpecs / operand shapes as one
# declarative dict, built by the SAME function the dispatch consumes —
# graftkern (analysis/kern/) abstractly interprets these plans, so the
# verifier checks exactly the grid and index maps the kernel runs (no
# drift by construction).  Shapes are the PADDED shapes the pallas_call
# sees; "scratch" lists fp32 VMEM scratch shapes.

def flash_fwd_plan(bh, tq, tk, d, bq, bk):
    """Plan of the flash-attention forward kernel (q, k, v -> o, lse)."""
    return {
        "grid": (bh, tq // bq, tk // bk),
        "in_specs": [_qspec(bq, d), _kspec(bk, d), _kspec(bk, d)],
        "in_shapes": [(bh, tq, d), (bh, tk, d), (bh, tk, d)],
        "out_specs": [_qspec(bq, d), _lmspec(bq)],
        "out_shapes": [(bh, tq, d), (bh, tq, LANES)],
        "scratch": [(bq, d), (bq, LANES), (bq, LANES)],
    }


def flash_bwd_dq_plan(bh, tq, tk, d, bq, bk):
    """Plan of the dq backward kernel
    (q, k, v, do, lse, delta -> dq)."""
    return {
        "grid": (bh, tq // bq, tk // bk),
        "in_specs": [_qspec(bq, d), _kspec(bk, d), _kspec(bk, d),
                     _qspec(bq, d), _lmspec(bq), _lmspec(bq)],
        "in_shapes": [(bh, tq, d), (bh, tk, d), (bh, tk, d),
                      (bh, tq, d), (bh, tq, LANES), (bh, tq, LANES)],
        "out_specs": [_qspec(bq, d)],
        "out_shapes": [(bh, tq, d)],
        "scratch": [(bq, d)],
    }


def flash_bwd_dkv_plan(bh, tq, tk, d, bq, bk):
    """Plan of the dk/dv backward kernel — grid (BH, nK, nQ), so the
    q-side specs transpose their two minor grid coordinates."""
    qspec_t = pl.BlockSpec((None, bq, d), lambda b, j, i: (b, i, 0))
    kspec_t = pl.BlockSpec((None, bk, d), lambda b, j, i: (b, j, 0))
    lmspec_t = pl.BlockSpec((None, bq, LANES), lambda b, j, i: (b, i, 0))
    return {
        "grid": (bh, tk // bk, tq // bq),
        "in_specs": [qspec_t, kspec_t, kspec_t, qspec_t, lmspec_t,
                     lmspec_t],
        "in_shapes": [(bh, tq, d), (bh, tk, d), (bh, tk, d),
                      (bh, tq, d), (bh, tq, LANES), (bh, tq, LANES)],
        "out_specs": [kspec_t, kspec_t],
        "out_shapes": [(bh, tk, d), (bh, tk, d)],
        "scratch": [(bk, d), (bk, d)],
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128):
    """Blockwise online-softmax attention.

    q, k, v: (BH, T, D) — fold batch and heads into the leading dim.
    Returns (BH, T, D).  O(T) memory; causal masking skips upper-
    triangular K blocks entirely.
    """
    o, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    _count("flash_attention_fwd")
    bh, tq, d = q.shape
    tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    nk = tk // bk
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    plan = flash_fwd_plan(bh, tq, tk, d, bq, bk)
    o, lse = pl.pallas_call(
        kernel,
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, tq, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32)
                        for s in plan["scratch"]],
        name="_flash_fwd_kernel",
        interpret=_interpret(),
    )(q, k, v)
    return o, (q, k, v, o, lse)


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k):
    o, res = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return o, res


def _flash_bwd_rule(causal, scale, block_q, block_k, res, do):
    _count("flash_attention_bwd")
    q, k, v, o, lse = res
    bh, tq, d = q.shape
    tk = k.shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    nq, nk = tq // bq, tk // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (bh, tq, LANES))
    dq_plan = flash_bwd_dq_plan(bh, tq, tk, d, bq, bk)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=s, causal=causal,
                          bq=bq, bk=bk, nk=nk),
        grid=dq_plan["grid"],
        in_specs=dq_plan["in_specs"],
        out_specs=dq_plan["out_specs"][0],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM(sh, jnp.float32)
                        for sh in dq_plan["scratch"]],
        name="_flash_bwd_dq_kernel",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    dkv_plan = flash_bwd_dkv_plan(bh, tq, tk, d, bq, bk)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=s, causal=causal,
                          bq=bq, bk=bk, nq=nq),
        grid=dkv_plan["grid"],
        in_specs=dkv_plan["in_specs"],
        out_specs=dkv_plan["out_specs"],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM(sh, jnp.float32)
                        for sh in dkv_plan["scratch"]],
        name="_flash_bwd_dkv_kernel",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


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
# One-sweep fused optimizer over flat param buckets
# ---------------------------------------------------------------------------
# The trainer's ZeRO path and the executor's fused step hand the update
# contiguous 1-D fp32 buffers (params / grads / slots in the SAME flat
# layout — parallel/collectives.py buckets).  One kernel sweeps a whole
# bucket: each grid step loads a (rows, 128) tile of every buffer into
# VMEM, applies the exact per-element expressions of the tree_map path,
# and writes the new tile — no per-parameter kernel launches, no HBM
# round-trips between the update's elementwise stages.  Hyperparameters
# arrive as ONE scalar-prefetch vector so schedule changes never retrace.

_OPT_BLOCK_ELEMS = 128 * 1024     # default elems per grid step (auto)


def _sweep_layout(n, block_elems):
    """(padded_rows, block_rows): the (rows, LANES) layout of an
    ``n``-element flat buffer, rows padded to a whole number of
    ``block_rows``-row grid steps (block_rows itself a multiple of the
    fp32 sublane tile, 8)."""
    be = int(block_elems) if block_elems else 0
    if be <= 0:
        be = _OPT_BLOCK_ELEMS
    block_rows = max(8, (be // LANES) // 8 * 8)
    rows = -(-n // LANES)
    block_rows = min(block_rows, -(-rows // 8) * 8)
    padded_rows = -(-rows // block_rows) * block_rows
    return padded_rows, block_rows


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


def sweep_plan(n, n_ins, n_outs, block_elems=None):
    """Plan of one optimizer sweep over ``n``-element flat buffers:
    the (rows, LANES) layout, 1-D row-block grid, the ONE block-local
    spec every operand shares, and the scalar-prefetch slot.  Built by
    the dispatch (:func:`_sweep_call`) and abstractly interpreted by
    graftkern — the ``kern-shard-safety`` verdict that unlocks
    :func:`mesh_sweep_safe` reads index maps from THIS plan, so the
    proof is about the grid the kernel actually runs."""
    if block_elems is None:
        block_elems = _knob("MXNET_PALLAS_OPT_BLOCK_ELEMS")
    padded_rows, block_rows = _sweep_layout(n, block_elems)
    spec = pl.BlockSpec((block_rows, LANES), lambda i, h: (i, 0))
    return {
        "grid": (padded_rows // block_rows,),
        "num_scalar_prefetch": 1,
        "in_specs": [spec] * n_ins,
        "in_shapes": [(padded_rows, LANES)] * n_ins,
        "out_specs": [spec] * n_outs,
        "out_shapes": [(padded_rows, LANES)] * n_outs,
        "scratch": [],
        "block_rows": block_rows,
    }


def _sweep_call_single(kernel, hyper, *flats, n_outs, block_elems):
    """One-device sweep dispatch (also the shard-local body under
    ``shard_map``): pad + reshape to rows, run the kernel over the
    plan's grid, slice the logical elements back out."""
    n = flats[0].shape[0]
    plan = sweep_plan(n, len(flats), n_outs, block_elems)
    padded_rows = plan["out_shapes"][0][0]
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=plan["num_scalar_prefetch"],
            grid=plan["grid"],
            in_specs=plan["in_specs"], out_specs=plan["out_specs"]),
        out_shape=[jax.ShapeDtypeStruct((padded_rows, LANES),
                                        jnp.float32)] * n_outs,
        name=kernel.func.__name__,
        interpret=_interpret(),
    )(hyper, *[_to_rows(f, padded_rows) for f in flats])
    return tuple(o.reshape(-1)[:n] for o in outs)


def _sweep_call(kernel, hyper, flats, n_outs, block_elems, mesh=None):
    """Dispatch one optimizer-sweep kernel over flat fp32 buffers.

    With a multi-device ``mesh`` the sweep runs under ``shard_map``:
    every chip sweeps its contiguous 1/mesh shard of each buffer with
    the same kernel (hyperparameters replicated), the exact ZeRO
    layout the trainer's bucket plan hands in.  ``check_vma=False`` is
    mandatory — pallas_call has no replication rule — which is
    precisely the unproven-safety gap graftkern closes: the
    ``kern-shard-safety`` verdict (block-local index maps along the
    sharded rows axis, analysis/kern/) is the static proof that
    shard-local sweeps touch disjoint data, and zero-padded shard
    tails update to exactly zero just like the global tail."""
    if mesh is not None and getattr(mesh, "size", 1) > 1:
        n = flats[0].shape[0]
        if n % mesh.size:
            raise ValueError(
                "fused sweep over a %d-device mesh needs the flat "
                "bucket length (%d) padded to a mesh multiple — the "
                "bucket plan's pad_multiple contract"
                % (mesh.size, n))
        from jax.sharding import PartitionSpec
        axes = PartitionSpec(tuple(mesh.axis_names))
        local = functools.partial(_sweep_call_single, kernel,
                                  n_outs=n_outs,
                                  block_elems=block_elems)
        outs = jax.shard_map(
            local, mesh=mesh,
            in_specs=(PartitionSpec(),) + (axes,) * len(flats),
            out_specs=(axes,) * n_outs,
            check_vma=False)(hyper, *flats)
        return list(outs)
    return list(_sweep_call_single(kernel, hyper, *flats, n_outs=n_outs,
                                   block_elems=block_elems))


def fused_sgd_momentum(w, g, mom=None, lr=0.01, momentum=0.0, wd=0.0,
                       rescale=1.0, clip=None, block_elems=None,
                       mesh=None):
    """One-sweep SGD(+momentum) over a flat fp32 bucket.

    ``w``/``g``/``mom`` are contiguous 1-D same-layout buffers; returns
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
    if block_elems is None:
        block_elems = _knob("MXNET_PALLAS_OPT_BLOCK_ELEMS")
    use_clip = clip is not None
    if mom is None:
        _count("fused_sgd")
        hyper = _hyper_vec([lr, wd, rescale] + ([clip] if use_clip else []))
        kernel = functools.partial(_sgd_kernel, use_clip=use_clip)
        (nw,) = _sweep_call(kernel, hyper, [w, g], 1, block_elems,
                            mesh=mesh)
        return nw, None
    _count("fused_sgd_momentum")
    hyper = _hyper_vec([lr, momentum, wd, rescale]
                       + ([clip] if use_clip else []))
    kernel = functools.partial(_sgd_mom_kernel, use_clip=use_clip)
    nw, nm = _sweep_call(kernel, hyper, [w, g, mom], 2, block_elems,
                         mesh=mesh)
    return nw, nm


def fused_adam(w, g, mean, var, lr_eff=0.001, beta1=0.9, beta2=0.999,
               epsilon=1e-8, wd=0.0, rescale=1.0, clip=None,
               block_elems=None, mesh=None):
    """One-sweep Adam over a flat fp32 bucket.

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
    if block_elems is None:
        block_elems = _knob("MXNET_PALLAS_OPT_BLOCK_ELEMS")
    _count("fused_adam")
    use_clip = clip is not None
    hyper = _hyper_vec(
        [lr_eff, beta1, beta2, 1.0 - float(beta1), 1.0 - float(beta2),
         epsilon, wd, rescale] + ([clip] if use_clip else []))
    kernel = functools.partial(_adam_kernel, use_clip=use_clip)
    nw, nm, nv = _sweep_call(kernel, hyper, [w, g, mean, var], 3,
                             block_elems, mesh=mesh)
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


def _norm_block_rows(r, c, knob, value=None, dtype=jnp.float32):
    # `value` lets grafttune price a CANDIDATE block size through the
    # exact production clamp without touching the process env
    sub = _sublane(dtype)
    br = _knob(knob) if value is None else value
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
