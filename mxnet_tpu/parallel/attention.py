"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference predates attention entirely (SURVEY.md §5.7 — its
long-sequence story is bucketing + fused cuDNN RNN).  These are the
first-class TPU-native long-context primitives layered on the collective
backend, as SURVEY.md §7 requires:

- ``ring_attention``: blockwise-stable attention over a sequence-sharded
  mesh axis.  K/V blocks rotate around the ring via ``lax.ppermute``
  (ICI neighbor exchange) while each device accumulates its queries'
  output with running log-sum-exp — memory O(T/sp) per device,
  overlapping compute with the permute.  (Liu et al. 2310.01889.)
- ``ulysses_attention``: all-to-all resharding seq->heads, local full
  attention, all-to-all back (Jacobs et al. 2309.14509).  Cheaper when
  heads % sp == 0; ring has no head-count constraint.

Both run under ``shard_map`` over the "sp" axis; causal masking uses
global position offsets per shard.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["ring_attention", "ulysses_attention", "local_attention",
           "block_diffusion_mask"]


def _flash_eligible(q, k, causal, q_offset, kv_offset):
    """Flash path: TPU backend, aligned offsets (the kernel's causal mask
    assumes a shared origin), sequence lengths the kernel's halving
    block choice tiles in whole sublane tiles of the operand dtype."""
    from ..ops.pallas_kernels import flash_seq_ok
    if jax.default_backend() != "tpu":
        return False
    if causal and (q_offset != 0 or kv_offset != 0):
        return False
    return flash_seq_ok(q.shape[1], q.dtype) \
        and flash_seq_ok(k.shape[1], k.dtype)


def block_diffusion_mask(t, length):
    """The ``(t, t)`` boolean mask block diffusion trains under
    (Arriola et al., arXiv:2503.09573, section 4), from its three-part
    definition: the ``t = 2 L`` rows are a noised copy of a sequence and
    then its clean copy, both in blocks of ``length`` positions; True
    where query row r sees key row c."""
    if t % (2 * length):
        raise ValueError(
            "the block-diffusion mask needs rows [noised ; clean] of two "
            "equal halves of whole blocks: got %d rows, blocks of %d"
            % (t, length))
    row = jnp.arange(t)
    clean = row >= t // 2
    blk = (row % (t // 2)) // length
    rq, rk, bq, bk = clean[:, None], clean[None, :], blk[:, None], \
        blk[None, :]
    return ((rq == rk) & (bq == bk)             # a block sees itself
            | ~rq & rk & (bq > bk)              # noised: clean blocks before
            | rq & rk & (bq >= bk))             # clean: block-causal


def local_attention(q, k, v, causal=False, q_offset=0, kv_offset=0,
                    scale=None, impl="auto", kv_len=None, window=None,
                    kept=False, block_diffusion=None):
    """Softmax attention on local blocks.

    q: (B, Tq, H, D), k: (B, Tk, Hkv, D), v: (B, Tk, Hkv, Dv) — the
    values' head size may differ from the keys' (latent attention:
    scores over 192 dimensions, values of 128); the result is (B, Tq, H,
    Dv) and the default scale is ``D ** -0.5``.  ``Hkv`` divides ``H``
    (grouped-query attention: ``H / Hkv`` query heads share a key/value
    head): the flash kernels index the shared head, the einsum form
    repeats it.  Offsets give the global
    positions of the first query/key for causal masking across shards.
    ``kv_len`` masks out keys whose global position is >= kv_len —
    the padding mask for sequences padded up to a shard multiple.
    ``window`` (with ``causal``): a query sees its last ``window`` keys,
    itself among them — in the flash kernels and in the einsum form
    alike.

    ``block_diffusion`` (a block length; neither ``causal`` nor
    ``window``, offsets 0, no ``kv_len``): the rows are ``[noised ;
    clean]`` and a query sees what :func:`block_diffusion_mask` says — in
    the flash kernels, which skip the tiles with no visible pair, and in
    the einsum form alike.

    impl: "auto" uses the Pallas flash kernel on TPU when offsets are
    aligned and T divides into blocks (O(T) memory instead of the
    materialized (T, T) logits); "einsum"/"flash" force a path.
    ``kept`` goes to the flash kernels' op as it is
    (``pallas_kernels.flash_attention``); the einsum form has nothing
    to keep.
    """
    d = q.shape[-1]
    if window is not None and not causal:
        raise ValueError("an attention window needs causal=True: a query "
                         "sees its last `window` keys, itself among them")
    if block_diffusion is not None:
        block_diffusion = int(block_diffusion)
        if causal or window is not None or q_offset or kv_offset \
                or kv_len is not None or q.shape[1] != k.shape[1]:
            raise ValueError(
                "the block-diffusion mask is a mask of its own over one "
                "whole sequence of rows [noised ; clean]: no causal, "
                "window, offset or kv_len goes with it")
    if kv_len is not None and kv_len >= kv_offset + k.shape[1]:
        kv_len = None  # no padded keys in this block
    use_flash = (kv_len is None and
                 (impl == "flash" or
                  (impl == "auto" and _flash_eligible(q, k, causal,
                                                      q_offset, kv_offset))))
    if use_flash and block_diffusion is not None and impl != "flash":
        from ..ops.pallas_kernels import flash_block_diffusion_ok
        use_flash = flash_block_diffusion_ok(q.shape[1], block_diffusion,
                                             q.dtype)
    if use_flash:
        from ..ops.pallas_kernels import flash_attention
        b, tq, h, _ = q.shape
        # batch-major: query head b H + i reads key/value head b Hkv + i // g
        fold = lambda a: jnp.transpose(a, (0, 2, 1, 3)).reshape(
            b * a.shape[2], a.shape[1], a.shape[-1])
        o = flash_attention(fold(q), fold(k), fold(v), causal, scale, None,
                            None, window, kept, block_diffusion)
        return jnp.transpose(o.reshape(b, h, tq, v.shape[-1]), (0, 2, 1, 3))
    k, v = _expand_kv_heads(q, k, v)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    kpos = kv_offset + jnp.arange(k.shape[1])
    mask = None
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask = mask & (qpos[:, None] - kpos[None, :] < int(window))
    if block_diffusion is not None:
        mask = block_diffusion_mask(q.shape[1], block_diffusion)
    if kv_len is not None:
        valid = (kpos < kv_len)[None, :]
        mask = valid if mask is None else mask & valid
    from ..ops.pallas_kernels import family_enabled
    # fused-path gate: kv_len padding keeps the einsum form (its
    # all-masked padded-query rows are DEFINED to come out zero via the
    # NaN fixup), and a causal mask is only safe when every query row
    # keeps at least one valid key (q_offset >= kv_offset ⇒ key 0 is
    # visible to every row) — a fully-masked row under the kernel's
    # finite NEG_INF bias would silently softmax to uniform instead of
    # surfacing the misuse as NaN
    if (kv_len is None and (not causal or q_offset >= kv_offset)
            and family_enabled("MXNET_PALLAS_SOFTMAX")):
        # fused bias+softmax(+mask) kernel: the (Tq, Tk) mask becomes an
        # additive bias (finite NEG_INF so masked columns underflow to
        # exactly 0), max/exp/normalize fuse into one VMEM pass per row
        # block, backward rides the kernel's custom_vjp.  The kv_len
        # (padded-tail) path keeps the einsum form: its all-masked
        # padded-query rows are DEFINED to come out zero, which the
        # -inf + NaN fixup below encodes.
        from ..ops.pallas_kernels import NEG_INF, fused_bias_softmax
        b, h, tq, tk = logits.shape
        bias = None
        if mask is not None:
            bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
        probs = fused_bias_softmax(
            logits.reshape(b * h, tq, tk), bias).reshape(b, h, tq, tk)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # rows with no valid key (padded queries under a pure padding mask)
    # would softmax over -inf only; zero them instead of NaN
    if kv_len is not None:
        probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _single_device_of(x):
    """The one device ``x`` lives on when eager/committed, else None
    (already distributed or inside a trace)."""
    try:
        devs = x.devices()
        if len(devs) == 1:
            return next(iter(devs))
    except Exception:
        pass
    return None


def _restore_device(out, home):
    """Gather a mesh-sharded eager result back to the caller's device so
    downstream eager ops (replicated weights on one device) compose.
    Under jit / with distributed inputs this is a no-op — GSPMD keeps
    the value sharded."""
    if home is None:
        return out
    try:
        return jax.device_put(out, home)
    except Exception:  # pragma: no cover - tracers
        return out


def _pad_to_shards(q, k, v, sp):
    """Pad the time axis up to a multiple of ``sp``.

    Returns (q, k, v, kv_len) where kv_len is the real key count when
    padding was added (the shard bodies mask keys past it) or None when
    the length already divides evenly."""
    t = q.shape[1]
    pad = (-t) % sp
    if pad == 0:
        return q, k, v, None
    widths = ((0, 0), (0, pad), (0, 0), (0, 0))
    return (jnp.pad(q, widths), jnp.pad(k, widths), jnp.pad(v, widths), t)


def _expand_kv_heads(q, k, v):
    """GQA/MQA: replicate K/V heads up to the query head count when
    num_kv_heads divides num_q_heads (grouped-query attention) — for the
    einsum form and the sequence-parallel bodies; the flash kernels
    index the shared head instead."""
    hq, hkv = q.shape[2], k.shape[2]
    if hq == hkv:
        return k, v
    if hq % hkv:
        raise ValueError("GQA needs q heads (%d) divisible by kv heads (%d)"
                         % (hq, hkv))
    rep = hq // hkv
    return (jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2))


def _ring_attention_local(q, k, v, axis_name, causal, scale, kv_len=None):
    """Per-device body under shard_map: rotate K/V around the ring.
    ``kv_len`` masks keys at global positions >= kv_len (tail padding)."""
    k, v = _expand_kv_heads(q, k, v)
    axis_size = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    q_offset = idx * t_local

    def block(carry, kv_and_src):
        o, m, l = carry                  # running output, max, denom
        kk, vv, src = kv_and_src
        kv_offset = src * t_local
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * scale
        kpos = kv_offset + jnp.arange(t_local)
        mask = None
        if causal:
            qpos = q_offset + jnp.arange(t_local)
            mask = qpos[:, None] >= kpos[None, :]
        if kv_len is not None:
            valid = (kpos < kv_len)[None, :]
            mask = valid if mask is None else mask & valid
        if mask is not None:
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
        block_max = jnp.max(logits, axis=-1)                    # (b,h,q)
        new_m = jnp.maximum(m, block_max)
        # guard -inf rows (no valid key yet) against NaN in exp
        new_m_safe = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        p = jnp.exp(logits - new_m_safe[..., None])
        p = jnp.where(jnp.isneginf(logits), 0.0, p)
        correction = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m)
                             - new_m_safe)
        correction = jnp.where(jnp.isneginf(m), 0.0, correction)
        l_new = l * correction + jnp.sum(p, axis=-1)
        o_new = o * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vv)
        return (o_new, new_m, l_new)

    o = jnp.zeros((b, h, t_local, d), q.dtype)
    m = jnp.full((b, h, t_local), -jnp.inf, q.dtype)
    l = jnp.zeros((b, h, t_local), q.dtype)
    carry = (o, m, l)

    kk, vv = k, v
    src = idx
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    for step in range(axis_size):
        carry = block(carry, (kk, vv, src))
        if step != axis_size - 1:
            # neighbor exchange on ICI; overlaps with next block's compute
            kk = lax.ppermute(kk, axis_name, perm)
            vv = lax.ppermute(vv, axis_name, perm)
            src = (src - 1) % axis_size
    o, m, l = carry
    l = jnp.where(l == 0.0, 1.0, l)
    out = o / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3))  # (b, t_local, h, d)


def _no_window_across_shards(window, what, block_diffusion=None):
    if window is not None:
        raise NotImplementedError(
            "%s has no attention window: its shards mask by the causal "
            "rule alone, and ignoring window=%r would be another model"
            % (what, window))
    if block_diffusion is not None:
        raise NotImplementedError(
            "%s has no block-diffusion mask: its shards mask by the causal "
            "rule alone, a noised row's keys lie in BOTH halves of the "
            "rows, and ignoring block_diffusion=%r would be another model"
            % (what, block_diffusion))


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=False,
                   scale=None, window=None, block_diffusion=None):
    """Ring attention over a sequence-sharded axis.

    Inputs (B, T, H, D) with T sharded over ``axis_name``; output has the
    same sharding.  Used directly or as the attention core of
    sequence-parallel transformer layers.  A ``window`` or the
    ``block_diffusion`` mask is served only where the axis has one
    member (``local_attention``); across shards either raises."""
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        return local_attention(q, k, v, causal=causal, scale=scale,
                               window=window,
                               block_diffusion=block_diffusion)
    _no_window_across_shards(window, "ring_attention", block_diffusion)
    sp = mesh.shape[axis_name]
    t_real = q.shape[1]
    home = _single_device_of(q)
    q, k, v, kv_len = _pad_to_shards(q, k, v, sp)
    spec = P(None, axis_name, None, None)
    # explicit scatter onto the mesh: inputs may arrive committed to a
    # single device (jit outputs are), which shard_map rejects
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(a, sharding) for a in (q, k, v))
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale, kv_len=kv_len),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = fn(q, k, v)
    if kv_len is not None:
        out = out[:, :t_real]
    return _restore_device(out, home)


def _ulysses_local(q, k, v, axis_name, causal, scale, kv_len=None):
    """all-to-all seq->head, full local attention, all-to-all back."""
    k, v = _expand_kv_heads(q, k, v)
    sp = lax.psum(1, axis_name)
    # (b, t/sp, h, d) -> gather seq, scatter heads -> (b, t, h/sp, d)
    q = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = local_attention(q, k, v, causal=causal, scale=scale, kv_len=kv_len)
    # back: scatter seq, gather heads
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(q, k, v, mesh=None, axis_name="sp", causal=False,
                      scale=None, window=None, block_diffusion=None):
    """DeepSpeed-Ulysses style sequence parallelism; requires
    num_heads % sp == 0.  A ``window`` or ``block_diffusion`` as in
    :func:`ring_attention`."""
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        return local_attention(q, k, v, causal=causal, scale=scale,
                               window=window,
                               block_diffusion=block_diffusion)
    _no_window_across_shards(window, "ulysses_attention", block_diffusion)
    sp = mesh.shape[axis_name]
    if q.shape[2] % sp:
        raise ValueError(
            "ulysses needs heads (%d) divisible by sp (%d); use "
            "ring_attention" % (q.shape[2], sp))
    t_real = q.shape[1]
    home = _single_device_of(q)
    q, k, v, kv_len = _pad_to_shards(q, k, v, sp)
    spec = P(None, axis_name, None, None)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(a, sharding) for a in (q, k, v))
    fn = jax.shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name, causal=causal,
                          scale=scale, kv_len=kv_len),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = fn(q, k, v)
    if kv_len is not None:
        out = out[:, :t_real]
    return _restore_device(out, home)
