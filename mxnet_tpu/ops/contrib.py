"""Contrib operator family: SSD detection ops, generic box ops, ROI
pooling, region proposals, deformable convolution, FFT.

Reference contracts re-designed (not ported):
- MultiBoxPrior/Target/Detection: src/operator/contrib/multibox_prior-inl.h,
  multibox_target.cc:72-280, multibox_detection.cc.
- box_nms / box_iou / bipartite_matching: src/operator/contrib/bounding_box-inl.h.
- ROIPooling: src/operator/roi_pooling.cc; ROIAlign is the modern variant.
- Proposal/MultiProposal: src/operator/contrib/multi_proposal-inl.h.
- DeformableConvolution: src/operator/contrib/deformable_convolution-inl.h.
- fft/ifft: src/operator/contrib/fft-inl.h (interleaved re/im layout).

TPU-native design notes: every op is a pure jax function with static
shapes.  The reference's per-element CPU/CUDA loops (greedy matching,
NMS chains) become fixed-trip ``lax.fori_loop``s over O(N^2) IoU
matrices — data-independent shapes so XLA compiles one program; the
batch dimension is ``jax.vmap``.  Sorting uses XLA's sort HLO.  ROI
pooling uses a masked-max formulation that differentiates cleanly with
``jax.vjp`` (the reference carries an explicit argmax aux output
instead, roi_pooling-inl.h kMaxIdx).
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, Param as P, normalize_tuple


# ---------------------------------------------------------------------------
# box utilities
# ---------------------------------------------------------------------------
def _corner_iou(a, b):
    """IoU matrix between corner-format boxes a:(N,4) and b:(M,4)."""
    ax1, ay1, ax2, ay2 = a[:, 0:1], a[:, 1:2], a[:, 2:3], a[:, 3:4]
    bx1, by1, bx2, by2 = b[None, :, 0], b[None, :, 1], b[None, :, 2], b[None, :, 3]
    iw = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0.0)
    ih = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0.0)
    inter = iw * ih
    area_a = jnp.maximum(ax2 - ax1, 0.0) * jnp.maximum(ay2 - ay1, 0.0)
    area_b = jnp.maximum(bx2 - bx1, 0.0) * jnp.maximum(by2 - by1, 0.0)
    union = area_a + area_b - inter
    return jnp.where(union > 0, inter / union, 0.0)


def _center_to_corner(boxes):
    x, y, w, h = boxes[..., 0], boxes[..., 1], boxes[..., 2], boxes[..., 3]
    hw, hh = w * 0.5, h * 0.5
    return jnp.stack([x - hw, y - hh, x + hw, y + hh], axis=-1)


def _corner_to_center(boxes):
    x1, y1, x2, y2 = boxes[..., 0], boxes[..., 1], boxes[..., 2], boxes[..., 3]
    return jnp.stack([(x1 + x2) * 0.5, (y1 + y2) * 0.5, x2 - x1, y2 - y1],
                     axis=-1)


# ---------------------------------------------------------------------------
# MultiBoxPrior
# ---------------------------------------------------------------------------
@register("_contrib_MultiBoxPrior", aliases=("MultiBoxPrior",))
def _multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                    steps=(-1.0, -1.0), offsets=(0.5, 0.5), **attrs):
    """SSD prior (anchor) boxes from a feature map.

    data: (B, C, H, W) -> (1, H*W*A, 4) corner boxes in [0,1] units, with
    A = len(sizes) + len(ratios) - 1: one box per size at ratio[0], plus
    one per extra ratio at sizes[0] (reference: multibox_prior.cc:43-71).
    """
    sizes = tuple(float(s) for s in np.atleast_1d(np.asarray(sizes, float)))
    ratios = tuple(float(r) for r in np.atleast_1d(np.asarray(ratios, float)))
    steps = tuple(float(s) for s in np.atleast_1d(np.asarray(steps, float)))
    offsets = tuple(float(o) for o in np.atleast_1d(np.asarray(offsets, float)))
    in_h, in_w = data.shape[-2], data.shape[-1]
    step_y = steps[0] if steps[0] > 0 else 1.0 / in_h
    step_x = steps[1] if len(steps) > 1 and steps[1] > 0 else 1.0 / in_w

    cy = (jnp.arange(in_h, dtype=jnp.float32) + offsets[0]) * step_y
    cx = (jnp.arange(in_w, dtype=jnp.float32) + offsets[1]) * step_x
    # per-cell half extents; aspect handling matches the reference exactly:
    # w scaled by in_h/in_w so ratio=1 gives a square box in pixel space
    hws, hhs = [], []
    for s in sizes:
        hws.append(s * in_h / in_w / 2.0)
        hhs.append(s / 2.0)
    for r in ratios[1:]:
        sr = float(np.sqrt(r))
        hws.append(sizes[0] * in_h / in_w * sr / 2.0)
        hhs.append(sizes[0] / sr / 2.0)
    hw = jnp.asarray(hws, dtype=jnp.float32)  # (A,)
    hh = jnp.asarray(hhs, dtype=jnp.float32)

    cyg, cxg = jnp.meshgrid(cy, cx, indexing="ij")        # (H, W)
    cxg = cxg[:, :, None]
    cyg = cyg[:, :, None]
    boxes = jnp.stack([cxg - hw, cyg - hh, cxg + hw, cyg + hh], axis=-1)
    boxes = boxes.reshape(1, -1, 4)
    if clip:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    return lax.stop_gradient(boxes.astype(data.dtype))


# ---------------------------------------------------------------------------
# MultiBoxTarget
# ---------------------------------------------------------------------------
def _multibox_target_one(anchors, labels, cls_pred, overlap_threshold,
                         ignore_label, negative_mining_ratio,
                         negative_mining_thresh, minimum_negative_samples,
                         variances):
    """Single-sample target assignment (vmapped over batch).

    anchors (N,4) corner; labels (M, 5+) rows [cls, x1, y1, x2, y2], pad
    rows cls=-1; cls_pred (num_classes, N) raw scores.
    Returns loc_target (N*4), loc_mask (N*4), cls_target (N).
    """
    N = anchors.shape[0]
    M = labels.shape[0]
    valid_gt = labels[:, 0] >= 0                         # (M,)
    iou = _corner_iou(anchors, labels[:, 1:5])           # (N, M)
    iou = jnp.where(valid_gt[None, :], iou, -1.0)

    # Phase 1 — greedy bipartite: repeatedly take the globally best
    # (anchor, gt) pair so every ground truth owns at least one anchor
    # (reference: multibox_target.cc:112-148 `while` loop).
    def bip_body(_, state):
        a_matched, g_matched, match_gt, match_iou = state
        masked = jnp.where(a_matched[:, None] | g_matched[None, :], -1.0, iou)
        flat = jnp.argmax(masked)
        bi, bj = flat // M, flat % M
        val = masked[bi, bj]
        take = val > 1e-6
        a_matched = a_matched.at[bi].set(jnp.where(take, True, a_matched[bi]))
        g_matched = g_matched.at[bj].set(jnp.where(take, True, g_matched[bj]))
        match_gt = match_gt.at[bi].set(jnp.where(take, bj, match_gt[bi]))
        match_iou = match_iou.at[bi].set(jnp.where(take, val, match_iou[bi]))
        return a_matched, g_matched, match_gt, match_iou

    a_matched = jnp.zeros((N,), bool)
    g_matched = jnp.zeros((M,), bool)
    match_gt = jnp.full((N,), -1, jnp.int32)
    match_iou = jnp.full((N,), -1.0, jnp.float32)
    a_matched, g_matched, match_gt, match_iou = lax.fori_loop(
        0, M, bip_body, (a_matched, g_matched, match_gt, match_iou))

    # Phase 2 — per-anchor best-IoU threshold matching for the rest
    best_gt = jnp.argmax(iou, axis=1).astype(jnp.int32)  # (N,)
    best_iou = jnp.max(iou, axis=1)
    thresh_pos = (~a_matched) & (best_iou > overlap_threshold) \
        & (overlap_threshold > 0)
    match_gt = jnp.where(thresh_pos, best_gt, match_gt)
    match_iou = jnp.where(a_matched, match_iou, best_iou)
    positive = a_matched | thresh_pos

    # Negatives: all unmatched, or hardest-first mining ranked by lowest
    # background softmax probability (reference: multibox_target.cc:180-240)
    if negative_mining_ratio > 0:
        logits = cls_pred.T                              # (N, num_classes)
        prob_bg = jax.nn.softmax(logits, axis=-1)[:, 0]
        candidate = (~positive) & (match_iou < negative_mining_thresh)
        num_pos = jnp.sum(positive)
        num_neg = jnp.minimum(
            jnp.maximum((num_pos * negative_mining_ratio).astype(jnp.int32),
                        int(minimum_negative_samples)),
            N - num_pos)
        score = jnp.where(candidate, -prob_bg, -jnp.inf)
        order = jnp.argsort(-score)                      # hardest first
        rank = jnp.zeros((N,), jnp.int32).at[order].set(jnp.arange(N))
        negative = candidate & (rank < num_neg)
    else:
        negative = ~positive

    cls_ids = jnp.where(valid_gt, labels[:, 0], 0.0)
    cls_target = jnp.where(
        positive, jnp.take(cls_ids, match_gt, mode="clip") + 1.0,
        jnp.where(negative, 0.0, float(ignore_label)))

    # loc targets: encode matched gt against anchor with variances
    a_ctr = _corner_to_center(anchors)                   # (N,4) x,y,w,h
    g_corner = jnp.take(labels[:, 1:5], match_gt, axis=0, mode="clip")
    g_ctr = _corner_to_center(g_corner)
    vx, vy, vw, vh = [float(v) for v in variances]
    aw = jnp.maximum(a_ctr[:, 2], 1e-12)
    ah = jnp.maximum(a_ctr[:, 3], 1e-12)
    tx = (g_ctr[:, 0] - a_ctr[:, 0]) / aw / vx
    ty = (g_ctr[:, 1] - a_ctr[:, 1]) / ah / vy
    tw = jnp.log(jnp.maximum(g_ctr[:, 2] / aw, 1e-12)) / vw
    th = jnp.log(jnp.maximum(g_ctr[:, 3] / ah, 1e-12)) / vh
    loc = jnp.stack([tx, ty, tw, th], axis=-1)
    loc = jnp.where(positive[:, None], loc, 0.0)
    mask = jnp.where(positive[:, None], 1.0, 0.0) * jnp.ones((N, 4))
    return loc.reshape(-1), mask.reshape(-1), cls_target


@register("_contrib_MultiBoxTarget", aliases=("MultiBoxTarget",),
          num_outputs=3)
def _multibox_target(anchor, label, cls_pred, overlap_threshold=0.5,
                     ignore_label=-1.0, negative_mining_ratio=-1.0,
                     negative_mining_thresh=0.5, minimum_negative_samples=0,
                     variances=(0.1, 0.1, 0.2, 0.2), **attrs):
    """SSD training-target assignment.

    anchor (1,N,4), label (B,M,5+), cls_pred (B,num_classes,N) ->
    (loc_target (B,N*4), loc_mask (B,N*4), cls_target (B,N)).
    Reference: multibox_target.cc:72-280.
    """
    variances = tuple(float(v) for v in
                      np.atleast_1d(np.asarray(variances, float)))
    anchors = anchor.reshape(-1, 4)
    fn = lambda lab, cp: _multibox_target_one(
        anchors, lab, cp, float(overlap_threshold), float(ignore_label),
        float(negative_mining_ratio), float(negative_mining_thresh),
        int(minimum_negative_samples), variances)
    loc, mask, cls = jax.vmap(fn)(label, cls_pred)
    return (lax.stop_gradient(loc), lax.stop_gradient(mask),
            lax.stop_gradient(cls))


# ---------------------------------------------------------------------------
# NMS core (shared by MultiBoxDetection / box_nms / Proposal)
# ---------------------------------------------------------------------------
def _greedy_nms_keep(boxes, scores, valid, iou_thresh, same_class_ok=None):
    """Greedy NMS on score-sorted candidates.  Returns keep mask aligned
    with the INPUT order.  boxes (N,4) corner, scores (N,), valid (N,)
    bool.  same_class_ok: (N,N) bool — pairs allowed to suppress each
    other (None = all)."""
    N = boxes.shape[0]
    order = jnp.argsort(-jnp.where(valid, scores, -jnp.inf))
    b = boxes[order]
    v = valid[order]
    iou = _corner_iou(b, b)
    can = iou > iou_thresh
    if same_class_ok is not None:
        can = can & same_class_ok[order][:, order]
    idx = jnp.arange(N)
    later = idx[None, :] > idx[:, None]   # j strictly after i in sort order

    def body(i, keep):
        sup = can[i] & later[i] & keep[i] & v[i]
        return keep & ~sup

    keep_sorted = lax.fori_loop(0, N, body, v)
    keep = jnp.zeros((N,), bool).at[order].set(keep_sorted)
    return keep


@register("_contrib_box_iou", params=[
    P("format", ("corner", "center"), default="corner")])
def _box_iou(lhs, rhs, format="corner", **attrs):
    """Pairwise IoU over the last axis of 4 (reference:
    bounding_box-inl.h box_iou).  Output shape lhs.shape[:-1] +
    rhs.shape[:-1]."""
    if format == "center":
        lhs, rhs = _center_to_corner(lhs), _center_to_corner(rhs)
    L = lhs.reshape(-1, 4)
    R = rhs.reshape(-1, 4)
    return _corner_iou(L, R).reshape(lhs.shape[:-1] + rhs.shape[:-1])


@register("_contrib_box_nms", params=[
    P("overlap_thresh", float, default=0.5, low=0.0, high=1.0),
    P("valid_thresh", float, default=0.0),
    P("topk", int, default=-1),
    P("coord_start", int, default=2),
    P("score_index", int, default=1),
    P("id_index", int, default=-1),
    P("force_suppress", bool, default=False),
    P("in_format", ("corner", "center"), default="corner"),
    P("out_format", ("corner", "center"), default="corner")],
          aliases=("_contrib_box_non_maximum_suppression",))
def _box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
             coord_start=2, score_index=1, id_index=-1,
             force_suppress=False, in_format="corner",
             out_format="corner", **attrs):
    """Generic NMS over (..., N, K) rows; suppressed rows become -1
    (reference: bounding_box-inl.h BoxNMSForward)."""
    shape = data.shape
    x = data.reshape(-1, shape[-2], shape[-1])
    cs, si = int(coord_start), int(score_index)

    def one(rows):
        boxes = lax.dynamic_slice_in_dim(rows, cs, 4, axis=1)
        if in_format == "center":
            boxes = _center_to_corner(boxes)
        scores = rows[:, si]
        valid = scores > valid_thresh
        if topk is not None and int(topk) > 0:
            order = jnp.argsort(-jnp.where(valid, scores, -jnp.inf))
            rank = jnp.zeros(rows.shape[0], jnp.int32).at[order].set(
                jnp.arange(rows.shape[0]))
            valid = valid & (rank < int(topk))
        same_ok = None
        if not force_suppress and int(id_index) >= 0:
            ids = rows[:, int(id_index)]
            same_ok = ids[:, None] == ids[None, :]
        keep = _greedy_nms_keep(boxes, scores, valid, float(overlap_thresh),
                                same_ok)
        out = jnp.where(keep[:, None], rows, -1.0)
        if out_format != in_format:
            ob = lax.dynamic_slice_in_dim(out, cs, 4, axis=1)
            ob = (_corner_to_center(ob) if out_format == "center"
                  else _center_to_corner(ob))
            ob = jnp.where(keep[:, None], ob, -1.0)
            out = lax.dynamic_update_slice_in_dim(out, ob, cs, axis=1)
        # compact kept rows to the front in score order, like the
        # reference which sorts survivors first
        order = jnp.argsort(-jnp.where(keep, scores, -jnp.inf))
        return out[order]

    return jax.vmap(one)(x).reshape(shape)


@register("_contrib_bipartite_matching", num_outputs=2)
def _bipartite_matching(data, threshold=0.5, is_ascend=False, topk=-1,
                        **attrs):
    """Greedy bipartite matching on a score matrix (..., N, M) ->
    (row_match (...,N), col_match (...,M)) with -1 for unmatched
    (reference: bounding_box-inl.h BipartiteMatchingForward)."""
    shape = data.shape
    N, M = shape[-2], shape[-1]
    x = data.reshape(-1, N, M)
    sign = 1.0 if is_ascend else -1.0
    sentinel = jnp.inf

    def one(mat):
        score = sign * mat   # minimize
        K = min(N, M) if topk is None or int(topk) <= 0 \
            else min(int(topk), min(N, M))

        def body(_, st):
            rm, cm, sc = st
            flat = jnp.argmin(sc)
            i, j = flat // M, flat % M
            val = sc[i, j] * sign
            # reference contract (bounding_box-inl.h:589): accept iff
            # score > thresh (descend) / score < thresh (ascend); the
            # +inf exhaustion sentinel fails both tests
            ok = (val > threshold) if not is_ascend else (val < threshold)
            rm = rm.at[i].set(jnp.where(ok, j, rm[i]))
            cm = cm.at[j].set(jnp.where(ok, i, cm[j]))
            sc = jnp.where(ok, sc.at[i, :].set(sentinel).at[:, j].set(sentinel),
                           jnp.full_like(sc, sentinel))
            return rm, cm, sc

        rm = jnp.full((N,), -1.0)
        cm = jnp.full((M,), -1.0)
        rm, cm, _ = lax.fori_loop(0, K, body, (rm, cm, score))
        return rm, cm

    rm, cm = jax.vmap(one)(x)
    return (rm.reshape(shape[:-1]), cm.reshape(shape[:-2] + (M,)))


# ---------------------------------------------------------------------------
# MultiBoxDetection
# ---------------------------------------------------------------------------
@register("_contrib_MultiBoxDetection", aliases=("MultiBoxDetection",))
def _multibox_detection(cls_prob, loc_pred, anchor, clip=True, threshold=0.01,
                        background_id=0, nms_threshold=0.5,
                        force_suppress=False,
                        variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1,
                        **attrs):
    """Decode SSD heads into detections.

    cls_prob (B, num_classes, N) softmax probs, loc_pred (B, N*4),
    anchor (1, N, 4) -> (B, N, 6) rows [cls_id, score, x1, y1, x2, y2],
    suppressed/background rows -1 (reference: multibox_detection.cc).
    """
    variances = tuple(float(v) for v in
                      np.atleast_1d(np.asarray(variances, float)))
    B, C, N = cls_prob.shape
    anchors = anchor.reshape(N, 4)
    a_ctr = _corner_to_center(anchors)
    bg = int(background_id)

    def one(prob, loc):
        loc = loc.reshape(N, 4)
        # class with best prob excluding background
        cls_id = jnp.argmax(jnp.where(
            (jnp.arange(C) == bg)[:, None], -jnp.inf, prob), axis=0)
        score = jnp.max(jnp.where(
            (jnp.arange(C) == bg)[:, None], -jnp.inf, prob), axis=0)
        # decode with variances
        vx, vy, vw, vh = variances
        cx = loc[:, 0] * vx * a_ctr[:, 2] + a_ctr[:, 0]
        cy = loc[:, 1] * vy * a_ctr[:, 3] + a_ctr[:, 1]
        w = jnp.exp(loc[:, 2] * vw) * a_ctr[:, 2]
        h = jnp.exp(loc[:, 3] * vh) * a_ctr[:, 3]
        boxes = _center_to_corner(jnp.stack([cx, cy, w, h], axis=-1))
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        valid = score > threshold
        if int(nms_topk) > 0:
            order = jnp.argsort(-jnp.where(valid, score, -jnp.inf))
            rank = jnp.zeros((N,), jnp.int32).at[order].set(jnp.arange(N))
            valid = valid & (rank < int(nms_topk))
        same_ok = None if force_suppress else \
            (cls_id[:, None] == cls_id[None, :])
        keep = _greedy_nms_keep(boxes, score, valid, float(nms_threshold),
                                same_ok)
        # background removed from the id space (reference:
        # multibox_detection.cc `p_out[...] = id - 1` with bg fixed at 0);
        # generalized: only classes above background_id shift down
        out_id = jnp.where(cls_id > bg, cls_id - 1, cls_id)
        rows = jnp.concatenate(
            [out_id[:, None].astype(prob.dtype), score[:, None], boxes],
            axis=-1)
        rows = jnp.where(keep[:, None], rows, -1.0)
        order = jnp.argsort(-jnp.where(keep, score, -jnp.inf))
        return rows[order]

    return lax.stop_gradient(jax.vmap(one)(cls_prob, loc_pred))


# ---------------------------------------------------------------------------
# ROI pooling / align
# ---------------------------------------------------------------------------
@register("ROIPooling", aliases=("_contrib_ROIPooling",))
def _roi_pooling(data, rois, pooled_size=(1, 1), spatial_scale=1.0, **attrs):
    """Max-pool regions of interest (reference: roi_pooling-inl.h).

    data (B,C,H,W); rois (R,5) rows [batch_idx, x1, y1, x2, y2] in input
    image coords -> (R, C, PH, PW).  Masked-max formulation: each output
    bin is the max over feature-map cells whose integer coordinates fall
    in the bin — identical to the reference's loop bounds
    (floor/ceil + clamp), and jax.vjp routes gradients to the argmax
    element (replacing the explicit max_idx aux output).
    """
    PH, PW = normalize_tuple(pooled_size, 2)
    B, C, H, W = data.shape
    scale = float(spatial_scale)
    ys = jnp.arange(H, dtype=jnp.float32)
    xs = jnp.arange(W, dtype=jnp.float32)

    def one(roi):
        bidx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * scale)
        y1 = jnp.round(roi[2] * scale)
        x2 = jnp.round(roi[3] * scale)
        y2 = jnp.round(roi[4] * scale)
        rh = jnp.maximum(y2 - y1 + 1.0, 1.0)
        rw = jnp.maximum(x2 - x1 + 1.0, 1.0)
        bin_h = rh / PH
        bin_w = rw / PW
        fmap = data[bidx]                          # (C, H, W)
        ph = jnp.arange(PH, dtype=jnp.float32)
        pw = jnp.arange(PW, dtype=jnp.float32)
        hstart = jnp.clip(jnp.floor(ph * bin_h) + y1, 0, H)      # (PH,)
        hend = jnp.clip(jnp.ceil((ph + 1) * bin_h) + y1, 0, H)
        wstart = jnp.clip(jnp.floor(pw * bin_w) + x1, 0, W)
        wend = jnp.clip(jnp.ceil((pw + 1) * bin_w) + x1, 0, W)
        ymask = (ys[None, :] >= hstart[:, None]) & (ys[None, :] < hend[:, None])
        xmask = (xs[None, :] >= wstart[:, None]) & (xs[None, :] < wend[:, None])
        m = ymask[:, None, :, None] & xmask[None, :, None, :]  # (PH,PW,H,W)
        empty = ~jnp.any(m, axis=(2, 3))
        vals = jnp.where(m[None], fmap[:, None, None, :, :], -jnp.inf)
        out = jnp.max(vals, axis=(3, 4))           # (C, PH, PW)
        return jnp.where(empty[None], 0.0, out)

    return jax.vmap(one)(rois)


@register("_contrib_ROIAlign", params=[
    P("pooled_size", tuple, required=True, low=1),
    P("spatial_scale", float, required=True, low=0.0),
    P("sample_ratio", int, default=2)])
def _roi_align(data, rois, pooled_size=(1, 1), spatial_scale=1.0,
               sample_ratio=2, **attrs):
    """ROIAlign with bilinear sampling (successor to ROIPooling; matches
    the contract detectors expect: no coordinate rounding, average of
    sample_ratio^2 bilinear samples per bin).

    sample_ratio=-1 means adaptive ceil(bin_size) sampling in the
    reference; per-ROI sample counts are data-dependent shapes XLA
    cannot compile, so it maps to a fixed 2x2 grid here (the value
    detectors typically configure explicitly)."""
    PH, PW = normalize_tuple(pooled_size, 2)
    S = 2 if int(sample_ratio) <= 0 else int(sample_ratio)
    B, C, H, W = data.shape
    scale = float(spatial_scale)

    def bilinear(fmap, y, x):
        y = jnp.clip(y, 0.0, H - 1.0)
        x = jnp.clip(x, 0.0, W - 1.0)
        y0 = jnp.floor(y).astype(jnp.int32)
        x0 = jnp.floor(x).astype(jnp.int32)
        y1 = jnp.minimum(y0 + 1, H - 1)
        x1 = jnp.minimum(x0 + 1, W - 1)
        ly, lx = y - y0, x - x0
        v00 = fmap[:, y0, x0]
        v01 = fmap[:, y0, x1]
        v10 = fmap[:, y1, x0]
        v11 = fmap[:, y1, x1]
        return (v00 * (1 - ly) * (1 - lx) + v01 * (1 - ly) * lx +
                v10 * ly * (1 - lx) + v11 * ly * lx)

    def one(roi):
        bidx = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = roi[1] * scale, roi[2] * scale, roi[3] * scale, \
            roi[4] * scale
        rh = jnp.maximum(y2 - y1, 1.0)
        rw = jnp.maximum(x2 - x1, 1.0)
        bh, bw = rh / PH, rw / PW
        fmap = data[bidx]
        ph = jnp.arange(PH, dtype=jnp.float32)[:, None, None, None]
        pw = jnp.arange(PW, dtype=jnp.float32)[None, :, None, None]
        sy = jnp.arange(S, dtype=jnp.float32)[None, None, :, None]
        sx = jnp.arange(S, dtype=jnp.float32)[None, None, None, :]
        shape4 = (PH, PW, S, S)
        yy = jnp.broadcast_to(y1 + (ph + (sy + 0.5) / S) * bh, shape4)
        xx = jnp.broadcast_to(x1 + (pw + (sx + 0.5) / S) * bw, shape4)
        samp = jax.vmap(lambda y, x: bilinear(fmap, y, x))(
            yy.reshape(-1), xx.reshape(-1))       # (PH*PW*S*S, C)
        samp = samp.reshape(PH, PW, S, S, C)
        return jnp.mean(samp, axis=(2, 3)).transpose(2, 0, 1)

    return jax.vmap(one)(rois)


# ---------------------------------------------------------------------------
# Region proposals (RPN)
# ---------------------------------------------------------------------------
def _rpn_anchors(H, W, feature_stride, scales, ratios):
    """Shifted base anchors, pixel coords, (H*W*A, 4)."""
    base = float(feature_stride)
    ws, hs = [], []
    for r in ratios:
        size = base * base / float(r)
        w0 = np.round(np.sqrt(size))
        h0 = np.round(w0 * float(r))
        for s in scales:
            ws.append(w0 * float(s))
            hs.append(h0 * float(s))
    ws = jnp.asarray(ws, jnp.float32)
    hs = jnp.asarray(hs, jnp.float32)
    ctr = (base - 1.0) / 2.0
    base_boxes = jnp.stack([ctr - 0.5 * (ws - 1), ctr - 0.5 * (hs - 1),
                            ctr + 0.5 * (ws - 1), ctr + 0.5 * (hs - 1)],
                           axis=-1)                      # (A, 4)
    sy = jnp.arange(H, dtype=jnp.float32) * base
    sx = jnp.arange(W, dtype=jnp.float32) * base
    syg, sxg = jnp.meshgrid(sy, sx, indexing="ij")
    shifts = jnp.stack([sxg, syg, sxg, syg], axis=-1)    # (H, W, 4)
    return (shifts[:, :, None, :] + base_boxes[None, None]).reshape(-1, 4)


def _boolattr(v):
    """Parse a bool attr that may arrive as a string via the symbol path."""
    if isinstance(v, str):
        return v.lower() in ("1", "true")
    return bool(v)


@register("_contrib_Proposal", aliases=("Proposal", "_contrib_MultiProposal"),
          num_outputs=lambda attrs: 2 if _boolattr(attrs.get("output_score",
                                                             False)) else 1)
def _proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
              rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
              scales=(4, 8, 16, 32), ratios=(0.5, 1, 2),
              feature_stride=16, output_score=False, iou_loss=False,
              **attrs):
    """RPN proposal generation (reference: multi_proposal-inl.h).

    cls_prob (B, 2A, H, W), bbox_pred (B, 4A, H, W), im_info (B, 3)
    [height, width, scale] -> rois (B*post_n, 5) [batch_idx, x1,y1,x2,y2]
    (+ scores (B*post_n, 1) if output_score).
    """
    scales = tuple(np.atleast_1d(np.asarray(scales, float)))
    ratios = tuple(np.atleast_1d(np.asarray(ratios, float)))
    B, A2, H, W = cls_prob.shape
    A = A2 // 2
    anchors = _rpn_anchors(H, W, feature_stride, scales, ratios)  # (K,4)
    K = anchors.shape[0]
    a_ctr = _corner_to_center(anchors)
    post_n = int(rpn_post_nms_top_n)
    pre_n = min(int(rpn_pre_nms_top_n), K)

    def one(prob, deltas, info):
        # fg scores: second half of the 2A channel dim
        score = prob[A:].transpose(1, 2, 0).reshape(-1)          # (K,)
        d = deltas.reshape(A, 4, H, W).transpose(2, 3, 0, 1).reshape(-1, 4)
        cx = d[:, 0] * a_ctr[:, 2] + a_ctr[:, 0]
        cy = d[:, 1] * a_ctr[:, 3] + a_ctr[:, 1]
        w = jnp.exp(jnp.clip(d[:, 2], -10, 10)) * a_ctr[:, 2]
        h = jnp.exp(jnp.clip(d[:, 3], -10, 10)) * a_ctr[:, 3]
        boxes = _center_to_corner(jnp.stack([cx, cy, w, h], axis=-1))
        im_h, im_w = info[0], info[1]
        boxes = jnp.stack([jnp.clip(boxes[:, 0], 0, im_w - 1),
                           jnp.clip(boxes[:, 1], 0, im_h - 1),
                           jnp.clip(boxes[:, 2], 0, im_w - 1),
                           jnp.clip(boxes[:, 3], 0, im_h - 1)], axis=-1)
        min_size = float(rpn_min_size) * info[2]
        keep_size = ((boxes[:, 2] - boxes[:, 0] + 1) >= min_size) & \
                    ((boxes[:, 3] - boxes[:, 1] + 1) >= min_size)
        score = jnp.where(keep_size, score, -jnp.inf)
        order = jnp.argsort(-score)
        rank = jnp.zeros((K,), jnp.int32).at[order].set(jnp.arange(K))
        valid = keep_size & (rank < pre_n)
        keep = _greedy_nms_keep(boxes, score, valid, float(threshold))
        order = jnp.argsort(-jnp.where(keep, score, -jnp.inf))
        top = order[:post_n]
        # pad slots past the kept count with the best box (reference
        # pads by re-sampling kept proposals)
        n_keep = jnp.sum(keep)
        top = jnp.where(jnp.arange(post_n) < n_keep, top, order[0])
        return boxes[top], score[top]

    boxes, scores = jax.vmap(one)(cls_prob, bbox_pred, im_info)
    bidx = jnp.repeat(jnp.arange(B, dtype=boxes.dtype), post_n)
    rois = jnp.concatenate([bidx[:, None], boxes.reshape(-1, 4)], axis=-1)
    rois = lax.stop_gradient(rois)
    if _boolattr(output_score):
        return rois, lax.stop_gradient(scores.reshape(-1, 1))
    return rois


# ---------------------------------------------------------------------------
# Deformable convolution / PSROI pooling
# ---------------------------------------------------------------------------
@register("_contrib_DeformableConvolution")
def _deformable_conv(data, offset, weight, bias=None, kernel=(3, 3),
                     stride=(1, 1), dilate=(1, 1), pad=(0, 0),
                     num_filter=1, num_group=1, num_deformable_group=1,
                     no_bias=False, **attrs):
    """Deformable convolution v1 (reference:
    deformable_convolution-inl.h): sample the input with learned
    per-position offsets (bilinear), then contract with the kernel —
    an im2col-with-offsets formulated as gather + one MXU matmul.

    data (B,C,H,W); offset (B, 2*DG*KH*KW, OH, OW); weight
    (num_filter, C/groups, KH, KW).
    """
    KH, KW = normalize_tuple(kernel, 2)
    SH, SW = normalize_tuple(stride, 2)
    DH, DW = normalize_tuple(dilate, 2)
    PH_, PW_ = normalize_tuple(pad, 2)
    B, C, H, W = data.shape
    OH = (H + 2 * PH_ - DH * (KH - 1) - 1) // SH + 1
    OW = (W + 2 * PW_ - DW * (KW - 1) - 1) // SW + 1
    DG = int(num_deformable_group)
    G = int(num_group)
    Cg = C // DG

    xpad = jnp.pad(data, ((0, 0), (0, 0), (PH_, PH_), (PW_, PW_)))
    Hp, Wp = H + 2 * PH_, W + 2 * PW_

    oy = jnp.arange(OH, dtype=jnp.float32)[:, None] * SH      # (OH,1)
    ox = jnp.arange(OW, dtype=jnp.float32)[None, :] * SW      # (1,OW)
    ky = jnp.arange(KH, dtype=jnp.float32)[:, None] * DH
    kx = jnp.arange(KW, dtype=jnp.float32)[None, :] * DW

    def bilinear_chan(fmap, y, x):
        """fmap (Cg,Hp,Wp); y,x (...,) -> (..., Cg)"""
        y0 = jnp.floor(y)
        x0 = jnp.floor(x)
        ly, lx = y - y0, x - x0
        y0i = jnp.clip(y0.astype(jnp.int32), 0, Hp - 1)
        x0i = jnp.clip(x0.astype(jnp.int32), 0, Wp - 1)
        y1i = jnp.clip(y0i + 1, 0, Hp - 1)
        x1i = jnp.clip(x0i + 1, 0, Wp - 1)
        inb = (y > -1.0) & (y < Hp) & (x > -1.0) & (x < Wp)
        g = lambda yi, xi: fmap[:, yi, xi]                    # (Cg, ...)
        v = (g(y0i, x0i) * (1 - ly) * (1 - lx) + g(y0i, x1i) * (1 - ly) * lx
             + g(y1i, x0i) * ly * (1 - lx) + g(y1i, x1i) * ly * lx)
        return jnp.where(inb, v, 0.0)

    def one(x_b, off_b):
        off = off_b.reshape(DG, KH * KW, 2, OH, OW)
        parts = []
        for dg in range(DG):
            fmap = x_b[dg * Cg:(dg + 1) * Cg]
            ks = []
            for k in range(KH * KW):
                khi, kwi = k // KW, k % KW
                yy = oy + ky[khi, 0] + off[dg, k, 0]          # (OH, OW)
                xx = ox + kx[0, kwi] + off[dg, k, 1]
                ks.append(bilinear_chan(fmap, yy, xx))        # (Cg, OH, OW)
            parts.append(jnp.stack(ks, axis=1))               # (Cg,KHKW,OH,OW)
        # channel-major x kernel-position, matching weight.reshape(F, -1)
        col = jnp.concatenate(parts, axis=0).reshape(C * KH * KW, OH * OW)
        wmat = weight.reshape(int(num_filter), -1)            # (F, C/G*KH*KW)
        if G == 1:
            out = wmat @ col
        else:
            Fg = int(num_filter) // G
            colg = col.reshape(G, (C // G) * KH * KW, OH * OW)
            wg = wmat.reshape(G, Fg, -1)
            out = jnp.einsum("gfk,gkn->gfn", wg, colg).reshape(
                int(num_filter), OH * OW)
        out = out.reshape(int(num_filter), OH, OW)
        if bias is not None and not no_bias:
            out = out + bias[:, None, None]
        return out

    return jax.vmap(one)(xpad, offset)


@register("_contrib_DeformablePSROIPooling")
def _deformable_psroi_pooling(data, rois, trans=None, spatial_scale=1.0,
                              output_dim=1, group_size=1, pooled_size=7,
                              part_size=0, sample_per_part=4,
                              trans_std=0.1, no_trans=False, **attrs):
    """Position-sensitive ROI pooling with learned part offsets
    (reference: deformable_psroi_pooling-inl.h).  data channel layout:
    (output_dim * group_size^2, H, W)."""
    P = int(pooled_size)
    GS = int(group_size)
    OD = int(output_dim)
    S = max(int(sample_per_part), 1)
    PS = int(part_size) or P
    B, C, H, W = data.shape
    scale = float(spatial_scale)

    # static bin -> part / group-channel index maps (vectorized over the
    # whole (OD, P, P, S, S) sample grid; one gather per corner instead
    # of an unrolled P*P*OD python loop, which would blow up trace size)
    part_h = np.minimum(np.arange(P) * PS // P, PS - 1)
    part_w = np.minimum(np.arange(P) * PS // P, PS - 1)
    grp_h = np.minimum(np.arange(P) * GS // P, GS - 1)
    grp_w = np.minimum(np.arange(P) * GS // P, GS - 1)
    chan = ((np.arange(OD)[:, None, None] * GS + grp_h[None, :, None]) * GS
            + grp_w[None, None, :])                       # (OD, P, P)
    chan_j = jnp.asarray(chan)
    part_hj, part_wj = jnp.asarray(part_h), jnp.asarray(part_w)

    def one(roi, tr):
        bidx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1]) * scale - 0.5
        y1 = jnp.round(roi[2]) * scale - 0.5
        x2 = (jnp.round(roi[3]) + 1.0) * scale - 0.5
        y2 = (jnp.round(roi[4]) + 1.0) * scale - 0.5
        rw = jnp.maximum(x2 - x1, 0.1)
        rh = jnp.maximum(y2 - y1, 0.1)
        bw, bh = rw / P, rh / P
        fmap = data[bidx]
        dy = tr[0][part_hj[:, None], part_wj[None, :]] * float(trans_std) * rh
        dx = tr[1][part_hj[:, None], part_wj[None, :]] * float(trans_std) * rw
        ph = jnp.arange(P, dtype=jnp.float32)
        sy = (jnp.arange(S, dtype=jnp.float32) + 0.5) * bh / S
        sx = (jnp.arange(S, dtype=jnp.float32) + 0.5) * bw / S
        yy = (y1 + ph[:, None, None, None] * bh + dy[:, :, None, None]
              + sy[None, None, :, None])                  # (P, P, S, S)
        xx = (x1 + ph[None, :, None, None] * bw + dx[:, :, None, None]
              + sx[None, None, None, :])
        y = jnp.clip(yy, 0.0, H - 1.0)
        x = jnp.clip(xx, 0.0, W - 1.0)
        y0 = jnp.floor(y).astype(jnp.int32)
        x0 = jnp.floor(x).astype(jnp.int32)
        y1i = jnp.minimum(y0 + 1, H - 1)
        x1i = jnp.minimum(x0 + 1, W - 1)
        ly, lx = y - y0, x - x0
        c = chan_j[:, :, :, None, None]                   # (OD, P, P, 1, 1)
        v = (fmap[c, y0[None], x0[None]] * ((1 - ly) * (1 - lx))[None]
             + fmap[c, y0[None], x1i[None]] * ((1 - ly) * lx)[None]
             + fmap[c, y1i[None], x0[None]] * (ly * (1 - lx))[None]
             + fmap[c, y1i[None], x1i[None]] * (ly * lx)[None])
        return jnp.mean(v, axis=(3, 4))                   # (OD, P, P)

    if trans is None or _boolattr(no_trans):
        tr_arg = jnp.zeros((rois.shape[0], 2, PS, PS))
    else:
        tr_arg = trans.reshape(-1, 2, PS, PS)[:rois.shape[0]]
    return jax.vmap(one)(rois, tr_arg)


# ---------------------------------------------------------------------------
# FFT (reference: src/operator/contrib/fft-inl.h — interleaved re/im)
# ---------------------------------------------------------------------------
@register("_contrib_fft")
def _fft(data, compute_size=128, **attrs):
    """FFT along the last axis; real input (..., D) -> interleaved
    complex output (..., 2D).  compute_size (batching granularity in the
    reference CUDA plan) is irrelevant under XLA and ignored."""
    out = jnp.fft.fft(data.astype(jnp.float32), axis=-1)
    return jnp.stack([out.real, out.imag], axis=-1).reshape(
        data.shape[:-1] + (2 * data.shape[-1],)).astype(data.dtype)


@register("_contrib_ifft")
def _ifft(data, compute_size=128, **attrs):
    """Inverse FFT: interleaved complex (..., 2D) -> real (..., D).
    Matches the reference's unnormalized ifft (scaled by D in cuFFT,
    reference divides in the python tests)."""
    D = data.shape[-1] // 2
    x = data.reshape(data.shape[:-1] + (D, 2)).astype(jnp.float32)
    comp = x[..., 0] + 1j * x[..., 1]
    out = jnp.fft.ifft(comp, axis=-1).real * D
    return out.astype(data.dtype)


# ---------------------------------------------------------------------------
# count_sketch (reference: src/operator/contrib/count_sketch-inl.h)
# ---------------------------------------------------------------------------
@register("_contrib_count_sketch")
def _count_sketch(data, h, s, out_dim=0, **attrs):
    """Count sketch projection: out[:, h[i]] += s[i] * data[:, i]
    (compact bilinear pooling building block)."""
    out_dim = int(out_dim)
    hi = h.reshape(-1).astype(jnp.int32)
    si = s.reshape(-1).astype(data.dtype)
    contrib = data * si[None, :]
    out = jnp.zeros(data.shape[:-1] + (out_dim,), data.dtype)
    return out.at[..., hi].add(contrib)


# ---------------------------------------------------------------------------
# Pallas-fused inference epilogue
# ---------------------------------------------------------------------------
@register("_contrib_fused_bn_relu")
def _fused_bn_relu(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
                   act=True, **attrs):
    """Inference BatchNorm folded to scale/bias + ReLU as ONE Pallas pass
    (ops/pallas_kernels.py fused_scale_bias_relu; reference analogue: the
    BN+Activation fusion of nn/mkldnn).  data NCHW."""
    from .pallas_kernels import fused_scale_bias_relu
    scale = gamma * lax.rsqrt(moving_var + eps)
    bias = beta - moving_mean * scale
    B, C = data.shape[0], data.shape[1]
    flat = jnp.transpose(data, (0, 2, 3, 1)).reshape(-1, C)
    y = fused_scale_bias_relu(flat, scale, bias, relu=_boolattr(act))
    H, W = data.shape[2], data.shape[3]
    return jnp.transpose(y.reshape(B, H, W, C), (0, 3, 1, 2))


# ---------------------------------------------------------------------------
# Fused attention (long-context primitive; no reference analogue —
# MXNet 1.2 predates attention, SURVEY.md §5.7)
# ---------------------------------------------------------------------------
@register("_contrib_flash_attention")
def _flash_attention_op(q, k, v, causal=False, scale=None, window=None,
                        kept=False, block_diffusion=None, **attrs):
    """Softmax attention over (B, T, H, D) tensors; K/V may carry fewer
    heads (GQA), and V a head size of its own, (B, T, H, Dv) — the
    result is then (B, T, H, Dv) and the default scale still ``D **
    -0.5`` (latent attention: 192-wide keys over 128-wide values).
    Dispatches to the Pallas flash kernel on TPU (O(T)
    memory), the einsum path elsewhere (mxnet_tpu/parallel/attention.py
    local_attention).  ``window`` (with ``causal``): a query sees its
    last ``window`` keys, itself among them.  For sequence-sharded T use
    parallel.ring_attention / ulysses_attention over an 'sp' mesh axis
    (which raise on a window).  ``kept`` is not a user's to set: a
    layer whose ``jax.checkpoint`` keeps the flash call's results
    (``gluon.contrib.transformer._layer_keeps``) says so with it, and the
    kernels' op holds its row statistics compactly; no number moves.

    ``block_diffusion`` (a block length ``B``, in place of ``causal`` and
    ``window``) is the mask a block-diffusion language model trains under
    (Arriola et al., "Block Diffusion", arXiv:2503.09573, section 4; SDAR,
    arXiv:2510.06303).  The ``T = 2 L`` rows are a noised copy of a
    sequence followed by its clean copy; with ``half(r) = [r >= L]`` and
    ``blk(r) = (r mod L) // B``, query row r sees key row c iff

    - ``half r = half c`` and ``blk r = blk c`` (a block sees itself,
      both ways), or
    - ``half r = 0``, ``half c = 1`` and ``blk r > blk c`` (a noised block
      sees the CLEAN blocks before it), or
    - ``half r = half c = 1`` and ``blk r >= blk c`` (clean rows are
      block-causal);

    a clean row never sees a noised one, and ``L^2 + L B`` of the ``4
    L^2`` pairs are visible.  On the TPU the flash kernels neither fetch
    nor run a tile without a visible pair
    (``ops.pallas_kernels.flash_attention``); elsewhere the einsum form
    builds the mask from this definition
    (``parallel.attention.block_diffusion_mask``).  Across the shards of
    an 'sp' mesh axis it raises, as a window does."""
    from ..parallel.attention import local_attention, ring_attention
    from ..parallel.mesh import current_mesh
    if scale is not None:
        scale = float(scale)
    if window is not None:
        window = int(window)
    if block_diffusion is not None:
        block_diffusion = int(block_diffusion)
    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        # an active sp mesh makes the SAME model sequence-parallel:
        # the time axis shards over the ring, K/V blocks rotate on ICI
        return ring_attention(q, k, v, mesh=mesh, causal=_boolattr(causal),
                              scale=scale, window=window,
                              block_diffusion=block_diffusion)
    return local_attention(q, k, v, causal=_boolattr(causal), scale=scale,
                           window=window, kept=_boolattr(kept),
                           block_diffusion=block_diffusion)


# ---------------------------------------------------------------------------
# Today's dense block: rotary positions, gated feed-forward, and the
# projection fused with its cross-entropy (no reference analogue)
# ---------------------------------------------------------------------------
def yarn_inv_freq(dim, base, factor, original_max_position, beta_fast=32.0,
                  beta_slow=1.0):
    """YaRN's frequency table (Peng et al., arXiv:2309.00071, as
    ``transformers`` computes it), ``(dim/2,)`` float64: the rotary
    frequencies ``base**(-2i/dim)`` blended with their ``1/factor``
    interpolation by a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over ``original_max_position``
    positions — below the first the frequency stays, above the second
    it is divided by ``factor``."""
    def turns(beta):
        return dim * math.log(original_max_position / (beta * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    inv = float(base) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return inv / float(factor) * ramp + inv * (1.0 - ramp)


@register("_contrib_rotary_embedding")
def _rotary_embedding(data, positions=None, base=10000.0, inv_freq=None,
                      scale=1.0, interleaved=False, rotary_dim=None,
                      **attrs):
    """Rotary position embedding (Su et al., arXiv:2104.09864) over
    ``(B, T, H, D)`` with HALF-SPLIT pairing: element ``i < D/2`` turns
    with element ``i + D/2`` by ``t * base**(-2i/D)`` — or by ``t *
    inv_freq[i]`` where a layer brings a frequency table of its own
    (``D/2`` numbers: :func:`yarn_inv_freq`); cos and sin are multiplied
    by ``scale`` (YaRN's attention factor).  ``interleaved``: the pairing
    is ``(2i, 2i + 1)`` instead, each pair turned where it lies (the
    layout GPT-J and DeepSeek's latent attention store their rotary
    dimensions in).  Row ``t`` turns by its index, or by ``positions[t]``
    where the rows are not positions ``0 .. T - 1`` (``(T,)``, one for
    every row: block diffusion's rows are two copies of a sequence, both
    at positions ``0 .. L - 1``).  ``rotary_dim`` (even, at most ``D``):
    only a head's first ``rotary_dim`` dimensions turn, as a head of that
    size would (``D`` read as ``rotary_dim`` above); the others pass
    through unturned (a partial rotary factor).  The angles, sines and
    the rotation run in float32; the result is cast back to ``data``'s
    dtype."""
    if rotary_dim is not None and int(rotary_dim) != data.shape[-1]:
        r = int(rotary_dim)
        if r % 2 or not 0 < r < data.shape[-1]:
            raise ValueError("rotary_dim %d is not an even part of a head "
                             "of %d" % (r, data.shape[-1]))
        turned = _rotary_embedding(data[..., :r], positions, base, inv_freq,
                                   scale, interleaved)
        return jnp.concatenate([turned, data[..., r:]], -1)
    t, d = data.shape[1], data.shape[-1]
    half = d // 2
    if inv_freq is None:
        inv_freq = float(base) ** (-jnp.arange(half, dtype=jnp.float32)
                                   * (2.0 / d))
    else:
        inv_freq = jnp.asarray([float(f) for f in inv_freq], jnp.float32)
        if inv_freq.shape != (half,):
            raise ValueError("inv_freq holds %d frequencies, the heads "
                             "need %d" % (inv_freq.shape[0], half))
    if positions is None:
        pos = jnp.arange(t, dtype=jnp.float32)
    else:
        if positions.shape != (t,):
            raise ValueError("positions hold %s entries, the data has %d "
                             "rows" % (positions.shape, t))
        pos = positions.astype(jnp.float32)
    ang = pos[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    if float(scale) != 1.0:
        cos, sin = cos * float(scale), sin * float(scale)
    xf = data.astype(jnp.float32)
    if _boolattr(interleaved):
        pairs = xf.reshape(xf.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        -1).reshape(xf.shape)
    else:
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              -1)
    return out.astype(data.dtype)


@register("_contrib_gated_ffn")
def _gated_ffn(data, gate_weight, up_weight, down_weight, **attrs):
    """SwiGLU feed-forward (Shazeer, arXiv:2002.05202):
    ``down(silu(gate(x)) * up(x))`` over the last axis, weights stored
    ``(out, in)`` like FullyConnected's, no biases."""
    g = jnp.einsum("...u,fu->...f", data, gate_weight)
    u = jnp.einsum("...u,fu->...f", data, up_weight)
    return jnp.einsum("...f,uf->...u", jax.nn.silu(g) * u, down_weight)


@register("_contrib_causal_conv")
def _causal_conv(data, weight, **attrs):
    """Causal convolution over the time axis of ``(B, T, C)``, tap ``j``
    reading the row ``j`` steps back (zero before the first row), no
    bias.  ``weight (K, C)``: depthwise, ``out_t = sum_j weight[j] *
    x_{t-j}``, in float32.  ``weight (G, K, Ci, Co)``: grouped, the
    channels in ``G`` groups of ``Ci``, ``out_t[g] = sum_j x_{t-j}[g] @
    weight[g, j]`` (``(B, T, G Co)``), each tap's product in ``data``'s
    dtype, the taps summed in float32.  The result is cast back to
    ``data``'s dtype.
    Compressed convolutional attention mixes its latent queries and keys
    with one of each (``gluon.contrib.transformer``)."""
    def back(x, j):
        if j == 0:
            return x
        pad = [(0, 0), (j, 0)] + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(x, pad)[:, :x.shape[1]]

    if weight.ndim == 2:
        x = data.astype(jnp.float32)
        w = weight.astype(jnp.float32)
        out = sum(back(x, j) * w[j] for j in range(w.shape[0]))
    else:
        b, t, _c = data.shape
        groups, taps, ci, co = weight.shape
        x = data.reshape(b, t, groups, ci)
        w = weight.astype(data.dtype)
        # one product a tap; batched over the groups, whose float32
        # result XLA's CPU backend does not take from bfloat16 operands
        out = sum(jnp.einsum("btgc,gcd->btgd", back(x, j),
                             w[:, j]).astype(jnp.float32)
                  for j in range(taps)).reshape(b, t, groups * co)
    return out.astype(data.dtype)


def _ssm_eligible(channels):
    """The selective scan's kernel path: a TPU, and channels the kernels
    tile whole (``pallas_kernels.ssm_scan_ok``)."""
    from . import pallas_kernels as pk
    return pk._on_tpu() and pk.ssm_scan_ok(channels)


def _selective_scan_plain(u, delta, a, b, c, d):
    """The scan as a ``lax.scan`` over rows, float32: what runs off the
    TPU (``pallas_kernels.selective_scan`` has the equations)."""
    f32 = lambda x: x.astype(jnp.float32)
    a = f32(a)

    def row(s, xs):
        dl, uu, bb, cc = xs
        s = jnp.exp(dl[..., None] * a) * s + (dl * uu)[..., None] \
            * bb[:, None, :]
        return s, jnp.einsum("ben,bn->be", s, cc)

    first = lambda x: jnp.swapaxes(f32(x), 0, 1)
    _, y = lax.scan(row, jnp.zeros(u.shape[:1] + a.shape, jnp.float32),
                    (first(delta), first(u), first(b), first(c)))
    return (jnp.swapaxes(y, 0, 1) + f32(d) * f32(u)).astype(u.dtype)


@register("_contrib_selective_scan")
def _selective_scan(data, delta, a, b, c, d, **attrs):
    """Mamba's selective scan over ``data``, ``delta (B, T, E)``, ``a (E,
    N)`` (negative), ``b``, ``c (B, T, N)``, ``d (E,)``: ``s_t =
    exp(delta_t a) * s_{t-1} + (delta_t data_t) b_t`` from ``s_0 = 0``, ``y_t
    = s_t c_t + d data_t``, float32 inside, ``data``'s dtype out.  On the
    TPU the Pallas kernel pair (``pallas_kernels.selective_scan``: the
    state in VMEM, never in HBM), elsewhere a ``lax.scan`` over rows; the
    path taken is counted in ``mxnet_ssm_scan_calls_total{path}`` (trace
    time)."""
    from .. import telemetry
    kernel = _ssm_eligible(data.shape[-1])
    if telemetry.enabled():
        telemetry.counter(
            "mxnet_ssm_scan_calls_total",
            "selective-scan (F.contrib.selective_scan) instantiations by "
            "path: kernel (the Pallas pair) or plain (a lax.scan over "
            "rows); trace time").labels(
                path="kernel" if kernel else "plain").inc()
    if kernel:
        from .pallas_kernels import selective_scan
        return selective_scan(data, delta, a, b, c, d)
    return _selective_scan_plain(data, delta, a, b, c, d)


def _head_kernel_eligible(rows, units, dtype):
    """The fused head's kernel path: a TPU, and rows and units the
    kernels tile whole (``pallas_kernels.head_ce_ok``)."""
    from . import pallas_kernels as pk
    return pk._on_tpu() and pk.head_ce_ok(rows, units, dtype)


def _count_head(path):
    """Advance ``mxnet_linear_ce_calls_total{path}`` (trace time, as the
    Pallas wrappers count)."""
    from .. import telemetry
    if telemetry.enabled():
        telemetry.counter(
            "mxnet_linear_ce_calls_total",
            "fused head (F.contrib.linear_cross_entropy) instantiations by "
            "path: kernel (the Pallas pair, no float32 logits in HBM) or "
            "xla (the XLA rules); trace time").labels(path=path).inc()


@register("_contrib_linear_cross_entropy")
def _linear_cross_entropy(data, weight, label, position_weight=None,
                          **attrs):
    """Projection fused with its softmax cross-entropy: per position
    ``-log softmax(data @ weight.T)[label]`` in float32, WITHOUT keeping
    the ``(..., V)`` logits for the backward pass.  The product runs in
    ``weight``'s dtype with float32 accumulation.

    Unweighted, on a TPU and at rows and units the kernels tile whole,
    the Pallas pair ``pallas_kernels.head_cross_entropy`` takes it: the
    forward keeps ``lse`` one float32 a row, the backward makes each
    block of the logits again in VMEM, and no float32 ``(N, V)`` array
    reaches HBM in either direction.  Elsewhere the logits are
    recomputed in the backward pass (``jax.checkpoint``), so only one
    projection's float32 logits live at a time however many heads a loss
    reads.  ``mxnet_linear_ce_calls_total{path}`` counts the two.

    ``position_weight`` (the label's shape, float32): the terms come
    back times their position's weight, ``w_i CE_i``, and the weight
    reaches the gradients BEHIND the head's two backward products, in
    float32.  Multiplied onto the terms instead it rides the cotangent
    INTO those products, whose operands the TPU rounds to bfloat16, and
    the label's entry of a position's cotangent is ``-w_i (1 - p_i)``:
    ONE rounding of up to ``2**-8`` tilts that position's whole gradient
    (``F.contrib.scale_gradient`` tells the same of a term's weight).
    Where the weights are a diffusion objective's ``m / p`` a single
    position can carry most of a step's gradient, and its rounding then
    tilts every leaf's.  Here ``d = softmax - onehot`` goes into the
    products unweighted, ``dx_i = w_i (d_i W)`` and ``dW = d^T (w x)``:
    what is rounded has as many independent roundings as it has
    entries."""
    def logits_of(x, w):
        return jnp.einsum("...u,vu->...v", x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    def terms(logits, y):
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return lse - picked

    label = label.astype(jnp.int32)
    units = data.shape[-1]
    rows = math.prod(label.shape)
    if position_weight is None \
            and _head_kernel_eligible(rows, units, weight.dtype):
        from . import pallas_kernels as pk
        _count_head("kernel")
        return pk.head_cross_entropy(
            data.reshape(rows, units).astype(weight.dtype), weight,
            label.reshape(rows)).reshape(label.shape)
    _count_head("xla")
    if position_weight is None:
        return jax.checkpoint(lambda x, w, y: terms(logits_of(x, w), y))(
            data, weight, label)

    @jax.custom_vjp
    def weighted(x, w, y, pw):
        return pw * terms(logits_of(x, w), y)

    def backward(res, g):
        x, w, y, pw = res
        logits = logits_of(x, w)
        d = (jax.nn.softmax(logits, axis=-1)
             - jax.nn.one_hot(y, logits.shape[-1], dtype=jnp.float32)
             ).astype(w.dtype)
        by = (g * pw)[..., None]
        dx = jnp.einsum("...v,vu->...u", d, w,
                        preferred_element_type=jnp.float32) * by
        dw = jnp.einsum("...v,...u->vu", d,
                        (x.astype(jnp.float32) * by).astype(w.dtype),
                        preferred_element_type=jnp.float32)
        return (dx.astype(x.dtype), dw.astype(w.dtype), None,
                g * terms(logits, y))

    weighted.defvjp(lambda *args: (weighted(*args), args), backward)
    return weighted(data, weight, label,
                    position_weight.astype(jnp.float32))


DRAW_BITS = 24      # a draw is k / 2**24, k an integer in [0, 2**24)


@register("_contrib_block_diffusion_noise", num_outputs=2, needs_rng=True)
def _block_diffusion_noise(data, position_draws=None, block_draws=None,
                           block_length=4, mask_id=0, eps=1e-3,
                           __rng__=None, **attrs):
    """The forward (noising) process of a masked block-diffusion language
    model (Arriola et al., "Block Diffusion", arXiv:2503.09573, equation
    8 under the linear schedule; SDAR, arXiv:2510.06303, clips the rate):
    ``(noised, weight)`` of clean ids ``data (B, L)``, ``L`` a whole
    number of blocks of ``block_length`` positions.  With one draw ``t_b
    in [0, 1)`` a block and one ``u_i in [0, 1)`` a position::

        p_b      = (1 - eps) t_b + eps          the block's mask rate
        m_i      = [u_i < p_b]                  b = i // block_length
        noised_i = mask_id if m_i else data_i
        weight_i = m_i / p_b                    float32

    so that ``mean_i weight_i CE_i`` over the masked positions is the
    block-diffusion bound (its weight ``1 / t`` with the clipped rate in
    ``t``'s place; ``gluon.loss.BlockDiffusionCELoss``).

    The draws are INTEGERS ``k`` in ``[0, 2**24)`` standing for ``k /
    2**24`` — ``position_draws (B, L)`` and ``block_draws (B, L //
    block_length)`` — and everything up to the weight's one division is
    integer arithmetic, so the same draws give the same mask on any
    backend: ``m_i = [k_i < P_b]`` with ``P_b = k_b + (2**24 - k_b) //
    round(1 / eps)``, and ``p_b = P_b / 2**24`` (``eps`` is held as one
    over a whole number; the rate so lies within ``2**-24`` of the
    formula).  Given no draws the operator draws both from the step's
    key, which is what a training step wants; a caller that must
    reproduce a step hands them in with the batch."""
    ids = data.astype(jnp.int32)
    b, l = ids.shape
    length = int(block_length)
    if l % length:
        raise ValueError("%d positions are no whole number of blocks of %d"
                         % (l, length))
    one = 1 << DRAW_BITS
    if position_draws is None or block_draws is None:
        if position_draws is not None or block_draws is not None:
            raise ValueError("hand in both draws or neither")
        ku, kt = jax.random.split(__rng__)
        position_draws = jax.random.randint(ku, (b, l), 0, one, jnp.int32)
        block_draws = jax.random.randint(kt, (b, l // length), 0, one,
                                         jnp.int32)
    if position_draws.shape != (b, l) \
            or block_draws.shape != (b, l // length):
        raise ValueError("draws of shapes %s and %s do not fit %d x %d "
                         "positions in blocks of %d"
                         % (position_draws.shape, block_draws.shape, b, l,
                            length))
    kt = block_draws.astype(jnp.int32)
    rate = kt + (one - kt) // int(round(1.0 / float(eps)))
    rate = jnp.repeat(rate, length, axis=1)                 # (B, L)
    masked = position_draws.astype(jnp.int32) < rate
    weight = jnp.where(masked, jnp.float32(one) / rate.astype(jnp.float32),
                       jnp.float32(0.0))
    return jnp.where(masked, jnp.int32(int(mask_id)), ids), weight


@register("_contrib_scale_gradient")
def _scale_gradient(data, scale=1.0, **attrs):
    """``data`` as it is; its gradient times ``scale``.  A term's weight
    put here, on what the term reads, multiplies the gradients AFTER the
    products that make them.  Put on the term itself it rides the
    cotangent INTO those products, whose operands the TPU rounds to
    bfloat16 — and under a cross-entropy every label's entry of that
    cotangent is the same number, ``-weight / positions``, so one
    rounding (0.3 / 8191 goes up by 0.26 %) tilts a whole gradient."""
    scale = float(scale)

    @jax.custom_vjp
    def scaled(x):
        return x

    scaled.defvjp(lambda x: (x, None),
                  lambda _res, g: ((g * scale).astype(g.dtype),))
    return scaled(data)


@register("_contrib_exit_weighted_loss")
def _exit_weighted_loss(loss, gate, beta=0.0, **attrs):
    """Expected loss of a model with ``P`` exits under its learned exit
    distribution, less ``beta`` times that distribution's entropy (the
    first-stage objective of looped LMs, arXiv:2510.25741).  ``loss``
    and ``gate`` are ``(B, P, ...)``: per-exit losses and gate logits.
    ``lam = sigmoid(gate)``; exit ``t < P`` is taken with probability
    ``lam_t * prod_{j<t}(1 - lam_j)``, the last with what is left (its own
    gate is not read), so the distribution sums to 1.  Returns
    ``(B, ...)``.  Computed through log-probabilities in float32."""
    lf, gf = loss.astype(jnp.float32), gate.astype(jnp.float32)
    stay = jax.nn.log_sigmoid(-gf[:, :-1])        # log(1 - lam_j), j < P
    before = jnp.concatenate(
        [jnp.zeros_like(gf[:, :1]), jnp.cumsum(stay, axis=1)], axis=1)
    logp = before + jnp.concatenate(
        [jax.nn.log_sigmoid(gf[:, :-1]), jnp.zeros_like(gf[:, :1])], axis=1)
    p = jnp.exp(logp)
    entropy = -jnp.sum(p * logp, axis=1)
    return jnp.sum(p * lf, axis=1) - float(beta) * entropy
