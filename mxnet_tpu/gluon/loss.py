"""Gluon losses.

Reference: ``python/mxnet/gluon/loss.py`` — Loss base (:66), L2Loss,
L1Loss, SigmoidBinaryCrossEntropyLoss, SoftmaxCrossEntropyLoss, KLDivLoss,
CTCLoss, HuberLoss, HingeLoss, SquaredHingeLoss, LogisticLoss,
TripletLoss (:66-666).
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from .block import HybridBlock

__all__ = ["Loss", "L2Loss", "L1Loss", "SigmoidBinaryCrossEntropyLoss",
           "SigmoidBCELoss", "SoftmaxCrossEntropyLoss", "SoftmaxCELoss",
           "KLDivLoss", "CTCLoss", "HuberLoss", "HingeLoss",
           "SquaredHingeLoss", "LogisticLoss", "TripletLoss",
           "ExitWeightedCELoss", "LinearCELoss", "BlockDiffusionCELoss",
           "MultiTokenCELoss"]


def _apply_weighting(F, loss, weight=None, sample_weight=None):
    """Reference: loss.py:31."""
    if sample_weight is not None:
        loss = F.broadcast_mul(loss, sample_weight) \
            if hasattr(F, "broadcast_mul") else loss * sample_weight
    if weight is not None:
        assert isinstance(weight, (int, float)), "weight must be a number"
        loss = loss * weight
    return loss


def _reshape_like(F, x, y):
    return x.reshape(y.shape)


class Loss(HybridBlock):
    """Base loss (reference: loss.py:66)."""

    def __init__(self, weight, batch_axis, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis

    def __repr__(self):
        return "{name}(batch_axis={_batch_axis}, w={_weight})".format(
            name=self.__class__.__name__, **self.__dict__)

    def hybrid_forward(self, F, x, *args, **kwargs):  # pragma: no cover
        raise NotImplementedError

    def _shape_hook(self, inputs):
        pass


def _mean_all_but_batch(loss, batch_axis):
    axes = tuple(i for i in range(loss.ndim) if i != batch_axis)
    if not axes:
        return loss
    return loss.mean(axis=axes if len(axes) > 1 else axes[0])


class L2Loss(Loss):
    """0.5 * (pred - label)^2 (reference: loss.py:114)."""

    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = (pred - label).square()
        loss = _apply_weighting(F, loss, self._weight / 2, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class L1Loss(Loss):
    """|pred - label| (reference: loss.py:155)."""

    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = (pred - label).abs()
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class SigmoidBinaryCrossEntropyLoss(Loss):
    """BCE with optional logits (reference: loss.py:195)."""

    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_sigmoid = from_sigmoid

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        if not self._from_sigmoid:
            # stable: max(x,0) - x*z + log(1+exp(-|x|))
            loss = F.relu(pred) - pred * label + \
                (1.0 + (-pred.abs()).exp()).log()
        else:
            eps = 1e-12
            loss = -((pred + eps).log() * label +
                     (1.0 - pred + eps).log() * (1.0 - label))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    """Softmax CE with integer or dense labels (reference: loss.py:252)."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = F.log_softmax(pred, axis=self._axis)
        if self._sparse_label:
            loss = -pred.pick(label, axis=self._axis, keepdims=True)
        else:
            label = _reshape_like(F, label, pred)
            loss = -(pred * label).sum(axis=self._axis, keepdims=True)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class KLDivLoss(Loss):
    """KL divergence (reference: loss.py:317)."""

    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = F.log_softmax(pred, axis=self._axis)
        loss = label * ((label + 1e-12).log() - pred)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class CTCLoss(Loss):
    """Connectionist temporal classification (reference: loss.py:379)."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None, **kwargs):
        assert layout in ["NTC", "TNC"], \
            "Only 'NTC' and 'TNC' layouts for pred are supported. Got: %s" % layout
        assert label_layout in ["NT", "TN"], \
            "Only 'NT' and 'TN' layouts for label are supported. Got: %s" % label_layout
        self._layout = layout
        self._label_layout = label_layout
        batch_axis = label_layout.find("N")
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, pred_lengths=None,
                       label_lengths=None, sample_weight=None):
        if self._layout == "NTC":
            pred = pred.swapaxes(0, 1)
        if self._batch_axis == 1:
            label = label.swapaxes(0, 1)
        loss = F.CTCLoss(pred, label, pred_lengths, label_lengths,
                         use_data_lengths=pred_lengths is not None,
                         use_label_lengths=label_lengths is not None,
                         blank_label="last")
        return _apply_weighting(F, loss, self._weight, sample_weight)


class HuberLoss(Loss):
    """Smoothed L1 (reference: loss.py:452)."""

    def __init__(self, rho=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = (pred - label).abs()
        loss = F.where(loss > self._rho,
                       loss - 0.5 * self._rho,
                       (0.5 / self._rho) * loss.square())
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class HingeLoss(Loss):
    """max(0, margin - pred*label) (reference: loss.py:500)."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.relu(self._margin - pred * label)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class SquaredHingeLoss(Loss):
    """max(0, margin - pred*label)^2 (reference: loss.py:547)."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.relu(self._margin - pred * label).square()
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class LogisticLoss(Loss):
    """log(1 + exp(-pred*label)) (reference: loss.py:594)."""

    def __init__(self, weight=None, batch_axis=0, label_format="signed",
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._label_format = label_format
        if self._label_format not in ["signed", "binary"]:
            raise ValueError(
                "label_format can only be signed or binary, recieved %s." %
                label_format)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        if self._label_format == "signed":
            label = (label + 1.0) / 2.0  # to binary
        loss = F.relu(pred) - pred * label + \
            (1.0 + (-pred.abs()).exp()).log()
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class TripletLoss(Loss):
    """max(0, |p-pos|^2 - |p-neg|^2 + margin) (reference: loss.py:646)."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, positive, negative, sample_weight=None):
        positive = _reshape_like(F, positive, pred)
        negative = _reshape_like(F, negative, pred)
        sq_pos = (pred - positive).square()
        sq_neg = (pred - negative).square()
        axes = tuple(range(1, pred.ndim))
        loss = (sq_pos - sq_neg).sum(
            axis=axes if len(axes) > 1 else axes[0]) + self._margin
        loss = F.relu(loss)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return loss


class ExitWeightedCELoss(Loss):
    """Expected next-token cross-entropy of a model with several exits
    under its learned exit distribution, less ``beta`` times that
    distribution's entropy (no reference analogue; the first-stage
    objective of looped LMs, arXiv:2510.25741):
    ``mean_tokens(sum_t p_t * CE_t - beta * H(p))``.

    Called as ``loss(logits, states, gates, label)`` on the outputs of
    ``gluon.contrib.transformer.LoopedLM``: ``states`` ``(B, P, T, U)``
    are the exits' normed states, ``gates`` ``(B, P, T)`` their gate
    logits; ``logits`` (the last exit's, for inference) is not read.
    Every exit's logits are the SHARED head over its state — construct
    the loss with ``params=net.params`` (``net.exit_loss()``) — and the
    product is fused with its cross-entropy
    (``F.contrib.linear_cross_entropy``), so one exit's float32 logits
    live at a time.  Returns ``(B,)``."""

    def __init__(self, beta=0.05, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._beta = beta
        self.head_weight = self.params.get("head_weight")

    def hybrid_forward(self, F, logits, states, gates, label, head_weight,
                       sample_weight=None):
        import jax
        from ..telemetry import phases
        ce = []
        for t in range(states.shape[1]):
            with jax.named_scope(phases.EXIT_SCOPE):
                ce.append(F.contrib.linear_cross_entropy(
                    states[:, t], head_weight, label))
        loss = F.contrib.exit_weighted_loss(F.stack(*ce, axis=1), gates,
                                            beta=self._beta)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class LinearCELoss(Loss):
    """Next-token cross-entropy of a model whose output is the state its
    head reads (no reference analogue): ``loss(states, label)`` with
    ``states`` ``(B, T, U)`` and the head the SHARED parameter
    ``head_weight`` ``(V, U)`` — construct the loss with
    ``params=net.params`` (``lm_loss()`` of the expert LMs,
    ``gluon.contrib.transformer.MoELM`` and ``LatentMoELM``, without
    prediction modules).  ``head`` names that parameter:
    ``"embed_weight"`` where the head is TIED to the embedding table,
    whose gradient is then the sum of the look-up's and the head's.  The projection is fused with its
    cross-entropy (``F.contrib.linear_cross_entropy``), so the float32
    logits are never kept for the backward pass.  Returns ``(B,)``."""

    def __init__(self, weight=None, batch_axis=0, head="head_weight",
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self.head_weight = self.params.get(head)

    def hybrid_forward(self, F, states, label, head_weight,
                       sample_weight=None):
        loss = F.contrib.linear_cross_entropy(states, head_weight, label)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class BlockDiffusionCELoss(Loss):
    """The objective of a masked block-diffusion language model (no
    reference analogue; Arriola et al., "Block Diffusion",
    arXiv:2503.09573, equation 8 under the linear schedule, with SDAR's
    clipped rate, arXiv:2510.06303): ``loss(states, weight, label)`` with
    ``states`` ``(B, L, U)`` the final-normed states at the NOISED copy's
    positions, ``weight`` ``(B, L)`` the positions' ``m_i / p_b`` —
    ``m_i`` whether position ``i`` was masked, ``p_b`` its block's mask
    rate (``F.contrib.block_diffusion_noise``) — and ``label`` ``(B, L)``
    the CLEAN ids.  Per sequence::

        loss = (1 / L) sum_i weight_i * -log softmax(W_head states_i)[label_i]

    no shift: position ``i`` predicts its own clean token; an unmasked
    position weighs nothing.  The head is the SHARED parameter
    ``head_weight`` ``(V, U)`` — construct the loss with
    ``params=net.params``
    (``gluon.contrib.transformer.MoELM.diffusion_loss()``) — fused with
    its cross-entropy (``F.contrib.linear_cross_entropy``), so the ``(L,
    V)`` float32 logits are never kept.  The operator takes the
    positions' weights itself (``position_weight``): ``m / p`` can put
    most of a step's gradient on one position, and multiplied onto the
    terms it would be rounded to bfloat16 with the cotangent, ONE
    rounding that tilts every leaf's gradient; the operator applies it
    in float32 behind the head's products.  Returns ``(B,)``."""

    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self.head_weight = self.params.get("head_weight")

    def hybrid_forward(self, F, states, weight, label, head_weight,
                       sample_weight=None):
        loss = F.contrib.linear_cross_entropy(states, head_weight, label,
                                              weight)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return _mean_all_but_batch(loss, self._batch_axis)


class MultiTokenCELoss(Loss):
    """Next-token cross-entropy plus ``mtp_weight`` times the mean
    cross-entropy of ``D`` multi-token-prediction modules (no reference
    analogue; DeepSeek-V3, arXiv:2412.19437, section 2.2):
    ``loss(states, mtp_states, label)`` with ``states`` ``(B, T, U)``
    the state the head reads for the next token, ``mtp_states`` ``(B,
    D, T, U)`` the modules' own final-normed states, ``label`` ``(B,
    T)`` the next token of every position.  Module ``k`` (from 1) at
    position ``i`` predicts the token ``k`` after the next one,
    ``label[i + k]``; its last ``k`` positions have no target and are
    left out, and its term is the mean over the positions it has.  All
    ``D + 1`` terms go through the ONE shared head ``head_weight`` ``(V,
    U)`` — construct the loss with ``params=net.params``
    (``gluon.contrib.transformer.LatentMoELM.lm_loss()``) — each fused
    with its cross-entropy (``F.contrib.linear_cross_entropy``), so no
    term's float32 logits are kept; a module's weight reaches its
    gradients in float32, behind the head's products
    (``F.contrib.scale_gradient``).  Returns ``(B,)``."""

    def __init__(self, mtp_weight=0.3, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._mtp_weight = float(mtp_weight)
        self.head_weight = self.params.get("head_weight")

    @staticmethod
    def _target(F, label, k):
        """``label[i + k]`` at position ``i``; the last ``k`` wrap round
        (the caller cuts them off)."""
        return F.concat(F.slice_axis(label, axis=1, begin=k, end=None),
                        F.slice_axis(label, axis=1, begin=0, end=k), dim=1)

    def hybrid_forward(self, F, states, mtp_states, label, head_weight,
                       sample_weight=None):
        import jax
        from ..telemetry import phases
        t, depth = label.shape[1], mtp_states.shape[1]
        loss = F.contrib.linear_cross_entropy(states, head_weight,
                                              label).mean(axis=1)
        # a module's weight multiplies its gradients where the term READS
        # (F.contrib.scale_gradient says why), so the term's value is
        # brought to weight x term beside it
        w = self._mtp_weight / depth
        weigh = lambda a: F.contrib.scale_gradient(a, scale=w)
        with jax.named_scope(phases.MTP_SCOPE):
            for k in range(1, depth + 1):
                ce = F.contrib.linear_cross_entropy(
                    weigh(mtp_states[:, k - 1]), weigh(head_weight),
                    self._target(F, label, k))
                term = F.slice_axis(ce, axis=1, begin=0,
                                    end=t - k).mean(axis=1)
                loss = loss + term - (1.0 - w) * F.BlockGrad(term)
        return _apply_weighting(F, loss, self._weight, sample_weight)
