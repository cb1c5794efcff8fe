"""Evaluation metrics.

Reference: ``python/mxnet/metric.py`` — EvalMetric base + registry (:68),
CompositeEvalMetric (:233), Accuracy/TopK/F1/Perplexity/MAE/MSE/RMSE/
CrossEntropy/NegativeLogLikelihood/PearsonCorrelation/Loss/Torch/Caffe/
CustomMetric (:363-1266), np()/create() helpers.
"""
from __future__ import annotations

import math

import numpy

from .base import MXNetError
from .ndarray import NDArray

__all__ = ["EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy",
           "F1", "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy",
           "NegativeLogLikelihood", "PearsonCorrelation", "Loss",
           "CustomMetric", "np", "create", "register"]

_METRIC_REGISTRY = {}


def register(klass, *names):
    for n in names or (klass.__name__.lower(),):
        _METRIC_REGISTRY[n.lower()] = klass
    return klass


def check_label_shapes(labels, preds, shape=False):
    if not shape:
        label_shape, pred_shape = len(labels), len(preds)
    else:
        label_shape, pred_shape = labels.shape, preds.shape
    if label_shape != pred_shape:
        raise ValueError("Shape of labels {} does not match shape of "
                         "predictions {}".format(label_shape, pred_shape))


def _acc_accum(pred, label, total, axis):
    """One fused device program for Accuracy's per-batch accumulate
    (argmax + compare + sum + add); jit-cached per (shape, axis)."""
    import jax

    global _ACC_ACCUM_JIT
    if _ACC_ACCUM_JIT is None:
        import jax.numpy as jnp

        def _body(pred, label, total, axis):
            if axis is not None:
                pred = jnp.argmax(pred, axis=axis)
            pred = pred.astype(jnp.int32).ravel()
            label = label.astype(jnp.int32).ravel()
            return total + (pred == label).sum()

        _ACC_ACCUM_JIT = jax.jit(_body, static_argnames=("axis",))
    return _ACC_ACCUM_JIT(pred, label, total, axis=axis)


_ACC_ACCUM_JIT = None


def _as_np(x):
    # deliberate sync: EvalMetric's contract is host-side accumulation —
    # update(labels, preds) consumes concrete values (the per-batch d2h
    # is counted by mxnet_transfer_d2h_total; heavy metrics should use
    # the jit-accumulated paths like Accuracy's _ACC_ACCUM_JIT)
    return x.asnumpy() if isinstance(x, NDArray) else numpy.asarray(x)  # graftlint: disable=host-sync


class EvalMetric:
    """Base metric (reference: metric.py:68)."""

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        self.reset()

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))

    def get_config(self):
        config = self._kwargs.copy()
        config.update({
            "metric": self.__class__.__name__,
            "name": self.name,
            "output_names": self.output_names,
            "label_names": self.label_names})
        return config

    def update_dict(self, label, pred):
        if self.output_names is not None:
            pred = [pred[name] for name in self.output_names if name in pred]
        else:
            pred = list(pred.values())
        if self.label_names is not None:
            label = [label[name] for name in self.label_names if name in label]
        else:
            label = list(label.values())
        self.update(label, pred)

    def update(self, labels, preds):  # pragma: no cover - abstract
        raise NotImplementedError()

    def reset(self):
        self.num_inst = 0
        self.sum_metric = 0.0

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        # sum_metric may be a device scalar (lazily accumulated on TPU —
        # see Accuracy.update); reading the value is the sync point
        return (self.name, float(self.sum_metric) / self.num_inst)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))


@register
class CompositeEvalMetric(EvalMetric):
    """Manage multiple metrics at once (reference: metric.py:233)."""

    def __init__(self, metrics=None, name="composite", output_names=None,
                 label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)
        self.metrics = metrics if metrics is not None else []
        for i, metric in enumerate(self.metrics):
            if not isinstance(metric, EvalMetric):
                self.metrics[i] = create(metric)

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        try:
            return self.metrics[index]
        except IndexError:
            return ValueError("Metric index {} is out of range 0 and {}".format(
                index, len(self.metrics)))

    def update_dict(self, labels, preds):
        if self.label_names is not None:
            labels = {name: label for name, label in labels.items()
                      if name in self.label_names}
        if self.output_names is not None:
            preds = {name: pred for name, pred in preds.items()
                     if name in self.output_names}
        for metric in self.metrics:
            metric.update_dict(labels, preds)

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def reset(self):
        try:
            for metric in self.metrics:
                metric.reset()
        except AttributeError:
            pass

    def get(self):
        names = []
        values = []
        for metric in self.metrics:
            name, value = metric.get()
            if isinstance(name, str):
                name = [name]
            if isinstance(value, (float, int, numpy.generic)):
                value = [value]
            names.extend(name)
            values.extend(value)
        return (names, values)

    def get_config(self):
        config = super().get_config()
        config.update({"metrics": [i.get_config() for i in self.metrics]})
        return config


@register
class Accuracy(EvalMetric):
    """Classification accuracy (reference: metric.py:363)."""

    def __init__(self, axis=1, name="accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, axis=axis, output_names=output_names,
                         label_names=label_names)
        self.axis = axis

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            if isinstance(pred_label, NDArray) and isinstance(label, NDArray):
                # device path: argmax/compare/sum/accumulate run as ONE
                # jitted program on the accelerator into a lazy device
                # scalar — one dispatch per batch instead of ~8, and no
                # per-batch host transfer of the (N, classes) prediction
                # matrix.  get() is the sync point (Speedometer interval
                # / epoch).
                import jax.numpy as jnp
                p = pred_label._data
                lab = label._data
                needs_argmax = p.ndim > 1 and \
                    p.shape[-1 if self.axis == -1 else self.axis] > 1 \
                    and p.ndim != lab.ndim
                if needs_argmax:
                    if p.size // p.shape[self.axis] != lab.size:
                        raise ValueError(
                            "Shape of labels %s does not match shape of "
                            "predictions %s" % (lab.shape, p.shape))
                else:
                    check_label_shapes(lab.ravel(), p.ravel(), shape=True)
                self.sum_metric = _acc_accum(
                    p, lab, jnp.asarray(self.sum_metric),
                    self.axis if needs_argmax else None)
                self.num_inst += int(lab.size)
                continue
            p = _as_np(pred_label)
            if p.ndim > 1 and p.shape[-1 if self.axis == -1 else self.axis] > 1 \
                    and p.ndim != _as_np(label).ndim:
                p = numpy.argmax(p, axis=self.axis)
            lab = _as_np(label).astype("int32").ravel()
            p = p.astype("int32").ravel()
            check_label_shapes(lab, p, shape=True)
            self.sum_metric += (p == lab).sum()
            self.num_inst += len(p)


@register
class TopKAccuracy(EvalMetric):
    """Top-k accuracy (reference: metric.py:446)."""

    def __init__(self, top_k=1, name="top_k_accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, top_k=top_k, output_names=output_names,
                         label_names=label_names)
        self.top_k = top_k
        assert self.top_k > 1, "Please use Accuracy if top_k is no more than 1"
        self.name += "_%d" % self.top_k

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            p = numpy.argsort(_as_np(pred_label).astype("float32"), axis=1)
            lab = _as_np(label).astype("int32")
            num_samples = p.shape[0]
            num_dims = len(p.shape)
            if num_dims == 1:
                self.sum_metric += (p.ravel() == lab.ravel()).sum()
            elif num_dims == 2:
                num_classes = p.shape[1]
                top_k = min(num_classes, self.top_k)
                for j in range(top_k):
                    self.sum_metric += (
                        p[:, num_classes - 1 - j].ravel() == lab.ravel()).sum()
            self.num_inst += num_samples


@register
class F1(EvalMetric):
    """Binary F1 score (reference: metric.py:533)."""

    def __init__(self, name="f1", output_names=None, label_names=None,
                 average="macro"):
        self.average = average
        self.metrics = _BinaryClassificationMetrics()
        super().__init__(name=name, output_names=output_names,
                         label_names=label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            self.metrics.update_binary_stats(label, pred)
        if self.average == "macro":
            # per-batch fscore averaged uniformly across batches
            self.sum_metric += self.metrics.fscore
            self.num_inst += 1
            self.metrics.reset_stats()
            return
        self.sum_metric = self.metrics.fscore * self.metrics.total_examples
        self.num_inst = self.metrics.total_examples

    def reset(self):
        self.sum_metric = 0.0
        self.num_inst = 0
        if hasattr(self, "metrics"):
            self.metrics.reset_stats()


class _BinaryClassificationMetrics:
    """TP/FP/FN bookkeeping for F1 (reference: metric.py:482)."""

    def __init__(self):
        self.reset_stats()

    def update_binary_stats(self, label, pred):
        pred_np = _as_np(pred)
        label_np = _as_np(label).astype("int32")
        pred_label = numpy.argmax(pred_np, axis=1) if pred_np.ndim > 1 else (
            pred_np > 0.5).astype("int32")
        check_label_shapes(label_np.ravel(), pred_label.ravel(), shape=True)
        if len(numpy.unique(label_np)) > 2:
            raise ValueError("%s currently only supports binary classification."
                             % self.__class__.__name__)
        pred_true = (pred_label.ravel() == 1)
        pred_false = ~pred_true
        label_true = (label_np.ravel() == 1)
        label_false = ~label_true
        self.true_positives += (pred_true & label_true).sum()
        self.false_positives += (pred_true & label_false).sum()
        self.false_negatives += (pred_false & label_true).sum()
        self.true_negatives += (pred_false & label_false).sum()

    @property
    def precision(self):
        tp = self.true_positives
        return tp / (tp + self.false_positives) if tp + self.false_positives > 0 else 0.0

    @property
    def recall(self):
        tp = self.true_positives
        return tp / (tp + self.false_negatives) if tp + self.false_negatives > 0 else 0.0

    @property
    def fscore(self):
        p, r = self.precision, self.recall
        return 2 * p * r / (p + r) if p + r > 0 else 0.0

    @property
    def total_examples(self):
        return (self.false_negatives + self.false_positives +
                self.true_negatives + self.true_positives)

    def reset_stats(self):
        self.false_positives = 0
        self.false_negatives = 0
        self.true_positives = 0
        self.true_negatives = 0


@register
class Perplexity(EvalMetric):
    """Perplexity (reference: metric.py:761)."""

    def __init__(self, ignore_label, axis=-1, name="perplexity",
                 output_names=None, label_names=None):
        super().__init__(name, ignore_label=ignore_label, axis=axis,
                         output_names=output_names, label_names=label_names)
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        loss = 0.0
        num = 0
        for label, pred in zip(labels, preds):
            lab = _as_np(label).astype("int32").ravel()
            p = _as_np(pred)
            p = p.reshape(-1, p.shape[-1] if self.axis == -1 else p.shape[self.axis])
            assert lab.size == p.shape[0], \
                "shape mismatch: %s vs. %s" % (lab.shape, p.shape)
            probs = p[numpy.arange(lab.size), lab]
            if self.ignore_label is not None:
                ignore = (lab == self.ignore_label).astype(p.dtype)
                num -= int(ignore.sum())
                probs = probs * (1 - ignore) + ignore
            loss -= numpy.sum(numpy.log(numpy.maximum(1e-10, probs)))
            num += lab.size
        self.sum_metric += loss
        self.num_inst += num

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, math.exp(self.sum_metric / self.num_inst))


@register
class MAE(EvalMetric):
    """Mean absolute error (reference: metric.py:828)."""

    def __init__(self, name="mae", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            lab, p = _as_np(label), _as_np(pred)
            if lab.ndim == 1:
                lab = lab.reshape(lab.shape[0], 1)
            if p.ndim == 1:
                p = p.reshape(p.shape[0], 1)
            self.sum_metric += numpy.abs(lab - p).mean()
            self.num_inst += 1


@register
class MSE(EvalMetric):
    """Mean squared error (reference: metric.py:880)."""

    def __init__(self, name="mse", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            lab, p = _as_np(label), _as_np(pred)
            if lab.ndim == 1:
                lab = lab.reshape(lab.shape[0], 1)
            if p.ndim == 1:
                p = p.reshape(p.shape[0], 1)
            self.sum_metric += ((lab - p) ** 2.0).mean()
            self.num_inst += 1


@register
class RMSE(EvalMetric):
    """Root mean squared error (reference: metric.py:932)."""

    def __init__(self, name="rmse", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            lab, p = _as_np(label), _as_np(pred)
            if lab.ndim == 1:
                lab = lab.reshape(lab.shape[0], 1)
            if p.ndim == 1:
                p = p.reshape(p.shape[0], 1)
            self.sum_metric += numpy.sqrt(((lab - p) ** 2.0).mean())
            self.num_inst += 1


@register
class CrossEntropy(EvalMetric):
    """Cross entropy vs integer labels (reference: metric.py:985)."""

    def __init__(self, eps=1e-12, name="cross-entropy", output_names=None,
                 label_names=None):
        super().__init__(name, eps=eps, output_names=output_names,
                         label_names=label_names)
        self.eps = eps

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            lab = _as_np(label).ravel()
            p = _as_np(pred)
            assert lab.shape[0] == p.shape[0]
            prob = p[numpy.arange(lab.shape[0]), numpy.int64(lab)]
            self.sum_metric += (-numpy.log(prob + self.eps)).sum()
            self.num_inst += lab.shape[0]


@register
class NegativeLogLikelihood(EvalMetric):
    """NLL (reference: metric.py:1043)."""

    def __init__(self, eps=1e-12, name="nll-loss", output_names=None,
                 label_names=None):
        super().__init__(name, eps=eps, output_names=output_names,
                         label_names=label_names)
        self.eps = eps

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            lab = _as_np(label).ravel()
            p = _as_np(pred)
            num_examples = p.shape[0]
            assert lab.shape[0] == num_examples, (lab.shape[0], num_examples)
            prob = p[numpy.arange(num_examples, dtype=numpy.int64), numpy.int64(lab)]
            self.sum_metric += (-numpy.log(prob + self.eps)).sum()
            self.num_inst += num_examples


@register
class PearsonCorrelation(EvalMetric):
    """Pearson correlation (reference: metric.py:1103)."""

    def __init__(self, name="pearsonr", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            check_label_shapes(label, pred, True)
            lab, p = _as_np(label).ravel(), _as_np(pred).ravel()
            self.sum_metric += numpy.corrcoef(p, lab)[0, 1]
            self.num_inst += 1


@register
class Loss(EvalMetric):
    """Dummy metric for mean of pre-computed losses (reference: metric.py:1156)."""

    def __init__(self, name="loss", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def update(self, _, preds):
        for pred in preds:
            loss = _as_np(pred).sum()
            self.sum_metric += loss
            self.num_inst += _as_np(pred).size


@register
class Torch(Loss):
    """Legacy name (reference: metric.py:1189)."""

    def __init__(self, name="torch", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class Caffe(Loss):
    """Legacy name (reference: metric.py:1198)."""

    def __init__(self, name="caffe", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class CustomMetric(EvalMetric):
    """Metric from a python function (reference: metric.py:1207)."""

    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__
            if name.find("<") != -1:
                name = "custom(%s)" % name
        super().__init__(name, feval=feval,
                         allow_extra_outputs=allow_extra_outputs,
                         output_names=output_names, label_names=label_names)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            lab, p = _as_np(label), _as_np(pred)
            reval = self._feval(lab, p)
            if isinstance(reval, tuple):
                (sum_metric, num_inst) = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1

    def get_config(self):
        raise NotImplementedError("CustomMetric cannot be serialized")


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a numpy eval function (reference: metric.py:1266)."""

    def feval(label, pred):
        return numpy_feval(label, pred)

    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


register(TopKAccuracy, "top_k_accuracy", "top_k_acc")
register(PearsonCorrelation, "pearsonr", "pearsoncorrelation")
register(Accuracy, "acc", "accuracy")
register(CrossEntropy, "ce", "cross-entropy")
register(NegativeLogLikelihood, "nll_loss")


def create(metric, *args, **kwargs):
    """Create a metric from name, function, or config (reference: metric.py:32)."""
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite_metric = CompositeEvalMetric()
        for child_metric in metric:
            composite_metric.add(create(child_metric, *args, **kwargs))
        return composite_metric
    if isinstance(metric, str) and metric.lower() in _METRIC_REGISTRY:
        return _METRIC_REGISTRY[metric.lower()](*args, **kwargs)
    raise MXNetError("Metric must be either callable or str; got %r" % metric)
