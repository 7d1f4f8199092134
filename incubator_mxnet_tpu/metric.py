"""Evaluation metrics registry (reference `python/mxnet/metric.py`)."""
from __future__ import annotations

import math

import numpy

from .base import MXNetError
from .ndarray.ndarray import NDArray

_METRIC_REGISTRY = {}


def register(klass):
    _METRIC_REGISTRY[klass.__name__.lower()] = klass
    return klass


def alias(*aliases):
    def deco(klass):
        for a in aliases:
            _METRIC_REGISTRY[a.lower()] = klass
        return klass
    return deco


def create(metric, *args, **kwargs):
    """Reference `metric.py create`."""
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite = CompositeEvalMetric()
        for child in metric:
            composite.add(create(child, *args, **kwargs))
        return composite
    if isinstance(metric, str) and metric.lower() in _METRIC_REGISTRY:
        return _METRIC_REGISTRY[metric.lower()](*args, **kwargs)
    raise MXNetError(f"Metric must be callable/str/list, got {metric!r}")


def np(numpy_feval, name=None, allow_extra_outputs=False):
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = name if name else numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


def _as_numpy(x):
    return x.asnumpy() if isinstance(x, NDArray) else numpy.asarray(x)


def check_label_shapes(labels, preds, wrap=False, shape=False):
    if isinstance(labels, NDArray):
        labels = [labels]
    if isinstance(preds, NDArray):
        preds = [preds]
    if len(labels) != len(preds):
        raise ValueError(f"Shape of labels {len(labels)} does not match shape "
                         f"of predictions {len(preds)}")
    return labels, preds


class EvalMetric:
    """Base metric (reference `metric.py:EvalMetric`)."""

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        self.reset()

    def __str__(self):
        return f"EvalMetric: {dict(self.get_name_value())}"

    def get_config(self):
        config = self._kwargs.copy()
        config.update({"metric": self.__class__.__name__, "name": self.name,
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

    def update(self, labels, preds):
        raise NotImplementedError()

    # -- device-side accumulation (fused train step) -------------------------
    # Metrics that can run in-graph define `device_update(labels, preds) ->
    # (sum_delta, num_delta)` over jax arrays; the fused Module train step
    # (`fused.FusedTrainStep`) then accumulates (sum, num) ON DEVICE as part
    # of the compiled program and stores the running totals here — `get()`
    # fetches them with a single host sync instead of one per batch.
    # Metrics without `device_update` keep the per-batch host path.
    _device_totals = None

    def _materialize(self):
        if self._device_totals is not None:
            import jax
            dsum, dnum = self._device_totals
            # ONE batched host read: two sequential float() fetches are
            # two device syncs; device_get of the pair is one
            hsum, hnum = jax.device_get([dsum, dnum])
            self.sum_metric += float(hsum)
            self.num_inst += int(round(float(hnum)))
            self._device_totals = None

    def reset(self):
        self.num_inst = 0
        self.sum_metric = 0.0
        self._device_totals = None

    def get(self):
        self._materialize()
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, self.sum_metric / self.num_inst)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))


@register
@alias("composite")
class CompositeEvalMetric(EvalMetric):
    def __init__(self, metrics=None, name="composite", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        self.metrics = [create(m) for m in metrics] if metrics else []

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update_dict(self, labels, preds):
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
            if not isinstance(name, list):
                name = [name]
            if not isinstance(value, list):
                value = [value]
            names.extend(name)
            values.extend(value)
        return names, values


@register
@alias("acc")
class Accuracy(EvalMetric):
    """Reference `metric.py:Accuracy`."""

    def __init__(self, axis=1, name="accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, axis=axis)
        self.axis = axis

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            pred = _as_numpy(pred_label)
            if pred.ndim > 1 and pred.shape != _as_numpy(label).shape:
                pred = pred.argmax(axis=self.axis)
            lab = _as_numpy(label).astype("int32").reshape(-1)
            pred = pred.astype("int32").reshape(-1)
            self.sum_metric += (pred == lab).sum()
            self.num_inst += len(pred)

    def device_update(self, labels, preds):
        import jax.numpy as jnp
        dsum, dnum = 0.0, 0.0
        for label, pred in zip(labels, preds):
            if pred.ndim > 1 and pred.shape != label.shape:
                pred = jnp.argmax(pred, axis=self.axis)
            lab = label.reshape(-1).astype(jnp.int32)
            pred = pred.reshape(-1).astype(jnp.int32)
            dsum = dsum + (pred == lab).sum()
            dnum = dnum + pred.size
        return dsum, dnum


@register
@alias("top_k_accuracy", "top_k_acc")
class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1, name="top_k_accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, top_k=top_k)
        self.top_k = top_k
        assert self.top_k > 1, "Please use Accuracy if top_k is no more than 1"
        self.name += f"_{self.top_k}"

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            pred = numpy.argsort(_as_numpy(pred_label).astype("float32"))
            lab = _as_numpy(label).astype("int32")
            num_samples = pred.shape[0]
            num_dims = len(pred.shape)
            if num_dims == 1:
                self.sum_metric += (pred.flat == lab.flat).sum()
            elif num_dims == 2:
                num_classes = pred.shape[1]
                top_k = min(num_classes, self.top_k)
                for j in range(top_k):
                    self.sum_metric += (
                        pred[:, num_classes - 1 - j].flat == lab.flat).sum()
            self.num_inst += num_samples

    def device_update(self, labels, preds):
        import jax.numpy as jnp
        dsum, dnum = 0.0, 0.0
        for label, pred in zip(labels, preds):
            if pred.ndim != 2:
                # raising at trace time makes the fused path fall back to
                # the host update, which surfaces the shape problem the
                # same way the reference does (silent skipping would
                # report NaN accuracy instead)
                raise ValueError(
                    f"TopKAccuracy expects 2-D predictions, got {pred.shape}")
            top_k = min(pred.shape[1], self.top_k)
            top = jnp.argsort(pred.astype(jnp.float32), axis=1)[:, -top_k:]
            lab = label.reshape(-1).astype(jnp.int32)
            dsum = dsum + (top == lab[:, None]).sum()
            dnum = dnum + pred.shape[0]
        return dsum, dnum


@register
class F1(EvalMetric):
    """Binary F1 (reference `metric.py:F1`)."""

    def __init__(self, name="f1", output_names=None, label_names=None,
                 average="macro"):
        self.average = average
        self.metrics = _BinaryClassificationMetrics()
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            self.metrics.update_binary_stats(label, pred)
        if self.average == "macro":
            self.sum_metric += self.metrics.fscore
            self.num_inst += 1
            self.metrics.reset_stats()
        else:
            self.sum_metric = self.metrics.fscore * self.metrics.total_examples
            self.num_inst = self.metrics.total_examples

    def reset(self):
        self.sum_metric = 0.0
        self.num_inst = 0
        if hasattr(self, "metrics"):
            self.metrics.reset_stats()


class _BinaryClassificationMetrics:
    def __init__(self):
        self.reset_stats()

    def reset_stats(self):
        self.true_positives = 0
        self.false_positives = 0
        self.true_negatives = 0
        self.false_negatives = 0

    def update_binary_stats(self, label, pred):
        pred = _as_numpy(pred)
        label = _as_numpy(label).astype("int32")
        pred_label = numpy.argmax(pred, axis=1) if pred.ndim > 1 else \
            (pred > 0.5).astype("int32")
        if len(numpy.unique(label)) > 2:
            raise ValueError("F1 currently only supports binary classification.")
        self.true_positives += ((pred_label == 1) & (label.reshape(-1) == 1)).sum()
        self.false_positives += ((pred_label == 1) & (label.reshape(-1) == 0)).sum()
        self.false_negatives += ((pred_label == 0) & (label.reshape(-1) == 1)).sum()
        self.true_negatives += ((pred_label == 0) & (label.reshape(-1) == 0)).sum()

    @property
    def precision(self):
        tp_fp = self.true_positives + self.false_positives
        return self.true_positives / tp_fp if tp_fp else 0.0

    @property
    def recall(self):
        tp_fn = self.true_positives + self.false_negatives
        return self.true_positives / tp_fn if tp_fn else 0.0

    @property
    def fscore(self):
        if self.precision + self.recall > 0:
            return 2 * self.precision * self.recall / (self.precision + self.recall)
        return 0.0

    @property
    def total_examples(self):
        return (self.false_negatives + self.false_positives +
                self.true_negatives + self.true_positives)


@register
class MCC(EvalMetric):
    """Matthews correlation coefficient (reference `metric.py:MCC`)."""

    def __init__(self, name="mcc", output_names=None, label_names=None,
                 average="macro"):
        self._average = average
        self._metrics = _BinaryClassificationMetrics()
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            self._metrics.update_binary_stats(label, pred)
        m = self._metrics
        terms = ((m.true_positives + m.false_positives) *
                 (m.true_positives + m.false_negatives) *
                 (m.true_negatives + m.false_positives) *
                 (m.true_negatives + m.false_negatives))
        denom = math.sqrt(terms) if terms else 1.0
        mcc = (m.true_positives * m.true_negatives -
               m.false_positives * m.false_negatives) / (denom or 1.0)
        if self._average == "macro":
            self.sum_metric += mcc
            self.num_inst += 1
            self._metrics.reset_stats()
        else:
            self.sum_metric = mcc * m.total_examples
            self.num_inst = m.total_examples

    def reset(self):
        self.sum_metric = 0.0
        self.num_inst = 0
        if hasattr(self, "_metrics"):
            self._metrics.reset_stats()


@register
class Perplexity(EvalMetric):
    """Reference `metric.py:Perplexity`."""

    def __init__(self, ignore_label=None, axis=-1, name="perplexity",
                 output_names=None, label_names=None):
        super().__init__(name, output_names, label_names,
                         ignore_label=ignore_label, axis=axis)
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        loss = 0.0
        num = 0
        for label, pred in zip(labels, preds):
            label = _as_numpy(label).astype("int32").reshape(-1)
            pred = _as_numpy(pred).reshape(-1, _as_numpy(pred).shape[-1]) \
                if _as_numpy(pred).ndim > 2 else _as_numpy(pred)
            probs = pred[numpy.arange(label.shape[0]), label]
            if self.ignore_label is not None:
                ignore = (label == self.ignore_label)
                probs = numpy.where(ignore, 1.0, probs)
                num -= ignore.sum()
            loss -= numpy.log(numpy.maximum(1e-10, probs)).sum()
            num += label.shape[0]
        self.sum_metric += loss
        self.num_inst += num

    def device_update(self, labels, preds):
        import jax.numpy as jnp
        dsum, dnum = 0.0, 0.0
        for label, pred in zip(labels, preds):
            lab = label.reshape(-1).astype(jnp.int32)
            pred = pred.astype(jnp.float32)
            if pred.ndim > 2:
                pred = pred.reshape(-1, pred.shape[-1])
            probs = jnp.take_along_axis(pred, lab[:, None], axis=1)[:, 0]
            if self.ignore_label is not None:
                ignore = lab == int(self.ignore_label)
                probs = jnp.where(ignore, 1.0, probs)
                dnum = dnum - ignore.sum()
            dsum = dsum - jnp.log(jnp.maximum(1e-10, probs)).sum()
            dnum = dnum + lab.shape[0]
        return dsum, dnum

    def get(self):
        self._materialize()
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, math.exp(self.sum_metric / self.num_inst))


@register
class MAE(EvalMetric):
    def __init__(self, name="mae", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label)
            pred = _as_numpy(pred)
            if len(label.shape) == 1:
                label = label.reshape(label.shape[0], 1)
            if len(pred.shape) == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self.sum_metric += numpy.abs(label - pred).mean()
            self.num_inst += 1

    def device_update(self, labels, preds):
        import jax.numpy as jnp
        dsum, dnum = 0.0, 0.0
        for label, pred in zip(labels, preds):
            label = label.reshape(label.shape[0], -1).astype(jnp.float32)
            pred = pred.reshape(pred.shape[0], -1).astype(jnp.float32)
            dsum = dsum + jnp.abs(label - pred).mean()
            dnum = dnum + 1
        return dsum, dnum


@register
class MSE(EvalMetric):
    def __init__(self, name="mse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label)
            pred = _as_numpy(pred)
            if len(label.shape) == 1:
                label = label.reshape(label.shape[0], 1)
            if len(pred.shape) == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self.sum_metric += ((label - pred) ** 2.0).mean()
            self.num_inst += 1

    def device_update(self, labels, preds):
        import jax.numpy as jnp
        dsum, dnum = 0.0, 0.0
        for label, pred in zip(labels, preds):
            label = label.reshape(label.shape[0], -1).astype(jnp.float32)
            pred = pred.reshape(pred.shape[0], -1).astype(jnp.float32)
            dsum = dsum + ((label - pred) ** 2.0).mean()
            dnum = dnum + 1
        return dsum, dnum


@register
class RMSE(EvalMetric):
    def __init__(self, name="rmse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label)
            pred = _as_numpy(pred)
            if len(label.shape) == 1:
                label = label.reshape(label.shape[0], 1)
            if len(pred.shape) == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self.sum_metric += numpy.sqrt(((label - pred) ** 2.0).mean())
            self.num_inst += 1


@register
@alias("ce")
class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-12, name="cross-entropy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, eps=eps)
        self.eps = eps

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label).ravel()
            pred = _as_numpy(pred)
            assert label.shape[0] == pred.shape[0]
            prob = pred[numpy.arange(label.shape[0]), numpy.int64(label)]
            self.sum_metric += (-numpy.log(prob + self.eps)).sum()
            self.num_inst += label.shape[0]

    def device_update(self, labels, preds):
        import jax.numpy as jnp
        dsum, dnum = 0.0, 0.0
        for label, pred in zip(labels, preds):
            lab = label.reshape(-1).astype(jnp.int32)
            pred = pred.astype(jnp.float32)
            prob = jnp.take_along_axis(pred, lab[:, None], axis=1)[:, 0]
            dsum = dsum + (-jnp.log(prob + self.eps)).sum()
            dnum = dnum + lab.shape[0]
        return dsum, dnum


@register
@alias("nll_loss")
class NegativeLogLikelihood(EvalMetric):
    def __init__(self, eps=1e-12, name="nll-loss", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, eps=eps)
        self.eps = eps

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label).ravel()
            pred = _as_numpy(pred)
            num_examples = pred.shape[0]
            assert label.shape[0] == num_examples
            prob = pred[numpy.arange(num_examples), numpy.int64(label)]
            self.sum_metric += (-numpy.log(prob + self.eps)).sum()
            self.num_inst += num_examples


@register
@alias("pearsonr")
class PearsonCorrelation(EvalMetric):
    def __init__(self, name="pearsonr", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _as_numpy(label).ravel()
            pred = _as_numpy(pred).ravel()
            self.sum_metric += numpy.corrcoef(pred, label)[0, 1]
            self.num_inst += 1


@register
class Loss(EvalMetric):
    """Mean of a loss output (reference `metric.py:Loss`)."""

    def __init__(self, name="loss", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, _, preds):
        if isinstance(preds, NDArray):
            preds = [preds]
        for pred in preds:
            loss = _as_numpy(pred).sum()
            self.sum_metric += loss
            self.num_inst += _as_numpy(pred).size

    def device_update(self, labels, preds):
        import jax.numpy as jnp
        dsum, dnum = 0.0, 0.0
        for pred in preds:
            dsum = dsum + pred.astype(jnp.float32).sum()
            dnum = dnum + pred.size
        return dsum, dnum


@register
class Torch(Loss):
    def __init__(self, name="torch", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class Caffe(Loss):
    def __init__(self, name="caffe", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class CustomMetric(EvalMetric):
    """Wrap a python feval(label, pred) (reference `metric.py:CustomMetric`)."""

    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__
            if name.find("<") != -1:
                name = "custom(%s)" % name
        super().__init__(name, output_names, label_names, feval=feval,
                         allow_extra_outputs=allow_extra_outputs)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            labels, preds = check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            label = _as_numpy(label)
            pred = _as_numpy(pred)
            reval = self._feval(label, pred)
            if isinstance(reval, tuple):
                (sum_metric, num_inst) = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1
