"""Evaluation metrics (parity: python/mxnet/metric.py:44-1132 — EvalMetric
registry, Accuracy, TopKAccuracy, F1, Perplexity, MAE/MSE/RMSE, CrossEntropy,
Pearson, Loss, Torch, Caffe, CustomMetric, CompositeEvalMetric, np helper)."""
from __future__ import annotations

import math

import numpy as _np

from .base import MXNetError, Registry
from .ndarray import NDArray

_REG = Registry("metric")


def check_label_shapes(labels, preds, shape=0):
    if shape == 0:
        label_shape, pred_shape = len(labels), len(preds)
    else:
        label_shape, pred_shape = labels.shape, preds.shape
    if label_shape != pred_shape:
        raise ValueError("Shape of labels %s does not match shape of "
                         "predictions %s" % (label_shape, pred_shape))


class EvalMetric:
    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = name
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        self.reset()

    def device_kernel(self):
        """Device-resident accumulation support: return a :class:`DeviceKernel`
        whose ``sum_fn`` computes this metric's partial sum in ``jax.numpy``
        (so the fit loop can accumulate it on device, asynchronously, instead
        of pulling every batch's outputs to the host), or ``None`` when the
        metric has no device kernel and must stay on the numpy path."""
        return None

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
        raise NotImplementedError

    def reset(self):
        self.num_inst = 0
        self.sum_metric = 0.0

    def get(self):
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

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))


_ALIASES = {"Accuracy": ("acc",), "TopKAccuracy": ("top_k_acc", "top_k_accuracy"),
            "CrossEntropy": ("ce", "cross-entropy"),
            "PearsonCorrelation": ("pearsonr",), "CompositeEvalMetric": ("composite",),
            "CustomMetric": ("custom",)}


def register(klass):
    _REG.register(klass, aliases=_ALIASES.get(klass.__name__, ()))
    return klass


def np(numpy_feval, name=None, allow_extra_outputs=False):
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = name or numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


def create(metric, *args, **kwargs):
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite = CompositeEvalMetric()
        for child in metric:
            composite.add(create(child, *args, **kwargs))
        return composite
    return _REG.create(metric, *args, **kwargs)


@register
class CompositeEvalMetric(EvalMetric):
    def __init__(self, metrics=None, name="composite", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        self.metrics = [create(m) for m in (metrics or [])]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def reset(self):
        for metric in getattr(self, "metrics", []):
            metric.reset()
        super().reset()

    def get(self):
        names, values = [], []
        for metric in self.metrics:
            name, value = metric.get()
            if isinstance(name, str):
                name = [name]
            if isinstance(value, (float, int)):
                value = [value]
            names.extend(name)
            values.extend(value)
        return (names, values)


@register
class Accuracy(EvalMetric):
    def __init__(self, axis=1, name="accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        self.axis = axis

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            pred = pred_label.asnumpy() if isinstance(pred_label, NDArray) else pred_label
            lab = label.asnumpy() if isinstance(label, NDArray) else label
            if pred.shape != lab.shape:
                pred = _np.argmax(pred, axis=self.axis)
            pred = pred.astype("int32").flatten()
            lab = lab.astype("int32").flatten()
            check_label_shapes(lab, pred, shape=1)
            self.sum_metric += float((pred == lab).sum())
            self.num_inst += len(pred)

    def device_kernel(self):
        import jax.numpy as jnp
        axis = self.axis

        def sum_fn(label, pred):
            if pred.shape != label.shape:
                pred = jnp.argmax(pred, axis=axis)
            pred = pred.astype(jnp.int32).reshape(-1)
            lab = label.astype(jnp.int32).reshape(-1)
            return jnp.sum(pred == lab).astype(jnp.float32)

        return DeviceKernel(sum_fn, lambda label, pred: _shape_size(label),
                            key=("Accuracy", axis))


@register
class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1, name="top_k_accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        self.top_k = top_k
        assert self.top_k > 1, "Please use Accuracy if top_k is no more than 1"
        self.name += "_%d" % self.top_k

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            pred = _np.argsort(pred_label.asnumpy().astype("float32"), axis=1)
            lab = label.asnumpy().astype("int32")
            num_samples = pred.shape[0]
            num_classes = pred.shape[1]
            top_k = min(num_classes, self.top_k)
            for j in range(top_k):
                self.sum_metric += float(
                    (pred[:, num_classes - 1 - j].flatten() == lab.flatten()).sum())
            self.num_inst += num_samples

    def device_kernel(self):
        import jax.numpy as jnp
        want_k = self.top_k

        def sum_fn(label, pred):
            order = jnp.argsort(pred.astype(jnp.float32), axis=1)
            lab = label.astype(jnp.int32).reshape(-1)
            num_classes = pred.shape[1]
            hits = jnp.float32(0)
            for j in range(min(num_classes, want_k)):
                hits = hits + jnp.sum(
                    order[:, num_classes - 1 - j] == lab).astype(jnp.float32)
            return hits

        return DeviceKernel(sum_fn, lambda label, pred: int(pred.shape[0]),
                            key=("TopKAccuracy", want_k))


@register
class F1(EvalMetric):
    def __init__(self, name="f1", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            pred = pred.asnumpy()
            label = label.asnumpy().astype("int32")
            pred_label = _np.argmax(pred, axis=1)
            check_label_shapes(label, pred_label)
            if len(_np.unique(label)) > 2:
                raise ValueError("F1 currently only supports binary classification.")
            tp = fp = fn = 0.0
            for y_pred, y_true in zip(pred_label, label):
                if y_pred == 1 and y_true == 1:
                    tp += 1.0
                elif y_pred == 1 and y_true == 0:
                    fp += 1.0
                elif y_pred == 0 and y_true == 1:
                    fn += 1.0
            precision = tp / (tp + fp) if tp + fp > 0 else 0.0
            recall = tp / (tp + fn) if tp + fn > 0 else 0.0
            if precision + recall > 0:
                self.sum_metric += 2 * precision * recall / (precision + recall)
            self.num_inst += 1


@register
class Perplexity(EvalMetric):
    def __init__(self, ignore_label, axis=-1, name="perplexity",
                 output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        loss = 0.0
        num = 0
        for label, pred in zip(labels, preds):
            probs = pred.asnumpy()
            lab = label.asnumpy().astype("int32").reshape(-1)
            probs = probs.reshape(-1, probs.shape[-1])
            picked = probs[_np.arange(lab.shape[0]), lab]
            if self.ignore_label is not None:
                ignore = (lab == self.ignore_label)
                num -= int(ignore.sum())
                picked = _np.where(ignore, 1.0, picked)
            loss -= float(_np.sum(_np.log(_np.maximum(1e-10, picked))))
            num += lab.shape[0]
        # accumulate raw NLL and token count; exponentiate only in get()
        # (corpus perplexity, matching the reference metric.py Perplexity)
        self.sum_metric += loss
        self.num_inst += max(1, num)

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, math.exp(self.sum_metric / self.num_inst))


@register
class MAE(EvalMetric):
    def __init__(self, name="mae", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = label.asnumpy()
            pred = pred.asnumpy()
            if len(label.shape) == 1:
                label = label.reshape(label.shape[0], 1)
            self.sum_metric += float(_np.abs(label - pred).mean())
            self.num_inst += 1

    def device_kernel(self):
        import jax.numpy as jnp

        def sum_fn(label, pred):
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            return jnp.mean(jnp.abs(label - pred))

        return DeviceKernel(sum_fn, lambda label, pred: 1, key=("MAE",))


@register
class MSE(EvalMetric):
    def __init__(self, name="mse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = label.asnumpy()
            pred = pred.asnumpy()
            if len(label.shape) == 1:
                label = label.reshape(label.shape[0], 1)
            self.sum_metric += float(((label - pred) ** 2.0).mean())
            self.num_inst += 1

    def device_kernel(self):
        import jax.numpy as jnp

        def sum_fn(label, pred):
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            return jnp.mean(jnp.square(label - pred))

        return DeviceKernel(sum_fn, lambda label, pred: 1, key=("MSE",))


@register
class RMSE(EvalMetric):
    def __init__(self, name="rmse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = label.asnumpy()
            pred = pred.asnumpy()
            if len(label.shape) == 1:
                label = label.reshape(label.shape[0], 1)
            self.sum_metric += float(_np.sqrt(((label - pred) ** 2.0).mean()))
            self.num_inst += 1

    def device_kernel(self):
        import jax.numpy as jnp

        def sum_fn(label, pred):
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            return jnp.sqrt(jnp.mean(jnp.square(label - pred)))

        return DeviceKernel(sum_fn, lambda label, pred: 1, key=("RMSE",))


@register
class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-12, name="cross-entropy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        self.eps = eps

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = label.asnumpy()
            pred = pred.asnumpy()
            label = label.ravel()
            assert label.shape[0] == pred.shape[0]
            prob = pred[_np.arange(label.shape[0]), _np.int64(label)]
            self.sum_metric += float((-_np.log(prob + self.eps)).sum())
            self.num_inst += label.shape[0]

    def device_kernel(self):
        import jax.numpy as jnp
        eps = self.eps

        def sum_fn(label, pred):
            lab = label.reshape(-1).astype(jnp.int32)
            prob = pred[jnp.arange(lab.shape[0]), lab]
            return jnp.sum(-jnp.log(prob + eps))

        return DeviceKernel(sum_fn, lambda label, pred: _shape_size(label),
                            key=("CrossEntropy", eps))


@register
class PearsonCorrelation(EvalMetric):
    def __init__(self, name="pearsonr", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = label.asnumpy()
            pred = pred.asnumpy()
            self.sum_metric += float(
                _np.corrcoef(pred.ravel(), label.ravel())[0, 1])
            self.num_inst += 1


@register
class Loss(EvalMetric):
    def __init__(self, name="loss", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, _, preds):
        for pred in preds:
            self.sum_metric += float(pred.asnumpy().sum())
            self.num_inst += pred.size

    def device_kernel(self):
        import jax.numpy as jnp
        return DeviceKernel(lambda label, pred: jnp.sum(pred),
                            lambda label, pred: _shape_size(pred),
                            needs_label=False, key=("Loss",))


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
    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__
            if name.find("<") != -1:
                name = "custom(%s)" % name
        super().__init__(name, output_names, label_names)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            label = label.asnumpy()
            pred = pred.asnumpy()
            reval = self._feval(label, pred)
            if isinstance(reval, tuple):
                (sum_metric, num_inst) = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1


# ---------------------------------------------------------------- device path
def _shape_size(arr):
    """Host-exact element count from a (possibly device) array's shape."""
    n = 1
    for d in arr.shape:
        n *= int(d)
    return n


class DeviceKernel:
    """One metric's device-resident accumulation recipe.

    ``sum_fn(label, pred)`` computes the metric's per-batch partial sum in
    ``jax.numpy`` (traced under jit, so it dispatches asynchronously and
    never pulls the step's outputs to the host); ``count_fn(label, pred)``
    computes the matching ``num_inst`` increment from shapes alone, on the
    host, so instance counts stay exact integers. Metrics that ignore
    labels (``Loss``) set ``needs_label=False`` and are fed predictions
    only, matching their numpy ``update`` pairing."""

    __slots__ = ("sum_fn", "count_fn", "needs_label", "key")

    def __init__(self, sum_fn, count_fn, needs_label=True, key=None):
        self.sum_fn = sum_fn
        self.count_fn = count_fn
        self.needs_label = needs_label
        # hashable recipe identity: two kernels with the same key compute
        # the same math, so their jitted accumulate programs are shared
        # process-wide instead of recompiled per fit() call
        self.key = key


_ACCUM_FN_CACHE = {}  # kernel-recipe key -> jitted accumulate program


def _flatten_metrics(metric):
    if isinstance(metric, CompositeEvalMetric):
        out = []
        for child in metric.metrics:
            out.extend(_flatten_metrics(child))
        return out
    return [metric]


class DeviceMetricAccum:
    """Device-resident accumulator over an EvalMetric (or composite).

    The reference's ``update_metric`` calls ``asnumpy()`` on every step's
    outputs, which blocks the accelerator behind a host round-trip per
    batch. This accumulator keeps the running partial sums ON DEVICE — one
    jitted program folds a batch's (labels, outputs) into per-metric f32
    scalars, asynchronously — and only ``sync()`` (called by ``fit`` at
    the metric-sync cadence and at epoch end) materializes those scalars
    on the host and folds them into the wrapped metric's
    ``sum_metric``/``num_inst``. Instance counts accumulate host-side as
    exact ints (they are pure shape arithmetic). ``last_snapshot`` holds
    the name/value pairs as of the latest sync so callbacks (Speedometer)
    can read cadence-fresh values without forcing their own device sync.
    """

    def __init__(self, metric, children, kernels):
        self.metric = metric
        self.children = children
        self.kernels = kernels
        self._fn = None
        self.last_snapshot = None
        self._sums = None
        self._counts = None
        self._pending = False
        self._riders = []
        self._zero()

    def add_rider(self, rider):
        """Register a cadence rider: an object whose ``pull()`` returns a
        device tree (or None) and whose ``deliver(host_tree)`` receives
        its host values. Riders share ``sync()``'s SINGLE ``device_get``
        — the seam that lets training-health stats (obs/health.py) reach
        the host with zero additional sync points."""
        if rider not in self._riders:
            self._riders.append(rider)

    def remove_rider(self, rider):
        if rider in self._riders:
            self._riders.remove(rider)

    @classmethod
    def wrap(cls, metric):
        """Build an accumulator for ``metric``, or return None when any
        component lacks a device kernel (custom metrics, F1, Pearson,
        Perplexity keep the numpy path)."""
        if not isinstance(metric, EvalMetric):
            return None
        children = _flatten_metrics(metric)
        if not children:
            return None
        try:
            kernels = [c.device_kernel() for c in children]
        except Exception:
            return None
        if any(k is None for k in kernels):
            return None
        return cls(metric, children, kernels)

    def _zero(self):
        self._sums = [0.0] * len(self.children)
        self._counts = [0] * len(self.children)
        self._pending = False

    def reset(self):
        self._zero()
        self.last_snapshot = None

    def _build_fn(self):
        # one jitted accumulate program per kernel RECIPE, shared process-
        # wide: every fit() call wraps a fresh accumulator, and without
        # this cache each would re-jit (and re-XLA-compile) an identical
        # program — ~100ms burned per fit on a kernel that runs in ~30µs
        cache_key = tuple(k.key for k in self.kernels)
        cacheable = all(k.key is not None for k in self.kernels)
        if cacheable and cache_key in _ACCUM_FN_CACHE:
            return _ACCUM_FN_CACHE[cache_key]
        import jax
        kernels = self.kernels

        def accumulate(sums, labels, preds):
            new = []
            for s, k in zip(sums, kernels):
                pairs = zip(labels, preds) if k.needs_label \
                    else ((None, p) for p in preds)
                for lab, p in pairs:
                    s = s + k.sum_fn(lab, p)
                new.append(s)
            return new

        # route through the executor's build seam so program_build_count,
        # the build listeners and executor_compile_ms{kind=metric_accum}
        # stay consistent with every other traced program in the process
        from .executor import named_jit, record_program_build
        fn = record_program_build(
            "metric_accum", self, named_jit("metric_accum", accumulate))
        if cacheable:
            _ACCUM_FN_CACHE[cache_key] = fn
        return fn

    def update(self, labels, preds):
        """Fold one batch in. ``labels``/``preds`` are device arrays or
        NDArrays; nothing is transferred to the host."""
        labels = [getattr(x, "_data", x) for x in (labels or [])]
        preds = [getattr(x, "_data", x) for x in (preds or [])]
        if any(k.needs_label for k in self.kernels):
            check_label_shapes(labels, preds)
        if self._fn is None:
            self._fn = self._build_fn()
        self._sums = self._fn(self._sums, labels, preds)
        for i, k in enumerate(self.kernels):
            if k.needs_label:
                for lab, p in zip(labels, preds):
                    self._counts[i] += k.count_fn(lab, p)
            else:
                for p in preds:
                    self._counts[i] += k.count_fn(None, p)
        self._pending = True

    def sync(self):
        """The ONLY host round-trip: pull the per-metric scalar sums —
        and every registered rider's pending device tree, in the SAME
        transfer — fold them into the wrapped host metrics, zero the
        device state, and refresh ``last_snapshot``. Returns the
        snapshot pairs."""
        cargo = [(r, r.pull()) for r in self._riders]
        cargo = [(r, t) for r, t in cargo if t is not None]
        if self._pending or cargo:
            import jax
            # mxtpu: allow-sync(sync() IS the cadence sync point — the
            # one intended host round-trip of the device metric path;
            # rider trees (training health) ride the same transfer)
            vals, freight = jax.device_get(
                (self._sums if self._pending else [],
                 [t for _, t in cargo]))
            if self._pending:
                for child, v, n in zip(self.children, vals,
                                       self._counts):
                    child.sum_metric += float(v)
                    child.num_inst += n
                self._zero()
            for (r, _), host in zip(cargo, freight):
                r.deliver(host)
        self.last_snapshot = self.metric.get_name_value()
        return self.last_snapshot
