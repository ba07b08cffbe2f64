"""Device-resident metric accumulation for the fused train step.

The reference fit loop pays one device->host sync per batch to update
``EvalMetric`` (metric.py ``asnumpy``); behind a remote TPU that transfer
dominates the step. Here the accumulation for the common classification
metrics (acc / top_k / ce / nll / loss) is folded INTO the jitted fused
step: a tiny ``(sum f32, count i32)`` carry per metric rides the donated
opt-state, and values move to host only when someone actually reads them
(``Speedometer`` display, epoch-end logging) — one small ``device_get``
of the whole carry per read, not one per batch.

The host ``EvalMetric`` object stays the single source of truth for
presentation: publish overwrites its ``sum_metric``/``num_inst`` and its
own ``get()`` formats the value, so ``Perplexity.get``-style post-
processing and callback code that pokes the metric keep working.

Semantics note: device sums accumulate in f32 in the compiled program;
the host path accumulates in python float64. Counts (acc/top_k) are
integer-valued either way; CE/loss sums agree to f32 rounding. What IS
bitwise-stable is the device path against itself: the same program
sequence at any engine depth produces identical bits, which
tests/test_async_loop.py and tests/test_step_sync_budget.py assert.
"""
from __future__ import annotations

import numpy as _np

from . import metric as _metric

__all__ = ["plan_for", "DeviceMetricPlan", "DeviceMetricProxy"]


def _leaves(metric):
    """Flatten a (possibly composite) metric into leaf EvalMetrics, or
    None if any level is unsupported for device accumulation."""
    if isinstance(metric, _metric.CompositeEvalMetric):
        out = []
        for m in metric.metrics:
            sub = _leaves(m)
            if sub is None:
                return None
            out.extend(sub)
        return out
    return [metric]


def _select_names(m, out_names, label_names):
    """Replicate EvalMetric.update_dict's name selection statically."""
    if m.output_names is not None:
        preds = [n for n in m.output_names if n in out_names]
    else:
        preds = list(out_names)
    if m.label_names is not None:
        labels = [n for n in m.label_names if n in label_names]
    else:
        labels = list(label_names)
    return labels, preds


def _build_update(m, label_keys, pred_keys):
    """Return a pure jnp update ``(sum, count, labels, preds) ->
    (sum, count)`` replicating ``m.update``'s math, or None if ``m`` is
    not device-fusable (stateful F1/MCC, per-batch-mean regression
    metrics, arbitrary CustomMetric fevals)."""
    import jax.numpy as jnp

    f32, i32 = jnp.float32, jnp.int32
    # exact class checks (not isinstance): a subclass may override update
    # with math the closure below would silently misrepresent.
    # NegativeLogLikelihood is the one subclass that changes no math.
    klass = type(m)

    if klass is _metric.Accuracy:
        axis = m.axis

        def upd(s, n, labels, preds):
            for label, pred in zip(labels, preds):
                if pred.ndim > label.ndim:
                    pred = jnp.argmax(pred, axis=axis)
                pred = pred.astype(i32).ravel()
                label = label.astype(i32).ravel()
                s = s + jnp.sum(pred == label).astype(f32)
                n = n + i32(label.size)
            return s, n
        return upd

    if klass is _metric.TopKAccuracy:
        top_k = m.top_k

        def upd(s, n, labels, preds):
            for label, pred in zip(labels, preds):
                label = label.astype(i32)
                idx = jnp.argsort(pred, axis=1)[:, -top_k:]
                hit = (idx == label.reshape(-1, 1)).any(axis=1)
                s = s + jnp.sum(hit).astype(f32)
                n = n + i32(label.shape[0])
            return s, n
        return upd

    if klass in (_metric.CrossEntropy, _metric.NegativeLogLikelihood):
        eps = m.eps

        def upd(s, n, labels, preds):
            for label, pred in zip(labels, preds):
                label = label.ravel().astype(i32)
                pred = pred.astype(f32)
                prob = pred[jnp.arange(label.shape[0]), label]
                s = s + jnp.sum(-jnp.log(prob + eps))
                n = n + i32(label.shape[0])
            return s, n
        return upd

    if klass in (_metric.Loss, _metric.Torch, _metric.Caffe):
        def upd(s, n, labels, preds):
            for pred in preds:
                s = s + jnp.sum(pred).astype(f32)
                n = n + i32(pred.size)
            return s, n
        return upd

    return None


def plan_for(metric, out_names, label_names):
    """Build a :class:`DeviceMetricPlan` for ``metric`` over a module
    with the given output/label names, or None when any leaf metric's
    math cannot be replicated on device (caller falls back to the
    per-batch host path)."""
    leaves = _leaves(metric)
    if leaves is None or not leaves:
        return None
    entries = []
    for m in leaves:
        lab_keys, pred_keys = _select_names(m, out_names, label_names)
        if not pred_keys:
            return None
        needs_labels = not isinstance(m, _metric.Loss)
        if needs_labels and len(lab_keys) != len(pred_keys):
            # host update would zip-truncate or _check-raise; don't guess
            return None
        upd = _build_update(m, lab_keys, pred_keys)
        if upd is None:
            return None
        entries.append((m, lab_keys, pred_keys, upd))
    return DeviceMetricPlan(entries)


class DeviceMetricPlan:
    """Compiled-side metric accumulation: ``update`` is traced inside the
    fused step; ``init_state``/``publish`` bracket it on the host."""

    def __init__(self, entries):
        self._entries = entries

    @property
    def leaves(self):
        return [e[0] for e in self._entries]

    def init_state(self):
        """Fresh zero carry: one (sum f32, count i32) pair per leaf."""
        return tuple((_np.float32(0.0), _np.int32(0))
                     for _ in self._entries)

    def update(self, state, label_dict, pred_dict):
        """Pure traced update: new state from one step's outputs/labels.
        Runs INSIDE the jitted fused step (and its lax.scan body)."""
        new = []
        for (m, lab_keys, pred_keys, upd), (s, n) in zip(self._entries,
                                                         state):
            labels = [label_dict[k] for k in lab_keys if k in label_dict]
            preds = [pred_dict[k] for k in pred_keys if k in pred_dict]
            new.append(upd(s, n, labels, preds))
        return tuple(new)

    def publish(self, host_state):
        """Overwrite each leaf metric's host accumulators from a fetched
        carry (caller did the single device_get)."""
        for (m, _, _, _), (s, n) in zip(self._entries, host_state):
            m.sum_metric = float(s)
            m.num_inst = int(n)


class DeviceMetricProxy:
    """Quacks like the wrapped EvalMetric for fit's loop and callbacks,
    but the accumulation lives on device: reads (``get`` /
    ``get_name_value``) publish the device carry into the wrapped metric
    first; ``update``/``update_dict`` are no-ops (the fused step already
    accumulated this batch); ``reset`` zeros both sides."""

    _device_resident = True

    def __init__(self, module, inner):
        self._module = module
        self.inner = inner
        self._pub_version = -1

    @property
    def name(self):
        return self.inner.name

    @property
    def sum_metric(self):
        self._publish()
        return self.inner.sum_metric

    @property
    def num_inst(self):
        self._publish()
        return self.inner.num_inst

    def _publish(self):
        mod = self._module
        version = getattr(mod, "_device_met_version", 0)
        if version != self._pub_version:
            mod._publish_device_metric()
            self._pub_version = version

    def update(self, labels, preds):
        pass  # accumulated inside the fused step

    def update_dict(self, label, pred):
        pass  # accumulated inside the fused step

    def reset(self):
        self._module._reset_device_metric()
        self.inner.reset()
        self._pub_version = getattr(self._module, "_device_met_version", 0)

    def get(self):
        self._publish()
        return self.inner.get()

    def get_name_value(self):
        self._publish()
        return self.inner.get_name_value()

    def __str__(self):
        return "DeviceMetricProxy(%s)" % self.inner
