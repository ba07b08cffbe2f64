"""Run-wide metric registry: named counters, gauges, and histograms.

Every subsystem that keeps numbers (training loop, kernel tier, tuner
cache, serve metrics, checkpoint/fault machinery) publishes into ONE
process-wide registry so exporters — the Prometheus text endpoint, the
JSONL snapshot stream, the flight recorder — see a single coherent view.

Design constraints, in order:

1. **Host-only and sync-free.** Publishing a sample is a dict update
   under a per-metric lock; nothing here may touch a device array or
   trigger a d2h transfer. Producers are responsible for only publishing
   values they already hold on the host (the training loop samples at
   16-step window boundaries for exactly this reason — see
   ``telemetry.publish_window`` and tests/test_step_sync_budget.py).
2. **Thread-safe.** Serve worker threads, the micro-batcher, the
   checkpoint save thread, and the training loop all publish
   concurrently; counter increments are never lost (tested in
   tests/test_telemetry.py).
3. **Single source of truth.** Metrics that used to be emitted straight
   into the chrome trace via ``profiler.record_counter`` go through the
   registry instead (mxlint MXL506 enforces this); the registry mirrors
   label-free gauges back into the trace so existing counter tracks
   (e.g. ``serve/queue_depth``) keep rendering.

Metric names are ``subsystem/metric_name`` (slash-namespaced, matching
the chrome-trace convention); the Prometheus exporter sanitizes them to
``mxtpu_subsystem_metric_name``. Labels are passed as keyword arguments:
``counter("kernel/dispatch_total").inc(1, op="bn_act")``.
"""
from __future__ import annotations

import math
import threading

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry",
    "default_registry", "counter", "gauge", "histogram",
    "snapshot", "set_run_info", "run_info",
]


def _label_key(labels):
    return tuple(sorted(labels.items()))


def _mirror_to_trace(name, value):
    """Keep the chrome-trace counter track alive for label-free series
    (test_serve pins ``serve/queue_depth`` rendering as a track)."""
    try:
        from mxnet_tpu import profiler
        if profiler.is_active("telemetry"):
            profiler.record_counter(name, value)
    except Exception:
        pass


class _Metric:
    kind = "untyped"

    def __init__(self, name, help=""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()

    def samples(self):
        raise NotImplementedError


class Counter(_Metric):
    """Monotonically increasing count (per label set)."""

    kind = "counter"

    def __init__(self, name, help=""):
        super().__init__(name, help)
        self._values = {}

    def inc(self, value=1.0, **labels):
        if value < 0:
            raise ValueError("counter %s cannot decrease (inc %r)"
                             % (self.name, value))
        key = _label_key(labels)
        with self._lock:
            new = self._values.get(key, 0.0) + value
            self._values[key] = new
        if not labels:
            _mirror_to_trace(self.name, new)

    def value(self, **labels):
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def samples(self):
        with self._lock:
            items = list(self._values.items())
        return [(dict(k), v) for k, v in items]


class Gauge(_Metric):
    """Point-in-time value (per label set); may go up or down."""

    kind = "gauge"

    def __init__(self, name, help=""):
        super().__init__(name, help)
        self._values = {}

    def set(self, value, **labels):
        key = _label_key(labels)
        with self._lock:
            self._values[key] = float(value)
        if not labels:
            _mirror_to_trace(self.name, float(value))

    def add(self, delta, **labels):
        key = _label_key(labels)
        with self._lock:
            new = self._values.get(key, 0.0) + delta
            self._values[key] = new
        if not labels:
            _mirror_to_trace(self.name, new)

    def value(self, **labels):
        with self._lock:
            return self._values.get(_label_key(labels))

    def samples(self):
        with self._lock:
            items = list(self._values.items())
        return [(dict(k), v) for k, v in items]


# Latency-flavoured default edges (ms); +inf is implicit.
DEFAULT_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                   500.0, 1000.0, 2500.0, 5000.0)


class Histogram(_Metric):
    """Cumulative-bucket histogram (Prometheus semantics)."""

    kind = "histogram"

    def __init__(self, name, help="", buckets=None):
        super().__init__(name, help)
        edges = tuple(sorted(float(b) for b in (buckets or DEFAULT_BUCKETS)))
        if not edges:
            raise ValueError("histogram %s needs at least one bucket edge"
                             % name)
        self.buckets = edges
        self._counts = {}   # label key -> [per-edge counts..., +inf count]
        self._sums = {}
        self._totals = {}

    def observe(self, value, **labels):
        key = _label_key(labels)
        value = float(value)
        with self._lock:
            counts = self._counts.setdefault(
                key, [0] * (len(self.buckets) + 1))
            for i, edge in enumerate(self.buckets):
                if value <= edge:
                    counts[i] += 1
                    break
            else:
                counts[-1] += 1
            self._sums[key] = self._sums.get(key, 0.0) + value
            self._totals[key] = self._totals.get(key, 0) + 1

    def samples(self):
        """[(labels, {"buckets": {le: cumulative}, "sum": s, "count": n})]"""
        with self._lock:
            keys = list(self._counts)
            out = []
            for key in keys:
                counts = self._counts[key]
                cum, cumulative = 0, {}
                for edge, c in zip(self.buckets, counts):
                    cum += c
                    cumulative[edge] = cum
                cumulative[math.inf] = cum + counts[-1]
                out.append((dict(key), {
                    "buckets": cumulative,
                    "sum": self._sums[key],
                    "count": self._totals[key],
                }))
        return out


class Registry:
    """Named metric store. ``counter/gauge/histogram`` are get-or-create
    and type-checked: two subsystems asking for the same series name get
    the same object, and a kind clash is a programming error."""

    def __init__(self):
        self._lock = threading.RLock()
        self._metrics = {}
        self._run_info = {}

    def _get_or_create(self, cls, name, help, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help=help, **kw)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(
                    "telemetry series %r already registered as %s, not %s"
                    % (name, m.kind, cls.kind))
            elif help and not m.help:
                m.help = help
            return m

    def counter(self, name, help=""):
        return self._get_or_create(Counter, name, help)

    def gauge(self, name, help=""):
        return self._get_or_create(Gauge, name, help)

    def histogram(self, name, help="", buckets=None):
        return self._get_or_create(Histogram, name, help, buckets=buckets)

    def get(self, name):
        with self._lock:
            return self._metrics.get(name)

    def collect(self):
        """Stable-ordered list of live metric objects (for exporters)."""
        with self._lock:
            return [self._metrics[k] for k in sorted(self._metrics)]

    def snapshot(self):
        """JSON-able view of every series: the payload embedded in bench
        output, the JSONL stream, and flight-recorder postmortems."""
        out = {}
        for m in self.collect():
            if m.kind == "histogram":
                samples = [
                    {"labels": lb,
                     "buckets": {("+Inf" if math.isinf(le) else repr(le)): c
                                 for le, c in s["buckets"].items()},
                     "sum": s["sum"], "count": s["count"]}
                    for lb, s in m.samples()]
            else:
                samples = [{"labels": lb, "value": v}
                           for lb, v in m.samples()]
            out[m.name] = {"type": m.kind, "help": m.help,
                           "samples": samples}
        return out

    # -- run-scoped static facts (model flops, device kind, batch size):
    #    set once by whoever knows them (bench.py, fit) so derived
    #    gauges like live MFU can be computed host-side.
    def set_run_info(self, **kw):
        with self._lock:
            self._run_info.update(
                {k: v for k, v in kw.items() if v is not None})

    def run_info(self):
        with self._lock:
            return dict(self._run_info)

    def reset(self):
        """Tests only: drop every series and the run info."""
        with self._lock:
            self._metrics.clear()
            self._run_info.clear()


_default = Registry()


def default_registry():
    return _default


def counter(name, help=""):
    return _default.counter(name, help)


def gauge(name, help=""):
    return _default.gauge(name, help)


def histogram(name, help="", buckets=None):
    return _default.histogram(name, help, buckets=buckets)


def snapshot():
    return _default.snapshot()


def set_run_info(**kw):
    _default.set_run_info(**kw)


def run_info():
    return _default.run_info()
