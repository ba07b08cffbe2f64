"""Profiler (parity: python/mxnet/profiler.py over src/profiler/ —
chrome://tracing JSON dump, aggregate per-op stats, pause/resume, custom
Task/Frame/Event/Counter/Marker objects).

TPU-native design: the reference hooks each engine OprBlock
(src/engine/threaded_engine.h:80). Here the analogs are the eager invoke
path and the CachedOp jitted runner (one event per op, measured to
completion — profiling forces a sync like MXNET_PROFILER on a stream
does), and on the training path (``Module.fit``, the feed, the engine,
the symbolic Executor) the never-syncing :func:`span`, which is also in
the device-side XLA trace via ``jax.profiler`` whenever one is taken
(``trace_dir``, or any other ``jax.profiler`` session).
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time

import jax

__all__ = ["set_config", "profiler_set_config", "set_state",
           "profiler_set_state", "dump", "dumps", "pause", "resume",
           "Task", "Frame", "Event", "Counter", "Marker",
           "record_host_sync", "sync_counters", "reset_sync_counters",
           "set_sync_trace", "record_counter",
           "span", "spans", "span_totals", "open_self_ns", "reset_spans",
           "Span", "SPAN_RING_LEN"]

_lock = threading.Lock()
CHROME_EVENTS_MAX = 1 << 20


class _ProfilerState:
    def __init__(self):
        self.running = False
        self.filename = "profile.json"
        self.aggregate_stats = False
        self.profile_imperative = True
        self.profile_symbolic = True
        self.profile_memory = False
        self.profile_api = False
        self.trace_dir = None       # jax.profiler XLA trace output
        # chrome trace events since the last dump; bounded, so a profiler
        # left on for a day drops its oldest events, not the process
        self.events = collections.deque(maxlen=CHROME_EVENTS_MAX)
        self.agg = {}               # name -> [count, total_us, min, max]
        # Two clocks, captured together: durations are differences of the
        # MONOTONIC clock (immune to NTP steps/slew mid-span), while event
        # `ts` start fields are anchored to the wall-clock epoch so traces
        # from different processes/hosts line up and telemetry JSONL
        # timestamps are comparable. Producers only ever pass
        # monotonic-relative microseconds; _ts_us converts at append time.
        self.epoch = time.monotonic()
        self.epoch_wall_us = time.time() * 1e6


_state = _ProfilerState()
_active = False  # fast-path flag read by the dispatch hooks


def _maybe_autostart():
    # MXNET_PROFILER_AUTOSTART=1 starts profiling as soon as the profiler
    # module loads (parity: env_var.md:179); called at end of module init.
    from .config import flags
    if flags.profiler_autostart:
        set_state("run")


def _now_us():
    return (time.monotonic() - _state.epoch) * 1e6


def _ts_us(rel_us):
    """Monotonic-relative microseconds -> epoch (wall) timestamp."""
    return _state.epoch_wall_us + rel_us


def set_config(**kwargs):
    """Configure (reference profiler.py set_config :33-151). Accepts
    filename, profile_all, profile_symbolic, profile_imperative,
    profile_memory, profile_api, aggregate_stats, continuous_dump (ignored),
    trace_dir (XLA device trace)."""
    if kwargs.pop("profile_all", False):
        _state.profile_symbolic = True
        _state.profile_imperative = True
        _state.profile_memory = True
        _state.profile_api = True
    _state.filename = kwargs.pop("filename", _state.filename)
    _state.aggregate_stats = kwargs.pop("aggregate_stats",
                                        _state.aggregate_stats)
    _state.profile_symbolic = kwargs.pop("profile_symbolic",
                                         _state.profile_symbolic)
    _state.profile_imperative = kwargs.pop("profile_imperative",
                                           _state.profile_imperative)
    _state.profile_memory = kwargs.pop("profile_memory",
                                       _state.profile_memory)
    _state.profile_api = kwargs.pop("profile_api", _state.profile_api)
    _state.trace_dir = kwargs.pop("trace_dir", _state.trace_dir)
    kwargs.pop("continuous_dump", None)
    if kwargs:
        raise ValueError("unknown profiler config keys: %s"
                         % sorted(kwargs))


profiler_set_config = set_config


def set_state(state="stop"):
    """'run' or 'stop' (reference set_state)."""
    global _active
    assert state in ("run", "stop")
    run = state == "run"
    if run and not _state.running and _state.trace_dir:
        jax.profiler.start_trace(_state.trace_dir)
    if not run and _state.running and _state.trace_dir:
        jax.profiler.stop_trace()
    _state.running = run
    _active = run


profiler_set_state = set_state


def pause():
    global _active
    _active = False


def resume():
    global _active
    _active = _state.running


def record_event(name, cat, start_us, dur_us, tid=0):
    """Internal: called by dispatch hooks."""
    with _lock:
        _state.events.append({"name": name, "cat": cat, "ph": "X",
                              "ts": _ts_us(start_us), "dur": dur_us,
                              "pid": 0, "tid": tid})
        if _state.aggregate_stats:
            ent = _state.agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
            ent[0] += 1
            ent[1] += dur_us
            ent[2] = min(ent[2], dur_us)
            ent[3] = max(ent[3], dur_us)


# ---------------------------------------------------------------------------
# Host-sync accounting. The async training loop's whole premise is that the
# host almost never blocks on the device; these counters make that property
# measurable (and regression-testable, tests/test_step_sync_budget.py)
# without a chip. Kinds:
#   d2h        — a device->host transfer (asnumpy / batched metric fetch /
#                device-metric publish); the involuntary sync the budget
#                test bounds
#   wait       — an explicit blocking wait (wait_to_read / waitall)
#   depth_wait — the engine depth controller throttling dispatch (expected
#                back-pressure, not a regression)
# Unlike the event hooks these are always on: a dict bump per sync is noise
# next to the sync itself.
# ---------------------------------------------------------------------------

_SYNC_KINDS = ("d2h", "wait", "depth_wait")
_sync_counts = {k: 0 for k in _SYNC_KINDS}
_sync_counts["d2h_bytes"] = 0
_sync_trace = None


def record_host_sync(kind, nbytes=0):
    """Count one host sync of ``kind`` (see module comment). Called by
    NDArray.asnumpy, the engine wait paths, the batched metric fetch and
    the device-metric publish."""
    with _lock:
        _sync_counts[kind] = _sync_counts.get(kind, 0) + 1
        if kind == "d2h" and nbytes:
            _sync_counts["d2h_bytes"] += nbytes
    cb = _sync_trace
    if cb is not None:
        import traceback
        # drop this frame and the caller's record_host_sync call site noise
        cb(kind, nbytes, traceback.extract_stack()[:-1])
    if _active:
        with _lock:
            _state.events.append({"name": "host_sync:%s" % kind, "ph": "i",
                                  "ts": _ts_us(_now_us()), "pid": 0,
                                  "tid": 0, "s": "t"})


def sync_counters():
    """Snapshot of the host-sync counters: {d2h, wait, depth_wait,
    d2h_bytes, total} (total excludes depth_wait — throttling is the
    loop working as designed, not a sync the user's code forced)."""
    with _lock:
        out = dict(_sync_counts)
    out["total"] = out.get("d2h", 0) + out.get("wait", 0)
    return out


def reset_sync_counters():
    with _lock:
        for k in list(_sync_counts):
            _sync_counts[k] = 0


def set_sync_trace(trace=None):
    """Install a callback fired on EVERY host sync: ``trace(kind, nbytes,
    stack)`` with ``stack`` a ``traceback.StackSummary``. ``trace=True``
    installs a default printer (one block per sync with the Python stack —
    the ``tools/diagnose_step_hlo.py --sync-trace`` backend); ``None``
    uninstalls. Returns the previous callback."""
    global _sync_trace
    if trace is True:
        def trace(kind, nbytes, stack):
            import sys
            lines = ["host sync [%s]%s at:" % (
                kind, " %d bytes" % nbytes if nbytes else "")]
            lines += ["  %s:%d in %s" % (f.filename, f.lineno, f.name)
                      for f in stack
                      if "/profiler.py" not in f.filename]
            print("\n".join(lines), file=sys.stderr, flush=True)
    prev = _sync_trace
    _sync_trace = trace
    return prev


def record_counter(name, value):
    """Stateless chrome-trace counter sample (ph='C') — a gauge track on
    the trace timeline. Used by the serving runtime for queue depth;
    unlike the stateful :class:`Counter` object, callers that already
    own the value just stamp it."""
    with _lock:
        _state.events.append({"name": name, "ph": "C",
                              "ts": _ts_us(_now_us()), "pid": 0,
                              "args": {name: value}})


class _OpTimer:
    """Context manager used by the CachedOp hook: like the eager per-op
    path it measures to completion, so it blocks on the outputs. The
    training path (``Module.fit``, ``Executor``) uses :func:`span`, which
    never blocks."""

    __slots__ = ("name", "cat", "arrays", "t0")

    def __init__(self, name, cat, arrays=None):
        self.name = name
        self.cat = cat
        self.arrays = arrays

    def __enter__(self):
        self.t0 = _now_us()
        return self

    def __exit__(self, *exc):
        if self.arrays:
            for a in self.arrays():
                if hasattr(a, "block_until_ready"):
                    try:
                        a.block_until_ready()
                    except Exception:
                        pass
        record_event(self.name, self.cat, self.t0, _now_us() - self.t0)


# ---------------------------------------------------------------------------
# Spans: the one primitive every layer boundary of the training path uses
# (docs/observability.md "Spans"). Always on, like the sync census: a span
# costs microseconds next to the work it brackets, the benchmark's command
# line can turn nothing on, and an operator wants the last minute after a
# stall. A span NEVER blocks on the device: with the profiler on, ``fit``
# runs the same program as with it off.
#
# Three sinks, one stamp:
#   * a bounded ring of finished spans and per-name totals, stamped with
#     ``time.time_ns()`` (``spans()``, ``span_totals()``);
#   * ``jax.profiler.TraceAnnotation`` of the same name whenever a device
#     trace is being taken, so the span is in the xplane beside the ops;
#   * the chrome JSON while ``set_state("run")`` is on.
# ---------------------------------------------------------------------------

SPAN_RING_LEN = 65536

Span = collections.namedtuple(
    "Span", "name start_ns end_ns parent step tid counts")

_ring = collections.deque(maxlen=SPAN_RING_LEN)
_span_totals = {}           # name -> [count, total_ns, self_ns]
_span_tls = threading.local()
_trace_enabled = jax.profiler.TraceAnnotation.is_enabled


def _span_stack():
    try:
        return _span_tls.stack
    except AttributeError:
        _span_tls.stack = []
        return _span_tls.stack


def _finish_span(name, t0, t1, parent, step, tid, counts, child_ns):
    _ring.append((name, t0, t1, parent, step, tid, counts))
    with _lock:
        ent = _span_totals.get(name)
        if ent is None:
            ent = _span_totals[name] = [0, 0, 0]
        ent[0] += 1
        ent[1] += t1 - t0
        ent[2] += t1 - t0 - child_ns
    if _active:
        # relative to the wall anchor, so that ``ts`` is the ring's stamp
        record_event(name, "span", t0 / 1e3 - _state.epoch_wall_us,
                     (t1 - t0) / 1e3, tid=tid)


class span:
    """``with profiler.span("mx/fit/dispatch", step=n, steps=1):`` — one
    timed region of the training path.

    ``step`` is the ``global_step`` of the dispatch the work belongs to,
    the identifier the spans of one step share (the feeder thread's
    included); left out, it is the enclosing span's. ``counts`` are what
    the region moved (``bytes``, ``steps``); :meth:`add` sets one that is
    only known inside. The parent is the enclosing span of the same
    thread, by name: spans of one thread nest, so the intervals say the
    rest."""

    __slots__ = ("name", "step", "counts", "_t0", "_child_ns", "_ann",
                 "_stack")

    def __init__(self, name, step=None, **counts):
        self.name = name
        self.step = step
        self.counts = counts

    def add(self, **counts):
        self.counts.update(counts)

    def __enter__(self):
        stack = self._stack = _span_stack()
        if self.step is None and stack:
            self.step = stack[-1].step
        stack.append(self)
        self._child_ns = 0
        self._ann = None
        if _trace_enabled():
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.time_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = self._stack
        stack.pop()
        parent = None
        if stack:
            parent = stack[-1].name
            stack[-1]._child_ns += t1 - self._t0
        _finish_span(self.name, self._t0, t1, parent, self.step,
                     threading.get_ident(), self.counts or None,
                     self._child_ns)


def spans(since_ns=None):
    """The ring's content, oldest first: :class:`Span` tuples ``(name,
    start_ns, end_ns, parent, step, tid, counts)`` on the clock of
    ``time.time_ns()``; with ``since_ns`` only those that ended later."""
    out = [Span._make(e) for e in list(_ring)]
    if since_ns is not None:
        out = [e for e in out if e.end_ns > since_ns]
    return out


def span_totals():
    """name -> (count, total_ns, self_ns) over every span finished since
    the process started (or :func:`reset_spans`), whatever the ring has
    dropped. Self time is what no child span covers."""
    with _lock:
        return {k: tuple(v) for k, v in _span_totals.items()}


def open_self_ns(name):
    """Self time so far of the calling thread's innermost OPEN span
    ``name``: for a long-lived span (``mx/fit/epoch``) whose total is
    only booked at its end. 0 where the thread has none open."""
    now = time.time_ns()
    stack = _span_stack()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i].name == name:
            inner = now - stack[i + 1]._t0 if i + 1 < len(stack) else 0
            return now - stack[i]._t0 - stack[i]._child_ns - inner
    return 0


def reset_spans():
    _ring.clear()
    with _lock:
        _span_totals.clear()


_COMPILE_EVENTS = "/jax/core/compile/"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _on_jax_duration(event, seconds, **_kwargs):
    """``jax.monitoring`` listener: every phase of a compilation (trace,
    lowering, backend compile or cache read) becomes an ``mx/compile``
    span that ends now, under the span and the ``step`` that caused it,
    so an operator sees WHICH step recompiled. ``compile/count`` counts
    backend compiles, ``compile/seconds`` every phase."""
    if not event.startswith(_COMPILE_EVENTS):
        return
    t1 = time.time_ns()
    dur = int(seconds * 1e9)
    stack = _span_stack()
    parent = step = None
    if stack:
        # not booked as the parent's child time: the phases of one
        # compilation nest (a trace inside a trace) and would count twice
        parent, step = stack[-1].name, stack[-1].step
    _finish_span("mx/compile", t1 - dur, t1, parent, step,
                 threading.get_ident(),
                 {"event": event[len(_COMPILE_EVENTS):],
                  "seconds": seconds}, 0)
    from . import telemetry
    if event == _BACKEND_COMPILE:
        telemetry.counter("compile/count",
                          "XLA backend compilations (or compile-cache "
                          "reads) since the process started").inc()
    telemetry.counter("compile/seconds",
                      "seconds spent tracing, lowering and compiling "
                      "jitted programs").inc(seconds)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def is_active(kind="imperative"):
    if not _active:
        return False
    if kind == "imperative":
        return _state.profile_imperative
    if kind == "symbolic":
        return _state.profile_symbolic
    return True


def op_timer(name, cat="operator", result_arrays=None):
    return _OpTimer(name, cat, result_arrays)


def dump(finished=True, profile_process="worker"):
    """Write the chrome://tracing JSON file."""
    with _lock:
        trace = {
            "traceEvents": [
                {"name": "process_name", "ph": "M", "pid": 0,
                 "args": {"name": "mxnet_tpu worker"}}]
            + list(_state.events),
            "displayTimeUnit": "ms",
        }
        with open(_state.filename, "w") as f:
            json.dump(trace, f)
        if finished:
            _state.events.clear()
    return _state.filename


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Aggregate stats as text (reference MXAggregateProfileStatsPrintEx)."""
    with _lock:
        lines = ["Profile Statistics:",
                 "%-40s %10s %14s %14s %14s %14s" % (
                     "Name", "Calls", "Total(us)", "Avg(us)", "Min(us)",
                     "Max(us)")]
        if sort_by == "avg":
            def sort_key(kv):
                return kv[1][1] / max(kv[1][0], 1)
        else:
            key_idx = {"total": 1, "min": 2, "max": 3,
                       "count": 0}.get(sort_by, 1)

            def sort_key(kv):
                return kv[1][key_idx]
        items = sorted(_state.agg.items(), key=sort_key,
                       reverse=not ascending)
        for name, (count, total, mn, mx) in items:
            lines.append("%-40s %10d %14.1f %14.1f %14.1f %14.1f" % (
                name[:40], count, total, total / max(count, 1), mn, mx))
        if reset:
            _state.agg = {}
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# User-defined profiling objects (reference profiler.py Task/Frame/Event/...)
# ---------------------------------------------------------------------------

class _Span:
    def __init__(self, name, cat):
        self.name = name
        self._cat = cat
        self._t0 = None

    def start(self):
        self._t0 = _now_us()

    def stop(self):
        if self._t0 is None:
            return
        record_event(self.name, self._cat, self._t0, _now_us() - self._t0)
        self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()


class Task(_Span):
    def __init__(self, domain=None, name="task"):
        super().__init__(name, "task")


class Frame(_Span):
    def __init__(self, domain=None, name="frame"):
        super().__init__(name, "frame")


class Event(_Span):
    def __init__(self, name="event"):
        super().__init__(name, "event")


class Counter:
    def __init__(self, domain=None, name="counter", value=0):
        self.name = name
        self._value = value

    def set_value(self, value):
        self._value = value
        with _lock:
            _state.events.append({"name": self.name, "ph": "C",
                                  "ts": _ts_us(_now_us()), "pid": 0,
                                  "args": {self.name: value}})

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    def __init__(self, domain=None, name="marker"):
        self.name = name

    def mark(self, scope="process"):
        with _lock:
            _state.events.append({"name": self.name, "ph": "i",
                                  "ts": _ts_us(_now_us()), "pid": 0,
                                  "tid": 0,
                                  "s": "p" if scope == "process" else "t"})


_maybe_autostart()
