"""Run-wide telemetry: one registry, many producers, many exporters.

The pieces (each its own module, all stdlib-only and import-light):

* ``registry`` — thread-safe named counters/gauges/histograms that
  every subsystem publishes into; ``snapshot()`` is the JSON-able view.
* ``prom`` — Prometheus text exposition of the registry plus a strict
  parser (``tools/serve_loadgen.py`` scrape-asserts with it).
* ``exporters`` — training-side HTTP listener (``MXNET_TELEMETRY_PORT``)
  and the per-window JSONL snapshot stream.
* ``recorder`` — bounded flight recorder dumped to a postmortem JSON on
  SIGTERM / unhandled exception / faultinject kill.
* ``federate`` — merges per-replica expositions under ``replica=<id>``
  labels for the fleet router's single ``/metrics`` scrape.

The one entry point producers on the training path use is
``publish_window``: called by ``Module.fit`` every 16 steps and at an
epoch's end with values it already holds on the host, so telemetry adds
**zero** device→host syncs to the step loop (pinned by
tests/test_step_sync_budget.py). Serving, the kernel tier, checkpoint,
and fault injection publish into the same registry from their own code.
See docs/observability.md for the operator-facing tour.
"""
from __future__ import annotations

import time

from mxnet_tpu.telemetry import exporters, federate, prom, recorder
from mxnet_tpu.telemetry.prom import parse_exposition
from mxnet_tpu.telemetry.recorder import FlightRecorder, flight_recorder
from mxnet_tpu.telemetry.registry import (
    Counter, Gauge, Histogram, Registry, counter, default_registry, gauge,
    histogram, run_info, set_run_info, snapshot,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "FlightRecorder",
    "counter", "gauge", "histogram", "snapshot", "default_registry",
    "set_run_info", "run_info", "flight_recorder", "prometheus_text",
    "parse_exposition", "publish_window", "count_h2d", "exporters",
    "federate", "prom",
    "recorder",
]

_jsonl = None


def prometheus_text(registry=None):
    return prom.exposition(registry)


def _ensure_exporters():
    global _jsonl
    exporters.maybe_start_http()
    recorder.maybe_install_handlers()
    if _jsonl is None:
        path = exporters.jsonl_path()
        if path:
            _jsonl = exporters.JsonlWriter(path)
    return _jsonl


def _live_mfu(steps, window_s):
    """Host-side live MFU from run-scoped flops — no device traffic.
    Returns None until the caller (bench.py does) has supplied
    ``set_run_info(flops_per_step=...)``."""
    info = run_info()
    flops = info.get("flops_per_step")
    if not flops or window_s <= 0:
        return None
    from mxnet_tpu import perfmodel
    kind = info.get("device_kind") or perfmodel.modelled_device_kind()
    return perfmodel.mfu(float(flops), window_s / steps, kind)


def _stall_attribution(steps, window_s, stall_ms):
    """Input-bound vs compute-bound for the window, host-side only.

    With run-scoped flops available (``set_run_info(flops_per_step=...)``)
    the perfmodel roofline gives the window's compute FLOOR; stall time
    eating most of the slack above that floor means the chip was waiting
    on data. Without flops, fall back to a plain stall-fraction
    threshold. Returns (stall_frac, input_bound)."""
    stall_s = max(0.0, float(stall_ms)) / 1e3
    frac = min(1.0, stall_s / window_s)
    info = run_info()
    flops = info.get("flops_per_step")
    if flops:
        from mxnet_tpu import perfmodel
        kind = info.get("device_kind") or perfmodel.modelled_device_kind()
        floor = steps * perfmodel.roofline_seconds(float(flops), 0.0, kind)
        slack = max(0.0, window_s - floor)
        return frac, bool(stall_s > 0.5 * slack and frac > 0.02)
    return frac, bool(frac > 0.10)


def count_h2d(nbytes):
    """Book ``nbytes`` of input copied to the device, at the place where
    the copy is made (``Executor.prepare_input``): the ``data/h2d_bytes``
    counter. Returns ``nbytes``."""
    counter("data/h2d_bytes",
            "host->device input bytes copied for the step loop, counted "
            "where the copy is made (an input already on the executor's "
            "device counts nothing)").inc(nbytes)
    return int(nbytes)


def publish_window(*, steps, window_s, examples=None, engine_depth=None,
                   global_step=None, source="train", ddp=None,
                   embed=None, data=None):
    """Publish one window's worth of training telemetry (fit: 16 steps).

    Everything passed in (and everything read here) is already host
    memory: wall-clock seconds, host-side batch shapes, the in-flight
    dispatch count, and ``profiler.sync_counters()``. Nothing touches a
    device array, so the PR-3 sync budget is untouched. Returns the
    step record (also pushed into the flight recorder and, when
    enabled, the JSONL stream).

    ``ddp`` (optional) is the Module's host-held bucketed-all-reduce
    summary for the window — ``{"buckets", "comm_bytes", "overlap_ms"}``
    from the GradReducer's STATIC plan (parallel/ddp.py), never a device
    read; with a sparse bucket kind it also carries
    ``sparse_comm_bytes`` (coalesced unique-row exchange) so dashboards
    can track the sparse-vs-densified win.

    ``embed`` (optional) is the HotRowCache's host-held counter view
    for the window — ``{"hit_rate", "spill_bytes"}`` where
    ``spill_bytes`` is the WINDOW'S DELTA (the cache's counter is
    cumulative; subtract the previous window's value before passing).
    embed/cache.py keeps every counter on host, so this too is zero
    extra device traffic.

    ``data`` (optional) is fit's host-held input-pipeline summary for
    the window — ``{"input_stall_ms", "queue_depth"}`` (stall = the
    window's ``mx/fit/next`` spans: wall-clock the loop spent blocked on
    the iterator; queue_depth from the iterator's bounded prefetch
    queue). Publishes ``data/*`` gauges plus the perfmodel-backed
    input-bound/compute-bound attribution (``data/stall_frac``,
    ``data/input_bound`` — docs/data.md). ``data/h2d_bytes`` is counted
    where the copy is made (:func:`count_h2d`); a caller that copies
    elsewhere may still add its own under ``"h2d_bytes"``.

    Also republishes ``profiler.span_totals()`` as cumulative
    ``host_span/<name>_ms`` gauges (the ``mx/`` prefix dropped) beside
    ``host_sync/*``, and ``host_span/fit/unspanned_ms``: the time of
    ``mx/fit/epoch`` under no child span, what the spans do not cover.
    """
    from mxnet_tpu import profiler

    steps = max(1, int(steps))
    window_s = max(float(window_s), 1e-9)
    step_ms = window_s * 1e3 / steps

    gauge("train/step_time_ms",
          "mean wall-clock ms per step over the last window").set(step_ms)
    counter("train/steps_total", "optimizer steps dispatched").inc(steps)
    gauge("train/window_steps",
          "steps in the last telemetry window").set(steps)
    if examples is not None and examples > 0:
        gauge("train/examples_per_s",
              "training throughput over the last window").set(
                  examples / window_s)
        counter("train/examples_total", "examples consumed").inc(examples)
    if engine_depth is not None:
        gauge("train/engine_depth",
              "in-flight dispatch windows (DepthController)").set(
                  engine_depth)
    if global_step is not None:
        gauge("train/global_step", "global optimizer step").set(global_step)

    mfu = _live_mfu(steps, window_s)
    if mfu is not None:
        gauge("train/mfu",
              "live model-flops utilization vs device peak").set(mfu)

    if ddp:
        counter("ddp/comm_bytes",
                "gradient bytes exchanged by the bucketed all-reduce").inc(
                    ddp.get("comm_bytes", 0))
        gauge("ddp/buckets",
              "gradient buckets per step (fused collectives)").set(
                  ddp.get("buckets", 0))
        gauge("ddp/overlap_ms",
              "model-estimated collective ms hidden under backward").set(
                  ddp.get("overlap_ms", 0.0))
        if "bucket_bytes_model" in ddp:
            gauge("ddp/bucket_bytes_model",
                  "interconnect-table bucket size the GradReducer "
                  "planned against (choose_bucket_bytes)").set(
                      ddp.get("bucket_bytes_model", 0))
        if "sparse_comm_bytes" in ddp:
            counter("ddp/sparse_comm_bytes",
                    "coalesced sparse-gradient bytes exchanged (touched "
                    "rows only, vs the densified table)").inc(
                        ddp.get("sparse_comm_bytes", 0))

    if embed:
        gauge("embed/cache_hit_rate",
              "hot-row cache hit rate over the cache's lifetime "
              "(host-held counters, no device read)").set(
                  embed.get("hit_rate", 0.0))
        counter("embed/spill_bytes",
                "bytes spilled from the device hot-row cache to the "
                "host store (dirty evictions)").inc(
                    embed.get("spill_bytes", 0))

    if data:
        stall_ms = float(data.get("input_stall_ms", 0.0))
        gauge("data/input_stall_ms",
              "wall-clock ms the fit loop spent blocked on the input "
              "pipeline over the last window (host-held timer)").set(
                  stall_ms)
        if examples is not None and examples > 0:
            gauge("data/examples_per_s",
                  "input-pipeline delivery rate over the last window "
                  "(examples the loop consumed / window seconds)").set(
                      examples / window_s)
        if "queue_depth" in data:
            gauge("data/queue_depth",
                  "prefetch queue occupancy at window end "
                  "(0 with stalls = producer-bound)").set(
                      data.get("queue_depth", 0))
        if data.get("h2d_bytes"):
            count_h2d(data["h2d_bytes"])
        frac, input_bound = _stall_attribution(steps, window_s, stall_ms)
        gauge("data/stall_frac",
              "fraction of the window spent input-stalled").set(frac)
        gauge("data/input_bound",
              "1 when the perfmodel attribution says the window was "
              "input-bound (stall ate the roofline slack), else 0").set(
                  1.0 if input_bound else 0.0)

    sync = profiler.sync_counters()
    for key in ("d2h", "wait", "depth_wait", "d2h_bytes", "total"):
        if key in sync:
            gauge("host_sync/%s" % key,
                  "cumulative host-sync census (profiler)").set(sync[key])

    totals = profiler.span_totals()
    for name, (_count, total_ns, _self_ns) in totals.items():
        gauge("host_span/%s_ms" % name.removeprefix("mx/"),
              "cumulative host ms inside the profiler span of that name "
              "(docs/observability.md, Spans)").set(total_ns / 1e6)
    gauge("host_span/fit/unspanned_ms",
          "cumulative host ms of mx/fit/epoch under no child span: what "
          "the spans inside fit do not cover").set(
              (totals.get("mx/fit/epoch", (0, 0, 0))[2]
               + profiler.open_self_ns("mx/fit/epoch")) / 1e6)

    record = {"source": source, "global_step": global_step,
              "steps": steps, "window_s": window_s, "step_ms": step_ms,
              "examples": examples, "engine_depth": engine_depth,
              "mfu": mfu, "sync": dict(sync)}
    if ddp:
        record["ddp"] = dict(ddp)
    if embed:
        record["embed"] = dict(embed)
    if data:
        record["data"] = dict(data)

    jsonl = _ensure_exporters()
    rec = flight_recorder()
    rec.record_step(record)
    rec.note_snapshot(snapshot())
    if jsonl is not None:
        jsonl.write({"ts": time.time(), "global_step": global_step,
                     "registry": snapshot()})
    return record
