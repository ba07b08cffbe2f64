"""Online inference server over one AOT artifact.

The paper's deployment story ends at an engine file; this is the piece
that turns one into a service: a dynamic MICRO-BATCHER coalesces
concurrent single requests into padded device batches under a
max-batch/max-latency policy (TVM/TensorRT serving practice: AOT
engines only pay off when a runtime amortizes them across callers),
admission control bounds the queue and rejects early, and a graceful
drain finishes every admitted request on shutdown.

Host-sync discipline (PR 3): the request path performs exactly ONE
device->host transfer per response batch — padding, execution and the
slice back to real rows all happen on device; the single
``jax.device_get`` of the sliced outputs is counted via
``profiler.record_host_sync("d2h")``.

In-process use (tests, bench, embedding in an existing event loop)::

    server = Server("model.mxtpu", buckets=(1, 8, 32))
    pending = server.submit(data=x)        # never blocks; may raise
    out = pending.result(timeout=1.0)      # tuple of np arrays
    server.close(drain=True)

``tools/serve.py`` wraps this in the HTTP/JSON front end
(:mod:`mxnet_tpu.serve.http`).
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict

import numpy as _np

import jax

from ..base import MXNetError
from ..config import flags
from ..parallel import faultinject
from .. import profiler
from ..serving import CompiledModel, GenerateModel, load_artifact
from .admission import (AdmissionQueue, DeadlineExceeded, Request,
                        ServerClosed)
from ..embed.serve import RecommendEngine, RecommendModel
from .decode import GenerateConfig, GenerateSession
from .engine_cache import check_buckets, pick_bucket
from .metrics import ServeMetrics

__all__ = ["Server", "ServeConfig"]


class ServeConfig:
    """Serving knobs; every default comes from the MXNET_SERVE_* flags."""

    def __init__(self, buckets=None, batch_timeout_ms=None,
                 queue_depth=None, timeout_ms=None, cache_engines=None,
                 warmup=None, drain_timeout_s=None):
        self.buckets = buckets    # None -> artifact-appropriate default
        self.batch_timeout_ms = (flags.serve_batch_timeout_ms
                                 if batch_timeout_ms is None
                                 else float(batch_timeout_ms))
        self.queue_depth = (flags.serve_queue_depth if queue_depth is None
                            else int(queue_depth))
        self.timeout_ms = (flags.serve_timeout_ms if timeout_ms is None
                           else float(timeout_ms))
        self.cache_engines = cache_engines
        self.warmup = warmup
        self.drain_timeout_s = (flags.serve_drain_timeout_s
                                if drain_timeout_s is None
                                else float(drain_timeout_s))


class Server:
    """Dynamic micro-batching server over a :class:`CompiledModel`.

    ``model`` is a loaded CompiledModel or an artifact path.
    ``auto_start=False`` leaves the batcher thread unstarted — requests
    queue until the test/driver calls :meth:`run_once` (deterministic
    coalescing for tests) or :meth:`start`.
    """

    def __init__(self, model, config=None, auto_start=True, quantized=None,
                 draft=None, **overrides):
        if not isinstance(model, (CompiledModel, GenerateModel,
                                  RecommendModel, RecommendEngine)):
            model = load_artifact(model)
        if isinstance(model, (RecommendModel, RecommendEngine)):
            if quantized is not None or draft is not None:
                raise MXNetError(
                    "Server: quantized=/draft= do not apply to "
                    "recommend artifacts")
            self._init_recommend(model, config, auto_start, overrides)
            return
        if isinstance(model, GenerateModel):
            if quantized is not None:
                raise MXNetError("Server: quantized= is a predict-mode "
                                 "option; generate artifacts do not take "
                                 "a precision sibling")
            # generate artifact: the continuous-batching decode engine
            # replaces the micro-batcher wholesale; Server proxies
            # lifecycle + metrics so the HTTP front end / CLI are shared
            if config is None:
                config = GenerateConfig(**overrides)
            elif overrides:
                raise MXNetError("Server: pass either config or kwargs, "
                                 "not both")
            if not isinstance(config, GenerateConfig):
                raise MXNetError(
                    "Server: a generate artifact takes a GenerateConfig "
                    "(continuous-batching knobs), not ServeConfig")
            if draft is not None:
                # --draft wiring: 'auto' speculates iff the artifact
                # bundles draft modules, 'on' requires them, 'off'
                # forces plain one-token decode
                if draft not in ("auto", "on", "off"):
                    raise MXNetError("Server: draft= must be 'auto', "
                                     "'on' or 'off' (got %r)" % (draft,))
                config.speculative = {"auto": None, "on": True,
                                      "off": False}[draft]
            self.mode = "generate"
            self.model = model
            self.config = config
            self._warming = False
            self._warm_thread = None
            self.session = GenerateSession(model, config=config,
                                           auto_start=auto_start)
            self.metrics_ = self.session.metrics_
            return
        self.mode = "predict"
        if draft is not None:
            raise MXNetError("Server: draft= is a generate-mode option; "
                             "predict artifacts have no draft model")
        self.session = None
        self._warming = False
        self._warm_thread = None
        if config is None:
            config = ServeConfig(**overrides)
        elif overrides:
            raise MXNetError("Server: pass either config or kwargs, "
                             "not both")
        self.model = model
        self.config = config
        self.buckets = check_buckets(config.buckets, model)
        if (model.engine_cache is None
                or model.buckets != self.buckets):
            model.set_buckets(self.buckets,
                              cache_engines=config.cache_engines,
                              warmup=config.warmup)
        self._cache = model.engine_cache
        if quantized is not None:
            # attach the int8 sibling artifact: same model, quantized by
            # tools/quantize_model.py, served side-by-side per bucket
            if not isinstance(quantized, CompiledModel):
                quantized = load_artifact(quantized)
            if not isinstance(quantized, CompiledModel):
                raise MXNetError(
                    "Server: quantized= must be a predict artifact")
            if not quantized.quantized:
                raise MXNetError(
                    "Server: quantized= artifact is not format_version 4 "
                    "(run tools/quantize_model.py to produce one)")
            if "int8" not in self._cache.dtypes:  # cache may be reused
                self._cache.add_model(quantized, "int8")
        self.metrics_ = ServeMetrics()
        self._queue = AdmissionQueue(
            config.queue_depth,
            retry_after_fn=lambda q: self.metrics_.estimate_drain_s(
                q.pending_rows() if hasattr(q, "pending_rows") else 0))
        self._thread = None
        self._closing = False
        self._closed = threading.Event()
        if auto_start:
            self.start()

    def _init_recommend(self, model, config, auto_start, overrides):
        """Recommend mode: the micro-batcher machinery (queue, window,
        drain, metrics) is shared with predict, but requests are ragged
        id lists billed in GATHER units and dispatch runs the embed
        subsystem's cache-backed engine instead of an AOT executable."""
        self.mode = "recommend"
        self.session = None
        self._warming = False
        self._warm_thread = None
        if config is None:
            config = ServeConfig(**overrides)
        elif overrides:
            raise MXNetError("Server: pass either config or kwargs, "
                             "not both")
        if isinstance(model, RecommendModel):
            model = model.engine()
        self.engine = model
        self.model = model.model
        self.config = config
        self.buckets = model.buckets
        self.metrics_ = ServeMetrics()
        # the queue bills gathers, not requests: retry-after is pending
        # gather units times the per-gather roofline, and the cost cap
        # (MXNET_SERVE_MAX_GATHERS) rejects on the same unit
        self._queue = AdmissionQueue(
            config.queue_depth,
            retry_after_fn=lambda q: (q.pending_units()
                                      * self.engine.gather_unit_s()),
            max_units=flags.serve_max_gathers)
        self._thread = None
        self._closing = False
        self._closed = threading.Event()
        if auto_start:
            self.start()

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        if self.mode == "generate":
            self.session.start()
            return self
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(target=self._loop,
                                            name="mxtpu-serve-batcher",
                                            daemon=True)
            self._thread.start()
        return self

    def warmup_async(self):
        """Compile/warm the serving path in a background thread while
        the HTTP listener is already accepting: the replica registers
        with the fleet immediately, reports not-ready (reason
        "warming") until compiles finish, then flips ready — so a
        router never sends traffic into a cold compile. Predict mode
        builds + warms every (bucket, dtype) engine; generate mode
        warms prefill/decode/commit then starts the scheduler."""
        if self._warm_thread is not None and self._warm_thread.is_alive():
            return self._warm_thread
        self._warming = True

        def _warm():
            try:
                if self.mode == "generate":
                    try:
                        self.session.warmup()
                    finally:
                        self.session.start()
                elif self.mode == "recommend":
                    self.start()
                    self.engine.warm()
                else:
                    self.start()   # batcher can queue while we compile
                    self._cache.warmup = True
                    for dtype in list(self._cache.dtypes):
                        for b in self.buckets:
                            self._cache.engine(b, dtype)
            except Exception:
                # a warmup failure must not wedge the replica in
                # "warming" forever; the first real request surfaces it
                pass
            finally:
                self._warming = False

        self._warm_thread = threading.Thread(target=_warm,
                                             name="mxtpu-serve-warmup",
                                             daemon=True)
        self._warm_thread.start()
        return self._warm_thread

    @property
    def warming(self):
        return self._warming

    def not_ready_reason(self):
        """None when this server should receive traffic; else the
        reason string the readiness probe / fleet heartbeat reports:
        "closed", "draining", or "warming". Liveness != readiness — a
        draining or warming replica is alive but must be out of
        rotation (see /readyz in serve/http.py)."""
        if self.closed:
            return "closed"
        if self.draining:
            return "draining"
        if self._warming:
            return "warming"
        return None

    @property
    def ready(self):
        return self.not_ready_reason() is None

    @property
    def draining(self):
        if self.mode == "generate":
            return self.session.draining
        return self._queue.closed and not self._closed.is_set()

    @property
    def closed(self):
        if self.mode == "generate":
            return self.session.closed
        return self._closed.is_set()

    def close(self, drain=True, timeout=None):
        """Shut down. ``drain=True`` (graceful): stop admitting, finish
        every queued request, then return. ``drain=False``: evict queued
        requests, failing them with ServerClosed (counted as dropped).
        Generate mode: drain is BOUNDED — each live sequence gets at
        most ``drain_tokens`` more tokens, then is evicted with a
        resumable cursor (see GenerateSession.close)."""
        if self.mode == "generate":
            return self.session.close(drain=drain, timeout=timeout)
        self._closing = True
        evicted = self._queue.close(drain=drain)
        for r in evicted:
            r._fail(ServerClosed("serve: server closed before this "
                                 "request was dispatched"))
        if evicted:
            self.metrics_.note_drop(len(evicted))
        if drain:
            budget = (self.config.drain_timeout_s if timeout is None
                      else timeout)
            if self._thread is not None and self._thread.is_alive():
                self._thread.join(budget)
                if self._thread.is_alive():
                    raise MXNetError(
                        "serve: drain did not finish within %.1fs (%d "
                        "requests still queued)"
                        % (budget, self._queue.pending_count()))
            else:
                # no batcher thread (auto_start=False): drain inline
                t_end = time.monotonic() + budget
                while self._queue.pending_count():
                    if time.monotonic() > t_end:
                        raise MXNetError(
                            "serve: inline drain did not finish within "
                            "%.1fs" % budget)
                    self.run_once(block=False)
        self._closed.set()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if not self.closed:
            self.close(drain=True)

    # -- request path -------------------------------------------------------
    def _require_mode(self, mode, what):
        if self.mode != mode:
            other = {
                "generate": "submit_generate()/generate() or "
                            "POST /v1/generate",
                "recommend": "submit_recommend()/recommend() or "
                             "POST /v1/recommend",
            }.get(self.mode, "submit()/predict() or POST /v1/predict")
            raise MXNetError(
                "Server.%s: this server holds a %s artifact; use %s"
                % (what, self.mode, other))

    def submit_generate(self, prompt, max_new_tokens=None,
                        temperature=0.0, seed=0, timeout_ms=None):
        """Generate-mode admit (never blocks); see
        :meth:`GenerateSession.submit`."""
        self._require_mode("generate", "submit_generate")
        return self.session.submit(prompt, max_new_tokens=max_new_tokens,
                                   temperature=temperature, seed=seed,
                                   timeout_ms=timeout_ms)

    def generate(self, prompt, **kw):
        """Blocking generate-mode convenience: submit + result."""
        self._require_mode("generate", "generate")
        return self.session.generate(prompt, **kw)

    def _prepare(self, data, kwdata):
        if data and kwdata:
            raise MXNetError("Server.submit: pass inputs positionally or "
                             "by name, not both")
        if kwdata:
            names = self.model.input_names
            extra = sorted(set(kwdata) - set(names))
            missing = sorted(set(names) - set(kwdata))
            if extra or missing:
                raise MXNetError(
                    "Server.submit: artifact inputs are %s%s%s"
                    % (names,
                       ("; missing %s" % missing) if missing else "",
                       ("; unexpected %s" % extra) if extra else ""))
            data = [kwdata[n] for n in names]
        arrs = self.model._check_inputs(list(data))
        rows = int(arrs[0].shape[0]) if arrs[0].ndim else 1
        if rows > self.buckets[-1]:
            raise MXNetError(
                "Server.submit: request batch of %d rows exceeds the "
                "largest bucket %d; split the request or serve with "
                "larger buckets" % (rows, self.buckets[-1]))
        return arrs, rows

    def submit(self, *data, timeout_ms=None, dtype=None, **kwdata):
        """Admit one request; never blocks. Returns a :class:`Request`
        whose ``.result()`` blocks for the response. ``dtype`` routes to
        an attached precision variant ("f32"/"int8"; default the
        primary artifact). Raises ServerBusy (queue full), ServerClosed,
        or MXNetError (validation)."""
        self._require_mode("predict", "submit")
        if dtype is not None and dtype not in self._cache.dtypes:
            raise MXNetError(
                "Server.submit: no %r engines on this server; available "
                "dtypes are %s (pass quantized= at construction to "
                "attach an int8 artifact)"
                % (dtype, list(self._cache.dtypes)))
        arrs, rows = self._prepare(data, kwdata)
        if timeout_ms is None:
            timeout_ms = self.config.timeout_ms
        deadline = (time.monotonic() + timeout_ms / 1e3
                    if timeout_ms and timeout_ms > 0 else None)
        req = Request(tuple(arrs), rows, deadline,
                      dtype=dtype or self._cache.primary_dtype)
        try:
            self._queue.submit(req)
        except ServerClosed:
            raise
        except Exception:
            self.metrics_.note_reject()
            raise
        # counted only when ADMITTED, so completed+expired == submitted
        # is a per-server drain invariant (the soak test's zero-dropped
        # check)
        self.metrics_.note_submit(rows)
        self.metrics_.set_queue_depth(self._queue.pending_count())
        return req

    def predict(self, *data, timeout_ms=None, dtype=None, **kwdata):
        """Blocking convenience: submit + result."""
        req = self.submit(*data, timeout_ms=timeout_ms, dtype=dtype,
                          **kwdata)
        budget = (None if req.deadline is None
                  else max(0.001, req.deadline - time.monotonic()) + 1.0)
        return req.result(timeout=budget)

    def submit_recommend(self, ids, timeout_ms=None):
        """Admit one recommend request (ragged id list); never blocks.
        The request is billed in GATHER units — ``len(ids)`` after the
        engine's ``max_ids`` truncation — so the admission cost cap
        (``MXNET_SERVE_MAX_GATHERS``) and the retry-after hint charge
        the device work a ragged request really costs."""
        self._require_mode("recommend", "submit_recommend")
        arr = _np.asarray(list(ids), dtype=_np.int64).reshape(-1)
        gathers = max(1, min(arr.size, self.engine.max_ids))
        if timeout_ms is None:
            timeout_ms = self.config.timeout_ms
        deadline = (time.monotonic() + timeout_ms / 1e3
                    if timeout_ms and timeout_ms > 0 else None)
        req = Request((arr,), 1, deadline, units=gathers)
        try:
            self._queue.submit(req)
        except ServerClosed:
            raise
        except Exception:
            self.metrics_.note_reject()
            raise
        self.metrics_.note_submit(1)
        self.metrics_.set_queue_depth(self._queue.pending_count())
        return req

    def recommend(self, ids, timeout_ms=None):
        """Blocking convenience: submit_recommend + result. Returns
        (scores, item_ids) host arrays of length ``k``."""
        req = self.submit_recommend(ids, timeout_ms=timeout_ms)
        budget = (None if req.deadline is None
                  else max(0.001, req.deadline - time.monotonic()) + 1.0)
        return req.result(timeout=budget)

    # -- batcher ------------------------------------------------------------
    def run_once(self, block=True):
        """One coalescing round: take a window's worth of requests, drop
        the expired, dispatch one padded bucket batch, distribute the
        results. Returns the number of requests taken (0 = nothing to
        do). Public so tests and auto_start=False drivers can step the
        batcher deterministically. Generate mode: one scheduler round
        (evict/admit/decode-step)."""
        if self.mode == "generate":
            return self.session.run_round()
        reqs = self._queue.take(self.buckets[-1],
                                self.config.batch_timeout_ms / 1e3,
                                block=block)
        self.metrics_.set_queue_depth(self._queue.pending_count())
        if not reqs:
            return 0
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                self.metrics_.note_expire()
                r._fail(DeadlineExceeded(
                    "serve: deadline passed %.1fms before dispatch"
                    % ((now - r.deadline) * 1e3)))
            else:
                live.append(r)
        if not live:
            return len(reqs)
        if self.mode == "recommend":
            self._dispatch_recommend(live)
            return len(reqs)
        # one padded device batch PER DTYPE GROUP (f32 and int8 requests
        # coexist in a window but run on different engines); each group
        # keeps the one-d2h-per-device-batch discipline
        primary = self._cache.primary_dtype
        groups = OrderedDict()
        for r in live:
            groups.setdefault(r.dtype or primary, []).append(r)
        for dtype, group in groups.items():
            self._dispatch_group(dtype, group)
        return len(reqs)

    def _dispatch_group(self, dtype, live):
        rows = sum(r.rows for r in live)
        bucket = pick_bucket(self.buckets, rows)
        # take() caps at the largest bucket, so bucket is never None
        try:
            # deterministic kill/raise point for fleet fault drills:
            # fires per DISPATCHED batch (warmup bypasses it), so
            # "kill@serve=predict_batch:skip=N" dies at real batch N+1
            faultinject.fire("serve", op="predict_batch", bucket=bucket)
            import jax.numpy as jnp
            if len(live) == 1:
                stacked = list(live[0].arrays)
            else:
                stacked = [jnp.concatenate([r.arrays[i] for r in live])
                           for i in range(len(self.model.input_names))]
            t0 = time.perf_counter()
            outs = self._cache.run(bucket, stacked, rows, dtype=dtype)
            # ONE d2h for the whole response batch (PR 3 discipline)
            host = jax.device_get(outs)
            sim_s = float(flags.serve_sim_batch_s)
            if sim_s > 0.0:
                # stand-in device occupancy for accelerator-less drill
                # hosts; inside the timed window so the cost model and
                # heartbeat load see it as real batch time
                time.sleep(sim_s)
            exec_ms = (time.perf_counter() - t0) * 1e3
        except Exception as e:
            self.metrics_.note_error(len(live))
            err = e if isinstance(e, MXNetError) else MXNetError(str(e))
            for r in live:
                r._fail(err)
            return
        nbytes = sum(getattr(h, "nbytes", 0) for h in host)
        profiler.record_host_sync("d2h", nbytes)
        self.metrics_.note_batch(bucket, rows, bucket - rows, exec_ms,
                                 dtype=dtype)
        t_done = time.monotonic()
        off = 0
        for r in live:
            r.bucket = bucket
            r._complete(tuple(_np.asarray(h[off:off + r.rows])
                              for h in host))
            off += r.rows
            self.metrics_.note_request_done(
                bucket, (t_done - r.t_submit) * 1e3, dtype=dtype)

    def _dispatch_recommend(self, live):
        rows = len(live)
        bucket = pick_bucket(self.buckets, rows)
        try:
            faultinject.fire("serve", op="recommend_batch", bucket=bucket)
            t0 = time.perf_counter()
            # the engine does the plan/upload, ONE device dispatch, and
            # ONE d2h (+ record_host_sync) for the whole batch
            scores, items = self.engine.recommend_batch(
                [r.arrays[0] for r in live], bucket=bucket)
            exec_ms = (time.perf_counter() - t0) * 1e3
        except Exception as e:
            self.metrics_.note_error(len(live))
            err = e if isinstance(e, MXNetError) else MXNetError(str(e))
            for r in live:
                r._fail(err)
            return
        self.metrics_.note_batch(bucket, rows, bucket - rows, exec_ms)
        t_done = time.monotonic()
        for j, r in enumerate(live):
            r.bucket = bucket
            r._complete((scores[j], items[j]))
            self.metrics_.note_request_done(
                bucket, (t_done - r.t_submit) * 1e3)

    def _loop(self):
        while True:
            try:
                self.run_once(block=True)
            except Exception:
                # a batch failure already failed its requests; a bug in
                # the loop itself must not silently kill serving
                if self._queue.closed:
                    break
                time.sleep(0.01)
                continue
            if self._queue.closed and self._queue.pending_count() == 0:
                break

    # -- cost model ---------------------------------------------------------
    def estimate_row_s(self):
        """Estimated seconds per served row: observed device throughput
        once the server has history, else the perfmodel memory-roofline
        floor over one row's input bytes — the same capability tables
        decode's admission control uses, so the fleet router's
        least-loaded policy scores every replica with ONE cost model,
        not a router-side heuristic."""
        self._require_mode("predict", "estimate_row_s")
        obs = self.metrics_.throughput_rows_per_s()
        if obs > 0:
            return 1.0 / obs
        from .. import perfmodel
        bytes_row = 0
        for s in self.model.meta["inputs"]:
            n = 1
            for d in s["shape"][1:]:
                n *= int(d)
            bytes_row += n * _np.dtype(s["dtype"]).itemsize
        return max(perfmodel.roofline_seconds(
            0.0, 2.0 * bytes_row, perfmodel.modelled_device_kind()), 1e-7)

    def load_status(self):
        """The live half of a fleet heartbeat: readiness (+reason) and
        the perfmodel-derived load summary (``load_s`` = estimated
        seconds of queued work, ``unit_s`` = marginal seconds per
        additional request) the router's least-loaded policy scores
        on."""
        reason = self.not_ready_reason()
        if self.mode == "generate":
            sess = self.session
            load = {
                "load_s": round(sess._retry_after(), 6),
                "unit_s": round(sess.estimate_step_s()
                                / max(1, sess.spec.max_slots), 9),
                "queue_depth": len(sess._pending),
                # memory pressure: queue-seconds can look calm while the
                # KV page pool is nearly exhausted (long contexts) — the
                # autoscaler scales out on this before admission stalls
                "kv_page_occupancy": round(sess.cache.occupancy(), 4),
                "p99_ms": sess.metrics_.ttft_p99(),
            }
        elif self.mode == "recommend":
            # billed in gather units: load_s = pending gathers x the
            # per-gather roofline (see RecommendEngine.gather_unit_s)
            unit = self.engine.gather_unit_s()
            load = {
                "load_s": round(self._queue.pending_units() * unit, 6),
                "unit_s": round(unit, 9),
                "queue_depth": self._queue.pending_count(),
                "p99_ms": self.metrics_.latency_p99(),
            }
        else:
            pending = self._queue.pending_count()
            unit = self.estimate_row_s()
            load = {
                "load_s": round(pending * unit, 6),
                "unit_s": round(unit, 9),
                "queue_depth": pending,
                "p99_ms": self.metrics_.latency_p99(),
            }
        # the deadline the p99 is judged against (request timeout):
        # p99/deadline > headroom means tail latency is about to turn
        # into expiries — scale out even when mean pressure looks fine
        timeout_ms = getattr(self.config, "timeout_ms", None)
        if timeout_ms:
            load["deadline_ms"] = float(timeout_ms)
        return {"ready": reason is None, "reason": reason, "load": load}

    # -- observability ------------------------------------------------------
    def metrics(self):
        """JSON-able snapshot: request counters, queue depth, per-bucket
        latency percentiles / occupancy / padding waste, engine-cache
        stats. The ``/metrics`` endpoint body. Generate mode: decode
        counters, TTFT/TPOT percentiles, slot/page occupancy."""
        if self.mode == "generate":
            snap = self.session.metrics()
            snap["mode"] = "generate"
            snap["ready"] = self.ready
            snap["not_ready_reason"] = self.not_ready_reason()
            return snap
        if self.mode == "recommend":
            snap = self.metrics_.snapshot()
            snap["mode"] = "recommend"
            snap["embed"] = self.engine.stats()
            snap["buckets_configured"] = list(self.buckets)
            snap["status"] = ("closed" if self.closed
                              else "draining" if self.draining else "ok")
            snap["ready"] = self.ready
            snap["not_ready_reason"] = self.not_ready_reason()
            return snap
        snap = self.metrics_.snapshot(engine_stats=self._cache.stats())
        snap["mode"] = "predict"
        snap["buckets_configured"] = list(self.buckets)
        snap["status"] = ("closed" if self.closed
                          else "draining" if self.draining else "ok")
        snap["ready"] = self.ready
        snap["not_ready_reason"] = self.not_ready_reason()
        return snap
