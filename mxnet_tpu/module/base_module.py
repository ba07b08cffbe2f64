"""BaseModule: the training-loop surface.

Parity: ``python/mxnet/module/base_module.py`` (reference — ``fit`` loop
:500-560, ``score``, ``predict``, ``forward_backward``). The subclass Module
does the executor work; fit() here is intentionally the same epoch loop shape
as the reference so reference-era training scripts port unchanged.
"""
from __future__ import annotations

import logging
import time

import numpy as _np

from .. import metric as _metric
from ..model import BatchEndParam
from ..base import MXNetError


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None

    def _op_counters(self):
        """{gauge: (value, help)} of the counters that ops carry on the
        device; Module overrides."""
        return {}

    def _ddp_stats(self, n_steps):
        """Per-window DDP telemetry payload for publish_window; Module
        overrides when the bucketed all-reduce path is engaged."""
        return None

    # ------------------------------------------------------------ high level
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def _fit_step(self, data_batch):
        """One fit-loop iteration: fwd+bwd then update. Subclasses may fuse
        the pair atomically (Module donates buffers to XLA here — in-place
        param/opt updates — which the public forward_backward()/update()
        contract, with its deferred commit, cannot allow)."""
        self.forward_backward(data_batch)
        self.update()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                for cb in _as_list(batch_end_callback):
                    cb(BatchEndParam(epoch=epoch, nbatch=nbatch,
                                     eval_metric=eval_metric, locals=locals()))
            actual_num_batch += 1
        if score_end_callback:
            for cb in _as_list(score_end_callback):
                cb(BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                 eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - (pad or 0)]
                       for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False,
                sparse_row_id_fn=None):
        from ..ndarray import ndarray as _nd
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - (pad or 0)].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise MXNetError("Cannot merge batches: different number "
                                     "of outputs per batch")
            output_list2 = [
                _nd.array(_np.concatenate(
                    [out[i].asnumpy() for out in output_list]))
                for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None,
            kvstore="local", optimizer="sgd",
            optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None, steps_per_dispatch=None,
            checkpoint=None):
        """Epoch loop (reference base_module.py:410-560).

        Every batch is one fused step, dispatched as one XLA program. The
        chip is idle under 0.07 % of a traced span in every benchmark cell
        at one step a program (ledger, PR 28), so nothing groups steps:
        ``steps_per_dispatch`` accepts ``None`` and ``1`` and selects
        nothing (ROADMAP.md D16); any other value raises ``ValueError``.

        Completed dispatches are NOT waited on synchronously: a
        :class:`~mxnet_tpu.engine.DepthController`
        (``flags.engine_depth``, default 2) bounds the in-flight queue,
        and the loop blocks only at checkpoint snapshots, epoch
        boundaries, and metric reads.

        ``checkpoint``: a :class:`mxnet_tpu.checkpoint.CheckpointManager`
        enabling elastic training — full training state (params, optimizer
        trajectory, RNG chain, loop position) is snapshotted every
        ``save_every`` steps, and when the launcher sets
        ``MXNET_RESUME_DIR`` after a worker death, fit() restores the
        newest snapshot all ranks share and continues bitwise-identically
        to an uninterrupted run (see docs/fault_tolerance.md). Defaults to
        an env-constructed manager when ``MXNET_CHECKPOINT_DIR`` or
        ``MXNET_RESUME_DIR`` is set."""
        from .. import initializer as _init
        assert num_epoch is not None, "please specify number of epochs"
        if initializer is None:
            initializer = _init.Uniform(0.01)

        # refuse BEFORE any side effect (bind/install_monitor/
        # init_optimizer are not undone by the raise)
        if steps_per_dispatch is not None:
            if steps_per_dispatch < 1:
                raise ValueError("steps_per_dispatch must be >= 1, got %r"
                                 % (steps_per_dispatch,))
            if steps_per_dispatch > 1:
                raise ValueError(
                    "steps_per_dispatch=%r: the K-step scan is gone, fit "
                    "dispatches one fused step a program; pass 1 or leave "
                    "it out" % (steps_per_dispatch,))

        # every layer boundary below runs under a profiler.span (the
        # table is in docs/observability.md "Spans"); none of them syncs
        from .. import profiler as _profiler
        with _profiler.span("mx/fit/bind"):
            self.bind(data_shapes=train_data.provide_data,
                      label_shapes=train_data.provide_label,
                      for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        with _profiler.span("mx/fit/init_params"):
            self.init_params(initializer=initializer, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init)
        with _profiler.span("mx/fit/init_optimizer"):
            self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                                optimizer_params=optimizer_params)
        if eval_metric is None and eval_data is not None and \
                validation_metric is None:
            raise ValueError(
                "eval_metric=None (benchmark mode) needs an explicit "
                "validation_metric when eval_data is given")
        if validation_metric is None:
            validation_metric = eval_metric
        # eval_metric=None: benchmark mode — no metric updates, so no
        # device->host sync per batch (the reference's --benchmark 1 path
        # still pays this)
        if eval_metric is not None and \
                not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        # ---- elastic checkpointing (docs/fault_tolerance.md) ----
        from .. import checkpoint as _ckpt
        from ..parallel import faultinject as _fi
        ckpt = checkpoint if checkpoint is not None \
            else _ckpt.CheckpointManager.from_env()
        global_step = 0
        resume_epoch, resume_nbatch = begin_epoch, 0
        resume_cursor = None
        if ckpt is not None and _ckpt.CheckpointManager.should_resume():
            state, manifest = ckpt.restore_latest()
            mine = manifest["step"] if manifest is not None else -1
            common = self._common_resume_step(mine)
            if common >= 0 and common != mine:
                # cross-rank snapshot skew (a rank died between its own
                # save and a peer's): roll back to the newest step EVERY
                # rank has, or the post-resume allreduces would silently
                # mix different weight histories
                state, manifest = ckpt.restore(step=common)
            if common >= 0 and state is not None:
                resume_cursor = _ckpt.cursor_from_state(state)
                _ckpt.restore_module(self, state)
                global_step = manifest["step"]
                resume_epoch = manifest["epoch"]
                resume_nbatch = manifest["nbatch"]
                self.logger.info(
                    "resumed from checkpoint step %d (epoch %d, batch %d) "
                    "in %s", global_step, resume_epoch, resume_nbatch,
                    ckpt.directory)
            else:
                self.logger.warning(
                    "MXNET_RESUME_DIR set but no common valid checkpoint "
                    "across ranks — starting from scratch")
        meta = {"kvstore": kvstore if isinstance(kvstore, str)
                else getattr(kvstore, "type", None)}

        # ---- async loop setup (docs/perf.md "Async fit loop") ----
        # 1. fold the metric into the device step when its math allows:
        #    per-batch update_metric becomes a no-op on the proxy and the
        #    (sum, count) carry moves to host only at reads
        if hasattr(self, "_engage_device_metric"):
            if eval_metric is not None and monitor is None:
                proxy = self._engage_device_metric(eval_metric)
                if proxy is not None:
                    eval_metric = proxy
            else:
                self._detach_device_metric()
        # 2. dispatch without blocking; bound the in-flight queue so the
        #    host can't run unboundedly ahead of the chip
        from ..engine import DepthController
        depth_ctl = DepthController()

        # 3. run-wide telemetry (docs/observability.md): publish step
        #    time / throughput / engine depth / sync census / span totals
        #    every 16 steps, using ONLY values this frame
        #    already holds on the host (wall clock, batch shapes, the
        #    in-flight dispatch count) — zero extra device->host syncs,
        #    pinned by tests/test_step_sync_budget.py
        from .. import telemetry as _telemetry
        _telem_t0 = time.monotonic()
        _telem_every = 16
        _telem_acc = [0, 0]          # (steps, examples) of the open window

        # 4. streaming-tier window stats (docs/data.md): input stall (the
        #    mx/fit/next spans: time the loop blocked on the iterator)
        #    and feed-queue depth — host-held values, zero extra
        #    device->host syncs (tests/test_step_sync_budget.py).
        #    data/h2d_bytes is counted where the copy is made
        #    (Executor.prepare_input).
        def _next_ns():
            return _profiler.span_totals().get("mx/fit/next", (0, 0, 0))[1]

        _stall_mark = [_next_ns()]
        qd_fn = getattr(train_data, "queue_depth", None)
        has_cursor = hasattr(train_data, "get_cursor") \
            and hasattr(train_data, "seek")
        data_cursor = [None]         # last CONSUMED batch's cursor

        def _timed_next(it, step):
            # blocking time on the iterator IS the loop's input stall
            with _profiler.span("mx/fit/next", step=step):
                return next(it)

        def _batch_examples(b):
            try:
                return int(b.data[0].shape[0])   # host metadata, no sync
            except Exception:
                return 0

        def _telem_window(n_steps, examples, gstep):
            # the tracing's own cost, visible like the rest
            nonlocal _telem_t0
            with _profiler.span("mx/fit/publish", step=gstep):
                now = time.monotonic()
                next_ns = _next_ns()
                data = {"input_stall_ms":
                        (next_ns - _stall_mark[0]) / 1e6}
                _stall_mark[0] = next_ns
                if qd_fn is not None:
                    try:
                        data["queue_depth"] = qd_fn()
                    except Exception:
                        pass
                _telemetry.publish_window(
                    steps=n_steps, window_s=now - _telem_t0,
                    examples=examples or None,
                    engine_depth=len(depth_ctl._inflight),
                    global_step=gstep,
                    ddp=self._ddp_stats(n_steps),
                    data=data)
                _telem_t0 = now

        def _snap_state():
            # quiesce first: a snapshot must capture a settled trajectory,
            # not buffers a still-running dispatch is about to donate away
            depth_ctl.quiesce()
            state = _ckpt.module_state(self)
            if data_cursor[0] is not None:
                # the iterator's consumed-position cursor rides the
                # snapshot so resume can seek instead of replaying batches
                state[_ckpt.DATA_CURSOR_KEY] = \
                    _ckpt.encode_cursor(data_cursor[0])
            return state

        for epoch in range(max(begin_epoch, resume_epoch), num_epoch):
            with _profiler.span("mx/fit/epoch", epoch=epoch):
                tic = time.time()
                if eval_metric is not None:
                    eval_metric.reset()
                nbatch = 0
                data_iter = iter(train_data)
                if ckpt is not None and epoch == resume_epoch \
                        and resume_nbatch:
                    if resume_cursor is not None and has_cursor:
                        # cursor seek: O(1) re-position to the exact
                        # (epoch, shard, offset) the snapshot had consumed,
                        # instead of the O(nbatch) batch-skip replay below
                        train_data.seek(resume_cursor)
                        data_iter = iter(train_data)
                        data_cursor[0] = dict(resume_cursor)
                    else:
                        # re-align the (deterministic, unshuffled-or-reseeded)
                        # iterator with the checkpointed loop position: the
                        # first resume_nbatch batches were consumed before the
                        # snapshot
                        for _ in range(resume_nbatch):
                            try:
                                next(data_iter)
                            except StopIteration:
                                break
                    nbatch = resume_nbatch
                end_of_batch = False
                try:
                    next_data_batch = _timed_next(data_iter, global_step)
                except StopIteration:
                    # resume landed exactly on this epoch's end
                    end_of_batch = True
                while not end_of_batch:
                    data_batch = next_data_batch
                    if monitor is not None:
                        monitor.tic()
                    # global_step steps have completed (and, on the save
                    # grid, been checkpointed) — "kill@step=N" dies HERE,
                    # so the supervised restart resumes at exactly step N
                    _fi.fire("step", step=global_step)
                    # Python, input placement (mx/feed/h2d inside) and
                    # the enqueue of the step program; never a wait
                    with _profiler.span("mx/fit/dispatch",
                                        step=global_step, steps=1):
                        self._fit_step(data_batch)
                    depth_ctl.admit(self._dispatch_handles(),
                                    step=global_step)
                    # metric BEFORE prefetch/prepare (reference
                    # base_module.py:528-545): prepare() may switch the
                    # bucketing module to the NEXT batch's bucket, whose
                    # executor has no outputs yet
                    if eval_metric is not None:
                        with _profiler.span("mx/fit/metric",
                                            step=global_step):
                            self.update_metric(eval_metric,
                                               data_batch.label)
                    if has_cursor:
                        # capture BEFORE prefetching the next batch: the
                        # cursor must reflect batches CONSUMED, not the
                        # loop's read-ahead
                        data_cursor[0] = train_data.get_cursor()
                    try:
                        next_data_batch = _timed_next(data_iter,
                                                      global_step + 1)
                        self.prepare(next_data_batch,
                                     sparse_row_id_fn=sparse_row_id_fn)
                    except StopIteration:
                        end_of_batch = True
                    if monitor is not None:
                        monitor.toc_print()
                    if batch_end_callback is not None:
                        with _profiler.span("mx/fit/callbacks",
                                            step=global_step):
                            for cb in _as_list(batch_end_callback):
                                cb(BatchEndParam(
                                    epoch=epoch, nbatch=nbatch,
                                    eval_metric=eval_metric,
                                    locals=locals()))
                    nbatch += 1
                    global_step += 1
                    _telem_acc[0] += 1
                    _telem_acc[1] += _batch_examples(data_batch)
                    if _telem_acc[0] >= _telem_every:
                        _telem_window(_telem_acc[0], _telem_acc[1],
                                      global_step)
                        _telem_acc = [0, 0]
                    if ckpt is not None:
                        with _profiler.span("mx/fit/checkpoint",
                                            step=global_step):
                            ckpt.maybe_save(_snap_state, global_step,
                                            epoch=epoch, nbatch=nbatch,
                                            meta=meta)
                # epoch boundary: drain in-flight dispatches before the host
                # reads metrics/params (one explicit wait, not one per step)
                depth_ctl.quiesce()
                if _telem_acc[0]:    # flush the partial window
                    _telem_window(_telem_acc[0], _telem_acc[1], global_step)
                    _telem_acc = [0, 0]
                # what ops count rides the step's own state on the device:
                # read here, where the host has just waited
                for name, (val, text) in self._op_counters().items():
                    _telemetry.gauge(name, text).set(val)
                for name, val in (eval_metric.get_name_value()
                                  if eval_metric is not None else []):
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                                 time.time() - tic)
                with _profiler.span("mx/fit/epoch_end"):
                    arg_p, aux_p = self.get_params()
                    self.set_params(arg_p, aux_p)
                if epoch_end_callback is not None:
                    with _profiler.span("mx/fit/epoch_callbacks"):
                        for cb in _as_list(epoch_end_callback):
                            cb(epoch, self.symbol, arg_p, aux_p)
                if eval_data is not None:
                    with _profiler.span("mx/fit/eval"):
                        res = self.score(
                            eval_data, validation_metric,
                            score_end_callback=eval_end_callback,
                            batch_end_callback=eval_batch_end_callback,
                            epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)
                train_data.reset()
        if ckpt is not None:
            ckpt.wait()  # join an in-flight async save; surface errors

    @staticmethod
    def _common_resume_step(mine):
        """Newest checkpoint step EVERY rank can restore (allgather-min);
        -1 if any rank has none. Single-process: just ``mine``."""
        from ..parallel import dist as _dist
        if not _dist.initialized() or _dist.num_workers() <= 1:
            return mine
        steps = _np.asarray(_dist.allgather(_np.int64(mine)))
        return int(steps.min())

    def _dispatch_handles(self):
        """Device handles standing for the most recent dispatch, for
        :class:`~mxnet_tpu.engine.DepthController` back-pressure. An XLA
        output buffer becomes ready only when its whole program retires,
        so the first output handle suffices per dispatch."""
        try:
            outs = self.get_outputs()
        except Exception:
            return []
        return [o._data for o in outs[:1] if hasattr(o, "_data")]

    # ---------------------------------------------------------- to override
    @property
    def symbol(self):
        return self._symbol

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass

    def install_monitor(self, mon):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    def init_params(self, **kwargs):
        raise NotImplementedError

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def bind(self, *args, **kwargs):
        raise NotImplementedError

    def init_optimizer(self, *args, **kwargs):
        raise NotImplementedError

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        raise NotImplementedError


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]
