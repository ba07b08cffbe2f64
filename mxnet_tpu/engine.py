"""Execution engine facade.

The reference's ThreadedEngine (src/engine/threaded_engine.h:269) exists to
overlap per-op kernel launches and enforce read/write ordering per variable.
On TPU, PJRT already runs every dispatched computation asynchronously and
XLA/PJRT orders executions on a device stream, so the *device-side* engine
degenerates to sync-point tracking — exactly the design predicted in
SURVEY.md §7. What remains engine-like on the host (threaded IO prefetch,
custom python ops, cross-host coordination) is handled by the C++ host engine
in ``mxnet_tpu/src/engine`` (see :mod:`mxnet_tpu.runtime`).

This module keeps the reference's escape hatches:
* ``MXNET_ENGINE_TYPE=NaiveEngine`` → every op blocks until complete
  (debug mode; reference src/engine/engine.cc:33-41).
* ``waitall()`` → block on all outstanding async work.
* async exception propagation: jax surfaces device errors at sync points;
  we translate them to MXNetError at wait()/asnumpy() like
  threaded_engine.cc:474-487 does.
"""
from __future__ import annotations

import jax

from . import profiler as _profiler
from .base import MXNetError
from .config import flags

__all__ = ["naive_mode", "waitall", "on_complete", "sync_point",
           "DepthController"]

_NAIVE = flags.engine_type == "NaiveEngine"


class DepthController:
    """Bounded in-flight dispatch (the ThreadedEngine's pending-op bound,
    reduced to what a PJRT device queue needs).

    Every jitted dispatch returns immediately with futures; an unthrottled
    fit loop would enqueue the whole epoch, ballooning host memory for the
    pending feeds and deferring device errors to the epoch end. ``admit``
    registers the freshly dispatched step's result handles and, once more
    than ``depth`` steps are outstanding, blocks on the OLDEST — steady
    state keeps ``depth`` steps in flight while the host runs ahead
    preparing feeds. ``quiesce`` drains everything: checkpoint snapshots,
    eval boundaries and epoch ends call it before reading state.

    depth <= 0 disables throttling (unbounded); depth 1 is lockstep
    (dispatch, then block on it at the next admit).
    """

    def __init__(self, depth=None):
        if depth is None:
            depth = flags.engine_depth
        self.depth = depth
        self._inflight = []  # deque of handle lists, oldest first

    def admit(self, handles, step=None):
        """Register one dispatched step's output handles (jax arrays);
        block on the oldest step beyond the depth bound. ``step`` names
        the dispatch just admitted on the wait's span."""
        handles = [h for h in handles if hasattr(h, "block_until_ready")]
        self._inflight.append(handles)
        if self.depth <= 0 or len(self._inflight) <= self.depth:
            return
        # the host's whole slack: how long it sits here says how far the
        # device is behind it (near 0: the host sets the pace)
        with _profiler.span("mx/fit/depth_wait", step=step):
            while len(self._inflight) > self.depth:
                oldest = self._inflight.pop(0)
                _profiler.record_host_sync("depth_wait")
                for h in oldest:
                    try:
                        h.block_until_ready()
                    except Exception as e:
                        raise MXNetError(str(e)) from e

    def quiesce(self):
        """Block until every admitted step has completed (checkpoint /
        eval / display boundary)."""
        pending, self._inflight = self._inflight, []
        if not pending:
            return
        _profiler.record_host_sync("wait")
        with _profiler.span("mx/fit/quiesce"):
            for handles in pending:
                for h in handles:
                    try:
                        h.block_until_ready()
                    except Exception as e:
                        raise MXNetError(str(e)) from e


def naive_mode() -> bool:
    return _NAIVE


def sync_point(arrays):
    """Called after every eager dispatch with the produced jax arrays."""
    if _NAIVE:
        for a in arrays:
            if hasattr(a, "block_until_ready"):
                a.block_until_ready()


def on_complete(array):
    """Block until one array's async computation completes (WaitForVar)."""
    try:
        if hasattr(array, "block_until_ready"):
            _profiler.record_host_sync("wait")
            with _profiler.span("mx/sync/wait"):
                array.block_until_ready()
    except Exception as e:  # surface async device errors like the reference
        raise MXNetError(str(e)) from e


def waitall():
    """Block until all async device work completes (parity: MXNDArrayWaitAll).

    ``jax.effects_barrier()`` only orders effectful computations. On TPU,
    each device executes enqueued programs IN ORDER, so one sentinel
    computation per device drains its queue in O(#devices) — a per-epoch
    waitall stays cheap no matter how many arrays are live. XLA:CPU runs
    executions on a thread pool with only data dependencies ordering
    them, so there the (O(live arrays)) walk remains the only correct
    drain, matching the reference's WaitForAll (threaded_engine.cc)."""
    try:
        _profiler.record_host_sync("wait")
        with _profiler.span("mx/sync/wait"):
            jax.effects_barrier()
            # Every outstanding async execution *and* transfer surfaces
            # as a not-yet-ready live array; is_ready() is a non-blocking
            # poll, so the walk costs O(live arrays) python but issues a
            # device sync only for the (few) actually-pending ones. A
            # per-device sentinel program would miss in-flight H2D/D2H
            # transfers, which are not enqueued on the compute queue.
            for a in jax.live_arrays():
                try:
                    if not a.is_ready():
                        a.block_until_ready()
                except AttributeError:
                    a.block_until_ready()
    except Exception as e:
        raise MXNetError(str(e)) from e
