"""StagedKFeed: the zero-stall K-step device feed (docs/data.md).

``FusedStep.run_k`` scans a jitted step over stacked ``(K, batch, ...)``
feeds. Without staging, the host builds that stacked buffer (cast +
``jnp.stack`` + ``device_put``) inside the dispatch call — serial with
the step loop, so every window pays the H2D latency before its dispatch
can issue. :class:`StagedKFeed` moves that work onto a feeder thread and
double-buffers it: while window ``W`` is in flight on the device, the
feeder is already pulling window ``W+1``'s K batches from the iterator
and committing them to the device layout (PJRT H2D is async, so the
copy itself overlaps compute). ``Module.fit`` then consumes
device-resident windows with zero added host syncs — the one-d2h-per-
window budget is pinned by tests/test_step_sync_budget.py.

What is deliberately NOT staged: PRNG keys and optimizer hyper-params.
Both advance deterministic host-side chains that checkpoint snapshots
capture at window boundaries; pre-drawing them for future windows would
put the saved chain ahead of the training position and break bitwise
kill/resume. The feeder stages data only — a pure function of the
batches — so the staged path is bitwise-identical to the unstaged one.

Cursor discipline: when the iterator exposes ``get_cursor``, the feeder
snapshots it right after pulling each window's batches (the feeder is
the only consumer, so that IS the consumed position when the window
commits) and attaches it to the window for the checkpoint path.
"""
from __future__ import annotations

import threading

from .pipeline import PrefetchQueue

__all__ = ["StagedKFeed", "StagedWindow"]


class StagedWindow:
    """One K-step window: the host batches (labels/metadata for metrics
    and callbacks), the pre-staged device feed (None on short tails —
    those take the per-step path) and the iterator cursor after these
    batches."""

    __slots__ = ("batches", "staged", "cursor")

    def __init__(self, batches, staged=None, cursor=None):
        self.batches = batches
        self.staged = staged
        self.cursor = cursor


class StagedKFeed:
    """Double-buffered window stager between a DataIter and fit's
    grouped loop.

    ``stage_fn(batches, step=...)`` is the module's host→device staging
    hook (``Module._stage_group``): it returns the opaque staged-feed
    payload ``run_k`` accepts, and times and counts its own copy
    (``mx/feed/h2d``). ``step`` is the ``global_step`` of the window's
    first batch (``first_step`` plus K a window), so that the feeder
    thread's spans carry the step of the dispatch they work for.
    ``depth`` bounds the staged windows in flight (2 = classic double
    buffering; staged windows hold device memory, so keep it small).
    """

    def __init__(self, data_iter, k, stage_fn, depth=2, cursor_fn=None,
                 first_step=0):
        self._it = data_iter
        self._k = max(2, int(k))
        self._stage_fn = stage_fn
        self._cursor_fn = cursor_fn
        self._step = first_step
        self._pq = PrefetchQueue(max(1, int(depth)))
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        pq = self._pq
        try:
            while not pq.stopped:
                batches = []
                ended = False
                while len(batches) < self._k:
                    try:
                        batches.append(next(self._it))
                    except StopIteration:
                        ended = True
                        break
                if not batches:
                    break
                cursor = self._cursor_fn() if self._cursor_fn else None
                staged = None
                if len(batches) == self._k:
                    # full window: commit to the stacked device layout
                    # now, overlapping the in-flight dispatch. Tails ride
                    # unstaged — fit's per-step path handles them.
                    staged = self._stage_fn(batches, step=self._step)
                self._step += len(batches)
                if not pq.put(StagedWindow(batches, staged, cursor)):
                    return
                if ended:
                    break
        except BaseException as e:
            pq.put(e)
        pq.put_sentinel()

    def next_window(self):
        """Next :class:`StagedWindow`; raises StopIteration at epoch end
        and re-raises feeder errors. Blocking time here is the fit
        loop's input stall (``data/input_stall_ms``)."""
        return self._pq.get()

    def queue_depth(self):
        return self._pq.qsize()

    def close(self):
        self._pq.shutdown(self._thread, timeout=30.0)
