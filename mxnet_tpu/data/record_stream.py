"""Sharded RecordIO streams + the streaming DataIter (docs/data.md).

:class:`ShardedRecordStream` partitions a RecordIO file set across dp
ranks so the fleet covers **every record exactly once per epoch**:

* per-epoch seeded shuffle — file order and within-file order both come
  from ``RandomState(seed + epoch)``, consumed identically on every rank
  (the plan is a pure function of ``(paths, seed, epoch)``, so all ranks
  agree on it without communicating);
* file-level + within-file strided sharding — for the file at position
  ``j`` of the epoch's file permutation, rank ``r`` reads the shuffled
  keys ``[(r + j) % world :: world]``. The per-file stride offsets are a
  permutation of ``0..world-1``, so the strided slices partition each
  file; rotating the offset with ``j`` keeps short files from starving
  high ranks.

The stream position is a resumable ``(epoch, shard, offset)`` cursor
(``shard`` = index into this rank's per-epoch file sequence, ``offset``
= records consumed within it). :class:`StreamingDataIter` attaches the
cursor to every delivered batch, so ``Module.fit`` can snapshot the
CONSUMED position into a checkpoint and ``seek`` back to it bitwise —
O(1) instead of the O(steps) batch-skip replay (docs/fault_tolerance.md).

Decode/augment runs in parallel on the ``image_record_iter`` worker
layout: each batch splits into P part jobs with per-part RNGs seeded
``(seed + epoch*1000003 + batch*1009 + part)`` — the same idiom as
``ImageRecordIter``, and the reason augmentation replays bitwise after a
cursor seek (epoch and batch index are both cursor-derived).
"""
from __future__ import annotations

import threading

import numpy as _np

from ..base import MXNetError
from ..io.io import DataBatch, DataDesc, DataIter
from ..io.image_record_iter import _build_augmenter, _RecordSource
from .pipeline import PrefetchQueue

__all__ = ["ShardedRecordStream", "StreamingDataIter", "RawTensorDecoder",
           "ImageDecoder"]


class ShardedRecordStream:
    """Exactly-once strided reader over a sharded RecordIO file set.

    ``paths`` is one ``.rec`` path or a list (each with its ``.idx``
    sidecar unless the native scanner is available). ``rank``/``world``
    select this reader's stride of the fleet-wide record set.
    """

    def __init__(self, paths, rank=0, world=1, shuffle=True, seed=0,
                 epoch=0):
        if isinstance(paths, str):
            paths = [paths]
        if not paths:
            raise ValueError("ShardedRecordStream needs at least one file")
        if world <= 0 or not 0 <= rank < world:
            raise ValueError("bad rank/world: %r/%r" % (rank, world))
        self._paths = list(paths)
        self._rank = int(rank)
        self._world = int(world)
        self._shuffle = bool(shuffle)
        self._seed = int(seed)
        self._sources = [_RecordSource(p) for p in self._paths]
        self._counts = [len(s) for s in self._sources]
        if sum(self._counts) == 0:
            raise MXNetError("empty RecordIO set: %r" % (self._paths,))
        self._epoch = int(epoch)
        self._shard = 0
        self._offset = 0
        self._plan = None

    # ---------------------------------------------------------------- plan
    @property
    def epoch(self):
        return self._epoch

    @property
    def seed(self):
        return self._seed

    def _epoch_plan(self):
        if self._plan is not None:
            return self._plan
        rs = _np.random.RandomState(self._seed + self._epoch)
        nfiles = len(self._sources)
        if self._shuffle:
            file_perm = rs.permutation(nfiles)
        else:
            file_perm = _np.arange(nfiles)
        plan = []
        for j, fi in enumerate(file_perm):
            fi = int(fi)
            keys = (rs.permutation(self._counts[fi]) if self._shuffle
                    else _np.arange(self._counts[fi]))
            off = (self._rank + j) % self._world
            plan.append((fi, keys[off::self._world]))
        self._plan = plan
        return plan

    def records_per_epoch(self):
        """This rank's record count for the CURRENT epoch (the strided
        split can differ by ±1 per file across epochs as the stride
        offsets rotate with the file permutation)."""
        return sum(len(keys) for _, keys in self._epoch_plan())

    def records_consumed(self):
        """Records this rank has consumed within the current epoch."""
        plan = self._epoch_plan()
        done = sum(len(keys) for _, keys in plan[:self._shard])
        return done + self._offset

    # ------------------------------------------------------------- reading
    def read_next(self):
        """Next raw record's bytes, or None at epoch end. Advances the
        cursor; single-threaded by contract (one feeder per stream)."""
        plan = self._epoch_plan()
        while self._shard < len(plan):
            fi, keys = plan[self._shard]
            if self._offset < len(keys):
                rec = self._sources[fi].read(int(keys[self._offset]))
                self._offset += 1
                return rec
            self._shard += 1
            self._offset = 0
        return None

    def __iter__(self):
        while True:
            rec = self.read_next()
            if rec is None:
                return
            yield rec

    def next_epoch(self):
        self._epoch += 1
        self._shard = 0
        self._offset = 0
        self._plan = None

    # -------------------------------------------------------------- cursor
    def cursor(self):
        """JSON-able resumable position. Carries the sharding fingerprint
        so a seek under a different fleet shape fails loudly instead of
        silently replaying someone else's stride."""
        return {"epoch": self._epoch, "shard": self._shard,
                "offset": self._offset, "rank": self._rank,
                "world": self._world, "seed": self._seed}

    def seek(self, cursor):
        for key in ("rank", "world", "seed"):
            if key in cursor and int(cursor[key]) != getattr(
                    self, "_" + key):
                raise MXNetError(
                    "cursor %s=%r does not match this stream's %s=%r — "
                    "resharding a cursor needs a fresh epoch, not a seek"
                    % (key, cursor[key], key, getattr(self, "_" + key)))
        self._epoch = int(cursor["epoch"])
        self._shard = int(cursor["shard"])
        self._offset = int(cursor["offset"])
        self._plan = None


class RawTensorDecoder:
    """Decode records whose payload is ONE sample's raw bytes in
    ``data_shape`` order (as packed by tools/make_recordio.py); the label
    comes from the IRHeader. No randomness — a stream of these feeds
    ``Module.fit`` bitwise-identically to an in-memory ``NDArrayIter``
    over the same rows (pinned by tests/test_step_sync_budget.py)."""

    randomized = False

    def __init__(self, data_shape, label_width=1, dtype=_np.float32):
        self.data_shape = tuple(data_shape)
        self.label_width = int(label_width)
        self.data_dtype = _np.dtype(dtype)

    def __call__(self, rec, out_data, out_label, j, rng):
        from .. import recordio as _rio
        header, payload = _rio.unpack(rec)
        out_data[j] = _np.frombuffer(
            payload, self.data_dtype).reshape(self.data_shape)
        lab = _np.asarray(header.label).reshape(-1)
        out_label[j] = lab[0] if self.label_width == 1 \
            else lab[:self.label_width]


class ImageDecoder:
    """JPEG decode + the reference default augmenter (HWC BGR uint8 ->
    CHW float32 RGB) — the same ``_build_augmenter`` transform
    ``ImageRecordIter`` runs, so both tiers share one augmentation
    definition. ``aug_params`` as in ImageRecordIter (resize, rand_crop,
    rand_mirror, mean/std, scale, pad, ...)."""

    randomized = True

    def __init__(self, data_shape, label_width=1, **aug_params):
        self.data_shape = tuple(data_shape)
        self.label_width = int(label_width)
        self.data_dtype = _np.dtype(_np.float32)
        self._aug = _build_augmenter(self.data_shape, **aug_params)

    def __call__(self, rec, out_data, out_label, j, rng):
        import cv2
        from .. import recordio as _rio
        header, img_bytes = _rio.unpack(rec)
        img = cv2.imdecode(
            _np.frombuffer(img_bytes, _np.uint8), cv2.IMREAD_COLOR)
        if img is None:
            raise MXNetError("corrupt/undecodable image record")
        out_data[j] = self._aug(img, rng)
        lab = _np.asarray(header.label).reshape(-1)
        out_label[j] = lab[0] if self.label_width == 1 \
            else lab[:self.label_width]


class StreamingDataIter(DataIter):
    """DataIter over a :class:`ShardedRecordStream` with parallel
    decode/augment and a resumable cursor.

    A feeder thread pulls records, splits each batch into part jobs on a
    thread pool (cv2 releases the GIL, so parts decode concurrently),
    and pushes finished ``DataBatch``es through a :class:`PrefetchQueue`
    (the bounded put is the pipeline's backpressure). Every queued batch
    carries the stream cursor taken right after its records were pulled,
    so ``get_cursor()`` always reflects the position of the batch the
    CONSUMER last saw — never the feeder's read-ahead. ``reset()``
    rewinds the stream to that consumed position before restarting, so
    prefetched-but-undelivered batches are re-read, not lost.

    The short epoch tail (fewer than ``batch_size`` records) is dropped —
    every delivered batch is full, and the cursor stays on the exact
    record grid a resumed run re-derives.
    """

    def __init__(self, stream, decoder, batch_size, decode_threads=None,
                 prefetch_depth=2, ctx=None, data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        from ..config import flags as _flags
        self._stream = stream
        self._decoder = decoder
        self._ctx = ctx
        self.data_name = data_name
        self.label_name = label_name
        self._nthreads = max(1, int(decode_threads
                                    or _flags.data_decode_threads
                                    or _flags.cpu_worker_nthreads))
        self._depth = max(2, int(prefetch_depth))
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(self._nthreads)
        self._pq = None
        self._feeder = None
        self._done = False
        self._last_cursor = stream.cursor()
        self.seeks = 0        # test instrumentation: cursor-resume count
        self._start()

    # ------------------------------------------------------------ metadata
    @property
    def provide_data(self):
        return [DataDesc(self.data_name,
                         (self.batch_size,) + self._decoder.data_shape,
                         self._decoder.data_dtype)]

    @property
    def provide_label(self):
        w = self._decoder.label_width
        shape = (self.batch_size,) if w == 1 else (self.batch_size, w)
        return [DataDesc(self.label_name, shape)]

    @property
    def num_batches(self):
        return self._stream.records_per_epoch() // self.batch_size

    def queue_depth(self):
        """Host-held prefetch depth (for ``data/queue_depth`` telemetry)."""
        pq = self._pq
        return pq.qsize() if pq is not None else 0

    # -------------------------------------------------------------- feeder
    def _start(self):
        pq = self._pq = PrefetchQueue(self._depth)
        self._feeder = threading.Thread(
            target=self._feed_epoch, args=(pq,), daemon=True)
        self._feeder.start()

    def _feed_epoch(self, pq):
        try:
            self._feed_epoch_inner(pq)
        except BaseException as e:
            pq.put(e)
        pq.put_sentinel()

    def _decode_part(self, recs, out_data, out_label, offset, rng):
        for j, rec in enumerate(recs):
            self._decoder(rec, out_data, out_label, offset + j, rng)

    def _feed_epoch_inner(self, pq):
        from ..ndarray import ndarray as _nd
        B = self.batch_size
        P = self._nthreads
        epoch = self._stream.epoch
        seed = self._stream.seed
        w = self._decoder.label_width
        lshape = (w,) if w > 1 else ()
        b = self._stream.records_consumed() // B
        while not pq.stopped:
            recs = []
            while len(recs) < B:
                rec = self._stream.read_next()
                if rec is None:
                    return  # epoch end (short tail dropped)
                recs.append(rec)
            # the cursor rides the batch: taken after ITS records, before
            # the feeder reads ahead
            cursor = self._stream.cursor()
            data = _np.empty((B,) + self._decoder.data_shape,
                             self._decoder.data_dtype)
            label = _np.empty((B,) + lshape, _np.float32)
            bounds = [(p * B // P, (p + 1) * B // P) for p in range(P)]
            rngs = [_np.random.RandomState(
                (seed + epoch * 1000003 + b * 1009 + p))
                for p in range(P)]
            futs = [self._pool.submit(self._decode_part, recs[lo:hi],
                                      data, label, lo, rngs[p])
                    for p, (lo, hi) in enumerate(bounds) if lo != hi]
            for f in futs:
                f.result()   # re-raise decode errors on the feeder
            batch = DataBatch(data=[_nd.array(data, ctx=self._ctx)],
                              label=[_nd.array(label, ctx=self._ctx)],
                              pad=0)
            if not pq.put((batch, cursor)):
                return
            b += 1

    # ------------------------------------------------------------ iterator
    def next(self):
        if self._done:
            raise StopIteration
        try:
            batch, cursor = self._pq.get()
        except StopIteration:
            self._done = True
            # clean epoch end: advance to the next epoch's plan so the
            # post-epoch reset() starts fresh (ImageRecordIter semantics)
            self._stream.next_epoch()
            self._last_cursor = self._stream.cursor()
            raise
        self._last_cursor = cursor
        return batch

    def get_cursor(self):
        """Resumable position of the last CONSUMED batch (a fresh copy —
        safe to stash in a checkpoint while iteration continues)."""
        return dict(self._last_cursor)

    def seek(self, cursor):
        """Reposition to a checkpointed cursor: the next delivered batch
        is the one that followed it, bitwise (decode RNGs are re-derived
        from the cursor's epoch/batch index)."""
        self._shutdown_feeder()
        self._stream.seek(cursor)
        self._last_cursor = dict(cursor)
        self._done = False
        self.seeks += 1
        self._start()

    def reset(self):
        self._shutdown_feeder()
        # rewind to the consumed position: the feeder read ahead of the
        # consumer, and those records belong to the NEXT generation
        self._stream.seek(self._last_cursor)
        self._done = False
        self._start()

    def _shutdown_feeder(self):
        if self._pq is not None:
            self._pq.shutdown(self._feeder, timeout=30.0)

    def close(self):
        self._shutdown_feeder()
        self._pool.shutdown(wait=False)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
