"""Streaming ingestion tier: sharded record streams feeding the fit loop
(docs/data.md).

The pieces, bottom-up:

* ``pipeline`` — the ONE bounded-queue backpressure/shutdown primitive
  every prefetching producer in the repo shares
  (:class:`PrefetchQueue`; also used by ``io.PrefetchingIter`` and
  ``io.ImageRecordIter``).
* ``record_stream`` — :class:`ShardedRecordStream` partitions a RecordIO
  file set across dp ranks (every record exactly once per epoch per
  fleet) with a resumable ``(epoch, shard, offset)`` cursor, and
  :class:`StreamingDataIter` turns it into a ``DataIter`` with parallel
  decode/augment and a bitwise kill/resume cursor that rides
  ``CheckpointManager``.
"""
from __future__ import annotations

from mxnet_tpu.data.pipeline import PrefetchQueue
from mxnet_tpu.data.record_stream import (
    ImageDecoder, RawTensorDecoder, ShardedRecordStream, StreamingDataIter,
)

__all__ = [
    "PrefetchQueue", "ShardedRecordStream", "StreamingDataIter",
    "RawTensorDecoder", "ImageDecoder",
]
