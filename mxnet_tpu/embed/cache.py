"""Device-resident hot-row embedding cache with host-side spill.

The Zipf reality of recommendation traffic: a few percent of rows take
almost all lookups. This module keeps those hot rows in a fixed
``(capacity, dim)`` device buffer updated IN PLACE (donated scatter,
the PR-9 paged-KV-cache discipline) and spills the cold tail to a host
:class:`SpillStore`, so the *logical* table is bounded by host+device
memory together — and, with a lazy row initializer, only by the rows
actually touched.

Budget discipline (PR 3): all placement decisions — hit/miss tests, LRU
eviction, slot assignment — happen on HOST metadata (a dict and an
order list), never by reading the device buffer. The per-step device
traffic is: one donated h2d scatter uploading missed rows, and (only
in training, only on eviction of a DIRTY row) a d2h pull of the evicted
rows for write-back. Serving is read-only — rows are never dirty, so
the served lookup performs ZERO d2h, which mxlint MXL511 pins on the
lowered program. Hit/miss/spill counters are plain ints published per
telemetry window through ``telemetry.publish_window(embed=...)``.

Bitwise across capacities: a row's update arithmetic depends only on
its value and its gradient, never on which slot it sits in or when it
was evicted (the d2h/h2d spill round-trip preserves bits), so training
the same stream with capacity 8 or 64 lands identical final tables —
the chip-free gate in tests/test_embed.py.
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as _np

from ..base import MXNetError
from .table import row_init

__all__ = ["HotRowCache", "SpillStore"]


class SpillStore:
    """Host-side cold-row store, lazily materialized.

    Rows live in a dict only once touched; an untouched row costs
    nothing and is (re)created deterministically by ``init_fn(ids)`` —
    by default :func:`row_init`, the same bits every mesh shard or
    reference run would produce. ``budget_bytes`` (optional,
    ``MXNET_EMBED_HOST_BUDGET_MB`` via the caller) bounds RESIDENT host
    bytes: the store raises rather than silently blowing past it, which
    is how the fleet test proves the logical table exceeds the
    configured host budget while training stays inside it."""

    def __init__(self, rows, dim, dtype="float32", init_fn=None, seed=0,
                 budget_bytes=None):
        self.rows = int(rows)
        self.dim = int(dim)
        self.dtype = _np.dtype(dtype)
        self.seed = int(seed)
        self._init_fn = init_fn
        self.budget_bytes = (None if budget_bytes is None
                             else int(budget_bytes))
        self._rows = {}
        self.row_bytes = self.dim * self.dtype.itemsize

    @property
    def logical_bytes(self):
        """Bytes a dense materialization of the table would take."""
        return self.rows * self.row_bytes

    @property
    def resident_bytes(self):
        """Bytes actually held on host right now."""
        return len(self._rows) * self.row_bytes

    def _materialize(self, ids):
        if self._init_fn is not None:
            return _np.asarray(self._init_fn(ids),
                               dtype=self.dtype).reshape(len(ids),
                                                         self.dim)
        return row_init(self.seed, ids, self.dim, self.dtype)

    def take(self, ids):
        """Pop rows (id array -> (n, dim)); cold ids are materialized.
        Rows move to the device cache EXCLUSIVELY — host memory shrinks
        by what the device now holds."""
        out = _np.empty((len(ids), self.dim), dtype=self.dtype)
        fresh = [i for i in ids if int(i) not in self._rows]
        if fresh:
            made = self._materialize(_np.asarray(fresh, _np.int64))
            for j, i in enumerate(fresh):
                self._rows[int(i)] = made[j]
        for j, i in enumerate(ids):
            out[j] = self._rows.pop(int(i))
        return out

    def put(self, ids, values):
        """Write evicted rows back (the training spill path)."""
        values = _np.asarray(values, dtype=self.dtype)
        for j, i in enumerate(ids):
            self._rows[int(i)] = _np.array(values[j], copy=True)
        if (self.budget_bytes is not None
                and self.resident_bytes > self.budget_bytes):
            raise MXNetError(
                "embed: host spill store exceeded its configured budget "
                "(%d resident > %d budget bytes; logical table is %d) — "
                "raise MXNET_EMBED_HOST_BUDGET_MB or the cache capacity"
                % (self.resident_bytes, self.budget_bytes,
                   self.logical_bytes))

    def peek(self, ids):
        """Read rows without removing them (debug/final-state export)."""
        out = _np.empty((len(ids), self.dim), dtype=self.dtype)
        fresh = [i for i in ids if int(i) not in self._rows]
        if fresh:
            made = self._materialize(_np.asarray(fresh, _np.int64))
            for j, i in enumerate(fresh):
                self._rows[int(i)] = made[j]
        for j, i in enumerate(ids):
            out[j] = self._rows[int(i)]
        return out


class HotRowCache:
    """Fixed-capacity device cache over a :class:`SpillStore`.

    Protocol per step (the two-tower trainer and the recommend engine
    both follow it)::

        slots = cache.ensure(ids)      # host plan + spill I/O
        out, cache.buf = step(cache.buf, slots, ...)   # donated jit
        cache.note_updated(ids)        # training only: mark dirty

    ``ensure`` is the only method that moves data: it evicts LRU rows
    (pulling DIRTY ones device->host first — the accounted d2h), uploads
    missed rows with ONE donated scatter, and returns the device slot of
    every requested id. The jitted step receives SLOT ids, so its
    lowering is capacity-shaped, never rows-shaped — that is what lets
    the logical table outgrow the device."""

    def __init__(self, store, capacity, pad_to=8):
        if capacity <= 0:
            raise MXNetError("HotRowCache: capacity must be positive")
        if capacity > store.rows:
            capacity = store.rows
        self.store = store
        self.capacity = int(capacity)
        self.dim = store.dim
        self.dtype = store.dtype
        # upload batches are padded to multiples of pad_to so the
        # donated scatter compiles O(log capacity) variants, not one
        # per distinct miss count
        self.pad_to = max(1, int(pad_to))
        self._slot_of = {}            # id -> slot
        self._id_of = [-1] * self.capacity
        self._lru = OrderedDict()     # id -> None, oldest first
        self._free = list(range(self.capacity - 1, -1, -1))
        self._dirty = set()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.spill_bytes = 0          # d2h write-back volume
        self.upload_bytes = 0         # h2d fill volume
        self.lookups = 0
        import jax
        self.buf = jax.device_put(
            _np.zeros((self.capacity, self.dim), dtype=self.dtype))
        self._scatter = jax.jit(
            lambda buf, slots, rows: buf.at[slots].set(rows),
            donate_argnums=(0,))

    # -- the per-step plan ---------------------------------------------------
    def ensure(self, ids):
        """Make every id device-resident; returns np.int32 slots aligned
        with ``ids`` (duplicates map to the same slot)."""
        ids = _np.clip(_np.asarray(ids, _np.int64).reshape(-1),
                       0, self.store.rows - 1)
        uniq = list(dict.fromkeys(int(i) for i in ids))  # order-stable
        if len(uniq) > self.capacity:
            raise MXNetError(
                "embed: one step touches %d distinct rows but the cache "
                "holds %d — raise capacity above the per-step working "
                "set (docs/embeddings.md cache sizing)" % (len(uniq),
                                                           self.capacity))
        self.lookups += len(ids)
        missing = []
        for i in uniq:
            if i in self._slot_of:
                self.hits += 1
                self._lru.move_to_end(i)
            else:
                self.misses += 1
                missing.append(i)
        if missing:
            self._fill(missing, protect=set(uniq))
        slots = _np.fromiter((self._slot_of[int(i)] for i in ids),
                             dtype=_np.int32, count=len(ids))
        return slots

    def _fill(self, missing, protect):
        import jax
        from .. import profiler
        need = len(missing) - len(self._free)
        if need > 0:
            evict = []
            for i in list(self._lru):
                if len(evict) == need:
                    break
                if i in protect:
                    continue
                evict.append(i)
            dirty = [i for i in evict if i in self._dirty]
            if dirty:
                d_slots = _np.asarray(
                    [self._slot_of[i] for i in dirty], _np.int32)
                # the ONLY d2h on this path, and only in training:
                # evicted dirty rows spill back to the host store
                vals = _np.asarray(jax.device_get(self.buf[d_slots]))
                nbytes = vals.nbytes
                profiler.record_host_sync("d2h", nbytes)
                self.spill_bytes += nbytes
                self.store.put(dirty, vals)
            for i in evict:
                self.evictions += 1
                slot = self._slot_of.pop(i)
                self._id_of[slot] = -1
                self._lru.pop(i, None)
                self._dirty.discard(i)
                self._free.append(slot)
        rows = self.store.take(missing)
        slots = []
        for i in missing:
            slot = self._free.pop()
            self._slot_of[i] = slot
            self._id_of[slot] = i
            self._lru[i] = None
            slots.append(slot)
        # pad to the bucket so the donated scatter's jit cache stays
        # small; padding re-writes the first row with its own value
        m = len(missing)
        pad = -(-m // self.pad_to) * self.pad_to - m
        if pad:
            slots = slots + [slots[0]] * pad
            rows = _np.concatenate([rows, _np.repeat(rows[:1], pad, 0)])
        self.upload_bytes += rows.nbytes
        self.buf = self._scatter(self.buf,
                                 _np.asarray(slots, _np.int32), rows)

    def note_updated(self, ids):
        """Training: the step's donated scatter rewrote these rows on
        device; they must spill before their slot is reused."""
        for i in _np.asarray(ids, _np.int64).reshape(-1):
            i = int(min(max(i, 0), self.store.rows - 1))
            if i in self._slot_of:
                self._dirty.add(i)

    def flush(self):
        """Spill every dirty row to the host store (end of training /
        checkpoint). One d2h for the whole dirty set."""
        import jax
        from .. import profiler
        dirty = sorted(self._dirty)
        if not dirty:
            return 0
        slots = _np.asarray([self._slot_of[i] for i in dirty], _np.int32)
        vals = _np.asarray(jax.device_get(self.buf[slots]))
        profiler.record_host_sync("d2h", vals.nbytes)
        self.spill_bytes += vals.nbytes
        self.store.put(dirty, vals)
        self._dirty.clear()
        return len(dirty)

    def hit_rate(self):
        n = self.hits + self.misses
        return (self.hits / n) if n else 0.0

    def stats(self):
        """Host-held counters — the ``embed/*`` telemetry source; never
        reads the device."""
        return {
            "capacity": self.capacity,
            "resident": len(self._slot_of),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "hit_rate": round(self.hit_rate(), 6),
            "spill_bytes": self.spill_bytes,
            "upload_bytes": self.upload_bytes,
            "lookups": self.lookups,
            "host_resident_bytes": self.store.resident_bytes,
            "logical_bytes": self.store.logical_bytes,
        }
