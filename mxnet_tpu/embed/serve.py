"""The recommend serving leg: retrieval-tower artifacts + cached engine.

A trained two-tower retriever is two embedding tables: the USER tower
(averaged history embeddings) and the ITEM corpus it scores against.
``export_recommend`` packages both as a format_version-6 ``.mxtpu``
artifact — but unlike predict artifacts the user table is **not baked
into a compiled program**: production user tables outgrow any
bake-time constant, so the artifact carries the table as data and the
serving engine streams it through the PR-15 hot-row cache
(:class:`mxnet_tpu.embed.cache.HotRowCache`).

:class:`RecommendEngine` is what ``Server`` (mode="recommend") and
``POST /v1/recommend`` drive: per batch it plans slots on host
(hit/miss/spill accounting — zero device reads), uploads misses with
one donated scatter, then runs ONE jitted capacity-shaped program —
gather user rows from the cache, masked-mean, score the corpus matmul,
``top_k`` — and performs ONE d2h for the whole response batch. mxlint
MXL511 (``embedding_lookup_discipline_pass``) pins the lowering: the
cache buffer must be donated and the program must contain zero
device->host ops.

Cost model: a recommend request is charged by its GATHER count through
``perfmodel.recommend_request_seconds`` — the admission queue bills in
gather units and the fleet heartbeat's ``load_s`` is pending gathers
times the per-gather roofline, so the router's least-loaded policy
sees ragged requests honestly (docs/embeddings.md, docs/serving.md).
"""
from __future__ import annotations

import io
import json
import struct

import numpy as _np

from ..base import MXNetError
from ..config import flags
from .cache import HotRowCache, SpillStore

__all__ = ["export_recommend", "RecommendModel", "RecommendEngine"]


def export_recommend(user_table, item_table, path, *, max_ids=64, k=10,
                     model_name="twotower", extra_meta=None):
    """Write a format_version-6 recommend artifact.

    ``user_table`` (rows x dim) and ``item_table`` (items x dim) are
    host arrays (the trained parameters — flush the training cache
    first). ``max_ids`` bounds one request's history length; ``k`` is
    the default result count. The payload is a raw ``.npz`` (tables as
    DATA, not program constants); meta carries the geometry the serving
    engine and ``/info`` need."""
    from ..serving import _MAGIC
    user_table = _np.ascontiguousarray(user_table)
    item_table = _np.ascontiguousarray(item_table)
    if user_table.ndim != 2 or item_table.ndim != 2:
        raise MXNetError("export_recommend: tables must be 2-D "
                         "(rows x dim)")
    if user_table.shape[1] != item_table.shape[1]:
        raise MXNetError(
            "export_recommend: tower dims disagree (%d vs %d)"
            % (user_table.shape[1], item_table.shape[1]))
    meta = {
        "format_version": 6,
        "model_name": model_name,
        "recommend": {
            "rows": int(user_table.shape[0]),
            "items": int(item_table.shape[0]),
            "dim": int(user_table.shape[1]),
            "dtype": str(user_table.dtype),
            "max_ids": int(max_ids),
            "k": int(min(k, item_table.shape[0])),
        },
    }
    if extra_meta:
        meta.update(extra_meta)
    buf = io.BytesIO()
    _np.savez(buf, user_table=user_table, item_table=item_table)
    blob = buf.getvalue()
    meta_b = json.dumps(meta, sort_keys=True).encode()
    with open(path, "wb") as f:
        f.write(_MAGIC)
        f.write(struct.pack("<I", len(meta_b)))
        f.write(meta_b)
        f.write(blob)
    return meta


class RecommendModel:
    """A loaded format_version-6 artifact: geometry + host tables."""

    def __init__(self, meta, user_table, item_table):
        self.meta = meta
        self.spec = dict(meta["recommend"])
        self.user_table = user_table
        self.item_table = item_table

    @classmethod
    def load(cls, path, **_kw):
        from ..serving import _read_artifact, _require_kind
        meta, payload = _read_artifact(path)
        _require_kind(path, meta, "recommend")
        with _np.load(io.BytesIO(payload)) as z:
            user = z["user_table"]
            item = z["item_table"]
        return cls(meta, user, item)

    def engine(self, capacity=None, buckets=None, max_ids=None, k=None):
        return RecommendEngine(self, capacity=capacity, buckets=buckets,
                               max_ids=max_ids, k=k)


class RecommendEngine:
    """Cache-backed scorer over one :class:`RecommendModel`.

    ``buckets`` are request-batch buckets (like the predict micro-
    batcher's); each compiles one capacity-shaped executable. The user
    table lives in a :class:`HotRowCache` sized ``capacity``
    (``MXNET_EMBED_CACHE_ROWS`` default); the item corpus is small by
    construction (it is the output vocabulary) and sits dense on
    device."""

    def __init__(self, model, capacity=None, buckets=None, max_ids=None,
                 k=None):
        import jax
        self.model = model
        spec = model.spec
        self.rows = spec["rows"]
        self.dim = spec["dim"]
        self.items = spec["items"]
        self.max_ids = int(max_ids or spec["max_ids"])
        self.k = int(min(k or spec["k"], self.items))
        self.buckets = tuple(sorted(set(int(b) for b in
                                        (buckets or (1, 4, 16)))))
        capacity = int(capacity or flags.embed_cache_rows)
        budget = float(flags.embed_host_budget_mb or 0.0)
        user = model.user_table
        store = SpillStore(
            self.rows, self.dim, dtype=user.dtype,
            init_fn=lambda ids: user[_np.asarray(ids, _np.int64)],
            budget_bytes=int(budget * (1 << 20)) if budget > 0 else None)
        self.cache = HotRowCache(store, capacity)
        self.corpus = jax.device_put(_np.ascontiguousarray(
            model.item_table))
        self._jits = {}
        self.requests = 0
        self.gathers = 0

    # -- the served lookup program ------------------------------------------
    def _score_fn(self):
        """(cache_buf, corpus, slots, lengths) -> (cache_buf, scores,
        ids). The cache buffer is DONATED and threaded through — the
        resident buffer is never copied (MXL511's first check); slot
        ids keep the program capacity-shaped."""
        import jax
        import jax.numpy as jnp
        from .table import local_gather
        k = self.k
        max_ids = self.max_ids

        def run(cache_buf, corpus, slots, lengths):
            b = slots.shape[0]
            emb = local_gather(cache_buf, slots.reshape(-1))
            emb = emb.reshape(b, max_ids, cache_buf.shape[-1])
            mask = (jnp.arange(max_ids)[None, :]
                    < lengths[:, None]).astype(emb.dtype)
            denom = jnp.maximum(lengths.astype(emb.dtype), 1.0)
            user = (emb * mask[..., None]).sum(axis=1) / denom[:, None]
            scores = user @ corpus.T
            top_s, top_i = jax.lax.top_k(scores, k)
            return cache_buf, top_s, top_i

        return jax.jit(run, donate_argnums=(0,))

    def _jit(self, bucket):
        fn = self._jits.get(bucket)
        if fn is None:
            fn = self._jits[bucket] = self._score_fn()
        return fn

    def warm(self, bucket=None):
        """Compile (and run once on zero inputs) the capacity-shaped
        executable(s) without touching the cache or the request
        counters — the Server.warmup_async path."""
        import jax
        for bk in ((bucket,) if bucket else self.buckets):
            slots = _np.zeros((bk, self.max_ids), _np.int32)
            lengths = _np.zeros((bk,), _np.int32)
            fn = self._jit(bk)
            self.cache.buf, s, i = fn(self.cache.buf, self.corpus,
                                      slots, lengths)
            jax.block_until_ready((s, i))

    def _plan(self, id_lists):
        """Host-side batch plan: clip/truncate each request to max_ids,
        make every needed row device-resident, return the slot matrix +
        lengths (+ the real gather count billed to admission)."""
        b = len(id_lists)
        slots = _np.zeros((b, self.max_ids), dtype=_np.int32)
        lengths = _np.zeros((b,), dtype=_np.int32)
        flat = []
        for ids in id_lists:
            ids = list(ids)[:self.max_ids]
            flat.extend(ids)
        all_slots = (self.cache.ensure(_np.asarray(flat, _np.int64))
                     if flat else _np.zeros((0,), _np.int32))
        off = 0
        for j, ids in enumerate(id_lists):
            n = min(len(ids), self.max_ids)
            lengths[j] = n
            slots[j, :n] = all_slots[off:off + n]
            off += n
        return slots, lengths, len(flat)

    def recommend_batch(self, id_lists, bucket=None):
        """Score a batch of ragged id lists; returns (scores, item_ids)
        as host arrays, one row per request. ONE device dispatch and
        ONE d2h for the whole batch (PR-3 discipline)."""
        import jax
        from .. import profiler
        b = len(id_lists)
        if bucket is None:
            bucket = next((bk for bk in self.buckets if bk >= b),
                          self.buckets[-1])
        if b > bucket:
            raise MXNetError(
                "recommend: batch of %d exceeds bucket %d" % (b, bucket))
        slots, lengths, gathers = self._plan(id_lists)
        if b < bucket:
            slots = _np.concatenate(
                [slots, _np.zeros((bucket - b, self.max_ids),
                                  _np.int32)])
            lengths = _np.concatenate(
                [lengths, _np.zeros((bucket - b,), _np.int32)])
        fn = self._jit(bucket)
        self.cache.buf, top_s, top_i = fn(self.cache.buf, self.corpus,
                                          slots, lengths)
        host = jax.device_get((top_s, top_i))
        nbytes = sum(h.nbytes for h in host)
        profiler.record_host_sync("d2h", nbytes)
        self.requests += b
        self.gathers += gathers
        return _np.asarray(host[0])[:b], _np.asarray(host[1])[:b]

    # -- cost model ----------------------------------------------------------
    def gather_unit_s(self, device_kind=None):
        """Roofline seconds per single gather unit — the admission
        queue's billing rate (load_s = pending gathers x this)."""
        from .. import perfmodel
        if device_kind is None:
            device_kind = perfmodel.modelled_device_kind()
        base = perfmodel.recommend_request_seconds(
            1, self.dim, self.items,
            dtype_bytes=self.cache.dtype.itemsize,
            device_kind=device_kind)
        return max(base, 1e-9)

    def estimate_request_s(self, gathers, device_kind=None):
        from .. import perfmodel
        if device_kind is None:
            device_kind = perfmodel.modelled_device_kind()
        return perfmodel.recommend_request_seconds(
            gathers, self.dim, self.items,
            dtype_bytes=self.cache.dtype.itemsize,
            device_kind=device_kind)

    # -- discipline ----------------------------------------------------------
    def lookup_lowering_text(self, bucket=None):
        """StableHLO of the served lookup program, chip-free
        (JAX_PLATFORMS=cpu) — MXL511's input."""
        import jax
        bucket = bucket or self.buckets[0]
        shapes = (
            jax.ShapeDtypeStruct((self.cache.capacity, self.dim),
                                 self.cache.dtype),
            jax.ShapeDtypeStruct((self.items, self.dim),
                                 self.corpus.dtype),
            jax.ShapeDtypeStruct((bucket, self.max_ids), _np.int32),
            jax.ShapeDtypeStruct((bucket,), _np.int32),
        )
        return self._jit(bucket).lower(*shapes).as_text()

    def check_discipline(self, bucket=None):
        """Run mxlint MXL511 over the served lookup lowering; returns
        the diagnostics list ([] = clean)."""
        from ..analysis import hlo_passes
        text = self.lookup_lowering_text(bucket)
        return hlo_passes.embedding_lookup_discipline_pass(
            text, "recommend/lookup", cache_params=(0,))

    def stats(self):
        """Host-held snapshot (cache counters + request accounting)."""
        out = self.cache.stats()
        out.update(requests=self.requests, gathers=self.gathers,
                   corpus_rows=self.items, max_ids=self.max_ids,
                   k=self.k, buckets=list(self.buckets))
        return out
