"""Bucketed, backward-overlapped gradient all-reduce for data parallelism.

The kvstore ``dist_sync`` path (kvstore.py -> dist.allreduce_sum) issues one
blocking, host-mediated collective per tensor AFTER the backward pass has
fully finished: gradient exchange serializes behind compute and per-tensor
launch overhead dominates on small params. This module is the fast path the
ROADMAP (item 4) calls for:

* the gradient pytree is partitioned into size-bounded, dtype-homogeneous
  **buckets** (``partition_buckets``), walked in *reverse production order*
  — the backward pass materializes the last layer's gradients first, so the
  first bucket closes while most of the backward graph is still pending;
* each bucket is flattened into ONE fused ``jax.lax.psum`` over the ``dp``
  mesh axis (``GradReducer.reduce``), *inside the traced step* — each
  collective's operands depend only on its own bucket's gradients, so XLA's
  latency-hiding scheduler is free to interleave the all-reduces with the
  remaining backward compute (the DepthController discipline from PR 3,
  generalized from host/device overlap to comm/compute overlap);
* the bucket size comes from the perfmodel interconnect table
  (``choose_bucket_bytes``): big enough that per-collective launch overhead
  is amortized below ``_LAUNCH_FRACTION`` of a bucket's transfer time,
  small enough that several buckets exist to overlap. ``MXNET_DDP_BUCKET_MB``
  overrides.

Wiring (enabled by ``MXNET_DDP=1`` / ``tools/launch.py --ddp``):
``module/fused.py`` wraps its step in ``shard_map`` over ``process_mesh()``
and reduces gradients through a ``GradReducer``; ``gluon/trainer.py`` and
the non-fused ``Module.update`` fall back to the eager
``dist.allreduce_tree`` (bucketed, but post-backward); ``parallel/spmd.py``
grows a ``ddp_bucketed`` mode composing the manual ``dp`` reduction with a
GSPMD-managed ``tp`` axis. The kvstore path remains for ``dist_async``.

MXL507 (analysis/hlo_passes.py) asserts the lowered step really does keep
the collectives interleavable; docs/distributed.md is the user guide.
"""
from __future__ import annotations

import numpy as _np

from .. import perfmodel as _perfmodel
from ..config import flags

__all__ = ["Bucket", "SparseBucket", "GradReducer", "enabled",
           "choose_bucket_bytes", "partition_buckets", "process_mesh",
           "estimate_overlap_ms", "to_global", "from_global"]

# A collective launch costs ~_LAUNCH_OVERHEAD_S on the host/ICI; size each
# bucket so that cost stays below _LAUNCH_FRACTION of its transfer time.
_LAUNCH_OVERHEAD_S = 20e-6
_LAUNCH_FRACTION = 0.05
_MIN_BUCKET_BYTES = 1 << 20    # 1 MiB: below this, launches dominate
_MAX_BUCKET_BYTES = 64 << 20   # 64 MiB: above this, overlap disappears


def enabled():
    """True when the bucketed DDP path is switched on (``MXNET_DDP=1``)."""
    return bool(flags.ddp)


def choose_bucket_bytes(device_kind=None):
    """Bucket size in bytes: ``MXNET_DDP_BUCKET_MB`` if set, else sized
    from the interconnect bandwidth so launch overhead amortizes to
    <= ``_LAUNCH_FRACTION`` of a bucket's transfer time, clamped to
    [1 MiB, 64 MiB]."""
    mb = float(flags.ddp_bucket_mb or 0.0)
    if mb > 0.0:
        return max(1, int(mb * (1 << 20)))
    bw = _perfmodel.interconnect_bytes_per_s(
        device_kind or _perfmodel.modelled_device_kind())
    raw = bw * _LAUNCH_OVERHEAD_S / _LAUNCH_FRACTION
    return int(min(max(raw, _MIN_BUCKET_BYTES), _MAX_BUCKET_BYTES))


class Bucket:
    """One fused all-reduce's worth of gradients (dtype-homogeneous)."""

    __slots__ = ("keys", "shapes", "sizes", "dtype", "nbytes")

    def __init__(self, entries):
        self.keys = tuple(k for k, _, _ in entries)
        self.shapes = tuple(tuple(s) for _, s, _ in entries)
        self.sizes = tuple(
            int(_np.prod(s, dtype=_np.int64)) if len(s) else 1
            for _, s, _ in entries)
        self.dtype = _np.dtype(entries[0][2])
        self.nbytes = sum(self.sizes) * self.dtype.itemsize

    def __repr__(self):
        return "Bucket(n=%d, dtype=%s, nbytes=%d)" % (
            len(self.keys), self.dtype.name, self.nbytes)


class SparseBucket:
    """One embedding gradient's sparse exchange plan.

    The dense path would all-reduce the full ``(rows, dim)`` gradient —
    almost entirely zeros when one step touches a few hundred of
    millions of rows. The sparse kind exchanges CONTRIBUTIONS instead:
    each rank all-gathers its ``(ids, values)`` pair (``length`` batch
    positions, duplicates included) and every rank coalesces the global
    set locally with a stable-sorted-id scatter-add. Comm volume is
    ``axis_size * length * (4 + dim*itemsize)`` vs ``rows*dim*itemsize``
    densified — orders of magnitude on real tables (the
    gradient-compression slot of PAPER.md capability 5).

    Determinism is the point, not a side effect: all_gather concatenates
    in rank order and the sort is STABLE, so each row's contributions
    fold in (rank, batch-position) order — bitwise-identical to the
    left fold a 1-rank dense VJP scatter-add performs over the same
    global batch. tests/test_embed.py pins both properties (>=10x bytes
    and bitwise-equal updates vs the 1-rank oracle)."""

    __slots__ = ("key", "length", "dim", "rows", "dtype")

    def __init__(self, key, length, dim, rows, dtype="float32"):
        self.key = key
        self.length = int(length)   # per-rank contribution count
        self.dim = int(dim)
        self.rows = int(rows)       # dense rows the grad densifies to
        self.dtype = _np.dtype(dtype)

    def comm_bytes(self, axis_size):
        """Gathered volume per device: ids (int32) + values."""
        return (self.length * axis_size
                * (4 + self.dim * self.dtype.itemsize))

    def densified_bytes(self):
        """What the dense bucket path would move for this grad."""
        return self.rows * self.dim * self.dtype.itemsize

    def __repr__(self):
        return ("SparseBucket(%r, L=%d, dim=%d, rows=%d)"
                % (self.key, self.length, self.dim, self.rows))


def coalesce_sparse_grad(ids, values, rows, axis_name=None):
    """Reduce one sparse gradient to its dense ``(rows, dim)`` form.

    ``ids``/``values`` are this rank's raw per-position contributions
    (any leading shape; flattened here). With ``axis_name`` (inside
    shard_map) the contributions are first all-gathered in rank order;
    the coalesce is then a stable sort by id + scatter-add — the
    sorted-id reduction order that makes the result independent of
    sharding, bit for bit. Traced, differentiable-free (gradient of a
    gradient is out of scope)."""
    import jax
    import jax.numpy as jnp
    dim = values.shape[-1]
    ids = ids.astype(jnp.int32).reshape(-1)
    values = values.reshape(-1, dim)
    if axis_name is not None:
        ids = jax.lax.all_gather(ids, axis_name, tiled=True)
        values = jax.lax.all_gather(values, axis_name, tiled=True)
    ids = jnp.clip(ids, 0, rows - 1)
    order = jnp.argsort(ids, stable=True)
    return (jnp.zeros((rows, dim), values.dtype)
            .at[ids[order]].add(values[order]))


def partition_buckets(entries, bucket_bytes=None, reverse=True):
    """Partition ``(key, shape, dtype)`` entries into size-bounded,
    dtype-homogeneous buckets.

    ``reverse=True`` (default) walks the entries back-to-front so bucket 0
    holds the *last* parameters' gradients — the ones the backward pass
    produces first, whose reduce can hide under the rest of the backward.
    A parameter larger than ``bucket_bytes`` gets a bucket of its own; a
    dtype change always closes the current bucket (mixed bf16/f32 grads
    never share a flat buffer).
    """
    bucket_bytes = bucket_bytes or choose_bucket_bytes()
    norm = [(k, tuple(s), _np.dtype(d)) for k, s, d in entries]
    if reverse:
        norm = norm[::-1]
    buckets, cur, cur_bytes = [], [], 0
    for key, shape, dtype in norm:
        n = int(_np.prod(shape, dtype=_np.int64)) if len(shape) else 1
        nbytes = n * dtype.itemsize
        if cur and (dtype != cur[0][2] or cur_bytes + nbytes > bucket_bytes):
            buckets.append(Bucket(cur))
            cur, cur_bytes = [], 0
        cur.append((key, shape, dtype))
        cur_bytes += nbytes
        if cur_bytes >= bucket_bytes:
            buckets.append(Bucket(cur))
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(Bucket(cur))
    return buckets


class GradReducer:
    """Traced bucketed all-reduce over a named mesh axis.

    Built once per compiled step from the gradients' (name, shape, dtype)
    entries; ``reduce`` must be called inside a ``shard_map`` (or pmap)
    body that binds ``axis_name``. Host-side ``stats()`` never touches the
    device — it is the telemetry source for ``ddp/*`` counters.
    """

    def __init__(self, entries, axis_name=None, bucket_bytes=None,
                 axis_size=None, device_kind=None, sparse=None):
        self.axis_name = axis_name or flags.ddp_axis
        self.bucket_bytes = int(
            bucket_bytes or choose_bucket_bytes(device_kind))
        self.buckets = partition_buckets(entries, self.bucket_bytes)
        self.comm_bytes = sum(b.nbytes for b in self.buckets)
        self.axis_size = axis_size
        self._device_kind = device_kind
        # sparse bucket kind: {key: SparseBucket} — these keys travel as
        # (ids, values) contribution pairs, never as dense tensors
        self.sparse = {}
        for sb in (sparse or ()):
            if not isinstance(sb, SparseBucket):
                sb = SparseBucket(*sb)
            self.sparse[sb.key] = sb
        self.sparse_comm_bytes = sum(
            sb.comm_bytes(self.axis_size or 1)
            for sb in self.sparse.values())
        self.sparse_densified_bytes = sum(
            sb.densified_bytes() for sb in self.sparse.values())

    def reduce(self, grads):
        """Sum a ``{name: grad}`` dict over ``axis_name``, one fused psum
        per bucket, in reverse-production order. Traced; returns a dict
        with the same keys.

        Keys registered as sparse carry ``(ids, values)`` contribution
        pairs instead of dense arrays; they are exchanged with
        all_gather and coalesced in sorted-id order
        (:func:`coalesce_sparse_grad`) — the returned dict holds their
        DENSE ``(rows, dim)`` form, so optimizers downstream are
        oblivious to how the grad traveled."""
        import jax
        import jax.numpy as jnp
        out = {}
        for key, sb in self.sparse.items():
            if key not in grads:
                continue
            ids, values = grads[key]
            out[key] = coalesce_sparse_grad(
                ids, values, sb.rows,
                axis_name=self.axis_name if (self.axis_size or 1) > 1
                else None)
        for b in self.buckets:
            if len(b.keys) == 1:
                k = b.keys[0]
                out[k] = jax.lax.psum(grads[k], self.axis_name)
                continue
            flat = jnp.concatenate([jnp.ravel(grads[k]) for k in b.keys])
            flat = jax.lax.psum(flat, self.axis_name)
            off = 0
            for k, shape, size in zip(b.keys, b.shapes, b.sizes):
                out[k] = jax.lax.reshape(flat[off:off + size], shape)
                off += size
        return out

    def stats(self):
        """Host-held summary for telemetry/bench (zero device syncs)."""
        sizes = [b.nbytes for b in self.buckets]
        out = {
            "buckets": len(self.buckets),
            "bucket_bytes": sizes,
            # the interconnect-table policy value this reducer planned
            # against (MXNET_DDP_BUCKET_MB override included) — lets
            # dashboards and tests cross-check the plan against the ICI
            # table without re-deriving it
            "bucket_bytes_model": choose_bucket_bytes(self._device_kind),
            "bucket_bytes_plan": self.bucket_bytes,
            "comm_bytes": self.comm_bytes,
            "overlap_ms": estimate_overlap_ms(
                sizes, self.axis_size or 1, self._device_kind),
        }
        if self.sparse:
            out["sparse_buckets"] = len(self.sparse)
            out["sparse_comm_bytes"] = self.sparse_comm_bytes
            out["sparse_densified_bytes"] = self.sparse_densified_bytes
            if self.sparse_comm_bytes:
                out["sparse_compression"] = round(
                    self.sparse_densified_bytes
                    / self.sparse_comm_bytes, 3)
        return out


def estimate_overlap_ms(bucket_nbytes, axis_size, device_kind=None):
    """Model-estimated collective time hideable under backward compute:
    ring all-reduce transfer time of every bucket except the last to
    close (the first layers' gradients end the backward pass — nothing
    remains to overlap them with). Chip-free; used for the
    ``ddp/overlap_ms`` gauge and the bench ``overlap_frac``."""
    if axis_size <= 1 or len(bucket_nbytes) <= 1:
        return 0.0
    bw = _perfmodel.interconnect_bytes_per_s(
        device_kind or _perfmodel.modelled_device_kind())
    ring = 2.0 * (axis_size - 1) / axis_size
    return sum(ring * b / bw for b in bucket_nbytes[:-1]) * 1e3


_MESHES = {}


def process_mesh(axis_name=None):
    """The 1-D data-parallel mesh: EVERY addressable-or-not device in the
    process group, ordered by (process_index, id), on one ``dp`` axis.
    On a CPU test fleet that is one device per process; on a pod slice it
    is every chip. Cached per axis name (Mesh identity keys jit caches)."""
    axis_name = axis_name or flags.ddp_axis
    mesh = _MESHES.get(axis_name)
    if mesh is None:
        import jax
        from jax.sharding import Mesh
        devs = sorted(jax.devices(),
                      key=lambda d: (d.process_index, d.id))
        mesh = Mesh(_np.array(devs), (axis_name,))
        _MESHES[axis_name] = mesh
    return mesh


def to_global(value, mesh, spec):
    """Promote a process-local array to a global array on ``mesh`` with
    ``spec`` (the multi-host shard_map input contract). Leaves already on
    ``mesh`` pass through — after the first step the rebound params/opt
    state are global and must not be re-converted."""
    sharding = getattr(value, "sharding", None)
    if sharding is not None and getattr(sharding, "mesh", None) == mesh:
        return value
    from jax.experimental import multihost_utils
    return multihost_utils.host_local_array_to_global_array(
        value, mesh, spec)


def from_global(value, mesh, spec):
    """Demote a global array back to this process's local view (the
    per-rank outputs the host metric/commit path consumes)."""
    from jax.experimental import multihost_utils
    return multihost_utils.global_array_to_host_local_array(
        value, mesh, spec)
