"""Mesh-partitioned embedding tables with an all-to-all lookup core.

A (rows x dim) table is ROW-sharded across the flattened ``dp``/``tp``
mesh (every device owns a contiguous ``rows_per_shard`` stripe), so the
aggregate table is bounded by fleet HBM, not one chip's. The lookup is
a pure function designed to run INSIDE ``shard_map`` — the same manual
collectives discipline as the PR-8 ``SPMDTrainStep`` ``ddp_bucketed``
step, so the two compose under one mesh:

1. clip ids to the logical row range (the take/Embedding contract —
   dispatch must never change numerics, docs/embeddings.md);
2. bucket ids by OWNER shard (``id // rows_per_shard``) with a stable
   sort, scatter them into a fixed ``(shards, capacity)`` send buffer
   (all-to-all needs equal splits; capacity = the local id count, the
   worst case of every id hashing to one owner);
3. ``jax.lax.all_to_all`` the id buffer, gather the owned rows locally
   through the PR-6 scalar-prefetch kernel tier (D%128 guard and clip
   semantics preserved — :func:`local_gather`), all-to-all the rows
   back, and unpermute.

Determinism is load-bearing, not incidental: the transpose of this
program scatter-adds gradient contributions into each owner stripe in
(source-rank, batch-position) order — exactly the left-fold a 1-rank
``jnp.take`` VJP performs over the same global batch — so training is
**bitwise-equal across shardings** (the chip-free fleet gate in
tests/test_embed.py). That only holds because the sort is stable and
the send-buffer layout is position-ordered; keep it that way.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError

__all__ = ["ShardedEmbedding", "sharded_lookup", "local_gather",
           "row_init"]


def row_init(seed, row_ids, dim, dtype="float32", scale=0.01):
    """Deterministic PER-ROW initializer: row ``r`` has the same bits
    whether it is materialized by a mesh shard, the host spill store's
    first touch, or a 1-rank reference run — the property every
    bitwise-across-shardings/capacities test leans on. Counter-based
    (Philox keyed by (seed, row)), so cost is per *touched* row and
    order-independent."""
    rows = _np.atleast_1d(_np.asarray(row_ids, dtype=_np.int64))
    out = _np.empty((rows.size, dim), dtype=_np.dtype(dtype))
    for i, r in enumerate(rows):
        g = _np.random.Generator(_np.random.Philox(key=[seed, int(r)]))
        out[i] = (g.standard_normal(dim) * scale).astype(out.dtype)
    return out


def local_gather(shard, idx):
    """Row gather on one shard through the kernel tier.

    ``idx`` must already be clipped to the shard's local range — both
    the Pallas scalar-prefetch kernel and the ``jnp.take(mode="clip")``
    fallback clamp, so dispatch never changes out-of-range numerics
    (the ops/nn.py Embedding contract; tests/test_embed.py pins the
    kernel/fallback parity on OOB ids, fwd AND grad)."""
    import jax.numpy as jnp
    from ..kernels import tier as _ktier
    if _ktier.enabled():
        from ..kernels import take as _ktake
        reason = _ktake.eligible(shard.shape, shard.dtype, idx.shape,
                                 idx.dtype)
        go, cfg = _ktier.should_dispatch(
            _ktake.OP_NAME,
            _ktake.shape_key_shapes(shard.shape, idx.shape),
            shard.dtype, guard_reason=reason)
        if go:
            return _ktake.take_rows(shard, idx, config=cfg)
    return jnp.take(shard, idx.astype(jnp.int32), axis=0, mode="clip")


def sharded_lookup(shard, ids, *, rows, rows_per_shard, num_shards,
                   axis_name):
    """Pure lookup core for use inside ``shard_map``.

    ``shard`` is this device's ``(rows_per_shard, dim)`` stripe; ``ids``
    is its local slice of the batch (any int shape), holding GLOBAL row
    ids. Returns ``ids.shape + (dim,)`` embeddings. ``axis_name`` may be
    one mesh axis or a tuple (the flattened ``("dp", "tp")`` mesh);
    ``num_shards`` is the product of those axis sizes. Single-shard
    meshes short-circuit to a local gather — no collectives, so the
    1-rank path is exactly the dense ``take``."""
    import jax
    import jax.numpy as jnp

    id_shape = ids.shape
    flat = jnp.clip(ids.astype(jnp.int32).reshape(-1), 0, rows - 1)
    if num_shards == 1:
        out = local_gather(shard, flat)
        return out.reshape(id_shape + (shard.shape[-1],))
    cap = flat.shape[0]              # per-peer capacity (worst case)
    me = jax.lax.axis_index(axis_name)
    owner = flat // rows_per_shard   # already < num_shards (ids clipped)
    # stable sort by owner: within one owner bucket the batch-position
    # order survives, which is what makes the transpose's scatter-add a
    # position-ordered left fold (see module docstring)
    order = jnp.argsort(owner, stable=True)
    s_owner = owner[order]
    s_ids = flat[order]
    counts = jnp.bincount(owner, length=num_shards).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    slot = jnp.arange(cap, dtype=jnp.int32) - starts[s_owner]
    dest = s_owner * cap + slot
    send = jnp.zeros((num_shards * cap,), jnp.int32).at[dest].set(s_ids)
    # row p of the received buffer = the ids peer p wants from my stripe
    want = jax.lax.all_to_all(send.reshape(num_shards, cap),
                              axis_name, 0, 0)
    loc = jnp.clip(want.reshape(-1) - me * rows_per_shard,
                   0, rows_per_shard - 1)
    rows_out = local_gather(shard, loc)
    rows_out = rows_out.reshape(num_shards, cap, shard.shape[-1])
    # row j of the return = my requested rows, in the order I sent them
    back = jax.lax.all_to_all(rows_out, axis_name, 0, 0)
    back = back.reshape(num_shards * cap, shard.shape[-1])
    gather_at = jnp.zeros((cap,), jnp.int32).at[order].set(dest)
    return back[gather_at].reshape(id_shape + (shard.shape[-1],))


class ShardedEmbedding:
    """A (rows x dim) table row-sharded over a mesh.

    Holds the STATIC plan only (padded rows, stripe size, axis names,
    partition specs) — parameters stay in the caller's pytree like every
    other mxnet_tpu layer, so checkpointing/donation/DDP treat the table
    like any param. ``mesh=None`` is the 1-rank layout (no collectives).

    Typical shard_map composition (the two-tower trainer)::

        emb = ShardedEmbedding(rows, dim, mesh=mesh,
                               axis_names=("dp", "tp"))
        table = emb.init(seed)                    # np (padded_rows, dim)
        def step(table_shard, ids_local, ...):    # inside shard_map
            vecs = emb.lookup(table_shard, ids_local)
            ...
        shard_map(step, mesh=mesh,
                  in_specs=(emb.table_spec, P(emb.axis_names), ...), ...)
    """

    def __init__(self, rows, dim, mesh=None, axis_names=None,
                 dtype="float32", seed=0, name="embed"):
        if rows <= 0 or dim <= 0:
            raise MXNetError("ShardedEmbedding: rows and dim must be "
                             "positive (got %d x %d)" % (rows, dim))
        self.rows = int(rows)
        self.dim = int(dim)
        self.dtype = _np.dtype(dtype)
        self.mesh = mesh
        self.seed = int(seed)
        self.name = name
        if mesh is None:
            self.axis_names = ()
            self.num_shards = 1
        else:
            names = tuple(axis_names) if axis_names else tuple(
                mesh.axis_names)
            for ax in names:
                if ax not in mesh.axis_names:
                    raise MXNetError(
                        "ShardedEmbedding: axis %r not in mesh axes %s"
                        % (ax, tuple(mesh.axis_names)))
            self.axis_names = names
            self.num_shards = int(_np.prod(
                [mesh.shape[ax] for ax in names], dtype=_np.int64))
        # pad the stripe so every shard is equal-sized; padded rows are
        # unreachable (ids clip to rows-1) and their grads are zero
        self.rows_per_shard = -(-self.rows // self.num_shards)
        self.padded_rows = self.rows_per_shard * self.num_shards

    @property
    def axis_name(self):
        """The all-to-all axis argument: one name or the tuple."""
        if self.num_shards == 1:
            return None
        return (self.axis_names[0] if len(self.axis_names) == 1
                else self.axis_names)

    @property
    def table_spec(self):
        """PartitionSpec for the (padded_rows, dim) table."""
        from jax.sharding import PartitionSpec as P
        if self.num_shards == 1:
            return P(None, None)
        return P(self.axis_name, None)

    def init(self, seed=None):
        """Full (padded_rows, dim) host table from :func:`row_init` —
        bitwise-identical rows to what a spill store or another mesh
        shape would materialize for the same seed."""
        seed = self.seed if seed is None else int(seed)
        tab = _np.zeros((self.padded_rows, self.dim), dtype=self.dtype)
        tab[:self.rows] = row_init(seed, _np.arange(self.rows),
                                   self.dim, self.dtype)
        return tab

    def device_put(self, table):
        """Place a host table onto the mesh with the row sharding."""
        import jax
        if self.mesh is None:
            return jax.device_put(table)
        from jax.sharding import NamedSharding
        return jax.device_put(
            table, NamedSharding(self.mesh, self.table_spec))

    def lookup(self, shard, ids):
        """The pure core, pre-bound to this table's plan. Call inside
        ``shard_map`` (or anywhere when ``mesh=None``)."""
        return sharded_lookup(
            shard, ids, rows=self.rows,
            rows_per_shard=self.rows_per_shard,
            num_shards=self.num_shards,
            axis_name=self.axis_name if self.num_shards > 1 else "_")

    def make_lookup(self):
        """A jitted standalone ``(table, ids) -> vecs`` over the mesh
        (shard_map-wrapped when sharded) — the serving-side and test
        entry point; training steps inline :meth:`lookup` instead."""
        import jax
        if self.num_shards == 1:
            return jax.jit(self.lookup)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        fn = shard_map(
            self.lookup, mesh=self.mesh,
            in_specs=(self.table_spec, P(self.axis_name)),
            out_specs=P(self.axis_name), check_vma=False)
        return jax.jit(fn)

    def comm_bytes_per_lookup(self, batch_ids):
        """Host-held all-to-all volume estimate for one lookup of
        ``batch_ids`` ids: the id exchange plus the row return (each
        crosses the mesh once). Telemetry/bench material — never a
        device read."""
        if self.num_shards == 1:
            return 0
        cap = -(-int(batch_ids) // self.num_shards) * self.num_shards
        ids_b = cap * self.num_shards * 4
        rows_b = cap * self.num_shards * self.dim * self.dtype.itemsize
        return ids_b + rows_b

    def __repr__(self):
        return ("ShardedEmbedding(%dx%d, shards=%d, stripe=%d, axes=%s)"
                % (self.rows, self.dim, self.num_shards,
                   self.rows_per_shard, list(self.axis_names)))
