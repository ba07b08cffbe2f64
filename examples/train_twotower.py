#!/usr/bin/env python
"""Two-tower retrieval trainer over the sharded embedding subsystem
(mxnet_tpu.embed) — the PR-15 end-to-end demo.

Pure-embedding matrix factorization: a USER table and an ITEM table,
dot-product score, L2 loss on synthetic low-rank ratings with Zipf-
skewed traffic (the access pattern that makes a hot-row cache work).
Every parameter gets a canonical sparse gradient, which is what makes
the cross-path bitwise checks below possible at all.

Three training paths over the SAME stream, all landing bitwise-equal
final tables:

1. ``--mesh 1``     — 1-rank dense reference (``jnp.take`` VJP).
2. ``--mesh dp,tp`` — tables row-sharded over the mesh
   (:class:`ShardedEmbedding`), lookups via the all-to-all core inside
   ``shard_map``; the autodiff transpose scatter-adds gradient
   contributions in global batch order, so the update is bitwise-equal
   to path 1 (the chip-free fleet gate).
3. ``--capacity N`` — hot-row cache + host spill
   (:class:`HotRowCache`): the device holds N rows, the logical table
   can exceed ``--host-budget-mb``-bounded host memory by lazy row
   init, and per-row update arithmetic is slot-independent — so the
   final table is bitwise-equal to paths 1 and 2 at ANY capacity.

Per ``--window`` steps the trainer publishes host-held telemetry
(``embed/cache_hit_rate``, ``embed/spill_bytes``,
``ddp/sparse_comm_bytes`` — zero extra d2h), and at the end exports
the trained towers as a format_version-6 ``.mxtpu`` recommend artifact
(serve it: ``python -m mxnet_tpu.tools.serve --artifact out.mxtpu``,
then ``POST /v1/recommend``).

``--recordio PREFIX`` swaps the in-process generator for a streamed
feed: the interactions come from a ``tools/make_recordio.py twotower``
shard set via :class:`mxnet_tpu.data.ShardedRecordStream`
(docs/data.md) into the same up-front arrays, so all three paths stay
bitwise-comparable over streamed data too.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def zipf_ids(rng, n, rows, a=1.2):
    """Zipf-skewed row ids in [0, rows) — heavier head for smaller a-1."""
    ids = rng.zipf(a, size=n)
    return ((ids - 1) % rows).astype("int64")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--users", type=int, default=512)
    p.add_argument("--items", type=int, default=128)
    p.add_argument("--dim", type=int, default=16)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--lr", type=float, default=0.5)
    p.add_argument("--zipf", type=float, default=1.3)
    p.add_argument("--mesh", default="2,2",
                   help="'1' for the dense 1-rank path, or 'DP,TP' "
                        "(e.g. 2,2) for the sharded mesh path")
    p.add_argument("--capacity", type=int, default=96,
                   help="hot-row cache rows for the cache+spill path "
                        "(0 disables that path)")
    p.add_argument("--host-budget-mb", type=float, default=0.0,
                   help="spill-store budget; 0 = unbounded")
    p.add_argument("--window", type=int, default=20,
                   help="telemetry publish window (steps)")
    p.add_argument("--out", default=None,
                   help="write the trained towers as a recommend "
                        ".mxtpu artifact")
    p.add_argument("--recordio", default=None, metavar="PREFIX",
                   help="stream the (user, item, rating) interactions "
                        "from a tools/make_recordio.py twotower shard "
                        "set (PREFIX-00000.rec ...) instead of "
                        "generating them in-process; --users/--items "
                        "must cover the packed id range")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--device", default=None)
    args = p.parse_args()

    if args.device != "tpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % args.devices)
        from _common import maybe_force_cpu
        maybe_force_cpu(["--device", "cpu"])

    import time

    import numpy as np
    import jax
    import jax.numpy as jnp
    import mxnet_tpu  # noqa: F401  (platform pinning, registry)
    from mxnet_tpu import telemetry
    from mxnet_tpu.embed import (HotRowCache, ShardedEmbedding,
                                 SpillStore, row_init)
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.ddp import SparseBucket

    U, I, D, B = args.users, args.items, args.dim, args.batch_size
    rng = np.random.RandomState(0)
    if args.recordio:
        # streaming feed (docs/data.md): fill the SAME up-front
        # (steps, B) arrays all three paths consume from a
        # make_recordio twotower shard set, so the cross-path bitwise
        # checks hold unchanged for streamed interactions.
        import glob

        from mxnet_tpu import recordio as rio
        from mxnet_tpu.data import ShardedRecordStream
        recs = sorted(glob.glob(args.recordio + "-*.rec"))
        if not recs:
            raise SystemExit("no shards match %s-*.rec — pack one with "
                             "tools/make_recordio.py twotower"
                             % args.recordio)
        stream = ShardedRecordStream(recs, shuffle=True, seed=0)
        need = args.steps * B
        triples = np.empty((need, 3), dtype="f4")
        got = 0
        while got < need:
            before = got
            for rec in stream:
                _, payload = rio.unpack(rec)
                triples[got] = np.frombuffer(payload, dtype="<f4", count=3)
                got += 1
                if got == need:
                    break
            if got == before:
                raise SystemExit("empty recordio set: %r" % recs)
            if got < need:
                stream.next_epoch()   # set smaller than steps*B: reuse
        u_ids = triples[:, 0].astype("int64").reshape(args.steps, B)
        i_ids = triples[:, 1].astype("int64").reshape(args.steps, B)
        if u_ids.max() >= U or i_ids.max() >= I:
            raise SystemExit(
                "packed ids exceed --users/--items (%d/%d): pass at "
                "least --users %d --items %d"
                % (U, I, int(u_ids.max()) + 1, int(i_ids.max()) + 1))
        ratings = triples[:, 2].reshape(args.steps, B).copy()
    else:
        # learnable signal: ratings from a hidden low-rank model
        gt_u = rng.randn(U, 8).astype("f4") / np.sqrt(8)
        gt_i = rng.randn(I, 8).astype("f4") / np.sqrt(8)
        u_ids = zipf_ids(rng, args.steps * B, U, args.zipf).reshape(
            args.steps, B)
        i_ids = zipf_ids(rng, args.steps * B, I, args.zipf).reshape(
            args.steps, B)
        ratings = ((gt_u[u_ids] * gt_i[i_ids]).sum(-1)
                   + 0.01 * rng.randn(args.steps, B)).astype("f4")
    lr = np.float32(args.lr)

    # -- path 1/2: dense or mesh-sharded tables ----------------------------
    shape = [int(s) for s in args.mesh.split(",")]
    if len(shape) == 1 and shape[0] == 1:
        mesh, axes = None, None
    else:
        mesh = make_mesh({"dp": shape[0], "tp": shape[1]})
        axes = ("dp", "tp")
    emb_u = ShardedEmbedding(U, D, mesh=mesh, axis_names=axes, seed=1)
    emb_i = ShardedEmbedding(I, D, mesh=mesh, axis_names=axes, seed=2)

    def loss_core(u_tab, i_tab, u, i, r, n_global):
        uv = emb_u.lookup(u_tab, u)
        iv = emb_i.lookup(i_tab, i)
        err = (uv * iv).sum(-1) - r
        return (err ** 2).sum() / n_global

    if mesh is None:
        def step_fn(u_tab, i_tab, u, i, r):
            loss, (gu, gi) = jax.value_and_grad(
                loss_core, argnums=(0, 1))(u_tab, i_tab, u, i, r, B)
            return u_tab - lr * gu, i_tab - lr * gi, loss
    else:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        ax = emb_u.axis_name

        def sharded_step(u_tab, i_tab, u, i, r):
            # grad of the LOCAL partial (cotangent 1 per shard; every
            # rank's contribution reaches the owner stripe through the
            # all-to-all transpose); psum only the REPORTED loss —
            # psum inside the grad would multiply cotangents by the
            # axis size
            loss, (gu, gi) = jax.value_and_grad(
                loss_core, argnums=(0, 1))(u_tab, i_tab, u, i, r, B)
            return (u_tab - lr * gu, i_tab - lr * gi,
                    jax.lax.psum(loss, ax))

        step_fn = shard_map(
            sharded_step, mesh=mesh,
            in_specs=(emb_u.table_spec, emb_i.table_spec,
                      P(ax), P(ax), P(ax)),
            out_specs=(emb_u.table_spec, emb_i.table_spec, P()),
            check_vma=False)
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    # host-held sparse-DDP exchange plan for telemetry: what a
    # dp-replicated variant of these tables would move per step,
    # coalesced vs densified (parallel/ddp.py sparse bucket kind)
    n_ranks = 1 if mesh is None else emb_u.num_shards
    sparse_plan = [SparseBucket("user", B // max(1, n_ranks), D, U),
                   SparseBucket("item", B // max(1, n_ranks), D, I)]
    sparse_comm = sum(sb.comm_bytes(n_ranks) for sb in sparse_plan)
    densified = sum(sb.densified_bytes() for sb in sparse_plan)

    u_tab = emb_u.device_put(emb_u.init())
    i_tab = emb_i.device_put(emb_i.init())
    losses, t0 = [], time.perf_counter()
    for s in range(args.steps):
        u_tab, i_tab, loss = step_fn(u_tab, i_tab,
                                     jnp.asarray(u_ids[s]),
                                     jnp.asarray(i_ids[s]),
                                     jnp.asarray(ratings[s]))
        if (s + 1) % args.window == 0:
            losses.append(float(loss))    # ONE d2h per window
            telemetry.publish_window(
                steps=args.window,
                window_s=time.perf_counter() - t0,
                examples=args.window * B, global_step=s + 1,
                source="twotower/%s" % ("mesh" if mesh else "dense"),
                ddp={"buckets": len(sparse_plan),
                     "comm_bytes": 0, "overlap_ms": 0.0,
                     "sparse_comm_bytes": sparse_comm * args.window})
            t0 = time.perf_counter()
    mesh_u = np.asarray(jax.device_get(u_tab))[:U]
    mesh_i = np.asarray(jax.device_get(i_tab))[:I]
    print("[%s] loss %.4f -> %.4f  (sparse comm %.1f KiB/step, "
          "densified %.1f KiB, %.0fx)"
          % ("mesh %dx%d" % tuple(shape) if mesh else "dense",
             losses[0], losses[-1], sparse_comm / 1024,
             densified / 1024, densified / max(1, sparse_comm)))
    assert losses[-1] < losses[0], "two-tower training did not improve"

    # -- path 3: hot-row cache + host spill --------------------------------
    if args.capacity > 0:
        budget = (int(args.host_budget_mb * (1 << 20))
                  if args.host_budget_mb > 0 else None)
        store_u = SpillStore(U, D, seed=1, budget_bytes=budget)
        store_i = SpillStore(I, D, seed=2, budget_bytes=budget)
        cache_u = HotRowCache(store_u, args.capacity)
        cache_i = HotRowCache(store_i, min(args.capacity, I))

        @jax.jit
        def cache_step(u_buf, i_buf, us, isl, r):
            uv = u_buf[us]
            iv = i_buf[isl]
            err = (uv * iv).sum(-1) - r
            loss = (err ** 2).sum() / r.shape[0]
            d = (2.0 / r.shape[0]) * err
            # coalesce per row FIRST (position-ordered scatter-add: the
            # same left fold as the dense take VJP), THEN one update per
            # row — bitwise-equal to the dense path, slot-independent
            gu = jnp.zeros_like(u_buf).at[us].add(d[:, None] * iv)
            gi = jnp.zeros_like(i_buf).at[isl].add(d[:, None] * uv)
            return u_buf - lr * gu, i_buf - lr * gi, loss

        cache_step = jax.jit(cache_step, donate_argnums=(0, 1))
        last_spill = 0
        closses, t0 = [], time.perf_counter()
        for s in range(args.steps):
            us = cache_u.ensure(u_ids[s])
            isl = cache_i.ensure(i_ids[s])
            cache_u.buf, cache_i.buf, loss = cache_step(
                cache_u.buf, cache_i.buf, us, isl,
                jnp.asarray(ratings[s]))
            cache_u.note_updated(u_ids[s])
            cache_i.note_updated(i_ids[s])
            if (s + 1) % args.window == 0:
                closses.append(float(loss))
                spill = (cache_u.spill_bytes + cache_i.spill_bytes)
                telemetry.publish_window(
                    steps=args.window,
                    window_s=time.perf_counter() - t0,
                    examples=args.window * B, global_step=s + 1,
                    source="twotower/cache",
                    embed={"hit_rate": cache_u.hit_rate(),
                           "spill_bytes": spill - last_spill})
                last_spill = spill
                t0 = time.perf_counter()
        cache_u.flush()
        cache_i.flush()
        fin_u = store_u.peek(np.arange(U))
        fin_i = store_i.peek(np.arange(I))
        st = cache_u.stats()
        print("[cache %d] loss %.4f -> %.4f  (hit rate %.3f, spilled "
              "%d KiB, host-resident %d/%d KiB)"
              % (args.capacity, closses[0], closses[-1], st["hit_rate"],
                 st["spill_bytes"] // 1024,
                 st["host_resident_bytes"] // 1024,
                 st["logical_bytes"] // 1024))
        exact_u = np.array_equal(fin_u, mesh_u)
        exact_i = np.array_equal(fin_i, mesh_i)
        print("bitwise cache-vs-%s: user=%s item=%s"
              % ("mesh" if mesh else "dense", exact_u, exact_i))
        assert exact_u and exact_i, (
            "cache+spill final tables diverged from the reference path")
        out_u, out_i = fin_u, fin_i
    else:
        out_u, out_i = mesh_u, mesh_i

    if args.out:
        from mxnet_tpu.embed.serve import export_recommend
        meta = export_recommend(out_u, out_i, args.out,
                                max_ids=64, k=10)
        print("exported %s (format_version %d, %dx%d users, %d items)"
              % (args.out, meta["format_version"], U, D, I))
    print("two-tower OK")


if __name__ == "__main__":
    main()
