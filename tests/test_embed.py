"""PR-15 sharded embedding subsystem: the chip-free fleet gates.

What is pinned here (ISSUE.md acceptance):

* the mesh all-to-all lookup is BITWISE-equal to the 1-rank dense
  ``take`` — forward AND gradient (the stable-sort / position-ordered
  send-buffer discipline of embed/table.py);
* out-of-range ids CLIP identically on every dispatch path (Pallas
  scalar-prefetch kernel, jnp.take fallback, ops/nn.py
  sparse_embedding, kernels/take.py gather_pages), fwd and grad;
* the sparse DDP bucket kind exchanges coalesced contributions that
  reduce BITWISE-equal to the densified oracle, at >= 10x fewer bytes;
* the two-tower fleet drill: a table whose LOGICAL size exceeds the
  configured host budget trains through cache+spill, and the final
  parameters are bitwise-equal across shardings (1 rank vs 2x2 mesh)
  and across cache capacities;
* the recommend serving leg: format_version-6 round trip, engine
  scores == the numpy oracle, ONE d2h per response batch, MXL511
  clean, gather-unit admission cap, and ``/v1/recommend`` end-to-end
  through the fleet router's least-loaded pick.
"""
import json
import os
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.base import MXNetError
from mxnet_tpu.embed import (HotRowCache, ShardedEmbedding, SpillStore,
                             row_init)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs the 8-virtual-device mesh")


def _mesh22():
    from mxnet_tpu.parallel import make_mesh
    return make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])


# ---------------------------------------------------------------- row init

def test_row_init_is_per_row_and_order_independent():
    a = row_init(7, [3, 11, 5], 16)
    b = row_init(7, [5, 3], 16)
    assert np.array_equal(a[2], b[0]) and np.array_equal(a[0], b[1])
    # different seed, different bits
    assert not np.array_equal(row_init(8, [3], 16)[0], a[0])


# ------------------------------------------------------- lookup bitwise

@needs_mesh
def test_sharded_lookup_bitwise_vs_dense_fwd_and_grad():
    """2x2-mesh all-to-all lookup == 1-rank dense take, bit for bit —
    forward and table gradient. rows=37 exercises stripe padding; the
    id batch includes out-of-range ids (the clip contract) and heavy
    duplication (the scatter-add fold order)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    rows, dim, batch = 37, 8, 16
    mesh = _mesh22()
    emb = ShardedEmbedding(rows, dim, mesh=mesh, axis_names=("dp", "tp"))
    dense = ShardedEmbedding(rows, dim)     # 1-rank layout
    assert emb.padded_rows % emb.num_shards == 0
    table = emb.init(0)                     # (padded_rows, dim) host
    tab_dense = dense.init(0)
    assert np.array_equal(table[:rows], tab_dense[:rows])

    rng = np.random.RandomState(0)
    ids = rng.randint(0, rows, size=(batch,)).astype(np.int64)
    ids[3] = rows + 9           # OOB high -> clips to rows-1
    ids[5] = ids[7] = ids[1]    # duplicates -> grad contributions fold
    targets = rng.randn(batch, dim).astype(np.float32)

    # forward
    got = np.asarray(emb.make_lookup()(emb.device_put(table), ids))
    want = np.asarray(dense.make_lookup()(tab_dense, ids))
    assert np.array_equal(got, want)

    # gradient: grad of the LOCAL partial loss — every rank's
    # contribution reaches the owner stripe through the all-to-all
    # transpose; a psum inside the grad would scale cotangents by the
    # axis size (see examples/train_twotower.py)
    def local_loss(tab, ids_l, tgt_l):
        v = emb.lookup(tab, ids_l)
        return ((v - tgt_l) ** 2).sum()

    g_fn = shard_map(
        lambda t, i, y: jax.grad(local_loss)(t, i, y),
        mesh=mesh,
        in_specs=(emb.table_spec, P(emb.axis_name), P(emb.axis_name)),
        out_specs=emb.table_spec, check_vma=False)
    g_mesh = np.asarray(jax.jit(g_fn)(emb.device_put(table), ids,
                                      targets))

    def dense_loss(tab):
        v = jnp.take(tab, jnp.clip(ids.astype(np.int32), 0, rows - 1),
                     axis=0)
        return ((v - targets) ** 2).sum()

    g_dense = np.asarray(jax.grad(dense_loss)(tab_dense))
    assert np.array_equal(g_mesh[:rows], g_dense[:rows])
    # padded stripe rows are unreachable: zero grad
    assert not g_mesh[rows:].any()


# ------------------------------------------------------------ OOB parity

def test_oob_clip_parity_across_dispatch_paths():
    """ids beyond the vocab (and negative) must clip identically on the
    Pallas kernel, the jnp.take fallback, sparse_embedding, and
    gather_pages — fwd and grad (tier-independent numerics)."""
    from mxnet_tpu.kernels import take as ktake
    from mxnet_tpu.ops import nn as opsnn

    V, D = 12, 128   # D lane-aligned so the kernel guard admits it
    rng = np.random.RandomState(1)
    w = rng.randn(V, D).astype(np.float32)
    ids = np.array([0, 3, V - 1, V + 7, -2, 3], np.int64)
    ref = np.asarray(jnp.take(w, jnp.clip(ids.astype(np.int32), 0,
                                          V - 1), axis=0))

    assert ktake.eligible(w.shape, w.dtype, ids.shape, ids.dtype) is None
    out_k = np.asarray(ktake.take_rows(jnp.asarray(w), jnp.asarray(ids),
                                       interpret=True))
    out_g = np.asarray(ktake.gather_pages(jnp.asarray(w),
                                          jnp.asarray(ids)))
    out_e = np.asarray(opsnn.sparse_embedding(jnp.asarray(ids),
                                              jnp.asarray(w)))
    assert np.array_equal(out_k, ref)
    assert np.array_equal(out_g, ref)
    assert np.array_equal(out_e, ref)

    # grad parity: the kernel's custom_vjp recomputes through jnp.take,
    # so the scatter-add over clipped (duplicated) ids is the same fold
    cot = rng.randn(len(ids), D).astype(np.float32)

    def via(fn):
        return np.asarray(jax.grad(
            lambda t: (fn(t) * cot).sum())(jnp.asarray(w)))

    g_ref = via(lambda t: jnp.take(
        t, jnp.clip(ids.astype(np.int32), 0, V - 1), axis=0))
    g_k = via(lambda t: ktake.take_rows(t, jnp.asarray(ids),
                                        interpret=True))
    g_e = via(lambda t: opsnn.sparse_embedding(jnp.asarray(ids), t))
    assert np.array_equal(g_k, g_ref)
    assert np.array_equal(g_e, g_ref)


# ------------------------------------------------------------- sparse DDP

@needs_mesh
def test_sparse_ddp_bitwise_and_10x_compression():
    """The sparse bucket kind: contributions all-gathered and coalesced
    in sorted-id order reduce BITWISE-equal to the densified psum oracle
    — at >= 10x fewer exchanged bytes for a realistically tall table."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import ddp, make_mesh

    rows, dim, per_rank, ranks = 4096, 16, 8, 4
    mesh = make_mesh({"dp": ranks}, devices=jax.devices()[:ranks])
    rng = np.random.RandomState(2)
    ids = rng.randint(0, rows, size=(ranks * per_rank,)).astype(np.int64)
    ids[1] = ids[9] = ids[17]   # cross-rank duplicates must coalesce
    vals = rng.randn(ranks * per_rank, dim).astype(np.float32)

    sb = ddp.SparseBucket("emb", per_rank, dim, rows)
    red = ddp.GradReducer([("w", (4, 4), "float32")], axis_name="dp",
                          axis_size=ranks, sparse=[sb])
    assert red.sparse_densified_bytes >= 10 * red.sparse_comm_bytes
    assert red.stats()["sparse_compression"] >= 10

    w_grad = rng.randn(ranks, 4, 4).astype(np.float32)

    def body(i_l, v_l, w_l):
        out = red.reduce({"emb": (i_l, v_l), "w": w_l[0]})
        return out["emb"], out["w"]

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P("dp"), P("dp"), P("dp")),
                   out_specs=(P(), P()), check_vma=False)
    dense_emb, dense_w = jax.jit(fn)(
        ids.reshape(ranks, per_rank), vals.reshape(ranks, per_rank, dim),
        w_grad)

    # 1-rank oracle: the same sorted-id scatter-add over the GLOBAL batch
    oracle = np.asarray(ddp.coalesce_sparse_grad(
        jnp.asarray(ids), jnp.asarray(vals), rows))
    assert np.array_equal(np.asarray(dense_emb), oracle)
    assert np.array_equal(np.asarray(dense_w), w_grad.sum(0))


# -------------------------------------------------------- cache + spill

def test_spill_store_budget_gate():
    store = SpillStore(64, 8, seed=0, budget_bytes=10 * 8 * 4)
    assert store.logical_bytes > store.budget_bytes  # table > host budget
    store.put(np.arange(10), np.zeros((10, 8), np.float32))
    with pytest.raises(MXNetError, match="host spill store exceeded"):
        store.put(np.arange(10, 14), np.zeros((4, 8), np.float32))


@needs_mesh
def test_twotower_fleet_bitwise_across_shardings_and_capacities():
    """The chip-free fleet drill: the same two-tower run converges to
    BITWISE-identical tables on (a) the 1-rank dense step, (b) the 2x2
    mesh all-to-all step, and (c) the hot-row cache + host-spill step at
    two different capacities — with the user table's LOGICAL bytes above
    the configured host budget for (c)."""
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    U, I, D, B, steps = 96, 32, 8, 8, 6
    lr = np.float32(0.5)
    rng = np.random.RandomState(3)
    u_ids = rng.randint(0, U, size=(steps, B)).astype(np.int64)
    i_ids = rng.randint(0, I, size=(steps, B)).astype(np.int64)
    ratings = rng.randn(steps, B).astype(np.float32)

    # (a) dense 1-rank reference
    @partial(jax.jit, donate_argnums=(0, 1))
    def dense_step(u_tab, i_tab, us, isl, r):
        uv = jnp.take(u_tab, us.astype(jnp.int32), axis=0)
        iv = jnp.take(i_tab, isl.astype(jnp.int32), axis=0)
        err = (uv * iv).sum(-1) - r
        d = (2.0 / B) * err
        gu = jnp.zeros_like(u_tab).at[us].add(d[:, None] * iv)
        gi = jnp.zeros_like(i_tab).at[isl].add(d[:, None] * uv)
        return u_tab - lr * gu, i_tab - lr * gi

    u_ref = jnp.asarray(row_init(1, np.arange(U), D))
    i_ref = jnp.asarray(row_init(2, np.arange(I), D))
    for s in range(steps):
        u_ref, i_ref = dense_step(u_ref, i_ref, u_ids[s], i_ids[s],
                                  ratings[s])
    u_ref, i_ref = np.asarray(u_ref), np.asarray(i_ref)

    # (b) 2x2 mesh: all-to-all lookup, grad of the LOCAL partial loss
    mesh = _mesh22()
    emb_u = ShardedEmbedding(U, D, mesh=mesh, axis_names=("dp", "tp"),
                             seed=1)
    emb_i = ShardedEmbedding(I, D, mesh=mesh, axis_names=("dp", "tp"),
                             seed=2)
    ax = emb_u.axis_name

    def local_loss(u_tab, i_tab, u, i, r):
        uv = emb_u.lookup(u_tab, u)
        iv = emb_i.lookup(i_tab, i)
        return (((uv * iv).sum(-1) - r) ** 2).sum() / B

    def mesh_step(u_tab, i_tab, u, i, r):
        gu, gi = jax.grad(local_loss, argnums=(0, 1))(u_tab, i_tab,
                                                      u, i, r)
        return u_tab - lr * gu, i_tab - lr * gi

    step_fn = jax.jit(shard_map(
        mesh_step, mesh=mesh,
        in_specs=(emb_u.table_spec, emb_i.table_spec, P(ax), P(ax),
                  P(ax)),
        out_specs=(emb_u.table_spec, emb_i.table_spec),
        check_vma=False), donate_argnums=(0, 1))
    u_tab = emb_u.device_put(emb_u.init())
    i_tab = emb_i.device_put(emb_i.init())
    for s in range(steps):
        u_tab, i_tab = step_fn(u_tab, i_tab, u_ids[s], i_ids[s],
                               ratings[s])
    assert np.array_equal(np.asarray(u_tab)[:U], u_ref)
    assert np.array_equal(np.asarray(i_tab)[:I], i_ref)

    # (c) cache + spill, two capacities; host budget < logical table
    def run_cached(cap):
        budget = (U - 8) * D * 4   # resident host rows must stay below
        store_u = SpillStore(U, D, seed=1, budget_bytes=budget)
        assert store_u.logical_bytes > budget
        store_i = SpillStore(I, D, seed=2)
        cu, ci = HotRowCache(store_u, cap), HotRowCache(store_i, I)

        @partial(jax.jit, donate_argnums=(0, 1))
        def cache_step(u_buf, i_buf, us, isl, r):
            uv, iv = u_buf[us], i_buf[isl]
            err = (uv * iv).sum(-1) - r
            d = (2.0 / B) * err
            # coalesce per row FIRST, then ONE update per row — the
            # fold that keeps this bitwise-equal to the dense step
            gu = jnp.zeros_like(u_buf).at[us].add(d[:, None] * iv)
            gi = jnp.zeros_like(i_buf).at[isl].add(d[:, None] * uv)
            return u_buf - lr * gu, i_buf - lr * gi

        for s in range(steps):
            us, isl = cu.ensure(u_ids[s]), ci.ensure(i_ids[s])
            cu.buf, ci.buf = cache_step(cu.buf, ci.buf, us, isl,
                                        jnp.asarray(ratings[s]))
            cu.note_updated(u_ids[s])
            ci.note_updated(i_ids[s])
        cu.flush(), ci.flush()
        assert cu.stats()["spill_bytes"] > 0   # the cache really spilled
        return (store_u.peek(np.arange(U)), store_i.peek(np.arange(I)))

    for cap in (24, 48):
        u_c, i_c = run_cached(cap)
        assert np.array_equal(u_c, u_ref), "capacity %d diverged" % cap
        assert np.array_equal(i_c, i_ref)


# ------------------------------------------------------ recommend serving

@pytest.fixture(scope="module")
def reco_artifact(tmp_path_factory):
    from mxnet_tpu.embed.serve import export_recommend
    path = str(tmp_path_factory.mktemp("reco") / "twotower.mxtpu")
    U, I, D = 64, 24, 8
    export_recommend(row_init(1, np.arange(U), D),
                     row_init(2, np.arange(I), D), path,
                     max_ids=8, k=5)
    return path


def test_recommend_roundtrip_oracle_one_d2h_and_mxl511(reco_artifact):
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import load_artifact

    model = load_artifact(reco_artifact)
    assert model.meta["format_version"] == 6
    eng = model.engine(capacity=16, buckets=(4,))
    id_lists = [[3, 9, 9, 60], [0], [5, 1, 2]]
    profiler.reset_sync_counters()
    scores, items = eng.recommend_batch(id_lists)
    # ONE d2h for the whole batch (cold cache: no dirty spills yet)
    assert profiler.sync_counters()["d2h"] == 1

    user, corpus = model.user_table, model.item_table
    for j, ids in enumerate(id_lists):
        vec = user[np.asarray(ids)].mean(0)
        want = np.argsort(-(corpus @ vec), kind="stable")[:5]
        assert list(items[j]) == list(want)
        np.testing.assert_allclose(scores[j], (corpus @ vec)[want],
                                   rtol=1e-6)
    assert eng.stats()["gathers"] == sum(len(x) for x in id_lists)
    assert eng.check_discipline() == []     # MXL511 clean


def test_recommend_admission_cap_bills_gather_units(reco_artifact):
    from mxnet_tpu.config import override
    from mxnet_tpu.serve import Server
    from mxnet_tpu.serve.admission import ServerBusy

    with override(serve_max_gathers=4):
        srv = Server(reco_artifact, auto_start=False)
        try:
            req = srv.submit_recommend([1, 2, 3])
            assert req.units == 3           # billed per-request gathers
            with pytest.raises(ServerBusy, match="cost cap"):
                srv.submit_recommend([4, 5, 6])
            srv.start()
            scores, items = req.result(timeout=30)
            assert len(items) == 5
            assert srv.load_status()["load"]["load_s"] >= 0.0
        finally:
            srv.close(drain=False)


def test_recommend_e2e_through_router_least_loaded(reco_artifact):
    """Two recommend replicas behind the fleet router: /v1/recommend
    proxies through the least-loaded pick (gather-derived load_s), both
    replicas take traffic, bad bodies 400."""
    from mxnet_tpu.fleet.router import Router, RouterHTTPFrontEnd
    from mxnet_tpu.serve import Server
    from mxnet_tpu.serve.http import HttpFrontEnd

    servers, fronts = [], []
    router = Router()
    rfe = None
    try:
        for rid in ("r0", "r1"):
            srv = Server(reco_artifact)
            fe = HttpFrontEnd(srv, port=0).start()
            servers.append(srv)
            fronts.append(fe)
            router.registry.register(
                {"id": rid, "url": fe.address, "model": "twotower",
                 "version": "1", "mode": "recommend", "ready": True})
        rfe = RouterHTTPFrontEnd(router, port=0).start()

        used = set()
        for n in range(8):
            body = json.dumps(
                {"ids": [int(x) for x in
                         np.random.RandomState(n).randint(0, 64, 3)],
                 "model": "twotower"}).encode()
            req = urllib.request.Request(
                rfe.address + "/v1/recommend", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
                out = json.loads(resp.read())
            used.add(out["replica"])
            assert len(out["items"]) == len(out["scores"]) == 5
            assert out["gathers"] == 3
        # cold fleet: served-count tie-break round-robins both replicas
        assert used == {"r0", "r1"}

        bad = urllib.request.Request(
            rfe.address + "/v1/recommend",
            data=json.dumps({"ids": "nope"}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=30)
        assert ei.value.code == 400
    finally:
        if rfe is not None:
            rfe.stop()
        for fe in fronts:
            fe.stop(drain=False)
        for srv in servers:
            srv.close(drain=False)
