"""Bucketed, backward-overlapped gradient all-reduce (parallel/ddp.py).

In-process half: bucket partitioning edges, GradReducer numerics on the
8-virtual-device mesh, the SPMDTrainStep ``ddp_bucketed`` mode against
the GSPMD reference (dp-only and dp x tp), Module.fit's DDP path vs the
kvstore path, and MXL507 over the really-lowered step.

Fleet half: N real processes through ``tools/launch.py --ddp`` (2 and 4
ranks) running tests/ddp_train_worker.py — bitwise parity across bucket
sizes incl. optimizer state, cross-rank equality, and (slow) an injected
kill survived by supervised restart with MXNET_DDP on.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import config
from mxnet_tpu.parallel import ddp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(ROOT, "tools", "launch.py")
WORKER = os.path.join(ROOT, "tests", "ddp_train_worker.py")

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs the 8-virtual-device mesh")


# ------------------------------------------------------------ bucket plan

def test_partition_buckets_reverse_order_and_size_bound():
    entries = [("a", (8,), np.float32), ("b", (8,), np.float32),
               ("c", (8,), np.float32)]
    buckets = ddp.partition_buckets(entries, bucket_bytes=64)
    # reverse production order: the LAST param leads bucket 0
    assert buckets[0].keys == ("c", "b")
    assert buckets[1].keys == ("a",)
    assert all(b.nbytes <= 64 for b in buckets)


def test_partition_buckets_oversized_param_gets_own_bucket():
    entries = [("small", (4,), np.float32), ("big", (1024,), np.float32),
               ("tail", (4,), np.float32)]
    buckets = ddp.partition_buckets(entries, bucket_bytes=64)
    big = [b for b in buckets if "big" in b.keys]
    assert len(big) == 1 and big[0].keys == ("big",)


def test_partition_buckets_dtype_change_closes_bucket():
    entries = [("f1", (4,), np.float32), ("h1", (4,), np.float16),
               ("h2", (4,), np.float16)]
    buckets = ddp.partition_buckets(entries, bucket_bytes=1 << 20,
                                    reverse=False)
    assert [b.dtype for b in buckets] == [np.dtype(np.float32),
                                          np.dtype(np.float16)]
    assert buckets[1].keys == ("h1", "h2")


def test_choose_bucket_bytes_override_and_model():
    with config.override(ddp_bucket_mb=2.0):
        assert ddp.choose_bucket_bytes() == 2 << 20
    with config.override(ddp_bucket_mb=0.0):
        b = ddp.choose_bucket_bytes("TPU v5p")
        assert (1 << 20) <= b <= (64 << 20)


def test_choose_bucket_bytes_tracks_interconnect_table():
    """The auto-sized bucket is the ICI-table formula, clamped — pinned
    per device kind so a table edit shows up as a policy change here."""
    from mxnet_tpu import perfmodel
    with config.override(ddp_bucket_mb=0.0):
        for kind in ("TPU v5p", "TPU v4", "TPU v3", "TPU v2"):
            bw = perfmodel.interconnect_bytes_per_s(kind)
            want = int(min(max(bw * 20e-6 / 0.05, 1 << 20), 64 << 20))
            assert ddp.choose_bucket_bytes(kind) == want
        # a device that is not in the table is an error, not a v5e
        with pytest.raises(KeyError, match="weird"):
            ddp.choose_bucket_bytes("weird")
        # fast ICI saturates the 64 MiB overlap ceiling; v2/v3 land
        # mid-range where the launch-amortization formula is live
        assert ddp.choose_bucket_bytes("TPU v5p") == 64 << 20
        assert ddp.choose_bucket_bytes("TPU v3") == 32_800_000
        assert ddp.choose_bucket_bytes("TPU v2") == 24_800_000


def test_grad_reducer_stats_model_vs_plan():
    """stats() must report both the ICI-table policy value (model) and
    what this reducer actually used (plan), so dashboards can spot a
    plan that drifted from policy."""
    entries = [("w", (256, 256), np.float32), ("b", (256,), np.float32)]
    with config.override(ddp_bucket_mb=0.0):
        auto = ddp.GradReducer(entries, axis_name="dp",
                               device_kind="TPU v3")
        st = auto.stats()
        assert st["bucket_bytes_model"] == ddp.choose_bucket_bytes("TPU v3")
        assert st["bucket_bytes_plan"] == st["bucket_bytes_model"]
        # an explicit bucket_bytes is the plan; the model stays on-table
        pinned = ddp.GradReducer(entries, axis_name="dp",
                                 bucket_bytes=4 << 20,
                                 device_kind="TPU v3")
        st = pinned.stats()
        assert st["bucket_bytes_plan"] == 4 << 20
        assert st["bucket_bytes_model"] == ddp.choose_bucket_bytes("TPU v3")
    # MXNET_DDP_BUCKET_MB is an operator decision: it IS the policy,
    # so model and plan agree under the override
    with config.override(ddp_bucket_mb=2.0):
        st = ddp.GradReducer(entries, axis_name="dp",
                             device_kind="TPU v3").stats()
        assert st["bucket_bytes_model"] == 2 << 20
        assert st["bucket_bytes_plan"] == 2 << 20


def test_estimate_overlap_excludes_last_bucket():
    assert ddp.estimate_overlap_ms([100, 100], 1) == 0.0       # no dp
    assert ddp.estimate_overlap_ms([100], 4) == 0.0            # one bucket
    two = ddp.estimate_overlap_ms([100, 100], 4, "TPU v4")
    three = ddp.estimate_overlap_ms([100, 100, 100], 4, "TPU v4")
    assert three == pytest.approx(2 * two)                     # last free


# -------------------------------------------------------- traced reducer

@needs_mesh
def test_grad_reducer_psum_matches_sum():
    from jax import shard_map
    mesh = ddp.process_mesh()
    n = mesh.size
    entries = [("w", (3, 4), np.float32), ("b", (4,), np.float32)]
    red = ddp.GradReducer(entries, axis_name=mesh.axis_names[0],
                          bucket_bytes=8, axis_size=n)
    grads = {"w": np.arange(12, np.float32).reshape(3, 4)
             if False else np.arange(12, dtype=np.float32).reshape(3, 4),
             "b": np.ones((4,), np.float32)}

    def body(g):
        return red.reduce(g)

    fn = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    out = jax.jit(fn)(grads)
    np.testing.assert_allclose(np.asarray(out["w"]), grads["w"] * n)
    np.testing.assert_allclose(np.asarray(out["b"]), grads["b"] * n)
    assert red.stats()["comm_bytes"] == 64


# ------------------------------------------------- SPMD ddp_bucketed mode

def _mlp_sym():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="ffn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=24, name="ffn2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="head")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _spmd_train(dp, tp, ddp_bucketed, bucket_bytes=None, steps=3,
                rule=None, batch=8):
    from mxnet_tpu.parallel import SPMDTrainStep, make_mesh
    sym = _mlp_sym()
    mesh = make_mesh({"dp": dp, "tp": tp}, devices=jax.devices()[:dp * tp])
    arg_shapes, _, _ = sym.infer_shape(data=(batch, 16))
    pshapes = {n: tuple(s)
               for n, s in zip(sym.list_arguments(), arg_shapes)
               if n not in ("data", "softmax_label")}
    st = SPMDTrainStep(sym, mesh, dp_axis="dp", tp_axis="tp", tp_rule=rule,
                       lr=0.1, momentum=0.9, ddp_bucketed=ddp_bucketed,
                       bucket_bytes=bucket_bytes)
    st.compile(pshapes, {}, {"data": (batch, 16)},
               {"softmax_label": (batch,)})
    params, aux, opt = st.init(pshapes, {}, seed=0)
    rng = np.random.RandomState(42)
    key = jax.random.PRNGKey(0)
    for _ in range(steps):
        data = {"data": jax.device_put(
            rng.randn(batch, 16).astype(np.float32),
            NamedSharding(mesh, P("dp")))}
        label = {"softmax_label": jax.device_put(
            rng.randint(0, 8, (batch,)).astype(np.float32),
            NamedSharding(mesh, P("dp")))}
        params, aux, opt, _ = st(params, aux, opt, data, label, key)
    st.quiesce()
    return ({k: np.asarray(jax.device_get(v)) for k, v in params.items()},
            st)


@needs_mesh
def test_spmd_ddp_bucketed_matches_gspmd():
    ref, _ = _spmd_train(8, 1, False)
    got, st = _spmd_train(8, 1, True, bucket_bytes=256)
    stats = st.ddp_stats()
    assert stats["buckets"] >= 2, stats
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


@needs_mesh
def test_spmd_ddp_bucket_size_is_bitwise_neutral():
    tiny, st1 = _spmd_train(8, 1, True, bucket_bytes=256)
    huge, st2 = _spmd_train(8, 1, True, bucket_bytes=64 << 20)
    assert st1.ddp_stats()["buckets"] > st2.ddp_stats()["buckets"] == 1
    for k in tiny:
        np.testing.assert_array_equal(tiny[k], huge[k], err_msg=k)


@needs_mesh
def test_spmd_ddp_composes_with_tp():
    from mxnet_tpu.parallel import megatron_tp_rule
    rule = megatron_tp_rule(column_parallel=["ffn1"],
                            row_parallel=["ffn2"])
    ref, _ = _spmd_train(4, 2, False, rule=rule)
    got, st = _spmd_train(4, 2, True, bucket_bytes=256, rule=rule)
    # tp-sharded params reduce per-param, outside the flat buckets
    assert "ffn1_weight" in st._ddp_tp_names
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=5e-4, atol=5e-5,
                                   err_msg=k)


@needs_mesh
def test_mxl507_on_lowered_ddp_step():
    """The lint rule against the REAL lowered step: collective count ==
    bucket count, every one schedulable off the backward's critical
    path with several buckets, zero-overlap flagged with one."""
    from mxnet_tpu.analysis import hlo_passes
    from mxnet_tpu.parallel import SPMDTrainStep, make_mesh

    def lower(bucket_bytes):
        sym = _mlp_sym()
        mesh = make_mesh({"dp": 8}, devices=jax.devices()[:8])
        arg_shapes, _, _ = sym.infer_shape(data=(8, 16))
        pshapes = {n: tuple(s)
                   for n, s in zip(sym.list_arguments(), arg_shapes)
                   if n not in ("data", "softmax_label")}
        st = SPMDTrainStep(sym, mesh, dp_axis="dp", ddp_bucketed=True,
                           bucket_bytes=bucket_bytes)
        jitted = st.compile(pshapes, {}, {"data": (8, 16)},
                            {"softmax_label": (8,)})
        sds = lambda s: jax.ShapeDtypeStruct(s, np.float32)  # noqa: E731
        text = jitted.lower(
            {k: sds(v) for k, v in pshapes.items()}, {},
            {k: sds(v) for k, v in pshapes.items()},
            {"data": sds((8, 16))}, {"softmax_label": sds((8,))},
            jax.ShapeDtypeStruct((2,), np.uint32)).as_text()
        return text, st.ddp_stats()

    text, stats = lower(256)
    rep = hlo_passes.collective_overlap_report(text)
    assert rep["collectives"] == stats["buckets"] >= 2, (rep, stats)
    assert rep["overlappable"] == rep["collectives"], rep
    assert hlo_passes.collective_interleave_pass(
        text, "ddp/step", max_collectives=stats["buckets"]) == []
    # budget violation: pretend the plan allowed fewer collectives
    over = hlo_passes.collective_interleave_pass(
        text, "ddp/step", max_collectives=stats["buckets"] - 1)
    assert len(over) == 1 and over[0].rule == "MXL507"
    # a single fused bucket cannot overlap anything — MXL507 says so
    text1, stats1 = lower(64 << 20)
    diags = hlo_passes.collective_interleave_pass(
        text1, "ddp/step", max_collectives=1)
    assert stats1["buckets"] == 1
    assert len(diags) == 1 and "critical path" in diags[0].message
    assert hlo_passes.metrics_from_text(text)["collective_count"] == \
        stats["buckets"]


def test_mxl507_flags_missing_collectives():
    from mxnet_tpu.analysis import hlo_passes
    text = ('func.func public @main(%arg0: tensor<4xf32>) {\n'
            '  %0 = stablehlo.add %arg0, %arg0 : tensor<4xf32>\n'
            '  return %0 : tensor<4xf32>\n}\n')
    diags = hlo_passes.collective_interleave_pass(text, "ddp/step")
    assert len(diags) == 1 and "not being reduced" in diags[0].message


# -------------------------------------------------- Module.fit DDP path

def _fit_module(kv_type, n_samples=64, batch=32, epochs=2,
                bucket_mb=None, ddp_on=False):
    rng = np.random.RandomState(11)
    X = rng.randn(n_samples, 8).astype(np.float32)
    Y = rng.randint(0, 4, (n_samples,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=batch,
                           label_name="softmax_label")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes, _, _ = sym.infer_shape(data=(batch, 8))
    arg_params = {name: mx.nd.array(
        np.random.RandomState(3).uniform(-0.1, 0.1, shp).astype(np.float32))
        for name, shp in zip(sym.list_arguments(), shapes)
        if name not in ("data", "softmax_label")}
    mod = mx.mod.Module(sym)
    over = {"ddp": ddp_on}
    if bucket_mb is not None:
        over["ddp_bucket_mb"] = bucket_mb
    with config.override(**over):
        mod.fit(it, num_epoch=epochs, kvstore=kv_type, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                  "rescale_grad": 1.0 / batch},
                arg_params=arg_params, initializer=None)
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}, mod


@needs_mesh
def test_module_ddp_in_process_matches_kvstore_path():
    """Single process, 8 virtual devices as dp ranks: the DDP fused step
    must match the kvstore-path params (allclose: the batch is split 8
    ways, so partial-sum order differs) and be bitwise-stable across
    bucket sizes."""
    ref, rmod = _fit_module("dist_sync", ddp_on=False)
    assert not rmod._ddp
    tiny, tmod = _fit_module("dist_sync", bucket_mb=0.0003, ddp_on=True)
    huge, hmod = _fit_module("dist_sync", bucket_mb=64.0, ddp_on=True)
    assert tmod._ddp and hmod._ddp
    ts, hs = tmod._ddp_stats(1), hmod._ddp_stats(1)
    assert ts["buckets"] >= 2 and hs["buckets"] == 1, (ts, hs)
    for k in ref:
        np.testing.assert_array_equal(tiny[k], huge[k], err_msg=k)
        np.testing.assert_allclose(ref[k], tiny[k], rtol=2e-5, atol=1e-6,
                                   err_msg=k)


@needs_mesh
def test_module_ddp_indivisible_batch_falls_back():
    """batch % mesh.size != 0 cannot shard evenly: DDP must decline and
    the kvstore path still trains."""
    params, mod = _fit_module("dist_sync", n_samples=42, batch=21,
                              ddp_on=True)
    assert not mod._ddp
    assert all(np.isfinite(v).all() for v in params.values())


@needs_mesh
def test_module_ddp_refuses_device_metric():
    """Per-rank device metric accumulation under check_vma=False would be
    silently wrong — the fused step must refuse it loudly."""
    _, mod = _fit_module("dist_sync", ddp_on=True)
    assert mod._fused is not None
    with pytest.raises(ValueError, match="MXNET_DDP"):
        mod._fused.attach_metric(lambda outs, label: outs[0].sum())


# ------------------------------------------------------------- telemetry

def test_publish_window_carries_ddp_stats():
    from mxnet_tpu import telemetry
    rec = telemetry.publish_window(
        steps=4, window_s=0.1, examples=128, global_step=40,
        ddp={"buckets": 3, "comm_bytes": 4096, "overlap_ms": 0.25})
    assert rec["ddp"] == {"buckets": 3, "comm_bytes": 4096,
                          "overlap_ms": 0.25}
    snap = telemetry.snapshot()
    assert snap["ddp/buckets"]["samples"][0]["value"] == 3
    assert snap["ddp/overlap_ms"]["samples"][0]["value"] == 0.25
    assert snap["ddp/comm_bytes"]["samples"][0]["value"] >= 4096


def test_publish_window_gauges_bucket_bytes_model():
    from mxnet_tpu import telemetry
    entries = [("w", (64, 64), np.float32)]
    with config.override(ddp_bucket_mb=0.0):
        st = ddp.GradReducer(entries, axis_name="dp",
                             device_kind="TPU v3").stats()
    rec = telemetry.publish_window(
        steps=4, window_s=0.1, examples=128, global_step=41, ddp=st)
    assert rec["ddp"]["bucket_bytes_model"] == \
        ddp.choose_bucket_bytes("TPU v3")
    snap = telemetry.snapshot()
    assert snap["ddp/bucket_bytes_model"]["samples"][-1]["value"] == \
        st["bucket_bytes_model"]


# ------------------------------------------------------------ fleet runs

def _run_fleet(n, tmp_path, extra_args=(), extra_env=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_FAULT_INJECT", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, LAUNCH, "--ddp", "-n", str(n)]
        + list(extra_args) + [sys.executable, WORKER],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)


@pytest.mark.parametrize("n", [2, 4])
def test_ddp_fleet_bitwise_parity(n, tmp_path):
    """N real processes (tools/launch.py --ddp): bucketed vs unbucketed
    bitwise parity incl. optimizer state, plus cross-rank equality."""
    dump = str(tmp_path / "ddp_params.npz")
    r = _run_fleet(n, tmp_path, extra_env={"DDP_TRAIN_DUMP": dump})
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-3000:]
    for rank in range(n):
        assert ("rank %d/%d: ddp bucketed training bitwise-stable"
                % (rank, n)) in r.stdout, r.stdout[-6000:]
    assert os.path.exists(dump)


def test_ddp_fleet_matches_kvstore_fleet(tmp_path):
    """Same 2-process fleet through the kvstore dist_sync path: the DDP
    params must agree to float tolerance (the per-rank partial-gradient
    sums associate differently, so bitwise is not the contract here)."""
    ddp_dump = str(tmp_path / "ddp.npz")
    r = _run_fleet(2, tmp_path, extra_env={"DDP_TRAIN_DUMP": ddp_dump})
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-3000:]
    kv_dump = str(tmp_path / "kv.npz")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["DIST_TRAIN_DUMP"] = kv_dump
    r = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", sys.executable,
         os.path.join(ROOT, "tests", "dist_train_worker.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-3000:]
    with np.load(ddp_dump) as a, np.load(kv_dump) as b:
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            np.testing.assert_allclose(a[k], b[k], rtol=2e-5, atol=1e-6,
                                       err_msg=k)


@pytest.mark.slow
def test_ddp_elastic_kill_resume(tmp_path):
    """MXNET_FAULT_INJECT kills rank 0 mid-DDP-training; the supervised
    restart resumes from checkpoint and the final params match an
    uninterrupted DDP run bitwise (same as the kvstore-path elastic test
    in test_fault.py, with the bucketed all-reduce on)."""
    resume_worker = os.path.join(ROOT, "tests", "fault_resume_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_FAULT_INJECT", None)

    def run(dump, extra):
        e = dict(env)
        e["FAULT_TRAIN_DUMP"] = dump
        return subprocess.run(
            [sys.executable, LAUNCH, "--ddp", "-n", "2",
             "--restart-backoff", "0.2"] + extra
            + [sys.executable, resume_worker],
            capture_output=True, text=True, timeout=600, env=e, cwd=ROOT)

    base = str(tmp_path / "base.npz")
    r = run(base, ["--max-restarts", "0"])
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-3000:]
    killed = str(tmp_path / "killed.npz")
    r = run(killed, ["--max-restarts", "3",
                     "--checkpoint-dir", str(tmp_path / "ckpt"),
                     "--env", "MXNET_FAULT_INJECT=kill@step=3:rank=0"])
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-3000:]
    assert "launch.py: restarting the group" in r.stderr, r.stderr[-3000:]
    assert "resumed from checkpoint step" in r.stdout, r.stdout[-6000:]
    with np.load(base) as b, np.load(killed) as k:
        assert sorted(b.files) == sorted(k.files)
        for name in b.files:
            np.testing.assert_array_equal(
                b[name], k[name],
                err_msg="param %r diverged after kill+resume" % name)
