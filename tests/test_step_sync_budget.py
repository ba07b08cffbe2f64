"""Host-sync budget guardrail for the async fit loop (chip-free).

The async-loop contract (docs/perf.md "Async fit loop"): the benched
ResNet-50 ``Module.fit`` inner loop, with a supported metric folded into
the device step, performs at most ONE involuntary device->host transfer
per 16-step telemetry window — the metric publish at the epoch/display
boundary. Every other read stays on device; the profiler's sync counters
(``profiler.record_host_sync``) are the evidence.

The second half asserts the OTHER side of the bargain: going async must
not change the answer. The same 16 steps replayed fully synchronously —
engine_depth=1 (lockstep dispatch) and device metrics OFF, so every batch
pays a host metric update with its own d2h — from the same initial params
must produce bitwise-identical metric values at the epoch boundary:
engine depth changes only WHEN the host waits, never what the device
computes, and the host metric consumes the same output bits the device
carry consumed.

Runs on CPU (tier-1): resnet_symbol is shape-agnostic until bind
(global_pool), so a 64x64 bind keeps the 50-layer program CPU-feasible
while exercising the exact graph bench.py measures.
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu import telemetry
from mxnet_tpu import config as _config
from mxnet_tpu.io import DataBatch, DataDesc

BATCH = 4
SIDE = 64
K = 16  # fit's telemetry window: the budget window
N_CLASSES = 100

_logger = logging.getLogger("sync_budget_test")
_logger.addHandler(logging.NullHandler())
_logger.propagate = False


class _OneBatchIter:
    """bench.py's --benchmark 1 iterator: one device-resident batch
    repeated, zero input-pipeline cost (and zero h2d after warmup)."""

    def __init__(self, batch, steps, provide_data, provide_label):
        self._batch = batch
        self._steps = steps
        self.provide_data = provide_data
        self.provide_label = provide_label
        self.batch_size = provide_data[0].shape[0]
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self._i >= self._steps:
            raise StopIteration
        self._i += 1
        return self._batch

    def reset(self):
        self._i = 0


def _make_iter():
    rng = np.random.RandomState(7)
    data = mx.nd.array(rng.randn(BATCH, 3, SIDE, SIDE).astype(np.float32))
    label = mx.nd.array(
        rng.randint(0, N_CLASSES, (BATCH,)).astype(np.float32))
    return _OneBatchIter(DataBatch(data=[data], label=[label]), K,
                         [DataDesc("data", (BATCH, 3, SIDE, SIDE))],
                         [DataDesc("softmax_label", (BATCH,))])


def _make_module(it, arg_params=None, aux_params=None):
    from mxnet_tpu import models
    sym = models.resnet_symbol(num_classes=N_CLASSES, num_layers=50)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.logger = _logger
    mod.bind(it.provide_data, it.provide_label, for_training=True)
    np.random.seed(11)  # Initializer draws from the global numpy RNG
    mod.init_params(mx.initializer.Xavier(factor_type="in", magnitude=2.0),
                    arg_params=arg_params, aux_params=aux_params)
    return mod


def _fit(mod, it, metric, **kw):
    mod.fit(it, num_epoch=1, eval_metric=metric, kvstore="tpu_sync",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            **kw)


def test_resnet50_fit_syncs_at_most_once_per_k_steps():
    it = _make_iter()
    mod = _make_module(it)
    # host-side snapshot of the starting point for the baseline run
    # (before the counters arm — this read is test scaffolding, not loop)
    arg0, aux0 = mod.get_params()
    arg0 = {k: mx.nd.array(v.asnumpy()) for k, v in arg0.items()}
    aux0 = {k: mx.nd.array(v.asnumpy()) for k, v in aux0.items()}

    # the epoch has exactly K batches, one telemetry window of K fused
    # steps; counters cover the whole fit inner loop including the
    # epoch-end metric read
    m_async = mx.metric.create("acc")
    profiler.reset_sync_counters()
    _fit(mod, it, m_async)
    counters = profiler.sync_counters()

    assert mod._fused is not None, "fused step must engage (tpu_sync)"
    assert mod._device_plan is not None, \
        "accuracy must fold into the device step"
    # the budget: <= 1 involuntary d2h for the whole 16-step window. The
    # single allowed transfer is the epoch-end metric publish (a few
    # bytes); compile/dispatch/feed never move device data to host.
    # Telemetry is ON (registry default-enabled, no flag) for this run,
    # so these bounds also pin the tentpole claim: window sampling adds
    # ZERO device->host transfers on top of the metric publish.
    assert counters["d2h"] <= 1, counters
    assert counters["d2h_bytes"] <= 64, counters

    # ...and the windows really were published from host-held values:
    # the K-batch epoch is one telemetry window, so every train/ series
    # carries the whole epoch
    reg = telemetry.default_registry()
    assert reg.get("train/step_time_ms").value() > 0
    assert reg.get("train/window_steps").value() == K
    assert reg.get("train/examples_per_s").value() > 0
    assert reg.get("train/engine_depth").value() is not None
    assert reg.get("train/global_step").value() >= K
    assert reg.get("train/steps_total").value() >= K
    # the host_sync/* gauges republish the same census sampled ABOVE at
    # the last window boundary — they can only lag counters, never add
    assert reg.get("host_sync/d2h").value() <= counters["d2h"]

    # the epoch-end publish wrote the device carry into the wrapped
    # host metric, so the caller's own metric object reads normally
    acc_async = dict(m_async.get_name_value())

    # ---- per-step-sync baseline: the same steps at lockstep depth and
    # on the reference host metric path — every batch's outputs go
    # through EvalMetric.update_dict, each paying its own d2h ----
    it.reset()
    base = _make_module(it, arg_params=arg0, aux_params=aux0)
    m_sync = mx.metric.create("acc")
    with _config.override(engine_depth=1, device_metrics=False):
        profiler.reset_sync_counters()
        _fit(base, it, m_sync)
        sync_counters = profiler.sync_counters()

    assert base._device_plan is None  # host path, as intended
    # the host path really did sync per batch (what the budget loop saves)
    assert sync_counters["d2h"] >= K, sync_counters
    acc_sync = dict(m_sync.get_name_value())

    # same initial params, same batches: the epoch accuracy must agree
    # bitwise (integer hit-counts over 64 samples; depth and metric
    # residency change no device math)
    assert acc_async == acc_sync, (acc_async, acc_sync)


def test_ddp_window_stats_add_no_d2h():
    """The DDP telemetry contract: ``ddp/comm_bytes``/``buckets``/
    ``overlap_ms`` come from the GradReducer's STATIC bucket plan — host
    memory decided at compile time — so sampling them at a window
    boundary performs ZERO device->host transfers."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-virtual-device mesh")
    rng = np.random.RandomState(5)
    X = rng.randn(32, 8).astype(np.float32)
    Y = rng.randint(0, 4, (32,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(sym)
    mod.logger = _logger
    with _config.override(ddp=True):
        mod.fit(it, num_epoch=1, kvstore="dist_sync", optimizer="sgd",
                optimizer_params={"learning_rate": 0.1})
    assert mod._ddp and mod._fused is not None

    profiler.reset_sync_counters()
    stats = mod._ddp_stats(K)
    telemetry.publish_window(steps=K, window_s=0.1, examples=16 * K,
                             global_step=K, ddp=stats)
    counters = profiler.sync_counters()
    assert counters["d2h"] == 0 and counters["d2h_bytes"] == 0, counters

    assert stats["buckets"] >= 1 and stats["comm_bytes"] > 0
    reg = telemetry.default_registry()
    assert reg.get("ddp/buckets").value() == stats["buckets"]
    assert reg.get("ddp/comm_bytes").value() >= stats["comm_bytes"]
    assert reg.get("ddp/overlap_ms").value() == stats["overlap_ms"]


def test_embed_window_stats_add_no_d2h():
    """The embedding telemetry contract (PR 15): ``embed/cache_hit_rate``
    and ``embed/spill_bytes`` come from the HotRowCache's HOST-HELD
    counters (embed/cache.py never reads the device to account), and
    ``ddp/sparse_comm_bytes`` from the SparseBucket STATIC plan — so a
    window publish carrying all three performs ZERO device->host
    transfers beyond what training itself already paid."""
    from mxnet_tpu.embed import HotRowCache, SpillStore
    from mxnet_tpu.parallel.ddp import SparseBucket

    store = SpillStore(64, 8, seed=3)
    cache = HotRowCache(store, 16)
    # touch enough distinct rows to force dirty evictions -> spill d2h,
    # all PAID here, before the window boundary being measured
    for lo in (0, 12, 24, 36):
        ids = np.arange(lo, lo + 12, dtype=np.int64)
        cache.ensure(ids)
        cache.note_updated(ids)
    assert cache.stats()["spill_bytes"] > 0

    sb = SparseBucket("emb_user", 32, 8, 64)
    spill_before = 0  # window delta: first window since cache creation
    profiler.reset_sync_counters()
    stats = cache.stats()
    telemetry.publish_window(
        steps=K, window_s=0.1, examples=16 * K, global_step=K,
        ddp={"buckets": 1, "comm_bytes": 0, "overlap_ms": 0.0,
             "sparse_comm_bytes": sb.comm_bytes(4)},
        embed={"hit_rate": stats["hit_rate"],
               "spill_bytes": stats["spill_bytes"] - spill_before})
    counters = profiler.sync_counters()
    assert counters["d2h"] == 0 and counters["d2h_bytes"] == 0, counters

    reg = telemetry.default_registry()
    assert reg.get("embed/cache_hit_rate").value() == stats["hit_rate"]
    assert reg.get("embed/spill_bytes").value() >= stats["spill_bytes"]
    assert reg.get("ddp/sparse_comm_bytes").value() >= sb.comm_bytes(4)


def test_counters_shape():
    profiler.reset_sync_counters()
    c = profiler.sync_counters()
    assert c["d2h"] == 0 and c["wait"] == 0 and c["total"] == 0
    profiler.record_host_sync("d2h", 128)
    profiler.record_host_sync("wait")
    profiler.record_host_sync("depth_wait")
    c = profiler.sync_counters()
    assert c["d2h"] == 1 and c["d2h_bytes"] == 128
    assert c["wait"] == 1 and c["depth_wait"] == 1
    # depth_wait is expected back-pressure, not a budget violation
    assert c["total"] == 2


def _pack_resnet_records(tmp_path, n):
    """n raw-tensor (3,SIDE,SIDE) f32 records + class labels, sharded."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        from make_recordio import write_shards
    finally:
        sys.path.pop(0)
    rng = np.random.RandomState(7)
    X = rng.randn(n, 3, SIDE, SIDE).astype(np.float32)
    Y = rng.randint(0, N_CLASSES, (n,)).astype(np.float32)
    return write_shards(((float(Y[i]), X[i].tobytes()) for i in range(n)),
                        str(tmp_path / "rset"), 2)


def _stream_iter(recs):
    from mxnet_tpu.data import (RawTensorDecoder, ShardedRecordStream,
                                StreamingDataIter)
    return StreamingDataIter(ShardedRecordStream(recs, seed=13),
                             RawTensorDecoder((3, SIDE, SIDE)),
                             batch_size=BATCH)


def test_streaming_fit_same_budget_and_bitwise_vs_in_memory(tmp_path):
    """The tentpole contract end to end: the benched ResNet-50 fit fed by
    the STREAMING tier (sharded stream -> parallel decode -> prefetch
    queue) keeps the <=1-d2h-per-window budget AND lands
    bitwise-identical params + metric to the same fit fed from memory
    (NDArrayIter over the same rows in the same order) — the feed moves
    work off the critical path without touching a single bit of the
    math."""
    recs = _pack_resnet_records(tmp_path, K * BATCH)

    # twin iterator captures the epoch-0 delivered order for the
    # in-memory baseline (same seed => same shuffle plan)
    twin = _stream_iter(recs)
    try:
        caps = [(b.data[0].asnumpy().copy(), b.label[0].asnumpy().copy())
                for b in twin]
    finally:
        twin.close()
    assert len(caps) == K
    X = np.concatenate([d for d, _ in caps])
    Y = np.concatenate([l for _, l in caps])

    it = _stream_iter(recs)
    try:
        mod = _make_module(it)
        arg0, aux0 = mod.get_params()
        arg0 = {k: mx.nd.array(v.asnumpy()) for k, v in arg0.items()}
        aux0 = {k: mx.nd.array(v.asnumpy()) for k, v in aux0.items()}

        m_stream = mx.metric.create("acc")
        h2d = telemetry.default_registry().get("data/h2d_bytes")
        h2d_before = h2d.value() if h2d is not None else 0
        profiler.reset_sync_counters()
        _fit(mod, it, m_stream)
        counters = profiler.sync_counters()
    finally:
        it.close()

    assert mod._fused is not None and mod._device_plan is not None
    # same budget as the one-batch loop: streaming feed + cursor capture
    # + data/* window telemetry add ZERO device->host transfers
    assert counters["d2h"] <= 1, counters
    assert counters["d2h_bytes"] <= 64, counters

    # the window telemetry actually reported the data plane (host-held)
    reg = telemetry.default_registry()
    assert reg.get("data/input_stall_ms").value() >= 0
    # data/h2d_bytes counts what is copied, where it is copied: in a CPU
    # process the stream's batches already live on the executor's device
    h2d = reg.get("data/h2d_bytes")
    assert (h2d.value() if h2d is not None else 0) == h2d_before
    assert reg.get("data/examples_per_s").value() > 0

    # ---- in-memory baseline: same rows, same order, same init ----
    base_it = mx.io.NDArrayIter(X, Y, batch_size=BATCH,
                                label_name="softmax_label")
    base = _make_module(base_it, arg_params=arg0, aux_params=aux0)
    m_base = mx.metric.create("acc")
    _fit(base, base_it, m_base)

    assert dict(m_stream.get_name_value()) == dict(m_base.get_name_value())
    arg_s, aux_s = mod.get_params()
    arg_b, aux_b = base.get_params()
    for name in arg_b:
        np.testing.assert_array_equal(
            arg_s[name].asnumpy(), arg_b[name].asnumpy(),
            err_msg="param %r diverged under the streaming feed" % name)
    for name in aux_b:
        np.testing.assert_array_equal(
            aux_s[name].asnumpy(), aux_b[name].asnumpy(),
            err_msg="aux %r diverged under the streaming feed" % name)


def test_data_window_stats_add_no_d2h():
    """The data-plane telemetry contract: ``data/input_stall_ms``,
    ``data/h2d_bytes``, ``data/queue_depth`` etc. come from host-held
    timers and shape arithmetic — publishing them moves ZERO device
    data to host."""
    profiler.reset_sync_counters()
    telemetry.publish_window(
        steps=K, window_s=0.5, examples=BATCH * K, global_step=K,
        data={"input_stall_ms": 12.5, "h2d_bytes": 4096,
              "queue_depth": 2})
    counters = profiler.sync_counters()
    assert counters["d2h"] == 0 and counters["d2h_bytes"] == 0, counters

    reg = telemetry.default_registry()
    assert reg.get("data/input_stall_ms").value() == 12.5
    assert reg.get("data/h2d_bytes").value() >= 4096
    assert reg.get("data/queue_depth").value() == 2
    assert reg.get("data/examples_per_s").value() == BATCH * K / 0.5
    assert reg.get("data/stall_frac").value() == pytest.approx(0.025)
    # 2.5% stall, no flops figure -> 10% threshold -> compute-bound
    assert reg.get("data/input_bound").value() == 0.0
