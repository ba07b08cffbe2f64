"""The spans inside ``Module.fit``, the feed and the engine
(docs/observability.md "Spans"): one primitive, ``profiler.span``, at
every layer boundary of the training path; always on, never blocking.

The module computes on ``mx.tpu(1)`` (the second virtual CPU device of
the test process), so that a batch on ``mx.cpu()`` is a *host* batch that
``fit`` has to copy, and a batch on ``mx.tpu(1)`` is resident."""
import inspect
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.io import DataBatch, DataDesc

BATCH, FEAT, STEPS = 8, 6, 8
DEV = mx.tpu(1)


class _Iter:
    """``steps`` batches a epoch, the given ones in turn."""

    def __init__(self, batches, steps=STEPS):
        self._batches, self._steps, self._i = batches, steps, 0
        b = batches[0]
        self.provide_data = [DataDesc("data", b.data[0].shape)]
        self.provide_label = [DataDesc("softmax_label", b.label[0].shape)]
        self.batch_size = b.data[0].shape[0]

    def __iter__(self):
        return self

    def __next__(self):
        if self._i >= self._steps:
            raise StopIteration
        self._i += 1
        return self._batches[(self._i - 1) % len(self._batches)]

    def reset(self):
        self._i = 0


def _batch(ctx, rows=BATCH, seed=0):
    rng = np.random.RandomState(seed)
    return DataBatch(
        data=[mx.nd.array(rng.rand(rows, FEAT).astype("f4"), ctx=ctx)],
        label=[mx.nd.array(rng.randint(0, 4, rows).astype("f4"), ctx=ctx)])


def _fit_module(it, eval_metric=None, **kw):
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4),
        name="softmax")
    mod = mx.mod.Module(sym, context=DEV)
    profiler.reset_spans()
    mod.fit(it, num_epoch=1, kvstore="tpu_sync", eval_metric=eval_metric,
            initializer=mx.initializer.Xavier(), **kw)
    assert mod._fused is not None
    return mod, profiler.spans()


def _fit(it, **kw):
    return _fit_module(it, **kw)[1]


def _h2d_counter():
    c = telemetry.default_registry().get("data/h2d_bytes")
    return c.value() if c is not None else 0


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _assert_children_inside_parents(spans):
    for s in spans:
        if s.parent is None:
            continue
        assert any(p.name == s.parent and p.tid == s.tid
                   and p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
                   for p in spans), s


def test_one_dispatch_span_a_dispatch_with_consecutive_steps():
    spans = _fit(_Iter([_batch(mx.cpu(), seed=i) for i in range(3)]))
    disp = _named(spans, "mx/fit/dispatch")
    assert [s.step for s in disp] == list(range(STEPS))
    assert all(s.counts == {"steps": 1} for s in disp)
    assert all(s.parent == "mx/fit/epoch" for s in disp)
    epoch, = _named(spans, "mx/fit/epoch")
    assert epoch.counts == {"epoch": 0} and epoch.parent is None
    for name in ("mx/fit/bind", "mx/fit/init_params",
                 "mx/fit/init_optimizer", "mx/fit/epoch_end",
                 "mx/fit/quiesce"):
        assert len(_named(spans, name)) == 1, name
    # every batch was asked for under mx/fit/next, and the end of data too
    assert len(_named(spans, "mx/fit/next")) == STEPS + 1
    assert all(s.end_ns >= s.start_ns for s in spans)
    _assert_children_inside_parents(spans)
    # the totals agree with the ring, and self time is what no child covers
    totals = profiler.span_totals()
    count, total_ns, self_ns = totals["mx/fit/dispatch"]
    assert count == len(disp)
    assert total_ns == sum(s.end_ns - s.start_ns for s in disp)
    assert 0 <= self_ns <= total_ns
    assert self_ns == total_ns - sum(
        s.end_ns - s.start_ns for s in _named(spans, "mx/feed/h2d"))


@pytest.mark.parametrize("metric", [None, "acc"])
def test_default_fit_is_one_step_a_program_and_starts_no_thread(
        metric, monkeypatch):
    """Nothing watches the host (no callback, monitor, scheduler or
    checkpoint; the metric absent or folded into the step): ``fit`` still
    dispatches one fused step a program, from its own thread."""
    started, start = [], threading.Thread.start
    monkeypatch.setattr(threading.Thread, "start",
                        lambda self: (started.append(self), start(self)))
    steps = 20          # more than one 16-step telemetry window
    mod, spans = _fit_module(_Iter([_batch(mx.cpu())], steps=steps),
                             eval_metric=metric)
    assert (mod._device_plan is not None) == (metric is not None)
    disp = _named(spans, "mx/fit/dispatch")
    assert [s.step for s in disp] == list(range(steps))
    assert all(s.counts == {"steps": 1} for s in disp)
    assert mod._fused._jitted_donate._cache_size() == 1
    assert started == []
    assert {s.tid for s in spans} == {threading.get_ident()}
    assert [s.step for s in _named(spans, "mx/fit/publish")] == [16, steps]


def test_h2d_span_carries_the_bytes_of_host_batches_per_step():
    before = _h2d_counter()
    spans = _fit(_Iter([_batch(mx.cpu())]))
    h2d = _named(spans, "mx/feed/h2d")
    data_bytes, label_bytes = BATCH * FEAT * 4, BATCH * 4
    # data and label of every step, under that step's dispatch
    assert [s.counts["bytes"] for s in h2d] == \
        [data_bytes, label_bytes] * STEPS
    assert [s.step for s in h2d] == [i // 2 for i in range(2 * STEPS)]
    assert all(s.parent == "mx/fit/dispatch"
               and s.tid == threading.get_ident() for s in h2d)
    assert _h2d_counter() - before == STEPS * (data_bytes + label_bytes)


def test_no_h2d_span_and_no_bytes_for_resident_batches():
    before = _h2d_counter()
    spans = _fit(_Iter([_batch(DEV)]))
    assert _named(spans, "mx/feed/h2d") == []
    assert _h2d_counter() == before      # the repaired counter


def test_profiler_on_adds_no_blocking_wait_to_fit(monkeypatch, tmp_path):
    """``set_state("run")`` used to wrap the fit step in ``op_timer``,
    which blocks on the outputs: the profiled ``fit`` ran in lockstep, a
    different program. Now the spans are all there is, and no span
    blocks."""
    assert "block_until_ready" not in inspect.getsource(profiler.span)

    def run():
        profiler.reset_sync_counters()
        spans = _fit(_Iter([_batch(DEV)]))
        return profiler.sync_counters(), sorted(
            s.name for s in spans if s.name != "mx/compile")

    off_syncs, off_names = run()

    def trap(*_a, **_k):
        raise AssertionError("fit took the blocking op_timer")

    monkeypatch.setattr(profiler, "op_timer", trap)
    profiler.set_config(profile_all=True,
                        filename=str(tmp_path / "fit_profile.json"))
    profiler.set_state("run")
    try:
        on_syncs, on_names = run()
    finally:
        profiler.set_state("stop")
        profiler.dump()                      # drop the chrome events
    # the same waits, the same spans: the same program
    assert on_syncs == off_syncs
    assert on_names == off_names


def test_a_new_shape_mid_epoch_leaves_a_compile_span_with_its_step():
    reg = telemetry.default_registry()
    odd = 5
    batches = [_batch(DEV)] * odd + [_batch(DEV, rows=BATCH // 2)] \
        + [_batch(DEV)] * (STEPS - odd - 1)
    spans = _fit(_Iter(batches))
    count = reg.get("compile/count").value()
    assert count >= 2 and reg.get("compile/seconds").value() > 0
    backend = [s for s in _named(spans, "mx/compile")
               if s.counts["event"] == "backend_compile_duration"]
    steps = {s.step for s in backend if s.parent == "mx/fit/dispatch"}
    # the first step compiles the program, the odd batch compiles it again
    assert steps == {0, odd}
    assert all(s.counts["seconds"] > 0 and s.end_ns > s.start_ns
               for s in backend)
    # and the step right after goes back to the first program: no compile
    again = reg.get("compile/count").value()
    assert again == count


def test_the_ring_stays_bounded_and_the_totals_keep_counting():
    profiler.reset_spans()
    n = profiler.SPAN_RING_LEN + 100
    for i in range(n):
        with profiler.span("mx/test/tick", step=i):
            pass
    ring = profiler.spans()
    assert len(ring) == profiler.SPAN_RING_LEN
    assert ring[-1].step == n - 1 and ring[0].step == 100
    assert profiler.span_totals()["mx/test/tick"][0] == n
    mid = ring[len(ring) // 2].end_ns
    assert all(s.end_ns > mid for s in profiler.spans(since_ns=mid))
    assert len(profiler.spans(since_ns=mid)) < len(ring)
    profiler.reset_spans()
    assert profiler.spans() == [] and profiler.span_totals() == {}


def test_step_is_inherited_and_open_self_time_is_live():
    profiler.reset_spans()
    with profiler.span("mx/test/outer", step=7) as outer:
        with profiler.span("mx/test/inner", bytes=3) as inner:
            inner.add(rows=2)
            assert profiler.open_self_ns("mx/test/outer") >= 0
        assert profiler.open_self_ns("mx/test/absent") == 0
    a, b = profiler.spans()
    assert (a.name, a.parent, a.step, a.counts) == \
        ("mx/test/inner", "mx/test/outer", 7, {"bytes": 3, "rows": 2})
    assert (b.name, b.parent, b.step, b.counts) == \
        ("mx/test/outer", None, 7, None)
    assert outer.step == 7


def test_publish_window_republishes_the_span_totals():
    _fit(_Iter([_batch(mx.cpu())]))
    reg = telemetry.default_registry()
    telemetry.publish_window(steps=1, window_s=0.1)
    totals = profiler.span_totals()
    for name in ("fit/dispatch", "fit/next", "feed/h2d", "fit/epoch"):
        assert reg.get("host_span/%s_ms" % name).value() == \
            pytest.approx(totals["mx/" + name][1] / 1e6)
    unspanned = reg.get("host_span/fit/unspanned_ms").value()
    assert unspanned == pytest.approx(totals["mx/fit/epoch"][2] / 1e6)
    assert 0 <= unspanned <= totals["mx/fit/epoch"][1] / 1e6


@pytest.mark.parametrize("resident", [True, False])
def test_mesh_executor_copies_only_what_is_not_under_its_sharding(resident):
    """Data-parallel over four devices: a batch made under the executor's
    own batch sharding is in place (no span, no bytes); a host batch is
    copied, and says so."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    ctxs = [mx.tpu(i) for i in range(4)]
    rng = np.random.RandomState(0)
    data = rng.rand(BATCH, FEAT).astype("f4")
    label = rng.randint(0, 4, BATCH).astype("f4")
    if resident:
        dp = NamedSharding(Mesh([c.jax_device for c in ctxs], ("dp",)),
                           P("dp"))
        batch = DataBatch(
            data=[mx.nd.NDArray(jax.device_put(data, dp), ctx=ctxs[0])],
            label=[mx.nd.NDArray(jax.device_put(label, dp), ctx=ctxs[0])])
    else:
        batch = DataBatch(data=[mx.nd.array(data, ctx=mx.cpu())],
                          label=[mx.nd.array(label, ctx=mx.cpu())])
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4),
        name="softmax")
    mod = mx.mod.Module(sym, context=ctxs)
    before = _h2d_counter()
    profiler.reset_spans()
    mod.fit(_Iter([batch]), num_epoch=1, kvstore="tpu_sync",
            eval_metric=None, initializer=mx.initializer.Xavier())
    assert mod._fused is not None and mod._exec._mesh is not None
    h2d = _named(profiler.spans(), "mx/feed/h2d")
    if resident:
        assert h2d == [] and _h2d_counter() == before
    else:
        assert sum(s.counts["bytes"] for s in h2d) == \
            STEPS * (data.nbytes + label.nbytes) == _h2d_counter() - before
