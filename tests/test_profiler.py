"""Profiler / monitor / visualization tests
(model: reference tests/python/unittest/test_profiler.py)."""
import json

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, monitor, profiler, visualization
from mxnet_tpu.gluon import nn


def test_profiler_chrome_trace(tmp_path):
    fname = str(tmp_path / "prof.json")
    profiler.set_config(filename=fname, profile_all=True,
                        aggregate_stats=True)
    profiler.set_state("run")
    a = mx.nd.ones((16, 16))
    mx.nd.invoke("dot", [a, a], {})
    (a * 3).sum()
    profiler.set_state("stop")
    out = profiler.dump()
    trace = json.load(open(out))
    names = {e.get("name") for e in trace["traceEvents"]}
    assert "dot" in names
    assert "_mul_scalar" in names
    assert all("ts" in e for e in trace["traceEvents"] if e.get("ph") == "X")


def test_profiler_aggregate_stats(tmp_path):
    profiler.set_config(filename=str(tmp_path / "p.json"),
                        aggregate_stats=True)
    profiler.set_state("run")
    a = mx.nd.ones((8,))
    for _ in range(3):
        a + a
    profiler.set_state("stop")
    table = profiler.dumps(reset=True)
    assert "broadcast_add" in table
    line = [ln for ln in table.splitlines() if "broadcast_add" in ln][0]
    assert int(line.split()[1]) >= 3  # call count


def test_profiler_cached_op_events(tmp_path):
    fname = str(tmp_path / "c.json")
    profiler.set_config(filename=fname)
    net = nn.Dense(4, in_units=3)
    net.initialize()
    net.hybridize()
    profiler.set_state("run")
    net(mx.nd.ones((2, 3)))
    profiler.set_state("stop")
    trace = json.load(open(profiler.dump()))
    assert any("CachedOp" in str(e.get("name"))
               for e in trace["traceEvents"])


def test_profiler_pause_resume(tmp_path):
    profiler.set_config(filename=str(tmp_path / "pr.json"),
                        aggregate_stats=True)
    profiler.dumps(reset=True)
    profiler.set_state("run")
    profiler.pause()
    mx.nd.ones((4,)) + 1
    profiler.resume()
    mx.nd.ones((4,)) * 2
    profiler.set_state("stop")
    table = profiler.dumps(reset=True)
    assert "_plus_scalar" not in table
    assert "_mul_scalar" in table


def test_profiler_custom_objects(tmp_path):
    fname = str(tmp_path / "obj.json")
    profiler.set_config(filename=fname)
    profiler.set_state("run")
    with profiler.Task(name="mytask"):
        pass
    c = profiler.Counter(name="ctr")
    c += 2
    profiler.Marker(name="mk").mark()
    profiler.set_state("stop")
    trace = json.load(open(profiler.dump()))
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"mytask", "ctr", "mk"} <= names


def test_monitor_block():
    mon = monitor.Monitor(1, pattern=".*weight")
    net = nn.Dense(4, in_units=3)
    net.initialize()
    mon.install_block(net)
    mon.tic()
    net(mx.nd.ones((2, 3)))
    res = mon.toc()
    assert len(res) == 1 and "weight" in res[0][1]
    # interval: every other step inactive
    mon2 = monitor.Monitor(2, pattern=".*")
    mon2.install_block(net)
    mon2.tic(); net(mx.nd.ones((2, 3))); r0 = mon2.toc()
    mon2.tic(); net(mx.nd.ones((2, 3))); r1 = mon2.toc()
    assert len(r0) > 0 and len(r1) == 0


def test_monitor_executor():
    from mxnet_tpu import symbol as sym
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=4, name="fc")
    exe = net.bind(ctx=mx.cpu(), args={
        "data": mx.nd.ones((2, 3)),
        "fc_weight": mx.nd.ones((4, 3)),
        "fc_bias": mx.nd.zeros((4,))})
    mon = monitor.Monitor(1, pattern=".*")
    mon.install(exe)
    mon.tic()
    exe.forward()
    res = mon.toc()
    assert any("fc" in name for _, name, _ in res)


def test_print_summary_and_plot():
    from mxnet_tpu import symbol as sym
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=10, name="fc1")
    net = sym.Activation(net, act_type="relu", name="relu1")
    text = visualization.print_summary(net, shape={"data": (1, 20)})
    assert "fc1" in text and "Total params: 210" in text
    g = visualization.plot_network(net)
    assert g is not None


def test_executor_events_profiled(tmp_path):
    """Executor fwd/bwd emit profiler events (round-2 weak #6: profiling
    was CachedOp-only)."""
    import json
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    f = str(tmp_path / "exec_profile.json")
    profiler.set_config(profile_symbolic=True, filename=f)
    profiler.set_state("run")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    ex = net.simple_bind(mx.cpu(), data=(8, 3))
    ex.forward(is_train=True, data=np.zeros((8, 3), np.float32),
               softmax_label=np.zeros((8,), np.float32))
    ex.backward()
    profiler.set_state("stop")
    profiler.dump()
    events = json.load(open(f))["traceEvents"]
    names = {e.get("name") for e in events}
    assert "mx/exec/forward_train" in names
    assert "mx/exec/backward" in names


def test_group2ctx_raises_loudly():
    import mxnet_tpu as mx
    import pytest as _pytest
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    with _pytest.raises(mx.MXNetError):
        net.simple_bind(mx.cpu(), data=(4, 3),
                        group2ctx={"dev1": mx.cpu(1)})
    with _pytest.raises(mx.MXNetError):
        mx.mod.Module(net, group2ctxs={"dev1": mx.cpu(1)})


def test_fused_fit_step_is_profiled():
    """The atomic donating fit step must appear in the profile like the
    eager Executor.forward does (observability parity for the path the
    bench measures): its span is fit's ``mx/fit/dispatch``."""
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4),
        name="softmax")
    X = np.random.rand(32, 8).astype("f4")
    it = mx.io.NDArrayIter(X, np.zeros(32, "f4"), batch_size=16,
                           label_name="softmax_label")
    profiler.set_config(profile_all=True, aggregate_stats=True)
    profiler.set_state("run")
    try:
        mod = mx.mod.Module(sym)
        mod.fit(it, num_epoch=1, kvstore="tpu_sync",
                initializer=mx.initializer.Xavier())
        assert mod._fused is not None
    finally:
        profiler.set_state("stop")
    d = profiler.dumps(reset=True)
    assert "mx/fit/dispatch" in d
