"""Fused Module train step (module/fused.py): numeric parity with the eager
per-parameter update path, through the public Module.fit API.

The reference semantics being matched: update_on_kvstore=False training
(python/mxnet/model.py:123-170) where fwd/bwd run, grads are reduced, and
the optimizer op applies per parameter — here all inside one XLA program
when kvstore='tpu_sync'.
"""
import numpy as np
import pytest

import mxnet_tpu as mx


def _make_net(with_bn=True):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    if with_bn:
        net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(n=64, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 8).astype("float32")
    Y = rng.randint(0, 4, (n,)).astype("float32")
    return X, Y


def _fixed_params(sym, seed=3):
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(16, 8))
    out = {}
    for name, shp in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        out[name] = mx.nd.array(rng.uniform(-0.1, 0.1, shp).astype("float32"))
    return out


def _fit(kvstore, optimizer, optimizer_params, ctx=None, num_epoch=3,
         with_bn=True, n=64):
    sym = _make_net(with_bn)
    X, Y = _data(n)
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(sym, context=ctx)
    mod.fit(it, num_epoch=num_epoch, kvstore=kvstore, optimizer=optimizer,
            optimizer_params=optimizer_params,
            arg_params={k: v.copy() for k, v in _fixed_params(sym).items()},
            initializer=None, allow_missing=False)
    return mod


def _assert_params_close(mod_a, mod_b, rtol=2e-5, atol=2e-6):
    args_a, aux_a = mod_a.get_params()
    args_b, aux_b = mod_b.get_params()
    assert set(args_a) == set(args_b)
    for k in args_a:
        np.testing.assert_allclose(args_a[k].asnumpy(), args_b[k].asnumpy(),
                                   rtol=rtol, atol=atol, err_msg=k)
    for k in aux_a:
        np.testing.assert_allclose(aux_a[k].asnumpy(), aux_b[k].asnumpy(),
                                   rtol=rtol, atol=atol, err_msg=k)


@pytest.mark.parametrize("opt,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.1}),
    ("adam", {"learning_rate": 0.01}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("adagrad", {"learning_rate": 0.05}),
    ("ftrl", {"learning_rate": 0.05}),
    ("signum", {"learning_rate": 0.01, "momentum": 0.9}),
])
def test_fused_matches_eager_one_step(opt, opt_params):
    """Single-step parity, tight tolerance: one batch, one update. (Multi-
    step comparison of two different XLA programs diverges chaotically for
    normalizing optimizers — sign(g)/sqrt(v) amplifies last-ulp rounding —
    so the strict multi-step check below is limited to the linear ones.)"""
    eager = _fit("local", opt, opt_params, num_epoch=1, n=16)
    assert eager._fused is None  # cpu ctx + local kv -> eager path
    fused = _fit("tpu_sync", opt, opt_params, num_epoch=1, n=16)
    assert fused._fused is not None, "tpu_sync must engage the fused step"
    _assert_params_close(eager, fused, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("opt,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
])
def test_fused_matches_eager_multi_step(opt, opt_params):
    eager = _fit("local", opt, opt_params)
    fused = _fit("tpu_sync", opt, opt_params)
    assert fused._fused is not None
    _assert_params_close(eager, fused)


def test_fused_with_lr_scheduler():
    sched = mx.lr_scheduler.FactorScheduler(step=4, factor=0.5)
    eager = _fit("local", "sgd",
                 {"learning_rate": 0.2, "momentum": 0.9,
                  "lr_scheduler": sched})
    sched2 = mx.lr_scheduler.FactorScheduler(step=4, factor=0.5)
    fused = _fit("tpu_sync", "sgd",
                 {"learning_rate": 0.2, "momentum": 0.9,
                  "lr_scheduler": sched2})
    assert fused._fused is not None
    _assert_params_close(eager, fused)
    # schedule actually advanced identically
    assert eager._optimizer.num_update == fused._optimizer.num_update


def test_fused_spmd_matches_single_device():
    ctxs = [mx.Context("cpu", i) for i in range(4)]
    single = _fit("tpu_sync", "sgd", {"learning_rate": 0.1, "momentum": 0.9})
    spmd = _fit("tpu_sync", "sgd", {"learning_rate": 0.1, "momentum": 0.9},
                ctx=ctxs)
    assert spmd._fused is not None
    _assert_params_close(single, spmd)


def test_fused_optimizer_states_roundtrip(tmp_path):
    fused = _fit("tpu_sync", "adam", {"learning_rate": 0.01}, num_epoch=2)
    assert fused._fused is not None
    f = str(tmp_path / "opt.states")
    fused.save_optimizer_states(f)

    # an eager module can load what the fused path saved
    sym = _make_net()
    X, Y = _data()
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    eager = mx.mod.Module(sym)
    eager.bind(it.provide_data, it.provide_label)
    eager.init_params(arg_params=_fixed_params(sym), aux_params={},
                      allow_missing=True)
    eager.init_optimizer(kvstore="local", optimizer="adam",
                         optimizer_params={"learning_rate": 0.01})
    eager.load_optimizer_states(f)
    # fused module reloads its own states
    fused.load_optimizer_states(f)
    st = fused._fused_opt_state
    names = fused._fused.param_names
    for k in names:
        idx = fused._fused._name2idx[k]
        es = eager._updater.states[idx]
        es = es if isinstance(es, tuple) else (es,)
        for a, b in zip(st[k], es):
            np.testing.assert_allclose(np.asarray(a), b.asnumpy(), rtol=1e-6)


def test_fit_step_donates_buffers():
    """The atomic fit-loop step donates param/aux/opt buffers to XLA:
    after one _fit_step, the PREVIOUS device buffers must be deleted
    (in-place update, no HBM double-buffering) — while data/label inputs
    survive for reuse across steps."""
    sym = _make_net(with_bn=True)
    X, Y = _data(16)
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    batch = next(iter(it))
    mod = mx.mod.Module(sym)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(arg_params=_fixed_params(sym), aux_params={},
                    allow_missing=True)
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    assert mod._fused is not None
    ex = mod._exec
    pnames = mod._fused.param_names
    old_params = {k: ex.arg_dict[k]._data for k in pnames}
    old_opt = {k: mod._fused_opt_state[k] for k in pnames}
    old_aux = {k: v._data for k, v in ex.aux_dict.items()}
    # copy=True: on CPU np.asarray(jax_array) is a zero-copy view whose
    # external reference would (correctly) block donation of that buffer
    w_before = {k: np.array(v, copy=True) for k, v in old_params.items()}

    mod._fit_step(batch)
    data_val = batch.data[0]._data

    for k in pnames:
        assert old_params[k].is_deleted(), "param %s was copied, not donated" % k
        assert not ex.arg_dict[k]._data.is_deleted()
    for k, st in old_opt.items():
        for s in st:
            assert s.is_deleted(), "opt state of %s not donated" % k
    for k, a in old_aux.items():
        assert a.is_deleted(), "aux %s not donated" % k
    assert not data_val.is_deleted(), "data input must NOT be donated"
    # and the step actually trained
    w_after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert any((w_before[k] != w_after[k]).any() for k in w_before)
    # a second step with the same (surviving) batch works
    mod._fit_step(batch)


def test_fused_flag_disables():
    from mxnet_tpu import config
    with config.override(module_fused_step=False):
        mod = _fit("tpu_sync", "sgd", {"learning_rate": 0.1})
    assert mod._fused is None


def test_fit_without_metric():
    sym = _make_net(with_bn=False)
    X, Y = _data()
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(sym)
    mod.fit(it, num_epoch=1, eval_metric=None, kvstore="tpu_sync",
            arg_params=_fixed_params(sym), initializer=None)
    assert mod._fused is not None


def test_unfusable_optimizer_falls_back():
    mod = _fit("tpu_sync", "nadam", {"learning_rate": 0.01}, num_epoch=1)
    assert mod._fused is None  # Nadam updates via NDArray math on host


# ------------------------------------------------- the fit loop's contract
def _fit_callbacks(n, num_epoch, eval_metric):
    sym = _make_net()
    X, Y = _data(n)
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(sym)
    calls = []
    mod.fit(it, num_epoch=num_epoch, kvstore="tpu_sync", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            arg_params={k: v.copy() for k, v in _fixed_params(sym).items()},
            initializer=None, eval_metric=eval_metric,
            batch_end_callback=lambda p: calls.append((p.epoch, p.nbatch)))
    return mod, calls


def test_callbacks_once_a_batch_and_host_metric_equals_device_folded():
    """n=288 -> 18 batches an epoch, no multiple of fit's 16-step telemetry
    window. Callbacks fire once a batch, in order; the metric folded into
    the step reads what the host metric reads from the same steps."""
    from mxnet_tpu import config
    m_dev = mx.metric.create("acc")
    dev, calls = _fit_callbacks(288, 2, m_dev)
    assert dev._fused is not None and dev._device_plan is not None
    assert calls == [(e, b) for e in range(2) for b in range(18)]
    m_host = mx.metric.create("acc")
    with config.override(device_metrics=False):
        host, host_calls = _fit_callbacks(288, 2, m_host)
    assert host._device_plan is None and host_calls == calls
    _assert_params_close(host, dev)
    np.testing.assert_allclose(m_host.get()[1], m_dev.get()[1], atol=1e-6)


def test_fit_accepts_numpy_feeds():
    """``DataBatch``es of raw numpy arrays through ``_fit_step``: set_inputs
    routes every value through Executor.prepare_input."""
    from mxnet_tpu.io import DataBatch, DataDesc
    sym = _make_net()
    X, Y = _data(64)
    batches = [DataBatch(data=[X[i * 16:(i + 1) * 16]],
                         label=[Y[i * 16:(i + 1) * 16]]) for i in range(4)]

    class It:
        provide_data = [DataDesc("data", (16, 8))]
        provide_label = [DataDesc("softmax_label", (16,))]
        batch_size = 16

        def __iter__(self):
            return iter(batches)

        def reset(self):
            pass

    mod = mx.mod.Module(sym)
    mod.fit(It(), num_epoch=1, eval_metric=None, kvstore="tpu_sync",
            optimizer="sgd", arg_params=_fixed_params(sym),
            initializer=None)
    assert mod._fused is not None
    assert mod._optimizer.num_update == 4


def test_fused_with_backward_mirror_matches():
    """Gradient mirroring under the fused step: jax.checkpoint recompute
    must not change the numerics (same program, residuals recomputed)."""
    from mxnet_tpu import config
    base = _fit("tpu_sync", "sgd", {"learning_rate": 0.1, "momentum": 0.9},
                num_epoch=1, n=16)
    with config.override(backward_do_mirror=True):
        mirrored = _fit("tpu_sync", "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9},
                        num_epoch=1, n=16)
    _assert_params_close(base, mirrored, rtol=1e-5, atol=1e-7)


def test_fit_rejects_bad_k():
    sym = _make_net()
    X, Y = _data(16)
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(sym)
    with pytest.raises(ValueError, match="steps_per_dispatch must be >= 1"):
        mod.fit(it, num_epoch=1, steps_per_dispatch=0)


@pytest.mark.parametrize("with_monitor", [False, True])
def test_fit_refuses_more_than_one_step_a_program(with_monitor):
    sym = _make_net()
    X, Y = _data(16)
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(sym)
    mon = mx.monitor.Monitor(1) if with_monitor else None
    with pytest.raises(ValueError, match="steps_per_dispatch=2: the K-step "
                       "scan is gone, fit dispatches one fused step"):
        mod.fit(it, num_epoch=1, kvstore="tpu_sync",
                steps_per_dispatch=2, monitor=mon)
    # the raise fired before bind/install_monitor/init_optimizer: a retry
    # must still engage the fused path
    assert not mod.binded
    it.reset()
    mod.fit(it, num_epoch=1, kvstore="tpu_sync", steps_per_dispatch=1,
            arg_params=_fixed_params(_make_net()), initializer=None)
    assert mod._fused is not None


# --------------------------------------------------------------- gluon side
def _gluon_train(fused, opt="sgd", opt_params=None, steps=6):
    from mxnet_tpu import gluon, autograd, config
    opt_params = dict(opt_params or {"learning_rate": 0.1, "momentum": 0.9})
    rng = np.random.RandomState(0)
    X = mx.nd.array(rng.randn(32, 8).astype("float32"))
    Y = mx.nd.array(rng.randn(32, 1).astype("float32"))
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(1))
    net.initialize(mx.initializer.Xavier(rnd_type="gaussian"),
                   force_reinit=True)
    mx.random.seed(7)
    # deterministic init: overwrite with fixed values
    net(X)  # shape inference
    r2 = np.random.RandomState(5)
    for p in net.collect_params().values():
        p.set_data(mx.nd.array(
            r2.uniform(-0.1, 0.1, p.shape).astype("float32")))
    trainer = gluon.Trainer(net.collect_params(), opt, opt_params)
    loss_fn = gluon.loss.L2Loss()
    with config.override(trainer_fused_update=fused):
        for _ in range(steps):
            with autograd.record():
                loss = loss_fn(net(X), Y)
            loss.backward()
            trainer.step(32)
    # positional keys: gluon name counters advance globally between runs
    return [p.data().asnumpy() for p in net.collect_params().values()], \
        trainer


def test_trainer_fused_matches_eager():
    eager, tr_e = _gluon_train(False)
    fused, tr_f = _gluon_train(True)
    assert tr_f._fused_jit is not None, "fused trainer update did not engage"
    for a, b in zip(eager, fused):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)


def test_gluon_hybridize_mirror_matches():
    """Mirroring on the CachedOp backward (hybridize path): identical
    training trajectory with remat on."""
    from mxnet_tpu import config
    base, _ = _gluon_train(True)
    with config.override(backward_do_mirror=True):
        mirrored, _ = _gluon_train(True)
    for a, b in zip(base, mirrored):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)


def test_trainer_fused_adam_matches_eager():
    eager, _ = _gluon_train(False, "adam", {"learning_rate": 0.01}, steps=1)
    fused, tr = _gluon_train(True, "adam", {"learning_rate": 0.01}, steps=1)
    assert tr._fused_jit is not None
    for a, b in zip(eager, fused):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_trainer_fused_states_roundtrip(tmp_path):
    _, tr = _gluon_train(True, "adam", {"learning_rate": 0.01}, steps=3)
    f = str(tmp_path / "trainer.states")
    tr.save_states(f)
    tr.load_states(f)
    assert tr._fused_jit is None  # caches dropped on load


def test_custom_loop_keeps_eager_semantics():
    """Bare forward()/backward()/update() must behave exactly like the
    reference even when the fused step is configured: weights move only at
    update(), grad_dict is populated, and a skipped update() leaves weights
    and the LR schedule untouched."""
    sym = _make_net(with_bn=False)
    X, Y = _data(16)
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    batch = next(iter(it))
    mod = mx.mod.Module(sym)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(arg_params=_fixed_params(sym), aux_params={},
                    allow_missing=True)
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    assert mod._fused is not None
    before = {k: v.asnumpy().copy() for k, v in mod.get_params()[0].items()}

    # eager-style loop: weights untouched until update()
    mod.forward(batch, is_train=True)
    mod.backward()
    assert any(g is not None for g in mod._exec.grad_dict.values())
    mid = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for k in before:
        np.testing.assert_array_equal(before[k], mid[k])
    mod.update()
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert any((before[k] != after[k]).any() for k in before)

    # fused fit-style step with update() SKIPPED: no weight/schedule motion
    n_before = mod._optimizer.num_update
    w_before = {k: v.asnumpy().copy() for k, v in mod.get_params()[0].items()}
    mod.forward_backward(batch)  # launches the fused program
    assert mod._fused_ran
    w_mid = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for k in w_before:
        np.testing.assert_array_equal(w_before[k], w_mid[k])
    assert mod._optimizer.num_update == n_before  # schedule not advanced
    mod.update()
    assert mod._optimizer.num_update == n_before + 1


def test_eval_metric_none_with_eval_data_raises():
    sym = _make_net(with_bn=False)
    X, Y = _data()
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    it2 = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(sym)
    with pytest.raises(ValueError):
        mod.fit(it, eval_data=it2, eval_metric=None, num_epoch=1)


def test_bucketing_fused_matches_eager_across_buckets():
    """BucketingModule engages the fused step per bucket with ONE
    optimizer accumulator per weight across buckets (mirrored through
    the shared Updater on switches) — numerics must match the all-eager
    path over an alternating-bucket schedule."""
    from mxnet_tpu import config
    from mxnet_tpu.io import DataBatch, DataDesc

    def sym_gen(L):
        data = mx.sym.Variable("data")
        net = mx.sym.mean(data, axis=1)             # (B, 4) for any L
        net = mx.sym.FullyConnected(net, num_hidden=8, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
        return (mx.sym.SoftmaxOutput(net, name="softmax"),
                ("data",), ("softmax_label",))

    rng = np.random.RandomState(0)
    buckets = [3, 5]
    batches = []
    for i in range(8):
        L = buckets[i % 2]
        b = DataBatch(
            data=[mx.nd.array(rng.randn(4, L, 4).astype("f4"))],
            label=[mx.nd.array(rng.randint(0, 4, (4,)).astype("f4"))],
            provide_data=[DataDesc("data", (4, L, 4))],
            provide_label=[DataDesc("softmax_label", (4,))])
        b.bucket_key = L
        batches.append(b)

    def train(fused):
        with config.override(module_fused_step=fused):
            mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=5)
            mod.bind([DataDesc("data", (4, 5, 4))],
                     [DataDesc("softmax_label", (4,))])
            prng = np.random.RandomState(3)
            sym5 = sym_gen(5)[0]
            shapes, _, _ = sym5.infer_shape(data=(4, 5, 4))
            fixed = {n: mx.nd.array(
                prng.uniform(-0.1, 0.1, s).astype("f4"))
                for n, s in zip(sym5.list_arguments(), shapes)
                if n not in ("data", "softmax_label")}
            mod.init_params(arg_params=fixed, aux_params={},
                            allow_missing=True)
            mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                               optimizer_params={"learning_rate": 0.1,
                                                 "momentum": 0.9})
            if fused:
                assert mod._curr_module._fused is not None
            for b in batches:
                mod._fit_step(b)
            return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    p_f = train(True)
    p_e = train(False)
    for k in p_e:
        np.testing.assert_allclose(p_f[k], p_e[k], rtol=2e-5, atol=2e-6,
                                    err_msg=k)


def test_bucketing_checkpoint_saves_active_bucket_momentum(tmp_path):
    """save_checkpoint(save_optimizer_states=True) while a NON-default
    bucket is active must capture that bucket's fused momentum (not the
    default bucket's stale snapshot)."""
    from mxnet_tpu import config
    from mxnet_tpu.io import DataBatch, DataDesc

    def sym_gen(L):
        data = mx.sym.Variable("data")
        net = mx.sym.mean(data, axis=1)
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc")
        return (mx.sym.SoftmaxOutput(net, name="softmax"),
                ("data",), ("softmax_label",))

    rng = np.random.RandomState(0)

    def batch(L):
        b = DataBatch(
            data=[mx.nd.array(rng.randn(4, L, 4).astype("f4"))],
            label=[mx.nd.array(rng.randint(0, 4, (4,)).astype("f4"))],
            provide_data=[DataDesc("data", (4, L, 4))],
            provide_label=[DataDesc("softmax_label", (4,))])
        b.bucket_key = L
        return b

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=5)
    mod.bind([DataDesc("data", (4, 5, 4))], [DataDesc("softmax_label", (4,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    assert mod._curr_module._fused is not None
    # switch to bucket 3 and train ONLY there: all momentum lives in
    # bucket 3's fused state
    for _ in range(4):
        mod._fit_step(batch(3))
    prefix = str(tmp_path / "bk")
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)

    states = open(prefix + "-0001.states", "rb").read()
    eager = mx.mod.Module(sym_gen(5)[0])
    eager.bind([DataDesc("data", (4, 5, 4))],
               [DataDesc("softmax_label", (4,))])
    eager.init_params(initializer=mx.initializer.Xavier())
    eager.init_optimizer(kvstore="local", optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9})
    eager._updater.set_states(states)
    moms = [s.asnumpy() if hasattr(s, "asnumpy") else np.asarray(s)
            for s in eager._updater.states.values() if s is not None]
    assert any(np.abs(m).max() > 0 for m in moms), \
        "saved momentum is all-zero: active bucket's state was lost"


# ---- bf16-native BatchNorm: parity with the f32 reference ------------------
# The bf16 path computes stats as f32-widened dot_general reductions over
# the bf16 activations and normalizes in bf16 (ops/nn.py batch_norm); these
# tests pin it against the unchanged f32 path on bit-identical input values.

def _bn_run(x, gamma, beta, rmean, rvar, training=True, fix_gamma=False):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import batch_norm

    kw = dict(eps=1e-3, momentum=0.9, fix_gamma=fix_gamma, axis=1,
              _training=training)
    # random target: with a plain sum, dgamma = sum(xhat) ~ 0, and with a
    # pure sum-of-squares, dx cancels analytically (dy lies in the span BN's
    # backward projects out) — either would make the comparison vacuous
    tgt = jnp.asarray(np.random.RandomState(7).randn(*x.shape)
                      .astype("f4"))

    def loss(xx, g, b):
        out = batch_norm(xx, g, b, rmean, rvar, **kw)[0]
        return jnp.sum((out.astype(jnp.float32) - tgt) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(x, gamma, beta)
    outs = batch_norm(x, gamma, beta, rmean, rvar, **kw)
    return outs, grads


def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-6)


def test_batchnorm_bf16_training_parity():
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    xbf = jnp.asarray(rng.randn(8, 5, 6, 7).astype("f4") * 2 + 1,
                      jnp.bfloat16)
    x32 = xbf.astype(jnp.float32)  # identical values, f32 reference path
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, (5,)).astype("f4"))
    beta = jnp.asarray(rng.randn(5).astype("f4"))
    rmean = jnp.zeros((5,), jnp.float32)
    rvar = jnp.ones((5,), jnp.float32)

    (o_bf, m_bf, v_bf, nm_bf, nv_bf), g_bf = _bn_run(xbf, gamma, beta,
                                                     rmean, rvar)
    (o_32, m_32, v_32, nm_32, nv_32), g_32 = _bn_run(x32, gamma, beta,
                                                     rmean, rvar)
    # output stays in the activation dtype — no hidden upcast
    assert o_bf.dtype == jnp.bfloat16 and o_32.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o_bf, np.float32),
                               np.asarray(o_32), rtol=0.05, atol=0.05)
    # batch stats and running-stat updates are f32 on both paths and the
    # widened reductions are exact f32 sums of the same values: tight
    for a, b, tol in ((m_bf, m_32, 1e-5), (v_bf, v_32, 1e-4),
                      (nm_bf, nm_32, 1e-5), (nv_bf, nv_32, 1e-4)):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)
    dx_bf, dg_bf, db_bf = g_bf
    dx_32, dg_32, db_32 = g_32
    assert dx_bf.dtype == jnp.bfloat16  # cotangent stays bf16 (no convert)
    assert _rel_err(dx_bf, dx_32) < 0.03
    assert _rel_err(dg_bf, dg_32) < 0.03
    assert _rel_err(db_bf, db_32) < 0.03


def test_batchnorm_bf16_inference_parity():
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    xbf = jnp.asarray(rng.randn(4, 3, 5, 5).astype("f4"), jnp.bfloat16)
    x32 = xbf.astype(jnp.float32)
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, (3,)).astype("f4"))
    beta = jnp.asarray(rng.randn(3).astype("f4"))
    rmean = jnp.asarray(rng.randn(3).astype("f4"))
    rvar = jnp.asarray(rng.uniform(0.5, 2.0, (3,)).astype("f4"))

    (o_bf, _, _, nm_bf, nv_bf), _ = _bn_run(xbf, gamma, beta, rmean, rvar,
                                            training=False)
    (o_32, _, _, _, _), _ = _bn_run(x32, gamma, beta, rmean, rvar,
                                    training=False)
    assert o_bf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o_bf, np.float32),
                               np.asarray(o_32), rtol=0.05, atol=0.05)
    # inference must not touch the running stats
    np.testing.assert_array_equal(np.asarray(nm_bf), np.asarray(rmean))
    np.testing.assert_array_equal(np.asarray(nv_bf), np.asarray(rvar))


def test_batchnorm_bf16_fix_gamma_zero_grad():
    import jax.numpy as jnp

    rng = np.random.RandomState(2)
    xbf = jnp.asarray(rng.randn(4, 3, 6).astype("f4"), jnp.bfloat16)
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, (3,)).astype("f4"))
    beta = jnp.zeros((3,), jnp.float32)
    _, (dx, dg, db) = _bn_run(xbf, gamma, beta, jnp.zeros((3,)),
                              jnp.ones((3,)), fix_gamma=True)
    np.testing.assert_array_equal(np.asarray(dg), np.zeros((3,), "f4"))
    assert np.abs(np.asarray(db)).max() > 0  # beta still trains


def test_fused_module_bf16_policy_trains_and_matches_f32():
    """End to end through the fused Module step under the session dtype
    policy (MXNET_COMPUTE_DTYPE=bfloat16): params stay f32 masters, BN
    running stats move, and 2 epochs stay close to the f32 run."""
    from mxnet_tpu import config

    p_32 = _fit("tpu_sync", "sgd", {"learning_rate": 0.05, "momentum": 0.9,
                                    "multi_precision": True}, num_epoch=2)
    with config.override(compute_dtype="bfloat16"):
        p_bf = _fit("tpu_sync", "sgd", {"learning_rate": 0.05,
                                        "momentum": 0.9,
                                        "multi_precision": True},
                    num_epoch=2)
    args_bf, aux_bf = p_bf.get_params()
    args_32, aux_32 = p_32.get_params()
    for k in args_32:
        a = args_bf[k].asnumpy()
        assert np.isfinite(a).all(), k
        assert a.dtype == np.float32, k  # master copies stay f32
        assert _rel_err(a, args_32[k].asnumpy()) < 0.05, k
    # BN running stats updated (and in f32) on the bf16 path
    rm = aux_bf["bn1_moving_mean"].asnumpy()
    assert rm.dtype == np.float32 and not np.allclose(rm, 0)
    rv = aux_bf["bn1_moving_var"].asnumpy()
    assert _rel_err(rv, aux_32["bn1_moving_var"].asnumpy()) < 0.05


# ------------------------------------------- the cast is per parameter
def _policy_fused_module():
    """Fused Module step of fc-bn-fc plus a zero-size weight (a non-float32
    parameter cannot get here: Module keeps those on the eager path)."""
    net = _make_net().get_internals()["fc2_output"]
    net = mx.sym.concat(net, mx.sym.Variable("empty_weight", shape=(0, 4)),
                        dim=0)
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    X, Y = _data(16)
    it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(sym)
    mod.fit(it, num_epoch=1, kvstore="tpu_sync", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    fused, ex = mod._fused, mod._exec
    assert fused is not None and fused._compute_dtype is not None
    text = fused.lower(ex._arg_vals(), ex._aux_vals(),
                       mod._fused_opt_state).as_text()
    masters = {k: ex.arg_dict[k] for k in fused.param_names}
    # the step's leading entry arguments are the params dict, keys sorted
    names = sorted(masters)
    handed = [(k, v.asnumpy()) for k, v in mod.get_params()[0].items()]
    handed += [(k, np.asarray(s)) for k, st in mod._fused_opt_state.items()
               for s in st]
    return (text, names, {"fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias"},
            {k: (np.dtype(v.dtype), v.shape) for k, v in masters.items()},
            handed)


def _policy_cachedop(monkeypatch):
    """Hybridized dense-bn-dense plus a float16 parameter (a zero-size one
    cannot get here: gluon reads a 0 in a shape as unknown)."""
    import jax
    from mxnet_tpu import gluon, autograd
    from mxnet_tpu.gluon import nn

    class Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.fc1, self.bn, self.fc2 = \
                    nn.Dense(8), nn.BatchNorm(), nn.Dense(3)
                self.half_bias = self.params.get(
                    "half_bias", shape=(1, 3), dtype="float16")

        def hybrid_forward(self, F, x, half_bias):
            y = self.fc2(F.relu(self.bn(self.fc1(x))))
            return F.broadcast_add(y, F.cast(half_bias, dtype="float32"))

    # the CachedOp keeps its jitted program to itself: catch it as built
    lowered = []
    real_jit = jax.jit

    def spy(fn, *a, **kw):
        jitted = real_jit(fn, *a, **kw)
        if getattr(fn, "__name__", "") != "traced":
            return jitted

        def call(*args):
            lowered.append(jitted.lower(*args))
            return jitted(*args)
        return call

    net = Net()
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(0).randn(4, 5).astype("f4"))
    with monkeypatch.context() as patched:
        patched.setattr(jax, "jit", spy)
        with autograd.record():
            loss = (net(x) ** 2).sum()
    loss.backward()
    params = list(net.collect_params().values())
    # entry arguments: the param arrays in collect_params() order
    names = [p.name for p in params]
    cast = {p.name for p in params
            if p.name.endswith(("dense0_weight", "dense0_bias",
                                "dense1_weight", "dense1_bias"))}
    handed = [(p.name, p.grad().asnumpy()) for p in params
              if p.grad_req != "null"]
    return (lowered[0].as_text(), names, cast,
            {p.name: (np.dtype(p.dtype), p.shape) for p in params}, handed)


@pytest.mark.parametrize("caller", ["fused_module", "gluon_cachedop"])
def test_compute_dtype_cast_is_per_parameter(caller, monkeypatch):
    """Under the bf16 policy both callers cast each castable master by
    itself, at its own shape: no flat buffer of the parameters in the
    lowered text (on the chip a reshape between such a buffer and a tiled
    weight is a relayout, PERF.md section 6, PR 26), BatchNorm's keep_f32
    parameters, a zero-size and a non-float32 one reach their ops as they
    are, and what the optimizer is handed (gradients; for the Module also
    the updated parameters and the momentum) is float32 at the masters'
    shapes."""
    import re
    from mxnet_tpu import config
    with config.override(compute_dtype="bfloat16"):
        text, names, cast, masters, handed = (
            _policy_fused_module() if caller == "fused_module"
            else _policy_cachedop(monkeypatch))
    to_bf16 = {names[int(i)] for i in re.findall(
        r"stablehlo\.convert %arg(\d+) : \(tensor<[^>]*>\) -> "
        r"tensor<[0-9x]*bf16>", text) if int(i) < len(names)}
    assert to_bf16 == cast, (to_bf16, cast)
    # the grouped cast was a rank-1 concatenate of every castable master
    total = sum(int(np.prod(masters[k][1])) for k in cast)
    flat = [int(n) for n in re.findall(
        r"stablehlo\.concatenate [^\n]* -> tensor<(\d+)x[a-z0-9]+>", text)]
    assert all(n < total for n in flat), (flat, total)
    assert handed
    for k, a in handed:
        assert (a.dtype, a.shape) == masters[k], (k, a.dtype, a.shape)
        assert np.isfinite(a.astype("f4")).all(), k
