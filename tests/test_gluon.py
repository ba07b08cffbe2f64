"""Gluon core tests (model: reference tests/python/unittest/test_gluon.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.test_utils import assert_almost_equal


def test_parameter_basic():
    p = gluon.Parameter("weight", shape=(10, 10))
    p.initialize(init="xavier")
    assert p.data().shape == (10, 10)
    assert p.grad().shape == (10, 10)
    assert p.list_ctx() == [mx.current_context()]


def test_parameter_invalid_access():
    p = gluon.Parameter("weight", shape=(10, 10))
    with pytest.raises(RuntimeError):
        p.data()


def test_constant():
    class Test(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            self.value = np.asarray([[1, 2], [3, 4]], dtype="float32")
            self.const = self.params.get_constant("const", self.value)

        def hybrid_forward(self, F, x, const):
            return x + const

    test = Test()
    test.initialize()
    trainer = gluon.Trainer(test.collect_params(), "sgd",
                            {"learning_rate": 1.0, "momentum": 0.5})
    with autograd.record():
        x = mx.nd.ones((2, 2))
        x.attach_grad()
        y = test(x)
        y.backward()
    trainer.step(1)
    assert (test.const.data().asnumpy() == test.value).all()
    assert (x.grad.asnumpy() == 1).all()


def test_paramdict_get_shared():
    shared = gluon.ParameterDict("net_")
    d1 = gluon.ParameterDict("net_", shared)
    p0 = shared.get("w", shape=(2, 2))
    p1 = d1.get("w")
    assert p0 is p1


def test_dense_forward_value():
    layer = nn.Dense(3, in_units=4, use_bias=True)
    layer.initialize(mx.init.One())
    x = mx.nd.array(np.arange(8).reshape(2, 4).astype("float32"))
    out = layer(x)
    # per-param init wins over default_init: bias_initializer='zeros' holds
    expect = np.arange(8).reshape(2, 4).sum(1, keepdims=True)
    assert_almost_equal(out.asnumpy(), np.tile(expect, (1, 3)))


def test_dense_deferred_init():
    layer = nn.Dense(7)
    layer.initialize()
    x = mx.nd.ones((4, 5))
    out = layer(x)
    assert out.shape == (4, 7)
    assert layer.weight.shape == (7, 5)


def test_dense_no_flatten():
    layer = nn.Dense(5, flatten=False)
    layer.initialize()
    out = layer(mx.nd.ones((2, 3, 4)))
    assert out.shape == (2, 3, 5)


def test_sequential_and_indexing():
    net = nn.Sequential()
    net.add(nn.Dense(4), nn.Dense(3), nn.Dense(2))
    assert len(net) == 3
    assert isinstance(net[1], nn.Dense)
    net.initialize()
    assert net(mx.nd.ones((1, 6))).shape == (1, 2)


def test_hybrid_matches_eager():
    np.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"),
                nn.LayerNorm(),
                nn.Dense(8))
    net.initialize()
    x = mx.nd.array(np.random.randn(4, 16).astype("float32"))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    assert_almost_equal(eager, hybrid, rtol=1e-5, atol=1e-5)


def test_hybrid_gradients_match_eager():
    np.random.seed(1)
    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="tanh"), nn.Dense(1))
        net.initialize(mx.init.Xavier())
        return net

    import tempfile, os
    net_e = build()
    x = mx.nd.array(np.random.randn(5, 8).astype("float32"))
    net_e(x)  # trigger deferred init
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "p.params")
        net_e.save_parameters(fname)
        net_h = build()
        net_h(x)
        net_h.load_parameters(fname)
    net_h.hybridize()
    grads = []
    for net in (net_e, net_h):
        with autograd.record():
            y = net(x)
            loss = (y * y).sum()
        loss.backward()
        grads.append({k: p.grad().asnumpy()
                      for k, p in net.collect_params().items()})
    keys_e = sorted(grads[0])
    keys_h = sorted(grads[1])
    for ke, kh in zip(keys_e, keys_h):
        assert_almost_equal(grads[0][ke], grads[1][kh], rtol=1e-4, atol=1e-5)


def test_batchnorm_running_stats_update():
    bn = nn.BatchNorm(in_channels=3)
    bn.initialize()
    x = mx.nd.array(np.random.randn(8, 3, 4, 4).astype("float32") * 2 + 5)
    with autograd.record():
        bn(x)
    rm = bn.running_mean.data().asnumpy()
    assert not np.allclose(rm, 0)  # moving mean moved toward batch mean
    # inference mode uses running stats, no update
    rm_before = rm.copy()
    bn(x)
    assert_almost_equal(bn.running_mean.data().asnumpy(), rm_before)


def test_batchnorm_hybrid_updates_stats():
    bn = nn.BatchNorm(in_channels=2)
    bn.initialize()
    bn.hybridize()
    x = mx.nd.array(np.random.randn(4, 2, 3, 3).astype("float32") + 3)
    with autograd.record():
        bn(x)
    assert not np.allclose(bn.running_mean.data().asnumpy(), 0)


def test_conv2d_shapes():
    layer = nn.Conv2D(16, (3, 3), padding=(1, 1))
    layer.initialize()
    out = layer(mx.nd.ones((2, 4, 8, 8)))
    assert out.shape == (2, 16, 8, 8)
    assert layer.weight.shape == (16, 4, 3, 3)


def test_conv1d_conv3d():
    l1 = nn.Conv1D(4, 3)
    l1.initialize()
    assert l1(mx.nd.ones((2, 3, 10))).shape == (2, 4, 8)
    l3 = nn.Conv3D(4, (2, 2, 2))
    l3.initialize()
    assert l3(mx.nd.ones((2, 3, 5, 5, 5))).shape == (2, 4, 4, 4, 4)


def test_conv2d_transpose():
    layer = nn.Conv2DTranspose(8, (3, 3), strides=(2, 2))
    layer.initialize()
    out = layer(mx.nd.ones((1, 4, 7, 7)))
    assert out.shape[0:2] == (1, 8)


def test_pooling_layers():
    x = mx.nd.ones((2, 3, 8, 8))
    assert nn.MaxPool2D()(x).shape == (2, 3, 4, 4)
    assert nn.AvgPool2D((2, 2), strides=2)(x).shape == (2, 3, 4, 4)
    assert nn.GlobalAvgPool2D()(x).shape == (2, 3, 1, 1)
    assert nn.GlobalMaxPool2D()(x).shape == (2, 3, 1, 1)


def test_activations_layers():
    x = mx.nd.array(np.array([-1.0, 0.0, 2.0], dtype="float32"))
    assert_almost_equal(nn.Activation("relu")(x).asnumpy(),
                        np.array([0, 0, 2], dtype="float32"))
    out = nn.LeakyReLU(0.1)(x).asnumpy()
    assert_almost_equal(out, np.array([-0.1, 0, 2], dtype="float32"))
    for layer in [nn.ELU(), nn.SELU(), nn.Swish(), nn.GELU()]:
        y = layer(x)
        assert y.shape == x.shape
    pr = nn.PReLU()
    pr.initialize()
    assert pr(x).shape == x.shape


def test_embedding():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    idx = mx.nd.array(np.array([1, 2, 3], dtype="float32"))
    out = emb(idx)
    assert out.shape == (3, 4)
    with autograd.record():
        loss = emb(idx).sum()
    loss.backward()
    g = emb.weight.grad().asnumpy()
    assert g[1].sum() != 0 and g[0].sum() == 0


def test_trainer_sgd_matches_manual():
    net = nn.Dense(1, in_units=2, use_bias=False)
    net.initialize(mx.init.One())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5})
    x = mx.nd.array([[1.0, 2.0]])
    with autograd.record():
        y = net(x)
    y.backward()
    trainer.step(1)
    # w <- w - 0.5 * grad; grad = x
    assert_almost_equal(net.weight.data().asnumpy(),
                        np.array([[0.5, 0.0]], dtype="float32"))


def test_trainer_save_load_states(tmp_path):
    net = nn.Dense(2, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    x = mx.nd.ones((1, 2))
    with autograd.record():
        net(x).sum().backward()
    trainer.step(1)
    fname = str(tmp_path / "trainer.states")
    trainer.save_states(fname)
    trainer.load_states(fname)


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    x = mx.nd.ones((1, 3))
    y0 = net(x).asnumpy()
    fname = str(tmp_path / "net.params")
    net.save_parameters(fname)

    net2 = nn.HybridSequential()
    with net2.name_scope():
        net2.add(nn.Dense(4), nn.Dense(2))
    net2.load_parameters(fname)
    assert_almost_equal(net2(x).asnumpy(), y0)


def test_losses_values():
    pred = mx.nd.array(np.array([[1.0, 2.0], [0.5, 0.5]], dtype="float32"))
    label = mx.nd.array(np.array([[0.0, 1.0], [1.0, 0.0]], dtype="float32"))
    l2 = gluon.loss.L2Loss()(pred, label).asnumpy()
    expect = ((np.array([[1, 1], [-0.5, 0.5]]) ** 2) / 2).mean(1)
    assert_almost_equal(l2, expect.astype("float32"), rtol=1e-5)

    l1 = gluon.loss.L1Loss()(pred, label).asnumpy()
    assert_almost_equal(l1, np.abs(
        np.array([[1, 1], [-0.5, 0.5]])).mean(1).astype("float32"), rtol=1e-5)

    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    sparse_label = mx.nd.array(np.array([1, 0], dtype="float32"))
    out = sce(pred, sparse_label).asnumpy()
    p = np.exp([[1, 2], [0.5, 0.5]])
    p = p / p.sum(1, keepdims=True)
    expect = -np.log(np.array([p[0, 1], p[1, 0]]))
    assert_almost_equal(out, expect.astype("float32"), rtol=1e-5)


def test_loss_shapes():
    pred = mx.nd.ones((4, 3))
    lab = mx.nd.ones((4, 3))
    for L in [gluon.loss.SigmoidBCELoss(), gluon.loss.KLDivLoss(),
              gluon.loss.HuberLoss(), gluon.loss.HingeLoss(),
              gluon.loss.SquaredHingeLoss(), gluon.loss.LogisticLoss()]:
        out = L(pred, lab)
        assert out.shape == (4,), (type(L).__name__, out.shape)
    tl = gluon.loss.TripletLoss()
    assert tl(pred, lab, 0 * lab).shape == (4,)


def test_split_and_load():
    data = mx.nd.array(np.arange(12).reshape(6, 2).astype("float32"))
    parts = gluon.utils.split_data(data, 3)
    assert [p.shape for p in parts] == [(2, 2)] * 3
    loaded = gluon.utils.split_and_load(data, [mx.cpu(), mx.cpu()])
    assert len(loaded) == 2
    with pytest.raises(ValueError):
        gluon.utils.split_data(data, 5)


def test_clip_global_norm():
    arrays = [mx.nd.ones((2, 2)) * 3, mx.nd.ones((2,)) * 4]
    total = gluon.utils.clip_global_norm(arrays, 1.0)
    new_norm = np.sqrt(sum((a.asnumpy() ** 2).sum() for a in arrays))
    assert abs(new_norm - 1.0) < 1e-4
    assert total > 1.0


def test_block_naming_and_scopes():
    class Model(gluon.Block):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.dense0 = nn.Dense(5)
                self.dense1 = nn.Dense(5)

        def forward(self, x):
            return self.dense1(self.dense0(x))

    model = Model(prefix="model_")
    assert model.prefix == "model_"
    assert model.dense0.prefix.startswith("model_dense")
    names = list(model.collect_params().keys())
    assert all(n.startswith("model_") for n in names)


def test_collect_params_select():
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(nn.Dense(4, prefix="fc1_"), nn.Dense(4, prefix="fc2_"))
    sel = net.collect_params("net_fc1_.*")
    assert all("fc1" in k for k in sel.keys())
    assert len(sel) == 2


def test_forward_hooks():
    calls = []
    net = nn.Dense(2, in_units=2)
    net.initialize()
    net.register_forward_pre_hook(lambda blk, ins: calls.append("pre"))
    net.register_forward_hook(lambda blk, ins, outs: calls.append("post"))
    net(mx.nd.ones((1, 2)))
    assert calls == ["pre", "post"]


def test_symbol_block_and_export(tmp_path):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    x = mx.nd.ones((2, 4))
    y0 = net(x).asnumpy()
    path = str(tmp_path / "model")
    net.export(path)
    imported = gluon.SymbolBlock.imports(path + "-symbol.json", ["data"],
                                         path + "-0000.params")
    y1 = imported(x).asnumpy()
    assert_almost_equal(y0, y1, rtol=1e-5, atol=1e-6)


def test_lambda_blocks():
    lam = nn.Lambda(lambda x: x * 2)
    assert_almost_equal(lam(mx.nd.ones((2,))).asnumpy(),
                        np.full((2,), 2, dtype="float32"))
    hl = nn.HybridLambda(lambda F, x: F.relu(x))
    assert hl(mx.nd.array(np.array([-1.0, 1.0]))).asnumpy()[0] == 0


def test_hybrid_static_shape_cache():
    net = nn.Dense(4, in_units=3)
    net.initialize()
    net.hybridize()
    net(mx.nd.ones((2, 3)))
    net(mx.nd.ones((5, 3)))  # second signature compiles separately
    assert len(net._cached_graph) == 2


def test_zero_grad_and_grad_req():
    p = gluon.Parameter("w_weight", shape=(2,))
    p.initialize()
    x = p.data()
    with autograd.record():
        (x * 2).sum().backward()
    assert p.grad().asnumpy().sum() != 0
    p.zero_grad()
    assert p.grad().asnumpy().sum() == 0
    p.grad_req = "null"
    with pytest.raises(RuntimeError):
        p.grad()


def test_lr_mult_freezes_param():
    """Review regression: Parameter.lr_mult must reach the optimizer."""
    net = nn.Dense(1, in_units=2, use_bias=False)
    net.initialize(mx.init.One())
    net.weight.lr_mult = 0.0
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1.0})
    with autograd.record():
        net(mx.nd.ones((1, 2))).backward()
    trainer.step(1)
    assert_almost_equal(net.weight.data().asnumpy(),
                        np.ones((1, 2), dtype="float32"))


def test_ctc_loss_lengths_change_result():
    """Review regression: pred_lengths must affect the CTC loss value."""
    np.random.seed(3)
    pred = mx.nd.array(np.random.randn(2, 20, 5).astype("float32"))  # NTC
    label = mx.nd.array(np.array([[1, 2, -1, -1], [2, 3, -1, -1]],
                                 dtype="float32"))  # -1 pad (blank='last')
    L = gluon.loss.CTCLoss()
    full = L(pred, label).asnumpy()
    lens = mx.nd.array(np.array([10, 20], dtype="float32"))
    lab_lens = mx.nd.array(np.array([2, 2], dtype="float32"))
    short = L(pred, label, lens, lab_lens).asnumpy()
    assert not np.allclose(full[0], short[0])  # sample 0 truncated at t=10
    assert np.allclose(full[1], short[1], rtol=1e-4)  # sample 1 full length


def test_trainer_stale_grad_detection():
    net = nn.Dense(1, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    with pytest.raises(UserWarning):
        trainer.step(1)  # no backward ran
    trainer.step(1, ignore_stale_grad=True)  # suppressed


def test_export_roundtrip_via_load_parameters(tmp_path):
    """Review regression: load_parameters on an export()-style file must not
    double-prefix names."""
    def build():
        net = nn.HybridSequential(prefix="model_")
        with net.name_scope():
            net.add(nn.Dense(3))
        net.initialize()
        return net

    net = build()
    x = mx.nd.ones((1, 2))
    y0 = net(x).asnumpy()
    fname = str(tmp_path / "full.params")
    net.collect_params().save(fname)  # fully-prefixed names
    net2 = build()
    net2(x)
    net2.collect_params().load(fname, restore_prefix="")
    # and through Block.load_parameters (auto-detect unstripped prefix)
    net3 = build()
    net3(x)
    net3.load_parameters(fname)
    assert_almost_equal(net3(x).asnumpy(), y0)


def test_hybrid_forward_contrib_namespace():
    """F.contrib.* must resolve inside hybrid_forward under BOTH eager and
    hybridized execution (reference hybrid blocks use F.contrib ops)."""
    import numpy as np

    class Net(gluon.HybridBlock):
        def hybrid_forward(self, F, x):
            # CamelCase contrib op and a snake_case one
            y = F.contrib.div_sqrt_dim(x)
            q = F.expand_dims(x, axis=1)            # (N, 1, T, D)
            att = F.contrib.FlashAttention(q, q, q, causal=True)
            return y + F.reshape(att, shape=(-3, 0, 0))

    net = Net()
    net.initialize()
    x = mx.nd.random.normal(shape=(2, 4, 9))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    np.testing.assert_allclose(eager, hybrid, rtol=1e-5, atol=1e-6)


def test_hybridize_compute_dtype_policy_bf16():
    """Session dtype policy (MXNET_COMPUTE_DTYPE=bfloat16) on the CachedOp
    path: compute runs bf16 off a cast of each parameter, BatchNorm
    params/stats are excluded (stay f32), and outputs track the f32 run."""
    from mxnet_tpu import config

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8), nn.BatchNorm(), nn.Activation("relu"),
                nn.Dense(3))
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(0).randn(4, 5).astype("f4"))
    y32 = net(x).asnumpy()
    with config.override(compute_dtype="bfloat16"):
        ybf = net(x)
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
    assert ybf.dtype == np.dtype("bfloat16").type or \
        str(ybf.asnumpy().dtype) == "bfloat16"
    assert_almost_equal(ybf.asnumpy().astype("f4"), y32, rtol=0.05,
                        atol=0.05)
    for name, p in net.collect_params().items():
        assert p.data().dtype == np.float32, name  # masters untouched
        if p.grad_req != "null":
            assert np.isfinite(p.grad().asnumpy().astype("f4")).all(), name
    # BatchNorm keeps f32 params/stats even under an explicit low-p cast
    bn = [b for b in net._children.values()
          if isinstance(b, nn.BatchNorm)][0]
    bn.cast("bfloat16")
    assert bn.gamma.dtype == np.float32
    assert bn.running_mean.dtype == np.float32
