"""Granite 4.0-H (``model_type: granitemoehybrid``) through ``Module.fit``
against the plain reference (benchmarks/references/granite_hybrid.py), at a
small size on the CPU: hidden 64, Mamba-2 of 4 heads of 32 with a state of
16 in chunks of 64, attention of 4 heads of 16 over 2 KV heads, the
published layers 4-6 (Mamba, attention, Mamba), 300 tokens: no multiple of
the chunk. The core ``_contrib_Mamba2`` against the reference's
token-by-token recurrence, forward and every input's gradient, and its
Pallas kernels (``ops/pallas_ssd.py``) against the plain core; the
convolution's bias; the symbol's losses, every leaf's gradient (the tied
leaf's the sum of its two uses) and three fused Adam steps; each of
Granite's four multipliers shown to matter; the gauges and scopes the
program publishes."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from references import granite_hybrid as ref     # noqa: E402
from runners.train_lm_cfg import build_symbol    # noqa: E402
from test_kimi_linear import (_abstract, _fit, _gradients,  # noqa: E402
                              _named_eqns)

B = 2


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "tests", "benchmarks", "configs",
                           "granite_hybrid_tiny.json")) as f:
        return json.load(f)


def _weights(cfg, seed=5):
    """Matrices five times the stated initial scale, so that the mixers
    and the head all move the loss at this size."""
    return {k: (v * 5 if k.endswith("_weight") and "conv" not in k else v)
            for k, v in ref.init_params(cfg, seed).items()}


def _tokens(cfg, seed=0):
    t = cfg["sequence_length"]
    ids = np.random.RandomState(seed).randint(0, cfg["vocab_size"],
                                              (B, t + 1))
    return ids[:, :-1].astype("f4"), ids[:, 1:].astype("f4")


# ------------------------------------------------------------------ the core
def _core_inputs(t, h=4, p=8, n=16, seed=0):
    """x, B, C, dt, dt_bias, A_log, D; the steps small enough that a state
    outlives three chunks of 64 (a = exp(-delta A) near 0.99)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (B, t, h * p)),
            jax.random.normal(ks[1], (B, t, n)),
            jax.random.normal(ks[2], (B, t, n)),
            jax.random.normal(ks[3], (B, t, h)),
            jax.random.uniform(ks[4], (h,), minval=-6.0, maxval=-4.0),
            jnp.log(jax.random.uniform(ks[5], (h,), minval=1.0, maxval=4.0)),
            jax.random.normal(ks[6], (h,)))


def _token_by_token(x, bm, cm, dt, dt_bias, a_log, d, reset_every=None):
    """The reference's recurrence, one token at a time."""
    b, t, h = dt.shape
    delta = jax.nn.softplus(dt + dt_bias)
    xh = x.reshape(b, t, h, -1)
    y = ref.ssm_recurrence(xh, bm, cm, delta, -delta * jnp.exp(a_log),
                           reset_every) + d[:, None] * xh
    return y.reshape(x.shape)


# (tokens, chunk, group): a multiple of the chunk and not, the
# configuration's chunk of 256 and a smaller one, one group and several, a
# sequence shorter than a chunk
_CORES = [(300, 64, 2), (256, 64, 8), (100, 256, 8), (1024, 256, 2),
          (520, 256, 1)]


@pytest.mark.parametrize("t,chunk,group", _CORES)
def test_the_core_is_the_token_by_token_recurrence(t, chunk, group):
    from mxnet_tpu.ops.lm_ops import ssd_chunked as mamba2
    args = _core_inputs(t)
    h = args[3].shape[-1]

    def core(*a):
        return mamba2(*a, num_heads=h, chunk=chunk, group=group)
    want = _token_by_token(*args)
    got = core(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * scale)
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    every = tuple(range(len(args)))
    gots = jax.grad(lambda *a: jnp.sum(core(*a) * w), argnums=every)(*args)
    wants = jax.grad(lambda *a: jnp.sum(_token_by_token(*a) * w),
                     argnums=every)(*args)
    for name, a, b in zip(("x", "B", "C", "dt", "dt_bias", "A_log", "D"),
                          gots, wants):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


# ---- the core's chunks as two Pallas kernels (ops/pallas_ssd.py)
def _plain_core(monkeypatch):
    """No core is the kernels': every one takes ``_ssd_group``."""
    from mxnet_tpu.ops import pallas_ssd
    monkeypatch.setattr(pallas_ssd, "eligible", lambda *a: False)


def _close(names, got, want, rtol):
    """``rtol`` of an element, or of the array's largest where the element
    is small."""
    for name, a, w in zip(names, got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), rtol=rtol,
            atol=rtol * float(jnp.max(jnp.abs(w))), err_msg=name)


_NAMES = ("x", "B", "C", "dt", "dt_bias", "A_log", "D")


# (tokens, heads, head width, state, chunk, group): more than one group
# and a sequence that is no multiple of the chunk, heads of 64 two to a
# register and of 128 one to a register, the cell's chunk of 256 and 128
@pytest.mark.parametrize("t,h,p,n,chunk,group", [
    (300, 4, 64, 128, 128, 1), (520, 2, 128, 256, 256, 1)],
    ids=["T300-heads64-chunk128-three-groups",
         "T520-heads128-state256-chunk256-three-groups"])
def test_ssd_kernels_are_the_plain_core_and_the_recurrence(
        t, h, p, n, chunk, group, monkeypatch):
    """At shapes the kernels take (interpreted here), float32: the core's
    output and the gradients of x, B, C, dt, dt_bias, A_log and D against
    the plain ``_ssd_group`` in the same frame to float32 round-off, and
    against the reference's token-by-token recurrence at the tolerances
    the plain core is held to; the state passes from group to group."""
    from mxnet_tpu.ops import pallas_ssd
    from mxnet_tpu.ops.lm_ops import ssd_chunked
    assert pallas_ssd.eligible(p, n, chunk, h)
    args = _core_inputs(t, h=h, p=p, n=n)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, t, h * p))

    def both(core):
        return core(*args), jax.grad(lambda *a: jnp.sum(core(*a) * w),
                                     argnums=range(7))(*args)

    def chunked(*a):
        return ssd_chunked(*a, num_heads=h, chunk=chunk, group=group)
    with jax.default_matmul_precision("highest"):
        got, g_got = both(chunked)
        want, g_want = both(_token_by_token)
        _plain_core(monkeypatch)
        plain, g_plain = both(chunked)
    _close(["y"], [got], [plain], 1e-5)
    _close(_NAMES, g_got, g_plain, 1e-4)
    _close(["y"], [got], [want], 1e-4)
    for name, a, b in zip(_NAMES, g_got, g_want):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_groups_kernels_are_its_plain_path_from_a_state(dtype):
    """One group from a state that is not zero, with a cotangent on the
    state after it too: the state after the group, y, and the gradients
    of x, B, C, dt, the three per-head vectors and the state carried in,
    against ``_ssd_group``. In bfloat16 both round the products' operands
    at the same points, and a rounding that falls the other way (the sums
    run in another order) moves a value by a bfloat16 step: the outputs
    held to 2e-3, the gradients to 2e-2, of their largest."""
    from mxnet_tpu.ops import lm_ops
    h, p, n, chunk, t = 4, 64, 128, 128, 256
    x, bm, cm, dt, dt_bias, a_log, d = _core_inputs(t, h=h, p=p, n=n)
    given = (x.astype(dtype), bm.astype(dtype), cm.astype(dtype), dt,
             dt_bias, a_log, d, jax.random.normal(jax.random.PRNGKey(3),
                                                  (B, h, p, n)))
    cts = (jax.random.normal(jax.random.PRNGKey(4), (B, h, p, n)),
           jax.random.normal(jax.random.PRNGKey(5), (B, t, h * p)))

    def group(run):
        def f(x, bm, cm, dt, dt_bias, a_log, d, s):
            return run(h, chunk, (dt_bias, a_log, d), s, (x, bm, cm, dt))
        out, pull = jax.vjp(f, *given)
        return out, pull(cts)
    with jax.default_matmul_precision("highest"):
        (got, g_got), (want, g_want) = (group(lm_ops._ssd_kernel_group),
                                        group(lm_ops._ssd_group))
    for x_ in g_want:
        assert float(jnp.max(jnp.abs(x_.astype(jnp.float32)))) > 0
    tol = (1e-5, 1e-4) if dtype == jnp.float32 else (2e-3, 2e-2)
    _close(["s_end", "y"], got, want, tol[0])
    _close(_NAMES + ("s0",), [g.astype(jnp.float32) for g in g_got],
           [g.astype(jnp.float32) for g in g_want], tol[1])


def _mamba_symbol(h):
    """One ``_contrib_Mamba2`` op as a loss."""
    import mxnet_tpu as mx
    v = [mx.sym.Variable(k) for k in ("x", "b", "c", "dt", "dt_bias",
                                      "a_log", "d")]
    return mx.sym.MakeLoss(mx.sym.contrib.Mamba2(*v, num_heads=h,
                                                 chunk=128))


def _mamba_shapes(h, p, n, t=256):
    return dict(x=(1, t, h * p), b=(1, t, n), c=(1, t, n), dt=(1, t, h),
                dt_bias=(h,), a_log=(h,), d=(h,))


@pytest.mark.parametrize("p,n,kernel", [(32, 16, False), (64, 128, True),
                                        (128, 256, True), (64, 64, False),
                                        (256, 128, False)],
                         ids=["p32-plain", "p64-kernels", "p128-n256-kernels",
                              "n64-plain", "p256-plain"])
def test_the_core_selects_kernels_or_the_plain_path(p, n, kernel):
    """No flag: heads of 64 or 128 over a state of 128 or 256 go to the
    kernels, any other core to ``_ssd_group``. Read where a user would,
    from the gauges ``ssm/core_kernel`` and ``ssm/core_plain`` that a
    traced training program sets (0 for a graph with no Mamba-2 core), and
    from the program: the step's forward, the group recomputed inside the
    core's backward, and the backward's walk for the states on entry to
    the chunks and its walk back, each a kernel under ``mx/ssm/scan``, the
    backward's two entered by name."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import resnet_symbol
    gauges = [telemetry.gauge("ssm/core_" + k) for k in ("kernel", "plain")]
    h = 256 // p
    sym = _mamba_symbol(h)
    jaxpr = jax.make_jaxpr(_gradients(sym))(
        *_abstract(sym, **_mamba_shapes(h, p, n))).jaxpr
    assert [g.value() for g in gauges] == [int(kernel), int(not kernel)]
    kernels = [(e.params["name"], name) for e, name in _named_eqns(jaxpr)
               if e.primitive.name == "pallas_call"]
    assert sorted(k for k, _ in kernels) == (
        ["ssd_scan_bwd", "ssd_scan_fwd", "ssd_scan_fwd", "ssd_scan_states"]
        if kernel else [])
    for k, name in kernels:
        assert "mx/ssm/scan" in name, (k, name)
        assert ("transpose(jvp(mx/ssm/scan))" in name) \
            == (k != "ssd_scan_fwd"), (k, name)
    net = resnet_symbol(num_classes=10, num_layers=18, image_shape="3,32,32")
    jax.make_jaxpr(_gradients(net))(
        *_abstract(net, data=(2, 3, 32, 32), softmax_label=(2,)))
    assert [g.value() for g in gauges] == [0, 0]


def test_a_state_outlives_three_chunks():
    """What the last chunk's rows hold of the first chunk's tokens: a core
    that dropped the state at a chunk's edge (the reference's fault
    ``no_carry``) gives other rows from the second chunk on."""
    from mxnet_tpu.ops.lm_ops import ssd_chunked as mamba2
    args = _core_inputs(256)
    h = args[3].shape[-1]
    got = mamba2(*args, num_heads=h, chunk=64, group=2)
    dropped = _token_by_token(*args, reset_every=64)
    np.testing.assert_allclose(got[:, :64], dropped[:, :64], rtol=1e-4,
                               atol=1e-5)
    later = np.abs(np.asarray(got - dropped))[:, 192:]
    assert later.max() > 0.05 * float(jnp.max(jnp.abs(got)))
    # and the first chunk's x reaches the last chunk's y through the states
    reach = jax.grad(lambda x: jnp.sum(mamba2(
        x, *args[1:], num_heads=h, chunk=64, group=2)[:, 192:]))(args[0])
    assert float(jnp.max(jnp.abs(reach[:, :64]))) > 1e-3


def test_the_core_keeps_its_float32_parts_under_bfloat16():
    """bfloat16 inputs: the output is bfloat16 and close to the float32
    core's, the per-head vectors stay float32 and get float32 gradients."""
    from mxnet_tpu.ops.lm_ops import ssd_chunked as mamba2
    args = _core_inputs(300)
    h = args[3].shape[-1]
    half = tuple(a.astype(jnp.bfloat16) for a in args[:4]) + args[4:]
    got = mamba2(*half, num_heads=h, chunk=64, group=2)
    want = mamba2(*args, num_heads=h, chunk=64, group=2)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert err.max() < 0.05 * float(jnp.max(jnp.abs(want)))
    grads = jax.grad(lambda *a: jnp.sum(mamba2(
        *a, num_heads=h, chunk=64, group=2).astype(jnp.float32)),
        argnums=(0, 4, 5, 6))(*half)
    assert [g.dtype for g in grads] == [jnp.bfloat16] + [jnp.float32] * 3


def test_the_convolutions_bias_is_added_before_the_activation():
    from mxnet_tpu.ops.lm_ops import causal_conv1d
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 10, 6).astype("f4"))
    w = jnp.asarray(rng.randn(6, 4).astype("f4"))
    bias = jnp.asarray(rng.randn(6).astype("f4"))
    plain = causal_conv1d(x, w, act_type="none")
    np.testing.assert_allclose(plain, ref.causal_conv(x, w, None), rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(causal_conv1d(x, w, bias, act_type="none",
                                 no_bias=False)),
        np.asarray(plain + bias))
    np.testing.assert_allclose(
        causal_conv1d(x, w, bias, no_bias=False),
        jax.nn.silu(ref.causal_conv(x, w, bias)), rtol=1e-6)
    # no bias asked for: none is created for the symbol, as before
    import mxnet_tpu as mx
    sym = mx.sym.contrib.CausalConv1D(
        data=mx.sym.Variable("data"), weight=mx.sym.Variable("w"), name="c")
    assert sym.list_arguments() == ["data", "w"]


def test_the_gated_norm_gates_first():
    from mxnet_tpu.ops.lm_ops import gated_rms_norm
    rng = np.random.RandomState(0)
    y, z = (jnp.asarray(rng.randn(2, 5, 8).astype("f4")) for _ in range(2))
    g = jnp.asarray(rng.rand(8).astype("f4") + 0.5)
    want = ref.rms_norm(y * jax.nn.silu(z), g, 1e-5)
    np.testing.assert_allclose(gated_rms_norm(y, z, g, eps=1e-5), want,
                               rtol=1e-6)
    other = ref.rms_norm(y, g, 1e-5) * jax.nn.silu(z)     # norm first
    assert float(jnp.max(jnp.abs(other - want))) > 0.1


# ----------------------------------------------------------------- the model
@pytest.fixture(scope="module")
def fitted(cfg):
    """One ``fit`` of three steps, watched, and the reference's three steps
    from the same weights and tokens."""
    from mxnet_tpu import telemetry
    w0 = _weights(cfg)
    data, label = _tokens(cfg)
    compiles, seen = [], {"losses": [], "m": [], "compiles": []}

    def listen(event, *_a, **_k):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)

    def each_step(mod):
        seen["losses"].append(mod.get_outputs()[0].asnumpy())
        seen["m"].append({k: np.asarray(st[0])
                          for k, st in mod._fused_opt_state.items()})
        seen["compiles"].append(len(compiles))

    mod = _fit(cfg, build_symbol(cfg), w0, data, label, 3, each_step)
    step = jax.jit(lambda p, m, v, t: ref.train_step(
        cfg, p, m, v, t, jnp.asarray(data), jnp.asarray(label)))
    p, m = w0, jax.tree.map(jnp.zeros_like, w0)
    v, steps = m, []
    for t in (1, 2, 3):
        rows, _none, p, m, v = step(p, m, v, t)
        steps.append((np.asarray(rows), p, m))
    seen.update(mod=mod, w0=w0, ref=steps, data=data, label=label, gauges={
        g: telemetry.gauge(g).value()
        for g in ("ssm/layers", "ssm/chunks", "ssm/state_mb",
                  "attn/window_layers", "attn/full_layers",
                  "stage/kept_values", "stage/kept_mb", "kda/intra_plain",
                  "kda/scan_plain", "ssm/core_kernel", "ssm/core_plain",
                  "attn/bwd_kernel", "attn/bwd_plain")})
    return seen


def test_fit_follows_the_reference_losses_and_three_adam_steps(cfg, fitted):
    mod, w0 = fitted["mod"], fitted["w0"]
    assert mod._fused is not None, "the fused step did not engage"
    for got, (want, _p, _m) in zip(fitted["losses"], fitted["ref"]):
        assert got.shape == (B, cfg["sequence_length"])
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    args, aux = mod.get_params()
    assert set(args) == set(w0) and not aux
    assert "head_weight" not in args        # one matrix, two uses
    for k in sorted(w0):
        moved = np.asarray(fitted["ref"][-1][1][k]) - np.asarray(w0[k])
        got = args[k].asnumpy() - np.asarray(w0[k])
        assert np.linalg.norm(got - moved) \
            <= 0.02 * np.linalg.norm(moved) + 1e-12, k


def test_fit_through_the_kernels_follows_the_reference(cfg):
    """The same fit with Mamba-2 cores the kernels take (two heads of 64, a
    state of 128, chunks of 128: ``ssm/core_kernel`` 2): three steps'
    losses and every leaf after them as the reference's."""
    from mxnet_tpu import telemetry
    cfg = dict(cfg, mamba_d_head=64, mamba_n_heads=2, mamba_d_state=128,
               mamba_chunk_size=128)
    w0 = _weights(cfg)
    data, label = _tokens(cfg)
    losses = []
    mod = _fit(cfg, build_symbol(cfg), w0, data, label, 3,
               lambda m: losses.append(m.get_outputs()[0].asnumpy()))
    assert telemetry.gauge("ssm/core_kernel").value() == 2
    assert telemetry.gauge("ssm/core_plain").value() == 0
    step = jax.jit(lambda p, m, v, t: ref.train_step(
        cfg, p, m, v, t, jnp.asarray(data), jnp.asarray(label)))
    p, m = w0, jax.tree.map(jnp.zeros_like, w0)
    v = m
    for t, got in zip((1, 2, 3), losses):
        rows, _none, p, m, v = step(p, m, v, t)
        np.testing.assert_allclose(got, rows, rtol=2e-4, atol=2e-5)
    args, _ = mod.get_params()
    for k in sorted(w0):
        moved = np.asarray(p[k]) - np.asarray(w0[k])
        got = args[k].asnumpy() - np.asarray(w0[k])
        assert np.linalg.norm(got - moved) \
            <= 0.02 * np.linalg.norm(moved) + 1e-12, k


def test_every_leafs_gradient_is_the_references(cfg, fitted):
    """Adam's first moment after one step is (1 - beta1) g, for every
    leaf: the per-head vectors of the state-space layers, the
    convolution's bias and the tied matrix among them."""
    m_ref = fitted["ref"][0][2]
    b1 = cfg["optimizer"]["beta1"]
    scale = max(float(jnp.max(jnp.abs(v))) for v in m_ref.values()) / (1 - b1)
    for k in sorted(fitted["w0"]):
        got = fitted["m"][0][k] / (1 - b1)
        want = np.asarray(m_ref[k]) / (1 - b1)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5 * scale,
                                   err_msg=k)
    for k in ("l4_mamba_A_log", "l4_mamba_dt_bias", "l6_mamba_D",
              "l4_mamba_conv_bias", "l5_attn_k_weight", "embed_weight"):
        assert float(jnp.max(jnp.abs(m_ref[k]))) > 0, k


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses(cfg, fitted):
    """The embedding's use alone (the head's dropped: the reference's
    fault ``untied``) and the head's alone add up to the program's."""
    w0, data, label = fitted["w0"], fitted["data"], fitted["label"]
    b1 = cfg["optimizer"]["beta1"]
    got = fitted["m"][0]["embed_weight"] / (1 - b1)

    def mean_loss(w, fault=None):
        rows, _ = ref.forward(cfg, w, jnp.asarray(data), jnp.asarray(label),
                              fault=fault)
        return jnp.mean(rows)
    as_embedding = jax.grad(lambda w: mean_loss(w, "untied"))(w0)[
        "embed_weight"]

    def head_alone(e):      # a head of its own: the embedding's use is out
        rows, _ = _forward_with_head(cfg, w0, e, data, label)
        return jnp.mean(rows)
    as_head = jax.grad(head_alone)(w0["embed_weight"])
    scale = float(np.max(np.abs(got)))
    assert float(jnp.max(jnp.abs(as_embedding))) > 0.01 * scale
    assert float(jnp.max(jnp.abs(as_head))) > 0.01 * scale
    np.testing.assert_allclose(got, np.asarray(as_embedding + as_head),
                               rtol=2e-3, atol=2e-5 * scale)
    assert np.max(np.abs(got - np.asarray(as_embedding))) > 0.01 * scale


def _forward_with_head(cfg, params, head, data, label):
    """The reference's forward with ``head`` in the head's place and
    ``params['embed_weight']`` in the embedding's."""
    untied = dict(cfg, tie_word_embeddings=False)
    return ref.forward(untied, dict(params, head_weight=head),
                       jnp.asarray(data), jnp.asarray(label))


# builder's argument -> a wrong value: each multiplier has to matter
_WRONG = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
          "attention_multiplier": 2.0, "logits_scaling": 1.0}


@pytest.mark.parametrize("name", sorted(_WRONG))
def test_a_wrong_multiplier_is_another_model(cfg, fitted, name):
    """The symbol's forward with one multiplier off is not the
    reference's: its losses leave the tolerance the right one keeps, ten
    times over."""
    import mxnet_tpu as mx
    want = fitted["ref"][0][0]

    def losses(c):
        sym = build_symbol(c)
        shapes = {"data": fitted["data"].shape,
                  "softmax_label": fitted["label"].shape}
        ex = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
        for k, v in fitted["w0"].items():
            ex.arg_dict[k][:] = np.asarray(v)
        ex.arg_dict["data"][:] = fitted["data"]
        ex.arg_dict["softmax_label"][:] = fitted["label"]
        return ex.forward(is_train=False)[0].asnumpy()
    np.testing.assert_allclose(losses(cfg), want, rtol=2e-4, atol=2e-5)
    off = losses(dict(cfg, **{name: _WRONG[name]}))
    assert np.max(np.abs(off - want)) > 2e-3 * np.max(np.abs(want)), name


def test_one_program_a_step_and_the_scopes_reach_it(fitted):
    mod = fitted["mod"]
    first, second, third = fitted["compiles"]
    assert first == second == third      # steps 2 and 3 reuse step 1's
    lowered = mod._fused.lower(mod._exec._arg_vals(), mod._exec._aux_vals(),
                               mod._fused_opt_state, donate=True)
    assert "jit_step" in lowered.as_text()
    debug = lowered.as_text(debug_info=True)
    for scope in ("mx/ssm", "mx/ssm/conv", "mx/ssm/intra", "mx/ssm/scan",
                  "mx/attn/full", "mx/lm_head"):
        assert scope in debug, scope


def test_the_gauges_count_the_cores_their_chunks_and_their_states(cfg,
                                                                    fitted):
    """Two Mamba layers and one attention layer; 300 tokens are 5 chunks of
    64; a core keeps one float32 state (2 sequences x 4 heads x 32 x 16)
    on entry to each group of 8 chunks, here one group: 2 x 16,384 bytes.
    A stage keeps its core's output and states, or its attention's
    output and rows' log-sum-exp."""
    g = fitted["gauges"]
    assert (g["ssm/layers"], g["ssm/chunks"]) == (2, 5)
    assert g["ssm/state_mb"] == pytest.approx(2 * 4 * B * 4 * 32 * 16 / 1e6)
    assert (g["attn/window_layers"], g["attn/full_layers"]) == (0, 1)
    assert g["kda/intra_plain"] == 0 and g["kda/scan_plain"] == 0
    # heads of 32 over a state of 16 are no tile of the kernels'
    assert (g["ssm/core_kernel"], g["ssm/core_plain"]) == (0, 2)
    # heads of 16: the attention core's backward is the plain one
    assert (g["attn/bwd_kernel"], g["attn/bwd_plain"]) == (0, 1)
    assert g["stage/kept_values"] == 6
    t, inner = cfg["sequence_length"], 4 * 32
    assert g["stage/kept_mb"] == pytest.approx(
        (2 * 4 * B * 4 * 32 * 16 + 2 * 4 * B * t * inner
         + 4 * B * t * cfg["hidden_size"]
         + 4 * B * cfg["num_attention_heads"] * t) / 1e6)


def test_every_block_is_a_stage_and_the_scopes_are_the_builders(cfg):
    from mxnet_tpu.executor import _mirror_stages
    sym = build_symbol(cfg)
    stages = _mirror_stages(sym._topo(), list(sym._entries))
    assert len(stages) == len(cfg["layers"]) == 3
    scopes = {n.name: n.attrs.get("device_scope") for n in sym._topo()
              if not n.is_variable and n.attrs.get("device_scope")}
    assert scopes == {"l4_mamba_conv": "mx/ssm/conv", "l4_mamba_norm": "mx/ssm",
                      "l5_attn": "mx/attn/full",
                      "l6_mamba_conv": "mx/ssm/conv", "l6_mamba_norm": "mx/ssm"}
    args = sym.list_arguments()
    assert args.count("embed_weight") == 1 and "head_weight" not in args


def test_an_unknown_layer_type_and_a_second_group_are_refused():
    from mxnet_tpu.models import granite_hybrid_symbol
    with pytest.raises(ValueError, match="unknown layer type"):
        granite_hybrid_symbol(layer_types=("mamba", "moe"), layers=(0, 1))
    with pytest.raises(ValueError, match="one group"):
        granite_hybrid_symbol(mamba_n_groups=2, layers=(0,))


def test_untied_the_symbol_has_a_head_of_its_own(cfg):
    sym = build_symbol(dict(cfg, tie_word_embeddings=False))
    assert set(sym.list_arguments()) - {"data", "softmax_label"} \
        == set(ref.param_shapes(dict(cfg, tie_word_embeddings=False)))
