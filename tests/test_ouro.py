"""Ouro (``model_type: ouro``), a looped language model, through
``Module.fit`` against the plain reference (benchmarks/references/ouro.py),
at a small size on the CPU: hidden 64, 4 heads of 16, the published layers
3-4 run 4 times, a vocabulary of 96, 2 sequences of 64 tokens. The symbol's
losses, every leaf's gradient and three fused Adam steps; a layer's
gradient as the sum of its four passes'; the exit distribution, the loss
it weights, and what it reduces to with the gates shut; the counters the
exit op carries; two heads on one matrix weighted by values of the graph;
the stages that read a shared variable, and the scopes the program runs
under."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from references import ouro as ref               # noqa: E402
from runners.train_lm_cfg import build_symbol    # noqa: E402
from test_kimi_linear import _fit, _gradients, _named_eqns  # noqa: E402

B = 2


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "tests", "benchmarks", "configs",
                           "ouro_tiny.json")) as f:
        return json.load(f)


def _weights(cfg, seed=5):
    """Matrices five times the stated initial scale, so that attention and
    the gate move the loss at this size."""
    return {k: (v * 5 if k.endswith("_weight") else v)
            for k, v in ref.init_params(cfg, seed).items()}


def _tokens(cfg, seed=0):
    t = cfg["sequence_length"]
    ids = np.random.RandomState(seed).randint(0, cfg["vocab_size"],
                                              (B, t + 1))
    return ids[:, :-1].astype("f4"), ids[:, 1:].astype("f4")


@pytest.fixture(scope="module")
def fitted(cfg):
    """One ``fit`` of three steps: every step's losses and Adam's first
    moment after it; the reference's three steps beside them."""
    from mxnet_tpu import telemetry
    w0 = _weights(cfg)
    data, label = _tokens(cfg)
    seen = {"losses": [], "m": []}

    def each_step(mod):
        seen["losses"].append(mod.get_outputs()[0].asnumpy())
        seen["m"].append({k: np.asarray(st[0])
                          for k, st in mod._fused_opt_state.items()})

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
        for g in ("attn/full_layers", "attn/bwd_kernel", "attn/bwd_plain")})
    return seen


def test_fit_follows_the_reference_losses_and_three_adam_steps(cfg, fitted):
    mod, w0 = fitted["mod"], fitted["w0"]
    assert mod._fused is not None, "the fused step did not engage"
    for got, (want, _p, _m) in zip(fitted["losses"], fitted["ref"]):
        assert got.shape == (B, cfg["sequence_length"])
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    args, aux = mod.get_params()
    assert set(args) == set(w0) and set(aux) == {"exit_counters"}
    for k in sorted(w0):
        moved = np.asarray(fitted["ref"][-1][1][k]) - np.asarray(w0[k])
        got = args[k].asnumpy() - np.asarray(w0[k])
        assert np.linalg.norm(got - moved) \
            <= 0.02 * np.linalg.norm(moved) + 1e-12, k


def test_every_leafs_gradient_is_the_references(cfg, fitted):
    """Adam's first moment after one step is (1 - beta1) g, for every
    leaf: the gate's weight and bias, the four norms of a layer, the final
    norm and the head among them."""
    m_ref = fitted["ref"][0][2]
    b1 = cfg["optimizer"]["beta1"]
    scale = max(float(jnp.max(jnp.abs(v))) for v in m_ref.values()) / (1 - b1)
    for k in sorted(fitted["w0"]):
        got = fitted["m"][0][k] / (1 - b1)
        want = np.asarray(m_ref[k]) / (1 - b1)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5 * scale,
                                   err_msg=k)
    for k in ("exit_gate_weight", "exit_gate_bias", "l3_input_norm_2_gamma",
              "l4_post_attn_norm_2_gamma", "final_norm_gamma",
              "head_weight", "embed_weight"):
        assert float(jnp.max(jnp.abs(m_ref[k]))) > 0, k


def _loss_with_a_copy_a_pass(cfg, w0, copies, data, label):
    """The reference's mean loss with pass ``t`` reading layer ``l`` from
    ``copies[t][l]`` (every pass its own weights), the rest from ``w0``."""
    d = ref.dims(cfg)
    b, t = data.shape
    lab = jnp.asarray(label).astype(jnp.int32).reshape(b * t)
    h = w0["embed_weight"][jnp.asarray(data).astype(jnp.int32)]
    rows, logits = [], []
    for held in copies:
        for l in d["layers"]:
            h = ref.layer(d, held[l], h, ref._identity)
        h = ref.rms_norm(h, w0["final_norm_gamma"], d["eps"])
        rows.append(ref.head_rows(w0["head_weight"], h.reshape(b * t, -1),
                                  lab, ref._identity).reshape(b, t))
        logits.append(ref.exit_gate(w0, h))
    return jnp.mean(ref.exit_loss(jnp.stack(rows), jnp.stack(logits),
                                  d["beta"]))


def test_a_layers_gradient_is_the_sum_of_its_four_passes(cfg, fitted):
    """Each pass given a copy of the layers of its own: the copies'
    gradients add up to the program's gradient of the one shared leaf,
    and no pass's alone is it."""
    w0, b1 = fitted["w0"], cfg["optimizer"]["beta1"]
    d = ref.dims(cfg)
    copies = [{l: ref.layer_of(w0, l) for l in d["layers"]}
              for _ in range(d["passes"])]
    per_pass = jax.grad(_loss_with_a_copy_a_pass, argnums=2)(
        cfg, w0, copies, fitted["data"], fitted["label"])
    for name in ("attn_q_weight", "mlp_down_weight", "input_norm_2_gamma"):
        for l in d["layers"]:
            got = fitted["m"][0]["l%d_%s" % (l, name)] / (1 - b1)
            parts = [np.asarray(g[l][name]) for g in per_pass]
            scale = float(np.max(np.abs(got)))
            np.testing.assert_allclose(got, sum(parts), rtol=2e-3,
                                       atol=2e-5 * scale)
            assert all(np.max(np.abs(got - p)) > 0.01 * scale
                       for p in parts), (l, name)


def test_the_exit_distribution_sums_to_one_and_is_the_references():
    from mxnet_tpu.ops.lm_ops import exit_distribution
    for passes in (1, 2, 4):
        logits = jax.random.normal(jax.random.PRNGKey(passes),
                                   (passes, 3, 5)) * 3
        log_p = exit_distribution(logits)
        np.testing.assert_allclose(jnp.sum(jnp.exp(log_p), 0), 1.0,
                                   rtol=1e-6)
        np.testing.assert_allclose(log_p, ref.exit_log_probs(logits),
                                   rtol=1e-6, atol=1e-6)
        # the first pass leaves with its gate's probability
        np.testing.assert_allclose(
            jnp.exp(log_p[0]),
            jax.nn.sigmoid(logits[0]) if passes > 1 else 1.0, rtol=1e-6)


def test_the_loss_is_the_expected_loss_less_beta_times_the_entropy():
    from mxnet_tpu.ops.lm_ops import loop_exit_loss
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    rows = jax.random.uniform(ks[0], (4, 2, 6), maxval=5.0)
    logits = jax.random.normal(ks[1], (4, 2, 6))
    counters = jnp.zeros((4,))
    p = jnp.exp(ref.exit_log_probs(logits))
    for beta in (0.0, 0.1):
        loss, _ = loop_exit_loss(logits, rows, counters, beta=beta)
        entropy = -jnp.sum(p * jnp.log(p), 0)
        np.testing.assert_allclose(loss, jnp.sum(p * rows, 0) - beta * entropy,
                                   rtol=1e-5)
    shut, _ = loop_exit_loss(jnp.full(logits.shape, -40.0), rows, counters,
                             beta=0.0)
    np.testing.assert_allclose(shut, rows[-1], rtol=1e-6)


def test_with_the_gates_shut_and_no_entropy_the_loss_is_the_last_passs(cfg):
    """The symbol with beta 0 and the gate's bias at -40: every token's
    loss is the last pass's cross-entropy, as the reference computes it."""
    import mxnet_tpu as mx
    cfg0 = dict(cfg, exit_entropy_weight=0.0)
    w0 = dict(_weights(cfg), exit_gate_bias=jnp.full((1,), -40.0))
    data, label = _tokens(cfg)
    sym = build_symbol(cfg0)
    ex = sym.simple_bind(mx.cpu(), grad_req="null", data=data.shape,
                         softmax_label=label.shape)
    for k, v in w0.items():
        ex.arg_dict[k][:] = np.asarray(v)
    ex.arg_dict["data"][:] = data
    ex.arg_dict["softmax_label"][:] = label
    got = ex.forward(is_train=True)[0].asnumpy()
    rows, _ = ref.passes(cfg0, w0, jnp.asarray(data), jnp.asarray(label))
    np.testing.assert_allclose(got, rows[-1], rtol=2e-5, atol=1e-6)
    assert np.max(np.abs(np.asarray(rows[0] - rows[-1]))) > 0.01


def test_the_exit_counters_are_reported(cfg, fitted):
    """``loop/passes``, ``exit/mean_step`` and ``exit/last_share``: the
    running means over the three steps of what the reference's gates give
    at the weights each step started from."""
    from mxnet_tpu import telemetry
    counters = {k: v for k, (v, _) in fitted["mod"]._op_counters().items()}
    assert set(counters) == {"loop/passes", "exit/mean_step",
                             "exit/last_share"}
    assert counters["loop/passes"] == 4
    assert telemetry.gauge("loop/passes").value() == 4
    data, label = jnp.asarray(fitted["data"]), jnp.asarray(fitted["label"])
    starts = [fitted["w0"]] + [p for _r, p, _m in fitted["ref"][:2]]
    mean_step, last = [], []
    for w in starts:
        _rows, logits = ref.passes(cfg, w, data, label)
        p = jnp.exp(ref.exit_log_probs(logits))
        mean_step.append(float(jnp.mean(jnp.sum(
            p * jnp.arange(1, 5)[:, None, None], 0))))
        last.append(float(jnp.mean(p[-1])))
    assert counters["exit/mean_step"] == pytest.approx(np.mean(mean_step),
                                                       rel=1e-4)
    assert counters["exit/last_share"] == pytest.approx(np.mean(last),
                                                        rel=1e-4)
    assert 1 < counters["exit/mean_step"] < 4
    assert 0 < counters["exit/last_share"] < 1


def test_every_layer_pass_is_an_attention_core_whose_backward_is_counted(
        cfg, fitted):
    """Two layers run four times are eight attention cores; heads of 16
    are no shape of the backward kernel's (``bwd_eligible``), so each
    core's backward is the plain one."""
    g = fitted["gauges"]
    cores = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    assert cores == 8
    assert g["attn/full_layers"] == cores
    assert (g["attn/bwd_kernel"], g["attn/bwd_plain"]) == (0, cores)


def test_two_heads_on_one_matrix_take_the_cotangents_the_graph_gives():
    """``_contrib_LMHeadLoss`` twice on one weight, its rows weighted by a
    gate the graph computes: every gradient is the plain
    cross-entropy's under the same weights, and the weight's the sum of
    its two uses."""
    from mxnet_tpu import symbol as sym
    b, t, hid, vocab = 2, 8, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    vals = {"x1": jax.random.normal(ks[0], (b, t, hid)),
            "x2": jax.random.normal(ks[1], (b, t, hid)),
            "w": jax.random.normal(ks[2], (vocab, hid)) * 0.5,
            "g": jax.random.normal(ks[3], (b, t)),
            "label": jnp.asarray(np.random.RandomState(0).randint(
                0, vocab, (b, t)), jnp.float32)}
    w, lab, g = sym.Variable("w"), sym.Variable("label"), sym.Variable("g")
    a = sym.sigmoid(g)
    out = sym.contrib.LMHeadLoss(data=sym.Variable("x1"), weight=w,
                                 label=lab) * a \
        + sym.contrib.LMHeadLoss(data=sym.Variable("x2"), weight=w,
                                 label=lab) * (1 - a)
    got = _gradients(out)(vals, {})

    def plain(v):
        lab_i = v["label"].astype(jnp.int32).reshape(-1)

        def ce(x):
            return ref.head_rows(v["w"], x.reshape(b * t, hid), lab_i,
                                 ref._identity).reshape(b, t)
        s = jax.nn.sigmoid(v["g"])
        return jnp.sum(ce(v["x1"]) * s + ce(v["x2"]) * (1 - s))
    want = jax.grad(plain)({k: v for k, v in vals.items()})
    for k in ("x1", "x2", "w", "g"):
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


def test_stages_that_share_a_variable_and_those_that_do_not(cfg):
    """Every (pass, layer) is a stage of its own, the last of a pass with
    the final norm and the gate; the variables that several stages read
    are the layers', the final norm's and the gate's, and those stages
    hand their cotangents on together. The other language models of the
    benchmark, at the sizes their files state, have stages that share no
    variable (Granite's one shared matrix, which the embedding and the
    head read, lies outside any stage), so their programs never take that
    path."""
    from mxnet_tpu.executor import _mirror_stages, _read_by_several
    from mxnet_tpu.models.kimi_linear import kimi_linear_symbol
    from runners import train_lm_fit

    def shared_of(sym):
        return {k[1] for k in _read_by_several(
            (last, reads, writes) for _f, last, reads, writes
            in _mirror_stages(sym._topo(), list(sym._entries)))}
    sym = build_symbol(cfg)
    stages = _mirror_stages(sym._topo(), list(sym._entries))
    nodes = sym._topo()
    names = [nodes[first].attrs["mirror_stage"] for first, *_ in stages]
    assert names == ["p%d_l%d" % (t, l) for t in range(4) for l in (3, 4)]
    assert shared_of(sym) == set(ref.param_shapes(cfg)) - {"embed_weight",
                                                           "head_weight"}
    for name in ("granite_4_0_h_micro_l10_s8k_bf16",
                 "trinity_mini_l5_s8k_bf16",
                 "kimi_linear_a3b_ep32_l5_s8k_bf16"):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               name + ".json")) as f:
            other = json.load(f)
        other_sym = kimi_linear_symbol(**train_lm_fit.symbol_kwargs(other)) \
            if other["runner"] == "train_lm_fit" else build_symbol(other)
        assert _mirror_stages(other_sym._topo(), list(other_sym._entries))
        assert not shared_of(other_sym), name


def test_the_program_runs_under_the_scopes_the_benchmark_reads(cfg):
    from mxnet_tpu.executor import _graph_eval_fn
    sym = build_symbol(cfg)
    shapes, _, aux_shapes = sym.infer_shape(
        data=(B, cfg["sequence_length"]),
        softmax_label=(B, cfg["sequence_length"]))
    args = {n: jnp.zeros(s) for n, s in zip(sym.list_arguments(), shapes)}
    aux = {n: jnp.zeros(s) for n, s in zip(sym.list_auxiliary_states(),
                                           aux_shapes)}
    eval_fn = _graph_eval_fn(sym)

    def step(a):
        outs, vjp, _ = jax.vjp(
            lambda d: eval_fn(d, aux, jax.random.PRNGKey(0), True), a,
            has_aux=True)
        return vjp([jnp.ones(o.shape, o.dtype) for o in outs])[0]
    names = {n for _e, n in _named_eqns(jax.make_jaxpr(step)(args).jaxpr)}
    for scope in ("mx/exit", "mx/lm_head", "mx/attn/full", "mx/rope"):
        assert any(scope in n for n in names), scope
