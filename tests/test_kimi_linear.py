"""Kimi-Linear through ``Module.fit`` against the plain reference
(benchmarks/references/kimi_linear.py), at a small size on the CPU: hidden
64, 4 heads of 16, 8 experts top-2 with 2 held, the five leading layers in
the published pattern (KDA dense, KDA, KDA, MLA, KDA), 64 tokens. Losses,
the gradient of every leaf and the parameters after three fused Adam steps;
the share of the experts tied to the uncut layer; the chunked recurrence
against the token-by-token one."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.io import DataBatch, DataDesc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from references import kimi_linear as ref      # noqa: E402
from runners.train_lm_fit import symbol_kwargs  # noqa: E402

B, T = 2, 64


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "tests", "benchmarks", "configs",
                           "kimi_linear_tiny.json")) as f:
        return json.load(f)


def _symbol(cfg, **over):
    from mxnet_tpu.models.kimi_linear import kimi_linear_symbol
    return kimi_linear_symbol(**dict(symbol_kwargs(cfg), **over))


def _weights(cfg, seed=5):
    """Matrices five times the stated initial scale, so that attention,
    gates and routing all move the loss at this size."""
    return {k: (v * 5 if k.endswith("_weight") else v)
            for k, v in ref.init_params(cfg, seed).items()}


def _tokens(cfg, seed=0, t=T):
    ids = np.random.RandomState(seed).randint(0, cfg["vocab_size"],
                                              (B, t + 1))
    return ids[:, :-1].astype("f4"), ids[:, 1:].astype("f4")


def _abstract(sym, **inputs):
    """(arguments, auxiliary states) of ``sym`` as float32 shapes."""
    shapes, _, aux_shapes = sym.infer_shape(**inputs)
    return ({n: jax.ShapeDtypeStruct(s, jnp.float32)
             for n, s in zip(sym.list_arguments(), shapes)},
            {n: jax.ShapeDtypeStruct(s, jnp.float32)
             for n, s in zip(sym.list_auxiliary_states(), aux_shapes)})


def _gradients(sym):
    """``(arguments, auxiliary states) -> every argument's gradient`` of
    the training graph, differentiated as the fused step does it. A new
    function a call: nothing traced before is reused."""
    from mxnet_tpu.executor import _graph_eval_fn
    eval_fn = _graph_eval_fn(sym)

    def grads(a, x):
        outs, vjp, _ = jax.vjp(
            lambda d: eval_fn(d, x, jax.random.PRNGKey(0), True), a,
            has_aux=True)
        return vjp([jnp.ones(o.shape, o.dtype) for o in outs])[0]
    return grads


class _Repeat:
    """``steps`` times the same batch, one epoch."""

    def __init__(self, batch, steps):
        self.provide_data = [DataDesc("data", batch.data[0].shape)]
        self.provide_label = [DataDesc("softmax_label", batch.label[0].shape)]
        self.batch_size = batch.data[0].shape[0]
        self._batch, self._steps, self._i = batch, steps, 0

    def __iter__(self):
        return self

    def reset(self):
        self._i = 0

    def __next__(self):
        if self._i >= self._steps:
            raise StopIteration
        self._i += 1
        return self._batch


def _fit(cfg, sym, w0, data, label, steps, each_step=None):
    mod = mx.mod.Module(sym, context=mx.cpu())
    batch = DataBatch(data=[mx.nd.array(data)], label=[mx.nd.array(label)])
    opt = dict(cfg["optimizer"])
    name = opt.pop("name")
    mod.fit(_Repeat(batch, steps), num_epoch=1, eval_metric=None,
            kvstore="tpu_sync", optimizer=name, optimizer_params=opt,
            arg_params={k: mx.nd.array(np.asarray(v)) for k, v in w0.items()},
            aux_params={}, allow_missing=True,
            batch_end_callback=(lambda _p: each_step(mod))
            if each_step else None)
    return mod


def _ref_steps(cfg, w0, data, label, steps):
    step = jax.jit(lambda p, m, v, t: ref.train_step(
        cfg, p, m, v, t, jnp.asarray(data), jnp.asarray(label)))
    p = w0
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    out = []
    for t in range(1, steps + 1):
        rows, _choices, p, m, v = step(p, m, v, t)
        out.append((np.asarray(rows), p, m))
    return out


@pytest.fixture(scope="module")
def fitted(cfg):
    """One ``fit`` of three steps, watched: every step's losses, Adam's
    first moment after each, the compilations each step caused; and the
    reference's three steps from the same weights and tokens."""
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

    mod = _fit(cfg, _symbol(cfg), w0, data, label, 3, each_step)
    seen.update(mod=mod, w0=w0, ref=_ref_steps(cfg, w0, data, label, 3),
                held=telemetry.gauge("moe/assignments_held").value(),
                largest=telemetry.gauge("moe/max_expert_tokens").value(),
                visited=telemetry.gauge("moe/rows_visited").value())
    return seen


def test_fit_follows_the_reference_losses_and_three_adam_steps(cfg, fitted):
    mod, w0 = fitted["mod"], fitted["w0"]
    assert mod._fused is not None, "the fused step did not engage"
    for got, (want, _p, _m) in zip(fitted["losses"], fitted["ref"]):
        assert got.shape == (B, T)                 # every token's loss
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    args, aux = mod.get_params()
    assert set(args) == set(w0)
    # every leaf's change over three Adam steps. Adam divides a gradient
    # by its own size, so where a component is round-off its step is a coin
    # toss of size lr: the leaf is held as a whole, not element by element
    for k in sorted(w0):
        moved = np.asarray(fitted["ref"][-1][1][k]) - np.asarray(w0[k])
        got = args[k].asnumpy() - np.asarray(w0[k])
        assert np.linalg.norm(got - moved) \
            <= 0.02 * np.linalg.norm(moved) + 1e-12, k
    # the counters: three steps, the held experts' assignments a step
    for name, v in aux.items():
        steps_seen, held, largest, visited = v.asnumpy()
        assert steps_seen == 3 and 0 < largest <= held <= visited, name


def test_every_leafs_gradient_is_the_references(cfg, fitted):
    """Adam's first moment after one step is (1 - beta1) g: the gradient
    as the optimizer got it, for every leaf (through the rematerialised
    stages, which the symbol asks for)."""
    m_ref = fitted["ref"][0][2]
    b1 = cfg["optimizer"]["beta1"]
    scale = max(float(jnp.max(jnp.abs(v))) for v in m_ref.values()) / (1 - b1)
    for k in sorted(fitted["w0"]):
        got = fitted["m"][0][k] / (1 - b1)
        want = np.asarray(m_ref[k]) / (1 - b1)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5 * scale,
                                   err_msg=k)
    # the selection bias only selects: no gradient reaches it
    assert all(float(jnp.max(jnp.abs(m_ref[k]))) == 0
               for k in fitted["w0"] if k.endswith("router_bias"))


def test_one_program_a_step_and_no_compile_after_the_first(fitted):
    mod = fitted["mod"]
    first, second, third = fitted["compiles"]
    assert first == second == third      # steps 2 and 3 reuse step 1's
    lowered = mod._fused.lower(mod._exec._arg_vals(), mod._exec._aux_vals(),
                               mod._fused_opt_state, donate=True)
    assert "jit_step" in lowered.as_text()
    # the scopes a device trace names the layers by reach the program
    debug = lowered.as_text(debug_info=True)
    for scope in ("mx/kda", "mx/mla", "mx/moe/route", "mx/moe/experts",
                  "mx/lm_head"):
        assert scope in debug, scope


def test_moe_counters_are_published_at_the_epoch_boundary(cfg, fitted):
    held, largest = fitted["held"], fitted["largest"]
    lo, hi = cfg["experts_held"]
    assert 0 < largest <= held <= B * T * cfg["num_experts_per_token"]
    assert largest >= held / (hi - lo)
    # a step's mean over the expert layers, from the state the step carries
    _args, aux = fitted["mod"].get_params()
    per_step = np.mean([v.asnumpy()[1] for v in aux.values()])
    assert held == pytest.approx(per_step)
    # any op's declared counters reach /metrics by the one generic hook
    assert fitted["mod"]._op_counters()["moe/assignments_held"][0] \
        == pytest.approx(held)


def test_a_mirror_stage_is_one_checkpoint_from_the_symbols_attribute(cfg):
    """Traced only: every block of the symbol is one ``jax.checkpoint``
    when training, none in inference."""
    from mxnet_tpu.executor import _STAGE_POLICY, _graph_eval_fn, \
        _mirror_stages

    def jaxpr(sym, training):
        args, aux = _abstract(sym, data=(B, T), softmax_label=(B, T))
        made = jax.make_jaxpr(
            lambda a, x: _graph_eval_fn(sym)(a, x, jax.random.PRNGKey(0),
                                             training))(args, aux)
        # the graph's own stages: the top level's (the ops keep theirs)
        return [e.params["policy"] for e in made.jaxpr.eqns
                if e.primitive.name == "remat2"]
    sym = _symbol(cfg)
    # a stage keeps what an op marked under the one name, and no more
    assert jaxpr(sym, True) == [_STAGE_POLICY] * len(cfg["layers"])
    assert jaxpr(sym, False) == []
    stages = _mirror_stages(sym._topo(), list(sym._entries))
    assert len(stages) == len(cfg["layers"])
    # a stage takes the residual stream and its own weights, and hands on
    # the residual stream alone
    for _first, _last, reads, writes in stages:
        assert len(writes) == 1
        assert sum(1 for r in reads if r[0] == "val") == 1


def test_float32_token_ids_survive_bfloat16_compute():
    """Ids fed the MXNet way, as float32, and above 256: the fused step's
    data cast leaves alone an input that an op reads as an index (bfloat16
    would round 1001 to 1000 and 513 to 512: other rows)."""
    data = mx.sym.Variable("data")
    emb = mx.sym.Embedding(data=data, input_dim=1024, output_dim=8,
                           name="embed")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        emb, num_hidden=4, name="fc"), name="softmax")
    ids = np.array([1001, 513, 999, 258], "f4")
    label = np.array([0, 1, 2, 3], "f4")
    table = np.random.RandomState(0).randn(1024, 8).astype("f4")
    mod = mx.mod.Module(net, context=mx.cpu())
    batch = DataBatch(data=[mx.nd.array(ids)], label=[mx.nd.array(label)])
    mod.fit(_Repeat(batch, 1), num_epoch=1, eval_metric=None,
            kvstore="tpu_sync", optimizer="sgd",
            optimizer_params={"learning_rate": 1.0, "multi_precision": True},
            arg_params={"embed_weight": mx.nd.array(table)},
            allow_missing=True, initializer=mx.init.Uniform(0.1))
    assert mod._fused._compute_dtype == jnp.bfloat16
    assert "data" not in mod._fused._data_names
    moved = np.abs(mod.get_params()[0]["embed_weight"].asnumpy()
                   - table).sum(1) > 0
    assert sorted(np.nonzero(moved)[0]) == [258, 513, 999, 1001]


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(cfg):
    """Each eighth of the experts in turn as ``experts_held``, the shared
    expert once: the sum is what the reference gives for the whole layer."""
    from mxnet_tpu.parallel.moe import expert_layer, swiglu
    d = ref.dims(dict(cfg, experts_held=[0, cfg["num_experts_published"]]))
    hid, inter, n = d["hidden"], d["moe_inter"], d["router"]
    rng = np.random.RandomState(3)
    p = {"moe_router_weight": rng.randn(n, hid).astype("f4"),
         "moe_router_bias": np.zeros(n, "f4"),
         "moe_gate_weight": rng.randn(n, inter, hid).astype("f4") * .2,
         "moe_up_weight": rng.randn(n, inter, hid).astype("f4") * .2,
         "moe_down_weight": rng.randn(n, hid, inter).astype("f4") * .2,
         "shared_gate_weight": rng.randn(inter, hid).astype("f4") * .2,
         "shared_up_weight": rng.randn(inter, hid).astype("f4") * .2,
         "shared_down_weight": rng.randn(hid, inter).astype("f4") * .2}
    p = {k: jnp.asarray(v) for k, v in p.items()}
    x = jnp.asarray(rng.randn(96, hid).astype("f4"))
    whole, _ = ref.moe_layer(d, p, x, lambda a: a)
    total = swiglu(x, p["shared_gate_weight"], p["shared_up_weight"],
                   p["shared_down_weight"])
    seen = 0
    for lo in range(n):                      # every eighth: one expert
        part, counts = expert_layer(
            x, p["moe_router_weight"], p["moe_router_bias"],
            p["moe_gate_weight"][lo:lo + 1], p["moe_up_weight"][lo:lo + 1],
            p["moe_down_weight"][lo:lo + 1], experts_held=(lo, lo + 1),
            top_k=d["top_k"], scale=d["scale"])
        total = total + part
        seen += int(counts.sum())
    assert seen == x.shape[0] * d["top_k"]   # no token dropped anywhere
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)


# ---- what a stage keeps: the cores run forward once a step, not twice
LONG_T, SMALL_CHUNK = 512, 16      # two groups of 16 chunks a KDA layer


def _traced_gradient(sym, t=LONG_T):
    """The training program of ``sym`` with its backward pass, traced
    only."""
    return jax.make_jaxpr(_gradients(sym))(
        *_abstract(sym, data=(B, t), softmax_label=(B, t))).jaxpr


def _named_eqns(jaxpr, outer=""):
    """Every equation, those of inner jaxprs too, with the whole stack of
    names it was traced under (an inner jaxpr's equations carry only their
    own part of it)."""
    for e in jaxpr.eqns:
        name = "/".join(x for x in (outer, str(e.source_info.name_stack))
                        if x)
        yield e, name
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _named_eqns(sub, name)


def _every_eqn(jaxpr):
    return (e for e, _ in _named_eqns(jaxpr))


@pytest.fixture(scope="module")
def traced_step(cfg):
    return list(_every_eqn(_traced_gradient(
        _symbol(cfg, kda_chunk=SMALL_CHUNK))))


@pytest.mark.parametrize("core", ["kda", "attention"])
def test_a_stage_runs_its_core_forward_once_for_the_step(cfg, traced_step,
                                                         core):
    """Traced only. A stage keeps the attention kernel's output, and the
    KDA core's output and group states: its backward pass recomputes the
    projections around them and runs neither core again. Per KDA layer the
    scan over a group's 16 chunks runs forward twice (the step's forward,
    the group's recompute inside the core's own backward) and backward
    once; the attention kernel once per attention layer. Before the marks
    the counts were three and two."""
    kda_layers = [l for l in cfg["layers"]
                  if l in cfg["linear_attn_config"]["kda_layers"]]
    if core == "kda":
        chunks = 16                # of SMALL_CHUNK tokens: no other scan's
        scans = [e.params["reverse"] for e in traced_step
                 if e.primitive.name == "scan"
                 and e.params["length"] == chunks]
        assert scans.count(False) == 2 * len(kda_layers)
        assert scans.count(True) == len(kda_layers)
        groups = [e.params["reverse"] for e in traced_step
                  if e.primitive.name == "scan"
                  and e.params["length"] == LONG_T // (SMALL_CHUNK * chunks)]
        assert groups.count(False) == groups.count(True) == len(kda_layers)
    else:       # by name: the KDA core has kernels of its own at 128 lanes
        kernels = [e.params["name"] or "" for e in traced_step
                   if e.primitive.name == "pallas_call"]
        attention = [k for k in kernels if not k.startswith("kda_intra")]
        assert len(attention) == len(cfg["layers"]) - len(kda_layers) == 1


def test_the_stage_gauges_read_what_the_marked_shapes_say(cfg):
    """``stage/kept_values`` and ``stage/kept_mb`` are set when a training
    program is traced: the tiny symbol's nine values by their shapes, the
    same on a second trace, 0 for a graph without stages."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import resnet_symbol
    values, mb = (telemetry.gauge("stage/kept_values"),
                  telemetry.gauge("stage/kept_mb"))
    lin = cfg["linear_attn_config"]
    kda_layers = [l for l in cfg["layers"] if l in lin["kda_layers"]]
    h, d = lin["num_heads"], lin["head_dim"]
    n_groups = LONG_T // (SMALL_CHUNK * 16)
    per_kda = 4 * (B * LONG_T * h * d + n_groups * B * h * d * d)
    attn = 4 * B * cfg["num_attention_heads"] * LONG_T * cfg["v_head_dim"]
    for _ in range(2):                     # set, not added
        _traced_gradient(_symbol(cfg, kda_chunk=SMALL_CHUNK))
        assert values.value() == 2 * len(kda_layers) + 1 == 9
        assert mb.value() == pytest.approx(
            (len(kda_layers) * per_kda + attn) / 1e6, rel=1e-9)
    net = resnet_symbol(num_classes=10, num_layers=18, image_shape="3,32,32")
    jax.make_jaxpr(_gradients(net))(
        *_abstract(net, data=(2, 3, 32, 32), softmax_label=(2,)))
    assert values.value() == 0 and mb.value() == 0


def test_kept_and_recomputed_are_the_same_numbers(cfg, monkeypatch):
    """Two stages (a KDA layer, an attention layer, each with its expert
    layer): every gradient with the marked values kept is bitwise the
    gradient with each stage under a plain ``jax.checkpoint``, which
    recomputes them. Compiled without XLA's fusion pass: fused, the CPU
    compiler rounds the recomputed copy of the KDA core's forward as its
    neighbours there suggest, and the two differ in the last digit (up to
    2e-6 of a leaf's largest element; the attention layer's not at all)."""
    from mxnet_tpu import executor
    sym = _symbol(cfg, layers=(3, 4))
    data, label = _tokens(cfg)
    shapes, aux_shapes = _abstract(sym, data=(B, T), softmax_label=(B, T))
    rng = np.random.RandomState(11)
    args = {n: jnp.asarray(rng.randn(*s.shape).astype("f4") * 0.1)
            for n, s in shapes.items()}
    args.update(data=jnp.asarray(data), softmax_label=jnp.asarray(label))
    aux = {n: jnp.zeros(s.shape, jnp.float32) for n, s in aux_shapes.items()}

    def grads():
        return jax.jit(_gradients(sym)).lower(args, aux).compile(
            compiler_options={"xla_disable_hlo_passes": "fusion"})(args, aux)
    kept = grads()
    monkeypatch.setattr(executor, "_STAGE_POLICY", None)
    plain = grads()
    moved = 0
    for k in sorted(kept):
        assert np.array_equal(np.asarray(kept[k]), np.asarray(plain[k])), k
        moved += bool(np.any(np.asarray(kept[k])))
    assert moved >= len(kept) - 4       # ids, labels, the selection biases


def _kda_inputs(t, b=2, h=3, dk=16, dv=8):
    """q, k, v, g, beta and a cotangent, under gates from weak to strong."""
    rng = np.random.RandomState(t)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = jnp.asarray(unit(rng.randn(b, t, h, dk)).astype("f4") * dk ** -.5)
    k = jnp.asarray(unit(rng.randn(b, t, h, dk)).astype("f4"))
    v = jnp.asarray(rng.randn(b, t, h, dv).astype("f4"))
    # per-token log decay from -0.001 to -6 (alpha down to 0.0025): a
    # chunk's total passes -200, what exp() of the naive form cannot hold
    g = jnp.asarray(-np.exp(rng.uniform(np.log(1e-3), np.log(6.0),
                                        (b, t, h, dk))).astype("f4"))
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (b, t, h)).astype("f4"))
    cot = jnp.asarray(rng.randn(b, t, h, dv).astype("f4"))
    return (q, k, v, g, beta), cot


def _assert_gradients(names, got, want):
    for name, a, w in zip(names, got, want):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=1e-3,
                                   atol=2e-5 * scale, err_msg=name)


@pytest.mark.parametrize("t", [40, 150])
def test_chunked_kda_is_the_token_by_token_recurrence(t):
    """Chunk 64 and sequences that are no multiple of it, float32, forward
    and the gradient of every input, under gates from weak to strong."""
    from mxnet_tpu.ops.lm_ops import kda_chunked
    xs, cot = _kda_inputs(t)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * cot)
    with jax.default_matmul_precision("highest"):
        want = ref.kda_recurrence(*xs)
        got = kda_chunked(xs, chunk=64, group=2)
        g_want = jax.grad(loss(ref.kda_recurrence), range(5))(*xs)
        g_got = jax.grad(loss(lambda *a: kda_chunked(a, chunk=64, group=2)),
                         range(5))(*xs)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    _assert_gradients("qkvgb", g_got, g_want)


def test_chunked_kdas_own_backward_carries_the_state_through_the_groups():
    """``kda_chunked`` has a backward pass of its own. Three groups, the
    last one short (300 tokens in groups of 128), the gate made inside
    ``pre`` from a rate that is no slice of the sequence: the gradient of
    every input and of the rate against ``jax.grad`` of the token-by-token
    recurrence; the output in the dtype asked for."""
    from mxnet_tpu.ops.lm_ops import kda_chunked
    t = 300
    xs, cot = _kda_inputs(t)
    rate = jnp.asarray(np.linspace(0.5, 1.5, 3).astype("f4"))[:, None]

    def pre(rate, q, k, v, f, beta):
        return q, k, v, rate * f, beta

    def chunked(rate, *a):
        return kda_chunked(a, pre, (rate,), chunk=64, group=2)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * cot)
    with jax.default_matmul_precision("highest"):
        g_want = jax.grad(loss(lambda rate, *a: ref.kda_recurrence(
            *pre(rate, *a))), range(6))(rate, *xs)
        g_got = jax.grad(loss(chunked), range(6))(rate, *xs)
        half = kda_chunked(xs, chunk=64, group=2, dtype=jnp.bfloat16)
    _assert_gradients(["rate"] + list("qkvgb"), g_got, g_want)
    assert float(jnp.max(jnp.abs(g_want[0]))) > 0
    assert half.dtype == jnp.bfloat16 and half.shape == cot.shape


# ---- the work inside chunks as two Pallas kernels (ops/pallas_kda.py)
def _plain_path(monkeypatch):
    """No tile is the kernels': every core takes the plain-JAX path."""
    from mxnet_tpu.ops import pallas_kda
    monkeypatch.setattr(pallas_kda, "eligible", lambda *a: False)
    monkeypatch.setattr(pallas_kda, "scan_eligible", lambda *a: False)


def _close(names, got, want, rtol):
    """Float32 round-off of sums of thousands of terms: ``rtol`` of an
    element, or of the array's largest where the element is small."""
    for name, a, w in zip(names, got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), rtol=rtol,
            atol=rtol * float(jnp.max(jnp.abs(w))), err_msg=name)


@pytest.mark.parametrize("t,b,h", [(64, 2, 3), (200, 1, 2), (200, 1, 8)],
                         ids=["T64-one-head-a-tile", "T200-two-heads-a-tile",
                              "T200-eight-heads-a-program"])
def test_kda_kernels_are_the_plain_path_and_the_recurrence(t, b, h,
                                                           monkeypatch):
    """At 128-wide heads, chunk 64, sub-blocks of 16 the work inside
    chunks runs in the two kernels (interpreted here): forward and the
    gradients of q, k, v, g, beta and of a rate in ``consts`` against the
    plain path to float32 round-off, and against the token-by-token
    recurrence at the tolerances the plain path is held to. 200 tokens are
    no multiple of the chunk; 3 heads go one a tile, 2 and 8 two a tile."""
    from mxnet_tpu.ops.lm_ops import kda_chunked
    xs, cot = _kda_inputs(t, b=b, h=h, dk=128, dv=128)
    rate = jnp.asarray(np.linspace(0.5, 1.5, h).astype("f4"))[:, None]

    def pre(rate, q, k, v, f, beta):
        return q, k, v, rate * f, beta

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * cot)

    def chunked(rate, *a):
        return kda_chunked(a, pre, (rate,), chunk=64, group=2)

    def both():
        return chunked(rate, *xs), jax.grad(loss(chunked), range(6))(rate, *xs)
    names = ["rate"] + list("qkvgb")
    with jax.default_matmul_precision("highest"):
        got, g_got = both()
        want = ref.kda_recurrence(*pre(rate, *xs))
        g_want = jax.grad(loss(lambda rate, *a: ref.kda_recurrence(
            *pre(rate, *a))), range(6))(rate, *xs)
        _plain_path(monkeypatch)
        plain, g_plain = both()
    assert np.isfinite(np.asarray(got)).all()
    _close(["o"], [got], [plain], 1e-5)
    _close(names, g_got, g_plain, 1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    _assert_gradients(names, g_got, g_want)


def _scan_inputs(n, b, h, c, dk, dv):
    """A state, the six values a group's scan consumes and the cotangents
    of its two results: none of them zero, decays from weak to strong."""
    rng = np.random.RandomState(n * h)

    def normal(*shape, scale=1.0):
        return jnp.asarray((rng.randn(*shape) * scale).astype("f4"))
    xs = (normal(n, b, h, c, dv), normal(n, b, h, c, dk, scale=dk ** -.5),
          normal(n, b, h, c, c, scale=c ** -.5),
          normal(n, b, h, c, dk, scale=dk ** -.5),
          normal(n, b, h, c, dk, scale=dk ** -.5),
          jnp.asarray(-np.exp(rng.uniform(np.log(1e-3), np.log(20.0),
                                          (n, b, h, 1, dk))).astype("f4")))
    return (normal(b, h, dk, dv),) + xs, (normal(b, h, dk, dv),
                                          normal(n, b, h, c, dv))


@pytest.mark.parametrize("n,b,h,hb,c,dk,dv", [
    (1, 2, 3, 1, 64, 128, 128), (5, 1, 8, 8, 64, 128, 128),
    (3, 2, 2, 2, 16, 128, 256), (16, 1, 4, 4, 8, 128, 128)],
    ids=["one-chunk-one-head-a-program", "five-chunks-eight-heads-a-program",
         "three-chunks-d_v-256-two-heads", "sixteen-chunks-four-heads"])
def test_kda_scan_kernels_are_the_plain_scan(n, b, h, hb, c, dk, dv):
    """The scan from chunk to chunk as two kernels (interpreted here)
    against ``lax.scan`` over the same step (``lm_ops._scan_plain``): the
    last state and o to 1e-5, the gradients of the state on entry and of
    all six values to 1e-4, from a state and cotangents (of the last state
    too) that are not zero; a group of one chunk and of many, one head a
    program (``hb``) and several."""
    from mxnet_tpu.ops import lm_ops, pallas_kda
    assert pallas_kda.scan_eligible(dk, dv, c)
    assert pallas_kda._scan_heads(h, c, dk, dv, True) == hb
    args, cts = _scan_inputs(n, b, h, c, dk, dv)

    def pulled(f):
        out, pull = jax.vjp(f, *args)
        return out, pull(cts)
    (got, g_got), (want, g_want) = (
        pulled(lambda *a: pallas_kda.kda_scan(*a, True)),
        pulled(lm_ops._scan_plain))
    for x in g_want:
        assert float(jnp.max(jnp.abs(x))) > 0
    _close(["s_end", "o"], got, want, 1e-5)
    _close(["s0", "u0", "w", "m", "q_in", "k_out", "g_end"], g_got, g_want,
           1e-4)


def _kda_symbol(h, chunk=64):
    """One ``_contrib_KDA`` op as a loss: the smallest training graph
    with a KDA core."""
    v = [mx.sym.Variable(n) for n in ("q", "k", "v", "f", "b", "a_log",
                                      "dt_bias")]
    return mx.sym.MakeLoss(mx.sym.contrib.KDA(*v, num_heads=h, chunk=chunk))


def _kda_shapes(h, d, t=64):
    wide = (1, t, h * d)
    return dict(q=wide, k=wide, v=wide, f=wide, b=(1, t, h), a_log=(h,),
                dt_bias=(h * d,))


@pytest.mark.parametrize("d,kernel", [(16, False), (128, True),
                                      (512, False)],
                         ids=["d16-plain", "d128-kernels", "d512-plain"])
def test_the_tile_selects_kernels_or_the_plain_path(d, kernel):
    """No flag: a 128-wide head's core goes to the kernels, a 16-wide one
    and one wider than the kernels' tiles through plain JAX. Read where a
    user would, from the gauges ``kda/intra_kernel``, ``kda/intra_plain``,
    ``kda/scan_kernel`` and ``kda/scan_plain`` that a traced training
    program sets (0 for a graph with no KDA core), and from the program
    itself: the step's forward, the group recomputed inside the core's
    backward, the backward, a kernel each for the work inside chunks and
    for the scan over them, every one under ``mx/kda/intra`` or
    ``mx/kda/scan`` for the device trace to charge to ``mx/kda``."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import resnet_symbol
    gauges = [telemetry.gauge("kda/%s_%s" % (half, path))
              for half in ("intra", "scan") for path in ("kernel", "plain")]
    sym = _kda_symbol(2)
    jaxpr = jax.make_jaxpr(_gradients(sym))(
        *_abstract(sym, **_kda_shapes(2, d))).jaxpr
    assert [g.value() for g in gauges] == [int(kernel), int(not kernel)] * 2
    kernels = [(e.params["name"], name) for e, name in _named_eqns(jaxpr)
               if e.primitive.name == "pallas_call"]
    assert sorted(k for k, _ in kernels) == (
        ["kda_intra_bwd", "kda_intra_fwd", "kda_intra_fwd", "kda_scan_bwd",
         "kda_scan_fwd", "kda_scan_fwd"] if kernel else [])
    for k, name in kernels:
        scope = "mx/kda/" + k.split("_")[1]
        assert scope in name, (k, name)
        assert ("transpose(jvp(%s))" % scope in name) \
            == k.endswith("_bwd"), (k, name)
    net = resnet_symbol(num_classes=10, num_layers=18, image_shape="3,32,32")
    jax.make_jaxpr(_gradients(net))(
        *_abstract(net, data=(2, 3, 32, 32), softmax_label=(2,)))
    assert [g.value() for g in gauges] == [0, 0, 0, 0]


def test_the_tiny_models_cores_are_counted_as_plain(cfg):
    """The five-layer tiny symbol has four KDA layers of 16-wide heads:
    four plain cores, no kernel, however often the program is traced."""
    from mxnet_tpu import telemetry
    for _ in range(2):                     # set, not added
        _traced_gradient(_symbol(cfg, kda_chunk=SMALL_CHUNK), t=64)
        assert telemetry.gauge("kda/intra_kernel").value() == 0
        assert telemetry.gauge("kda/intra_plain").value() == 4
        assert telemetry.gauge("kda/scan_kernel").value() == 0
        assert telemetry.gauge("kda/scan_plain").value() == 4


@pytest.mark.parametrize("t,tile,gauges", [
    (64, (64, 64), (0, 1, 1, 1, 1)),            # under one tile: one step
    (2048, (1024, 1024), (0, 1, 3, 3, 4)),      # 1 + 2 key blocks, 2 x 2
])
def test_the_attention_gauges_read_the_tile_the_shapes_give(cfg, t, tile,
                                                            gauges):
    """The tiny symbol's one MLA core (no window, every head its own keys)
    at the tile ``_contrib_FlashAttention`` takes from its shapes: the five
    ``attn/*`` gauges, set when the training program is traced."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.pallas_flash import tile_for
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    assert tile_for(t, t, d, cfg["v_head_dim"], 4) == tile
    for _ in range(2):                     # set, not added
        _traced_gradient(_symbol(cfg, kda_chunk=SMALL_CHUNK), t=t)
        assert tuple(telemetry.gauge("attn/" + g).value() for g in (
            "window_layers", "full_layers", "kv_blocks_visited",
            "kv_blocks_causal", "grid_steps")) == gauges


@pytest.mark.parametrize("tq,tk,causal", [(96, 96, True), (70, 70, True),
                                          (40, 104, True), (64, 64, False)])
def test_flash_attention_with_a_value_width_of_its_own(tq, tk, causal):
    """192-wide q.k beside 128-wide v in the model; here 24 and 16.
    Forward (the kernel, interpreted) and backward (blockwise) against the
    dense softmax."""
    from mxnet_tpu.ops.pallas_flash import flash_attention
    rng = np.random.RandomState(tq + tk)
    b, h, d, dv = 2, 2, 24, 16
    q = jnp.asarray(rng.randn(b, h, tq, d).astype("f4"))
    k = jnp.asarray(rng.randn(b, h, tk, d).astype("f4"))
    v = jnp.asarray(rng.randn(b, h, tk, dv).astype("f4"))
    cot = jnp.asarray(rng.randn(b, h, tq, dv).astype("f4"))

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        if causal:
            mask = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None] \
                + (tk - tq)
            s = jnp.where(mask, s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    got = flash_attention(q, k, v, 32, 32, causal)
    assert got.shape == (b, h, tq, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense(q, k, v)),
                               rtol=2e-4, atol=2e-5)
    g_got = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, 32, 32, causal) * cot), (0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: jnp.sum(dense(*a) * cot), (0, 1, 2))(q, k, v)
    for name, a, w in zip("qkv", g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-3,
                                   atol=2e-4, err_msg=name)


def test_routing_however_skewed_is_one_exact_grouped_product():
    """A router that sends every token to one held expert and none to the
    other, then an even one: ragged groups from empty to every row, no
    capacity to pass, the result the masked sum over the held experts."""
    from mxnet_tpu.parallel import moe
    rng = np.random.RandomState(0)
    n, hid, inter, e = 256, 16, 8, 16
    x = jnp.asarray(np.abs(rng.randn(n, hid)).astype("f4"))
    w_r = np.zeros((e, hid), "f4")
    w_r[3] = 1.0                              # every token's first choice
    w_r[5] = 0.5
    wg, wu = (jnp.asarray(rng.randn(2, inter, hid).astype("f4"))
              for _ in range(2))
    wd = jnp.asarray(rng.randn(2, hid, inter).astype("f4"))
    kw = dict(experts_held=(3, 5), top_k=2, scale=1.5)

    def masked(x, router):
        ch, wt = moe.route(x, router, jnp.zeros(e), 2, 1.5)
        return sum(moe.swiglu(x, wg[i], wu[i], wd[i]) * jnp.sum(
            jnp.where(ch == 3 + i, wt, 0.0), -1, keepdims=True)
            for i in (0, 1))
    layer = jax.jit(lambda *a: moe.expert_layer(*a, **kw))
    y, counts = layer(x, jnp.asarray(w_r), jnp.zeros(e), wg, wu, wd)
    assert counts.tolist() == [n, 0]
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(masked(x, jnp.asarray(w_r))),
                               rtol=2e-4, atol=2e-5)
    x2 = jnp.asarray(rng.randn(n, hid).astype("f4"))
    even = jnp.asarray(rng.randn(e, hid).astype("f4"))
    y2, c2 = layer(x2, even, jnp.zeros(e), wg, wu, wd)
    assert 0 < int(c2.min()) and int(c2.sum()) < n
    np.testing.assert_allclose(np.asarray(y2), np.asarray(masked(x2, even)),
                               rtol=2e-4, atol=2e-5)
    # the layer is one program for any routing: no branch on the counts,
    # one loop over the blocks that hold a row, three products a block
    eqns = list(_every_eqn(jax.make_jaxpr(
        lambda *a: moe.expert_layer(*a, **kw))(
            x, jnp.asarray(w_r), jnp.zeros(e), wg, wu, wd).jaxpr))
    names = [q.primitive.name for q in eqns]
    assert "cond" not in names and names.count("while") == 1
    loop, = (q for q in eqns if q.primitive.name == "while")
    inside = [q.primitive.name
              for q in _every_eqn(loop.params["body_jaxpr"].jaxpr)]
    assert inside.count("ragged_dot_general") == 3 \
        and names.count("ragged_dot_general") == 3


def test_expert_layer_gradients_reach_router_and_experts():
    from mxnet_tpu.parallel import moe
    rng = np.random.RandomState(1)
    n, hid, inter, e = 64, 8, 4, 8
    x = jnp.asarray(rng.randn(n, hid).astype("f4"))
    w_r = jnp.asarray(rng.randn(e, hid).astype("f4"))
    wg, wu = (jnp.asarray(rng.randn(2, inter, hid).astype("f4"))
              for _ in range(2))
    wd = jnp.asarray(rng.randn(2, hid, inter).astype("f4"))
    kw = dict(experts_held=(2, 4), top_k=2, scale=2.0)

    def plain(x, w_r, wg, wu, wd):
        ch, wt = moe.route(x, w_r, jnp.zeros(e), 2, 2.0)
        return sum(moe.swiglu(x, wg[i], wu[i], wd[i]) * jnp.sum(
            jnp.where(ch == 2 + i, wt, 0.0), -1, keepdims=True)
            for i in (0, 1))
    got = jax.grad(lambda *a: jnp.sum(moe.expert_layer(
        a[0], a[1], jnp.zeros(e), *a[2:], **kw)[0] ** 2), range(5))(
            x, w_r, wg, wu, wd)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), range(5))(
        x, w_r, wg, wu, wd)
    for a, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-3,
                                   atol=2e-4)
    assert float(jnp.max(jnp.abs(got[1]))) > 0      # the router learns


def _hand_routed(monkeypatch, total, n, e=8, top_k=2, lo=2):
    """``moe.route`` replaced by a routing made by hand: the first
    ``total`` assignments (token by token, slot by slot) fall to the held
    experts ``lo + slot``, every other one to an absent expert; the
    weights are the router's own, so its gradient flows."""
    from mxnet_tpu.parallel import moe
    slot = np.arange(n * top_k) % top_k
    chosen = np.where(np.arange(n * top_k) < total, lo + slot,
                      np.where(slot == 0, 0, e - 1)).reshape(n, top_k)
    chosen = jnp.asarray(chosen, jnp.int32)

    def route(x, router_weight, router_bias, top_k, scale):
        s = jax.nn.sigmoid(x @ router_weight.T)
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        return chosen, scale * picked / jnp.sum(picked, -1, keepdims=True)
    monkeypatch.setattr(moe, "route", route)
    return route


WALK_BLOCK = 16


@pytest.mark.parametrize("n,total", [
    (24, 0), (24, 1), (24, WALK_BLOCK), (24, WALK_BLOCK + 1), (24, 48),
    (25, 50)], ids=["none", "one", "a_block", "a_block_and_one",
                    "every_assignment", "blocks_do_not_divide"])
def test_the_block_walk_is_the_masked_sum_forward_and_backward(
        n, total, monkeypatch):
    """The loop over blocks of 16 sorted assignments against the masked
    sum over the held experts, result and all five gradients, from no
    held assignment to every one (the most trips) and a last block that
    the assignments do not fill; one block of all ``n * k`` rows gives
    the same to rounding."""
    from mxnet_tpu.parallel import moe
    rng = np.random.RandomState(total)
    hid, inter, e, top_k = 8, 4, 8, 2
    route = _hand_routed(monkeypatch, total, n, e, top_k)
    x = jnp.asarray(rng.randn(n, hid).astype("f4"))
    w_r = jnp.asarray(rng.randn(e, hid).astype("f4"))
    wg, wu = (jnp.asarray(rng.randn(2, inter, hid).astype("f4"))
              for _ in range(2))
    wd = jnp.asarray(rng.randn(2, hid, inter).astype("f4"))
    kw = dict(experts_held=(2, 4), top_k=top_k, scale=2.0)

    def plain(x, w_r, wg, wu, wd):
        ch, wt = route(x, w_r, None, top_k, 2.0)
        return sum(moe.swiglu(x, wg[i], wu[i], wd[i]) * jnp.sum(
            jnp.where(ch == 2 + i, wt, 0.0), -1, keepdims=True)
            for i in (0, 1))

    def walked(block):
        monkeypatch.setattr(moe, "block_rows", lambda *_a: block)

        def layer(x, w_r, wg, wu, wd):
            return moe.expert_layer(x, w_r, jnp.zeros(e), wg, wu, wd, **kw)
        y, counts = layer(x, w_r, wg, wu, wd)
        return (y, counts) + jax.grad(
            lambda *a: jnp.sum(layer(*a)[0] ** 2), range(5))(
                x, w_r, wg, wu, wd)
    y, counts, *got = walked(WALK_BLOCK)
    assert int(counts.sum()) == total
    assert int(moe.trips(counts, WALK_BLOCK)) == -(-total // WALK_BLOCK)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), range(5))(
        x, w_r, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(plain(x, w_r, wg, wu, wd)),
                               rtol=2e-4, atol=2e-5)
    for name, a, w in zip(("x", "router", "gate", "up", "down"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=1e-3,
                                   atol=1e-4, err_msg=name)
    y1, _, *one = walked(n * top_k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1), rtol=1e-5,
                               atol=1e-6)
    for name, a, w in zip(("x", "router", "gate", "up", "down"), got, one):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=1e-5,
                                   atol=1e-5, err_msg=name)


def test_no_value_of_the_layer_is_every_assignment_tall():
    """Forward and backward traced at the block the layer takes from its
    own shapes: 2,048 assignments, 2 of 16 experts held, blocks of 512.
    Nothing ``n * k`` rows by the model's or the experts' width exists,
    loop bodies included; what a block makes is ``B`` rows tall."""
    from mxnet_tpu.parallel import moe
    n, hid, inter, e, top_k, held = 1024, 24, 12, 16, 2, 2
    block = moe.block_rows(n, top_k, held, e, inter)
    assert block == 512 and n * top_k == 4 * block
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, f32) for s in (
        (n, hid), (e, hid), (e,), (held, inter, hid), (held, inter, hid),
        (held, hid, inter))]
    traced = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(moe.expert_layer(
            *a, experts_held=(4, 6), top_k=top_k)[0] ** 2),
        (0, 1, 3, 4, 5)))(*shapes)
    tall = {}
    for q in _every_eqn(traced.jaxpr):
        for v in q.outvars:
            shape = getattr(v.aval, "shape", ())
            if len(shape) == 2 and shape[1] in (hid, inter):
                tall.setdefault(shape[0], set()).add(q.primitive.name)
    assert n * top_k not in tall, tall[n * top_k]
    assert "ragged_dot_general" in tall[block]
    assert max(tall) == n                  # the tokens themselves
    # a rank that holds every expert: one block of every assignment
    assert moe.block_rows(n, top_k, e, e, inter) == n * top_k
    # the cells: two even shares (Trinity's 16 of 128 experts), the held
    # experts' 8 x 1,024 hidden units (Kimi's 8 of 256)
    assert moe.block_rows(8192, 8, 16, 128, 1024) == 16384
    assert moe.block_rows(8192, 8, 8, 256, 1024) == 8192
    assert moe.block_rows(8192, 8, 8, 256, 100) == 4096
    rng = np.random.RandomState(2)
    x, w_r = (jnp.asarray(rng.randn(*s.shape).astype("f4"))
              for s in shapes[:2])
    whole = [jnp.asarray(rng.randn(e, *s.shape[1:]).astype("f4"))
             for s in shapes[3:]]
    _, counts = moe.expert_layer(x, w_r, jnp.zeros(e), *whole,
                                 experts_held=(0, e), top_k=top_k)
    assert int(counts.sum()) == n * top_k
    assert int(moe.trips(counts, n * top_k)) == 1


def test_rows_visited_is_whole_blocks_and_reaches_the_metrics(
        cfg, fitted, monkeypatch):
    """The op's state on a routing made by hand: [steps, held, busiest,
    trips x block]; then the same gauge after the fit's epoch."""
    from mxnet_tpu.ops import registry
    from mxnet_tpu.parallel import moe
    n, hid, inter, e, top_k = 24, 8, 4, 8, 2
    _hand_routed(monkeypatch, WALK_BLOCK + 1, n, e, top_k)
    monkeypatch.setattr(moe, "block_rows", lambda *_a: WALK_BLOCK)
    rng = np.random.RandomState(0)
    arrays = [jnp.asarray(rng.randn(*s).astype("f4")) for s in (
        (1, n, hid), (e, hid), (e,), (2, inter, hid), (2, inter, hid),
        (2, hid, inter))]
    _, state = registry.get("_contrib_MoE").fn(
        *arrays, jnp.zeros(4), experts_held=(2, 4), top_k=top_k)
    assert state.tolist() == [1.0, WALK_BLOCK + 1, 9.0, 2 * WALK_BLOCK]
    _, state = registry.get("_contrib_MoE").fn(
        *arrays, state, experts_held=(2, 4), top_k=top_k)
    assert state.tolist() == [2.0, WALK_BLOCK + 1, 9.0, 2 * WALK_BLOCK]
    monkeypatch.undo()
    # the tiny model: 256 assignments a layer, one block of them all
    every = B * T * cfg["num_experts_per_token"]
    lo, hi = cfg["experts_held"]
    assert moe.block_rows(B * T, cfg["num_experts_per_token"], hi - lo,
                          cfg["num_experts_published"],
                          cfg["moe_intermediate_size"]) == every
    assert fitted["visited"] == every
    assert fitted["mod"]._op_counters()["moe/rows_visited"][0] == every
    from mxnet_tpu.telemetry import prom
    assert "mxtpu_moe_rows_visited %d" % every in prom.exposition()
    # where the benchmark's readers find the two older gauges
    _args, aux = fitted["mod"].get_params()
    for v in aux.values():
        steps, held, busiest, visited = v.asnumpy().tolist()
        assert steps == 3 and 0 < busiest <= held <= visited == every


def test_head_loss_is_cross_entropy_and_never_the_whole_logits():
    rng = np.random.RandomState(2)
    b, t, hid, vocab = 2, 50, 8, 37
    x = jnp.asarray(rng.randn(b, t, hid).astype("f4"))
    w = jnp.asarray(rng.randn(vocab, hid).astype("f4"))
    label = jnp.asarray(rng.randint(0, vocab, (b, t)).astype("f4"))
    op = mx.ops.get("_contrib_LMHeadLoss").fn

    def plain(x, w):
        logp = jax.nn.log_softmax(x @ w.T, -1)
        return -jnp.take_along_axis(
            logp, label.astype(jnp.int32)[..., None], -1)[..., 0]
    got = op(x, w, label, block=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(x, w)),
                               rtol=1e-5, atol=1e-5)
    g_got = jax.grad(lambda *a: jnp.sum(op(*a, label, block=16,
                                           normalization="tokens")),
                     (0, 1))(x, w)
    g_want = jax.grad(lambda *a: jnp.sum(plain(*a)) / t, (0, 1))(x, w)
    for a, want in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                                   rtol=1e-4, atol=1e-6)
    # blocks of 16 tokens: no array of all the tokens by the vocabulary
    text = str(jax.make_jaxpr(lambda *a: op(*a, label, block=16))(x, w))
    assert "f32[%d,%d]" % (b * t, vocab) not in text


def test_rmsnorm_and_causal_conv_ops():
    rng = np.random.RandomState(4)
    x = rng.randn(2, 9, 6).astype("f4")
    g = rng.rand(6).astype("f4")
    got = mx.nd.RMSNorm(mx.nd.array(x), mx.nd.array(g), eps=1e-5).asnumpy()
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    w = rng.randn(6, 4).astype("f4")
    y = mx.nd.contrib.CausalConv1D(mx.nd.array(x), mx.nd.array(w),
                                   act_type="none").asnumpy()
    xp = np.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = sum(xp[:, i:i + 9] * w[:, i] for i in range(4))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    # a later token changes nothing before it
    x2 = x.copy()
    x2[:, 5:] += 1.0
    y2 = mx.nd.contrib.CausalConv1D(mx.nd.array(x2), mx.nd.array(w),
                                    act_type="none").asnumpy()
    np.testing.assert_allclose(y2[:, :5], y[:, :5], rtol=1e-6)
