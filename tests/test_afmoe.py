"""Trinity (``model_type: afmoe``) through ``Module.fit`` against the plain
reference (benchmarks/references/afmoe.py), at a small size on the CPU:
hidden 64, 4 heads of 16 over 2 KV heads, 8 experts top-2 with 2 held, the
published layers 1-5 (window + dense, window, full, window, window with
experts), 300 tokens under a window of 160: neither a multiple of the
kernel's block of 128, the sequence three blocks long. Losses, the gradient
of every leaf and the parameters after three fused Adam steps; one program
a step; the share of the experts tied to the uncut layer; the rotary op;
the gauges and scopes the program publishes."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from references import afmoe as ref              # noqa: E402
from runners.train_lm_cfg import build_symbol    # noqa: E402
from test_kimi_linear import _fit                # noqa: E402

B = 2


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "tests", "benchmarks", "configs",
                           "afmoe_tiny.json")) as f:
        return json.load(f)


def _weights(cfg, seed=5):
    """Matrices five times the stated initial scale, so that attention,
    gates and routing all move the loss at this size."""
    return {k: (v * 5 if k.endswith("_weight") else v)
            for k, v in ref.init_params(cfg, seed).items()}


def _tokens(cfg, seed=0):
    t = cfg["sequence_length"]
    ids = np.random.RandomState(seed).randint(0, cfg["vocab_size"],
                                              (B, t + 1))
    return ids[:, :-1].astype("f4"), ids[:, 1:].astype("f4")


@pytest.fixture(scope="module")
def fitted(cfg):
    """One ``fit`` of three steps, watched: every step's losses, Adam's
    first moment after each, the compilations each step caused, the gauges
    the traced program set; and the reference's three steps from the same
    weights and tokens."""
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
        rows, _choices, p, m, v = step(p, m, v, t)
        steps.append((np.asarray(rows), p, m))
    seen.update(mod=mod, w0=w0, ref=steps, gauges={
        g: telemetry.gauge(g).value()
        for g in ("attn/window_layers", "attn/full_layers",
                  "attn/kv_blocks_visited", "attn/kv_blocks_causal",
                  "attn/grid_steps", "stage/kept_values", "attn/bwd_kernel",
                  "attn/bwd_plain")})
    return seen


def test_fit_follows_the_reference_losses_and_three_adam_steps(cfg, fitted):
    mod, w0 = fitted["mod"], fitted["w0"]
    assert mod._fused is not None, "the fused step did not engage"
    for got, (want, _p, _m) in zip(fitted["losses"], fitted["ref"]):
        assert got.shape == (B, cfg["sequence_length"])
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    args, aux = mod.get_params()
    assert set(args) == set(w0)
    # every leaf's change over three Adam steps, each leaf as a whole (a
    # component at round-off steps by a coin toss of size lr)
    for k in sorted(w0):
        moved = np.asarray(fitted["ref"][-1][1][k]) - np.asarray(w0[k])
        got = args[k].asnumpy() - np.asarray(w0[k])
        assert np.linalg.norm(got - moved) \
            <= 0.02 * np.linalg.norm(moved) + 1e-12, k
    assert len(aux) == 4            # the expert layers' counters
    for name, v in aux.items():
        steps_seen, held, largest, visited = v.asnumpy()
        assert steps_seen == 3 and 0 < largest <= held <= visited, name


def test_every_leafs_gradient_is_the_references(cfg, fitted):
    """Adam's first moment after one step is (1 - beta1) g: the gradient
    as the optimizer got it, for every leaf; the keys' and values'
    matrices among them, whose gradient is summed over a group's query
    heads inside the attention's backward."""
    m_ref = fitted["ref"][0][2]
    b1 = cfg["optimizer"]["beta1"]
    scale = max(float(jnp.max(jnp.abs(v))) for v in m_ref.values()) / (1 - b1)
    for k in sorted(fitted["w0"]):
        got = fitted["m"][0][k] / (1 - b1)
        want = np.asarray(m_ref[k]) / (1 - b1)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5 * scale,
                                   err_msg=k)
    assert float(jnp.max(jnp.abs(m_ref["l3_attn_k_weight"]))) > 0
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
    for scope in ("mx/attn/window", "mx/attn/full", "mx/rope",
                  "mx/moe/route", "mx/moe/experts", "mx/lm_head"):
        assert scope in debug, scope


def test_the_gauges_count_the_layers_and_the_key_blocks(cfg, fitted):
    """Set when the training program is traced: four window layers and one
    full, each counted at the tile the op takes from its shapes. Under
    the window of 160 that is blocks of 128 (no block longer than the
    window): 300 tokens are three of them, a causal head visits 1 + 2 + 3
    = 6 a layer, the window hides none of those (block 0 holds keys 0-127,
    the last query block starts at 256 and sees back to 97), and the grid
    is 3 query blocks by the longest span, 3. The full layer's 300 tokens
    are under one tile: one block, one step. 4 x 6 + 1 and 4 x 9 + 1. A
    stage keeps each attention's output and rows' log-sum-exp."""
    from mxnet_tpu.ops.pallas_flash import (blocks_visited, grid_steps,
                                            tile_for)
    g = fitted["gauges"]
    assert (g["attn/window_layers"], g["attn/full_layers"]) == (4, 1)
    t, window = cfg["sequence_length"], cfg["sliding_window"]
    d = cfg["head_dim"]
    assert tile_for(t, t, d, d, 4, window) == (128, 128)
    assert tile_for(t, t, d, d, 4, None) == (t, t)
    assert blocks_visited(t, t, 128, 128, window) == (6, 6)
    assert grid_steps(t, t, 128, 128, True, window) == 9
    assert (g["attn/kv_blocks_visited"], g["attn/kv_blocks_causal"]) \
        == (25, 25)
    assert g["attn/grid_steps"] == 37
    # heads of 16: every core's backward is the plain one
    assert (g["attn/bwd_kernel"], g["attn/bwd_plain"]) == (0, 5)
    assert g["stage/kept_values"] == 10
    # a window of 40 hides block 0 from the last query block
    assert blocks_visited(t, t, 128, 128, 40) == (5, 6)
    assert grid_steps(t, t, 128, 128, True, 40) == 6


def test_every_block_is_a_stage_that_keeps_its_attention_output(cfg):
    from mxnet_tpu.executor import _mirror_stages
    sym = build_symbol(cfg)
    stages = _mirror_stages(sym._topo(), list(sym._entries))
    assert len(stages) == len(cfg["layers"])
    for _first, _last, reads, writes in stages:
        assert len(writes) == 1
        assert sum(1 for r in reads if r[0] == "val") == 1
    kinds = {n.name: n.attrs.get("device_scope") for n in sym._topo()
             if not n.is_variable and n.name.endswith("_attn")}
    assert kinds == {"l1_attn": "mx/attn/window", "l2_attn": "mx/attn/window",
                     "l3_attn": "mx/attn/full", "l4_attn": "mx/attn/window",
                     "l5_attn": "mx/attn/window"}


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(cfg):
    """Each fourth of the experts in turn as ``experts_held``, the shared
    expert once: the sum is what the reference gives for the whole
    layer."""
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
    for lo in range(0, n, 2):                # every fourth: two experts
        part, counts = expert_layer(
            x, p["moe_router_weight"], p["moe_router_bias"],
            p["moe_gate_weight"][lo:lo + 2], p["moe_up_weight"][lo:lo + 2],
            p["moe_down_weight"][lo:lo + 2], experts_held=(lo, lo + 2),
            top_k=d["top_k"], scale=d["scale"])
        total = total + part
        seen += int(counts.sum())
    assert seen == x.shape[0] * d["top_k"]   # no token dropped anywhere
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rotary_op_turns_pairs_split_at_the_half(dtype):
    """Against the definition, pair by pair: element ``i`` of the first
    half and of the second are one pair, turned by ``t theta^(-i / (D /
    2))``; position 0 is untouched, the norm of every pair is kept, and
    ``q . k`` depends on the distance alone."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops.lm_ops import rope
    rng = np.random.RandomState(0)
    x = rng.randn(2, 9, 3, 8).astype("f4")
    out = np.asarray(rope(jnp.asarray(x, jnp.dtype(dtype)), theta=100.0),
                     np.float32)
    xs = np.asarray(jnp.asarray(x, jnp.dtype(dtype)), np.float32)
    want = np.empty_like(xs)
    for t in range(9):
        for i in range(4):
            a = t * 100.0 ** (-i / 4)
            x1, x2 = xs[:, t, :, i], xs[:, t, :, 4 + i]
            want[:, t, :, i] = x1 * np.cos(a) - x2 * np.sin(a)
            want[:, t, :, 4 + i] = x2 * np.cos(a) + x1 * np.sin(a)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)
    np.testing.assert_array_equal(out[:, 0], xs[:, 0])
    np.testing.assert_allclose(out, np.asarray(ref.rotary(
        jnp.asarray(xs), 100.0)), rtol=tol, atol=tol)
    if dtype == "float32":
        q = rng.randn(1, 1, 1, 8).astype("f4")
        k = rng.randn(1, 1, 1, 8).astype("f4")
        turned = lambda v: np.asarray(rope(                    # noqa: E731
            jnp.asarray(np.repeat(v, 9, axis=1)), theta=100.0))
        dots = np.einsum("bthd,bshd->ts", turned(q), turned(k))
        assert dots[5, 3] == pytest.approx(dots[8, 6], rel=1e-4)
        assert dots[5, 3] != pytest.approx(dots[5, 2], rel=1e-3)
    sym = mx.sym.contrib.RoPE(mx.sym.Variable("x"), theta=100.0)
    assert sym.infer_shape(x=(2, 9, 3, 8))[1] == [(2, 9, 3, 8)]


def test_an_unknown_layer_type_is_refused():
    from mxnet_tpu.models.afmoe import afmoe_symbol
    with pytest.raises(ValueError, match="unknown layer type"):
        afmoe_symbol(layer_types=("chunked_attention",) * 32)
