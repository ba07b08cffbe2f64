"""Every device op of a fused training step runs under a named scope: a
node under its builder's ``device_scope`` or ``mx/op/<registered name>``
(executor.py), the step's own parts under ``mx/cast``, ``mx/opt`` and
``mx/metric`` (module/fused.py); the gauges the step and the dense ops set
when they are traced; and where the expert layer's scope stops. On the
CPU, at a tiny size, in the compiled program's own text."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import config, sym, telemetry

ROWS, WIDE, HID, INTER, OUT = 8, 12, 16, 24, 5
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _symbol():
    x = sym.Variable("data")
    h = sym.FullyConnected(data=x, num_hidden=HID, no_bias=True,
                           flatten=False, name="fc1")
    h = sym.RMSNorm(data=h, gamma=sym.Variable("n_gamma", shape=(HID,)),
                    eps=1e-5, name="n")
    with mx.AttrScope(device_scope="mx/mine"):
        h = sym.Activation(h, act_type="tanh", name="act")
    h = sym.contrib.SwiGLU(
        data=h, gate_weight=sym.Variable("g_weight", shape=(INTER, HID)),
        up_weight=sym.Variable("u_weight", shape=(INTER, HID)),
        down_weight=sym.Variable("d_weight", shape=(HID, INTER)), name="mlp")
    h = sym.FullyConnected(data=h, num_hidden=OUT, name="fc2")
    return sym.SoftmaxOutput(h, name="softmax")


# what the step updates: fc1, the norm's scale, the three matrices of the
# feed-forward block, fc2 and its bias; all float32
PARAMS = WIDE * HID + HID + 3 * HID * INTER + HID * OUT + OUT
# 2 x rows x in x out: fc1, gate + up + down, fc2
FLOPS = 2 * ROWS * (WIDE * HID + 3 * HID * INTER + HID * OUT)


def _fitted(optimizer, **params):
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.randn(ROWS, WIDE).astype("f4"),
                           rng.randint(0, OUT, (ROWS,)).astype("f4"),
                           batch_size=ROWS)
    mod = mx.mod.Module(_symbol(), context=mx.tpu(0))
    mod.fit(it, num_epoch=1, optimizer=optimizer, optimizer_params=params,
            kvstore="tpu_sync", initializer=mx.init.Xavier())
    assert mod._fused is not None, "the fused step did not engage"
    ex = mod._exec
    text = mod._fused.lower(
        ex._arg_vals(), ex._aux_vals(), mod._fused_opt_state,
        met_state=mod._fused_met_state, donate=True).compile().as_text()
    gauges = {g: telemetry.gauge(g).value() for g in (
        "opt/param_bytes", "opt/state_bytes", "dense/flops_fwd")}
    return set(OP_NAME.findall(text)), gauges


@pytest.fixture(scope="module")
def adam():
    return _fitted("adam", learning_rate=1e-3, multi_precision=True)


def test_every_op_of_the_fused_step_runs_under_a_named_scope(adam):
    names, _ = adam

    def some(part):
        return [n for n in names if part in n]
    # a node by its op's registered name, forward and backward
    for op in ("FullyConnected", "_contrib_SwiGLU", "RMSNorm",
               "SoftmaxOutput"):
        assert some("jvp(mx/op/%s)" % op) or some("/mx/op/%s/" % op), op
        assert some("transpose(jvp(mx/op/%s))" % op), op
    # the builder's name on its node, and not the op's beside it
    assert some("jvp(mx/mine)") and some("transpose(jvp(mx/mine))")
    assert not some("mx/op/Activation")
    assert not [n for n in some("mx/mine") if "mx/op/" in n]
    # the step's own parts: the update, the casts of bfloat16 compute over
    # float32 masters (the gradient's cast back is the transpose), the
    # metric that rides the step
    assert some("/mx/opt/") and some("mx/cast") and some("/mx/metric/")
    assert not some("mx/allreduce")          # one device: no reducer
    # no instruction that the program traced is left without a name:
    # what has none is the compiler's own (parameters, tuples, copies)
    for n in names:
        if n.startswith("jit(step)/"):
            assert "mx/" in n, n


@pytest.mark.parametrize("optimizer,params,state_arrays", [
    ("adam", {"learning_rate": 1e-3}, 2),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, 1)])
def test_the_gauges_equal_the_hand_count(optimizer, params, state_arrays):
    _, gauges = _fitted(optimizer, **params)
    assert gauges == {"opt/param_bytes": 4.0 * PARAMS,
                      "opt/state_bytes": 4.0 * PARAMS * state_arrays,
                      "dense/flops_fwd": float(FLOPS)}


def test_flops_count_rows_of_every_leading_axis():
    """``flatten=False`` over (B, T, in) is B x T rows; ``flatten=True``
    folds what follows the first axis into ``in``; counted only while a
    training program is traced."""
    from mxnet_tpu.ops.registry import get, program_counts
    fc, swiglu = get("FullyConnected").fn, get("_contrib_SwiGLU").fn
    x = jnp.zeros((2, 3, 4))
    counts = {}
    with program_counts(counts):
        fc(x, jnp.zeros((5, 4)), no_bias=True, flatten=False)
        assert counts == {"dense/flops_fwd": 2 * 6 * 4 * 5}
        fc(x, jnp.zeros((5, 12)), no_bias=True)
        assert counts["dense/flops_fwd"] == 2 * 6 * 4 * 5 + 2 * 2 * 12 * 5
        counts.clear()
        swiglu(x, jnp.zeros((7, 4)), jnp.zeros((7, 4)), jnp.zeros((4, 7)))
        assert counts == {"dense/flops_fwd": 3 * 2 * 6 * 4 * 7}
    fc(x, jnp.zeros((5, 4)), no_bias=True, flatten=False)   # no trace: no-op
    assert counts == {"dense/flops_fwd": 3 * 2 * 6 * 4 * 7}


def test_the_kernel_tier_names_a_pattern_by_its_head_node():
    """With the kernel tier on, ``FullyConnected -> gelu`` is one planned
    pattern evaluated at its last node: its device ops carry that node's
    name, as they would unfused."""
    from mxnet_tpu.executor import _graph_eval_fn
    from mxnet_tpu.kernels import tier
    x = sym.Variable("data")
    out = sym.LeakyReLU(sym.FullyConnected(x, num_hidden=128, name="fc"),
                        act_type="gelu", name="act")
    args = {"data": jnp.zeros((128, 128)), "fc_weight": jnp.zeros((128, 128)),
            "fc_bias": jnp.zeros((128,))}
    with config.override(kernel_tier="auto"):
        tier.reset_stats()
        fn = _graph_eval_fn(out)
        text = jax.jit(lambda a: fn(a, {}, jax.random.PRNGKey(0), False)[0]) \
            .lower(args).as_text(debug_info=True)
        assert tier.stats()["dispatch"].get("scale_bias_act") == 1
    assert "mx/op/LeakyReLU" in text and "mx/op/FullyConnected" not in text


def test_the_expert_layers_scope_is_on_the_grouped_products_as_traced():
    """The grouped products (``lax.ragged_dot``) are traced under
    ``mx/moe/experts``, forward and backward. That is as far as the program
    can name them: the TPU compiler rewrites each into a kernel call of its
    own, ``%ragged-dot-none``, whose ``op_name`` it sets to that name
    (tests/test_tpu_aot_compile.py pins that), so a reader that joins the
    device trace to the text by ``op_name`` sees no scope on them."""
    from mxnet_tpu.parallel import moe
    n, d, experts, held, h, k = 64, 16, 8, 2, 8, 2

    def loss(x, rw, wg, wu, wd):
        y, _ = moe.expert_layer(x, rw, jnp.zeros((experts,)), wg, wu, wd,
                                experts_held=(0, held), top_k=k)
        return jnp.sum(y)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 2, 3, 4))).lower(
        jnp.ones((n, d)), jnp.ones((experts, d)), jnp.ones((held, h, d)),
        jnp.ones((held, h, d)), jnp.ones((held, d, h))).as_text(
            debug_info=True)
    assert "ragged_dot" in text
    traced = set(re.findall(r'loc\("([^"]*/ragged_dot_general)"', text))
    # the forward walk, the block again inside the backward walk's vjp,
    # and its transpose
    assert {re.sub(r"^.*experts\)*/", "", t) for t in traced} == {
        "while/body/ragged_dot_general",
        "while/body/jvp()/ragged_dot_general",
        "while/body/transpose(jvp())/ragged_dot_general"}
    for name in traced:
        assert "mx/moe/experts" in name, name


def test_two_steps_that_differ_in_their_names_alone_are_the_same_program(
        tmp_path):
    """``tools/step_text.py``: a named scope is metadata on an instruction;
    the tables of files and frames at the head move with it and nothing
    else may."""
    import subprocess
    import sys
    from pathlib import Path
    tool = Path(__file__).resolve().parents[1] / "tools" / "step_text.py"
    text = '''HloModule jit_step, is_scheduled=true

FileNames
1 "/root/%(where)s/mxnet_tpu/executor.py"

StackFrames
1 {file_location_id=%(frame)d}

ENTRY %%main (a: f32[8]) -> f32[8] {
  %%a = f32[8]{0} parameter(0), metadata={op_name="a"}
  ROOT %%fusion.1 = f32[8]{0} fusion(%%a), kind=%(kind)s, calls=%%c, metadata={op_name="jit(step)/%(scope)smul" source_file="x{y}.py" stack_frame_id=%(frame)d}, backend_config={"k":"v"}
}
'''
    sides = {"parent": dict(where="a", frame=3, kind="kLoop", scope=""),
             "change": dict(where="b", frame=9, kind="kLoop",
                            scope="jvp(mx/op/RMSNorm)/"),
             "other": dict(where="a", frame=3, kind="kOutput", scope="")}
    for side, how in sides.items():
        (tmp_path / side).write_text(text % how)

    def same(a, b):
        return subprocess.run([sys.executable, str(tool), str(tmp_path / a),
                               str(tmp_path / b)], capture_output=True,
                              text=True)
    r = same("parent", "change")
    assert r.returncode == 0 and "IDENTICAL" in r.stdout, r.stdout + r.stderr
    assert "metadata taken from 2 instructions" in r.stdout
    r = same("parent", "other")
    assert r.returncode == 1 and "DIFFERENT in 1 lines" in r.stdout
    assert "differs: ROOT %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, " \
        "calls=%c, backend_config" in r.stdout
