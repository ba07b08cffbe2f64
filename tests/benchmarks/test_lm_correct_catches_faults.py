"""``correct`` of the language-model cell has to come out false when the
timed path computes another model, and when the reference's arithmetic is
done in the precision below the configuration's. Each test drives a run in
this process at a tiny size (``--rehearse-cpu``'s path) with the fault
planted underneath ``Module.fit``, in the registered op that every timed
step runs: no forgetting in KDA, the routed experts left out, the routing
weights renormalised over the held experts, half the tokens out of the
loss, rotary applied in the latent attention."""
import argparse
import os
import time

import numpy as np
import pytest

from benchpaths import BENCH_DIR, ROOT

MANIFEST = os.path.join("tests", "benchmarks", "rehearsal_lm.json")


def _cell():
    """The rehearsal's cell cut to one KDA and one MLA layer, both with
    experts: every planted fault has its layer, and the step compiles in a
    third of the time."""
    from harness import manifest
    cell = manifest.load_cell(os.path.join(ROOT, MANIFEST), ROOT, BENCH_DIR,
                              "tiny_lm_resident")
    cell["cfg"] = dict(cell["cfg"], layers=[2, 4], num_hidden_layers=2)
    return cell


def _run(seed=3):
    from runners import train_lm_fit
    args = argparse.Namespace(seed=seed, seconds=0.2, trace=0,
                              rehearse_cpu=True)
    return train_lm_fit.run(_cell(), args, time.perf_counter())


def _failed(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


@pytest.fixture
def plant(monkeypatch):
    """Puts ``wrong`` in the place of a registered op's function: the
    fault is then underneath ``Module.fit``, in the one program every
    timed step runs."""
    from mxnet_tpu.ops import registry

    def plant(op_name, wrong):
        op = registry.get(op_name)
        sound = op.fn
        monkeypatch.setattr(op, "fn",
                            lambda *a, **k: wrong(sound, *a, **k))
    return plant


def test_a_sound_run_is_correct():
    result = _run()
    assert result["correct"] is True, result["compared"]


def test_no_forgetting_is_not_correct(plant):
    import jax.numpy as jnp

    def alpha_one(sound, q, k, v, f, b, a_log, dt_bias, **kw):
        return sound(q, k, v, f, b, jnp.full_like(a_log, -80.0), dt_bias,
                     **kw)                     # exp(-80): no decay at all
    plant("_contrib_KDA", alpha_one)
    result = _run()
    assert result["correct"] is False
    assert {"grad1_gap", "change3_gap"} <= _failed(result)


def test_routed_experts_left_out_is_not_correct(plant):
    import jax.numpy as jnp

    def shared_only(sound, *a, **k):
        y, counters = sound(*a, **k)
        return jnp.zeros_like(y), counters
    plant("_contrib_MoE", shared_only)
    result = _run()
    assert result["correct"] is False
    assert {"grad1_gap", "change3_gap"} <= _failed(result)


def test_weights_renormalised_over_the_held_experts_is_not_correct(
        monkeypatch):
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe
    sound = moe.route
    lo, hi = _cell()["cfg"]["experts_held"]

    def renorm(x, w, b, top_k, scale):
        chosen, weight = sound(x, w, b, top_k, scale)
        own = jnp.sum(jnp.where((chosen >= lo) & (chosen < hi), weight, 0.0),
                      -1, keepdims=True)
        return chosen, weight * scale / (own + 1e-20)
    monkeypatch.setattr(moe, "route", renorm)
    result = _run()
    assert result["correct"] is False
    assert {"grad1_gap", "change3_gap"} <= _failed(result)


def test_half_the_tokens_out_of_the_loss_is_not_correct(plant):
    import jax
    import jax.numpy as jnp

    def first_half(sound, data, weight, label, **kw):
        t = data.shape[1]
        keep = (jnp.arange(t) < t // 2).astype(jnp.float32)
        rows = sound(data, weight, label, **kw)
        # the forward value stays, the second half's gradient goes, and
        # the mean is over what is kept
        return jax.lax.stop_gradient(rows) + 2.0 * keep * (
            rows - jax.lax.stop_gradient(rows))
    plant("_contrib_LMHeadLoss", first_half)
    result = _run()
    assert result["correct"] is False
    assert {"grad1_gap", "change3_gap"} <= _failed(result)


def test_rotary_in_the_latent_attention_is_not_correct(plant):
    from references import kimi_linear as ref
    rope = _cell()["cfg"]["qk_rope_head_dim"]

    def with_rotary(sound, q, k, v, **kw):
        import jax.numpy as jnp

        def turn(x):      # (B, H, T, d): rotate the positional part
            part = jnp.moveaxis(x[..., -rope:], 2, 1)      # (B, T, H, r)
            return jnp.concatenate(
                [x[..., :-rope], jnp.moveaxis(ref._rotary(part), 1, 2)], -1)
        return sound(turn(q), turn(k), v, **kw)
    plant("_contrib_FlashAttention", with_rotary)
    result = _run()
    assert result["correct"] is False
    assert _failed(result) & {"grad1_gap", "change3_gap"}


def test_the_lower_precision_control_is_not_correct():
    """The reference put in the program's place and computed with bfloat16
    operands (float8 under the bfloat16 cell), against the same reference
    as it stands: at least one number passes its limit, on each of three
    seeds; and the reference against itself passes every one."""
    import jax
    from harness import compare, compare_lm, token_traffic
    from references import kimi_linear as ref
    from runners import train_lm_fit
    cell = _cell()
    cfg = cell["cfg"]
    for seed in (11, 12, 13):
        w0 = {k: np.asarray(v) for k, v in ref.init_params(cfg, seed).items()}
        batches = token_traffic.make_token_batches(cell["mix"], cfg, seed)
        plain = train_lm_fit.reference_readings(ref, cfg, w0, batches)
        control = train_lm_fit.reference_readings(
            ref, cfg, w0, batches, operand=getattr(ref, cfg["control"]))
        ok, rows = compare.judge(compare_lm.numbers(control, plain, w0),
                                 cfg["limits"])
        assert not ok, rows
        same, _ = compare.judge(compare_lm.numbers(plain, plain, w0),
                                cfg["limits"])
        assert same
    del jax
