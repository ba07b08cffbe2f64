"""``correct`` of the window / full attention cell has to come out false
when the timed path computes another model, and when the reference's
arithmetic is done in the precision below the configuration's. Each test
drives a run in this process at a tiny size (``--rehearse-cpu``'s path)
with one of the reference's seven faults planted underneath ``Module.fit``,
in the registered op that every timed step runs: the window layers see
everything, rotary on the full layer too, query head ``i`` reading KV head
``i % H_kv``, no output gate, the routed experts left out, the routing
weights renormalised over the held experts, half the tokens out of the
loss."""
import argparse
import os
import time

import numpy as np
import pytest

from benchpaths import BENCH_DIR, ROOT

MANIFEST = os.path.join("tests", "benchmarks", "rehearsal_afmoe.json")


def _cell():
    """The rehearsal's cell cut to one window and one full layer, both
    with experts: every planted fault has its layer."""
    from harness import manifest
    cell = manifest.load_cell(os.path.join(ROOT, MANIFEST), ROOT, BENCH_DIR,
                              "tiny_afmoe_resident")
    cell["cfg"] = dict(cell["cfg"], layers=[2, 3], num_hidden_layers=2)
    return cell


def _run(seed=3):
    from runners import train_lm_cfg
    args = argparse.Namespace(seed=seed, seconds=0.2, trace=0,
                              rehearse_cpu=True)
    return train_lm_cfg.run(_cell(), args, time.perf_counter())


def _failed(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


def _heads_last(fn, x):
    """``fn`` over (B, T, H, D) applied to (B, H, T, D)."""
    import jax.numpy as jnp
    return jnp.moveaxis(fn(jnp.moveaxis(x, 1, 2)), 2, 1)


def _no_window(sound, q, k, v, **kw):
    kw.pop("window", None)
    return sound(q, k, v, **kw)


def _rope_on_full(sound, q, k, v, **kw):
    from mxnet_tpu.ops.lm_ops import rope
    if kw.get("window") is None:
        q, k = _heads_last(rope, q), _heads_last(rope, k)
    return sound(q, k, v, **kw)


def _kv_heads_interleaved(sound, q, k, v, **kw):
    import jax.numpy as jnp
    kv_of = jnp.arange(q.shape[1]) % k.shape[1]
    return sound(q, k[:, kv_of], v[:, kv_of], **kw)


def _gate_open(sound, x, **kw):
    import jax.numpy as jnp
    return jnp.ones_like(x)


def _shared_only(sound, *a, **kw):
    import jax.numpy as jnp
    y, counters = sound(*a, **kw)
    return jnp.zeros_like(y), counters


def _first_half(sound, data, weight, label, **kw):
    import jax
    import jax.numpy as jnp
    t = data.shape[1]
    keep = (jnp.arange(t) < t // 2).astype(jnp.float32)
    rows = sound(data, weight, label, **kw)
    # the forward value stays, the second half's gradient goes, and the
    # mean is over what is kept
    return jax.lax.stop_gradient(rows) + 2.0 * keep * (
        rows - jax.lax.stop_gradient(rows))


# fault of the reference -> (registered op, what stands in its place)
PLANTED = {
    "no_window": ("_contrib_FlashAttention", _no_window),
    "rope_on_full": ("_contrib_FlashAttention", _rope_on_full),
    "kv_heads_interleaved": ("_contrib_FlashAttention",
                             _kv_heads_interleaved),
    "no_attn_gate": ("sigmoid", _gate_open),
    "no_routed": ("_contrib_MoE", _shared_only),
    "half_tokens": ("_contrib_LMHeadLoss", _first_half),
}


def test_every_fault_of_the_reference_is_planted_here():
    from references import afmoe as ref
    assert set(ref.FAULTS) == set(PLANTED) | {"renorm_held"}


def test_a_sound_run_is_correct():
    result = _run()
    assert result["correct"] is True, result["compared"]


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    from mxnet_tpu.ops import registry
    op_name, wrong = PLANTED[fault]
    op = registry.get(op_name)
    sound = op.fn
    monkeypatch.setattr(op, "fn", lambda *a, **k: wrong(sound, *a, **k))
    result = _run()
    assert result["correct"] is False
    assert {"grad1_gap", "change3_gap"} <= _failed(result), (
        fault, result["compared"])


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


def test_the_lower_precision_control_and_the_references_faults_are_not_correct():
    """The reference put in the program's place: computed with bfloat16
    operands (the control of this float32 rehearsal; float8 under the
    bfloat16 cell), and with each of its own planted faults, against the
    same reference as it stands: at least one number passes its limit
    every time; the reference against itself passes every one."""
    from harness import compare, compare_lm, token_traffic
    from references import afmoe as ref
    from runners import train_lm_fit
    cell = _cell()
    cfg = cell["cfg"]
    for seed in (11, 12):
        w0 = {k: np.asarray(v) for k, v in ref.init_params(cfg, seed).items()}
        batches = token_traffic.make_token_batches(cell["mix"], cfg, seed)
        plain = train_lm_fit.reference_readings(ref, cfg, w0, batches)
        same, _ = compare.judge(compare_lm.numbers(plain, plain, w0),
                                cfg["limits"])
        assert same
        variants = [{"operand": getattr(ref, cfg["control"])}]
        if seed == 11:
            variants += [{"fault": f} for f in ref.FAULTS]
        for kw in variants:
            other = train_lm_fit.reference_readings(ref, cfg, w0, batches,
                                                    **kw)
            ok, rows = compare.judge(compare_lm.numbers(other, plain, w0),
                                     cfg["limits"])
            assert not ok, (kw, rows)
