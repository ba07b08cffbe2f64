"""``correct`` of the Mamba-2 / attention hybrid cell has to come out false
when the timed path computes another model, and when the reference's
arithmetic is done in the precision below the configuration's. Each test
drives a run in this process at a tiny size (``--rehearse-cpu``'s path)
with one of the reference's nine faults planted underneath ``Module.fit``,
in the registered op that every timed step runs: the state dropped at
every chunk's edge, no ``dt_bias``, the output norm without its gate, the
convolution without its bias, no ``D`` skip, the branches joined at
multiplier 1, the scores times ``1 / sqrt(head)``, the head's gradient kept
from the tied matrix, half the tokens out of the loss."""
import argparse
import os
import time

import numpy as np
import pytest

from benchpaths import BENCH_DIR, ROOT

MANIFEST = os.path.join("tests", "benchmarks", "rehearsal_granite_hybrid.json")


def _cell():
    from harness import manifest
    return manifest.load_cell(os.path.join(ROOT, MANIFEST), ROOT, BENCH_DIR,
                              "tiny_granite_resident")


def _run(seed=3):
    from runners import train_lm_cfg
    args = argparse.Namespace(seed=seed, seconds=0.2, trace=0,
                              rehearse_cpu=True)
    return train_lm_cfg.run(_cell(), args, time.perf_counter())


def _failed(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


def _no_carry(sound, x, b, c, dt, *vectors, **kw):
    """Every chunk from a zero state."""
    import jax.numpy as jnp
    n = kw["chunk"]
    return jnp.concatenate([
        sound(*(a[:, i:i + n] for a in (x, b, c, dt)), *vectors, **kw)
        for i in range(0, x.shape[1], n)], axis=1)


def _no_dt_bias(sound, x, b, c, dt, dt_bias, a_log, d, **kw):
    return sound(x, b, c, dt, dt_bias * 0, a_log, d, **kw)


def _no_skip(sound, x, b, c, dt, dt_bias, a_log, d, **kw):
    return sound(x, b, c, dt, dt_bias, a_log, d * 0, **kw)


def _no_gate(sound, data, gate, gamma, **kw):
    from mxnet_tpu.ops.lm_ops import rms_norm
    return rms_norm(data, gamma, **kw)


def _no_conv_bias(sound, data, weight, bias=None, **kw):
    return sound(data, weight, None, **dict(kw, no_bias=True))


def _residual_one(sound, data, **kw):
    """``x * 0.22`` is the one product by that scalar in the graph."""
    if abs(float(kw["scalar"]) - 0.22) < 1e-9:
        kw = dict(kw, scalar=1.0)
    return sound(data, **kw)


def _scale_rsqrt(sound, q, k, v, **kw):
    kw.pop("scale")
    return sound(q, k, v, **kw)


def _untied(sound, data, weight, label, **kw):
    import jax
    return sound(data, jax.lax.stop_gradient(weight), label, **kw)


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
    "no_carry": ("_contrib_Mamba2", _no_carry),
    "no_dt_bias": ("_contrib_Mamba2", _no_dt_bias),
    "no_skip": ("_contrib_Mamba2", _no_skip),
    "no_gate": ("_contrib_GatedRMSNorm", _no_gate),
    "no_conv_bias": ("_contrib_CausalConv1D", _no_conv_bias),
    "residual_one": ("_mul_scalar", _residual_one),
    "scale_rsqrt": ("_contrib_FlashAttention", _scale_rsqrt),
    "untied": ("_contrib_LMHeadLoss", _untied),
    "half_tokens": ("_contrib_LMHeadLoss", _first_half),
}


def test_every_fault_of_the_reference_is_planted_here():
    from references import granite_hybrid as ref
    assert set(ref.FAULTS) == set(PLANTED)


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


def test_the_lower_precision_control_and_the_references_faults_are_not_correct():
    """The reference put in the program's place: computed with bfloat16
    operands (the control of this float32 rehearsal; float8 under the
    bfloat16 cell), and with each of its own planted faults, against the
    same reference as it stands: at least one number passes its limit
    every time; the reference against itself passes every one."""
    from harness import compare, compare_lm, token_traffic
    from references import granite_hybrid as ref
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
