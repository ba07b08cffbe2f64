"""``correct`` of the looped-model cell has to come out false when the
timed path computes another model, and when the reference's arithmetic is
done in the precision below the configuration's. Each program test drives
a run in this process at a tiny size (``--rehearse-cpu``'s path) with one
of the reference's faults planted underneath ``Module.fit``, in the
registered op that every timed step runs: one pass alone, the exit
distribution uniform, no entropy term, rotary at theta 10,000. The
reference's six faults (those two that change the graph's structure, the
next pass reading the stream before the final norm and the layers without
their second norms, among them) and its lower-precision control are put in
the program's place against the plain reference."""
import argparse
import math
import os
import time

import numpy as np
import pytest

from benchpaths import BENCH_DIR, ROOT

MANIFEST = os.path.join("tests", "benchmarks", "rehearsal_ouro.json")


def _cell():
    from harness import manifest
    return manifest.load_cell(os.path.join(ROOT, MANIFEST), ROOT, BENCH_DIR,
                              "tiny_ouro_resident")


def _run(seed=3):
    from runners import train_lm_cfg
    args = argparse.Namespace(seed=seed, seconds=0.2, trace=0,
                              rehearse_cpu=True)
    return train_lm_cfg.run(_cell(), args, time.perf_counter())


def _failed(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


def _one_pass(sound, logits, rows, counters, **kw):
    return sound(logits[:1], rows[:1], counters, **kw)


def _uniform_exit(sound, logits, rows, counters, **kw):
    """Gates of 1/4, 1/3, 1/2 leave a quarter after each of four passes;
    the gate's own logits are not read."""
    import jax
    import jax.numpy as jnp
    p = logits.shape[0]
    flat = jnp.asarray([math.log(1.0 / (p - 1 - t)) if t < p - 1 else 0.0
                        for t in range(p)], logits.dtype)[:, None, None]
    return sound(jax.lax.stop_gradient(logits) * 0 + flat, rows, counters,
                 **kw)


def _no_entropy(sound, logits, rows, counters, **kw):
    return sound(logits, rows, counters, **dict(kw, beta=0.0))


def _rope_1e4(sound, data, **kw):
    return sound(data, **dict(kw, theta=1e4))


# fault of the reference -> (registered op, what stands in its place)
PLANTED = {
    "one_pass": ("_contrib_LoopExitLoss", _one_pass),
    "uniform_exit": ("_contrib_LoopExitLoss", _uniform_exit),
    "no_entropy": ("_contrib_LoopExitLoss", _no_entropy),
    "rope_1e4": ("_contrib_RoPE", _rope_1e4),
}


def test_the_planted_faults_are_the_references():
    from references import ouro as ref
    assert set(PLANTED) < set(ref.FAULTS)
    assert set(ref.FAULTS) - set(PLANTED) == {"raw_carry", "no_post_norms"}


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
    from references import ouro as ref
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
