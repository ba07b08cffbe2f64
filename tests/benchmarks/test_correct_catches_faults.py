"""``correct`` has to come out false when the timed path is broken, and
when the reference's arithmetic is done in the precision below the
configuration's. Each test skips the harness's look for a chip
(``--rehearse-cpu``) and drives the rest of a run in this process, at a
tiny size, with the fault planted underneath ``Module.fit``: in
``FusedStep.run``, the one call through which every timed step goes."""
import argparse
import os
import time

import pytest

from benchpaths import BENCH_DIR, ROOT

MANIFEST = os.path.join(ROOT, "tests", "benchmarks", "rehearsal.json")


def _run(workload, seed=3):
    from harness import manifest
    from runners import train_fit
    cell = manifest.load_cell(MANIFEST, ROOT, BENCH_DIR, workload)
    args = argparse.Namespace(seed=seed, seconds=0.3, trace=0,
                              rehearse_cpu=True)
    return train_fit.run(cell, args, time.perf_counter())


def _failed(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


@pytest.fixture
def break_step(monkeypatch):
    """Plants ``fault(run, self, arg_vals, ...)`` in place of the fused
    step's ``run``."""
    from mxnet_tpu.module import fused
    sound = fused.FusedStep.run

    def plant(fault):
        def run(self, arg_vals, aux_vals, opt_state, key, donate=False,
                met_state=None):
            return fault(sound, self, arg_vals, aux_vals, opt_state, key,
                         donate, met_state)
        monkeypatch.setattr(fused.FusedStep, "run", run)
    return plant


def _repeat_rows(share):
    """Every step sees only the first ``share`` of its batch, repeated to
    the batch's size: the rest is left out and the mean is over what
    stays. On four devices with share 1/4, every device's rows are the
    first device's, which is what the step computes there when the
    exchange between chips is left out."""
    import jax.numpy as jnp

    def fault(sound, self, arg_vals, aux_vals, opt_state, key, donate, met):
        arg_vals = dict(arg_vals)
        for name in ("data", "softmax_label"):
            v = arg_vals[name]
            keep = int(v.shape[0] * share)
            arg_vals[name] = jnp.concatenate(
                [v[:keep]] * (v.shape[0] // keep))
        return sound(self, arg_vals, aux_vals, opt_state, key, donate, met)
    return fault


def test_sound_runs_are_correct_on_one_device_and_on_four():
    for workload in ("tiny_resident", "tiny_dp4_resident"):
        result = _run(workload)
        assert result["correct"] is True, result["compared"]
        assert not _failed(result)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(break_step):
    def unchanged(sound, self, arg_vals, aux_vals, opt_state, key, donate,
                  met):
        outs, _args, _aux, _opt, new_met = sound(
            self, arg_vals, aux_vals, opt_state, key, False, met)
        return outs, dict(arg_vals), aux_vals, opt_state, new_met
    break_step(unchanged)
    result = _run("tiny_resident")
    assert result["correct"] is False
    # nothing moved: the gap of norms is the whole of the reference's
    assert result["compared"]["change3_gap"]["value"] == pytest.approx(1.0)
    assert {"grad1_gap", "change3_gap", "stats3_gap"} <= _failed(result)


def test_half_of_the_batch_left_out_is_not_correct(break_step):
    break_step(_repeat_rows(0.5))
    result = _run("tiny_resident")
    assert result["correct"] is False
    assert _failed(result) & {"grad1_gap", "change3_gap", "loss2_gap"}


def test_the_exchange_between_chips_left_out_is_not_correct(break_step):
    break_step(_repeat_rows(0.25))
    result = _run("tiny_dp4_resident")
    assert result["correct"] is False
    assert _failed(result) & {"grad1_gap", "change3_gap", "loss2_gap"}


def test_the_lower_precision_control_is_not_correct():
    """The reference put in the program's place and computed with operands
    of the precision below the configuration's (bfloat16 under this
    float32 rehearsal, float8 under the bfloat16 cells), against the same
    reference as it stands: at least one number passes its limit, on each
    of three seeds."""
    from harness import compare, manifest, traffic
    from references import resnet_v1 as ref
    from runners import train_fit
    cell = manifest.load_cell(MANIFEST, ROOT, BENCH_DIR, "tiny_resident")
    cfg = cell["cfg"]
    for seed in (11, 12, 13):
        w0, aux0 = ref.init_params(cfg, seed)
        batches = traffic.make_batches(cell["mix"], cfg, seed)
        plain = train_fit.reference_readings(ref, cfg, w0, aux0, batches)
        control = train_fit.reference_readings(
            ref, cfg, w0, aux0, batches, operand=getattr(ref, cfg["control"]))
        ok, rows = compare.judge(compare.numbers(control, plain),
                                 cfg["limits"])
        assert not ok, rows
        same, _ = compare.judge(compare.numbers(plain, plain), cfg["limits"])
        assert same
