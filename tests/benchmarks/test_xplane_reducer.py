"""The reduction from a device trace to the per-layer numbers: on hand-made
traces whose answers can be worked out on paper, and on a small trace
recorded on the chip and kept beside this file."""
import json
import os

import pytest

from benchpaths import BENCH_DIR
from harness import manifest, xplane

MS = 1_000_000
STEP = "^jit_step"


def _dev(ops, modules, name="/device:TPU:0"):
    """A device's lines; ``modules`` are the whole executions, and the
    profiler's two cut ones are put around them."""
    first, last = modules[0], modules[-1]
    cut = [[first[0], first[1] - 3 * MS, 2 * MS],
           [last[0], last[1] + last[2] + MS, MS]]
    return {"name": name, "ops": ops, "modules": cut[:1] + modules + cut[1:]}


def _ctx(trace, **kw):
    ctx = {"trace": trace, "step_program": STEP, "steps_per_program": 1,
           "batch_size": 128, "chips": len(trace["devices"]),
           "train_flops_per_image": 1e9,
           "peaks": {"flops_per_s": 1e12}, "counters": {}}
    ctx.update(kw)
    return ctx


def _read(name, ctx):
    return manifest.layer_reader(BENCH_DIR, name)(ctx)


@pytest.fixture
def three_steps():
    """Three step programs of 8 ms every 10 ms; in each, ops cover 6 ms."""
    ops, modules = [], []
    for i in range(3):
        t = i * 10 * MS
        modules.append(["jit_step(1)", t, 8 * MS])
        ops += [["fusion.1", t, 4 * MS], ["fusion.2", t + 5 * MS, 2 * MS]]
    return {"devices": [_dev(ops, modules)], "host": []}


def test_union_merges_overlap_and_nesting():
    ivs = [(0, 10), (5, 15), (20, 30), (22, 25), (30, 31), (40, 40)]
    assert xplane.union(ivs) == [(0, 15), (20, 31)]
    assert xplane.total(xplane.union(ivs)) == 26


def test_two_overlapping_ops_are_not_counted_twice():
    """A while op that holds its body's ops, and two ops that overlap:
    summed they would be 19 ms busy in a 10 ms span, an idle share of
    -90% and a utilization without bound."""
    ops = [["while.1", 0, 9 * MS], ["fusion.a", 0, 5 * MS],
           ["fusion.b", 4 * MS, 5 * MS]]
    mods = [["jit_step(1)", 0, 9 * MS], ["jit_step(1)", 10 * MS, 9 * MS]]
    trace = {"devices": [_dev(ops, mods)], "host": []}
    idle = _read("device_idle_pct.train", _ctx(trace))
    assert idle == pytest.approx(10.0)
    assert 0.0 <= idle <= 100.0
    assert xplane.self_times(ops, 0, 10 * MS) == {
        "while.1": 0, "fusion.a": 4 * MS, "fusion.b": 5 * MS}


def test_subtract():
    assert xplane.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) \
        == [(0, 2), (4, 8), (22, 29)]
    assert xplane.subtract([(0, 10)], []) == [(0, 10)]
    assert xplane.subtract([(0, 10)], [(0, 10)]) == []


def test_busy_gaps_and_step_time(three_steps):
    ctx = _ctx(three_steps)
    # the steady span is two whole periods: 20 ms, 12 ms of them busy
    assert _read("device_idle_pct.train", ctx) == pytest.approx(40.0)
    assert _read("step_device_ms.train", ctx) == pytest.approx(6.0)
    assert _read("dispatch_gap_ms.train", ctx) == pytest.approx(2.0)
    # 2 steps x 128 images x 1e9 FLOP in 20 ms over 1e12 FLOP/s
    assert _read("step_mfu_pct.train", ctx) == pytest.approx(
        100 * 2 * 128 * 1e9 / 0.020 / 1e12)
    assert xplane.device_summary(three_steps, STEP) == \
        pytest.approx((0.012, 0.020))
    assert _read("collective_exposed_pct.train", ctx) is None


def test_k_steps_in_a_program_divide_its_time(three_steps):
    ctx = _ctx(three_steps, steps_per_program=4)
    assert _read("step_device_ms.train", ctx) == pytest.approx(1.5)
    assert _read("step_mfu_pct.train", ctx) == pytest.approx(
        100 * 8 * 128 * 1e9 / 0.020 / 1e12)


def test_exposed_collective_is_what_no_other_op_covers():
    """An all-reduce of 4 ms, 1 ms of it under a convolution: 3 ms of a
    20 ms span are exposed; a second, wholly hidden, adds nothing."""
    ops = [["fusion.1", 0, 3 * MS], ["all-reduce.7", 2 * MS, 4 * MS],
           ["fusion.2", 12 * MS, 6 * MS], ["all-reduce-start.9", 13 * MS, MS]]
    mods = [["jit_step(1)", 0, 9 * MS], ["jit_step(1)", 20 * MS, 9 * MS]]
    trace = {"devices": [_dev(ops, mods)], "host": []}
    assert xplane.exposed_collective(trace["devices"][0], 0, 20 * MS) == \
        [(3 * MS, 6 * MS)]
    assert _read("collective_exposed_pct.train", _ctx(trace)) == \
        pytest.approx(15.0)


def test_metrics_are_means_over_chips(three_steps):
    quiet = _dev([["fusion.1", 0, 10 * MS], ["fusion.1", 10 * MS, 10 * MS]],
                 [["jit_step(1)", 0, 10 * MS], ["jit_step(1)", 10 * MS, 10 * MS],
                  ["jit_step(1)", 20 * MS, 10 * MS]], "/device:TPU:1")
    trace = {"devices": three_steps["devices"] + [quiet], "host": []}
    assert _read("device_idle_pct.train", _ctx(trace)) == pytest.approx(20.0)


def test_a_reader_with_nothing_to_read_returns_nothing():
    trace = {"devices": [_dev([], [["jit_other(1)", 0, MS]])], "host": []}
    for name in ("device_idle_pct.train", "step_mfu_pct.train",
                 "step_device_ms.train", "dispatch_gap_ms.train",
                 "collective_exposed_pct.train"):
        assert _read(name, _ctx(trace)) is None
    assert xplane.device_summary(trace, STEP) is None


def test_idle_gaps_are_named_by_the_host_span_that_covers_them(three_steps):
    three_steps["host"] = [["bench/next", 8 * MS + 1, MS]]
    gaps = dict(xplane.idle_gaps(three_steps, STEP))
    # gaps of the span: 4-5, 7-10 (middle 8.5: under bench/next), 14-15, 17-20
    assert gaps["bench/next"] == pytest.approx(0.003)
    assert gaps["inside fit"] == pytest.approx(0.005)
    top = xplane.top_ops(three_steps, STEP)
    assert top[0] == ["fusion.1", pytest.approx(0.008)]


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_trace.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_sane_numbers(recorded):
    """A few steps of the ResNet-50 step as the v5e's profiler wrote them
    (ops thinned to keep the file small, see its ``note``): shares stay
    shares, and the numbers agree with each other."""
    cfg = recorded["context"]
    ctx = _ctx(recorded, step_program=cfg["step_program"],
               batch_size=cfg["batch_size"], chips=cfg["chips"],
               train_flops_per_image=cfg["train_flops_per_image"],
               peaks={"flops_per_s": cfg["peak_flops_per_s"]})
    idle = _read("device_idle_pct.train", ctx)
    mfu = _read("step_mfu_pct.train", ctx)
    step_ms = _read("step_device_ms.train", ctx)
    gap_ms = _read("dispatch_gap_ms.train", ctx)
    exposed = _read("collective_exposed_pct.train", ctx)
    assert len(recorded["devices"]) == cfg["chips"]
    assert 0.0 <= idle < 100.0 and 0.0 < mfu < 100.0
    if cfg["chips"] > 1:        # the gradient exchange is in the trace
        assert 0.0 <= exposed <= 100.0 - idle + 1e-9
    else:
        assert exposed is None
    busy_s, window_s = xplane.device_summary(recorded, cfg["step_program"])
    assert 0.0 < busy_s <= window_s
    assert idle == pytest.approx(100.0 * (1 - busy_s / window_s), abs=1e-6)
    # a period is the step's busy time plus what lies idle around it
    lo, hi, steps = xplane.steady_span(recorded["devices"][0],
                                       cfg["step_program"])
    period_ms = (hi - lo) / steps / 1e6
    assert step_ms <= period_ms and gap_ms <= period_ms
    got = {"idle": idle, "mfu": mfu, "step_ms": step_ms, "gap_ms": gap_ms,
           "exposed": exposed}
    for key, value in got.items():
        assert value == pytest.approx(recorded["expected"][key],
                                      rel=1e-9), key
