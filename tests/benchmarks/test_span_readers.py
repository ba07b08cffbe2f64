"""The readers of the program's spans (``harness/spans.py`` and the six
``layer_metrics`` that use it) on a synthetic ring and reduced trace whose
answers are known by construction: ten steps of the program, six of them
traced, the two clocks 1.79e18 ns apart."""
import pytest

from benchpaths import BENCH_DIR
from harness import manifest, spans

MS = 1_000_000
OFF = -1_790_815_798_000_000_000      # trace clock - time.time_ns()
FIT, FEEDER = 11, 22
# step k starts STARTS[k] on the trace's clock: uneven, as a host is
STARTS = [1000 * MS + k * 100 * MS + j * 1000 for k, j in
          enumerate([0, 40, 7, 93, 21, 66, 5, 78, 30, 51])]
TRACED = range(2, 8)                  # the steps the device trace holds
READERS = ["input_wait_ms.train", "h2d_ms.train", "h2d_mb_per_step.train",
           "host_dispatch_ms.train", "fit_host_busy_pct.train",
           "idle_unattributed_pct.train"]


def _ring(h2d=True, skip_wait=(5,)):
    """The program's ring: a step is 1 ms of next, 47 ms of dispatch with
    40 ms of h2d inside, then a depth wait to the 90th ms (left out at the
    steps ``skip_wait``); on the ring's own clock."""
    out = []

    def add(name, s, e, parent, step, tid=FIT, counts=None):
        out.append((name, s - OFF, e - OFF, parent, step, tid, counts))

    for k, t in enumerate(STARTS):
        add("mx/fit/next", t + MS - 10_000, t + 2 * MS + 20_000,
            "mx/fit/epoch", k)
        if h2d:
            add("mx/feed/h2d", t + 5 * MS, t + 45 * MS, "mx/fit/dispatch",
                k, counts={"bytes": 77_070_848})
        add("mx/fit/dispatch", t + 3 * MS, t + 50 * MS, "mx/fit/epoch", k,
            counts={"steps": 1})
        if k not in skip_wait:
            add("mx/fit/depth_wait", t + 51 * MS, t + 90 * MS,
                "mx/fit/epoch", k)
        # another thread's span never counts as the fit thread's child
        add("mx/other", t + 4 * MS, t + 6 * MS, None, k, tid=FEEDER)
    add("mx/fit/epoch", STARTS[0] - MS, STARTS[-1] + 100 * MS, None, None,
        counts={"epoch": 1})
    return out


def _trace(shift_one_next=0):
    """The reduced trace: the step program runs the first 60 ms of every
    traced step, ``bench/next`` inside the program's ``mx/fit/next``."""
    mods = [["jit_step(1)", STARTS[k], 60 * MS] for k in TRACED]
    ops = [["fusion.1", STARTS[k], 60 * MS] for k in TRACED]
    host = [["bench/next", STARTS[k] + MS, MS] for k in TRACED]
    host[3][1] += shift_one_next
    host.append(["bench/final_fetch", STARTS[7] + 70 * MS, MS])
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": mods}], "host": host}


def _ctx(ring, trace=None):
    return {"trace": trace or _trace(), "step_program": "^jit_step",
            "steps_per_program": 1, "program_spans": ring}


def _read(name, ctx):
    return manifest.layer_reader(BENCH_DIR, name)(ctx)


def test_offset_is_recovered_and_the_view_is_the_steady_span(capsys):
    ctx = _ctx(_ring())
    v = spans.view(ctx)
    # mx/fit/next starts 10 us before bench/next, and that is all the bias
    assert v["offset_ns"] == OFF + 10_000
    # xplane.steady_span drops the first and last execution: steps 3..6
    assert (v["lo"], v["hi"], v["steps"]) == (STARTS[3], STARTS[6], 3)
    assert spans.fit_tid(v) == FIT
    err = capsys.readouterr().err
    assert "offset %d ns" % (OFF + 10_000) in err
    assert "mx/fit/depth_wait" in err and "(none)" in err


EXPECTED = {
    # three steps in the span; the 10 us of the offset's bias cancel in
    # every span that lies wholly inside it
    "input_wait_ms.train": 1.03,
    "h2d_ms.train": 40.0,
    "h2d_mb_per_step.train": 77.070848,
    "host_dispatch_ms.train": 7.0,
    # waits of steps 3 and 4 (39 ms each), step 5 has none
    "fit_host_busy_pct.train": 100.0 * (1 - 2 * 39 * MS
                                        / (STARTS[6] - STARTS[3])),
    # three gaps: two lie in a depth wait, step 5's in the epoch alone
    "idle_unattributed_pct.train":
        100.0 * (STARTS[6] - STARTS[5] - 60 * MS)
        / (STARTS[6] - STARTS[3] - 3 * 60 * MS),
}


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_the_value_known_by_construction(name):
    assert _read(name, _ctx(_ring())) == pytest.approx(EXPECTED[name],
                                                       rel=1e-6)


def test_idle_gaps_go_to_the_innermost_span():
    ctx = _ctx(_ring())
    spans.view(ctx)
    idle = spans.idle_by_span(ctx)
    assert idle == {
        "mx/fit/depth_wait": STARTS[5] - STARTS[3] - 2 * 60 * MS,
        "(none)": STARTS[6] - STARTS[5] - 60 * MS}
    v = ctx["_span_view"]
    # h2d lies inside dispatch: the innermost of the two covers its time
    assert spans.innermost(v, STARTS[4] + 10 * MS) == "mx/feed/h2d"
    assert spans.innermost(v, STARTS[4] + 48 * MS) == "mx/fit/dispatch"
    assert spans.innermost(v, STARTS[4] + 95 * MS) is None


@pytest.mark.parametrize("name", READERS)
def test_a_bench_next_outside_its_fit_next_refuses(name, capsys):
    ctx = _ctx(_ring(), _trace(shift_one_next=3 * MS))
    assert _read(name, ctx) is None
    assert "clock check failed" in capsys.readouterr().err


@pytest.mark.parametrize("name,value", [
    ("h2d_ms.train", 0.0), ("h2d_mb_per_step.train", 0.0),
    ("host_dispatch_ms.train", 47.0)])
def test_zero_not_nothing_where_no_span_of_the_name(name, value):
    assert _read(name, _ctx(_ring(h2d=False))) == pytest.approx(value)


@pytest.mark.parametrize("name", READERS)
def test_nothing_where_the_program_keeps_no_ring(name, capsys):
    """The parent commit has no ``profiler.spans``: the reader returns
    nothing and does not raise."""
    assert _read(name, _ctx(None)) is None
    assert "no span ring" in capsys.readouterr().err


def test_fewer_ring_spans_than_traced_ones_refuses(capsys):
    ring = [e for e in _ring() if e[0] != "mx/fit/next" or e[4] >= 6]
    assert spans.view(_ctx(ring)) is None
    assert "nothing to lay them over" in capsys.readouterr().err


def test_a_periodic_loop_is_told_apart_by_the_smaller_offset():
    """A device-bound loop repeats to the microsecond: every run of
    consecutive ``mx/fit/next`` fits the traced ``bench/next`` alike.
    Where the clocks roughly agree, the run with the smaller offset is
    the one."""
    ring, host = [], []
    for k in range(10):
        t = 10 ** 15 + k * 80 * MS
        ring.append(("mx/fit/next", t, t + 2 * MS, "mx/fit/epoch", k, FIT,
                     None))
        if 3 <= k < 8:
            host.append(["bench/next", t + 2_000 + 37, MS])
    assert spans.offset_ns(ring, host) == 2_037


def test_fetch_reads_the_programs_own_ring():
    from mxnet_tpu import profiler
    profiler.reset_spans()
    with profiler.span("mx/test/fetch", step=3, bytes=5):
        pass
    (e,) = spans.fetch()
    assert (e[spans.NAME], e[spans.STEP], e[spans.COUNTS]) == \
        ("mx/test/fetch", 3, {"bytes": 5})
    assert e[spans.END] >= e[spans.START] and e[spans.PARENT] is None
    profiler.reset_spans()
