"""The program's own spans, put on the device trace's clock, for the
per-layer metrics that read them (``source: program_span`` and
``program_counter``).

The program (``mxnet_tpu.profiler``) keeps a ring of finished spans
``(name, start_ns, end_ns, parent, step, tid, counts)`` stamped with
``time.time_ns()``; the reduced trace (``harness/xplane.py``) is on the
profiler's clock. Nothing here assumes that the two agree. The
benchmark's own ``bench/next`` span, which is in the trace, runs strictly
inside the program's ``mx/fit/next``, which is in the ring: the traced
``bench/next`` spans are laid over every run of consecutive
``mx/fit/next`` spans, the run under which the differences of the starts
scatter least is theirs, and the median difference is the offset. After
it every ``bench/next`` has to lie inside its ``mx/fit/next`` to within
``TOLERANCE_NS``, or there is no view and standard error says why.

A *view* is the ring shifted onto the trace's clock and cut to the
*steady span* of the device metrics (``xplane.steady_span`` of the first
device that has one), so that a program-span metric and a device metric
of one run are taken over the same interval. Where the program has no
ring (a commit before the spans existed) there is no view, and every
reader returns nothing.
"""
from __future__ import annotations

import statistics
import sys

from harness import xplane

FIT_NEXT = "mx/fit/next"
BENCH_NEXT = "bench/next"
EPOCH = "mx/fit/epoch"
TOLERANCE_NS = 200_000
TIE_NS = 5_000          # scatters this close are told apart by the offset
AGREE_NS = 10 ** 9      # under this offset the two clocks "roughly agree"

NAME, START, END, PARENT, STEP, TID, COUNTS = range(7)


def fetch():
    """The program's ring as plain tuples, or None where the program has
    none."""
    try:
        from mxnet_tpu import profiler
        ring = profiler.spans
    except (ImportError, AttributeError):
        return None
    return [tuple(s) for s in ring()]


def _say(msg):
    print("spans: " + msg, file=sys.stderr)


def offset_ns(ring, host):
    """What to add to a ring stamp to get the trace's clock, or None (and
    why, on standard error)."""
    inner = [(s, s + d) for name, s, d in host if name == BENCH_NEXT]
    outer = sorted((e[START], e[END]) for e in ring if e[NAME] == FIT_NEXT)
    if not inner or len(outer) < len(inner):
        _say("%d %s in the trace, %d %s in the ring: nothing to lay them "
             "over" % (len(inner), BENCH_NEXT, len(outer), FIT_NEXT))
        return None
    cands = []
    for j in range(len(outer) - len(inner) + 1):
        diffs = [b[0] - m[0] for b, m in zip(inner, outer[j:])]
        med = statistics.median_low(diffs)   # an int: 1e18 outgrows a float
        cands.append((max(abs(d - med) for d in diffs), med, j))
    best = min(cands)
    # a device-bound loop is periodic to microseconds, so the next run
    # may scatter almost as little: where the two clocks roughly agree,
    # the smaller offset decides between runs that close
    near = [c for c in cands
            if c[0] <= best[0] + TIE_NS and abs(c[1]) < AGREE_NS]
    if near:
        best = min(near, key=lambda c: abs(c[1]))
    _scatter, off, j = best
    off = int(off)
    for b, m in zip(inner, outer[j:]):
        if b[0] - off < m[0] - TOLERANCE_NS or \
                b[1] - off > m[1] + TOLERANCE_NS:
            _say("clock check failed: after the offset %d ns a %s "
                 "[%d, %d] lies outside its %s [%d, %d] by more than "
                 "%d ns" % (off, BENCH_NEXT, b[0] - off, b[1] - off,
                            FIT_NEXT, m[0], m[1], TOLERANCE_NS))
            return None
    return off


def view(ctx):
    """The view of this run (made once, kept in ``ctx``), or None."""
    if "_span_view" in ctx:
        return ctx["_span_view"]
    ctx["_span_view"] = v = _make_view(ctx)
    if v is not None:
        _say("offset %d ns (trace clock - time.time_ns) from %d %s; "
             "steady span %.3f s, %d steps" % (
                 v["offset_ns"], v["matched"], BENCH_NEXT,
                 (v["hi"] - v["lo"]) / 1e9, v["steps"]))
        rows = sorted(idle_by_span(ctx).items(), key=lambda kv: -kv[1])
        _say("device idle time in the steady span by program span: "
             + ("; ".join("%s %.3f ms" % (n, ns / 1e6) for n, ns in rows)
                or "none"))
    return v


def _make_view(ctx):
    ring = ctx["program_spans"] if "program_spans" in ctx else fetch()
    if ring is None:
        _say("the program keeps no span ring (mxnet_tpu.profiler.spans)")
        return None
    trace = ctx["trace"]
    steady = [(d, xplane.steady_span(d, ctx["step_program"]))
              for d in trace["devices"]]
    steady = [(d, s) for d, s in steady if s]
    if not steady:
        return None
    off = offset_ns(ring, trace["host"])
    if off is None:
        return None
    dev, (lo, hi, programs) = steady[0]
    spans = sorted(((e[NAME], e[START] + off, e[END] + off) + tuple(e[3:])
                    for e in ring if e[END] + off > lo
                    and e[START] + off < hi), key=lambda e: e[START])
    return {"spans": spans, "lo": lo, "hi": hi, "device": dev,
            "steps": programs * ctx["steps_per_program"], "offset_ns": off,
            "matched": sum(1 for h in trace["host"] if h[0] == BENCH_NEXT)}


def named(v, *names):
    return [e for e in v["spans"] if e[NAME] in names]


def clipped(v, spans):
    """Disjoint sorted intervals inside the steady span that ``spans``
    cover."""
    return xplane.clip(xplane.union([(e[START], e[END]) for e in spans]),
                       v["lo"], v["hi"])


def total_ns(v, name):
    """Time inside the steady span under spans of that name, every thread
    added up."""
    return sum(xplane.total(xplane.clip([(e[START], e[END])],
                                        v["lo"], v["hi"]))
               for e in named(v, name))


def self_ns(v, name):
    """The same less what the spans' children cover: the spans of the
    same thread that lie inside them."""
    out = 0
    for e in named(v, name):
        kids = [k for k in v["spans"] if k is not e and k[TID] == e[TID]
                and k[START] >= e[START] and k[END] <= e[END]]
        own = xplane.clip([(e[START], e[END])], v["lo"], v["hi"])
        out += xplane.total(xplane.subtract(own, clipped(v, kids)))
    return out


def ms_per_step(ctx, name, own=False):
    """Mean ms a step inside the spans of that name over the steady span
    (``own``: less their children), or None where there is no view."""
    v = view(ctx)
    if v is None:
        return None
    return (self_ns if own else total_ns)(v, name) / 1e6 / v["steps"]


def count_sum(v, name, key):
    """Sum of the count ``key`` over the spans of that name that began
    inside the steady span."""
    return sum((e[COUNTS] or {}).get(key, 0) for e in named(v, name)
               if v["lo"] <= e[START] < v["hi"])


def fit_tid(v):
    """The thread of the fit loop: the one that dispatches."""
    for e in v["spans"]:
        if e[NAME] in ("mx/fit/dispatch", EPOCH):
            return e[TID]
    return None


def innermost(v, t):
    """The name of the span below ``mx/fit/epoch`` that covers ``t`` and
    began last, or None."""
    best = None
    for e in v["spans"]:
        if e[START] > t:
            break
        if e[END] > t and e[NAME] != EPOCH:
            best = e
    return best[NAME] if best else None


def idle_by_span(ctx):
    """name -> ns of the device's idle time inside the steady span, each
    gap put down to the innermost program span that covers its middle;
    ``(none)`` where only ``mx/fit/epoch`` or nothing does."""
    v = ctx.get("_span_view")
    if v is None:
        return {}
    busy = xplane.busy(v["device"], v["lo"], v["hi"])
    acc = {}
    for s, e in xplane.subtract([(v["lo"], v["hi"])], busy):
        name = innermost(v, (s + e) / 2) or "(none)"
        acc[name] = acc.get(name, 0) + e - s
    return acc
