"""From a profiler trace to the numbers the per-layer metrics read.

Two stages. ``load`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote
into a plain *reduced trace* (JSON-able, and small enough to keep one
recorded with the tests)::

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_ns, dur_ns], ...],       # "XLA Ops"
                  "modules": [[name, start_ns, dur_ns], ...]}], # "XLA Modules"
     "host": [[name, start_ns, dur_ns], ...]}                   # TraceMe spans

The functions below it reduce that form, all over intervals in
nanoseconds. Busy time is the *union* of op intervals: ops nest (a
``while`` holds its body's ops) and overlap, so a sum would count time
twice, read an idle share below zero and a utilization without bound.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")


# ------------------------------------------------------------------ stage 1
def load(trace_dir, host_prefix="bench/"):
    """The reduced trace of the newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    data = ProfileData.from_file(found[-1])
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    devices.sort(key=lambda d: d["name"])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


# ------------------------------------------------------------------ stage 2
def intervals(events, match=None):
    """[(start, end)] of the events whose name ``match`` accepts."""
    return [(s, s + d) for name, s, d in events
            if match is None or match(name)]


def union(ivs):
    """Sorted, disjoint intervals covering the same time as ``ivs``."""
    out = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs
            if min(e, hi) > max(s, lo)]


def total(ivs):
    return sum(e - s for s, e in ivs)


def subtract(a, b):
    """The part of the disjoint sorted intervals ``a`` that no interval
    of the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def step_modules(dev, step_program):
    """The whole executions of the step program on one device, in time
    order. The profiler starts and stops in the middle of an execution and
    records the part it saw as an event of its own, so the first and the
    last event are dropped."""
    pat = re.compile(step_program)
    return sorted((e for e in dev["modules"] if pat.search(e[0])),
                  key=lambda e: e[1])[1:-1]


def steady_span(dev, step_program):
    """(lo, hi, steps): from the start of the first whole execution of the
    step program in the trace to the start of the last, and the executions
    begun in between: a whole number of periods, gaps included. None with
    fewer than two whole executions."""
    mods = step_modules(dev, step_program)
    if len(mods) < 2:
        return None
    return mods[0][1], mods[-1][1], len(mods) - 1


def busy(dev, lo, hi):
    """Disjoint intervals within [lo, hi) in which some op ran."""
    return clip(union(intervals(dev["ops"])), lo, hi)


def step_gaps(dev, step_program):
    """Idle time between each execution of the step program and the next."""
    mods = step_modules(dev, step_program)
    return [max(0, b[1] - (a[1] + a[2])) for a, b in zip(mods, mods[1:])]


def exposed_collective(dev, lo, hi):
    """Intervals in which a collective runs on the device and nothing
    else does."""
    coll = union(intervals(dev["ops"], COLLECTIVE.search))
    other = union(intervals(dev["ops"], lambda n: not COLLECTIVE.search(n)))
    return clip(subtract(coll, other), lo, hi)


OP_TEXT = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = ")
FUSION_KIND = re.compile(r"kind=k(\w+)")


def op_class(name):
    """The class an op's time is added up under: the stem of its HLO name
    (``reshape``, ``copy-done``, ``add_add_fusion``), and for a plain
    ``fusion`` its kind as well (``fusion/Output`` is the convolutions')."""
    m = OP_TEXT.match(name)
    stem = m.group(1) if m else name.split(" ")[0].lstrip("%")
    if stem == "fusion":
        kind = FUSION_KIND.search(name)
        return "fusion/" + (kind.group(1) if kind else "?")
    return stem


def top_ops(trace, step_program, n=10):
    """[[class of op, seconds]]: the classes that took most device time
    inside the steady span, by self time (every instant goes to one op),
    averaged over the devices. The trace names an op by its whole HLO
    text; ``op_class`` keeps the stem."""
    acc = {}
    devs = [d for d in trace["devices"] if steady_span(d, step_program)]
    for dev in devs:
        lo, hi, _ = steady_span(dev, step_program)
        for name, ns in self_times(dev["ops"], lo, hi).items():
            cls = op_class(name)
            acc[cls] = acc.get(cls, 0) + ns
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / len(devs)] for name, ns in rows]


def self_times(ops, lo, hi):
    """name -> ns of self time for ops that start within [lo, hi): every
    instant goes to the op that started last among those running, so the
    self times add up to the union and nothing is counted twice."""
    evs = sorted((e for e in ops if lo <= e[1] < hi),
                 key=lambda e: (e[1], -e[2]))
    acc, stack = {}, []           # stack of (name, end)
    cur = 0

    def advance(t):
        nonlocal cur
        while stack:
            name, end = stack[-1]
            if end > cur:
                upto = min(end, t)
                acc[name] = acc.get(name, 0) + upto - cur
                cur = upto
                if end > t:
                    return
            stack.pop()
        cur = max(cur, t)

    for name, s, d in evs:
        advance(s)
        cur = max(cur, s)
        acc.setdefault(name, 0)
        stack.append((name, s + d))
    advance(float("inf"))
    return acc


def idle_gaps(trace, step_program, n=10):
    """[[what the host was doing, seconds]]: the device's idle time inside
    the steady span of the first device, by the benchmark's host span
    that covers the middle of each gap (``inside fit`` where none does),
    the longest totals first."""
    devs = [d for d in trace["devices"] if steady_span(d, step_program)]
    if not devs:
        return []
    lo, hi, _ = steady_span(devs[0], step_program)
    gaps = subtract([(lo, hi)], busy(devs[0], lo, hi))
    acc = {}
    for s, e in gaps:
        mid = (s + e) / 2
        name = "inside fit"
        for hname, hs, hd in trace["host"]:
            if hs <= mid < hs + hd:
                name = hname
                break
        acc[name] = acc.get(name, 0) + (e - s)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def device_summary(trace, step_program):
    """(busy_s, window_s) for the result line: op-busy seconds inside the
    steady span, averaged over the devices, and the span's length."""
    rows = []
    for dev in trace["devices"]:
        sp = steady_span(dev, step_program)
        if sp:
            lo, hi, _ = sp
            rows.append((total(busy(dev, lo, hi)) / 1e9, (hi - lo) / 1e9))
    if not rows:
        return None
    return (sum(r[0] for r in rows) / len(rows),
            sum(r[1] for r in rows) / len(rows))
