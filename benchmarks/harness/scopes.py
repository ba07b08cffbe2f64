"""Device time by the program's named scopes (``jax.named_scope``:
``mx/kda``, ``mx/mla``, ``mx/moe/route``, ``mx/moe/experts``,
``mx/lm_head``).

The device trace names an op by its HLO instruction (``%fusion.12 = ...``)
and carries no scope. The compiled step program's own text does: every
instruction's ``metadata={op_name="jit(step)/.../mx/kda/..."}`` keeps the
name stack it was traced under, forward (``mx/kda``), backward
(``transpose(jvp(mx/kda))``) and recomputed (``rematted_computation/mx/
kda``) alike. So the runner asks the program for that text
(``ctx["hlo_text"]``), and an op's time goes to the scope its instruction
was traced under. Where the program has no such text, or no scope is in
it, there is nothing to read and every reader returns nothing.
"""
from __future__ import annotations

import re

from harness import xplane

SCOPES = ("mx/kda", "mx/mla", "mx/moe/route", "mx/moe/experts", "mx/lm_head")
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\-.]+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_scopes(hlo_text, scopes=SCOPES):
    """instruction name -> the scope it was traced under; the longest
    scope that its ``op_name`` holds."""
    by_len = sorted(scopes, key=len, reverse=True)
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        op = OP_NAME.search(line)
        if not op:
            continue
        for scope in by_len:
            if scope in op.group(1):
                out[m.group(1)] = scope
                break
    return out


def scope_classes(ctx):
    """scope -> {class of op (``xplane.op_class``) -> device milliseconds
    a step} inside the steady span, by self time (every instant goes to
    one op), the mean over the chips; kept in ``ctx``. None where there is
    nothing to read."""
    if "_scope_classes" in ctx:
        return ctx["_scope_classes"]
    ctx["_scope_classes"] = None
    where = instruction_scopes(ctx.get("hlo_text") or "")
    spans = [(d, xplane.steady_span(d, ctx["step_program"]))
             for d in ctx["trace"]["devices"]]
    spans = [(d, sp) for d, sp in spans if sp]
    if not where or not spans:
        return None
    acc = {s: {} for s in SCOPES}
    for dev, (lo, hi, steps) in spans:
        per_step = 1e6 * steps * ctx["steps_per_program"] * len(spans)
        for name, ns in xplane.self_times(dev["ops"], lo, hi).items():
            m = INSTRUCTION.match(name)
            scope = where.get(m.group(1)) if m else None
            if scope:
                cls = xplane.op_class(name)
                acc[scope][cls] = acc[scope].get(cls, 0.0) + ns / per_step
    ctx["_scope_classes"] = acc
    return acc


def scope_ms(ctx):
    """scope -> device milliseconds a step, or None."""
    classes = scope_classes(ctx)
    return classes and {s: sum(by.values()) for s, by in classes.items()}


def scope_top_ops(ctx, n=4):
    """scope -> [[class of op, device ms a step]], the largest first: for
    the result line's breakdown."""
    classes = scope_classes(ctx)
    return classes and {
        s: [[c, ms] for c, ms in sorted(by.items(), key=lambda kv: -kv[1])[:n]]
        for s, by in classes.items() if by}


def roofline_pct(ctx, scope, flops, nbytes):
    """The share of its roofline that ``scope`` reached: the least time
    the chip could take for ``flops`` and ``nbytes`` a step (the larger of
    operations over peak FLOP/s and bytes over peak bytes/s), over the
    measured device time a step."""
    ms = scope_ms(ctx)
    if not ms or not ms.get(scope) or not ctx.get("peaks"):
        return None
    least_s = max(flops / ctx["peaks"]["flops_per_s"],
                  nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms[scope] / 1e3)
