"""Device time by the named scopes a configuration lists
(``device_scopes``): ``harness/scopes.py``'s join of the compiled step's
text with the device trace, over a list that is given and not fixed.
Where the program has no such text, the configuration lists no scope, or
none of them is in the text, every reader returns nothing.
"""
from __future__ import annotations

from harness import scopes, xplane


def scope_classes(ctx):
    """scope -> {class of op -> device milliseconds a step} inside the
    steady span, by self time, the mean over the chips, for the scopes of
    ``ctx["cfg"]["device_scopes"]``; kept in ``ctx``."""
    if "_scope_classes_of" in ctx:
        return ctx["_scope_classes_of"]
    ctx["_scope_classes_of"] = None
    names = tuple((ctx.get("cfg") or {}).get("device_scopes", ()))
    where = scopes.instruction_scopes(ctx.get("hlo_text") or "", names) \
        if names else {}
    spans = [(d, xplane.steady_span(d, ctx["step_program"]))
             for d in ctx["trace"]["devices"]]
    spans = [(d, sp) for d, sp in spans if sp]
    if not where or not spans:
        return None
    acc = {s: {} for s in names}
    for dev, (lo, hi, steps) in spans:
        per_step = 1e6 * steps * ctx["steps_per_program"] * len(spans)
        for name, ns in xplane.self_times(dev["ops"], lo, hi).items():
            m = scopes.INSTRUCTION.match(name)
            scope = where.get(m.group(1)) if m else None
            if scope:
                cls = xplane.op_class(name)
                acc[scope][cls] = acc[scope].get(cls, 0.0) + ns / per_step
    ctx["_scope_classes_of"] = acc
    return acc


def scope_ms(ctx):
    """scope -> device milliseconds a step, or None."""
    classes = scope_classes(ctx)
    return classes and {s: sum(by.values()) for s, by in classes.items()}


def scope_top_ops(ctx, n=4):
    """scope -> [[class of op, device ms a step]], the largest first."""
    classes = scope_classes(ctx)
    return classes and {
        s: [[c, ms] for c, ms in sorted(by.items(), key=lambda kv: -kv[1])[:n]]
        for s, by in classes.items() if by}


def roofline_pct(ctx, scope, flops, nbytes):
    """The share of its roofline that ``scope`` reached: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s, over the
    measured device time a step."""
    ms = scope_ms(ctx)
    if not ms or not ms.get(scope) or not ctx.get("peaks"):
        return None
    least_s = max(flops / ctx["peaks"]["flops_per_s"],
                  nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms[scope] / 1e3)
