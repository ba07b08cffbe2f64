"""Device time by any of the program's named scopes, and the time that no
scope names.

Every op node of a fused training step runs under a ``jax.named_scope``
(``executor.py``: the builder's ``device_scope``, else ``mx/op/<the op's
registered name>``), and the step's own parts under ``mx/cast``,
``mx/allreduce``, ``mx/opt`` and ``mx/metric`` (``module/fused.py``). As in
``harness/scopes.py`` the compiled step's text says under which names an
instruction was traced (``op_name``) and the device trace how long it ran;
here the join is kept whole, one row an instruction, so that a reader may
ask for any list of names, and for what is left: the instructions whose
``op_name`` holds no ``mx/`` at all (what the compiler made and named
itself, such as the grouped products' ``ragged-dot-none``). An instruction
goes to a name by its **own** ``op_name``: a fusion is one device op with
one name, the compiler's choice among those of the instructions it holds
(a product's, where it fuses a weight's update into the product that makes
its gradient), and what it holds under other names is said on the line of
``report`` and read by no metric. Times are self times
(``xplane.self_times``): every instant goes to one instruction and an
instruction to one name, so nothing is counted twice. Where the program
has no such text, or the trace no whole step, there is nothing to read and
everything here returns nothing.
"""
from __future__ import annotations

import re
import sys

from harness import scopes, xplane

STEP_SCOPES = ("mx/opt", "mx/cast", "mx/allreduce", "mx/metric")
DENSE_SCOPES = ("mx/op/FullyConnected", "mx/op/_contrib_SwiGLU")
OP_SCOPE = re.compile(r"mx/op/\w+")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w\-.]+) \(.*\) -> .*\{\s*$")
CALLS = re.compile(r"\bcalls=%?([\w\-.]+)")
OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
ARRAY = re.compile(r"\b(pred|bf16|[suf]\d+|f8\w+|c\d+)\[([\d,]*)\]")
OPERAND = re.compile(r"%?([\w\-.]+)\s*(?:,|$)")
ATTRIBUTES = (", metadata=", ", backend_config=", ", frontend_attributes=")


def _holds(op_name, scope):
    """Whether ``scope`` is one of the names ``op_name`` was traced under:
    the whole name, not the head of a longer one (``mx/op/Convolution`` is
    not in ``mx/op/Convolution_v1/...``)."""
    at = op_name.find(scope)
    while at >= 0:
        end = at + len(scope)
        if end == len(op_name) or not (op_name[end].isalnum()
                                       or op_name[end] == "_"):
            return True
        at = op_name.find(scope, at + 1)
    return False


def _array_bytes(types):
    """Bytes of the arrays a type names (``f32[8,128]{1,0}``, a tuple of
    them): elements times the element's width, layouts' padding left out."""
    total = 0
    for dtype, dims in ARRAY.findall(types):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        bits = 8 if dtype == "pred" else int(re.search(r"\d+", dtype).group())
        total += n * max(bits, 8) // 8
    return total


def program(hlo_text):
    """instruction -> (its own ``op_name``; the ``op_name``s of the
    instructions of the computation it calls, one a line; the bytes of its
    result and of its operands' results: the least it reads and writes,
    each array once). The text writes an instruction as ``%name = type
    opcode(%operand, ...), attribute=...``."""
    own, calls, members, made, reads, cur = {}, {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            cur = m.group(1)
            continue
        m = scopes.INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = scopes.OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        members.setdefault(cur, []).append(name)
        called = CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        head = line[m.end() - 1:]
        for attribute in ATTRIBUTES:
            head = head.split(attribute, 1)[0]
        opcode = OPCODE.search(head)
        if opcode:
            made[name] = _array_bytes(head[:opcode.start()])
            args, depth = head[opcode.end():], 1
            for at, ch in enumerate(args):
                depth += (ch == "(") - (ch == ")")
                if not depth:
                    args = args[:at]
                    break
            reads[name] = OPERAND.findall(re.sub(r"\{[^{}]*\}", "", args))

    def inside(name, seen):
        for inner in members.get(calls.get(name), ()):
            if inner not in seen:
                seen.add(inner)
                yield own[inner]
                yield from inside(inner, seen)
    return {name: (op, "\n".join(sorted(set(inside(name, set())) - {""})),
                   made.get(name, 0)
                   + sum(made.get(r, 0) for r in reads.get(name, ())))
            for name, op in own.items()}


def rows(ctx):
    """[(op_name, the op_names inside, class of op, device milliseconds a
    step, bytes)]: one row an instruction that ran inside the steady span,
    by self time, the mean over the chips; ``op_name`` is ``""`` where the
    text gives the instruction none. Kept in ``ctx``; None where there is
    nothing to read."""
    if "_scope_cover" in ctx:
        return ctx["_scope_cover"]
    ctx["_scope_cover"] = None
    text = program(ctx.get("hlo_text") or "")
    spans = [(d, xplane.steady_span(d, ctx["step_program"]))
             for d in ctx["trace"]["devices"]]
    spans = [(d, sp) for d, sp in spans if sp]
    if not text or not spans:
        return None
    acc = {}
    for dev, (lo, hi, steps) in spans:
        per_step = 1e6 * steps * ctx["steps_per_program"] * len(spans)
        for name, ns in xplane.self_times(dev["ops"], lo, hi).items():
            m = scopes.INSTRUCTION.match(name)
            key = (m.group(1) if m and m.group(1) in text else None,
                   xplane.op_class(name))
            acc[key] = acc.get(key, 0.0) + ns / per_step
    ctx["_scope_cover"] = [
        (text[name][:2] if name else ("", "")) + (cls, ms)
        + (text[name][2] if name else 0,)
        for (name, cls), ms in acc.items()]
    return ctx["_scope_cover"]


def under(ctx, names):
    """name -> (device milliseconds a step, bytes a step) of the
    instructions traced under it, for the names given; an instruction
    under several goes to the longest. An instruction is taken to run once
    a step, as those of the step's own scopes do (they sit in no loop).
    None where no instruction that ran was traced under any of them (a
    program from before the scopes)."""
    table = rows(ctx)
    if not table:
        return None
    by_len = sorted(names, key=len, reverse=True)
    out, found = {n: (0.0, 0) for n in names}, False
    for op, _inner, _cls, ms, nbytes in table:
        for scope in by_len:
            if _holds(op, scope):
                out[scope] = (out[scope][0] + ms, out[scope][1] + nbytes)
                found = True
                break
    return out if found else None


def under_ms(ctx, names):
    """The milliseconds a step of ``under``, summed over the names."""
    got = under(ctx, names)
    return sum(ms for ms, _ in got.values()) if got else None


def unattributed(ctx):
    """(milliseconds a step, {class of op: milliseconds a step}) of the
    instructions that hold no ``mx/`` in their ``op_name`` nor in one
    inside them, and the milliseconds a step of all that ran; or None."""
    table = rows(ctx)
    if not table:
        return None
    classes = {}
    for op, inner, cls, ms, _ in table:
        if "mx/" not in op and "mx/" not in inner:
            classes[cls] = classes.get(cls, 0.0) + ms
    return sum(classes.values()), classes, sum(r[3] for r in table)


def _part(where, by_len):
    m = OP_SCOPE.search(where)
    return m.group(0) if m else next(
        (s for s in STEP_SCOPES + by_len if _holds(where, s)),
        "mx/(other)" if "mx/" in where else "(no mx/ scope)")


def _pass(op_name):
    """Which pass of the step an instruction belongs to, by the transforms
    in its ``op_name``."""
    return "recomputed" if "rematted_computation" in op_name else \
        "backward" if "transpose(" in op_name else "forward"


def partition(ctx, others=()):
    """For the line a person reads: (name -> milliseconds a step, every
    instruction once: under its outermost ``mx/op/<name>``, else one of
    the step's own scopes, else the longest of ``others`` (the scopes a
    builder gave), else ``mx/(other)``, else ``(no mx/ scope)``; by its
    own ``op_name`` where that holds a scope, else by those inside it;
    name -> the part of that in fusions that hold an update of ``mx/opt``
    under another's name; pass -> the dense products' milliseconds)."""
    table = rows(ctx)
    if not table:
        return None
    by_len = tuple(sorted(others, key=len, reverse=True))
    out, with_opt, dense = {}, {}, {}
    for op, inner, _cls, ms, _ in table:
        key = _part(op if "mx/" in op else inner, by_len)
        out[key] = out.get(key, 0.0) + ms
        if key != "mx/opt" and _holds(inner, "mx/opt"):
            with_opt[key] = with_opt.get(key, 0.0) + ms
        if any(_holds(op, s) for s in DENSE_SCOPES):
            dense[_pass(op)] = dense.get(_pass(op), 0.0) + ms
    return out, with_opt, dense


def gauge(name):
    """What the program's gauge ``name`` reads in this process (set when
    the training step was traced), or None where the program has no such
    gauge. No metric is computed from one: ``report`` prints them beside
    the harness's own counts."""
    from mxnet_tpu import telemetry
    found = telemetry.default_registry().get(name)
    return found.value() if found is not None else None


def report(ctx):
    """One line: every scope's milliseconds a step, the largest first; of
    those, the fusions that hold an optimizer update under another name;
    the dense products by pass; the bytes the updates under ``mx/opt``
    move beside what the program says a whole update moves; the dense
    operations by the harness and by the program; the five largest classes
    of op that no scope names."""
    cfg = ctx.get("cfg") or {}
    parts = partition(ctx, cfg.get("device_scopes", scopes.SCOPES))
    left, opt = unattributed(ctx), under(ctx, ("mx/opt",))
    if not parts or not left:
        return

    def largest(d, n=None):
        return " ".join("%s=%.3f" % kv for kv in sorted(
            d.items(), key=lambda kv: -kv[1])[:n]) or "-"

    def said(value, scale):
        return "-" if value is None else "%.3f" % (value / scale)
    held, state = gauge("opt/param_bytes"), gauge("opt/state_bytes")
    whole = None if held is None or state is None \
        else 2 * (held + state) + held
    from harness import flops_dense
    print("scopes ms/step %s | of which hold mx/opt inside: %s "
          "| dense by pass: %s | mx/opt moves %s GB of the %s the program "
          "counts for every update | dense forward TFLOP %s, the program's "
          "%s | no mx/ scope %.3f of %.3f: %s" % (
              largest(parts[0]), largest(parts[1]), largest(parts[2]),
              said(opt and opt["mx/opt"][1], 1e9), said(whole, 1e9),
              said(flops_dense.forward_flops(cfg, ctx["batch_size"]), 1e12),
              said(gauge("dense/flops_fwd"), 1e12),
              left[0], left[2], largest(left[1], 5)), file=sys.stderr)
