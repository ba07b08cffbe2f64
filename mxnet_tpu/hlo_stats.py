"""Static analysis of lowered StableHLO text: layout/precision op counts.

Extracted from tools/diagnose_step_hlo.py so the same counters serve both
the diagnosis CLI and chip-free regression tests: the pre-optimization
StableHLO of a jitted program is a deterministic function of the traced
graph, so the nominal element traffic through `convert` / `transpose` ops
(and the `convolution` / `dot_general` dtypes) on CPU catches an
activation round-tripping through f32 without a chip. An op COUNT is not
a cost: one convert over a flat buffer of all parameters read better here
and cost a third of the step in relayouts on the chip (PERF.md, PR 26).

    import jax, mxnet_tpu.hlo_stats as hs
    stats = hs.analyze_stablehlo(jax.jit(f).lower(*args).as_text())
    assert hs.convert_gelems_between(stats, "f32", "bf16") < BUDGET
"""
from __future__ import annotations

import collections
import re

# "?" dims appear in dynamic-batch (jax.export symbolic-shape) modules;
# an unknown dim counts as 1 element in _elems, which keeps every count
# a LOWER bound — the direction the budgets ratchet against
_SHAPE_RE = re.compile(r"tensor<([0-9?x]*)x?([a-z0-9]+)>")
_OP_RE = re.compile(r"stablehlo\.(\w+)")


def _elems(shape_str):
    """Element count of a StableHLO shape prefix like '128x3x224x224'."""
    n = 1
    for d in shape_str.split("x"):
        if d.isdigit():
            n *= int(d)
    return n


def analyze_stablehlo(text):
    """Count the layout/precision ops in StableHLO text.

    Returns an OrderedDict of human-readable counters:

    * ``transpose_count`` / ``transpose_gelems`` — layout shuffles and the
      billions of elements they move;
    * ``convert_count`` / ``convert_pairs`` / ``convert_gelems`` — dtype
      converts broken down by ``src->dst`` pair with nominal element
      traffic per pair;
    * ``convolution`` / ``dot_general`` — MXU-op counts keyed by result
      element type;
    * ``total_ops`` / ``top_ops`` — overall op census.
    """
    out = collections.OrderedDict()
    op_counts = collections.Counter()
    transpose_elems = 0
    convert_pairs = collections.Counter()
    convert_elems = collections.Counter()
    conv_types = collections.Counter()
    dot_types = collections.Counter()

    for line in text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        op_counts[op] += 1
        if op == "transpose":
            shapes = _SHAPE_RE.findall(line)
            if shapes:
                transpose_elems += _elems(shapes[0][0])
        elif op == "convert":
            shapes = _SHAPE_RE.findall(line)
            if len(shapes) >= 2:
                pair = "%s->%s" % (shapes[0][1], shapes[-1][1])
                convert_pairs[pair] += 1
                convert_elems[pair] += _elems(shapes[0][0])
        elif op == "convolution":
            shapes = _SHAPE_RE.findall(line)
            if shapes:
                conv_types[shapes[-1][1]] += 1
        elif op == "dot_general":
            shapes = _SHAPE_RE.findall(line)
            if shapes:
                dot_types[shapes[-1][1]] += 1

    out["transpose_count"] = op_counts["transpose"]
    out["transpose_gelems"] = transpose_elems / 1e9
    out["convert_count"] = op_counts["convert"]
    out["convert_pairs"] = dict(convert_pairs.most_common())
    out["convert_gelems"] = {k: v / 1e9
                             for k, v in convert_elems.most_common()}
    out["convolution"] = dict(conv_types)
    out["dot_general"] = dict(dot_types)
    out["total_ops"] = sum(op_counts.values())
    out["top_ops"] = dict(op_counts.most_common(12))
    return out


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
    "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1,
    "c64": 8, "c128": 16,
}

_ENTRY_RE = re.compile(r"func\.func\s+(?:public\s+)?@(\w+)\s*\(")
_DONOR_RE = re.compile(r"jax\.buffer_donor\s*=\s*true|tf\.aliasing_output")
_CUSTOM_CALL_RE = re.compile(r"stablehlo\.custom_call\s+@([\w.$-]+)")


def _matching_paren(text, open_idx):
    """Index just past the ')' matching the '(' at ``open_idx``; -1 if the
    text ends first (truncated module)."""
    depth = 0
    for i in range(open_idx, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i
    return -1


def entry_params(text):
    """Parse the entry computation's parameter list from StableHLO text.

    Returns a list of dicts — ``{"name", "dtype", "elems", "bytes",
    "donated"}`` in argument order — for the first ``func.func public``
    (falling back to any ``func.func``). A module with **zero entry
    computations** (e.g. an empty or constant-folded-away lowering)
    returns ``[]`` instead of raising, and parameters whose type is not a
    plain ranked tensor (token, tuple) are included with ``elems=0``.
    """
    m = None
    for cand in _ENTRY_RE.finditer(text):
        m = cand
        # prefer @main / the first public func; _ENTRY_RE already skips
        # private helper parens like stablehlo.reduce regions
        break
    if m is None:
        return []
    open_idx = text.index("(", m.end() - 1)
    close_idx = _matching_paren(text, open_idx)
    if close_idx < 0:
        return []
    sig = text[open_idx + 1:close_idx]
    params = []
    # split on top-level commas only (attr dicts contain commas)
    depth = 0
    start = 0
    parts = []
    for i, c in enumerate(sig):
        if c in "({<[":
            depth += 1
        elif c in ")}>]":
            depth -= 1
        elif c == "," and depth == 0:
            parts.append(sig[start:i])
            start = i + 1
    if sig[start:].strip():
        parts.append(sig[start:])
    for part in parts:
        part = part.strip()
        if not part:
            continue
        name = part.split(":", 1)[0].strip()
        tm = _SHAPE_RE.search(part)
        if tm:
            dtype = tm.group(2)
            elems = _elems(tm.group(1)) if tm.group(1) else 1
        else:
            dtype, elems = "unknown", 0
        params.append({
            "name": name,
            "dtype": dtype,
            "elems": elems,
            "bytes": elems * _DTYPE_BYTES.get(dtype, 4),
            "donated": bool(_DONOR_RE.search(part)),
        })
    return params


def custom_call_targets(text):
    """Counter of ``stablehlo.custom_call`` target names in the module.

    Robust to tuple-returning custom calls (``%0:2 = stablehlo.custom_call
    @target(...) : (...) -> (tensor<...>, tensor<...>)``) — the target is
    read from the op token itself, never from the result arity."""
    return collections.Counter(_CUSTOM_CALL_RE.findall(text))


# ops whose results are pure data movement / pointwise math: every byte
# they write is an intermediate XLA must either fuse away or spill to HBM.
# The *nominal* sum over them (pre-optimization) is an upper bound on the
# fusion work the backend has to do — and the number a fused Pallas
# epilogue (kernels/) removes from the program outright.
_ELEMENTWISE_OPS = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "select", "convert", "transpose", "negate", "exponential", "tanh",
    "logistic", "rsqrt", "sqrt", "compare", "clamp", "abs", "power",
    "and", "or", "xor", "broadcast_in_dim",
))


def elementwise_bytes(text):
    """(total_bytes, per_op_bytes) nominally written by elementwise and
    layout ops in the module.

    Counts the RESULT tensor of every op in ``_ELEMENTWISE_OPS`` (the last
    ``tensor<...>`` on the line — StableHLO prints the result type last).
    Pre-optimization this is a deterministic, chip-free proxy for the
    bytes-moved pressure the fusion pass (mxlint MXL505) budgets."""
    total = 0
    per_op = collections.Counter()
    for line in text.splitlines():
        m = _OP_RE.search(line)
        if not m or m.group(1) not in _ELEMENTWISE_OPS:
            continue
        shapes = _SHAPE_RE.findall(line)
        if not shapes:
            continue
        shape_str, dtype = shapes[-1]
        b = _elems(shape_str) * _DTYPE_BYTES.get(dtype, 4)
        total += b
        per_op[m.group(1)] += b
    return total, per_op


_KERNEL_NAME_RE = re.compile(r'kernel_name\s*=\s*"([\w.$-]+)"')


def pallas_kernel_names(text):
    """Counter of Pallas ``kernel_name`` attributes in the module.

    A ``pl.pallas_call(..., name="mxk_foo")`` lowered for TPU shows up as
    a ``stablehlo.custom_call @tpu_custom_call`` whose backend config
    carries ``kernel_name = "mxk_foo"`` in plain text — so a chip-free
    ``jax.export``-for-TPU module proves which kernels the tier actually
    dispatched, no accelerator needed. Interpreter-mode lowerings inline
    to plain HLO and (correctly) report nothing here."""
    return collections.Counter(_KERNEL_NAME_RE.findall(text))


def convert_count_between(stats, a, b):
    """Total converts in either direction between element types ``a`` and
    ``b`` (e.g. ``("f32", "bf16")``) from an :func:`analyze_stablehlo`
    result."""
    pairs = stats.get("convert_pairs", {})
    return pairs.get("%s->%s" % (a, b), 0) + pairs.get("%s->%s" % (b, a), 0)


def convert_gelems_between(stats, a, b):
    """Nominal element traffic (Gelem) through converts between ``a`` and
    ``b`` in either direction."""
    g = stats.get("convert_gelems", {})
    return g.get("%s->%s" % (a, b), 0.0) + g.get("%s->%s" % (b, a), 0.0)
