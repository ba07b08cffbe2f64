"""Layer-1 mxlint rules: TPU-discipline checks over Python source (ast).

No chip, no jax import, no execution — pure syntax-tree analysis, so the
whole repo lints in well under a second inside tier-1. The rules encode
the disciplines PRs 1-4 enforced by hand:

* **host-sync** (MXL101/MXL102/MXL103) — a ``.asnumpy()`` / ``float()``
  / ``jax.device_get`` inside a traced (jit/scan/fused) body either
  errors at trace time or, worse, silently forces a device round-trip
  per step (the exact bug class tests/test_step_sync_budget.py pins);
* **retrace hazards** (MXL201/MXL202/MXL203) — Python-value branching
  on traced arrays, stringifying traced values, and unhashable static
  args all force recompilation (or crash) on every call;
* **donation misuse** (MXL301) — reading a buffer after passing it to a
  ``donate_argnums`` program is use-after-free at the XLA level;
* **lock discipline** (MXL401/MXL402) — blocking device/queue work while
  holding a lock serializes the batcher/engine threads (and inconsistent
  acquisition order across engine/serve/io is a deadlock waiting for
  load);
* **telemetry discipline** (MXL506) — named metric series belong to the
  run-wide telemetry registry (mxnet_tpu/telemetry), which mirrors them
  into the chrome trace itself; a direct ``profiler.record_counter``
  call forks a second source of truth that Prometheus/JSONL exporters
  and the flight recorder never see.

A function body is considered **traced** when its def is decorated with
a jit-like wrapper (``jax.jit``, ``partial(jax.jit, ...)``,
``jax.custom_vjp``, ``@fused``) or when its NAME is passed to a trace
entry point anywhere in the same module (``jax.jit(step)``,
``lax.scan(body, ...)``, ``jax.vjp(mirror_wrap(f), ...)``). Nested defs
inherit the traced context. This over-approximates on purpose: a false
positive is one baseline entry; a false negative is a silent 100x.
"""
from __future__ import annotations

import ast
import re

from .diagnostics import Diagnostic

__all__ = ["RULES", "analyze_module", "LockOrderCollector", "Rule"]


class Rule:
    """Static descriptor of one lint rule (id, severity, fix hint)."""

    def __init__(self, id, name, severity, hint):
        self.id = id
        self.name = name
        self.severity = severity
        self.hint = hint


RULES = {r.id: r for r in [
    Rule("MXL101", "host-sync-in-traced", "error",
         "move the host transfer (asnumpy/device_get/np.asarray) outside "
         "the jitted/scanned body; keep values as traced arrays inside"),
    Rule("MXL102", "scalar-coerce-in-traced", "error",
         "float()/int()/bool() on a traced value forces a concrete host "
         "value; use jnp ops (astype, where, lax.cond) instead"),
    Rule("MXL103", "unbatched-host-fetch", "warning",
         "N separate .asnumpy()/device_get calls in one loop iteration "
         "are N device round-trips; fetch once with jax.device_get((a, b, "
         "...)) or metric.update_dict's batched fetch"),
    Rule("MXL201", "python-branch-on-traced", "error",
         "an if/while on a traced value concretizes it (TracerBoolConv"
         "ersionError or a silent recompile); branch with jnp.where / "
         "lax.cond, or branch on .shape/.dtype which are static"),
    Rule("MXL202", "traced-value-in-format", "error",
         "str()/f-string on a traced value concretizes it at trace time; "
         "format shapes/dtypes (static) or move logging outside the "
         "traced body"),
    Rule("MXL203", "unhashable-static-arg", "error",
         "list/dict/set literals are unhashable; jit static args must be "
         "hashable (tuple/frozenset) or every call re-traces/raises"),
    Rule("MXL301", "use-after-donation", "error",
         "this buffer was donated to XLA (donate_argnums) and is dead "
         "after the call; rebind the name to the program's output or "
         "drop the donation"),
    Rule("MXL401", "blocking-call-under-lock", "error",
         "blocking device/queue/thread work while holding a lock stalls "
         "every other thread contending it; move the blocking call "
         "outside the critical section (engine_cache._build pattern)"),
    Rule("MXL402", "inconsistent-lock-order", "error",
         "these two locks are acquired in both nestings; pick one global "
         "order (document it where the locks are defined) to make "
         "deadlock impossible"),
    Rule("MXL506", "raw-profiler-counter", "error",
         "publish through the telemetry registry instead "
         "(telemetry.counter(name).inc() / telemetry.gauge(name).set()); "
         "the registry mirrors label-free series into the chrome trace, "
         "and a direct profiler.record_counter call is invisible to the "
         "Prometheus/JSONL exporters and the flight recorder"),
]}


# -- traced-context discovery -------------------------------------------------

# callables that trace their function argument(s)
_TRACE_ENTRY = frozenset([
    "jit", "scan", "vmap", "pmap", "grad", "value_and_grad", "vjp", "jvp",
    "checkpoint", "remat", "custom_vjp", "custom_jvp", "while_loop",
    "fori_loop", "cond", "switch", "named_call", "shard_map",
])

# decorator name fragments that mark the decorated def as traced
_TRACE_DECOR = _TRACE_ENTRY | frozenset(["fused"])

_STATIC_ATTRS = frozenset(["shape", "ndim", "dtype", "size", "aval",
                           "sharding", "weak_type", "name"])
_SAFE_CALLS = frozenset(["isinstance", "len", "hasattr", "getattr",
                         "callable", "type", "issubclass", "range",
                         "enumerate", "zip"])

_HOST_SYNC_ATTRS = frozenset(["asnumpy", "item", "tolist",
                              "block_until_ready"])
_NP_NAMES = frozenset(["np", "_np", "numpy", "onp"])

_LOCKISH = re.compile(r"(?i)(^|_)(lock|cond|mutex|mu|glock|sched_lock)$")
_THREADISH = re.compile(r"(?i)(thread|proc|worker)")
_QUEUEISH = re.compile(r"(?i)(queue|^_?q$)")


def _dotted(node):
    """'a.b.c' for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last_seg(name):
    return name.rsplit(".", 1)[-1] if name else ""


def _is_constish(node):
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp) and isinstance(node.operand,
                                                    ast.Constant):
        return True
    return False


def _collect_traced_names(tree):
    """Names of functions passed (possibly through one wrapping call) to a
    trace entry point anywhere in the module."""
    traced = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _last_seg(_dotted(node.func))
        # partial(jax.jit, ...) / functools.partial(jax.custom_vjp, ...)
        if callee in ("partial", "_partial") and node.args:
            inner = _last_seg(_dotted(node.args[0]))
            if inner in _TRACE_ENTRY:
                for a in node.args[1:]:
                    if isinstance(a, ast.Name):
                        traced.add(a.id)
            continue
        if callee not in _TRACE_ENTRY:
            continue
        for a in node.args:
            if isinstance(a, ast.Name):
                traced.add(a.id)
            elif isinstance(a, ast.Lambda):
                pass  # lambdas are checked via context inheritance
            elif isinstance(a, ast.Call):
                # one unwrap level: jax.vjp(mirror_wrap(f), ...)
                for b in a.args:
                    if isinstance(b, ast.Name):
                        traced.add(b.id)
    return traced


def _decorated_traced(fn):
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = _last_seg(_dotted(target))
        if name in _TRACE_DECOR:
            return True
        if isinstance(dec, ast.Call) and name in ("partial", "_partial") \
                and dec.args:
            if _last_seg(_dotted(dec.args[0])) in _TRACE_ENTRY:
                return True
    return False


# -- jit-wrapper registries (static/donate argnums) ---------------------------

def _int_elems(node):
    """Literal int or tuple/list of ints -> list of ints (else [])."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return out
    return []


def _str_elems(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def _collect_jit_wrappers(tree):
    """Map assigned-name -> {'static': [pos...], 'static_names': [...],
    'donate': [pos...]} for ``x = jax.jit(f, static_argnums=..,
    donate_argnums=..)`` bindings (incl. ``self._x = ...``)."""
    out = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        call = node.value
        if not isinstance(call, ast.Call):
            continue
        if _last_seg(_dotted(call.func)) not in ("jit", "pjit"):
            continue
        info = {"static": [], "static_names": [], "donate": []}
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                info["static"] = _int_elems(kw.value)
            elif kw.arg == "static_argnames":
                info["static_names"] = _str_elems(kw.value)
            elif kw.arg == "donate_argnums":
                info["donate"] = _int_elems(kw.value)
        if not (info["static"] or info["static_names"] or info["donate"]):
            continue
        tname = _dotted(node.targets[0])
        if tname:
            out[tname] = info
    return out


# -- the per-module visitor ---------------------------------------------------

class _Ctx:
    __slots__ = ("fn", "qualname", "traced", "params")

    def __init__(self, fn, qualname, traced, params):
        self.fn = fn
        self.qualname = qualname
        self.traced = traced
        self.params = params


class ModuleLinter(ast.NodeVisitor):
    """One file's worth of rule checks; lock-order edges are handed to the
    cross-file :class:`LockOrderCollector` by the runner."""

    def __init__(self, path, tree, src, lock_collector=None,
                 enabled=None):
        self.path = path
        self.tree = tree
        self.src = src
        self.diags = []
        self.enabled = enabled  # None = all
        self._traced_names = _collect_traced_names(tree)
        self._wrappers = _collect_jit_wrappers(tree)
        self._ctx = []          # stack of _Ctx
        self._class = []        # stack of class names
        self._locks_held = []   # stack of (token, node) while visiting
        self._lock_collector = lock_collector
        self._loop_syncs = []   # per-loop: list of (node, expr_src)

    # -- helpers --
    def _emit(self, rule_id, node, message):
        if self.enabled is not None and rule_id not in self.enabled:
            return
        r = RULES[rule_id]
        sym = self._ctx[-1].qualname if self._ctx else "<module>"
        self.diags.append(Diagnostic(
            rule_id, self.path, getattr(node, "lineno", 1),
            getattr(node, "col_offset", 0), r.severity, message,
            hint=r.hint, symbol=sym))

    def _in_traced(self):
        return bool(self._ctx) and self._ctx[-1].traced

    def _traced_params(self):
        for c in reversed(self._ctx):
            if c.traced:
                return c.params
        return frozenset()

    def _lock_token(self, expr):
        name = _dotted(expr)
        if not name:
            return None
        if not _LOCKISH.search(_last_seg(name)):
            return None
        # canonicalize self._lock -> <Class>._lock so the same lock object
        # matches across methods (and files, for shared class names)
        if name.startswith("self.") and self._class:
            return "%s.%s" % (self._class[-1], name[5:])
        return name

    # -- scope tracking --
    def visit_ClassDef(self, node):
        self._class.append(node.name)
        self.generic_visit(node)
        self._class.pop()

    def _visit_fn(self, node):
        traced = (_decorated_traced(node)
                  or node.name in self._traced_names
                  or self._in_traced())
        args = node.args
        params = set(
            a.arg for a in (args.posonlyargs + args.args + args.kwonlyargs)
            if a.arg not in ("self", "cls"))
        outer = ".".join(c.qualname for c in self._ctx[-1:])
        qual = node.name if not self._ctx else "%s.%s" % (outer, node.name)
        if self._class and not self._ctx:
            qual = "%s.%s" % (self._class[-1], node.name)
        self._ctx.append(_Ctx(node, qual, traced, params))
        self._check_donation(node)
        self.generic_visit(node)
        self._ctx.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    # -- MXL101 / MXL102 / MXL103: host sync --------------------------------
    def visit_Call(self, node):
        callee = _dotted(node.func)
        last = _last_seg(callee)
        traced = self._in_traced()

        if traced:
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _HOST_SYNC_ATTRS:
                self._emit("MXL101", node,
                           ".%s() inside a traced body is a forced host "
                           "sync (or a trace-time error)" % node.func.attr)
            elif last == "device_get":
                self._emit("MXL101", node,
                           "jax.device_get inside a traced body is a "
                           "forced device->host transfer")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("asarray", "array") \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id in _NP_NAMES \
                    and node.args and not _is_constish(node.args[0]):
                self._emit("MXL101", node,
                           "np.%s on a traced value materializes it on "
                           "host inside the traced body (use jnp.%s)"
                           % (node.func.attr, node.func.attr))
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in ("float", "int", "bool") \
                    and node.args and not _is_constish(node.args[0]):
                self._emit("MXL102", node,
                           "%s() on a non-constant inside a traced body "
                           "concretizes a traced value" % node.func.id)
            elif isinstance(node.func, ast.Name) and node.func.id == "str" \
                    and node.args and not _is_constish(node.args[0]) \
                    and self._refs_traced_param(node.args[0]):
                self._emit("MXL202", node,
                           "str() of a traced value concretizes it at "
                           "trace time")

        # MXL103 bookkeeping: host fetches inside the innermost loop
        if self._loop_syncs:
            is_fetch = (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "asnumpy") \
                or last == "device_get"
            if is_fetch and not traced:
                try:
                    expr = ast.unparse(node)
                except Exception:
                    expr = "<fetch>"
                self._loop_syncs[-1].append((node, expr))

        # MXL203: unhashable literal passed in a static arg slot
        info = self._wrappers.get(callee) if callee else None
        if info:
            for pos in info["static"]:
                if pos < len(node.args) and isinstance(
                        node.args[pos], (ast.List, ast.Dict, ast.Set)):
                    self._emit("MXL203", node.args[pos],
                               "unhashable %s literal passed as static "
                               "arg %d of %s"
                               % (type(node.args[pos]).__name__.lower(),
                                  pos, callee))
            for kw in node.keywords:
                if kw.arg in info["static_names"] and isinstance(
                        kw.value, (ast.List, ast.Dict, ast.Set)):
                    self._emit("MXL203", kw.value,
                               "unhashable %s literal passed as static "
                               "arg %r of %s"
                               % (type(kw.value).__name__.lower(),
                                  kw.arg, callee))

        # MXL401: blocking call while a lock is held
        if self._locks_held:
            self._check_blocking(node, callee, last)

        # MXL506: metric series published around the telemetry registry.
        # Only slash-named series (the registry's namespace convention)
        # are claimed; the registry's own trace mirror is the one place
        # allowed to call through.
        if last == "record_counter" and callee and "profiler" in callee \
                and "telemetry" not in self.path.replace("\\", "/") \
                and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str) \
                and "/" in node.args[0].value:
            self._emit("MXL506", node,
                       "profiler.record_counter(%r) bypasses the "
                       "telemetry registry that owns slash-named series"
                       % node.args[0].value)

        self.generic_visit(node)

    def _check_blocking(self, node, callee, last):
        blocking = None
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            vname = _last_seg(_dotted(node.func.value) or "")
            if attr in ("asnumpy", "block_until_ready", "result"):
                blocking = ".%s()" % attr
            elif attr == "join" and _THREADISH.search(vname or ""):
                blocking = "%s.join()" % vname
            elif attr in ("put", "get") and _QUEUEISH.search(vname or ""):
                nowait = any(kw.arg == "block" and isinstance(
                    kw.value, ast.Constant) and kw.value.value is False
                    for kw in node.keywords)
                if not nowait:
                    blocking = "queue.%s()" % attr
            elif attr == "sleep" and vname == "time":
                blocking = "time.sleep()"
        if last == "device_get":
            blocking = "jax.device_get"
        if blocking:
            held = ", ".join(t for t, _ in self._locks_held)
            self._emit("MXL401", node,
                       "%s while holding %s blocks every thread "
                       "contending that lock" % (blocking, held))

    # taint propagation: a local assigned from a traced value is traced too
    def visit_Assign(self, node):
        if self._in_traced() and self._refs_traced_param(node.value):
            ctx = next(c for c in reversed(self._ctx) if c.traced)
            for tgt in node.targets:
                for n in ast.walk(tgt):
                    if isinstance(n, ast.Name):
                        ctx.params.add(n.id)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        if self._in_traced() and isinstance(node.target, ast.Name) \
                and self._refs_traced_param(node.value):
            ctx = next(c for c in reversed(self._ctx) if c.traced)
            ctx.params.add(node.target.id)
        self.generic_visit(node)

    # -- MXL201 / MXL202: retrace hazards -----------------------------------
    def _refs_traced_param(self, expr):
        """True if ``expr`` reads a traced-function parameter in a way
        that needs its VALUE (not just static metadata like .shape)."""
        params = self._traced_params()
        if not params:
            return False

        def walk(node, shadow=frozenset(), extra=frozenset()):
            if isinstance(node, ast.Attribute):
                if node.attr in _STATIC_ATTRS:
                    return False        # x.shape etc: static under jit
                return walk(node.value, shadow, extra)
            if isinstance(node, ast.Call):
                name = _last_seg(_dotted(node.func))
                if name in _SAFE_CALLS:
                    return False
                recv = walk(node.func, shadow, extra) \
                    if isinstance(node.func, ast.Attribute) else False
                return recv \
                    or any(walk(a, shadow, extra) for a in node.args) \
                    or any(walk(kw.value, shadow, extra)
                           for kw in node.keywords)
            if isinstance(node, ast.Compare):
                if all(isinstance(op, (ast.Is, ast.IsNot))
                       for op in node.ops):
                    return False        # `x is None` is a static check
                return any(walk(c, shadow, extra) for c in
                           [node.left] + list(node.comparators))
            if isinstance(node, (ast.ListComp, ast.SetComp,
                                 ast.GeneratorExp, ast.DictComp)):
                # dict .keys()/.items() enumerate the STATIC structure of
                # a pytree: the key loop-var is never traced; the items()
                # VALUE loop-var is traced iff the dict itself is
                shadow, extra = set(shadow), set(extra)
                for gen in node.generators:
                    itr = gen.iter
                    itname = _last_seg(_dotted(itr.func)) \
                        if isinstance(itr, ast.Call) else None
                    tgt = gen.target
                    if itname == "keys":
                        shadow.update(n.id for n in ast.walk(tgt)
                                      if isinstance(n, ast.Name))
                    elif itname == "items" and isinstance(tgt, ast.Tuple) \
                            and len(tgt.elts) == 2 \
                            and isinstance(tgt.elts[0], ast.Name):
                        shadow.add(tgt.elts[0].id)
                        if isinstance(tgt.elts[1], ast.Name) \
                                and walk(itr.func.value, shadow, extra):
                            extra.add(tgt.elts[1].id)
                    elif walk(itr, shadow, extra):
                        return True
                parts = ([node.key, node.value]
                         if isinstance(node, ast.DictComp) else [node.elt])
                parts.extend(i for gen in node.generators
                             for i in gen.ifs)
                return any(walk(p, shadow, extra) for p in parts)
            if isinstance(node, ast.Name):
                return (node.id in params or node.id in extra) \
                    and node.id not in shadow
            return any(walk(c, shadow, extra)
                       for c in ast.iter_child_nodes(node))

        return walk(expr)

    def _check_branch(self, node):
        if self._in_traced() and self._refs_traced_param(node.test):
            self._emit("MXL201", node,
                       "python %s on a traced value forces concretization "
                       "(crash) or a per-value retrace"
                       % type(node).__name__.lower())

    def visit_If(self, node):
        self._check_branch(node)
        self.generic_visit(node)

    def visit_While(self, node):
        self._check_branch(node)
        self._visit_loop_body(node)

    def visit_IfExp(self, node):
        if self._in_traced() and self._refs_traced_param(node.test):
            self._emit("MXL201", node,
                       "conditional expression on a traced value forces "
                       "concretization; use jnp.where")
        self.generic_visit(node)

    def visit_JoinedStr(self, node):
        if self._in_traced():
            for v in node.values:
                if isinstance(v, ast.FormattedValue) \
                        and self._refs_traced_param(v.value):
                    self._emit("MXL202", node,
                               "f-string interpolates a traced value "
                               "(concretizes at trace time)")
                    break
        self.generic_visit(node)

    def visit_BinOp(self, node):
        if self._in_traced() and isinstance(node.op, ast.Mod) \
                and isinstance(node.left, ast.Constant) \
                and isinstance(node.left.value, str) \
                and self._refs_traced_param(node.right):
            self._emit("MXL202", node,
                       "%%-formatting a traced value concretizes it at "
                       "trace time")
        self.generic_visit(node)

    # -- MXL103: loop-body fetch batching -----------------------------------
    def _visit_loop_body(self, node):
        self._loop_syncs.append([])
        self.generic_visit(node)
        syncs = self._loop_syncs.pop()
        if len(syncs) >= 2:
            first = syncs[0][0]
            self._emit("MXL103", first,
                       "%d separate host fetches per loop iteration "
                       "(%s); batch them into one device_get"
                       % (len(syncs),
                          ", ".join(s for _, s in syncs[:4])))

    def visit_For(self, node):
        self._visit_loop_body(node)

    visit_AsyncFor = visit_For

    # -- MXL301: donation misuse --------------------------------------------
    def _donate_info(self, call):
        name = _dotted(call.func)
        if not name:
            return None, None
        info = self._wrappers.get(name)
        if info and info["donate"]:
            return name, info["donate"]
        return None, None

    def _check_donation(self, fn):
        """Linear scan of ``fn``'s body: a Load of a name after it was
        passed in a donated position (without an intervening rebind) is a
        use-after-free."""
        donated = {}   # name -> (call_node, wrapper_name)

        def loads(expr, skip_call=None):
            for n in ast.walk(expr):
                if n is skip_call:
                    continue
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                    yield n

        def handle_value(expr):
            # 1) flag loads of already-dead names
            for n in loads(expr):
                if n.id in donated:
                    call, wname = donated[n.id]
                    self._emit("MXL301", n,
                               "'%s' was donated to %s (line %d) and is "
                               "dead; reading it is use-after-free"
                               % (n.id, wname, call.lineno))
                    donated.pop(n.id, None)   # report once per donation
            # 2) register fresh donations from calls in this expr
            for n in ast.walk(expr):
                if isinstance(n, ast.Call):
                    wname, positions = self._donate_info(n)
                    if not wname:
                        continue
                    for pos in positions:
                        if pos < len(n.args) and isinstance(
                                n.args[pos], ast.Name):
                            donated[n.args[pos].id] = (n, wname)

        def scan(stmts):
            for st in stmts:
                if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                    continue
                for expr in _stmt_exprs(st):
                    handle_value(expr)
                for tgt in _stmt_targets(st):
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            donated.pop(n.id, None)
                for body in _stmt_bodies(st):
                    scan(body)

        scan(fn.body)

    # -- MXL401/402: with-statement lock tracking ---------------------------
    def visit_With(self, node):
        tokens = []
        for item in node.items:
            tok = self._lock_token(item.context_expr)
            if tok:
                if self._lock_collector is not None:
                    for held, hnode in self._locks_held:
                        self._lock_collector.edge(
                            held, tok, self.path, node,
                            self._ctx[-1].qualname if self._ctx
                            else "<module>")
                self._locks_held.append((tok, node))
                tokens.append(tok)
        self.generic_visit(node)
        for _ in tokens:
            self._locks_held.pop()

    visit_AsyncWith = visit_With


def _stmt_exprs(st):
    """The value-expressions of one statement (evaluated parts only)."""
    out = []
    for field in ("value", "test", "iter", "exc", "msg"):
        v = getattr(st, field, None)
        if isinstance(v, ast.expr):
            out.append(v)
    if isinstance(st, ast.With):
        out.extend(i.context_expr for i in st.items)
    return out


def _stmt_targets(st):
    if isinstance(st, ast.Assign):
        return st.targets
    if isinstance(st, (ast.AugAssign, ast.AnnAssign, ast.For)):
        return [st.target]
    return []


def _stmt_bodies(st):
    out = []
    for field in ("body", "orelse", "finalbody"):
        v = getattr(st, field, None)
        if isinstance(v, list):
            out.append(v)
    for h in getattr(st, "handlers", []) or []:
        out.append(h.body)
    return out


class LockOrderCollector:
    """Cross-file lock acquisition-order graph (MXL402).

    ``edge(A, B)`` records "B acquired while A held" with its site; after
    every file is visited, :meth:`diagnostics` reports each pair seen in
    BOTH orders — one diagnostic per direction, at the first site seen.
    """

    def __init__(self):
        self._edges = {}   # (A, B) -> (path, line, col, symbol)

    def edge(self, held, inner, path, node, symbol):
        key = (held, inner)
        if key not in self._edges:
            self._edges[key] = (path, node.lineno, node.col_offset, symbol)

    def diagnostics(self, enabled=None):
        if enabled is not None and "MXL402" not in enabled:
            return []
        out = []
        for (a, b), (path, line, col, sym) in sorted(self._edges.items()):
            if a >= b or (b, a) not in self._edges:
                continue
            r = RULES["MXL402"]
            for (x, y) in ((a, b), (b, a)):
                p, ln, c, s = self._edges[(x, y)]
                d = Diagnostic("MXL402", p, ln, c, r.severity,
                               "lock order conflict: %s -> %s here, but "
                               "%s -> %s elsewhere" % (x, y, y, x),
                               hint=r.hint, symbol=s)
                out.append(d)
        return out


def analyze_module(path, src, lock_collector=None, enabled=None,
                   tree=None):
    """Lint one file's source. Returns a list of Diagnostics (lock-order
    findings come later, from the shared collector). ``tree`` lets the
    runner parse once and share the AST with the Layer-3 passes."""
    if tree is None:
        tree = ast.parse(src, filename=path)
    linter = ModuleLinter(path, tree, src, lock_collector=lock_collector,
                          enabled=enabled)
    linter.visit(tree)
    return linter.diags
