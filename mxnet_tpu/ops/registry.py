"""Central operator registry.

The reference keeps a single NNVM registry consumed by both the imperative
runtime and the symbolic executor (SURVEY.md §1; reference:
include/mxnet/op_attr_types.h, src/operator/nn/fully_connected.cc:239-326 for
the registration pattern). We keep that key design point — one registry, two
front-ends — but each op is a **pure JAX function**:

* gradients come from ``jax.vjp`` (no hand-written FGradient),
* shape/type inference comes from ``jax.eval_shape`` (no FInferShape),
* CPU/TPU portability comes from XLA (no per-device kernels),
* fusion/memory planning come from ``jax.jit`` (no PlanMemory pass).

Op functions take positional array arguments followed by keyword hyper
parameters and return one array or a tuple of arrays. Ops that need
randomness draw keys via :mod:`mxnet_tpu.random` (stateful facade; traced
graphs thread an explicit key input).
"""
from __future__ import annotations

import contextlib
import functools
import inspect
import threading

from jax.ad_checkpoint import checkpoint_name

__all__ = ["Operator", "register", "get", "list_ops", "alias",
           "STAGE_KEEP", "stage_keep", "stage_marks", "PROGRAM_GAUGES",
           "program_gauge", "program_count", "program_max",
           "program_counts"]

_REGISTRY: dict[str, "Operator"] = {}

# ------------------------------------------------------- what a stage keeps
# The one name under which an op marks a value that its backward pass reads
# and that is dear to recompute. A ``mirror_stage`` of the executor keeps
# the values so marked and recomputes the rest of its interior; anywhere
# else the mark is an identity.
STAGE_KEEP = "mx_stage_keep"
_marks = threading.local()


def stage_keep(x):
    """``x``, marked for the stage around it to keep. Call it in the
    forward rule of a ``jax.custom_vjp`` on what goes into the residuals,
    before it goes there and out, so that the kept value and the residual
    are one array."""
    kept = getattr(_marks, "kept", None)
    if kept is not None:
        kept.append(x.size * x.dtype.itemsize)
    return checkpoint_name(x, STAGE_KEEP)


@contextlib.contextmanager
def stage_marks(kept):
    """While a stage is traced and differentiated: the bytes of every
    value marked inside it are appended to ``kept``."""
    prev = getattr(_marks, "kept", None)
    _marks.kept = kept
    try:
        yield
    finally:
        _marks.kept = prev


# --------------------------------------------- what a program was built of
# Gauges fed by a choice an op makes while it is traced (which of two
# implementations a shape selects): name -> help. When a training program is
# traced the executor sets each to the number of times the program's ops
# called ``program_count(name)``, 0 where none did.
PROGRAM_GAUGES: dict[str, str] = {}


def program_gauge(name, help):
    PROGRAM_GAUGES[name] = help


def program_count(name, n=1):
    """``n`` more of ``name`` in the program being traced; nothing outside
    the trace of one."""
    counts = getattr(_marks, "counts", None)
    if counts is not None:
        counts[name] = counts.get(name, 0) + n


def program_max(name, n):
    """``name`` at least ``n`` in the program being traced: for what a
    program has one of however many of its ops report it."""
    counts = getattr(_marks, "counts", None)
    if counts is not None:
        counts[name] = max(counts.get(name, 0), n)


@contextlib.contextmanager
def program_counts(counts):
    """While a training program is traced: ``program_count`` adds into the
    dict ``counts``."""
    prev = getattr(_marks, "counts", None)
    _marks.counts = counts
    try:
        yield
    finally:
        _marks.counts = prev


class Operator:
    """A registered op: a pure jax fn + metadata for the two front-ends."""

    __slots__ = ("name", "fn", "num_outputs", "param_names", "is_random",
                 "doc", "shape_hook", "dtype_hook", "aux_inputs",
                 "aux_outputs", "num_visible_outputs", "input_names",
                 "input_optional", "has_var_inputs", "f32_inputs",
                 "index_inputs", "counters")

    def __init__(self, name, fn, num_outputs=1, is_random=False):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs  # int, or callable(params)->int
        self.is_random = is_random
        self.doc = fn.__doc__ or ""
        # symbolic-layer metadata (set via set_op_meta):
        self.shape_hook = None        # fn(in_shapes, params) -> completed in_shapes
        self.dtype_hook = None        # fn(in_dtypes, params) -> (in_dtypes, out_dtypes)
        self.aux_inputs = ()          # input slots that are auxiliary states
        self.aux_outputs = ()         # output slots holding updated aux values
        self.num_visible_outputs = None  # outputs exposed to the graph (prefix)
        # what a low-precision compute policy must leave alone (fused.py):
        self.f32_inputs = ()          # parameter slots the op reads as float32
        self.index_inputs = ()        # slots read as indices (ids, labels)
        # auxiliary states that count on the device: ((input slot, ((gauge,
        # help), ...)), ...) for a state [steps, a step's mean of each
        # gauge]; Module publishes them where the host waits anyway
        self.counters = ()
        sig = inspect.signature(fn)
        self.param_names = [
            p.name for p in sig.parameters.values()
            if p.kind == inspect.Parameter.KEYWORD_ONLY
        ]
        # positional (array) inputs: name -> has_default
        self.input_names = []
        self.input_optional = []
        self.has_var_inputs = False
        for p in sig.parameters.values():
            if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                          inspect.Parameter.POSITIONAL_ONLY):
                self.input_names.append(p.name)
                self.input_optional.append(p.default is not inspect.Parameter.empty)
            elif p.kind == inspect.Parameter.VAR_POSITIONAL:
                self.has_var_inputs = True

    def bind_positional(self, args, kwargs):
        """Split positional call args into (input_args, kwargs): anything
        past the declared tensor-input slots binds to param_names in
        declaration order — the reference's generated-signature contract
        (mx.nd.reshape(x, (3, 2)), mx.nd.sum(x, 1)). Variadic-input ops
        treat every positional as an input."""
        if self.has_var_inputs or len(args) <= len(self.input_names):
            return args, kwargs
        extra = args[len(self.input_names):]
        if len(extra) > len(self.param_names):
            raise TypeError("%s: too many positional arguments" % self.name)
        for pname, val in zip(self.param_names, extra):
            if pname in kwargs:
                raise TypeError("%s: parameter %r given positionally and "
                                "by keyword" % (self.name, pname))
            kwargs[pname] = val
        return args[:len(self.input_names)], kwargs

    def resolve_num_outputs(self, params):
        if callable(self.num_outputs):
            return self.num_outputs(params)
        return self.num_outputs

    def resolve_num_visible_outputs(self, params):
        """Outputs exposed to the graph (reference FNumVisibleOutputs);
        the hidden suffix carries updated aux state."""
        if self.num_visible_outputs is None:
            return self.resolve_num_outputs(params)
        if callable(self.num_visible_outputs):
            return self.num_visible_outputs(params)
        return self.num_visible_outputs

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)

    def __repr__(self):
        return "Operator(%s)" % self.name


def register(name=None, num_outputs=1, is_random=False):
    """Decorator: register a pure jax function as an operator."""
    def deco(fn):
        opname = name or fn.__name__
        op = Operator(opname, fn, num_outputs=num_outputs, is_random=is_random)
        if opname in _REGISTRY:
            raise ValueError("duplicate op registration: %s" % opname)
        _REGISTRY[opname] = op
        return fn
    return deco


def set_op_meta(name, shape_hook=None, dtype_hook=None, aux_inputs=None,
                aux_outputs=None, num_visible_outputs=None, f32_inputs=None,
                index_inputs=None, counters=None):
    """Attach symbolic-layer metadata (parameter-shape/dtype inference
    hooks and auxiliary-state slots — the reference's FInferShape /
    FInferType / aux_states)."""
    op = _REGISTRY[name]
    if shape_hook is not None:
        op.shape_hook = shape_hook
    if dtype_hook is not None:
        op.dtype_hook = dtype_hook
    if aux_inputs is not None:
        op.aux_inputs = tuple(aux_inputs)
    if aux_outputs is not None:
        op.aux_outputs = tuple(aux_outputs)
    if num_visible_outputs is not None:
        op.num_visible_outputs = num_visible_outputs
    if f32_inputs is not None:
        op.f32_inputs = tuple(f32_inputs)
    if index_inputs is not None:
        op.index_inputs = tuple(index_inputs)
    if counters is not None:
        op.counters = tuple(counters)
    return op


def alias(existing, *names):
    op = _REGISTRY[existing]
    for n in names:
        _REGISTRY[n] = op
    return op


def get(name) -> Operator:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError("operator %r is not registered (have %d ops)"
                       % (name, len(_REGISTRY)))


def get_or_none(name):
    return _REGISTRY.get(name)


def list_ops():
    return sorted(_REGISTRY.keys())


def namespaced_surface(module_globals, make_fn, resolve, listing=None):
    """Generic generated-namespace machinery (mx.nd.op / mx.nd.image /
    mx.sym.random ... — reference code-generated namespace modules):
    returns (__getattr__, __dir__) where ``resolve(attr)`` maps the
    attribute to a registry op name (or None -> AttributeError) and
    ``listing()`` yields the dir() names."""
    def __getattr__(name):
        opname = resolve(name)
        op = get_or_none(opname) if opname else None
        if op is None:
            raise AttributeError(
                "%s has no attribute %r" % (module_globals.get(
                    "__name__", "<namespace>"), name))
        fn = make_fn(op)
        fn.__name__ = name
        module_globals[name] = fn   # cache for the next lookup
        return fn

    def __dir__():
        extra = list(listing()) if listing else []
        return sorted(set(list(module_globals) + extra))

    return __getattr__, __dir__


def contrib_surface(module_globals, make_fn):
    """mx.nd.contrib / mx.sym.contrib namespaces: ``name`` resolves to
    the registered ``_contrib_<name>`` operator."""
    return namespaced_surface(
        module_globals, make_fn,
        resolve=lambda n: "_contrib_" + n,
        listing=lambda: [n[len("_contrib_"):] for n in list_ops()
                         if n.startswith("_contrib_")])
