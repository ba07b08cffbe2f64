"""Gluon Block / HybridBlock.

Parity surface: ``python/mxnet/gluon/block.py`` — `Block` (define-by-run),
`HybridBlock.hybridize()` (reference :504/:832 builds a `CachedOp` from a
Symbol trace, :748-785), `SymbolBlock`, name scoping, parameter management,
save/load.

TPU-native design: ``hybridize()`` does NOT build a symbol graph — it traces
the block's Python forward with **jax arrays** and compiles the whole thing
with ``jax.jit`` (one XLA module per input signature — the endgame the
reference approximates with CachedOp + static_alloc + bulking, SURVEY.md §7).
The ``hybrid_forward(F, ...)`` contract is kept: eager calls get
``F = mxnet_tpu.ndarray``; traced calls get an F namespace whose ops operate
on raw jax arrays straight from the op registry; symbolic export gets
``F = mxnet_tpu.symbol``. Autograd through a cached graph records ONE tape
node whose vjp is the jit-compiled backward (CachedOp::Backward analog).
Deferred shape inference runs as a free ``jax.eval_shape`` probe instead of
a symbolic infer_shape pass.
"""
from __future__ import annotations

import re
import threading

import numpy as _np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .. import autograd as _autograd
from .. import random as _random
from ..ndarray import ndarray as _nd
from ..ops import registry as _registry
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


# ---------------------------------------------------------------------------
# Name scoping (reference block.py _BlockScope)
# ---------------------------------------------------------------------------

class _BlockScope:
    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """Create prefix and params for a new Block."""
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _name_manager().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *a):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


class _NameManager:
    def __init__(self):
        self._counter = {}

    def get(self, name, hint):
        if name is not None:
            return name
        count = self._counter.get(hint, 0)
        self._counter[hint] = count + 1
        return "%s%d" % (hint, count)


_global_name_manager = _NameManager()


def _name_manager():
    return _global_name_manager


# ---------------------------------------------------------------------------
# Traced-execution context: while jax-tracing a hybridized block, parameters
# resolve to tracers through this thread-local (the CachedOp input binding).
# ---------------------------------------------------------------------------

import itertools as _itertools

_trace_counter = _itertools.count(1)  # next() is atomic at the C level


class _TraceCtx:
    __slots__ = ("param_arrays", "tracer_names", "aux_updates", "training",
                 "seq")

    def __init__(self, param_arrays, training):
        self.param_arrays = param_arrays        # param full name -> tracer
        self.tracer_names = {id(v): k for k, v in param_arrays.items()}
        self.aux_updates = {}                   # param full name -> new value
        self.training = training
        self.seq = next(_trace_counter)         # unique per trace


_trace_state = threading.local()


def _current_trace():
    return getattr(_trace_state, "ctx", None)


class _trace_scope:
    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        self.prev = getattr(_trace_state, "ctx", None)
        _trace_state.ctx = self.ctx
        return self.ctx

    def __exit__(self, *a):
        _trace_state.ctx = self.prev


class _JaxF:
    """Op namespace for traced execution: registry ops on raw jax arrays.

    Mirrors the eager invoke path (ndarray.invoke) minus NDArray wrapping:
    aux-state updates (BatchNorm moving stats) are collected into the active
    trace context instead of rebinding arrays.
    """

    def __getattr__(self, name):
        if name in ("contrib", "linalg"):
            # sub-namespaces mirror the eager nd.contrib/nd.linalg
            # surfaces (reference F.contrib.* works under hybridize)
            return _JaxFSub(self, "_%s_" % name)
        return self._op_fn(name)

    def _op_fn(self, name):
        try:
            op = _registry.get(name)
        except KeyError:
            raise AttributeError(name)

        def fn(*args, name=None, **kwargs):
            arrs = [a for a in args if a is not None]
            kwargs.pop("ctx", None)
            params = {k: v for k, v in kwargs.items() if v is not None}
            tctx = _current_trace()
            training = tctx.training if tctx is not None \
                else _autograd.is_training()
            if "_training" in op.param_names and "_training" not in params:
                params["_training"] = training
            out = op.fn(*arrs, **params)
            outs = out if isinstance(out, tuple) else (out,)
            if op.aux_outputs:
                if training and tctx is not None:
                    for in_slot, out_slot in zip(op.aux_inputs,
                                                 op.aux_outputs):
                        if in_slot < len(arrs):
                            pname = tctx.tracer_names.get(id(arrs[in_slot]))
                            if pname is not None:
                                tctx.aux_updates[pname] = outs[out_slot]
                n_vis = op.resolve_num_visible_outputs(params)
                outs = outs[:n_vis]
            return outs[0] if len(outs) == 1 else outs

        fn.__name__ = name
        return fn

    def __repr__(self):
        return "<traced-F (jax)>"


class _JaxFSub:
    """F.contrib / F.linalg under traced execution: attribute X resolves
    to the registry op ``<prefix>X`` (e.g. _contrib_ROIAlign) — exact
    match only, mirroring the eager contrib_surface resolver so a name
    behaves identically eager and hybridized."""

    # functional contrib helpers with no registry op: control flow +
    # float predicates dispatch to the ndarray.contrib implementations,
    # which lower to lax.scan/while/cond on raw jax values — so
    # F.contrib.foreach works identically eager and hybridized
    _FUNCTIONAL = ("foreach", "while_loop", "cond", "isinf", "isnan",
                   "isfinite")

    def __init__(self, parent, prefix):
        self._parent = parent
        self._prefix = prefix

    def __getattr__(self, name):
        if self._prefix == "_contrib_" and name in self._FUNCTIONAL:
            from ..ndarray import contrib as _nd_contrib
            return getattr(_nd_contrib, name)
        return self._parent._op_fn(self._prefix + name)


_F_JAX = _JaxF()


def _is_jax_value(x):
    return isinstance(x, jax.Array) or hasattr(x, "aval")


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------

class Block:
    """Base class for all neural network layers and models
    (reference gluon/block.py:Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    def __repr__(self):
        if not self._children:
            return "%s()" % type(self).__name__
        modstr = "\n".join("  (%s): %s" % (key, _indent(repr(block), 2))
                           for key, block in self._children.items())
        return "%s(\n%s\n)" % (type(self).__name__, modstr)

    def __setattr__(self, name, value):
        if not name.startswith("_"):
            existing = self.__dict__.get(name)
            if isinstance(value, Block):
                if existing is not None and not isinstance(existing, Block):
                    raise TypeError(
                        "Changing attribute type for %s from %s to Block is "
                        "not allowed." % (name, type(existing)))
                self.register_child(value, name)
            elif isinstance(value, Parameter):
                assert name not in self._reg_params or \
                    self._reg_params[name] is value, \
                    "Overriding Parameter attribute %s is not allowed." % name
                self._reg_params[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        """All Parameters of this block and children, regex-filterable."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            from .. import initializer
            init = initializer.Uniform()
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self._reg_params.items():
            param.cast(dtype)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    # ------------------------------------------------------------- serialize
    def save_parameters(self, filename):
        params = self._collect_params_with_prefix()
        arg_dict = {key: val.data() for key, val in params.items()
                    if val._data is not None}
        _nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        loaded = _nd.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if loaded and not any("." in k for k in loaded.keys()):
            # fully-prefixed format (ParameterDict.save / export). Restore
            # the prefix only if the saved names were actually stripped.
            stripped = not any(k.split(":", 1)[-1].startswith(self.prefix)
                               for k in loaded.keys()) if self.prefix else False
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra,
                self.prefix if stripped else "")
            return
        if not allow_missing:
            for name in params.keys():
                if name not in loaded:
                    raise IOError("Parameter '%s' is missing in file '%s'"
                                  % (name, filename))
        for name in loaded:
            if name not in params:
                if not ignore_extra:
                    raise IOError(
                        "Parameter '%s' loaded from '%s' is not present in "
                        "the Block" % (name, filename))
                continue
            params[name].set_data(loaded[name])

    # deprecated aliases (the reference keeps both surfaces)
    save_params = save_parameters
    load_params = load_parameters

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # --------------------------------------------------------------- forward
    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary table (reference block.py summary)."""
        rows = []
        registered = []

        def _register(blk):
            def hook(block, ins, outs, _blk=blk):
                outs_ = outs if isinstance(outs, (list, tuple)) else [outs]
                n_params = sum(int(_np.prod(p.shape))
                               for p in block._reg_params.values()
                               if p.shape is not None)
                rows.append((block.name, type(block).__name__,
                             [tuple(o.shape) for o in outs_
                              if hasattr(o, "shape")], n_params))
            blk._forward_hooks.append(hook)
            registered.append((blk, hook))
        self.apply(_register)
        try:
            self(*inputs)
        finally:
            for blk, hook in registered:
                blk._forward_hooks.remove(hook)
        lines = ["%-30s %-20s %-28s %10s" % ("Layer", "Type", "Output Shape",
                                             "Params")]
        total = 0
        for name, typ, shapes, n in rows:
            total += n
            lines.append("%-30s %-20s %-28s %10d"
                         % (name, typ, ",".join(map(str, shapes)), n))
        lines.append("Total params: %d" % total)
        text = "\n".join(lines)
        print(text)
        return text


def _indent(s, num_spaces):
    lines = s.split("\n")
    if len(lines) == 1:
        return s
    first = lines.pop(0)
    return first + "\n" + "\n".join(" " * num_spaces + line for line in lines)


# ---------------------------------------------------------------------------
# HybridBlock
# ---------------------------------------------------------------------------

class HybridBlock(Block):
    """A Block whose forward can be jit-compiled (hybridized).

    Subclasses implement ``hybrid_forward(F, x, *args, **params)`` where F is
    the ndarray namespace (eager), a jax-level namespace (traced/compiled) or
    the symbol namespace (export), and params are this block's registered
    Parameters passed as arrays/symbols.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graph = {}     # signature -> compiled runner
        self._flags = {}

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = kwargs
        self._cached_graph = {}
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._cached_graph = {}
        super().cast(dtype)

    # ------------------------------------------------- deferred shape infer
    def _layer_infer_shape(self, *args):
        """Complete this layer's own deferred parameter shapes given input
        shapes. Library layers override; the default handles blocks whose
        own reg_params never defer (containers, user models)."""
        deferred = [p.name for p in self._reg_params.values()
                    if p._deferred_init is not None]
        if deferred:
            raise DeferredInitializationError(
                "%s cannot infer shapes of %s; override _layer_infer_shape "
                "or initialize with explicit shapes." % (self.name, deferred))

    def _maybe_infer_shape(self, *args):
        if any(p._deferred_init is not None
               for p in self._reg_params.values()):
            shapes = [tuple(a.shape) if hasattr(a, "shape") else a
                      for a in args]
            self._layer_infer_shape(*shapes)

    def infer_shape(self, *args):
        """Complete all deferred parameter shapes from example inputs by
        abstract-evaluating the forward (jax.eval_shape — zero FLOPs; the
        reference runs a symbolic infer_shape pass instead)."""
        from .parameter import shape_only_scope
        abstract = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype) if hasattr(a, "shape")
            else a, list(args))

        def probe(*xs):
            tctx = _TraceCtx({}, training=False)
            with _trace_scope(tctx):
                with _random.trace_scope(jax.random.PRNGKey(0)):
                    return self.forward(*xs)
        with shape_only_scope():
            jax.eval_shape(probe, *abstract)
        # shapes are now known: allocate for real, outside any trace
        for p in self.collect_params().values():
            if p._deferred_init is not None and p.shape is not None \
                    and all(s > 0 for s in p.shape):
                p._finish_deferred_init(p.shape)

    # --------------------------------------------------------------- forward
    def forward(self, x, *args):
        if _is_jax_value(x):
            # traced mode (inside jit/eval_shape): params become tracers
            self._maybe_infer_shape(x, *args)
            tctx = _current_trace()
            params = {}
            for name, param in self._reg_params.items():
                if tctx is not None and param.name in tctx.param_arrays:
                    params[name] = tctx.param_arrays[param.name]
                elif param._data is None and param._deferred_init is not None \
                        and param.shape is not None \
                        and all(s > 0 for s in param.shape):
                    # inside a shape-only probe: stand in with zeros
                    params[name] = jnp.zeros(param.shape, param.dtype)
                else:
                    params[name] = param.data()._data
            return self.hybrid_forward(_F_JAX, x, *args, **params)
        if isinstance(x, _nd.NDArray):
            if self._active:
                return self._call_cached(x, *args)
            self._maybe_infer_shape(x, *args)
            try:
                params = {name: param.data()
                          for name, param in self._reg_params.items()}
            except DeferredInitializationError:
                self.infer_shape(x, *args)
                params = {name: param.data()
                          for name, param in self._reg_params.items()}
            from .. import ndarray as F
            return self.hybrid_forward(F, x, *args, **params)
        from ..symbol.symbol import Symbol
        if isinstance(x, Symbol):
            from .. import symbol as F
            params = {name: param.var()
                      for name, param in self._reg_params.items()}
            return self.hybrid_forward(F, x, *args, **params)
        raise TypeError("HybridBlock input must be NDArray, Symbol or jax "
                        "array, got %s" % type(x))

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # ------------------------------------------------------------ cached op
    def _call_cached(self, *args):
        """Hybridized execution: one jitted XLA module per input signature
        (CachedOp analog, reference cached_op.h:72 DynamicForward →
        shape-keyed compile cache, SURVEY.md §7 hard-part 1)."""
        try:
            for p in self.collect_params().values():
                p._check_initialized()
        except DeferredInitializationError:
            self.infer_shape(*args)

        # args may be a pytree mixing NDArrays with lists/statics (e.g. a
        # recurrent cell stepped with a state list)
        leaves, treedef = jax.tree_util.tree_flatten(list(args))
        training = _autograd.is_training()
        from .. import config as _config
        # the kernel tier changes what a trace lowers to (Pallas custom
        # calls vs pure JAX), and so does the tuning cache feeding it —
        # both join the signature so flipping MXNET_KERNEL_TIER or
        # re-tuning invalidates cached runners instead of silently
        # serving stale programs
        from ..kernels import tier as _ktier
        ktier = _ktier.tier()
        if ktier != "off":
            from ..tune import cache as _tcache
            ktier = "%s/%s" % (ktier, _tcache.get_default().fingerprint())
        sig = (treedef,
               tuple((a.shape, str(a.dtype)) if isinstance(a, _nd.NDArray)
                     else ("static", repr(a)) for a in leaves), training,
               str(_config.compute_dtype(default=None)), ktier)
        runner = self._cached_graph.get(sig)
        if runner is None:
            runner = self._build_cache(treedef, leaves, training)
            self._cached_graph[sig] = runner
        return runner(leaves)

    def _build_cache(self, treedef, ex_leaves, training):
        block = self
        # param binding order is fixed at build time
        params = [p for p in self.collect_params().values()
                  if p._data is not None]
        param_names = [p.name for p in params]
        static_leaves = [None if isinstance(a, _nd.NDArray) else a
                         for a in ex_leaves]
        # session dtype policy (config.compute_dtype): cast f32 params and
        # inputs to the compute dtype INSIDE the traced program, so the
        # hybridized path gets the same mixed-precision semantics as the
        # fused Module step: each param cast at its own shape. Params
        # flagged _keep_f32 (BN affine/stats) are exempt.
        from .. import config as _config
        cdt = _config.compute_dtype(default=None)
        keep_idx = frozenset(i for i, p in enumerate(params)
                             if getattr(p, "_keep_f32", False))

        def traced(param_arrays, in_arrays, key):
            if cdt is not None:
                param_arrays = [
                    a.astype(cdt) if a.dtype == jnp.float32
                    and i not in keep_idx and a.size > 0 else a
                    for i, a in enumerate(param_arrays)]
                in_arrays = [a.astype(cdt) if a.dtype == jnp.float32 else a
                             for a in in_arrays]
            tctx = _TraceCtx(dict(zip(param_names, param_arrays)), training)
            with _trace_scope(tctx):
                with _random.trace_scope(key):
                    it = iter(in_arrays)
                    call_leaves = [next(it) if s is None else s
                                   for s in static_leaves]
                    call_args = jax.tree_util.tree_unflatten(
                        treedef, call_leaves)
                    out = block.hybrid_forward_entry(*call_args)
            return out, tctx.aux_updates  # out may be any pytree

        jitted = jax.jit(traced)
        tree = jax.tree_util

        def run(leaves):
            param_arrays = [p._data._data for p in params]
            in_nds = [a for a in leaves if isinstance(a, _nd.NDArray)]
            in_arrays = [a._data for a in in_nds]
            key = _random.next_key()

            recording = (_autograd.is_recording()
                         and (any(p._data._ag is not None for p in params)
                              or any(a._ag is not None for a in in_nds)))
            if not recording:
                out_pytree, aux = jitted(param_arrays, in_arrays, key)
                _apply_aux(params, param_names, aux)
                flat, out_td = tree.tree_flatten(out_pytree)
                return tree.tree_unflatten(
                    out_td, [_nd.NDArray(o) for o in flat])

            diff_idx = [i for i, p in enumerate(params)
                        if p.grad_req != "null"]

            def fwd(diff_params, diff_ins):
                pa = list(param_arrays)
                for i, v in zip(diff_idx, diff_params):
                    pa[i] = v
                return jitted(pa, diff_ins, key)

            diff_params = [param_arrays[i] for i in diff_idx]
            from ..executor import mirror_wrap
            (out_pytree, aux), vjp = jax.vjp(mirror_wrap(fwd), diff_params,
                                             in_arrays)
            _apply_aux(params, param_names, aux)
            flat, out_td = tree.tree_flatten(out_pytree)
            out_nds = [_nd.NDArray(o) for o in flat]
            tape_inputs = [params[i]._data for i in diff_idx] + in_nds
            zero_aux = tree.tree_map(jnp.zeros_like, aux)

            def tape_vjp(cot):
                cots = list(cot) if isinstance(cot, tuple) else [cot]
                dp, di = vjp((tree.tree_unflatten(out_td, cots), zero_aux))
                return list(dp) + list(di)

            _autograd.record_op(tape_vjp, tape_inputs, out_nds,
                                name="CachedOp(%s)" % block.name)
            return tree.tree_unflatten(out_td, out_nds)

        def profiled_run(leaves):
            from .. import profiler as _profiler
            if not _profiler.is_active("symbolic"):
                return run(leaves)
            with _profiler.op_timer("CachedOp(%s)" % block.name,
                                    "cached_op"):
                out = run(leaves)
                for o in tree.tree_leaves(out):
                    if isinstance(o, _nd.NDArray):
                        o.wait_to_read()
            return out

        return profiled_run

    def hybrid_forward_entry(self, *args):
        """Entry point for tracing: dispatch through forward() so the whole
        child tree runs in traced mode."""
        return self.forward(*args)

    # ---------------------------------------------------------------- export
    def export(self, path, epoch=0):
        """Export to symbol JSON + params (reference block.py export)."""
        from .. import symbol as _sym
        data = _sym.Variable("data")
        with _autograd.pause():
            out = self(data)
        if isinstance(out, (list, tuple)):
            out = _sym.Group(list(out))
        out.save("%s-symbol.json" % path)
        arg_dict = {}
        for name, param in self.collect_params().items():
            if param._data is not None:
                arg_dict["arg:%s" % name] = param.data()
        _nd.save("%s-%04d.params" % (path, epoch), arg_dict)
        return out


def _apply_aux(params, param_names, aux_updates):
    """Commit traced aux-state updates (BatchNorm moving stats) back into the
    owning Parameters (the reference mutates aux NDArrays in place)."""
    if not aux_updates:
        return
    by_name = dict(zip(param_names, params))
    for name, val in aux_updates.items():
        p = by_name.get(name)
        if p is not None and p._data is not None:
            ag = p._data._ag
            p._data._rebind(val)
            p._data._ag = ag


# ---------------------------------------------------------------------------
# SymbolBlock — wrap a symbol graph as a Block (reference block.py SymbolBlock)
# ---------------------------------------------------------------------------

class SymbolBlock(HybridBlock):
    """Construct a Block from a Symbol and input symbols."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from ..symbol.symbol import Symbol, Group
        if isinstance(outputs, (list, tuple)):
            outputs = Group(list(outputs))
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        self._output_sym = outputs
        self._input_names = [i.name if isinstance(i, Symbol) else str(i)
                             for i in inputs]
        aux_names = set(outputs.list_auxiliary_states())
        for name in outputs.list_arguments():
            if name not in self._input_names:
                self.params.get(name, allow_deferred_init=True)
        for name in aux_names:
            self.params.get(name, allow_deferred_init=True, grad_req="null")
        self._aux_names = list(aux_names)
        self._eval_fn = None

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as _sym
        sym = _sym.load(symbol_file)
        if not isinstance(input_names, (list, tuple)):
            input_names = [input_names]
        inputs = [_sym.Variable(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            arg_dict = _nd.load(param_file)
            for k, v in arg_dict.items():
                name = k.split(":", 1)[-1]
                if name in ret.params:
                    ret.params[name].set_data(v)
        return ret

    def forward(self, x, *args):
        if not isinstance(x, _nd.NDArray):
            raise TypeError("SymbolBlock supports eager NDArray calls")
        from ..executor import _graph_eval_fn
        if self._eval_fn is None:
            self._eval_fn = _graph_eval_fn(self._output_sym)
        arg_vals, aux_vals = {}, {}
        ins = [x] + [a for a in args if isinstance(a, _nd.NDArray)]
        for name, v in zip(self._input_names, ins):
            arg_vals[name] = v._data
        for name, p in self.params.items():
            if name in self._aux_names:
                aux_vals[name] = p.data()._data
            else:
                arg_vals[name] = p.data()._data
        key = _random.next_key()
        outs, _ = self._eval_fn(arg_vals, aux_vals, key,
                                _autograd.is_training())
        out_nds = [_nd.NDArray(o) for o in outs]
        return out_nds[0] if len(out_nds) == 1 else out_nds
