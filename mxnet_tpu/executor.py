"""Graph executor: bind a Symbol, run it as compiled XLA modules.

Parity surface: ``python/mxnet/executor.py`` + the C++ GraphExecutor
(reference src/executor/graph_executor.cc: Init :297, Forward :64,
Backward :77, simple_bind/bind entries :1594-1637). TPU-native design
(SURVEY.md §7): every pass the reference runs at bind time — PlanMemory,
DetectInplaceAddTo, AttachOpExecs, op bulking — is XLA's job. ``bind``
traces the Symbol DAG into a pure function and ``jax.jit``s it:

* forward (predict) module,
* forward (train) module,
* fused forward+backward module (one XLA program: the reference's bulked
  whole-graph endgame, with shared intermediates instead of a tape).

Auxiliary states (BatchNorm moving stats) are explicit inputs/outputs of the
pure function; the executor commits them after each training forward —
observably identical to the reference's in-place aux mutation.

Gradients follow ``grad_req`` ('write'/'add'/'null') into caller-provided
``args_grad`` buffers, like GraphExecutor.
"""
from __future__ import annotations

import numpy as _np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import Context, current_context
from . import profiler as _profiler
from . import random as _random
from . import telemetry as _telemetry
from . import autograd as _autograd
from .ndarray import ndarray as _nd
from .ndarray.ndarray import NDArray
from .ops.registry import (PROGRAM_GAUGES, STAGE_KEEP, program_counts,
                           stage_marks)

__all__ = ["Executor", "simple_bind"]


def mirror_wrap(f):
    """Gradient mirroring (the MXNET_BACKWARD_DO_MIRROR analog —
    reference graph_executor.cc:260-283 recomputes cheap segments in the
    backward): when the flag is on, wrap the differentiated function in
    ``jax.checkpoint`` so the backward recomputes activations per the
    configured rematerialization policy instead of keeping them in HBM.
    Evaluated at trace time — a no-op passthrough when the flag is off."""
    from .config import flags as _flags
    if not _flags.backward_do_mirror:
        return f
    policy = getattr(jax.checkpoint_policies, _flags.mirror_policy, None)
    if policy is None:
        raise ValueError(
            "MXNET_MIRROR_POLICY=%r is not a jax.checkpoint_policies "
            "name" % _flags.mirror_policy)
    return jax.checkpoint(f, policy=policy)


# what a ``mirror_stage`` keeps for its backward pass beside what enters and
# leaves it: the values an op marked (``ops.registry.stage_keep``: an
# attention kernel's output, a recurrence's states), and nothing else
_STAGE_POLICY = jax.checkpoint_policies.save_only_these_names(STAGE_KEEP)
_KEPT_VALUES = ("values that the mirror_stages of the training program "
                "traced last keep for their backward pass because an op "
                "marked them (0: no stage, or nothing marked)")
_KEPT_MB = "what those values hold, from their shapes, in 1e6 bytes"


def _mirror_stages(nodes, entries):
    """The graph's rematerialised stages: ``[(first, last, reads, writes)]``
    for every run of consecutive op nodes (in topological order, variables
    aside) that carry the same ``mirror_stage`` attribute. ``first`` and
    ``last`` index ``nodes``; ``reads`` are the values the run takes from
    outside, ``("var", name)`` or ``("val", id(node), output)``; ``writes``
    the ``(id(node), output)`` of its nodes that something outside reads."""
    def stage_of(n):
        return n.attrs.get("mirror_stage", n.attrs.get("__mirror_stage__"))

    runs, cur = [], None
    for i, n in enumerate(nodes):
        if n.is_variable:
            continue
        st = stage_of(n)
        if cur is not None and st is not None and st == cur[0]:
            cur[2] = i
        else:
            cur = [st, i, i]
            if st is not None:
                runs.append(cur)
    out = []
    for _st, first, last in runs:
        inside = {id(n) for n in nodes[first:last + 1] if not n.is_variable}
        reads, writes = [], []
        for n in nodes[first:last + 1]:
            if n.is_variable:
                continue
            for src, oi in n.inputs:
                key = ("var", src.name) if src.is_variable \
                    else ("val", id(src), oi)
                if (src.is_variable or id(src) not in inside) \
                        and key not in reads:
                    reads.append(key)
        users = [(src, oi) for n in nodes if not n.is_variable
                 and id(n) not in inside for src, oi in n.inputs]
        for src, oi in users + list(entries):
            if id(src) in inside and (id(src), oi) not in writes:
                writes.append((id(src), oi))
        out.append((first, last, reads, writes))
    return out


def _device_scope(node):
    """The name a node's device ops run under: the builder's
    (``mx.AttrScope(device_scope=...)`` names a generic op by the layer it
    serves) where the node carries one, else ``mx/op/`` and the op's
    registered name. Never both: a reader that adds scopes up counts a
    device op once."""
    return node.attrs.get("device_scope") or "mx/op/" + node.op.name


def _graph_eval_fn(symbol):
    """Build eval(arg_vals, aux_vals, key, training) -> (outputs, aux_updates).

    Pure function over jax values; traced under jit. Nodes that carry a
    ``mirror_stage`` attribute (``mx.AttrScope(mirror_stage=...)`` around a
    block) are evaluated stage by stage under ``jax.checkpoint`` when
    training: the backward pass then keeps what enters and leaves a stage
    and what an op inside marked as dear to recompute (``_STAGE_POLICY``),
    and recomputes the rest of its interior; the gauges
    ``stage/kept_values`` and ``stage/kept_mb`` say what the marked values
    of the program traced last come to, and the gauges its ops declared
    (``registry.PROGRAM_GAUGES``: ``kda/intra_kernel``, ``kda/intra_plain``)
    what they counted in it. Every op node runs under a
    ``jax.named_scope`` (:func:`_device_scope`), so that each device op of
    the compiled program says in its ``op_name`` which node it serves.
    """
    nodes = symbol._topo()
    entries = list(symbol._entries)
    # kernel-tier graph fusion (BN->relu(+residual), FC->act, ...):
    # planned structurally at bind time, decided per-shape at trace time.
    # Empty when MXNET_KERNEL_TIER=off, which is the default.
    from .kernels import graph_fuse as _gfuse
    kplan, kdeferred = _gfuse.plan(nodes, entries)
    stages = {first: (last, reads, writes) for first, last, reads, writes
              in _mirror_stages(nodes, entries)}

    def run(todo, var, is_aux, values, training):
        """Evaluate the op nodes ``todo`` in order into ``values``
        (id(node) -> output, or (id(node), output index) -> value for what
        came from outside a stage); ``var`` reads a variable by name and
        ``is_aux`` says whether a name is an auxiliary state. Returns the
        aux updates and the reader of values."""
        aux_updates = {}

        def route_aux(node, out):
            # route aux output slots back to their aux variable names
            if node.op.aux_outputs:
                outs = out if isinstance(out, tuple) else (out,)
                for in_slot, out_slot in zip(node.op.aux_inputs,
                                             node.op.aux_outputs):
                    src, _ = node.inputs[in_slot]
                    if src.is_variable and is_aux(src.name):
                        aux_updates[src.name] = outs[out_slot]

        def force(node):
            """Eager (pure-JAX) evaluation of one node — the normal path,
            and the lazy fallback for deferred fusion interiors."""
            ins = [read(s, oi) for (s, oi) in node.inputs]
            params = dict(node.params)
            if "_training" in node.op.param_names:
                params["_training"] = training
            with jax.named_scope(_device_scope(node)):
                out = node.op.fn(*ins, **params)
            values[id(node)] = out
            route_aux(node, out)
            return out

        def read(src, oi):
            if src.is_variable:
                return var(src.name)
            if (id(src), oi) in values:
                return values[(id(src), oi)]
            v = values.get(id(src))
            if v is None and id(src) not in values:
                # deferred fusion interior read outside its pattern
                # (guard rejected the kernel): evaluate it unfused
                v = force(src)
            return v[oi] if isinstance(v, tuple) else v

        for node in todo:
            if node.is_variable:
                continue
            if id(node) in kdeferred:
                continue    # forced lazily only if a guard rejects
            kp = kplan.get(id(node))
            if kp is not None:
                with jax.named_scope(_device_scope(node)):
                    fused = _gfuse.try_eval(kp, node, read, values,
                                            route_aux, training)
                if fused:
                    continue
            force(node)
        return aux_updates, read

    def eval_fn(arg_vals, aux_vals, key, training):
        def var(name):
            if name in arg_vals:
                return arg_vals[name]
            if name in aux_vals:
                return aux_vals[name]
            raise MXNetError("unbound variable %r" % name)

        values = {}
        aux_updates = {}
        kept = []       # bytes of every value a stage of this trace keeps
        counts = {}     # what its ops counted (``registry.program_count``)
        with _random.trace_scope(key), program_counts(counts):
            i = 0
            while i < len(nodes):
                if i not in stages or not training:
                    last = i
                    while last + 1 < len(nodes) and (
                            last + 1 not in stages or not training):
                        last += 1
                    upd, read = run(nodes[i:last + 1], var,
                                    aux_vals.__contains__, values, training)
                    aux_updates.update(upd)
                    i = last + 1
                    continue
                last, reads, writes = stages[i]
                def stage(taken, first=i, last=last, reads=reads,
                          writes=writes):
                    got = dict(zip(reads, taken))
                    inner = {(k[1], k[2]): v for k, v in got.items()
                             if k[0] == "val"}
                    upd, _ = run(nodes[first:last + 1],
                                 lambda name: got[("var", name)],
                                 aux_vals.__contains__, inner, training)
                    outs = []
                    for nid, oi in writes:
                        v = inner[nid]
                        outs.append(v[oi] if isinstance(v, tuple) else v)
                    return outs, upd

                taken = []
                for k in reads:
                    if k[0] == "var":
                        taken.append(var(k[1]))
                    else:
                        v = values[k[1]] if k[1] in values \
                            else values[(k[1], k[2])]
                        taken.append(v[k[2]] if isinstance(v, tuple) else v)
                with stage_marks(kept):
                    outs, upd = jax.checkpoint(
                        stage, policy=_STAGE_POLICY)(taken)
                for (nid, oi), v in zip(writes, outs):
                    values[(nid, oi)] = v
                aux_updates.update(upd)
                i = last + 1
            _, read = run([], var, aux_vals.__contains__, values, training)
            outputs = [read(n, oi) for (n, oi) in entries]
        if training:    # set, not added: a retrace counts the same values
            _telemetry.gauge("stage/kept_values", _KEPT_VALUES).set(len(kept))
            _telemetry.gauge("stage/kept_mb", _KEPT_MB).set(sum(kept) / 1e6)
            for name, doc in PROGRAM_GAUGES.items():
                _telemetry.gauge(name, doc).set(counts.get(name, 0))
        return outputs, aux_updates

    return eval_fn


class Executor:
    """A bound, compiled computation graph.

    ``ctx`` may be a LIST of contexts: the executor then builds a 1-D 'dp'
    device mesh over them and runs every compiled module SPMD — args named
    in ``batch_args`` are sharded on their leading (batch) axis, parameters
    and aux states are replicated, and GSPMD inserts the gradient
    all-reduce inside the fused fwd+bwd program. This is the TPU-native
    collapse of the reference's DataParallelExecutorGroup
    (python/mxnet/module/executor_group.py:143): instead of N replicated
    executors + host-side kvstore reduce, one XLA program spans the mesh
    and the reduce rides ICI.
    """

    def __init__(self, symbol, ctx=None, args=None, args_grad=None,
                 grad_req="write", aux_states=None, group2ctx=None,
                 batch_args=None):
        if group2ctx:
            # the reference's manual model parallelism (graph_executor.cc
            # :1594-1637) does not map to SPMD: refuse loudly instead of
            # silently running single-device
            raise MXNetError(
                "group2ctx manual device placement is not supported on "
                "TPU: express model parallelism with a device mesh "
                "instead (Module(context=[...]) data parallelism, or "
                "parallel.SPMDTrainStep(tp_axis=..., tp_rule=...) for "
                "tensor parallelism)")
        # MXNET_SUBGRAPH_BACKEND applies here so BOTH bind paths (raw
        # Symbol.bind and simple_bind) partition, like the reference's
        # GraphExecutor::Init
        from .subgraph import maybe_partition_for_bind
        symbol = maybe_partition_for_bind(symbol)
        self._symbol = symbol
        if isinstance(ctx, (list, tuple)):
            ctxs = [Context(c) for c in ctx] or [current_context()]
        else:
            ctxs = [ctx or current_context()]
        self._ctx = ctxs[0]
        self._ctxs = ctxs
        self._mesh = None
        self._batch_args = frozenset(batch_args or ())
        devices = [c.jax_device for c in ctxs]
        if len(set(devices)) != len(devices):
            # a mesh over fewer devices than contexts would train on a
            # smaller machine than the one asked for, in silence
            raise MXNetError(
                "contexts %s resolve to %d distinct device(s) %s: each "
                "context of a multi-device bind needs a device of its own"
                % (ctxs, len(set(devices)), sorted(set(devices), key=str)))
        if len(devices) > 1:
            from jax.sharding import Mesh
            self._mesh = Mesh(_np.asarray(devices), ("dp",))
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        # ---- normalize args ------------------------------------------------
        if isinstance(args, dict):
            self.arg_dict = {k: args[k] for k in self._arg_names}
        else:
            if args is None or len(args) != len(self._arg_names):
                raise MXNetError("bind: need %d args (%s)"
                                 % (len(self._arg_names), self._arg_names))
            self.arg_dict = dict(zip(self._arg_names, args))
        self.arg_arrays = [self.arg_dict[k] for k in self._arg_names]

        if isinstance(aux_states, dict):
            self.aux_dict = {k: aux_states[k] for k in self._aux_names}
        elif aux_states is None:
            self.aux_dict = {}
            if self._aux_names:
                raise MXNetError("bind: aux_states required for %s"
                                 % self._aux_names)
        else:
            self.aux_dict = dict(zip(self._aux_names, aux_states))
        self.aux_arrays = [self.aux_dict[k] for k in self._aux_names]

        # ---- grad bookkeeping ---------------------------------------------
        if isinstance(grad_req, str):
            self._grad_req = {k: grad_req for k in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._arg_names, grad_req))
        else:
            self._grad_req = {k: grad_req.get(k, "null") for k in self._arg_names}
        if args_grad is None:
            self.grad_dict = {}
            self._grad_req = {k: "null" for k in self._arg_names}
        elif isinstance(args_grad, dict):
            self.grad_dict = dict(args_grad)
        else:
            self.grad_dict = dict(zip(self._arg_names, args_grad))
        for k in self._arg_names:
            if k not in self.grad_dict:
                self._grad_req[k] = "null"
        self.grad_arrays = [self.grad_dict.get(k) for k in self._arg_names]
        self._req_args = [k for k in self._arg_names
                          if self._grad_req.get(k, "null") != "null"]

        # ---- mesh placement ------------------------------------------------
        # Committed input shardings drive GSPMD: batch args sharded on dp,
        # everything else replicated. The jitted modules below then compile
        # as SPMD programs spanning the mesh; gradient all-reduce and
        # cross-replica BatchNorm stats fall out of sharding propagation.
        self._dp_sharding = self._rep_sharding = None
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._dp_sharding = NamedSharding(self._mesh, P("dp"))
            self._rep_sharding = NamedSharding(self._mesh, P())
            for name, arr in self.arg_dict.items():
                arr._rebind(jax.device_put(arr._data, self._input_sharding(name)))
            for arr in self.aux_dict.values():
                arr._rebind(jax.device_put(arr._data, self._rep_sharding))
            for arr in self.grad_dict.values():
                if arr is not None:
                    arr._rebind(jax.device_put(arr._data, self._rep_sharding))

        # ---- compiled callables -------------------------------------------
        eval_fn = _graph_eval_fn(symbol)
        self._eval_fn = eval_fn
        dev = self._ctx.jax_device

        # MXNET_EXEC_BULK_EXEC_{INFERENCE,TRAIN}=0 disables whole-graph
        # compilation (the reference's bulked-segment toggle): the graph
        # then runs op-by-op eagerly — slow, but each op's error surfaces
        # at its own call site (debugging escape hatch).
        from .config import flags as _flags
        _jit_inf = jax.jit if _flags.exec_bulk_exec_inference else (lambda f: f)
        _jit_train = jax.jit if _flags.exec_bulk_exec_train else (lambda f: f)

        @_jit_inf
        def fwd_predict(arg_vals, aux_vals, key):
            outs, _ = eval_fn(arg_vals, aux_vals, key, False)
            return outs

        @_jit_train
        def fwd_train(arg_vals, aux_vals, key):
            return eval_fn(arg_vals, aux_vals, key, True)

        req = list(self._req_args)

        @_jit_train
        def fwd_bwd(arg_vals, aux_vals, key, ograds):
            diff = {k: arg_vals[k] for k in req}
            rest = {k: v for k, v in arg_vals.items() if k not in diff}

            def f(d):
                outs, auxu = eval_fn({**rest, **d}, aux_vals, key, True)
                return outs, auxu

            outs, vjp, auxu = jax.vjp(mirror_wrap(f), diff, has_aux=True)
            grads = vjp(list(ograds))[0]
            return outs, auxu, grads

        self._fwd_predict = fwd_predict
        self._fwd_train = fwd_train
        self._fwd_bwd = fwd_bwd
        self.outputs = []
        self._pending = None  # (grads, aux_updates) from fused train step
        self._ones_cache = None

    # ---------------------------------------------------------------- run
    def _input_sharding(self, name):
        return self._dp_sharding if name in self._batch_args \
            else self._rep_sharding

    def _to_exec_device(self, val):
        dev = self._ctx.jax_device
        if dev is not None and val.sharding.device_set != {dev}:
            val = jax.device_put(val, dev)
        return val

    def _place_input(self, val, name, replicated=False):
        """Place a host/foreign-device value where this executor computes:
        the named input's mesh sharding when SPMD, else the executor device."""
        if self._mesh is not None:
            return jax.device_put(
                val, self._rep_sharding if replicated
                else self._input_sharding(name))
        return self._to_exec_device(val)

    def _placed(self, nd_arr, sharding):
        """Value of an NDArray, re-committed to `sharding` if a write
        replaced it with a differently-placed array (writes like
        ``arr[:] = v`` adopt v's placement). No-op when already placed."""
        d = nd_arr._data
        if not d.sharding.is_equivalent_to(sharding, d.ndim):
            d = jax.device_put(d, sharding)
            nd_arr._rebind(d)
        return d

    def _arg_vals(self):
        if self._mesh is None:
            return {k: v._data for k, v in self.arg_dict.items()}
        return {k: self._placed(v, self._input_sharding(k))
                for k, v in self.arg_dict.items()}

    def _aux_vals(self):
        if self._mesh is None:
            return {k: v._data for k, v in self.aux_dict.items()}
        return {k: self._placed(v, self._rep_sharding)
                for k, v in self.aux_dict.items()}

    def _is_placed(self, val, name):
        """Whether a feed value already lives where this executor
        computes, so that feeding it copies nothing."""
        if isinstance(val, NDArray):
            val = val._data
        if not isinstance(val, jax.Array):
            return False
        if self._mesh is not None:
            return val.sharding.is_equivalent_to(
                self._input_sharding(name), val.ndim)
        dev = self._ctx.jax_device
        return dev is None or val.sharding.device_set == {dev}

    def _cast_input(self, name, v):
        """Feed value (NDArray / jax array / numpy / nested list) as a jax
        array of the bound arg's dtype, wherever it lives."""
        dtype = self.arg_dict[name].dtype
        if isinstance(v, NDArray):
            v = v._data
        if isinstance(v, jax.Array):
            return v.astype(dtype)
        return jnp.asarray(_np.asarray(v), dtype)

    def prepare_input(self, name, v, place=True):
        """Feed value cast to the bound arg's dtype; with ``place``
        (default), also committed where the executor computes — feeds may
        come from a host iterator (NDArrayIter on cpu()) and jit must not
        see mixed platforms. A value that is not there yet is the step's
        host-to-device copy: the cast and the ``device_put`` run under
        ``mx/feed/h2d`` and the bytes copied go to ``data/h2d_bytes``. A
        value already in place costs neither."""
        if not place or self._is_placed(v, name):
            return self._cast_input(name, v)
        with _profiler.span("mx/feed/h2d") as sp:
            val = self._place_input(self._cast_input(name, v), name)
            sp.add(bytes=_telemetry.count_h2d(val.nbytes))
        return val

    def set_inputs(self, **kwargs):
        """Feed input arrays (by arg name) into the bound buffers, placing
        them where the executor computes."""
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._rebind(self.prepare_input(k, v))

    def forward(self, is_train=False, **kwargs):
        with _profiler.span("mx/exec/forward_train" if is_train
                            else "mx/exec/forward"):
            return self._forward_impl(is_train, **kwargs)

    def _forward_impl(self, is_train=False, **kwargs):
        self.set_inputs(**kwargs)
        key = _random.next_key()
        if is_train:
            if self._req_args:
                if self._ones_cache is None:
                    # cotangent dtype must match the output dtype (fp16
                    # graphs seed fp16 ones)
                    self._ones_cache = [jnp.ones(o.shape, o.dtype)
                                        for o in self._out_structs()]
                ones = self._ones_cache
                outs, auxu, grads = self._fwd_bwd(
                    self._arg_vals(), self._aux_vals(), key, ones)
                self._pending = (grads, auxu)
            else:
                outs, auxu = self._fwd_train(self._arg_vals(),
                                             self._aux_vals(), key)
                self._pending = (None, auxu)
            # commit aux updates (reference mutates aux in place each fwd)
            for k, v in self._pending[1].items():
                self.aux_dict[k]._rebind(v)
        else:
            outs = self._fwd_predict(self._arg_vals(), self._aux_vals(), key)
        self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        return self.outputs

    def _out_structs(self):
        eval_fn = self._eval_fn
        return jax.eval_shape(
            lambda a, x, k: eval_fn(a, x, k, True)[0],
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in self.arg_dict.items()},
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in self.aux_dict.items()},
            jax.ShapeDtypeStruct((2,), _np.uint32))

    def _out_shapes(self):
        return [o.shape for o in self._out_structs()]

    def backward(self, out_grads=None, is_train=True):
        if not self._req_args:
            return
        with _profiler.span("mx/exec/backward"):
            return self._backward_impl(out_grads)

    def release_grad_buffers(self):
        """Give back the gradient buffers' device memory (one copy of the
        model): for a caller whose gradients never leave a compiled
        program, as the fused train step's. The NDArrays stay, empty, and
        keep their dtype; an eager ``backward()`` binds them to fresh
        gradients again (``grad_req='add'`` buffers are kept: they
        accumulate)."""
        for k in self._req_args:
            buf = self.grad_dict.get(k)
            if buf is not None and self._grad_req[k] == "write":
                buf._rebind(jnp.zeros((0,), buf.dtype))

    def _backward_impl(self, out_grads=None):
        if out_grads is not None:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            ograds = [self._place_input(g._data, None, replicated=True)
                      for g in out_grads]
            key = _random.next_key()
            outs, auxu, grads = self._fwd_bwd(
                self._arg_vals(), self._aux_vals(), key, ograds)
        else:
            if self._pending is None or self._pending[0] is None:
                raise MXNetError("backward called before forward(is_train=True)")
            grads = self._pending[0]
        for k in self._req_args:
            g = grads[k]
            buf = self.grad_dict[k]
            if self._grad_req[k] == "add":
                buf._rebind(buf._data + g.astype(buf.dtype))
            else:
                buf._rebind(g.astype(buf.dtype))

    # ------------------------------------------------------------- utility
    @property
    def arg_names(self):
        return self._arg_names

    @property
    def aux_names(self):
        return self._aux_names

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in arg_params.items():
            if k in self.arg_dict:
                val = v._data.astype(self.arg_dict[k].dtype)
                self.arg_dict[k]._rebind(self._place_input(val, k))
            elif not allow_extra_params:
                raise MXNetError("unknown arg %r" % k)
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    val = v._data.astype(self.aux_dict[k].dtype)
                    self.aux_dict[k]._rebind(
                        self._place_input(val, k, replicated=True))
                elif not allow_extra_params:
                    raise MXNetError("unknown aux %r" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new shapes (jit handles recompile per shape)."""
        new_args = {}
        arg_shapes, _, aux_shapes = self._symbol.infer_shape_partial(**kwargs)
        for name, shp in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[name]
            if shp is not None and tuple(shp) != cur.shape:
                new_args[name] = _nd.zeros(shp, ctx=self._ctx, dtype=cur.dtype)
            else:
                new_args[name] = cur
        new_grads = {k: _nd.zeros(new_args[k].shape, ctx=self._ctx)
                     for k in self.grad_dict}
        new_aux = {}
        for name, shp in zip(self._aux_names, aux_shapes):
            cur = self.aux_dict[name]
            new_aux[name] = cur if shp is None or tuple(shp) == cur.shape \
                else _nd.zeros(shp, ctx=self._ctx, dtype=cur.dtype)
        return Executor(self._symbol,
                        self._ctxs if self._mesh is not None else self._ctx,
                        new_args, new_grads, self._grad_req, new_aux,
                        batch_args=self._batch_args)

    @property
    def output_dict(self):
        return dict(zip(self._output_names, self.outputs))


def simple_bind(symbol, ctx=None, grad_req="write", type_dict=None,
                group2ctx=None, batch_args=None, **kwargs):
    """Infer shapes from partial bindings, allocate arrays, bind.

    ``ctx`` may be a list of contexts for SPMD data parallelism (see
    Executor); ``batch_args`` names the args sharded on their batch axis.
    reference: GraphExecutor::Init simple_bind path (graph_executor.cc:1594).
    """
    ctx = ctx or current_context()
    alloc_ctx = ctx[0] if isinstance(ctx, (list, tuple)) else ctx
    shape_kwargs = {k: v for k, v in kwargs.items()
                    if isinstance(v, (tuple, list))}
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shape_kwargs)
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    # type_dict seeds dtype propagation: unnamed params adopt the dtypes
    # inference derives (fp16 data -> fp16 weights, f32 BN stats — the
    # reference's simple_bind type_dict path, graph_executor.cc:1594)
    arg_types, _, aux_types = symbol.infer_type(**(type_dict or {}))
    args = {name: _nd.zeros(shp, ctx=alloc_ctx, dtype=dt)
            for name, shp, dt in zip(arg_names, arg_shapes, arg_types)}
    if isinstance(grad_req, str):
        req_map = {k: grad_req for k in arg_names}
    elif isinstance(grad_req, (list, tuple)):
        req_map = dict(zip(arg_names, grad_req))
    else:
        req_map = {k: grad_req.get(k, "null") for k in arg_names}
    args_grad = {k: _nd.zeros(args[k].shape, ctx=alloc_ctx, dtype=args[k].dtype)
                 for k in arg_names if req_map.get(k, "null") != "null"}
    aux = {name: _nd.zeros(shp, ctx=alloc_ctx, dtype=dt)
           for name, shp, dt in zip(aux_names, aux_shapes, aux_types)}
    return Executor(symbol, ctx, args, args_grad, req_map, aux,
                    group2ctx=group2ctx, batch_args=batch_args)
