"""Module: symbol + executor + optimizer intermediate-level trainer.

Parity: ``python/mxnet/module/module.py`` (reference :573 forward, :627
backward, :644 update) over DataParallelExecutorGroup. TPU-native design:
one Executor per module; data parallelism over multiple chips is SPMD inside
the executor's jitted program (mesh sharding), not N replicated executors —
the reference's executor_group slicing collapses into GSPMD. ``contexts``
may be a list for API parity; the first entry selects the mesh.
"""
from __future__ import annotations

import logging
import pickle

import numpy as _np

from .base_module import BaseModule, _as_list
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import ndarray as _nd
from ..ndarray.ndarray import NDArray
from .. import optimizer as _opt
from .. import kvstore as _kvstore
from ..model import save_checkpoint, load_checkpoint
from ..initializer import InitDesc


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = current_context()
        if isinstance(context, Context):
            context = [context]
        self._context = list(context)
        if group2ctxs:
            raise MXNetError(
                "group2ctxs manual device placement is not supported on "
                "TPU: use context=[...] (SPMD data parallelism) or "
                "parallel.SPMDTrainStep tensor parallelism instead")
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        self._state_names = list(state_names or [])
        self._compression_params = compression_params

        arg_names = symbol.list_arguments()
        input_names = self._data_names + self._label_names + self._state_names
        self._param_names = [n for n in arg_names if n not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._fused = None
        self._fused_opt_state = None
        self._fused_pending = None
        self._fused_ran = False
        self._ddp = False
        self._monitor_installed = False
        # device-resident metrics (device_metric.py): the (sum, count)
        # carry rides the fused step; host sees it only on publish
        self._fused_met_state = None
        self._device_plan = None
        self._device_proxy = None
        self._device_met_version = 0

    # ------------------------------------------------------------ properties
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        return self._data_shapes

    @property
    def label_shapes(self):
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        if self._exec.outputs:
            return list(zip(self._output_names,
                            [o.shape for o in self._exec.outputs]))
        # before the first forward: shapes from an abstract trace (no device
        # work) — needed by containers like SequentialModule at bind time
        return list(zip(self._output_names, self._exec._out_shapes()))

    # ------------------------------------------------------------------ bind
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if self.binded and not force_rebind:
            self.logger.warning("Already bound, ignoring bind()")
            return
        self._drop_fused()
        # reference parity (module.py bind): a rebind invalidates the
        # optimizer binding too — init_optimizer must run again (fit does),
        # which also re-engages the fused step for the new executor
        self.optimizer_initialized = False
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        self._data_shapes = _normalize_shapes(data_shapes, self._data_names)
        self._label_shapes = _normalize_shapes(label_shapes, self._label_names) \
            if label_shapes else []

        shape_kwargs = {}
        for desc in self._data_shapes + (self._label_shapes or []):
            shape_kwargs[desc[0]] = desc[1]
        # context=[c0, c1, ...] selects SPMD data parallelism: the executor
        # builds a 'dp' mesh over the devices, shards data/label on the
        # batch axis, replicates parameters, and GSPMD all-reduces the
        # gradients inside the compiled step (the reference's
        # DataParallelExecutorGroup + kvstore reduce, collapsed into XLA).
        ctx = self._context if len(self._context) > 1 else self._context[0]
        req = {}
        for name in self._symbol.list_arguments():
            if name in self._data_names:
                req[name] = "write" if inputs_need_grad else "null"
            elif name in self._label_names or name in self._state_names:
                req[name] = "null"
            elif name in self._fixed_param_names:
                req[name] = "null"
            else:
                req[name] = grad_req if for_training else "null"
        from ..executor import simple_bind
        self._exec = simple_bind(
            self._symbol, ctx, grad_req=req,
            batch_args=self._data_names + self._label_names, **shape_kwargs)
        if self._arg_params is not None:
            self._exec.copy_params_from(self._arg_params, self._aux_params,
                                        allow_extra_params=True)

    # ------------------------------------------------------------ parameters
    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing parameters"

        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            if arg_params is not None and name in arg_params:
                arg_params[name].copyto(arr)
            elif initializer is not None:
                desc = InitDesc(name, self._get_var_attrs(name))
                initializer(desc, arr)
            elif not allow_missing:
                raise MXNetError("parameter %r missing and no initializer"
                                 % name)
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            if aux_params is not None and name in aux_params:
                aux_params[name].copyto(arr)
            elif initializer is not None:
                desc = InitDesc(name, self._get_var_attrs(name))
                initializer(desc, arr)
        self.params_initialized = True
        self._params_dirty = False
        self._arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        self._aux_params = {n: self._exec.aux_dict[n] for n in self._aux_names}

    def _get_var_attrs(self, name):
        for node in self._symbol._topo():
            if node.is_variable and node.name == name:
                return dict(node.attrs)
        return {}

    def get_params(self):
        assert self.binded and self.params_initialized
        return ({k: v.copy() for k, v in self._arg_params.items()},
                {k: v.copy() for k, v in self._aux_params.items()})

    # ------------------------------------------------------------- optimizer
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            # reference module.py: default rescale_grad = 1/batch_size so
            # sum-style loss heads (SoftmaxOutput) yield mean gradients
            if "rescale_grad" not in optimizer_params and self._data_shapes:
                batch_size = self._data_shapes[0][1][0]
                optimizer_params["rescale_grad"] = 1.0 / max(batch_size, 1)
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer = _opt.create(optimizer, sym=self._symbol,
                                    param_idx2name=idx2name,
                                    **optimizer_params)
        self._optimizer = optimizer

        kv = None
        update_on_kvstore = False
        if kvstore:
            if isinstance(kvstore, str):
                kv = _kvstore.create(kvstore)
            else:
                kv = kvstore
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            # update_on_kvstore: reference default for dist_* (optimizer
            # runs on the server). tpu_sync has no server — its gradient
            # all-reduce happens inside the compiled SPMD step (GSPMD psum
            # over the executor's mesh), so the update applies directly to
            # the executor's replicated weights via the updater path.
            update_on_kvstore = kv.type.startswith("dist")
            # MXNET_DDP=1 (tools/launch.py --ddp): the dist_sync gradient
            # exchange moves INSIDE the compiled step — bucketed lax.psum
            # over the dp mesh (parallel/ddp.py), optimizer replicated on
            # every rank. dist_async keeps the kvstore server path.
            if update_on_kvstore and not kv.type.endswith("async"):
                from ..parallel import ddp as _ddp
                if _ddp.enabled():
                    mesh = _ddp.process_mesh()
                    batch = (self._data_shapes[0][1][0]
                             if self._data_shapes else 0)
                    if mesh.size > 1 and batch % mesh.size == 0:
                        update_on_kvstore = False
                        self._ddp = True
                    elif mesh.size > 1:
                        self.logger.warning(
                            "MXNET_DDP: batch %d not divisible by dp "
                            "mesh size %d; falling back to the kvstore "
                            "path", batch, mesh.size)
        self._kvstore = kv
        self._update_on_kvstore = update_on_kvstore

        if kv is not None:
            for i, name in enumerate(self._param_names):
                kv.init(name, self._arg_params[name])
            if update_on_kvstore:
                kv.set_optimizer(self._optimizer)
        if not update_on_kvstore:
            self._updater = _opt.get_updater(self._optimizer)
        self._init_fused_step(kv)
        self.optimizer_initialized = True

    def _drop_fused(self):
        """Invalidate the fused step (rebind/monitor), first mirroring its
        optimizer state into the eager Updater so momentum/moments survive."""
        if self._fused is not None:
            if self._fused_opt_state is not None and \
                    self._updater is not None:
                self._updater.states = self._fused.state_to_updater(
                    self._fused_opt_state)
            self._fused = None
            self._fused_opt_state = None
            self._fused_pending = None
            self._fused_ran = False
            self._detach_device_metric()

    def _init_fused_step(self, kv):
        """Build the fused one-program train step (module/fused.py) when it
        can faithfully replace the eager fwd/bwd/update path: tpu_sync
        kvstore (always), or local/no kvstore on a TPU context (auto)."""
        from ..config import flags as _flags
        self._fused = None
        self._fused_ran = False
        self._detach_device_metric()
        kv_type = kv.type if kv is not None else None

        def eager(why):
            # the eager path trains correctly, one dispatch per op: where
            # the caller asked for the fused path by name, say why not
            if kv_type == "tpu_sync":
                self.logger.warning(
                    "kvstore='tpu_sync' but the fused train step is not "
                    "engaged (%s): training runs the eager per-op path",
                    why)

        if not self.for_training:
            return
        if not _flags.module_fused_step:
            return eager("MXNET_MODULE_FUSED_STEP=0")
        if self.inputs_need_grad:
            return eager("inputs_need_grad=True")
        if self._monitor_installed:
            return eager("a monitor is installed")
        if self._update_on_kvstore:
            return  # optimizer runs on the (dist) kvstore server
        on_tpu = all(c.device_type == "tpu" for c in self._context)
        if not (kv_type == "tpu_sync" or self._ddp
                or (on_tpu and kv_type in (None, "local", "device"))):
            return
        # 'add' grad accumulation needs the eager grad buffers
        if any(self._exec._grad_req.get(n) == "add"
               for n in self._param_names):
            return eager("grad_req='add'")
        if self._optimizer.fused_ops() is None:
            return eager("optimizer %s has no fused update"
                         % type(self._optimizer).__name__)
        # fp16 params need the eager multi-precision path (f32 master copy
        # per weight, optimizer.py:71-75) — fused state layout differs
        if any(self._exec.arg_dict[n].dtype != _np.float32
               for n in self._param_names):
            return eager("non-float32 parameters")
        from .fused import FusedStep
        # multi_precision on a TPU module = bf16 compute over f32 master
        # weights (the reference's fp16 multi-precision SGD, optimizer.py
        # :452, mapped to the MXU's native dtype); the session dtype policy
        # (MXNET_COMPUTE_DTYPE, config.compute_dtype) can force or veto it
        default_cdt = None
        if getattr(self._optimizer, "multi_precision", False):
            import jax.numpy as _jnp
            default_cdt = _jnp.bfloat16
        from .. import config as _config
        compute_dtype = _config.compute_dtype(default=default_cdt)
        ddp_mesh = None
        if self._ddp:
            from ..parallel import ddp as _ddp
            ddp_mesh = _ddp.process_mesh()
        self._fused = FusedStep(self._exec, self._optimizer,
                                self._param_names,
                                compute_dtype=compute_dtype,
                                data_names=self._data_names,
                                keep_f32=self._norm_stat_params(),
                                index_names=self._inputs_in_slots(
                                    "index_inputs"),
                                ddp_mesh=ddp_mesh)
        self._fused_opt_state = self._fused.init_state()
        # the step's gradients live and die inside its program: the eager
        # buffers would only hold a float32 copy of the model on the chip
        self._exec.release_grad_buffers()

    def _ddp_stats(self, n_steps):
        """Host-held DDP bucket/comm summary scaled to a telemetry window
        of ``n_steps`` (base_module._telem_window). Pure bookkeeping from
        the reducer's static plan — ZERO device syncs, so the ≤1
        d2h-per-window budget is untouched. None when DDP is off."""
        if not self._ddp or self._fused is None:
            return None
        s = self._fused.ddp_stats()
        if s is None:
            return None
        return {"buckets": s["buckets"],
                "comm_bytes": s["comm_bytes"] * max(int(n_steps), 0),
                "overlap_ms": s["overlap_ms"]}

    def _op_counters(self):
        """{gauge: (a step's mean over the nodes that count it, help)} from
        the auxiliary states that ops declare as counters (``counters`` in
        ops/registry.py: the state is [steps, a step's mean a gauge]).
        One small device read a state: call it where the host has waited
        for the device anyway."""
        if not self.binded:
            return {}
        seen = {}
        for node in self._symbol._topo():
            for slot, gauges in (node.op.counters if node.op else ()):
                src = node.inputs[slot][0]
                if src.is_variable and src.name in self._exec.aux_dict:
                    row = self._exec.aux_dict[src.name].asnumpy()
                    for (name, text), v in zip(gauges, row[1:]):
                        seen.setdefault((name, text), []).append(float(v))
        return {name: (float(_np.mean(vs)), text)
                for (name, text), vs in seen.items()}

    def _inputs_in_slots(self, which):
        """Names of the variables that feed, directly, a slot that some op
        lists under ``which`` (``f32_inputs`` or ``index_inputs``,
        ops/registry.py)."""
        names = set()
        for node in self._symbol._topo():
            if node.op is None:
                continue
            for slot in getattr(node.op, which, ()):
                if slot < len(node.inputs) and node.inputs[slot][0].is_variable:
                    names.add(node.inputs[slot][0].name)
        return frozenset(names)

    def _norm_stat_params(self):
        """Names of params that must stay f32 under a low-precision compute
        policy: the slots their ops read as float32 (``f32_inputs``):
        BatchNorm and RMSNorm scales, a router's matrix, a forget gate's
        rates. The bf16-native BN kernel keeps its statistics/scale math in
        f32 and consumes f32 affine params directly (ops/nn.py), so
        downcasting them would only add converts back at every BN
        boundary; a router that rounds its matrix chooses other experts."""
        return self._inputs_in_slots("f32_inputs")

    # --------------------------------------------------------------- running
    def _feed(self, data_batch):
        feed = {}
        for name, arr in zip(self._data_names, data_batch.data):
            feed[name] = arr
        if self._label_shapes and data_batch.label:
            for name, arr in zip(self._label_names, data_batch.label):
                feed[name] = arr
        return feed

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        self._exec.forward(is_train=is_train, **self._feed(data_batch))

    def forward_backward(self, data_batch):
        """fit's per-batch entry. On the fused path this launches ONE
        compiled program (fwd+bwd+reduce+optimizer update); the parameter/
        optimizer-state commit is deferred to update(). Bare forward()/
        backward() always take the eager path, so custom training loops see
        reference semantics (weights never move before update())."""
        if self._fused is not None and self.optimizer_initialized:
            self._forward_fused(self._feed(data_batch))
        else:
            self.forward(data_batch, is_train=True)
            self.backward()

    def _fit_step(self, data_batch):
        """Atomic fused fit step: one donating XLA program updates params/
        aux/optimizer state IN PLACE (no HBM double-buffering), and the
        results commit immediately. Falls back to the eager pair when the
        fused step is not engaged."""
        if self._fused is not None and self.optimizer_initialized:
            return self._fit_step_fused_impl(data_batch)
        else:
            self.forward_backward(data_batch)
            self.update()

    def _commit_fused(self, last_outs, new_params, new_aux, new_opt,
                      new_met=None):
        """Commit a donating fused dispatch: the input buffers are dead, so
        params/aux/opt-state/outputs must all be adopted now."""
        from ..ndarray.ndarray import NDArray
        ex = self._exec
        for k, v in new_aux.items():
            ex.aux_dict[k]._rebind(v)
        for k in self._fused.param_names:
            ex.arg_dict[k]._rebind(new_params[k])
        ex.outputs = [NDArray(o, ctx=ex._ctx) for o in last_outs]
        ex._pending = None
        self._fused_opt_state = new_opt
        self._fused.commit_counts()
        self._params_dirty = True
        self._fused_pending = None
        self._fused_ran = False
        if new_met is not None:
            # donated carry: the old device buffers are dead, adopt now
            self._fused_met_state = new_met
            self._device_met_version += 1

    def _fit_step_fused_impl(self, data_batch):
        from .. import random as _random
        ex = self._exec
        ex.set_inputs(**self._feed(data_batch))
        key = _random.next_key()
        outs, new_args, new_aux, new_opt, new_met = self._fused.run(
            ex._arg_vals(), ex._aux_vals(), self._fused_opt_state, key,
            donate=True, met_state=self._fused_met_state)
        self._commit_fused(outs, new_args, new_aux, new_opt,
                           new_met=new_met)

    # ------------------------------------------------- device-resident metric
    def _engage_device_metric(self, eval_metric):
        """Fold ``eval_metric``'s accumulation into the fused step
        (device_metric.py): returns a :class:`DeviceMetricProxy` for fit's
        loop, or None when the metric's math can't be replicated on device
        / the fused step isn't engaged (caller keeps the per-batch host
        path)."""
        from ..config import flags as _flags
        if self._fused is None or not _flags.device_metrics:
            self._detach_device_metric()
            return None
        if self._ddp:
            # under check_vma=False a replicated metric carry would
            # silently accumulate only each rank's LOCAL batches — keep
            # the host metric path (per-worker metric, reference
            # dist_sync semantics)
            self._detach_device_metric()
            return None
        if eval_metric is None \
                or getattr(eval_metric, "_device_resident", False):
            self._detach_device_metric()
            return None
        from .. import device_metric as _dm
        out_names = list(self._output_names)
        label_names = list(self._label_names)
        plan = _dm.plan_for(eval_metric, out_names, label_names)
        if plan is None:
            # a previous fit() may have attached a met_fn for a different
            # metric; a stale carry would ride every step for nothing
            self._detach_device_metric()
            return None

        def met_fn(state, outs, rest):
            pred_dict = dict(zip(out_names, outs))
            label_dict = {k: rest[k] for k in label_names if k in rest}
            return plan.update(state, label_dict, pred_dict)

        self._device_plan = plan
        self._fused.attach_metric(met_fn)
        self._fused_met_state = self._place_met_state(plan.init_state())
        self._device_met_version += 1
        proxy = _dm.DeviceMetricProxy(self, eval_metric)
        proxy._pub_version = self._device_met_version
        self._device_proxy = proxy
        return proxy

    def _place_met_state(self, state):
        """Commit a fresh metric carry where the step's outputs will live:
        the mesh's replicated sharding, or the module's one device. Left
        on the host, the fresh carry and the carry a step returns would
        differ in placement, and XLA would compile the step once for
        each."""
        import jax
        ex = self._exec
        where = ex._rep_sharding if ex._mesh is not None \
            else ex._ctx.jax_device
        return tuple(tuple(jax.device_put(x, where) for x in p)
                     for p in state)

    def _reset_device_metric(self):
        """Zero the device carry. Safe mid-flight at any engine depth: the
        in-flight dispatches already consumed the old (donated) handles,
        and the next dispatch picks up the fresh zeros."""
        if self._device_plan is None:
            return
        self._fused_met_state = self._place_met_state(
            self._device_plan.init_state())
        self._device_met_version += 1

    def _publish_device_metric(self):
        """ONE device->host fetch of the whole metric carry, written into
        the wrapped metric's host accumulators. This is the only d2h the
        device-metric path pays, and only when someone reads the metric."""
        if self._device_plan is None or self._fused_met_state is None:
            return
        pending = [x for p in self._fused_met_state for x in p
                   if hasattr(x, "block_until_ready")]
        host = self._fused_met_state
        if pending:
            from .. import profiler as _profiler
            _profiler.record_host_sync(
                "d2h", sum(int(getattr(x, "nbytes", 0)) for x in pending))
            import jax
            host = jax.device_get(self._fused_met_state)
        self._device_plan.publish(host)

    def _detach_device_metric(self):
        if self._fused is not None:
            self._fused.detach_metric()
        self._fused_met_state = None
        self._device_plan = None
        self._device_proxy = None

    def _forward_fused(self, feed):
        from .. import random as _random
        from ..ndarray.ndarray import NDArray
        ex = self._exec
        ex.set_inputs(**feed)
        key = _random.next_key()
        # met_state=None: the public forward_backward path never touches
        # metric accumulation (the caller updates its metric by hand)
        outs, new_args, new_aux, new_opt, _ = self._fused.run(
            ex._arg_vals(), ex._aux_vals(), self._fused_opt_state, key)
        # aux (BN stats) commit at forward time, like the eager path
        for k, v in new_aux.items():
            ex.aux_dict[k]._rebind(v)
        ex.outputs = [NDArray(o, ctx=ex._ctx) for o in outs]
        ex._pending = None
        # params/opt state commit only in update(): a skipped update()
        # (e.g. NaN-loss guard) must leave weights and the LR schedule
        # untouched, as in the eager path
        self._fused_pending = (new_args, new_opt)
        self._fused_ran = True

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def update(self):
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        self._params_dirty = True
        if self._fused_ran:
            new_args, new_opt = self._fused_pending
            ex = self._exec
            for k in self._fused.param_names:
                ex.arg_dict[k]._rebind(new_args[k])
            self._fused_opt_state = new_opt
            self._fused.commit_counts()
            self._fused_pending = None
            self._fused_ran = False
            return
        if self._update_on_kvstore and self._kvstore is not None:
            for name in self._param_names:
                grad = self._exec.grad_dict.get(name)
                if grad is None:
                    continue
                self._kvstore.push(name, grad)
                # weights must always come back, even from a sparse store
                self._kvstore.pull(name, self._exec.arg_dict[name],
                                   ignore_sparse=False)
        else:
            if self._ddp:
                # eager DDP fallback (optimizer without a fused form):
                # the backward is already done so there is nothing left
                # to overlap with, but the exchange is still ONE bucketed
                # collective per dtype-bucket instead of one per tensor
                from ..parallel import dist as _dist
                names = [n for n in self._param_names
                         if self._exec.grad_dict.get(n) is not None]
                reduced = _dist.allreduce_tree(
                    [self._exec.grad_dict[n]._data for n in names])
                for n, g in zip(names, reduced):
                    self._exec.grad_dict[n]._rebind(g)
            for i, name in enumerate(self._param_names):
                grad = self._exec.grad_dict.get(name)
                if grad is None:
                    continue
                self._updater(i, grad, self._exec.arg_dict[name])

    def get_outputs(self, merge_multi_context=True):
        assert self.binded
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.inputs_need_grad
        return [self._exec.grad_dict[n] for n in self._data_names]

    def get_states(self, merge_multi_context=True):
        return [self._exec.arg_dict[n] for n in self._state_names]

    def set_states(self, states=None, value=None):
        for name, v in zip(self._state_names, states or []):
            v.copyto(self._exec.arg_dict[name])

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        if labels:
            eval_metric.update_dict(
                dict(zip(self._label_names, labels)),
                dict(zip(self._output_names, self._exec.outputs)))
        else:
            eval_metric.update_dict(
                {}, dict(zip(self._output_names, self._exec.outputs)))

    def install_monitor(self, mon):
        # monitors watch per-op values — incompatible with the fused
        # whole-step program, so its construction is skipped (or dropped,
        # preserving accumulated optimizer state)
        self._monitor_installed = True
        self._drop_fused()
        mon.install(self._exec)

    # ------------------------------------------------------------ checkpoint
    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, self.symbol, arg_params, aux_params)
        if save_optimizer_states:
            self.save_optimizer_states("%s-%04d.states" % (prefix, epoch))

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.save_optimizer_states(fname)
        else:
            if self._fused is not None and self._fused_opt_state is not None:
                # fused state is authoritative; mirror into the updater
                # layout so the on-disk format matches the eager path
                self._updater.states = self._fused.state_to_updater(
                    self._fused_opt_state)
            with open(fname, "wb") as f:
                f.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())
            if self._fused is not None:
                self._fused_opt_state = self._fused.state_from_updater(
                    self._updater.states)

    # ------------------------------------------------- elastic checkpointing
    def _live_updater(self):
        """The Updater currently applying updates: the kvstore's when the
        optimizer runs on the (dist) kvstore, ours otherwise. None on the
        dist_async path (state lives in the server process)."""
        if self._update_on_kvstore and self._kvstore is not None:
            return getattr(self._kvstore, "_updater", None)
        return self._updater

    def _optimizer_state_bytes(self):
        """Opaque blob of the full optimizer trajectory for
        CheckpointManager: momentum/moment buffers (updater states) plus
        the update counters that drive lr schedules and Adam bias
        correction. Restored by ``_set_optimizer_state_bytes`` WITHOUT
        replacing the live optimizer object, so fused-step and kvstore
        closures over it stay valid."""
        if not self.optimizer_initialized:
            return None
        updater = self._live_updater()
        states_blob = None
        if updater is not None:
            if self._fused is not None and \
                    self._fused_opt_state is not None:
                updater.states = self._fused.state_to_updater(
                    self._fused_opt_state)
            states_blob = updater.get_states(dump_optimizer=False)
        opt = self._optimizer
        return pickle.dumps({
            "states": states_blob,
            "num_update": opt.num_update,
            "index_counts": dict(opt._index_update_count),
        }, protocol=2)

    def _set_optimizer_state_bytes(self, blob):
        if not self.optimizer_initialized or blob is None:
            return
        obj = pickle.loads(bytes(blob))
        updater = self._live_updater()
        if updater is not None and obj.get("states") is not None:
            updater.set_states(obj["states"])
            if self._fused is not None:
                self._fused_opt_state = self._fused.state_from_updater(
                    updater.states)
        # counters are copied INTO the live optimizer (not pickled over
        # it): the kvstore updater and fused step hold references to this
        # exact object
        opt = self._optimizer
        opt.num_update = obj["num_update"]
        opt._index_update_count.clear()
        opt._index_update_count.update(obj["index_counts"])

    def _sync_params_to_kvstore(self):
        """Make the kvstore's weight copy match the executor's.

        On dist_sync the AUTHORITATIVE weights live in ``kv._store`` (push
        updates them there, update() pulls them back) — restoring only the
        executor would be overwritten by the first post-resume pull."""
        kv = self._kvstore
        if kv is None or not self.binded:
            return
        if getattr(kv, "_async_client", None) is not None:
            return  # dist_async: the server's weights are authoritative
        for name in self._param_names:
            if name in kv._store:
                kv._store[name] = self._exec.arg_dict[name].copy()

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        arg_params, aux_params = self.get_params()
        self.bind(data_shapes, label_shapes, self.for_training,
                  self.inputs_need_grad, force_rebind=True)
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, force_init=True)


def _normalize_shapes(shapes, names):
    """Accept DataDesc list, (name, shape) list, or dict."""
    if shapes is None:
        return []
    out = []
    for item in shapes:
        if hasattr(item, "name") and hasattr(item, "shape"):
            out.append((item.name, tuple(item.shape)))
        elif isinstance(item, (tuple, list)):
            out.append((item[0], tuple(item[1])))
        else:
            raise TypeError("bad shape spec %r" % (item,))
    return out
