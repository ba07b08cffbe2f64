"""SPMD training step: forward+backward+allreduce+update in ONE XLA program.

This is the performance endgame the reference approaches with bulked engine
segments + kvstore reduce (SURVEY.md §3.3): here the whole training step —
including the gradient all-reduce that the reference routes through
CommDevice/RCCL/ps-lite — is a single jitted SPMD module over a device mesh.
GSPMD inserts the psum on ICI; the optimizer update (the reference's
optimizer ops) fuses into the same program, and parameter buffers are donated
so updates are in-place in HBM.

Sharding strategy:
* batch axis → 'dp' mesh axis (DataParallelExecutorGroup's slicing, done by
  GSPMD instead of python);
* optionally, large parameter matrices → 'tp' mesh axis (the reference's
  manual group2ctx model parallelism, done as tensor parallelism);
* everything else replicated.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..executor import _graph_eval_fn
from .. import random as _random

__all__ = ["SPMDTrainStep"]


def megatron_tp_rule(column_parallel=(), row_parallel=(), tp_axis="tp"):
    """Build a ``tp_rule`` implementing the Megatron-LM sharding pattern
    for FullyConnected weights (layout (out_features, in_features), the
    reference's FC layout — src/operator/nn/fully_connected-inl.h):

    * column-parallel layers (the FIRST matmul of an MLP pair, or the QKV
      projection of attention) split the OUTPUT dim: weight P(tp, None),
      bias P(tp). The activation comes out tp-sharded on features — no
      collective needed. NOTE for fused QKV: lay the output features out
      HEAD-MAJOR (reshape to (..., heads, 3, head_dim), not
      (..., 3, heads, head_dim)) so a contiguous row split is a whole-head
      partition; a 3-major interleave forces GSPMD to reshard at the
      downstream q/k/v split and costs extra all-gathers (numerics stay
      right, the one-psum-per-pair property doesn't).
    * row-parallel layers (the SECOND matmul / attention output proj)
      split the INPUT dim: weight P(None, tp), bias replicated. Consuming
      the tp-sharded activation needs one psum, which GSPMD inserts
      automatically at the sharding boundary.

    One collective per MLP/attention pair — the Megatron recipe — falls
    out of the two specs; nothing is hand-scheduled.

    ``column_parallel`` / ``row_parallel``: iterables of layer-name
    prefixes (e.g. ``["ffn1", "attn_qkv"]``; matches ``<prefix>_weight`` /
    ``<prefix>_bias``).
    """
    col = tuple(column_parallel)
    row = tuple(row_parallel)

    def rule(name, shape):
        for p in col:
            if name == p + "_weight" and len(shape) >= 2:
                return P(tp_axis, None)
            if name == p + "_bias":
                return P(tp_axis)
        for p in row:
            if name == p + "_weight" and len(shape) >= 2:
                return P(None, tp_axis)
            if name == p + "_bias":
                return P()   # replicated; added after the psum
        return None

    return rule


class SPMDTrainStep:
    """Compile a Symbol's training step over a mesh.

    step(params, aux, opt_state, data, label, key) ->
        (params, aux, opt_state, outputs)
    with SGD-momentum fused in (optimizer fusion = BASELINE MFU work item).
    """

    def __init__(self, symbol, mesh, data_names=("data",),
                 label_names=("softmax_label",), dp_axis="dp", tp_axis=None,
                 lr=0.05, momentum=0.9, wd=0.0, rescale_grad=None,
                 tp_rule=None, dtype=None, ddp_bucketed=False,
                 bucket_bytes=None):
        self.symbol = symbol
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.tp_axis = tp_axis
        self._data_names = list(data_names)
        self._label_names = list(label_names)
        arg_names = symbol.list_arguments()
        inputs = set(self._data_names + self._label_names)
        self.param_names = [n for n in arg_names if n not in inputs]
        self.aux_names = symbol.list_auxiliary_states()
        eval_fn = _graph_eval_fn(symbol)
        self._eval_fn = eval_fn
        self.lr, self.momentum, self.wd = lr, momentum, wd
        self.rescale_grad = rescale_grad
        self.tp_rule = tp_rule or (lambda name, shape: None)

        dn, ln = self._data_names, self._label_names
        mom_coeff = momentum
        # Mixed precision (reference: multi-precision SGD,
        # python/mxnet/optimizer/optimizer.py:452): master weights stay
        # float32; compute runs in `dtype` (bf16 on the MXU). The cast sits
        # inside the differentiated function so grads come back f32. The
        # session dtype policy (config.compute_dtype) supplies/overrides
        # the default, same as the fused Module and Gluon paths.
        from .. import config as _config
        compute_dtype = _config.compute_dtype(default=dtype)

        def step(params, aux, opt_state, data, label, key):
            n_batch = data[dn[0]].shape[0]
            if self._reducer is not None:
                # manual-dp body: shapes are PER-SHARD — the mean must
                # still be over the global batch, and the psum'd gradient
                # is the global sum, so scale by local * dp_size (static)
                n_batch = n_batch * self._ddp_size
                # decorrelate per-shard dropout/noise deterministically
                key = jax.random.fold_in(
                    key, jax.lax.axis_index(self.dp_axis))
            scale = (1.0 / n_batch) if rescale_grad is None else rescale_grad

            def loss_fn(p):
                if compute_dtype is not None:
                    p = {k: (v.astype(compute_dtype)
                             if v.dtype == jnp.float32 else v)
                         for k, v in p.items()}
                arg_vals = {**p, **data, **label}
                outs, auxu = eval_fn(arg_vals, aux, key, True)
                # loss heads (SoftmaxOutput etc.) carry custom VJPs seeded by
                # an all-ones cotangent — summing outputs reproduces the
                # reference's backward() seed exactly.
                total = 0.0
                for o in outs:
                    total = total + jnp.sum(o)
                return total, (outs, auxu)

            from ..executor import mirror_wrap
            grads, (outs, auxu) = jax.grad(mirror_wrap(loss_fn),
                                           has_aux=True)(params)
            if self._reducer is not None:
                # bucketed manual psum over dp (parallel/ddp.py): one
                # fused collective per bucket, in reverse-production
                # order, interleavable with the remaining backward.
                # tp-sharded params (GSPMD's auto axis) are reduced
                # per-param so their flat buffers never force a layout
                # change of the tp sharding.
                red = self._reducer.reduce(
                    {k: grads[k] for k in self._reducer_keys})
                for k in self._ddp_tp_names:
                    red[k] = jax.lax.psum(grads[k], self.dp_axis)
                grads = red
            new_params = {}
            new_opt = {}
            for k, w in params.items():
                g = grads[k] * scale + wd * w
                m = mom_coeff * opt_state[k] - lr * g
                new_opt[k] = m
                new_params[k] = w + m
            new_aux = {**aux, **auxu}
            return new_params, new_aux, new_opt, outs

        # shardings
        self._param_sharding = {}
        self._step = step
        self._jitted = None
        self._depth_ctl = None
        # bucketed-DDP mode: the dp gradient reduction becomes explicit
        # (shard_map + GradReducer) instead of GSPMD-inferred; built in
        # compile() where the param shapes are known
        self._ddp_bucketed = bool(ddp_bucketed)
        self._bucket_bytes = bucket_bytes
        self._reducer = None
        self._reducer_keys = frozenset()
        self._ddp_tp_names = ()
        self._ddp_size = int(mesh.shape[dp_axis]) if ddp_bucketed else 1

    def _shard_params(self, shapes):
        out = {}
        for name, shp in shapes.items():
            spec = None
            if self.tp_axis is not None:
                spec = self.tp_rule(name, shp)
            out[name] = NamedSharding(self.mesh, spec if spec is not None else P())
        return out

    def _build_reducer(self, param_shapes):
        """Split params into the bucketed-replicated set and the
        tp-sharded set (reduced per-param), then build the GradReducer
        over the replicated ones in forward order (it re-walks them in
        reverse-production order itself)."""
        from . import ddp as _ddp
        rep, tp_names = [], []
        for n in self.param_names:
            if n not in param_shapes:
                continue
            spec = self.tp_rule(n, param_shapes[n]) \
                if self.tp_axis is not None else None
            if spec is not None and tuple(spec) and \
                    any(ax is not None for ax in tuple(spec)):
                tp_names.append(n)
            else:
                rep.append((n, tuple(param_shapes[n]), _np.dtype(_np.float32)))
        self._reducer = _ddp.GradReducer(
            rep, axis_name=self.dp_axis, bucket_bytes=self._bucket_bytes,
            axis_size=self._ddp_size)
        self._reducer_keys = frozenset(e[0] for e in rep)
        self._ddp_tp_names = tuple(tp_names)

    def ddp_stats(self):
        """Host-held bucket plan summary (None unless ddp_bucketed)."""
        return self._reducer.stats() if self._reducer is not None else None

    def compile(self, param_shapes, aux_shapes, data_shapes, label_shapes):
        p_sh = self._shard_params(param_shapes)
        a_sh = {k: NamedSharding(self.mesh, P()) for k in aux_shapes}
        d_sh = {k: NamedSharding(self.mesh, P(self.dp_axis))
                for k in data_shapes}
        l_sh = {k: NamedSharding(self.mesh, P(self.dp_axis))
                for k in label_shapes}
        key_sh = NamedSharding(self.mesh, P())
        fn = self._step
        if self._ddp_bucketed:
            # explicit-collective mode: dp becomes a MANUAL mesh axis
            # (shard_map) so the bucketed psums in step() are real; any
            # other axes (tp) stay auto — GSPMD still places those.
            from jax import shard_map
            self._build_reducer(param_shapes)
            d_spec = {k: P(self.dp_axis) for k in data_shapes}
            l_spec = {k: P(self.dp_axis) for k in label_shapes}
            p_spec = {k: P() for k in param_shapes}
            a_spec = {k: P() for k in aux_shapes}
            fn = shard_map(
                fn, mesh=self.mesh,
                in_specs=(p_spec, a_spec, p_spec, d_spec, l_spec, P()),
                out_specs=(p_spec, a_spec, p_spec, P(self.dp_axis)),
                axis_names={self.dp_axis}, check_vma=False)
        self._jitted = jax.jit(
            fn,
            in_shardings=(p_sh, a_sh, p_sh, d_sh, l_sh, key_sh),
            out_shardings=(p_sh, a_sh, p_sh, None),
            donate_argnums=(0, 1, 2))
        self._shardings = (p_sh, a_sh, d_sh, l_sh)
        return self._jitted

    def init(self, param_shapes, aux_shapes, seed=0):
        """Xavier-ish init placed with the right shardings."""
        rng = _np.random.RandomState(seed)
        p_sh, a_sh, _, _ = self._shardings
        params = {}
        for name, shp in param_shapes.items():
            if name.endswith("bias") or name.endswith("beta") or \
                    name.endswith("_mean"):
                v = _np.zeros(shp, _np.float32)
            elif name.endswith("gamma") or name.endswith("_var"):
                v = _np.ones(shp, _np.float32)
            else:
                fan = _np.prod(shp[1:]) if len(shp) > 1 else shp[0]
                v = rng.normal(0, _np.sqrt(2.0 / max(fan, 1)), shp).astype(_np.float32)
            params[name] = jax.device_put(v, p_sh[name])
        aux = {}
        for name, shp in aux_shapes.items():
            v = _np.ones(shp, _np.float32) if name.endswith("var") \
                else _np.zeros(shp, _np.float32)
            aux[name] = jax.device_put(v, a_sh[name])
        opt = {k: jax.device_put(_np.zeros(shp, _np.float32), p_sh[k])
               for k, shp in param_shapes.items()}
        return params, aux, opt

    def __call__(self, params, aux, opt_state, data, label, key=None):
        if key is None:
            key = _random.next_key()
        out = self._jitted(params, aux, opt_state, data, label, key)
        # async dispatch with bounded depth: the caller's loop keeps
        # enqueueing steps; block only once flags.engine_depth programs
        # are in flight (one output handle stands for the whole step)
        if self._depth_ctl is None:
            from ..engine import DepthController
            self._depth_ctl = DepthController()
        outs = out[3]
        self._depth_ctl.admit(list(outs)[:1] if outs else [])
        return out

    def quiesce(self):
        """Block until every in-flight SPMD step has retired."""
        if self._depth_ctl is not None:
            self._depth_ctl.quiesce()

    # -- elastic checkpointing ----------------------------------------------
    def save_checkpoint(self, manager, params, aux, opt_state, step,
                        epoch=0, nbatch=0, blocking=None):
        """Snapshot the SPMD training state through a CheckpointManager.

        Buffers are materialised to host numpy BEFORE handing off to the
        (possibly async) writer, so donation/in-place reuse of the device
        buffers by the next step can't race the save."""
        import pickle as _pickle
        self.quiesce()  # settle in-flight steps before materialising
        state = {}
        for k, v in params.items():
            state["arg:" + k] = _np.asarray(v)
        for k, v in aux.items():
            state["aux:" + k] = _np.asarray(v)
        for k, v in opt_state.items():
            state["opt:" + k] = _np.asarray(v)
        state["__rng__"] = _pickle.dumps(_random.get_state(), protocol=2)
        manager.save(state, step, epoch=epoch, nbatch=nbatch,
                     meta={"kvstore": "spmd"}, blocking=blocking)

    def restore_latest(self, manager, step=None):
        """Load the newest valid snapshot and place every buffer with the
        compiled shardings. Returns (params, aux, opt_state, manifest) or
        None. ``compile()`` must have run (the shardings come from it)."""
        import pickle as _pickle
        import jax as _jax
        state, manifest = manager.restore(step=step)
        if state is None:
            return None
        p_sh, a_sh, _, _ = self._shardings
        params, aux, opt = {}, {}, {}
        for k, v in state.items():
            if k == "__rng__":
                _random.set_state(_pickle.loads(bytes(v)))
            elif k.startswith("arg:"):
                params[k[4:]] = _jax.device_put(v, p_sh[k[4:]])
            elif k.startswith("aux:"):
                aux[k[4:]] = _jax.device_put(v, a_sh[k[4:]])
            elif k.startswith("opt:"):
                opt[k[4:]] = _jax.device_put(v, p_sh[k[4:]])
        return params, aux, opt, manifest
