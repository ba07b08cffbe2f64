"""Eager NDArray.

Parity surface: ``python/mxnet/ndarray/ndarray.py`` (4k LoC in the
reference) backed by ``src/ndarray/ndarray.cc`` + the dependency engine.
TPU-native design:

* The payload is a ``jax.Array`` — **every eager op dispatch is already
  asynchronous** on PJRT, so the reference's ThreadedEngine var-tracking
  collapses into buffer futures; ``wait_to_read``/``asnumpy`` are the sync
  points (engine.py translates async device errors there, matching
  threaded_engine.cc:474-487 exception semantics).
* NDArray is *mutable by rebinding*: in-place ops swap ``_data`` (functional
  update under the hood — XLA donates buffers inside jit; eager rebind is a
  new buffer, same as the reference's copy-on-write-ish Chunk swap).
* Autograd: ``_ag`` carries tape linkage (AGInfo); recording wraps the op in
  ``jax.vjp`` (see mxnet_tpu/autograd.py).
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from ..base import MXNetError, normalize_dtype, numeric_types, mx_real_t
from ..context import Context, current_context, cpu
from .. import engine as _engine
from .. import autograd as _autograd
from ..ops import registry as _registry

__all__ = ["NDArray", "array", "zeros", "zeros_like", "ones", "full",
           "arange", "empty", "concat", "invoke", "waitall", "save", "load",
           "moveaxis", "imperative_invoke"]


def zeros_like(other):
    """Zeros with the shape/dtype/placement of `other` — placement includes
    mesh sharding, so optimizer state created from a replicated weight is
    itself replicated (jnp.zeros_like preserves sharding)."""
    return NDArray(jnp.zeros_like(other._data), ctx=other.context)


_X64_NARROW = {_np.dtype(_np.int64): _np.int32,
               _np.dtype(_np.uint64): _np.uint32,
               _np.dtype(_np.float64): _np.float32}


def _as_jax(x, dtype=None, ctx=None):
    dev = (ctx or current_context()).jax_device
    if not jax.config.jax_enable_x64:
        # narrow 64-bit requests deliberately (and silently) when x64 is
        # off — jax would truncate anyway but with a per-call warning
        if dtype is not None and _np.dtype(dtype) in _X64_NARROW:
            dtype = _X64_NARROW[_np.dtype(dtype)]
        elif dtype is None and isinstance(x, _np.ndarray) and \
                x.dtype in _X64_NARROW:
            dtype = _X64_NARROW[x.dtype]
    return jax.device_put(jnp.asarray(x, dtype=dtype), dev)


class NDArray:
    """Multi-dimensional, fixed-size array on a device context."""

    __slots__ = ("_data", "_ctx", "_ag", "_version", "__weakref__")

    _collect_stats = False

    def __init__(self, data, ctx=None):
        if isinstance(data, NDArray):
            data = data._data
        self._data = data
        self._ctx = ctx or _infer_ctx(data)
        self._ag = None
        self._version = 0

    # ------------------------------------------------------------------ meta
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def data(self):
        """Raw jax array (mxnet_tpu extension; stable read snapshot)."""
        return self._data

    @property
    def grad(self):
        if self._ag is None:
            return None
        return self._ag.grad

    # ------------------------------------------------------------ conversion
    def asnumpy(self):
        from .. import profiler as _profiler
        nbytes = getattr(self._data, "nbytes", 0)
        _profiler.record_host_sync("d2h", nbytes)
        try:
            with _profiler.span("mx/sync/d2h", bytes=nbytes):
                return _np.asarray(self._data)
        except Exception as e:
            raise MXNetError(str(e)) from e

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def astype(self, dtype, copy=True):
        dt = normalize_dtype(dtype)
        if not copy and self.dtype == dt:
            return self
        return invoke("Cast", [self], {"dtype": dtype})

    def copy(self):
        return invoke("_copy", [self], {})

    def copyto(self, other):
        if isinstance(other, NDArray):
            # writing into a buffer preserves the buffer's placement —
            # including mesh sharding/replication, which a bare
            # ``device_put(..., ctx.jax_device)`` would collapse to one chip
            if other.shape == self.shape:
                dst = other._data.sharding
            else:
                dst = other._ctx.jax_device
            other._rebind(jax.device_put(self._data, dst))
            return other
        if isinstance(other, Context):
            return self.as_in_context(other)
        raise TypeError("copyto: expected NDArray or Context")

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        out = NDArray(jax.device_put(self._data, context.jax_device), ctx=context)
        return out

    def as_in_ctx(self, context):
        return self.as_in_context(context)

    def tolist(self):
        return self.asnumpy().tolist()

    # ----------------------------------------------------------------- sync
    def wait_to_read(self):
        _engine.on_complete(self._data)

    def wait_to_write(self):
        _engine.on_complete(self._data)

    # ------------------------------------------------------------- autograd
    def attach_grad(self, grad_req="write", stype=None):
        grad_buf = zeros(self.shape, dtype=self.dtype, ctx=self._ctx)
        info = _autograd.AGInfo(node=None, grad=grad_buf, grad_req=grad_req)
        self._ag = info

    def detach(self):
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _autograd.backward([self], [out_grad] if out_grad is not None else None,
                           retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------- mutation
    def _rebind(self, new_data):
        self._data = new_data
        self._version += 1
        _engine.sync_point([new_data])

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, numeric_types):
            v = value
        else:
            v = jnp.asarray(_np.asarray(value), dtype=self.dtype)
        if key is None or (isinstance(key, slice) and key == slice(None)):
            if isinstance(v, (int, float)):
                self._rebind(jnp.full(self.shape, v, self.dtype))
            else:
                self._rebind(jnp.broadcast_to(
                    jnp.asarray(v, self.dtype), self.shape))
            return
        key = _norm_index(key)
        # basic slicing routes through the registered _slice_assign ops
        # (parity: src/operator/tensor/matrix_op.cc:434-459; reference
        # __setitem__ dispatches the same way, python/mxnet/ndarray/
        # ndarray.py _set_nd_basic_indexing)
        basic = key if isinstance(key, tuple) else (key,)
        if all(isinstance(k, (slice, int)) for k in basic):
            sls = tuple(k if isinstance(k, slice) else slice(k, k + 1 or None)
                        for k in basic)
            begin = [s.start for s in sls]
            end = [s.stop for s in sls]
            step = [s.step for s in sls]
            from .. import ops as _ops_pkg  # noqa: F401 (registry populated)
            if isinstance(v, (int, float)):
                new = _registry.get("_slice_assign_scalar").fn(
                    self._data, scalar=float(v), begin=begin, end=end,
                    step=step)
            else:
                # static index arithmetic: no device slice just for a shape
                tgt = tuple(len(range(*s.indices(d)))
                            for s, d in zip(sls, self.shape)) \
                    + self.shape[len(sls):]
                rhs = jnp.broadcast_to(jnp.asarray(v, self.dtype), tgt)
                new = _registry.get("_slice_assign").fn(
                    self._data, rhs, begin=begin, end=end, step=step)
            # int keys collapse axes in numpy semantics; sls kept them as
            # length-1 slices, so shapes already agree
            self._rebind(new)
            return
        self._rebind(self._data.at[key].set(v))

    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key._data.astype(jnp.int32)
        key = _norm_index(key)
        out = self._data[key]
        nd = NDArray(out, ctx=self._ctx)
        if _autograd.is_recording() and self._ag is not None:
            _, vjp = jax.vjp(lambda d: d[key], self._data)
            _autograd.record_op(lambda ct: vjp(ct), [self], [nd], name="getitem")
        return nd

    def __len__(self):
        return self.shape[0]

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # ------------------------------------------------------------ operators
    def __add__(self, other):
        return _binary("broadcast_add", "_plus_scalar", self, other)

    def __radd__(self, other):
        return self.__add__(other)

    def __iadd__(self, other):
        out = self.__add__(other)
        self._rebind(out._data)
        return self

    def __sub__(self, other):
        return _binary("broadcast_sub", "_minus_scalar", self, other)

    def __rsub__(self, other):
        return _binary_r("broadcast_sub", "_rminus_scalar", self, other)

    def __isub__(self, other):
        out = self.__sub__(other)
        self._rebind(out._data)
        return self

    def __mul__(self, other):
        return _binary("broadcast_mul", "_mul_scalar", self, other)

    def __rmul__(self, other):
        return self.__mul__(other)

    def __imul__(self, other):
        out = self.__mul__(other)
        self._rebind(out._data)
        return self

    def __truediv__(self, other):
        return _binary("broadcast_div", "_div_scalar", self, other)

    def __rtruediv__(self, other):
        return _binary_r("broadcast_div", "_rdiv_scalar", self, other)

    def __itruediv__(self, other):
        out = self.__truediv__(other)
        self._rebind(out._data)
        return self

    def __mod__(self, other):
        return _binary("broadcast_mod", "_mod_scalar", self, other)

    def __rmod__(self, other):
        return _binary_r("broadcast_mod", "_rmod_scalar", self, other)

    def __pow__(self, other):
        return _binary("broadcast_power", "_power_scalar", self, other)

    def __rpow__(self, other):
        return _binary_r("broadcast_power", "_rpower_scalar", self, other)

    def __neg__(self):
        return invoke("negative", [self], {})

    def __abs__(self):
        return invoke("abs", [self], {})

    def __eq__(self, other):
        if other is None:
            return False
        return _binary("broadcast_equal", "_equal_scalar", self, other)

    def __ne__(self, other):
        if other is None:
            return True
        return _binary("broadcast_not_equal", "_not_equal_scalar", self, other)

    def __gt__(self, other):
        return _binary("broadcast_greater", "_greater_scalar", self, other)

    def __ge__(self, other):
        return _binary("broadcast_greater_equal", "_greater_equal_scalar", self, other)

    def __lt__(self, other):
        return _binary("broadcast_lesser", "_lesser_scalar", self, other)

    def __le__(self, other):
        return _binary("broadcast_lesser_equal", "_lesser_equal_scalar", self, other)

    def __hash__(self):
        return id(self)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __repr__(self):
        return "%s\n<NDArray %s @%s>" % (
            str(self.asnumpy()), "x".join(str(s) for s in self.shape), self._ctx)

    # ------------------------------------------------ fluent method wrappers
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        return invoke("Reshape", [self], {"shape": shape, **kwargs})

    def reshape_like(self, other, **kwargs):
        return invoke("reshape_like", [self, other], kwargs)

    def flatten(self):
        return invoke("Flatten", [self], {})

    def transpose(self, axes=None):
        return invoke("transpose", [self], {"axes": axes})

    @property
    def T(self):
        return self.transpose()

    def expand_dims(self, axis):
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke("squeeze", [self], {"axis": axis})

    def broadcast_to(self, shape):
        return invoke("broadcast_to", [self], {"shape": shape})

    def broadcast_like(self, other):
        return invoke("broadcast_like", [self, other], {})

    def slice(self, begin, end, step=None):
        return invoke("slice", [self], {"begin": begin, "end": end, "step": step})

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def one_hot(self, depth, **kw):
        return invoke("one_hot", [self], {"depth": depth, **kw})

    def sum(self, axis=None, keepdims=False):
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, **kw):
        return invoke("topk", [self], kw)

    def clip(self, a_min, a_max):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return invoke("abs", [self], {})

    def sign(self):
        return invoke("sign", [self], {})

    def sqrt(self):
        return invoke("sqrt", [self], {})

    def square(self):
        return invoke("square", [self], {})

    def exp(self):
        return invoke("exp", [self], {})

    def log(self):
        return invoke("log", [self], {})

    def relu(self):
        return invoke("relu", [self], {})

    def sigmoid(self):
        return invoke("sigmoid", [self], {})

    def tanh(self):
        return invoke("tanh", [self], {})

    def softmax(self, axis=-1):
        return invoke("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", [self], {"axis": axis})

    def dot(self, other, **kw):
        return invoke("dot", [self, other], kw)

    def pick(self, index, axis=-1, keepdims=False):
        return invoke("pick", [self, index], {"axis": axis, "keepdims": keepdims})

    def flip(self, axis):
        return invoke("flip", [self], {"axis": axis})

    def tile(self, reps):
        return invoke("tile", [self], {"reps": reps})

    def repeat(self, repeats, axis=None):
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def pad(self, **kw):
        return invoke("pad", [self], kw)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("split", [self], {"num_outputs": num_outputs,
                                        "axis": axis,
                                        "squeeze_axis": squeeze_axis})

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse as _sp
        return _sp.cast_storage(self, stype)


def _binary(op_name, scalar_op, lhs, rhs):
    if isinstance(rhs, NDArray):
        return invoke(op_name, [lhs, rhs], {})
    return invoke(scalar_op, [lhs], {"scalar": float(rhs)})


def _binary_r(op_name, rscalar_op, lhs, rhs):
    """rhs OP lhs where rhs is scalar or NDArray (reflected operators)."""
    if isinstance(rhs, NDArray):
        return invoke(op_name, [rhs, lhs], {})
    return invoke(rscalar_op, [lhs], {"scalar": float(rhs)})


def _infer_ctx(data):
    try:
        dev = list(data.devices())[0]
        if dev.platform == "cpu":
            return Context("cpu", dev.id)
        return Context("tpu", dev.id)
    except Exception:
        return current_context()


def _norm_index(key):
    if isinstance(key, NDArray):
        return key._data.astype(jnp.int32)
    if isinstance(key, tuple):
        return tuple(_norm_index(k) for k in key)
    return key


# ---------------------------------------------------------------------------
# op invocation (analog of MXImperativeInvokeEx → Imperative::Invoke,
# reference src/c_api/c_api_ndarray.cc:81-143 / src/imperative/imperative.cc:87)
# ---------------------------------------------------------------------------

def invoke(op_name, inputs, params, out=None):
    from .. import profiler as _profiler
    _prof = _profiler._active and _profiler._state.profile_imperative
    if _prof:
        _prof_t0 = _profiler._now_us()
    op = _registry.get(op_name)
    params = {k: v for k, v in params.items() if v is not None or k in ("axis",)}
    # explicit device placement for no-input ops (creation/random): reference
    # semantics place the output on the requested ctx
    req_ctx = params.pop("ctx", None)
    if req_ctx is not None and not isinstance(req_ctx, Context):
        req_ctx = None
    arrs = [x._data if isinstance(x, NDArray) else jnp.asarray(x) for x in inputs]
    if "_training" in op.param_names and "_training" not in params:
        params["_training"] = _autograd.is_training()

    recording = (_autograd.is_recording()
                 and any(isinstance(x, NDArray) and x._ag is not None
                         for x in inputs))
    # only floating-point inputs are differentiable; ints/bools are constants
    diff_idx = [i for i, a in enumerate(arrs)
                if jnp.issubdtype(a.dtype, jnp.floating)]
    if recording and not diff_idx:
        recording = False
    if recording:
        diff_arrs = [arrs[i] for i in diff_idx]

        def fn(*xs):
            full = list(arrs)
            for i, x in zip(diff_idx, xs):
                full[i] = x
            if op.is_random:
                from .. import random as _random
                with _random.trace_scope(_base_key):
                    return op.fn(*full, **params)
            return op.fn(*full, **params)

        if op.is_random:
            from .. import random as _random
            _base_key = _random.next_key()
        out_data, vjp_fn = jax.vjp(fn, *diff_arrs)
    else:
        if req_ctx is not None:
            with jax.default_device(req_ctx.jax_device):
                out_data = op.fn(*arrs, **params)
        else:
            out_data = op.fn(*arrs, **params)
        vjp_fn = None

    single = not isinstance(out_data, tuple)
    outs_data = (out_data,) if single else out_data
    if req_ctx is not None:
        ctx = req_ctx
    elif inputs and isinstance(inputs[0], NDArray):
        ctx = inputs[0]._ctx
    else:
        ctx = current_context()

    # commit hidden aux-update outputs in place (reference eager BatchNorm
    # mutates moving_mean/moving_var aux inputs) and trim to visible outputs
    if op.aux_outputs:
        training = params.get("_training", True)
        if training:
            for in_slot, out_slot in zip(op.aux_inputs, op.aux_outputs):
                if in_slot < len(inputs) and isinstance(inputs[in_slot], NDArray):
                    inputs[in_slot]._rebind(outs_data[out_slot])
        n_vis = op.resolve_num_visible_outputs(params)
        if vjp_fn is not None and n_vis < len(outs_data):
            # tape sees only visible outputs; pad hidden cotangents with zeros
            hidden = [(o.shape, o.dtype) for o in outs_data[n_vis:]]
            orig_vjp = vjp_fn

            def vjp_fn(cot, _orig=orig_vjp, _hidden=hidden):
                cots = cot if isinstance(cot, tuple) else (cot,)
                padded = tuple(cots) + tuple(jnp.zeros(s, d) for s, d in _hidden)
                return _orig(padded)
        outs_data = outs_data[:n_vis]
        single = n_vis == 1

    out_nds = [NDArray(d, ctx=ctx) for d in outs_data]
    _engine.sync_point([d for d in outs_data])
    if _prof:
        # profiling measures to completion (the reference's engine events
        # cover kernel execution, not just dispatch)
        for d in outs_data:
            if hasattr(d, "block_until_ready"):
                try:
                    d.block_until_ready()
                except Exception:
                    pass
        _profiler.record_event(op_name, "operator", _prof_t0,
                               _profiler._now_us() - _prof_t0)

    if recording:
        _autograd.record_op(vjp_fn, [inputs[i] for i in diff_idx], out_nds,
                            name=op_name)

    if out is not None:
        targets = out if isinstance(out, (list, tuple)) else [out]
        for t, o in zip(targets, out_nds):
            t._rebind(o._data)
            if o._ag is not None:
                # carry tape linkage so autograd flows through out=; when not
                # recording (e.g. optimizer updates), keep the target's own
                # AGInfo so leaf grad sinks survive in-place updates
                t._ag = o._ag
        return out
    return out_nds[0] if single else tuple(out_nds)


def imperative_invoke(op_name, *inputs, out=None, **params):
    return invoke(op_name, list(inputs), params, out=out)


# ---------------------------------------------------------------------------
# creation
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy()
        if dtype is None:
            dtype = src.dtype
    elif isinstance(source_array, _np.ndarray):
        src = source_array
        if dtype is None:
            dtype = src.dtype
    else:
        # python lists/scalars default to float32 (reference
        # python/mxnet/ndarray/ndarray.py `array`: float32 unless source
        # carries an explicit dtype)
        src = _np.asarray(source_array)
        if dtype is None:
            dtype = mx_real_t
    return NDArray(_as_jax(src, normalize_dtype(dtype), ctx), ctx=ctx or current_context())


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    dt = normalize_dtype(dtype) or _np.float32
    return NDArray(_as_jax(jnp.zeros(shape, dt), None, ctx), ctx=ctx or current_context())


def ones(shape, ctx=None, dtype=None, **kwargs):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    dt = normalize_dtype(dtype) or _np.float32
    return NDArray(_as_jax(jnp.ones(shape, dt), None, ctx), ctx=ctx or current_context())


def full(shape, val, ctx=None, dtype=None):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    dt = normalize_dtype(dtype) or _np.float32
    return NDArray(_as_jax(jnp.full(shape, val, dt), None, ctx), ctx=ctx or current_context())


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    return invoke("_arange", [], {"start": start, "stop": stop, "step": step,
                                  "repeat": repeat, "dtype": dtype or "float32"})


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._data, source, destination), ctx=tensor._ctx)


def concat(*data, dim=1):
    return invoke("Concat", list(data), {"dim": dim})


def waitall():
    _engine.waitall()


def asnumpy_all(*arrays):
    """Fetch several arrays to host in ONE blocking device->host sync.

    The batched counterpart of per-array ``asnumpy()``: N separate
    fetches in a loop body are N device round-trips (mxlint MXL103);
    this moves the whole tuple in a single ``jax.device_get``. Non-device
    values (numpy, scalars) pass through unchanged.

        loss_h, out_h, label_h = nd.asnumpy_all(loss, out, label)
    """
    devs = [a._data if isinstance(a, NDArray) else a for a in arrays]
    pending = [d for d in devs if hasattr(d, "block_until_ready")]
    if pending:
        from .. import profiler as _profiler
        _profiler.record_host_sync(
            "d2h", sum(int(getattr(d, "nbytes", 0)) for d in pending))
        import jax
        devs = jax.device_get(devs)
    return tuple(_np.asarray(d) for d in devs)


# ---------------------------------------------------------------------------
# serialization — reference binary .params format (ndarray.cc:1583-1795),
# see serialization.py for the wire layout. Round-1/2 npz files still load.
# ---------------------------------------------------------------------------

_MAGIC = b"MXTPU001"  # legacy (rounds 1-2) npz container magic, read-only


def _to_record(a):
    """NDArray -> serialization record (numpy or sparse tuple)."""
    stype = getattr(a, "stype", "default")
    if stype == "row_sparse":
        return ("row_sparse", _np.asarray(a.data.asnumpy()),
                _np.asarray(a.indices.asnumpy()), a.shape)
    if stype == "csr":
        return ("csr", _np.asarray(a.data.asnumpy()),
                _np.asarray(a.indptr.asnumpy()),
                _np.asarray(a.indices.asnumpy()), a.shape)
    return a.asnumpy()


def _from_record(rec):
    if isinstance(rec, _np.ndarray):
        return array(rec)
    from .sparse import RowSparseNDArray, CSRNDArray
    if rec[0] == "row_sparse":
        _, data, indices, shape = rec
        return RowSparseNDArray(jnp.asarray(data), jnp.asarray(indices),
                                shape)
    _, data, indptr, indices, shape = rec
    return CSRNDArray(jnp.asarray(data), jnp.asarray(indptr),
                      jnp.asarray(indices), shape)


def save(fname, data):
    """Serialize NDArrays (list or name->array dict) to a file in the
    reference's versioned binary .params format (list magic 0x112,
    per-array V2 records — src/ndarray/ndarray.cc:1583-1795), so
    checkpoints interoperate with reference-lineage MXNet in both
    directions. Dense, row_sparse and csr arrays round-trip."""
    from . import serialization as _ser
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        keys = list(data.keys())
        arrays = [data[k] for k in keys]
    else:
        keys = []
        arrays = list(data)
    _ser.save_file(fname, [_to_record(a) for a in arrays], keys)


def load(fname):
    """Load a .params file: the reference binary format (including V1/V0
    legacy per-array records), or the npz container earlier builds of
    this library wrote."""
    from . import serialization as _ser
    with open(fname, "rb") as f:
        head = f.read(8)
    if head == _MAGIC:
        return _load_npz_legacy(fname)
    arrays, names = _ser.load_file(fname)
    arrays = [_from_record(r) for r in arrays]
    if not names:
        return arrays
    return dict(zip(names, arrays))


def _load_npz_legacy(fname):
    with open(fname, "rb") as f:
        f.read(8)
        z = _np.load(f, allow_pickle=False)
        keys = list(z["__keys__"])
        if not keys:
            out = []
            i = 0
            while "arr_%d" % i in z:
                out.append(array(z["arr_%d" % i]))
                i += 1
            return out
        return {str(k): array(z["data_" + str(k)]) for k in keys}
