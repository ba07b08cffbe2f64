"""Shape-manipulation, indexing, joining and linear-algebra ops.

Parity: src/operator/tensor/matrix_op.cc, dot-inl.h, indexing_op.cc,
ordering_op.cc, init_op.cc in the reference. All static-shape so XLA can tile
matmuls onto the MXU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register, alias
from ..base import index_dtype as _index_dtype


@register("Reshape")
def reshape(data, *, shape=None, reverse=False):
    """MXNet reshape with special codes 0 (copy dim), -1 (infer),
    -2 (copy rest), -3 (merge two), -4 (split, consumes two following)."""
    if shape is None:
        raise ValueError("reshape requires shape")
    src = list(data.shape)
    if reverse:
        src = src[::-1]
        shape = list(shape)[::-1]
    out = []
    i = 0  # index into src
    it = iter(range(len(shape)))
    shape = list(shape)
    j = 0
    while j < len(shape):
        s = shape[j]
        if s == 0:
            out.append(src[i]); i += 1
        elif s == -1:
            out.append(-1); i += 1
        elif s == -2:
            out.extend(src[i:]); i = len(src)
        elif s == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif s == -4:
            a, b = shape[j + 1], shape[j + 2]
            if a == -1:
                a = src[i] // b
            if b == -1:
                b = src[i] // a
            out.extend([a, b]); i += 1; j += 2
        else:
            out.append(s)
            if i < len(src):
                i += 1
        j += 1
    if reverse:
        out = out[::-1]
    return jnp.reshape(data, tuple(out))


alias("Reshape", "reshape")


@register("Flatten")
def flatten(data):
    return jnp.reshape(data, (data.shape[0], -1))


alias("Flatten", "flatten")


@register("transpose")
def transpose(data, *, axes=None):
    if axes is None or (hasattr(axes, "__len__") and len(axes) == 0):
        return jnp.transpose(data)
    return jnp.transpose(data, tuple(axes))


@register("expand_dims")
def expand_dims(data, *, axis):
    return jnp.expand_dims(data, axis)


@register("squeeze")
def squeeze(data, *, axis=None):
    return jnp.squeeze(data, axis=tuple(axis) if isinstance(axis, (list, tuple)) else axis)


@register("slice")
def slice_op(data, *, begin, end, step=None):
    nd = data.ndim
    begin = list(begin) + [None] * (nd - len(begin))
    end = list(end) + [None] * (nd - len(end))
    step = list(step or []) + [None] * (nd - len(step or []))
    idx = tuple(slice(b, e, s) for b, e, s in zip(begin, end, step))
    return data[idx]


@register("slice_axis")
def slice_axis(data, *, axis, begin=0, end=None):
    # end=None slices to the end of the axis (reference slice_axis accepts
    # None for both bounds)
    idx = [slice(None)] * data.ndim
    idx[axis] = slice(begin, end)
    return data[tuple(idx)]


@register("slice_like")
def slice_like(data, shape_like, *, axes=None):
    axes = range(data.ndim) if axes is None or len(axes) == 0 else axes
    idx = [slice(None)] * data.ndim
    for a in axes:
        idx[a] = slice(0, shape_like.shape[a])
    return data[tuple(idx)]


@register("Concat")
def concat(*data, dim=1):
    return jnp.concatenate(data, axis=dim)


alias("Concat", "concat")


@register("stack")
def stack(*data, axis=0):
    return jnp.stack(data, axis=axis)


@register("split", num_outputs=lambda p: int(p.get("num_outputs", 1)))
def split(data, *, num_outputs, axis=1, squeeze_axis=False):
    parts = jnp.split(data, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


alias("split", "SliceChannel")


@register("tile")
def tile(data, *, reps):
    return jnp.tile(data, tuple(reps))


@register("repeat")
def repeat(data, *, repeats, axis=None):
    return jnp.repeat(data, repeats, axis=axis)


@register("pad")
def pad(data, *, mode="constant", pad_width=None, constant_value=0.0):
    # MXNet pad_width is flat (before,after) per axis
    pw = [(pad_width[2 * i], pad_width[2 * i + 1]) for i in range(data.ndim)]
    if mode == "constant":
        return jnp.pad(data, pw, constant_values=constant_value)
    return jnp.pad(data, pw, mode={"edge": "edge", "reflect": "reflect"}[mode])


alias("pad", "Pad")


@register("flip")
def flip(data, *, axis):
    return jnp.flip(data, axis=axis)


alias("flip", "reverse")


@register("swapaxes")
def swapaxes(data, *, dim1=0, dim2=0):
    return jnp.swapaxes(data, dim1, dim2)


alias("swapaxes", "SwapAxis")


@register("depth_to_space")
def depth_to_space(data, *, block_size):
    n, c, h, w = data.shape
    b = block_size
    x = jnp.reshape(data, (n, b, b, c // (b * b), h, w))
    x = jnp.transpose(x, (0, 3, 4, 1, 5, 2))
    return jnp.reshape(x, (n, c // (b * b), h * b, w * b))


@register("space_to_depth")
def space_to_depth(data, *, block_size):
    n, c, h, w = data.shape
    b = block_size
    x = jnp.reshape(data, (n, c, h // b, b, w // b, b))
    x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
    return jnp.reshape(x, (n, c * b * b, h // b, w // b))


# ---------------------------------------------------------------------------
# dot / linalg
# ---------------------------------------------------------------------------

@register("dot")
def dot(lhs, rhs, *, transpose_a=False, transpose_b=False):
    a = lhs.T if transpose_a else lhs
    b = rhs.T if transpose_b else rhs
    if a.ndim == 1 and b.ndim == 1:
        return jnp.dot(a, b)
    # MXNet dot: reduce over last axis of a and first axis of b
    return jnp.tensordot(a, b, axes=([a.ndim - 1], [0]))


@register("batch_dot")
def batch_dot(lhs, rhs, *, transpose_a=False, transpose_b=False):
    a = jnp.swapaxes(lhs, -1, -2) if transpose_a else lhs
    b = jnp.swapaxes(rhs, -1, -2) if transpose_b else rhs
    return jnp.matmul(a, b)




@register("khatri_rao")
def khatri_rao(*args):
    out = args[0]
    for m in args[1:]:
        out = jnp.einsum("i...,j...->ij...", out, m).reshape(
            out.shape[0] * m.shape[0], *out.shape[1:])
    return out


# ---------------------------------------------------------------------------
# indexing
# ---------------------------------------------------------------------------

@register("take")
def take(a, indices, *, axis=0, mode="clip"):
    return jnp.take(a, indices.astype(jnp.int32), axis=axis,
                    mode="clip" if mode != "wrap" else "wrap")


@register("pick")
def pick(data, index, *, axis=-1, keepdims=False, mode="clip"):
    idx = jnp.clip(index.astype(jnp.int32), 0, data.shape[axis] - 1)
    out = jnp.take_along_axis(data, jnp.expand_dims(idx, axis), axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


@register("gather_nd")
def gather_nd(data, indices):
    idx = tuple(indices.astype(jnp.int32))
    return data[idx]


@register("scatter_nd")
def scatter_nd(data, indices, *, shape):
    out = jnp.zeros(tuple(shape), dtype=data.dtype)
    idx = tuple(indices.astype(jnp.int32))
    return out.at[idx].set(data)


@register("one_hot")
def one_hot(indices, *, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    from ..base import normalize_dtype
    oh = jax.nn.one_hot(indices.astype(jnp.int32), depth)
    out = oh * on_value + (1.0 - oh) * off_value
    return out.astype(normalize_dtype(dtype))


@register("boolean_mask_dense")
def boolean_mask_dense(data, mask):
    # dynamic-shape op: not traceable; eager-only fallback
    import numpy as np
    return jnp.asarray(np.asarray(data)[np.asarray(mask).astype(bool)])


# ---------------------------------------------------------------------------
# ordering
# ---------------------------------------------------------------------------

@register("sort")
def sort(data, *, axis=-1, is_ascend=True):
    out = jnp.sort(data, axis=axis)
    return out if is_ascend else jnp.flip(out, axis=axis)


@register("argsort")
def argsort(data, *, axis=-1, is_ascend=True, dtype="float32"):
    from ..base import normalize_dtype
    out = jnp.argsort(data if is_ascend else -data, axis=axis)
    return out.astype(normalize_dtype(dtype))


@register("topk", num_outputs=lambda p: 2 if p.get("ret_typ") == "both" else 1)
def topk(data, *, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    from ..base import normalize_dtype
    d = jnp.moveaxis(data, axis, -1)
    vals, raw_idx = jax.lax.top_k(-d if is_ascend else d, k)
    if is_ascend:
        vals = -vals
    if ret_typ == "mask":
        # 1 at every top-k position, 0 elsewhere, in the DATA's layout
        # (reference ordering_op ReturnType::kReturnMask); built from the
        # raw integer indices before any float cast
        onehot = jax.nn.one_hot(raw_idx, d.shape[-1], dtype=data.dtype)
        return jnp.moveaxis(onehot.sum(axis=-2), -1, axis)
    vals = jnp.moveaxis(vals, -1, axis)
    idx = jnp.moveaxis(raw_idx, -1, axis).astype(normalize_dtype(dtype))
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idx
    return idx


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

@register("diag")
def diag(data, *, k=0):
    if data.ndim == 1:
        return jnp.diag(data, k=k)
    return jnp.diagonal(data, offset=k, axis1=-2, axis2=-1)


@register("shape_array")
def shape_array(data):
    return jnp.asarray(data.shape, dtype=_index_dtype())


@register("size_array")
def size_array(data):
    return jnp.asarray([data.size], dtype=_index_dtype())


@register("histogram", num_outputs=2)
def histogram(data, *, bin_cnt=10, range=None):
    lo, hi = range if range is not None else (float(data.min()), float(data.max()))
    counts, edges = jnp.histogram(data, bins=bin_cnt, range=(lo, hi))
    return counts.astype(_index_dtype()), edges.astype(data.dtype)


@register("ravel_multi_index")
def ravel_multi_index(data, *, shape):
    strides = []
    acc = 1
    for s in reversed(shape):
        strides.append(acc)
        acc *= s
    strides = jnp.asarray(list(reversed(strides)), dtype=data.dtype)
    return jnp.sum(data * strides[:, None], axis=0)


@register("unravel_index")
def unravel_index(data, *, shape):
    idx = data.astype(_index_dtype())
    out = []
    for s in reversed(shape):
        out.append(idx % s)
        idx = idx // s
    return jnp.stack(list(reversed(out)), axis=0).astype(data.dtype)


@register("sequence_mask")
def sequence_mask(data, sequence_length=None, *, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data * 1.0
    maxlen = data.shape[axis]
    steps = jnp.arange(maxlen)
    mask = steps[:, None] < sequence_length[None, :].astype(steps.dtype)  # (T, B)
    shape = [1] * data.ndim
    shape[axis] = maxlen
    batch_axis = 1 if axis == 0 else 0
    shape[batch_axis] = data.shape[batch_axis]
    mask = jnp.reshape(mask if axis == 0 else mask.T, shape)
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


alias("sequence_mask", "SequenceMask")


@register("sequence_last")
def sequence_last(data, sequence_length=None, *, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.take(data, data.shape[axis] - 1, axis=axis)
    idx = (sequence_length - 1).astype(jnp.int32)
    d = jnp.moveaxis(data, axis, 0)  # (T, B, ...)
    return jax.vmap(lambda t, i: t[i], in_axes=(1, 0))(d, idx)


alias("sequence_last", "SequenceLast")


@register("sequence_reverse")
def sequence_reverse(data, sequence_length=None, *, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=axis)
    d = jnp.moveaxis(data, axis, 0)
    T = d.shape[0]
    steps = jnp.arange(T)

    def rev_one(col, L):
        idx = jnp.where(steps < L, L - 1 - steps, steps)
        return col[idx]

    out = jax.vmap(rev_one, in_axes=(1, 0), out_axes=1)(d, sequence_length.astype(jnp.int32))
    return jnp.moveaxis(out, 0, axis)


alias("sequence_reverse", "SequenceReverse")


def _param_dtype_out(in_dtypes, params):
    """argsort/topk indices take the `dtype` param (default f32), not the
    input dtype; topk ret_typ=value/both lead with the input dtype."""
    import numpy as _np2
    from ..base import normalize_dtype
    idx_dt = _np2.dtype(normalize_dtype(params.get("dtype", "float32")))
    d = in_dtypes[0] if in_dtypes and in_dtypes[0] is not None \
        else _np2.dtype("float32")
    ret = params.get("ret_typ", "indices")
    if ret == "value":
        return list(in_dtypes), [d]
    if ret == "both":
        return list(in_dtypes), [d, idx_dt]
    return list(in_dtypes), [idx_dt]


from .registry import set_op_meta as _set_op_meta  # noqa: E402
for _name, _slots in (("take", (1,)), ("pick", (1,)), ("gather_nd", (1,)),
                      ("one_hot", (0,))):
    _set_op_meta(_name, index_inputs=_slots)
_set_op_meta("argsort", dtype_hook=_param_dtype_out)
_set_op_meta("topk", dtype_hook=_param_dtype_out)


@register("reshape_like")
def reshape_like(lhs, rhs, *, lhs_begin=None, lhs_end=None, rhs_begin=None,
                 rhs_end=None):
    """Reshape lhs to the shape of rhs (parity:
    src/operator/tensor/elemwise_unary_op_basic.cc:429 — gradient flows to
    lhs only; rhs contributes shape, not values). The begin/end ranges
    replace ONLY lhs dims [lhs_begin, lhs_end) with rhs dims
    [rhs_begin, rhs_end), keeping the rest of lhs's shape (reference
    ReshapeLikeParam)."""

    def _rng(b, e, ndim, what):
        b = 0 if b is None else (b + ndim if b < 0 else b)
        e = ndim if e is None else (e + ndim if e < 0 else e)
        if not (0 <= b <= e <= ndim):   # reference GetReshapeLikeParams
            raise ValueError(
                "reshape_like: invalid %s range [%s, %s) for %d dims"
                % (what, b, e, ndim))
        return b, e

    lb, le = _rng(lhs_begin, lhs_end, lhs.ndim, "lhs")
    rb, re = _rng(rhs_begin, rhs_end, rhs.ndim, "rhs")
    shape = lhs.shape[:lb] + rhs.shape[rb:re] + lhs.shape[le:]
    return jnp.reshape(lhs, shape)


@register("batch_take")
def batch_take(a, indices):
    """out[i] = a[i, indices[i]] (parity:
    src/operator/tensor/indexing_op.cc:730 — deprecated alias of pick
    along axis 1)."""
    idx = indices.astype(_index_dtype()).reshape((-1,))
    return jnp.take_along_axis(
        a, idx[:, None], axis=1).reshape(idx.shape)


def _slice_tuple(shape, begin, end, step=None):
    """MXNet SliceParam begin/end/step (entries may be None) -> python
    slice tuple over leading len(begin) axes."""
    step = step if step is not None and len(step) else (None,) * len(begin)
    out = []
    for b, e, s in zip(begin, end, step):
        out.append(slice(b, e, s))
    return tuple(out)


@register("_slice_assign")
def slice_assign(lhs, rhs, *, begin, end, step=None):
    """Write rhs into lhs[begin:end:step] (parity:
    src/operator/tensor/matrix_op.cc:434 _slice_assign/_crop_assign).
    XLA scatters in place when the buffer is donated; under jit the
    functional update fuses."""
    return lhs.at[_slice_tuple(lhs.shape, begin, end, step)].set(rhs)


@register("_slice_assign_scalar")
def slice_assign_scalar(data, *, scalar=0.0, begin=(), end=(), step=None):
    """Fill data[begin:end:step] with a scalar (parity:
    src/operator/tensor/matrix_op.cc:459)."""
    return data.at[_slice_tuple(data.shape, begin, end, step)].set(
        jnp.asarray(scalar, data.dtype))


_set_op_meta("batch_take", index_inputs=(1,))
alias("_slice_assign", "_crop_assign")
alias("_slice_assign_scalar", "_crop_assign_scalar")
