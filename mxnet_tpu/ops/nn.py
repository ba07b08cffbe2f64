"""Neural-network operators.

Parity: src/operator/nn/ in the reference (Convolution, FullyConnected,
BatchNorm, Pooling, Activation, Dropout, softmax family, LayerNorm, Embedding
— fully_connected.cc:239-326 is the canonical registration). TPU-native
design notes:

* FullyConnected / Convolution / Deconvolution map straight to
  ``lax.dot_general`` / ``lax.conv_general_dilated`` → MXU. Layout semantics
  stay NCHW (reference default) while XLA's layout assignment is free to pick
  the TPU-optimal physical layout.
* Where the reference dispatches to MIOpen/cuDNN autotuned kernels
  (src/operator/nn/cudnn/), we rely on XLA conv emitters; no algo search.
* BatchNorm keeps running stats as explicit aux arrays (reference aux_states
  moving_mean/moving_var), returned as extra outputs so the functional core
  stays pure; the Gluon/Module layers wire them back to aux storage.
* Dropout draws from :mod:`mxnet_tpu.random` (trace-safe key threading).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, alias, program_count, program_gauge
from .. import random as _random


# ---------------------------------------------------------------------------
# FullyConnected
# ---------------------------------------------------------------------------

@register("FullyConnected")
def fully_connected(data, weight, bias=None, *, num_hidden=0, no_bias=False,
                    flatten=True):
    if flatten:
        x = jnp.reshape(data, (data.shape[0], -1))
    else:
        x = data
    # weight layout: (num_hidden, in_units) — reference convention
    program_count("dense/flops_fwd", 2 * x.size * weight.shape[0])
    out = jnp.matmul(x, weight.T)
    if not no_bias and bias is not None:
        out = out + bias
    return out


alias("FullyConnected", "fully_connected")
program_gauge("dense/flops_fwd",
              "operations of the forward pass of every FullyConnected and "
              "_contrib_SwiGLU of the training program traced last: 2 x "
              "rows x in x out a product, from shapes, whatever runs it")


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------

def _conv_dims(kernel):
    return len(kernel)


def _tuplize(v, n):
    if v is None:
        return (0,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


@register("Convolution")
def convolution(data, weight, bias=None, *, kernel, num_filter,
                stride=None, dilate=None, pad=None, num_group=1,
                no_bias=False, layout=None):
    n = _conv_dims(kernel)
    stride = _tuplize(stride, n) or (1,) * n
    stride = tuple(s if s else 1 for s in stride)
    dilate = tuple(d if d else 1 for d in _tuplize(dilate, n))
    padding = [(p, p) for p in _tuplize(pad, n)]
    if n == 1:
        dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                        ("NCH", "OIH", "NCH"))
    elif n == 2:
        dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                        ("NCHW", "OIHW", "NCHW"))
    else:
        dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                        ("NCDHW", "OIDHW", "NCDHW"))
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride, padding=padding,
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + jnp.reshape(bias, (1, -1) + (1,) * n)
    return out


@register("Deconvolution")
def deconvolution(data, weight, bias=None, *, kernel, num_filter,
                  stride=None, dilate=None, pad=None, adj=None,
                  target_shape=None, num_group=1, no_bias=True, layout=None):
    n = _conv_dims(kernel)
    stride = tuple(s if s else 1 for s in _tuplize(stride, n))
    dilate = tuple(d if d else 1 for d in _tuplize(dilate, n))
    pad_ = _tuplize(pad, n)
    adj_ = _tuplize(adj, n)
    # Transposed convolution == gradient of convolution wrt its input.
    # conv_general_dilated computes CORRELATION, so the kernel must be
    # spatially flipped to realize the transpose (caught by torch
    # conv_transpose2d parity); weight layout (reference):
    # (in_channels, num_filter//num_group, *kernel)
    weight = jnp.flip(weight, axis=tuple(range(2, 2 + n)))
    spatial = data.shape[2:]
    out_spatial = tuple(
        (spatial[i] - 1) * stride[i] - 2 * pad_[i]
        + dilate[i] * (kernel[i] - 1) + 1 + adj_[i]
        for i in range(n))
    if target_shape and any(int(t) > 0 for t in target_shape):
        # all-zero target_shape means UNSET (reference bCal guard)
        # reference DeconvolutionParam::InferPad (deconvolution-inl.h:121):
        # target_shape REPLACES user pad/adj — total = stride*(in-1) +
        # dilated_ksize - target, adj = total % 2, pad = (total+1)//2
        target = tuple(int(t) for t in target_shape)
        dksize = tuple(dilate[i] * (kernel[i] - 1) + 1 for i in range(n))
        total = tuple(stride[i] * (spatial[i] - 1) + dksize[i] - target[i]
                      for i in range(n))
        if any(t < 0 for t in total):
            raise ValueError("too big target shape %s (natural zero-pad "
                             "output is %s)" % (target, tuple(
                                 stride[i] * (spatial[i] - 1) + dksize[i]
                                 for i in range(n))))
        adj_ = tuple(t % 2 for t in total)
        pad_ = tuple((t + 1) // 2 for t in total)
        out_spatial = target
    # lax.conv_transpose with flipped kernel reproduces gradient-of-conv.
    if n == 2:
        dn = lax.conv_dimension_numbers(
            (data.shape[0], data.shape[1]) + out_spatial,
            weight.shape, ("NCHW", "IOHW", "NCHW"))
    elif n == 1:
        dn = lax.conv_dimension_numbers(
            (data.shape[0], data.shape[1]) + out_spatial,
            weight.shape, ("NCH", "IOH", "NCH"))
    else:
        dn = lax.conv_dimension_numbers(
            (data.shape[0], data.shape[1]) + out_spatial,
            weight.shape, ("NCDHW", "IODHW", "NCDHW"))
    pads = []
    for i in range(n):
        lo = dilate[i] * (kernel[i] - 1) - pad_[i]
        hi = dilate[i] * (kernel[i] - 1) - pad_[i] + adj_[i]
        pads.append((lo, hi))
    if num_group != 1:
        # grouped deconv: split channels, run per group, concat
        xs = jnp.split(data, num_group, axis=1)
        ws = jnp.split(weight, num_group, axis=0)
        outs = [lax.conv_general_dilated(
            x, w, window_strides=(1,) * n, padding=pads,
            lhs_dilation=stride, rhs_dilation=dilate,
            dimension_numbers=dn)
            for x, w in zip(xs, ws)]
        out = jnp.concatenate(outs, axis=1)
    else:
        out = lax.conv_general_dilated(
            data, weight, window_strides=(1,) * n, padding=pads,
            lhs_dilation=stride, rhs_dilation=dilate,
            dimension_numbers=dn)
    if not no_bias and bias is not None:
        out = out + jnp.reshape(bias, (1, -1) + (1,) * n)
    return out


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

@register("Pooling")
def pooling(data, *, kernel=(), pool_type="max", global_pool=False,
            stride=None, pad=None, pooling_convention="valid",
            count_include_pad=True, cudnn_off=False, p_value=2):
    n = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * n
        pad = (0,) * n
    stride = tuple(s if s else 1 for s in _tuplize(stride, n)) if not global_pool else (1,) * n
    pad_ = _tuplize(pad, n) if not global_pool else (0,) * n
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad_)
    if pooling_convention == "full":
        # ceil-mode output: add extra padding on the high side when needed
        extra = []
        for i in range(n):
            size = data.shape[2 + i] + 2 * pad_[i] - kernel[i]
            rem = size % stride[i]
            extra.append(0 if rem == 0 else stride[i] - rem)
        padding = ((0, 0), (0, 0)) + tuple(
            (p, p + e) for p, e in zip(pad_, extra))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1.0
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return s / cnt
    if pool_type == "lp":
        # reference pooling-inl.h: Lp pooling with integer p (1/2/3 common)
        p = int(p_value)
        if p == 1:
            return lax.reduce_window(jnp.abs(data), 0.0, lax.add, window,
                                     strides, padding)
        if p == 2:
            p2 = lax.reduce_window(jnp.square(data), 0.0, lax.add, window,
                                   strides, padding)
            return jnp.sqrt(p2)
        pp = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add, window,
                               strides, padding)
        return pp ** (1.0 / p)
    raise ValueError("unknown pool_type %r" % pool_type)


alias("Pooling", "pooling")


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def _bn_widened_sums(x, red):
    """Per-channel sum and sum-of-squares of a low-precision tensor,
    accumulated in f32 *inside* the reduction via dot_general's
    preferred_element_type — no convert of the activation tensor.

    bf16·bf16 products are exact in f32 (8-bit mantissas), so the results
    equal an f32 upcast-then-reduce bit-for-bit up to summation order.
    """
    axis = [i for i in range(x.ndim) if i not in red][0]
    ones = jnp.ones(tuple(x.shape[i] for i in red), x.dtype)
    s1 = lax.dot_general(x, ones,
                         ((red, tuple(range(len(red)))), ((), ())),
                         preferred_element_type=jnp.float32)
    s2 = lax.dot_general(x, x, ((red, red), ((axis,), (axis,))),
                         preferred_element_type=jnp.float32)
    n = 1
    for i in red:
        n *= x.shape[i]
    return s1, s2, n


def _bn_coef_apply(x, axis, *cols32):
    """Concatenate per-channel f32 coefficient vectors, downcast with a
    single convert, and return them reshaped for broadcasting against x.
    One convert per BN per pass instead of one per full activation
    tensor."""
    C = x.shape[axis]
    coef = jnp.concatenate(cols32).astype(x.dtype)
    shape = [1] * x.ndim
    shape[axis] = C
    return [jnp.reshape(coef[i * C:(i + 1) * C], shape)
            for i in range(len(cols32))]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_lowp_train(x, g32, b32, eps, axis):
    out, mean, var, _ = _bn_lowp_fwd_impl(x, g32, b32, eps, axis)
    return out, mean, var


def _bn_lowp_fwd_impl(x, g32, b32, eps, axis):
    red = tuple(i for i in range(x.ndim) if i != axis)
    s1, s2, n = _bn_widened_sums(x, red)
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    scale = inv * g32
    shift = b32 - mean * scale
    sc, sh = _bn_coef_apply(x, axis, scale, shift)
    return x * sc + sh, mean, var, inv


def _bn_lowp_train_fwd(x, g32, b32, eps, axis):
    out, mean, var, inv = _bn_lowp_fwd_impl(x, g32, b32, eps, axis)
    return (out, mean, var), (x, g32, mean, inv)


def _bn_lowp_train_bwd(eps, axis, res, cots):
    dy, _dmean, _dvar = cots  # stat outputs carry no gradient
    x, g32, mean, inv = res
    red = tuple(i for i in range(x.ndim) if i != axis)
    ones = jnp.ones(tuple(x.shape[i] for i in red), x.dtype)
    s_dy = lax.dot_general(dy, ones,
                           ((red, tuple(range(len(red)))), ((), ())),
                           preferred_element_type=jnp.float32)
    s_dyx = lax.dot_general(dy, x, ((red, red), ((axis,), (axis,))),
                            preferred_element_type=jnp.float32)
    n = 1
    for i in red:
        n *= x.shape[i]
    dgamma = inv * (s_dyx - mean * s_dy)
    dbeta = s_dy
    # dx = A*dy + B*x + C with per-channel f32 coefficients, applied bf16
    A = g32 * inv
    B = -A * inv * dgamma / n
    Cc = -A * s_dy / n - B * mean
    a, b, c = _bn_coef_apply(x, axis, A, B, Cc)
    dx = dy * a + x * b + c
    return dx, dgamma, dbeta


_bn_lowp_train.defvjp(_bn_lowp_train_fwd, _bn_lowp_train_bwd)


@register("BatchNorm", num_outputs=5)
def batch_norm(data, gamma, beta, moving_mean, moving_var, *, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False,
               _training=True):
    """Returns (out, batch_mean, batch_var, new_moving_mean, new_moving_var).

    Visible outputs follow the reference's FNumVisibleOutputs (3 when
    output_mean_var else 1); the trailing two are the updated aux states —
    the reference mutates moving stats in place (src/operator/nn/batch_norm.cc),
    our pure-functional form returns them and the invoke layer/executor
    commits them. Same observable semantics, XLA-friendly.

    Mixed precision: stats/scale math stays f32 regardless of data dtype
    (reference cuDNN BN semantics), but for bf16/f16 activations the f32
    widening happens *inside* the reductions (dot_general with
    preferred_element_type=f32) and the normalize/scale/shift runs in the
    data dtype off a single per-channel downcast — the activation tensor
    is never round-tripped through f32 in fwd or bwd.
    """
    axis = axis % data.ndim
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    g32 = g.astype(jnp.float32) if g.dtype != jnp.float32 else g
    b32 = beta.astype(jnp.float32) if beta.dtype != jnp.float32 else beta
    lowp = data.dtype in (jnp.bfloat16, jnp.float16)
    if _training and not use_global_stats:
        if lowp:
            out, mean, var = _bn_lowp_train(data, g32, b32, float(eps), axis)
        else:
            mean = jnp.mean(data, axis=red_axes)
            var = jnp.var(data, axis=red_axes)
        new_mean = moving_mean * momentum + mean * (1.0 - momentum)
        new_var = moving_var * momentum + var * (1.0 - momentum)
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    if not (_training and not use_global_stats and lowp):
        inv = lax.rsqrt(var + eps)
        if lowp:
            sc, sh = _bn_coef_apply(data, axis, inv * g32,
                                    b32 - mean * (inv * g32))
            out = data * sc + sh
        else:
            shape = [1] * data.ndim
            shape[axis] = data.shape[axis]
            out = (data - jnp.reshape(mean, shape)) \
                * jnp.reshape(inv * g32, shape) + jnp.reshape(b32, shape)
    return (out.astype(data.dtype), lax.stop_gradient(mean),
            lax.stop_gradient(var),
            lax.stop_gradient(new_mean), lax.stop_gradient(new_var))


@register("_rnn_begin_state")
def _rnn_begin_state(ref, *, state_shape, batch_axis=0):
    """Zero initial RNN state whose batch dim comes from `ref` (entries of
    0 in state_shape are replaced by ref.shape[batch_axis]); keeps
    shape inference flowing forward when cells unroll with default
    states."""
    shp = tuple(ref.shape[batch_axis] if int(s) == 0 else int(s)
                for s in state_shape)
    return jnp.zeros(shp, ref.dtype)


@register("LayerNorm")
def layer_norm(data, gamma, beta, *, axis=-1, eps=1e-5, output_mean_var=False):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    return out * jnp.reshape(gamma, shape) + jnp.reshape(beta, shape)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, *, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return out * jnp.reshape(gamma, shape) + jnp.reshape(beta, shape)


@register("L2Normalization")
def l2_normalization(data, *, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        nrm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    elif mode == "channel":
        nrm = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps)
    else:  # spatial
        red = tuple(range(2, data.ndim))
        nrm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / nrm


@register("LRN")
def lrn(data, *, nsize, alpha=1e-4, beta=0.75, knorm=2.0):
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = jnp.stack([padded[:, i:i + data.shape[1]] for i in range(nsize)], 0).sum(0)
    return data / jnp.power(knorm + alpha * window / nsize, beta)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

@register("Activation")
def activation(data, *, act_type="relu"):
    acts = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
    }
    return acts[act_type](data)


@register("LeakyReLU")
def leaky_relu(data, gamma=None, *, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        a, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, a * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "prelu":
        g = gamma
        shape = [1] * data.ndim
        if g.ndim == 1 and data.ndim > 1:
            shape[1] = g.shape[0]
            g = jnp.reshape(g, shape)
        return jnp.where(data >= 0, data, g * data)
    if act_type == "rrelu":
        key = _random.next_key()
        slope_r = jax.random.uniform(key, data.shape, data.dtype,
                                     lower_bound, upper_bound)
        return jnp.where(data >= 0, data, slope_r * data)
    raise ValueError(act_type)


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------

@register("softmax")
def softmax(data, *, axis=-1, temperature=None, length=None):
    x = data / temperature if temperature else data
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(data, *, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("softmin")
def softmin(data, *, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.softmax(-x, axis=axis)


@register("SoftmaxActivation")
def softmax_activation(data, *, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    lp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    nll = -jnp.take_along_axis(lp, lab[:, None], axis=-1)
    return jnp.sum(nll)


def _softmax_output_impl(data, label, grad_scale, ignore_label, multi_output,
                         use_ignore, normalization, smooth_alpha):
    axis = 1 if multi_output else -1
    return jax.nn.softmax(data, axis=axis)


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _softmax_output_core(data, label, grad_scale, ignore_label, multi_output,
                         use_ignore, normalization, smooth_alpha):
    return _softmax_output_impl(data, label, grad_scale, ignore_label,
                                multi_output, use_ignore, normalization,
                                smooth_alpha)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, multi_output,
                        use_ignore, normalization, smooth_alpha):
    out = _softmax_output_impl(data, label, grad_scale, ignore_label,
                               multi_output, use_ignore, normalization,
                               smooth_alpha)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, ignore_label, multi_output, use_ignore,
                        normalization, smooth_alpha, res, g):
    """Loss-layer gradient: softmax(data) - one_hot(label), the reference's
    SoftmaxOutput backward (src/operator/softmax_output-inl.h) — the incoming
    cotangent is ignored (SoftmaxOutput is a head/loss op)."""
    out, label = res
    axis = 1 if multi_output else -1
    ncls = out.shape[axis]
    lab = label.astype(jnp.int32)
    oh = jax.nn.one_hot(lab, ncls, dtype=out.dtype)
    if smooth_alpha:
        oh = oh * (1.0 - smooth_alpha) + smooth_alpha / ncls
    if multi_output:
        # label shape (N, spatial...) -> one_hot gives (..., C); move C to axis 1
        oh = jnp.moveaxis(oh, -1, 1)
    grad = out - oh
    if use_ignore:
        mask = (lab != jnp.asarray(ignore_label, jnp.int32))
        if multi_output:
            grad = grad * mask[:, None].astype(grad.dtype)
        else:
            grad = grad * mask[..., None].astype(grad.dtype)
    scale = grad_scale
    if normalization == "batch":
        scale = scale / out.shape[0]
    elif normalization == "valid" and use_ignore:
        valid = jnp.maximum(jnp.sum((lab != jnp.asarray(ignore_label, jnp.int32))
                                    .astype(grad.dtype)), 1.0)
        scale = scale / valid
    return (grad * scale, jnp.zeros_like(label))


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput")
def softmax_output(data, label=None, *, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    if label is None:
        axis = 1 if multi_output else -1
        return jax.nn.softmax(data, axis=axis)
    return _softmax_output_core(data, label, grad_scale, ignore_label,
                                multi_output, use_ignore, normalization,
                                smooth_alpha)


@register("CTCLoss")
def ctc_loss(data, label, data_lengths=None, label_lengths=None, *,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    # data: (T, N, C) activations (pre-softmax), label: (N, L); optional
    # per-sample lengths (reference src/operator/nn/ctc_loss: 4-input op)
    logp = jax.nn.log_softmax(data, axis=-1)
    T, N, C = data.shape
    lab = label.astype(jnp.int32)
    L = lab.shape[1]
    blank = 0 if blank_label == "first" else C - 1
    # extended label sequence with blanks: length 2L+1
    ext = jnp.full((N, 2 * L + 1), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    S = 2 * L + 1
    neg_inf = -1e30

    if use_label_lengths and label_lengths is not None:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        # count of non-(-1/0-pad) entries; MXNet pads with -1 or 0
        pad_mask = (lab >= 0) & (lab != 0) if blank == 0 else (lab >= 0)
        lab_len = jnp.sum(pad_mask.astype(jnp.int32), axis=1)
    ext_len = 2 * lab_len + 1

    def step(alpha_prev, logp_t):
        # alpha: (N, S)
        emit = jnp.take_along_axis(logp_t, ext, axis=1)  # (N, S)
        a0 = alpha_prev
        a1 = jnp.concatenate([jnp.full((N, 1), neg_inf), alpha_prev[:, :-1]], 1)
        a2 = jnp.concatenate([jnp.full((N, 2), neg_inf), alpha_prev[:, :-2]], 1)
        # skip allowed only when ext[s] != blank and ext[s] != ext[s-2]
        ext_m2 = jnp.concatenate([jnp.full((N, 2), -2, jnp.int32), ext[:, :-2]], 1)
        can_skip = (ext != blank) & (ext != ext_m2)
        a2 = jnp.where(can_skip, a2, neg_inf)
        alpha = jnp.logaddexp(jnp.logaddexp(a0, a1), a2) + emit
        return alpha, alpha

    alpha0 = jnp.full((N, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    first_lab = ext[:, 1]
    alpha0 = alpha0.at[:, 1].set(
        jnp.take_along_axis(logp[0], first_lab[:, None], 1)[:, 0])
    alpha_T, alpha_seq = lax.scan(step, alpha0, logp[1:])
    if use_data_lengths and data_lengths is not None:
        # per-sample final alpha at t = data_length-1
        alpha_all = jnp.concatenate([alpha0[None], alpha_seq], axis=0)  # (T,N,S)
        t_idx = jnp.clip(data_lengths.astype(jnp.int32) - 1, 0, T - 1)
        alpha_T = alpha_all[t_idx, jnp.arange(N)]                       # (N,S)
    idx_last = (ext_len - 1)[:, None]
    idx_prev = (ext_len - 2)[:, None]
    ll = jnp.logaddexp(
        jnp.take_along_axis(alpha_T, idx_last, 1),
        jnp.take_along_axis(alpha_T, jnp.maximum(idx_prev, 0), 1))[:, 0]
    return -ll


alias("CTCLoss", "ctc_loss")


# ---------------------------------------------------------------------------
# Dropout / Embedding
# ---------------------------------------------------------------------------

@register("Dropout", is_random=True)
def dropout(data, *, p=0.5, mode="training", axes=(), cudnn_off=False,
            _training=True):
    # mode='always': apply dropout regardless of train/predict (MC dropout;
    # reference src/operator/nn/dropout-inl.h DropoutParam::mode)
    if (not _training and mode != "always") or p <= 0.0:
        return data * 1.0
    key = _random.next_key()
    shape = list(data.shape)
    for a in axes or ():
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape))
    return jnp.where(mask, data / keep, jnp.zeros_like(data))


def _maybe_take_rows(data, weight):
    """Kernel-tier dispatch for the embedding gather: the Pallas
    scalar-prefetch row-DMA kernel when the tier policy + guard allow,
    else None (caller falls back to jnp.take)."""
    from ..kernels import tier as _ktier
    if not _ktier.enabled():
        return None
    from ..kernels import take as _ktake
    reason = _ktake.eligible(weight.shape, weight.dtype, data.shape,
                             data.dtype)
    go, cfg = _ktier.should_dispatch(
        _ktake.OP_NAME,
        _ktake.shape_key_shapes(weight.shape, data.shape),
        weight.dtype, guard_reason=reason)
    if not go:
        return None
    return _ktake.take_rows(weight, data, config=cfg)


@register("Embedding")
def embedding(data, weight, *, input_dim=0, output_dim=0, dtype="float32",
              sparse_grad=False):
    out = _maybe_take_rows(data, weight)
    if out is not None:
        return out
    # clip mode: the reference take/Embedding clamp out-of-range rows,
    # and the Pallas take_rows kernel clips too — dispatch must never
    # change numerics
    return jnp.take(weight, data.astype(jnp.int32), axis=0, mode="clip")


@register("_contrib_SparseEmbedding")
def sparse_embedding(data, weight, *, input_dim=0, output_dim=0,
                     dtype="float32", deterministic=False):
    """Embedding whose weight gradient is row-sparse (parity:
    src/operator/tensor/indexing_op.cc:98-133 SparseEmbedding). The
    forward is a plain gather; the sparse-gradient contract lives in the
    storage layer (gluon Parameter grad_stype='row_sparse' /
    RowSparseNDArray), which the optimizers' lazy row updates consume —
    XLA scatters the VJP, so there is no dense-vs-rsp kernel split to
    reproduce."""
    out = _maybe_take_rows(data, weight)
    if out is not None:
        return out
    return jnp.take(weight, data.astype(jnp.int32), axis=0, mode="clip")


# ---------------------------------------------------------------------------
# RNN (fused; reference: src/operator/rnn-inl.h, cudnn_rnn-inl.h)
# ---------------------------------------------------------------------------

def _lstm_cell(xproj, h, c, wh, bh):
    # xproj = x @ wx.T + bx, hoisted out of the scan (see rnn())
    gates = xproj + h @ wh.T + bh
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
    g = jnp.tanh(g)
    c2 = f * c + i * g
    h2 = o * jnp.tanh(c2)
    return h2, c2


def _gru_cell(xproj, h, wh, bh):
    xr, xz, xn = jnp.split(xproj, 3, axis=-1)
    hr, hz, hn = jnp.split(h @ wh.T + bh, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    z = jax.nn.sigmoid(xz + hz)
    n = jnp.tanh(xn + r * hn)
    return (1 - z) * n + z * h


def _rnn_cell(xproj, h, wh, bh, act):
    return act(xproj + h @ wh.T + bh)


def _rnn_param_shapes(mode, input_size, state_size, num_layers, bidirectional):
    mult = {"lstm": 4, "gru": 3, "rnn_tanh": 1, "rnn_relu": 1}[mode]
    dirs = 2 if bidirectional else 1
    shapes = []
    for layer in range(num_layers):
        for d in range(dirs):
            in_sz = input_size if layer == 0 else state_size * dirs
            shapes.append(("wx", (mult * state_size, in_sz)))
            shapes.append(("wh", (mult * state_size, state_size)))
    for layer in range(num_layers):
        for d in range(dirs):
            shapes.append(("bx", (mult * state_size,)))
            shapes.append(("bh", (mult * state_size,)))
    return shapes


def rnn_param_size(mode, input_size, state_size, num_layers, bidirectional):
    return sum(int(jnp.prod(jnp.asarray(s))) for _, s in
               _rnn_param_shapes(mode, input_size, state_size, num_layers, bidirectional))


def _unpack_rnn_params(params, mode, input_size, state_size, num_layers,
                       bidirectional):
    shapes = _rnn_param_shapes(mode, input_size, state_size, num_layers, bidirectional)
    out, off = [], 0
    for _, s in shapes:
        n = 1
        for d in s:
            n *= d
        out.append(jnp.reshape(lax.dynamic_slice(params, (off,), (n,)), s))
        off += n
    return out


@register("RNN", num_outputs=lambda p: 3 if p.get("mode") == "lstm" and p.get("state_outputs") else (2 if p.get("state_outputs") else 1))
def rnn(data, parameters, state, state_cell=None, *, state_size, num_layers,
        mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
        projection_size=None, lstm_state_clip_min=None,
        lstm_state_clip_max=None, lstm_state_clip_nan=False):
    """Fused multi-layer RNN over ``lax.scan`` (time major: (T, N, I)).

    The TPU analog of the reference's miopenRNN fused kernels
    (src/operator/cudnn_rnn-inl.h:43), with the cuDNN scheduling trick
    done at the XLA level: the input projection ``x @ wx.T + bx`` for ALL
    timesteps is hoisted out of the scan into one (T*N, I)x(I, G*H)
    matmul — a large, MXU-efficient contraction — so the sequential scan
    body carries only the (N, H)x(H, G*H) recurrence.
    """
    T, N, I = data.shape
    dirs = 2 if bidirectional else 1
    flat = _unpack_rnn_params(parameters, mode, I, state_size, num_layers,
                              bidirectional)
    mult = {"lstm": 4, "gru": 3, "rnn_tanh": 1, "rnn_relu": 1}[mode]
    n_gate_pairs = num_layers * dirs
    wxs = flat[0:2 * n_gate_pairs:2]
    whs = flat[1:2 * n_gate_pairs:2]
    bxs = flat[2 * n_gate_pairs::2]
    bhs = flat[2 * n_gate_pairs + 1::2]

    h0 = state  # (L*dirs, N, H)
    c0 = state_cell if mode == "lstm" else None
    x = data
    h_finals, c_finals = [], []
    act = jnp.tanh if mode != "rnn_relu" else jax.nn.relu

    for layer in range(num_layers):
        outs_dir = []
        for d in range(dirs):
            li = layer * dirs + d
            wx, wh, bx, bh = wxs[li], whs[li], bxs[li], bhs[li]
            xs = x if d == 0 else jnp.flip(x, axis=0)
            # whole-sequence input projection: one big MXU matmul
            xp = jnp.einsum("tni,gi->tng", xs, wx) + bx
            if mode == "lstm":
                def step(carry, xt):
                    h, c = carry
                    h2, c2 = _lstm_cell(xt, h, c, wh, bh)
                    return (h2, c2), h2
                (hT, cT), ys = lax.scan(step, (h0[li], c0[li]), xp)
                c_finals.append(cT)
            elif mode == "gru":
                def step(h, xt):
                    h2 = _gru_cell(xt, h, wh, bh)
                    return h2, h2
                hT, ys = lax.scan(step, h0[li], xp)
            else:
                def step(h, xt):
                    h2 = _rnn_cell(xt, h, wh, bh, act)
                    return h2, h2
                hT, ys = lax.scan(step, h0[li], xp)
            h_finals.append(hT)
            if d == 1:
                ys = jnp.flip(ys, axis=0)
            outs_dir.append(ys)
        x = jnp.concatenate(outs_dir, axis=-1) if dirs == 2 else outs_dir[0]
        if p > 0.0 and layer < num_layers - 1:
            key = _random.next_key()
            mask = jax.random.bernoulli(key, 1.0 - p, x.shape)
            x = jnp.where(mask, x / (1.0 - p), jnp.zeros_like(x))

    hF = jnp.stack(h_finals, axis=0)
    if mode == "lstm":
        cF = jnp.stack(c_finals, axis=0)
        if state_outputs:
            return x, hF, cF
        return x
    if state_outputs:
        return x, hF
    return x


# ---------------------------------------------------------------------------
# Upsampling / resize
# ---------------------------------------------------------------------------

@register("UpSampling")
def upsampling(*data, scale=2, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=512):
    x = data[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        if len(data) > 1 and multi_input_mode == "concat":
            outs = [out]
            for d in data[1:]:
                s = out.shape[2] // d.shape[2]
                outs.append(jnp.repeat(jnp.repeat(d, s, axis=2), s, axis=3))
            out = jnp.concatenate(outs, axis=1)
        return out
    raise NotImplementedError("bilinear UpSampling via Deconvolution")


def _interp_axis_align_corners(x, out_len, axis):
    """1-D linear interpolation along `axis` with the reference's
    align-corners ratio (bilinear_resize.cc:69: rwidth = (in-1)/(out-1);
    jax.image.resize uses half-pixel centers, which the reference kernel
    does NOT)."""
    in_len = x.shape[axis]
    if out_len == in_len:
        return x
    if out_len > 1 and in_len > 1:
        pos = jnp.arange(out_len, dtype=jnp.float32) \
            * ((in_len - 1) / (out_len - 1))
    else:
        pos = jnp.zeros((out_len,), jnp.float32)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, in_len - 1)
    t = pos - lo
    shape = [1] * x.ndim
    shape[axis] = out_len
    t = t.reshape(shape).astype(x.dtype)
    return jnp.take(x, lo, axis=axis) * (1 - t) \
        + jnp.take(x, hi, axis=axis) * t


@register("_contrib_BilinearResize2D")
def bilinear_resize(data, *, height=0, width=0, scale_height=None, scale_width=None):
    n, c, h, w = data.shape
    oh = height or int(h * scale_height)
    ow = width or int(w * scale_width)
    out = _interp_axis_align_corners(data, oh, 2)
    return _interp_axis_align_corners(out, ow, 3)


def _adaptive_pool_matrix(in_len, out_len, dtype):
    """Averaging matrix A (out,in): A[i,j] = 1/len(win_i) for j in the
    reference's variable window [floor(i*in/out), ceil((i+1)*in/out))
    (contrib/adaptive_avg_pooling.cc). Dense matmul form: exact for any
    size ratio and XLA/MXU-friendly."""
    import numpy as _np
    a = _np.zeros((out_len, in_len), _np.float32)
    for i in range(out_len):
        s = (i * in_len) // out_len
        e = -(-((i + 1) * in_len) // out_len)   # ceil
        a[i, s:e] = 1.0 / (e - s)
    return jnp.asarray(a, dtype)


@register("_contrib_AdaptiveAvgPooling2D")
def adaptive_avg_pool(data, *, output_size=1):
    if isinstance(output_size, int):
        oh = ow = output_size
    else:
        oh, ow = output_size
    n, c, h, w = data.shape
    if h % oh == 0 and w % ow == 0:
        x = data.reshape(n, c, oh, h // oh, ow, w // ow)
        return x.mean(axis=(3, 5))
    ah = _adaptive_pool_matrix(h, oh, data.dtype)     # (oh, h)
    aw = _adaptive_pool_matrix(w, ow, data.dtype)     # (ow, w)
    return jnp.einsum("oh,nchw,pw->ncop", ah, data, aw)


# ---------------------------------------------------------------------------
# Symbolic-layer metadata: parameter-shape inference hooks + aux slots.
# Role parity: the backward direction of the reference's FInferShape
# (e.g. src/operator/nn/fully_connected.cc FullyConnectedShape infers the
# weight shape from data + num_hidden) and aux_states declaration
# (batch_norm.cc moving_mean/moving_var).
# ---------------------------------------------------------------------------
from .registry import set_op_meta as _set_op_meta


def _fc_shapes(ins, p):
    data, weight, bias = (ins + [None] * 3)[:3]
    nh = int(p.get("num_hidden", 0))
    out = list(ins)
    if data is not None:
        in_units = 1
        if p.get("flatten", True):
            for d in data[1:]:
                in_units *= d
        else:
            in_units = data[-1]
        if len(ins) > 1 and ins[1] is None:
            out[1] = (nh, in_units)
    if len(ins) > 2 and ins[2] is None:
        out[2] = (nh,)
    return out


def _conv_shapes(ins, p):
    data, weight, bias = (ins + [None] * 3)[:3]
    nf = int(p["num_filter"])
    k = tuple(p["kernel"])
    ng = int(p.get("num_group", 1))
    out = list(ins)
    if data is not None and len(ins) > 1 and ins[1] is None:
        out[1] = (nf, data[1] // ng) + k
    if len(ins) > 2 and ins[2] is None:
        out[2] = (nf,)
    return out


def _deconv_shapes(ins, p):
    data, weight, bias = (ins + [None] * 3)[:3]
    nf = int(p["num_filter"])
    k = tuple(p["kernel"])
    ng = int(p.get("num_group", 1))
    out = list(ins)
    if data is not None and len(ins) > 1 and ins[1] is None:
        out[1] = (data[1], nf // ng) + k
    if len(ins) > 2 and ins[2] is None:
        out[2] = (nf,)
    return out


def _bn_shapes(ins, p):
    data = ins[0]
    out = list(ins)
    if data is not None:
        ax = int(p.get("axis", 1)) % len(data)
        c = (data[ax],)
        for i in range(1, min(5, len(ins))):
            if out[i] is None:
                out[i] = c
    return out


def _ln_shapes(ins, p):
    data = ins[0]
    out = list(ins)
    if data is not None:
        ax = int(p.get("axis", -1)) % len(data)
        c = (data[ax],)
        for i in range(1, min(3, len(ins))):
            if out[i] is None:
                out[i] = c
    return out


def _in_shapes(ins, p):
    data = ins[0]
    out = list(ins)
    if data is not None:
        c = (data[1],)
        for i in range(1, min(3, len(ins))):
            if out[i] is None:
                out[i] = c
    return out


def _embedding_shapes(ins, p):
    out = list(ins)
    if len(ins) > 1 and ins[1] is None:
        out[1] = (int(p["input_dim"]), int(p["output_dim"]))
    return out


def _rnn_shapes(ins, p):
    data, params_, state = (ins + [None] * 4)[:3]
    out = list(ins)
    if data is not None:
        H = int(p["state_size"])
        L = int(p["num_layers"])
        dirs = 2 if p.get("bidirectional") else 1
        I = data[2]
        if len(ins) > 1 and out[1] is None:
            out[1] = (rnn_param_size(p.get("mode", "lstm"), I, H, L,
                                     bool(p.get("bidirectional", False))),)
        if len(ins) > 2 and out[2] is None:
            out[2] = (L * dirs, data[1], H)
        if len(ins) > 3 and out[3] is None:
            out[3] = (L * dirs, data[1], H)
    return out


def _prelu_shapes(ins, p):
    out = list(ins)
    if p.get("act_type") == "prelu" and len(ins) > 1 and ins[1] is None and ins[0] is not None:
        out[1] = (ins[0][1] if len(ins[0]) > 1 else 1,)
    return out


_set_op_meta("FullyConnected", shape_hook=_fc_shapes)
_set_op_meta("Convolution", shape_hook=_conv_shapes)
_set_op_meta("Deconvolution", shape_hook=_deconv_shapes)
def _bn_dtypes(in_dtypes, params):
    """fp16/bf16 data keeps f32 gamma/beta/moving stats and f32 batch
    stats (reference BN FInferType pins aux float32)."""
    import numpy as _np2
    d = in_dtypes[0] if in_dtypes and in_dtypes[0] is not None \
        else _np2.dtype("float32")
    f32 = _np2.dtype("float32")
    return [d, f32, f32, f32, f32], [d, f32, f32, f32, f32]


_set_op_meta("BatchNorm", shape_hook=_bn_shapes, dtype_hook=_bn_dtypes,
             aux_inputs=(3, 4), aux_outputs=(3, 4), f32_inputs=(1, 2),
             num_visible_outputs=lambda p: 3 if p.get("output_mean_var") else 1)
_set_op_meta("LayerNorm", shape_hook=_ln_shapes)
_set_op_meta("InstanceNorm", shape_hook=_in_shapes)
_set_op_meta("Embedding", shape_hook=_embedding_shapes, index_inputs=(0,))
_set_op_meta("_contrib_SparseEmbedding", shape_hook=_embedding_shapes,
             index_inputs=(0,))
_set_op_meta("RNN", shape_hook=_rnn_shapes)
_set_op_meta("LeakyReLU", shape_hook=_prelu_shapes)


# ---------------------------------------------------------------------------
# Regression output heads (reference: src/operator/regression_output-inl.h)
# Forward is identity/sigmoid; backward seeds (pred - label)/batch like the
# reference, via custom_vjp (loss-head convention as SoftmaxOutput).
# ---------------------------------------------------------------------------

def _regression_core(transform, grad_fn):
    @_partial(jax.custom_vjp, nondiff_argnums=(2,))
    def core(data, label, grad_scale):
        return transform(data)

    def fwd(data, label, grad_scale):
        out = transform(data)
        return out, (out, label)

    def bwd(grad_scale, res, g):
        out, label = res
        # reference scales by per-sample output count (label.Size()/batch),
        # NOT by batch size (src/operator/regression_output-inl.h backward)
        num_output = max(label.size // label.shape[0], 1)
        grad = grad_fn(out, label) * (grad_scale / num_output)
        return (grad, jnp.zeros_like(label))

    core.defvjp(fwd, bwd)
    return core


_linreg_core = _regression_core(lambda x: x * 1.0, lambda o, l: o - l.reshape(o.shape))
_maereg_core = _regression_core(lambda x: x * 1.0,
                                lambda o, l: jnp.sign(o - l.reshape(o.shape)))
_logreg_core = _regression_core(jax.nn.sigmoid,
                                lambda o, l: o - l.reshape(o.shape))


@register("LinearRegressionOutput")
def linear_regression_output(data, label=None, *, grad_scale=1.0):
    if label is None:
        return data * 1.0
    return _linreg_core(data, label, grad_scale)


@register("MAERegressionOutput")
def mae_regression_output(data, label=None, *, grad_scale=1.0):
    if label is None:
        return data * 1.0
    return _maereg_core(data, label, grad_scale)


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label=None, *, grad_scale=1.0):
    if label is None:
        return jax.nn.sigmoid(data)
    return _logreg_core(data, label, grad_scale)


@_partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _make_loss_core(data, grad_scale, normalization, valid_thresh):
    return data * 1.0


def _make_loss_fwd(data, grad_scale, normalization, valid_thresh):
    return data * 1.0, data


def _make_loss_bwd(grad_scale, normalization, valid_thresh, data, g):
    """MakeLoss backward (reference src/operator/make_loss-inl.h:92-118):
    the input IS the loss, so its gradient is the constant grad_scale —
    divided by batch ('batch') or by the count of elements above
    valid_thresh ('valid'). The incoming cotangent is ignored (head op
    seeded with all-ones, like SoftmaxOutput)."""
    scale = jnp.asarray(grad_scale, data.dtype)
    if normalization == "batch":
        scale = scale / data.shape[0]
    elif normalization == "valid":
        valid = jnp.maximum(
            jnp.sum((data > valid_thresh).astype(data.dtype)), 1.0)
        scale = scale / valid
    return (jnp.full(data.shape, scale, data.dtype),)


_make_loss_core.defvjp(_make_loss_fwd, _make_loss_bwd)


@register("MakeLoss")
def make_loss(data, *, grad_scale=1.0, normalization="null",
              valid_thresh=0.0):
    """Turn any symbol into a loss head (reference make_loss.cc): forward
    is identity; backward injects grad_scale (grad_scale=0 makes a
    monitoring output that contributes no gradient, the SSD pattern)."""
    return _make_loss_core(data, float(grad_scale), normalization,
                           float(valid_thresh))


alias("MakeLoss", "make_loss")


def _softmax_out_shapes(ins, p):
    out = list(ins)
    data = ins[0]
    if data is not None and len(ins) > 1 and ins[1] is None:
        if p.get("multi_output"):
            out[1] = (data[0],) + tuple(data[2:])
        else:
            out[1] = tuple(data[:-1])
    return out


def _reg_out_shapes(ins, p):
    out = list(ins)
    if ins[0] is not None and len(ins) > 1 and ins[1] is None:
        out[1] = tuple(ins[0])
    return out


_set_op_meta("SoftmaxOutput", shape_hook=_softmax_out_shapes)
_set_op_meta("softmax_cross_entropy", shape_hook=_softmax_out_shapes)
_set_op_meta("LinearRegressionOutput", shape_hook=_reg_out_shapes)
_set_op_meta("MAERegressionOutput", shape_hook=_reg_out_shapes)
_set_op_meta("LogisticRegressionOutput", shape_hook=_reg_out_shapes)
