"""Plain reference of the ResNet v1 training step (He et al. 2015,
arXiv:1512.03385, Table 1), in straightforward ``jax.numpy`` and float32
at the highest matmul precision. It imports nothing of the program: the
benchmark makes the weights here from the seed, hands them to the program
and keeps a copy for this file.

What it follows of the program (``mxnet_tpu/models/resnet.py`` and the
MXNet 1.x operator semantics it keeps), each a published convention:

* v1 units: conv-BN-ReLU, the stride on the first convolution of a unit,
  a 1x1 convolution + BN on a shortcut whose shape changes, ReLU after the
  addition; the stem is 7x7/2 convolution, BN, ReLU, 3x3/2 max pooling;
* BatchNorm with batch statistics (biased variance), eps 2e-5, running
  statistics ``moving * 0.9 + batch * 0.1``;
* the loss is the mean cross-entropy of the batch (SoftmaxOutput's
  gradient ``softmax - onehot`` times ``rescale_grad = 1 / batch``);
* SGD with momentum on float32 weights: ``g += wd * w`` for names that
  end in ``_weight`` or ``_gamma``, ``m = momentum * m - lr * g``,
  ``w += m``.

Departure, for memory only: each residual unit is rematerialised
(``jax.checkpoint``), so that float32 activations of the timed batch fit
one chip beside nothing else. The arithmetic is unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# depth -> (units per stage, bottleneck units?)
DEPTHS = {
    18: ((2, 2, 2, 2), False),
    34: ((3, 4, 6, 3), False),
    50: ((3, 4, 6, 3), True),
    101: ((3, 4, 23, 3), True),
    152: ((3, 8, 36, 3), True),
}
BN_EPS = 2e-5
BN_MOMENTUM = 0.9
HIGHEST = lax.Precision.HIGHEST


def _unit_plan(num_layers):
    """[(name, filters, stride, dim_match)] for every residual unit."""
    units, bottleneck = DEPTHS[num_layers]
    widths = (256, 512, 1024, 2048) if bottleneck else (64, 128, 256, 512)
    plan = []
    for stage, (n, width) in enumerate(zip(units, widths), start=1):
        for j in range(n):
            plan.append(("stage%d_unit%d" % (stage, j + 1), width,
                         (1 if stage == 1 else 2) if j == 0 else 1, j > 0))
    return plan, bottleneck


def conv_plan(num_layers, image_hw):
    """Every convolution as (name, c_in, c_out, kernel, stride, out_hw):
    the shapes both the parameters and the analytic FLOP count follow."""
    plan, bottleneck = _unit_plan(num_layers)
    convs = []
    hw = (image_hw + 2 * 3 - 7) // 2 + 1
    convs.append(("conv0", 3, 64, 7, 2, hw))
    hw = (hw + 2 * 1 - 3) // 2 + 1          # max pooling 3x3 / 2, pad 1
    c_in = 64
    for name, width, stride, dim_match in plan:
        out_hw = (hw - 1) // stride + 1
        if bottleneck:
            mid = width // 4
            convs.append((name + "_conv1", c_in, mid, 1, stride, out_hw))
            convs.append((name + "_conv2", mid, mid, 3, 1, out_hw))
            convs.append((name + "_conv3", mid, width, 1, 1, out_hw))
        else:
            convs.append((name + "_conv1", c_in, width, 3, stride, out_hw))
            convs.append((name + "_conv2", width, width, 3, 1, out_hw))
        if not dim_match:
            convs.append((name + "_sc_conv", c_in, width, 1, stride, out_hw))
        c_in, hw = width, out_hw
    return convs, c_in


def param_shapes(cfg):
    """(arguments, auxiliary states): name -> shape, under the names the
    program's symbol gives its variables."""
    convs, c_last = conv_plan(cfg["num_layers"], cfg["image_shape"][1])
    args, aux = {}, {}
    for name, c_in, c_out, k, _stride, _hw in convs:
        args[name + "_weight"] = (c_out, c_in, k, k)
        bn = name.replace("conv", "bn")     # conv0 -> bn0, _sc_conv -> _sc_bn
        args[bn + "_gamma"] = (c_out,)
        args[bn + "_beta"] = (c_out,)
        aux[bn + "_moving_mean"] = (c_out,)
        aux[bn + "_moving_var"] = (c_out,)
    args["fc1_weight"] = (cfg["num_classes"], c_last)
    args["fc1_bias"] = (cfg["num_classes"],)
    return args, aux


def init_params(cfg, seed, sharding=None):
    """Initial weights from the seed, on the device, in one jitted call:
    He-normal convolutions and classifier (fan-in), BatchNorm at identity,
    running statistics at (0, 1). With ``zero_last_gamma`` the scale of
    the last BatchNorm of every residual unit starts at 0, so that every
    unit starts as the identity (Goyal et al. 2017, arXiv:1706.02677,
    section 5.1)."""
    arg_shapes, aux_shapes = param_shapes(cfg)
    _units, bottleneck = DEPTHS[cfg["num_layers"]]
    last_gamma = "_bn%d_gamma" % (3 if bottleneck else 2)

    def make(key):
        args, aux = {}, {}
        for i, (name, shape) in enumerate(sorted(arg_shapes.items())):
            if name.endswith("_weight"):
                fan_in = 1
                for d in shape[1:]:
                    fan_in *= d
                args[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32) \
                    * jnp.sqrt(2.0 / fan_in)
            elif name.endswith("_gamma"):
                zero = cfg.get("zero_last_gamma") \
                    and name.endswith(last_gamma)
                args[name] = (jnp.zeros if zero else jnp.ones)(
                    shape, jnp.float32)
            else:
                args[name] = jnp.zeros(shape, jnp.float32)
        for name, shape in aux_shapes.items():
            aux[name] = (jnp.ones if name.endswith("_var") else jnp.zeros)(
                shape, jnp.float32)
        return args, aux

    return jax.jit(make, out_shardings=sharding)(
        jax.random.PRNGKey(seed % (2 ** 31)))


@jax.custom_vjp
def fp8_operand(x):
    """An operand as float8 e4m3 would hold it, under one scale a tensor;
    the gradient passes straight through. The lower-precision control of
    a bfloat16 configuration."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


fp8_operand.defvjp(lambda x: (fp8_operand(x), None), lambda _, g: (g,))


def bf16_operand(x):
    """An operand as bfloat16 holds it: the lower-precision control of a
    float32 configuration."""
    return x.astype(jnp.bfloat16).astype(x.dtype)


def _conv(x, w, stride, operand):
    k = w.shape[2]
    pad = (k - 1) // 2
    return lax.conv_general_dilated(
        operand(x), operand(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)


def _bn(x, args, aux, new_aux, name):
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(x - mean[None, :, None, None]), axis=(0, 2, 3))
    new_aux[name + "_moving_mean"] = \
        aux[name + "_moving_mean"] * BN_MOMENTUM + mean * (1 - BN_MOMENTUM)
    new_aux[name + "_moving_var"] = \
        aux[name + "_moving_var"] * BN_MOMENTUM + var * (1 - BN_MOMENTUM)
    inv = lax.rsqrt(var + BN_EPS) * args[name + "_gamma"]
    return (x - mean[None, :, None, None]) * inv[None, :, None, None] \
        + args[name + "_beta"][None, :, None, None]


def _unit(x, args, aux, name, stride, dim_match, bottleneck, operand):
    new_aux = {}
    n_conv = 3 if bottleneck else 2
    y = x
    for i in range(1, n_conv + 1):
        y = _conv(y, args["%s_conv%d_weight" % (name, i)],
                  stride if i == 1 else 1, operand)
        y = _bn(y, args, aux, new_aux, "%s_bn%d" % (name, i))
        if i < n_conv:
            y = jax.nn.relu(y)
    if not dim_match:
        x = _conv(x, args[name + "_sc_conv_weight"], stride, operand)
        x = _bn(x, args, aux, new_aux, name + "_sc_bn")
    return jax.nn.relu(y + x), new_aux


def forward(cfg, args, aux, data, operand=lambda x: x):
    """Logits of a training-mode forward pass, and the new running
    statistics."""
    plan, bottleneck = _unit_plan(cfg["num_layers"])
    new_aux = {}
    x = _conv(data, args["conv0_weight"], 2, operand)
    x = jax.nn.relu(_bn(x, args, aux, new_aux, "bn0"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          ((0, 0), (0, 0), (1, 1), (1, 1)))
    for name, _width, stride, dim_match in plan:
        unit = jax.checkpoint(functools.partial(
            _unit, name=name, stride=stride, dim_match=dim_match,
            bottleneck=bottleneck, operand=operand))
        x, unit_aux = unit(x, args, aux)
        new_aux.update(unit_aux)
    x = jnp.mean(x, axis=(2, 3))
    logits = jnp.dot(operand(x), operand(args["fc1_weight"]).T,
                     precision=HIGHEST) + args["fc1_bias"]
    return logits, new_aux


def cross_entropy_rows(logits, label):
    """Every row's cross-entropy; the loss is their mean."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(
        logp, label.astype(jnp.int32)[:, None], axis=-1)[:, 0]


def train_step(cfg, args, aux, mom, data, label, operand=lambda x: x):
    """One SGD-momentum step: (every row's loss before the update, new
    weights, new running statistics, new momentum)."""
    opt = cfg["optimizer"]

    def loss_fn(a):
        logits, new_aux = forward(cfg, a, aux, data, operand)
        rows = cross_entropy_rows(logits, label)
        return jnp.mean(rows), (new_aux, rows)

    (_, (new_aux, rows)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(args)
    new_args, new_mom = {}, {}
    for name, w in args.items():
        g = grads[name]
        if name.endswith("_weight") or name.endswith("_gamma"):
            g = g + opt["wd"] * w
        new_mom[name] = opt["momentum"] * mom[name] - opt["learning_rate"] * g
        new_args[name] = w + new_mom[name]
    return rows, new_args, new_aux, new_mom
