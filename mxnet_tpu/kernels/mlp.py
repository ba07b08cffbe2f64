"""Fused scale+bias+activation Pallas kernel for the transformer MLP.

The MLP epilogue — per-feature scale (when present), bias add, then
GeLU/ReLU — sits between two MXU matmuls. This kernel runs it in one
VMEM pass over the (rows, features) view: per-feature f32 coefficients
stream as (1, block_f) tiles while the activation tensor is tiled
(block_r, block_f), everything computed in f32 with a single downcast
on the way out. Exact (erf) GeLU as in ``ops/nn.py``
``leaky_relu(act_type='gelu')``, with erf from an f32 rational fit
(:func:`_erf_f32`) because Mosaic lowers neither ``erf`` nor ``erfc``.

The matmul itself stays in XLA: the executor's fusion pass rewrites
``FullyConnected(+bias) -> gelu`` into ``FullyConnected(no_bias)``
followed by this kernel, so the bias+act epilogue never materializes.

Backward is the ``ops/pallas_flash.py`` pattern: ``jax.custom_vjp``
recomputing with the pure-JAX reference. Kernel name in exported HLO:
``mxk_scale_bias_act``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import tier

__all__ = ["fused_scale_bias_act", "eligible", "DEFAULT_CONFIG", "OP_NAME"]

OP_NAME = "scale_bias_act"
DEFAULT_CONFIG = {"block_r": 256, "block_f": 512}

_ACTS = ("gelu", "relu", "identity")
_RSQRT2 = 0.7071067811865476
# erf(x) ~= x * P(x^2) / Q(x^2) on [-4, 4]: the f32 rational fit XLA's own
# erf expands to (3.5e-7 max abs error against math.erf)
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


class _Cfg(NamedTuple):
    act: str
    block_r: int
    block_f: int
    interpret: bool


def _erf_f32(x):
    """erf from mul/add/div only: the Pallas TPU lowering has neither
    ``erf`` nor the ``erfc`` that ``jax.nn.gelu`` is built on."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.full_like(x, _ERF_P[0])
    for c in _ERF_P[1:]:
        p = p * x2 + c
    q = jnp.full_like(x, _ERF_Q[0])
    for c in _ERF_Q[1:]:
        q = q * x2 + c
    return x * p / q


def _act_f32(y, act):
    if act == "gelu":
        return 0.5 * y * (1.0 + _erf_f32(y * _RSQRT2))
    if act == "relu":
        return jnp.maximum(y, 0.0)
    return y


def _kernel(x_ref, sc_ref, b_ref, o_ref, *, act):
    y = (x_ref[...].astype(jnp.float32) * sc_ref[...]
         + b_ref[...])
    o_ref[...] = _act_f32(y, act).astype(o_ref.dtype)


def _call(x2, sc_row, b_row, act, block_r, block_f, interpret):
    R, F = x2.shape
    block_r = max(1, min(block_r, R))
    block_f = max(1, min(block_f, F))
    pad_r = (-R) % block_r
    pad_f = (-F) % block_f
    if pad_r or pad_f:
        x2 = jnp.pad(x2, ((0, pad_r), (0, pad_f)))
        sc_row = jnp.pad(sc_row, ((0, 0), (0, pad_f)))
        b_row = jnp.pad(b_row, ((0, 0), (0, pad_f)))
    grid = ((R + pad_r) // block_r, (F + pad_f) // block_f)
    out = pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, block_f), lambda ri, fi: (ri, fi)),
            pl.BlockSpec((1, block_f), lambda ri, fi: (0, fi)),
            pl.BlockSpec((1, block_f), lambda ri, fi: (0, fi)),
        ],
        out_specs=pl.BlockSpec((block_r, block_f),
                               lambda ri, fi: (ri, fi)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret,
        name="mxk_scale_bias_act",
    )(x2, sc_row, b_row)
    if pad_r or pad_f:
        out = out[:R, :F]
    return out


def _impl(x, scale, bias, cfg):
    F = x.shape[-1]
    x2 = x.reshape(-1, F)
    sc32 = (jnp.ones((F,), jnp.float32) if scale is None
            else scale.astype(jnp.float32))
    b32 = (jnp.zeros((F,), jnp.float32) if bias is None
           else bias.astype(jnp.float32))
    out2 = _call(x2, sc32[None, :], b32[None, :], cfg.act,
                 cfg.block_r, cfg.block_f, cfg.interpret)
    return out2.reshape(x.shape)


def _reference(x, scale, bias, act):
    y = x
    if scale is not None:
        y = y * scale.astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(x.dtype)
    if act == "gelu":
        return jax.nn.gelu(y, approximate=False)
    if act == "relu":
        return jax.nn.relu(y)
    return y


# one custom_vjp per operand arity so None operands never need cotangents
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_sb(x, scale, bias, cfg):
    return _impl(x, scale, bias, cfg)


def _fused_sb_fwd(x, scale, bias, cfg):
    return _impl(x, scale, bias, cfg), (x, scale, bias)


def _fused_sb_bwd(cfg, res, g):
    x, scale, bias = res
    _, vjp = jax.vjp(lambda a, s, b: _reference(a, s, b, cfg.act),
                     x, scale, bias)
    return vjp(g)


_fused_sb.defvjp(_fused_sb_fwd, _fused_sb_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_b(x, bias, cfg):
    return _impl(x, None, bias, cfg)


def _fused_b_fwd(x, bias, cfg):
    return _impl(x, None, bias, cfg), (x, bias)


def _fused_b_bwd(cfg, res, g):
    x, bias = res
    _, vjp = jax.vjp(lambda a, b: _reference(a, None, b, cfg.act), x, bias)
    return vjp(g)


_fused_b.defvjp(_fused_b_fwd, _fused_b_bwd)


def eligible(shape, dtype, act="gelu", scale_shape=None, bias_shape=None):
    """Strict guard; returns None when dispatchable, else the reason."""
    if len(shape) < 2:
        return "data must be >= 2-D (rows, features), got %d-D" % len(shape)
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return "dtype must be f32 or bf16, got %s" % jnp.dtype(dtype)
    if act not in _ACTS:
        return "unsupported activation %r" % (act,)
    F = shape[-1]
    for nm, s in (("scale", scale_shape), ("bias", bias_shape)):
        if s is not None and tuple(s) != (F,):
            return "%s shape %s != (features,)=(%d,)" % (nm, tuple(s), F)
    if F < 1:
        return "empty feature dim"
    return None


def shape_key_shapes(shape):
    """Tuner key: the flattened (rows, features) view."""
    rows = 1
    for d in shape[:-1]:
        rows *= d
    return ((rows, shape[-1]),)


def fused_scale_bias_act(x, scale=None, bias=None, *, act="gelu",
                         config=None, interpret=None):
    """``act(x * scale + bias)`` with per-feature f32 coefficients, one
    Pallas pass. ``scale``/``bias`` are optional (features,) vectors."""
    reason = eligible(x.shape, x.dtype, act=act,
                      scale_shape=None if scale is None else scale.shape,
                      bias_shape=None if bias is None else bias.shape)
    if reason is not None:
        raise ValueError("fused_scale_bias_act guard: %s" % reason)
    cfgd = dict(DEFAULT_CONFIG)
    cfgd.update(config or {})
    if interpret is None:
        interpret = tier.resolve_interpret()
    cfg = _Cfg(act, int(cfgd["block_r"]), int(cfgd["block_f"]),
               bool(interpret))
    if scale is None:
        if bias is None:
            return _fused_b(x, jnp.zeros((x.shape[-1],), jnp.float32), cfg)
        return _fused_b(x, bias, cfg)
    return _fused_sb(x, scale, bias if bias is not None
                     else jnp.zeros((x.shape[-1],), jnp.float32), cfg)


# eager/symbolic surface: mx.nd._contrib_FusedScaleBiasGeLU(x, scale, bias)
from ..ops.registry import register as _register  # noqa: E402


@_register("_contrib_FusedScaleBiasGeLU")
def _contrib_fused_scale_bias_gelu(data, scale=None, bias=None, *,
                                   act_type="gelu"):
    """Per-feature scale+bias+activation as a registered op."""
    return fused_scale_bias_act(data, scale, bias, act=act_type)
