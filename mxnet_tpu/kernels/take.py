"""Embedding / sparse row-gather Pallas kernel (scalar-prefetch DMA).

``jnp.take(weight, idx, axis=0)`` lowers to a generic XLA gather; on TPU
that routes through gather machinery that can't exploit the structure of
an embedding lookup (whole contiguous rows). This kernel uses the Pallas
scalar-prefetch idiom instead: the int32 index vector is prefetched to
SMEM before the grid runs, and each grid cell's ``BlockSpec`` index_map
reads ``idx_ref[i]`` to DMA exactly row ``idx[i]`` (in ``block_d`` lane
chunks) from the HBM-resident table into VMEM and copy it out — a pure
data-movement kernel, no compute.

Out-of-range indices clamp, matching ``jnp.take``'s default clip mode.
Backward is the recompute pattern: ``jax.custom_vjp`` differentiating
pure-JAX ``jnp.take``, which XLA turns into the usual scatter-add (the
row-sparse gradient contract of ``_contrib_SparseEmbedding`` lives a
layer up and is unchanged). Kernel name in exported HLO:
``mxk_take_rows``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tier

__all__ = ["take_rows", "gather_pages", "eligible", "DEFAULT_CONFIG",
           "OP_NAME"]

OP_NAME = "take_rows"
DEFAULT_CONFIG = {"block_d": 512}


class _Cfg(NamedTuple):
    block_d: int
    interpret: bool


def _gather_kernel(idx_ref, w_ref, o_ref):
    del idx_ref  # consumed by the index_maps
    o_ref[...] = w_ref[...]


def _call(weight, idx_flat, block_d, interpret):
    V, D = weight.shape
    L = idx_flat.shape[0]
    block_d = max(1, min(block_d, D))
    grid = (L, D // block_d)
    # Rows ride a unit middle axis: Mosaic wants a block's last two dims
    # (8, 128)-aligned or equal to the array's, and a (1, block_d) block
    # of a (V, D) table is neither, while (1, block_d) of (V, 1, D) is.
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(
                (None, 1, block_d),
                lambda i, di, idx_ref: (idx_ref[i], 0, di))],
            out_specs=pl.BlockSpec(
                (None, 1, block_d), lambda i, di, idx_ref: (i, 0, di)),
        ),
        out_shape=jax.ShapeDtypeStruct((L, 1, D), weight.dtype),
        interpret=interpret,
        name="mxk_take_rows",
    )(idx_flat, weight.reshape(V, 1, D))
    return out.reshape(L, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused(weight, idx_flat, cfg):
    return _call(weight, idx_flat, cfg.block_d, cfg.interpret)


def _fused_fwd(weight, idx_flat, cfg):
    return _fused(weight, idx_flat, cfg), (weight, idx_flat)


def _fused_bwd(cfg, res, g):
    weight, idx_flat = res
    _, vjp = jax.vjp(lambda w: jnp.take(w, idx_flat, axis=0), weight)
    (dw,) = vjp(g)
    # integer primal: float0 cotangent (there is no gradient to an index)
    return dw, np.zeros(idx_flat.shape, dtype=jax.dtypes.float0)


_fused.defvjp(_fused_fwd, _fused_bwd)


def eligible(weight_shape, weight_dtype, idx_shape, idx_dtype):
    """Strict guard; returns None when dispatchable, else the reason."""
    if len(weight_shape) != 2:
        return "weight must be (vocab, dim) 2-D, got %d-D" % \
            len(weight_shape)
    if jnp.dtype(weight_dtype) not in (jnp.dtype(jnp.float32),
                                       jnp.dtype(jnp.bfloat16)):
        return "weight dtype must be f32 or bf16, got %s" % \
            jnp.dtype(weight_dtype)
    V, D = weight_shape
    if D % 128 != 0:
        return "embedding dim %d not lane-aligned (must be a multiple " \
            "of 128; padding the table would copy it)" % D
    if V < 1:
        return "empty vocab"
    if len(idx_shape) not in (1, 2):
        return "indices must be 1-D or 2-D, got %d-D" % len(idx_shape)
    if not (jnp.issubdtype(jnp.dtype(idx_dtype), jnp.integer)
            or jnp.issubdtype(jnp.dtype(idx_dtype), jnp.floating)):
        return "indices dtype %s not castable to int32" % \
            jnp.dtype(idx_dtype)
    n = 1
    for d in idx_shape:
        n *= d
    if n < 1:
        return "empty index set"
    return None


def shape_key_shapes(weight_shape, idx_shape):
    """Tuner key: (vocab, dim) table and the flattened index count."""
    n = 1
    for d in idx_shape:
        n *= d
    return (tuple(weight_shape), (n,))


def take_rows(weight, idx, *, config=None, interpret=None):
    """Gather rows of a (vocab, dim) table by integer index via Pallas.

    ``idx`` may be 1-D or 2-D (the Embedding op's data); the output is
    ``idx.shape + (dim,)``, bit-identical to
    ``jnp.take(weight, idx.astype(int32), axis=0)``.
    """
    reason = eligible(weight.shape, weight.dtype, idx.shape, idx.dtype)
    if reason is not None:
        raise ValueError("take_rows guard: %s" % reason)
    cfgd = dict(DEFAULT_CONFIG)
    cfgd.update(config or {})
    if interpret is None:
        interpret = tier.resolve_interpret()
    block_d = int(cfgd["block_d"])
    if weight.shape[1] % block_d != 0:
        block_d = weight.shape[1]
    cfg = _Cfg(block_d, bool(interpret))
    idx_flat = jnp.clip(idx.astype(jnp.int32).reshape(-1), 0,
                        weight.shape[0] - 1)
    out = _fused(weight, idx_flat, cfg)
    return out.reshape(tuple(idx.shape) + (weight.shape[1],))


def gather_pages(table, idx, *, interpret=None):
    """Tier-dispatched row gather for the paged-KV decode step.

    ``table`` is one layer's flat page store ``(rows, dim)``; ``idx`` is
    the block-table expansion ``(max_slots, max_context)`` of flat row
    ids (serve/decode_model.py). Same numerics contract as
    ``jnp.take(table, idx, axis=0, mode="clip")`` — the scalar-prefetch
    kernel pre-clips its ids, so the fallback must clip too (jnp.take's
    default "fill" mode would turn an out-of-range id into NaN rows on
    the fallback path only, a tier-dependent numerics split; the
    embedding OOB parity test in tests/test_embed.py pins this). The
    kernel is bit-identical to the clipped take, so the bitwise-parity
    guarantee of the decode engine is tier-independent. Falls back to
    ``jnp.take`` whenever the tier is off or the guard declines
    (non-lane-aligned dim, dtype)."""
    reason = eligible(table.shape, table.dtype, idx.shape, idx.dtype)
    go, cfg = tier.should_dispatch(
        OP_NAME, shape_key_shapes(table.shape, idx.shape), table.dtype,
        guard_reason=reason)
    if go:
        return take_rows(table, idx, config=cfg, interpret=interpret)
    return jnp.take(table, idx.astype(jnp.int32), axis=0, mode="clip")
