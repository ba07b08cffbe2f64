"""Pallas TPU flash-attention kernel.

The hot op of long-context training, hand-tiled for the MXU per
/opt/skills/guides/pallas_guide.md: the Q block lives in VMEM, the kernel
streams KV blocks with an online softmax (f32 running max / denominator /
accumulator in VMEM scratch), and the QK^T / PV matmuls run on the MXU
with ``preferred_element_type=f32``.  Grid = (batch*heads, q_blocks); the
KV stream is a ``fori_loop`` inside the kernel so the accumulator never
leaves VMEM.  Causal masking prunes the loop bound (blocks entirely in
the future are never read).

The value's width may differ from the width q and k share (latent
attention: 192-wide q.k, 128-wide v): the output takes v's.

Backward: ``jax.custom_vjp`` whose bwd is the flash backward written
blockwise in plain jax (:func:`_flash_bwd`): per block of queries it
recomputes the scores against the blocks of keys that block may see (a
causal block skips its future), so it holds tiles and never a T x T
array, whatever the sequence length.  (The reference has no analog — its
attention ops are cuDNN calls.)

On CPU the kernel runs in interpreter mode (tests); on TPU it lowers via
Mosaic.  ``mxnet_tpu.parallel.flash_attention`` auto-selects this kernel
on TPU when shapes allow.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register as _register, stage_keep

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_q, block_k, seq_q, seq_k, causal, sm_scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: a KV block entirely in this Q block's future contributes
    # nothing — skip its compute (the diagonal offset seq_k - seq_q
    # aligns cross-length attention like blockwise_attention)
    if causal:
        visible = ki * block_k <= (qi + 1) * block_q - 1 + (seq_k - seq_q)
    else:
        visible = True

    @pl.when(visible)
    def _():
        q = q_ref[0]                                       # (bq, d)
        bq = q.shape[0]
        k_blk = k_ref[0]                                   # (bk, d)
        v_blk = v_ref[0]
        # operands as they are stored (a bf16 product is exact in the
        # f32 accumulator; widening them first only costs MXU passes)
        s = jax.lax.dot_general(
            q, k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        kv_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = kv_pos < seq_k                              # tail padding
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            mask &= kv_pos <= q_pos + (seq_k - seq_q)
        s = jnp.where(mask, s, _NEG_INF)
        m = m_scr[:]
        l = l_scr[:]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        m_scr[:] = m_new
        l_scr[:] = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        o_ref[0] = (acc_scr[:]
                    / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def _flash_fwd(q, k, v, block_q, block_k, causal, interpret):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    dv = v.shape[3]
    sm_scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)

    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v

    bh = b * h
    qp = qp.reshape(bh, tq + pad_q, d)
    kp = kp.reshape(bh, tk + pad_k, d)
    vp = vp.reshape(bh, tk + pad_k, dv)
    n_q = (tq + pad_q) // block_q
    n_k = (tk + pad_k) // block_k

    # KV blocks are the innermost grid dim: each (block_k, d) tile is
    # DMA'd per step while the online-softmax state (m, l, acc) persists
    # in VMEM scratch — VMEM holds O(block) tiles, never the sequence, so
    # long contexts fit (the review of the first version found whole-KV
    # staging capped usable sequence length)
    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, seq_q=tq, seq_k=tk,
        causal=causal, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bi, qi, ki: (bi, ki, 0)),
            pl.BlockSpec((1, block_k, dv), lambda bi, qi, ki: (bi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv),
                               lambda bi, qi, ki: (bi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq + pad_q, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    out = out.reshape(b, h, tq + pad_q, dv)
    return out[:, :, :tq] if pad_q else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, block_q=128, block_k=128, causal=False,
                    interpret=None):
    """Flash attention on (B, H, T, D) tensors via a pallas TPU kernel.

    ``interpret=None`` auto-selects: interpreter off TPU (tests), Mosaic
    on TPU. f32 accumulation regardless of input dtype.

    Fully-masked rows (causal with ``seq_q > seq_k``: queries before the
    first key) return **zeros** — the flash/blockwise convention shared
    with :func:`~mxnet_tpu.parallel.blockwise_attention`. The dense
    ``attention_reference`` instead softmaxes an all-masked row into a
    uniform distribution; that row is mathematically undefined, and the
    zero convention is what fused kernels produce.
    """
    if interpret is None:
        from ..kernels.tier import resolve_interpret
        interpret = resolve_interpret()
    return _flash_fwd(q, k, v, block_q, block_k, causal, interpret)


def _flash_bwd(q, k, v, o, do, causal, block_q=512, block_k=512):
    """Gradients of ``softmax(q k^T / sqrt(d)) v`` (masked as the forward
    masks) from the output and its cotangent, blockwise: for each block of
    queries, one pass over the key blocks it may see for the rows' log sum
    of exponentials, and one for ``dv += p^T do``, ``ds = p (do v^T -
    rowsum(o do))``, ``dq += ds k``, ``dk += ds^T q``. Scores and the
    accumulators are float32; the loops' bounds follow the causal mask, so
    a block of the future costs nothing."""
    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    f32 = jnp.float32
    scale = 1.0 / math.sqrt(d)
    bq, bk = min(block_q, tq), min(block_k, tk)
    pad_q, pad_k = (-tq) % bq, (-tk) % bk
    off = tk - tq

    def padded(x, pad):
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x

    qp, op, dop = padded(q, pad_q), padded(o, pad_q), padded(do, pad_q)
    kp, vp = padded(k, pad_k), padded(v, pad_k)
    n_q, n_k = (tq + pad_q) // bq, (tk + pad_k) // bk
    delta = jnp.sum(op.astype(f32) * dop.astype(f32), -1, keepdims=True)

    def rows(x, i, size):
        return jax.lax.dynamic_slice_in_dim(x, i * size, size, axis=2)

    def q_block(i, carry):
        dq, dk, dvv = carry
        qi = rows(qp, i, bq).astype(f32) * scale
        doi = rows(dop, i, bq).astype(f32)
        di = rows(delta, i, bq)
        q_pos = i * bq + jnp.arange(bq)[:, None]
        if causal:      # the last block of keys a row of this block sees
            n_vis = jnp.clip(((i + 1) * bq - 1 + off) // bk + 1, 0, n_k)
        else:
            n_vis = n_k

        def scores(j):
            kj = rows(kp, j, bk).astype(f32)
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, kj,
                           preferred_element_type=f32)
            kv_pos = j * bk + jnp.arange(bk)[None, :]
            mask = kv_pos < tk
            if causal:
                mask = mask & (kv_pos <= q_pos + off)
            return jnp.where(mask, s, _NEG_INF), mask, kj

        def stats(j, ml):
            m, l = ml
            s, mask, _ = scores(j)
            m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            return m_new, l * jnp.exp(m - m_new) \
                + jnp.sum(p, -1, keepdims=True)

        shape = (b, h, bq, 1)
        m, l = jax.lax.fori_loop(
            0, n_vis, stats,
            (jnp.full(shape, _NEG_INF, f32), jnp.zeros(shape, f32)))
        lse = m + jnp.log(jnp.maximum(l, 1e-30))

        def grads(j, c):
            dqi, dk, dvv = c
            s, mask, kj = scores(j)
            vj = rows(vp, j, bk).astype(f32)
            p = jnp.where(mask, jnp.exp(s - lse), 0.0)
            dvj = jnp.einsum("bhqk,bhqd->bhkd", p, doi)
            dp = jnp.einsum("bhqd,bhkd->bhqk", doi, vj)
            ds = p * (dp - di)
            dqi = dqi + jnp.einsum("bhqk,bhkd->bhqd", ds, kj)
            dkj = jnp.einsum("bhqk,bhqd->bhkd", ds, qi)

            def add(acc, x):
                return jax.lax.dynamic_update_slice_in_dim(
                    acc, rows(acc, j, bk) + x, j * bk, axis=2)
            return dqi, add(dk, dkj), add(dvv, dvj)

        dqi, dk, dvv = jax.lax.fori_loop(
            0, n_vis, grads, (jnp.zeros((b, h, bq, d), f32), dk, dvv))
        dq = jax.lax.dynamic_update_slice_in_dim(dq, dqi * scale, i * bq,
                                                 axis=2)
        return dq, dk, dvv

    dq, dk, dvv = jax.lax.fori_loop(
        0, n_q, q_block,
        (jnp.zeros(qp.shape, f32), jnp.zeros(kp.shape, f32),
         jnp.zeros(vp.shape, f32)))
    return (dq[:, :, :tq].astype(q.dtype), dk[:, :, :tk].astype(k.dtype),
            dvv[:, :, :tk].astype(v.dtype))


def _fwd(q, k, v, block_q, block_k, causal, interpret):
    # inside a mirror_stage the output is kept and the kernel is not run
    # again in the backward pass; q, k, v are recomputed like the rest
    o = stage_keep(
        flash_attention(q, k, v, block_q, block_k, causal, interpret))
    return o, (q, k, v, o)


def _bwd(block_q, block_k, causal, interpret, res, g):
    q, k, v, o = res
    return _flash_bwd(q, k, v, o, g, causal)


flash_attention.defvjp(_fwd, _bwd)


# eager/symbolic surface: mx.nd._contrib_FlashAttention(q, k, v, causal=...)
@_register("_contrib_FlashAttention")
def _contrib_flash_attention(q, k, v, *, causal=False, block_q=128,
                             block_k=128):
    """(B, H, T, D) flash attention as a registered op (pallas on TPU);
    ``v`` may be (B, H, T, Dv) of another width than q and k share.

    Tier-aware: under ``MXNET_KERNEL_TIER=safe|auto`` the call dispatches
    to the kernel-tier attention (kernels/attention.py — the
    ``mxk_flash_attn`` HLO name the bench census counts, tuning-cache
    tile configs, and a ``custom_vjp`` backward exact against the dense
    reference), so the gluon GPT's hybridized train step picks up the
    tuned kernel with zero model changes. With the tier off (the
    default) it lowers this module's kernel with the caller's explicit
    block sizes, unchanged — eligibility rejections (e.g. causal
    cross-length) take the same legacy path and the reason lands in
    ``tier.stats()['fallback']``."""
    from ..kernels import attention as _attn
    out = _attn.attend_or_none(q, k, v, causal=bool(causal))
    if out is not None:
        return out
    return flash_attention(q, k, v, block_q, block_k, bool(causal))
