"""Pallas TPU flash-attention kernel.

The hot op of long-context training, hand-tiled for the MXU per
/opt/skills/guides/pallas_guide.md: the Q block lives in VMEM, the kernel
streams KV blocks with an online softmax (f32 running max / denominator /
accumulator in VMEM scratch), and the QK^T / PV matmuls run on the MXU
with ``preferred_element_type=f32``.  Grid = (batch*heads, q_blocks,
key steps), the key steps innermost so the accumulator never leaves VMEM.
Under the causal mask a query block's steps count from its first visible
key block and stay on its last: a block entirely in the future is neither
fetched nor multiplied, and a block every row sees whole skips the mask's
arithmetic.

**The tile** ``(block_q, block_k)`` is the op's own choice from what it
sees in its input (:func:`tile_for`: the lengths, the widths, the
operands' itemsize, the window), unless the caller gives one, which is
taken as given. At blocks of 128 the kernel is bound by its grid steps
(0.4 us each on a v5e, eight times a 128 x 128 x 128 tile's work), so the
rule takes the first of 1,024 x 1,024, 512 x 1,024, 512 x 512, 256 x 256
and 128 x 128 (the order the chip timed them in at 8,192 tokens, PERF.md,
PR 32) that a program can hold in VMEM and that is no longer than the
window; a sequence under a tile is one block. **The VMEM budget** is the
16 MiB a v5e's compiler lets a kernel scope by default (``VMEM_BUDGET``;
no call raises the limit), counted by :func:`tile_bytes`: bfloat16
operands at widths up to 256 take 1,024 x 1,024 (14 MiB at 192 / 128),
float32 operands take it at 128 / 128 and 512 x 1,024 from 192 / 128 on.

The value's width may differ from the width q and k share (latent
attention: 192-wide q.k, 128-wide v): the output takes v's.

``k`` and ``v`` may carry fewer heads than ``q`` (grouped-query attention:
``H % H_kv == 0``, query head ``i`` reads KV head ``i // (H / H_kv)``). The
kernel reads the group's one KV head through its index map, so the heads
are never repeated in memory, and the backward sums ``dK``, ``dV`` over
the group's query heads. A static ``window`` (with ``causal``) lets query
``i`` see keys ``i - window < j <= i``: the grid's key axis is then as long
as the most key blocks any query block sees, its index map starts at the
block's first visible key block and stops at its last, so a key block
wholly behind the window or wholly in the future is neither fetched nor
multiplied; the backward's loop bounds follow the same rule.

Backward: ``jax.custom_vjp``. Its forward rule runs the kernel with a
second output, the rows' log-sum-exp of the scaled, masked scores
(float32, one lane-dense row a query block), and keeps it beside the
output, so the backward never rebuilds it. Where :func:`bwd_eligible`
takes the shapes, the backward is one Pallas kernel (:func:`flash_bwd`,
``flash_bwd`` in a profile): key blocks outermost, the group's query heads
and the query blocks that see the key block inside, at most five MXU
products a visited pair, dK and dV accumulated in VMEM, dQ read, added to
and written back by DMA. Elsewhere it is the flash backward written
blockwise in plain jax (:func:`_flash_bwd`, the kernel's oracle): per
block of queries it recomputes the scores against the blocks of keys that
block may see (a causal block skips its future). Both hold tiles and never
a T x T array, whatever the sequence length.  (The reference has no analog
— its attention ops are cuDNN calls.)

On CPU the kernel runs in interpreter mode (tests); on TPU it lowers via
Mosaic.  ``mxnet_tpu.parallel.flash_attention`` auto-selects this kernel
on TPU when shapes allow.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import (program_count, program_gauge,
                       register as _register, stage_keep)

_NEG_INF = -1e30


def _block_span(xp, qi, block_q, block_k, n_k, off, window):
    """(first, last) key block that a row of query block ``qi`` sees under
    the causal mask and the window (``None``: every earlier key), ``last <
    first`` where no row sees a key; ``xp`` is ``numpy`` over every query
    block at once (the grid's length, the gauges) or ``jax.numpy`` over a
    program's own."""
    hi = (qi + 1) * block_q - 1 + off
    last = xp.where(hi >= 0, xp.minimum(xp.maximum(hi, 0) // block_k,
                                        n_k - 1), -1)
    if window is None:
        return qi * 0, last
    lo = qi * block_q + off - window + 1
    return xp.minimum(xp.maximum(lo, 0) // block_k, n_k - 1), last


# What one program of the forward kernel may hold in VMEM, by
# :func:`tile_bytes`: the 16 MiB a v5e's compiler lets a kernel scope by
# default, so no call raises the limit.
VMEM_BUDGET = 16 * 2 ** 20
# the tiles tried, best first as timed alone on a v5e at 8,192 tokens
# (PERF.md, PR 32): a longer key block pays more than a longer query block
_TILES = ((1024, 1024), (512, 1024), (512, 512), (256, 256), (128, 128))


def tile_bytes(block_q, block_k, d, dv, itemsize):
    """VMEM a program of the forward kernel holds at a tile: the q, k, v
    and output blocks (two buffers each, the pipeline's), the float32
    accumulator, the running max and denominator (a lane-wide register
    row each) and two and a half float32 (block_q, block_k) tiles (the
    scores, the probabilities and their cast for the second product).
    Within 1 MB of what Mosaic compiled and refused at 1,024 x 1,024."""
    blocks = (block_q + block_k) * (d + dv) * itemsize * 2
    state = block_q * (dv + 2 * 128) * 4
    return blocks + state + 10 * block_q * block_k


def tile_for(tq, tk, d, dv, itemsize, window=None):
    """(block_q, block_k) of the forward kernel, from the shapes alone: the
    first of ``_TILES`` that fits ``VMEM_BUDGET`` and, under a window, is no
    longer than it (a longer block multiplies pairs the mask then hides);
    no longer than the sequence (one under a tile is one block)."""
    for block_q, block_k in _TILES:
        if window is not None and max(block_q, block_k) > max(window, 128):
            continue
        if tile_bytes(block_q, block_k, d, dv, itemsize) <= VMEM_BUDGET:
            break
    return min(block_q, tq), min(block_k, tk)


def _tile(q, k, v, block_q, block_k, window):
    """The caller's blocks as given (cut to the sequence), else the
    shapes' own."""
    tq, tk = q.shape[2], k.shape[2]
    auto_q, auto_k = tile_for(tq, tk, q.shape[3], v.shape[3],
                              q.dtype.itemsize, window)
    return (auto_q if block_q is None else min(block_q, tq),
            auto_k if block_k is None else min(block_k, tk))


def _spans(tq, tk, block_q, block_k, window):
    """(first, last) visible key block of every query block, as arrays."""
    n_q, n_k = -(-tq // block_q), -(-tk // block_k)
    return _block_span(np, np.arange(n_q), block_q, block_k, n_k, tk - tq,
                       window)


def blocks_visited(tq, tk, block_q=128, block_k=128, window=None):
    """(key blocks a head's causal forward visits, what it would visit
    without the window): 952 and 2,080 at 8,192 tokens under a window of
    2,048 at blocks of 128, 70 and 136 at blocks of 512."""
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    first, last = _spans(tq, tk, block_q, block_k, window)
    causal = int(np.sum(np.maximum(last + 1, 0)))
    return int(np.sum(np.maximum(last - first + 1, 0))), causal


def grid_steps(tq, tk, block_q=128, block_k=128, causal=True, window=None):
    """Steps one head's forward grid takes: query blocks times the longest
    span of key blocks a query block sees (all of them without the causal
    mask). 4,096 at 8,192 tokens at blocks of 128 and 1,088 under a window
    of 2,048; 256 and 80 at blocks of 512."""
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    n_q, n_k = -(-tq // block_q), -(-tk // block_k)
    if not causal:
        return n_q * n_k
    first, last = _spans(tq, tk, block_q, block_k, window)
    return n_q * max(1, int(np.max(last - first + 1)))


def _kernel(q_ref, k_ref, v_ref, o_ref, *refs, block_q, block_k, seq_q,
            seq_k, causal, sm_scale, window=None):
    # with a second output, the rows' log-sum-exp (what the backward reads)
    lse_ref, (m_scr, l_scr, acc_scr) = (
        (refs[0], refs[1:]) if len(refs) == 4 else (None, refs))
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    off = seq_k - seq_q      # the diagonal of cross-length attention

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    if causal:
        # the grid's key axis counts from the query block's first visible
        # key block (the index maps fetch that one): ``kb`` is the block
        # this step holds; past the last visible one (the future) there is
        # nothing to do and nothing was fetched
        first, last = _block_span(jnp, qi, block_q, block_k,
                                  -(-seq_k // block_k), off, window)
        kb = first + ki
        visible = kb <= last
        # every row of the query block sees every key of the block: its
        # last key is no later than the first row's own position (which
        # also keeps it off the padded tail) and its first key is inside
        # the last row's window
        whole = (kb + 1) * block_k - 1 <= qi * block_q + off
        if window is not None:
            whole &= kb * block_k > (qi + 1) * block_q - 1 + off - window
    else:
        kb = ki
        visible = True
        whole = True if seq_k % block_k == 0 else (kb + 1) * block_k <= seq_k

    def attend(masked):
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
        if masked:
            kv_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            mask = kv_pos < seq_k                          # tail padding
            if causal:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0)
                mask &= kv_pos <= q_pos + off
                if window is not None:
                    mask &= kv_pos > q_pos + off - window
            s = jnp.where(mask, s, _NEG_INF)
        m = m_scr[:]
        l = l_scr[:]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if masked:      # a row that sees no key of the block adds nothing
            p = jnp.where(mask, p, 0.0)
        m_scr[:] = m_new
        l_scr[:] = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # a block wholly inside the visible region needs none of the mask's
    # iota, compare and select passes over the (bq, bk) scores
    if whole is True:
        attend(False)
    else:
        pl.when(visible & whole)(lambda: attend(False))
        pl.when(visible & jnp.logical_not(whole))(lambda: attend(True))

    @pl.when(ki == n_k - 1)
    def _():
        o_ref[0] = (acc_scr[:]
                    / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            # a column of the rows' values, laid along the lanes: one row
            lse = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))
            lse_ref[0] = jnp.broadcast_to(lse, (lse.shape[0], 128)).T[:1]


def _flash_fwd(q, k, v, block_q, block_k, causal, interpret, window=None,
               scale=None, with_lse=False):
    """The output, and with ``with_lse`` also the rows' log-sum-exp of the
    scaled, masked scores, float32 (B, H, T)."""
    b, h, tq, d = q.shape
    h_kv, tk = k.shape[1], k.shape[2]
    dv = v.shape[3]
    if h % h_kv or v.shape[1] != h_kv:
        raise ValueError("flash_attention: %d query heads over %d and %d "
                         "key and value heads" % (h, h_kv, v.shape[1]))
    if window is not None and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    group = h // h_kv
    sm_scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    block_q, block_k = _tile(q, k, v, block_q, block_k, window)

    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v

    bh = b * h
    qp = qp.reshape(bh, tq + pad_q, d)
    kp = kp.reshape(b * h_kv, tk + pad_k, d)
    vp = vp.reshape(b * h_kv, tk + pad_k, dv)
    n_q = (tq + pad_q) // block_q
    n_k = (tk + pad_k) // block_k
    # as many steps as the longest span of key blocks a query block sees
    n_steps = grid_steps(tq, tk, block_q, block_k, causal, window) // n_q

    def kv_map(bi, qi, ki):
        # a group's query heads read their one KV head; under the causal
        # mask the step's block counts from the query block's first visible
        # one and stays on its last (a block index that repeats is not
        # fetched again: a step in the future costs no DMA)
        if group > 1:
            bi = bi // group
        if causal:
            first, last = _block_span(jnp, qi, block_q, block_k, n_k,
                                      tk - tq, window)
            ki = jnp.minimum(first + ki, jnp.maximum(last, first))
        return bi, ki, 0

    # KV blocks are the innermost grid dim: each (block_k, d) tile is
    # DMA'd per step while the online-softmax state (m, l, acc) persists
    # in VMEM scratch — VMEM holds O(block) tiles, never the sequence, so
    # long contexts fit (the review of the first version found whole-KV
    # staging capped usable sequence length)
    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, seq_q=tq, seq_k=tk,
        causal=causal, sm_scale=sm_scale, window=window)
    out_specs = pl.BlockSpec((1, block_q, dv), lambda bi, qi, ki: (bi, qi, 0))
    out_shape = jax.ShapeDtypeStruct((bh, tq + pad_q, dv), q.dtype)
    if with_lse:    # (B*H, 1, T): lane-dense rows
        out_specs = [out_specs, pl.BlockSpec((1, 1, block_q),
                                             lambda bi, qi, ki: (bi, 0, qi))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((bh, 1, tq + pad_q),
                                                     jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, dv), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    if with_lse:
        out, lse = out
        lse = lse.reshape(b, h, tq + pad_q)[:, :, :tq]
    out = out.reshape(b, h, tq + pad_q, dv)
    out = out[:, :, :tq] if pad_q else out
    return (out, lse) if with_lse else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, block_q=None, block_k=None, causal=False,
                    interpret=None, window=None, scale=None):
    """Flash attention on (B, H, T, D) tensors via a pallas TPU kernel.

    ``interpret=None`` auto-selects: interpreter off TPU (tests), Mosaic
    on TPU. f32 accumulation regardless of input dtype. ``k`` and ``v``
    may be (B, H_kv, T, .) with ``H % H_kv == 0`` (grouped-query
    attention); ``window`` (static, with ``causal``) hides the keys more
    than ``window - 1`` positions behind a query. ``block_q`` /
    ``block_k`` left ``None`` are :func:`tile_for`'s, from the shapes; a
    caller's own are taken as given. ``scale`` (static) multiplies the
    scores before the softmax; ``None`` is ``1 / sqrt(D)``.

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
    return _flash_fwd(q, k, v, block_q, block_k, causal, interpret, window,
                      scale)


def _flash_bwd(q, k, v, o, lse, do, causal, block_q=512, block_k=512,
               window=None, scale=None):
    """Gradients of ``softmax(scale q k^T) v`` (``scale`` None: ``1 /
    sqrt(d)``; masked as the forward masks) from the output, the rows'
    log-sum-exp ``lse`` the forward handed on and the output's cotangent,
    blockwise in plain JAX: for each block of queries, one pass over the
    key blocks it may see for ``dv += p^T do``, ``ds = p (do v^T -
    rowsum(o do))``, ``dq += ds k``, ``dk += ds^T q``. Scores and the
    accumulators are float32; the loops' bounds follow the causal mask and
    the window, so a block of the future or behind the window costs
    nothing. Under grouped heads q, o and do are taken as (B, H_kv, group,
    T, .): a group's scores are made against its one KV head, and ``dk``,
    ``dv`` are summed over the group by the products that make them. The
    oracle of :func:`flash_bwd`, and the path of every shape
    :func:`bwd_eligible` declines."""
    b, h, tq, d = q.shape
    h_kv, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // h_kv
    # the axes before (rows, width) of the query side and of the key side
    qa, ka = ("bng", "bn") if group > 1 else ("bh", "bh")
    lse = lse[..., None]
    if group > 1:
        q, o, do, lse = (x.reshape((b, h_kv, group) + x.shape[2:])
                         for x in (q, o, do, lse))
    f32 = jnp.float32
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    bq, bk = min(block_q, tq), min(block_k, tk)
    pad_q, pad_k = (-tq) % bq, (-tk) % bk
    off = tk - tq

    def padded(x, pad):
        widths = ((0, 0),) * (x.ndim - 2) + ((0, pad), (0, 0))
        return jnp.pad(x, widths) if pad else x

    qp, op, dop = padded(q, pad_q), padded(o, pad_q), padded(do, pad_q)
    lsep = padded(lse, pad_q)
    kp, vp = padded(k, pad_k), padded(v, pad_k)
    n_q, n_k = (tq + pad_q) // bq, (tk + pad_k) // bk
    delta = jnp.sum(op.astype(f32) * dop.astype(f32), -1, keepdims=True)

    def rows(x, i, size):
        return jax.lax.dynamic_slice_in_dim(x, i * size, size, axis=x.ndim - 2)

    def q_block(i, carry):
        dq, dk, dvv = carry
        qi = rows(qp, i, bq).astype(f32) * scale
        doi = rows(dop, i, bq).astype(f32)
        di = rows(delta, i, bq)
        lse_i = rows(lsep, i, bq)
        q_pos = i * bq + jnp.arange(bq)[:, None]
        if causal:      # the last block of keys a row of this block sees
            n_vis = jnp.clip(((i + 1) * bq - 1 + off) // bk + 1, 0, n_k)
        else:
            n_vis = n_k
        first = 0       # and the first: a window hides those before it
        if window is not None:
            first = jnp.clip((i * bq + off - window + 1) // bk, 0, n_k)

        def grads(j, c):
            dqi, dk, dvv = c
            kj = rows(kp, j, bk).astype(f32)
            s = jnp.einsum("%sqd,%skd->%sqk" % (qa, ka, qa), qi, kj,
                           preferred_element_type=f32)
            kv_pos = j * bk + jnp.arange(bk)[None, :]
            mask = kv_pos < tk
            if causal:
                mask = mask & (kv_pos <= q_pos + off)
            if window is not None:
                mask = mask & (kv_pos > q_pos + off - window)
            vj = rows(vp, j, bk).astype(f32)
            p = jnp.where(mask, jnp.exp(s - lse_i), 0.0)
            dvj = jnp.einsum("%sqk,%sqd->%skd" % (qa, qa, ka), p, doi)
            dp = jnp.einsum("%sqd,%skd->%sqk" % (qa, ka, qa), doi, vj)
            ds = p * (dp - di)
            dqi = dqi + jnp.einsum("%sqk,%skd->%sqd" % (qa, ka, qa), ds, kj)
            dkj = jnp.einsum("%sqk,%sqd->%skd" % (qa, qa, ka), ds, qi)

            def add(acc, x):
                return jax.lax.dynamic_update_slice_in_dim(
                    acc, rows(acc, j, bk) + x, j * bk, axis=2)
            return dqi, add(dk, dkj), add(dvv, dvj)

        dqi, dk, dvv = jax.lax.fori_loop(
            first, n_vis, grads, (jnp.zeros(qi.shape, f32), dk, dvv))
        dq = jax.lax.dynamic_update_slice_in_dim(dq, dqi * scale, i * bq,
                                                 axis=dq.ndim - 2)
        return dq, dk, dvv

    dq, dk, dvv = jax.lax.fori_loop(
        0, n_q, q_block,
        (jnp.zeros(qp.shape, f32), jnp.zeros(kp.shape, f32),
         jnp.zeros(vp.shape, f32)))
    if group > 1:
        dq = dq.reshape((b, h) + dq.shape[-2:])
    return (dq[:, :, :tq].astype(q.dtype), dk[:, :, :tk].astype(k.dtype),
            dvv[:, :, :tk].astype(v.dtype))


def _query_span(xp, kj, block_q, block_k, n_q, off, window):
    """(first, last) query block with a row that sees a key of key block
    ``kj`` under the causal mask and the window, ``last < first`` where
    none does: the pairs :func:`_block_span` gives, from the key side."""
    lo = kj * block_k - off          # the first row that sees its first key
    first = xp.maximum(lo, 0) // block_q
    if window is None:
        return first, kj * 0 + n_q - 1
    hi = (kj + 1) * block_k - 2 - off + window  # the last to see its last
    return first, xp.where(hi >= 0, xp.minimum(xp.maximum(hi, 0) // block_q,
                                               n_q - 1), -1)


# the backward kernel's tiles, (block_q, block_k), best first as timed on a
# v5e at 8,192 tokens (PERF.md §6: forward + backward of one core, ms,
# Ouro / Kimi / Trinity's window layer: 1,024 x 1,024 7.39 / refused /
# 9.65, 512 x 1,024 7.61 / 24.76 / 9.87, 512 x 512 8.12 / 26.67 / 9.63,
# 256 x 512 9.21 / 29.39 / 10.70): the key block stays while the query
# blocks stream by, and the read-modify-write of dQ costs its bytes once a
# key block
_BWD_TILES = ((1024, 1024), (512, 1024), (512, 512), (256, 256), (128, 128))


def bwd_tile_bytes(block_q, block_k, d, dv, itemsize):
    """VMEM a program of the backward kernel holds at a tile: the q, dO, k,
    v, dK and dV blocks (two buffers each, their widths in whole lanes),
    the rows' log-sum-exp and deltas (eight sublanes, two buffers each),
    the float32 dK / dV accumulators and the two dQ buffers, and ten bytes
    an element of a (block_k, block_q) tile (the scores, dP, dS and their
    casts). Mosaic compiled 1,024 x 1,024 at heads of 128 (15.1 MiB here)
    and refused it at 192 / 128, asking 19.24 MiB (18.1 MiB here)."""
    def lanes(w):
        return -(-w // 128) * 128
    width = lanes(d) + lanes(dv)
    blocks = (block_q + 2 * block_k) * width * itemsize * 2 \
        + 2 * 2 * 8 * block_q * 4
    state = block_k * width * 4 + 2 * block_q * lanes(d) * 4
    return blocks + state + 10 * block_q * block_k


def bwd_tile_for(tq, tk, d, dv, itemsize, window=None):
    """(block_q, block_k) of the backward kernel, from the shapes alone:
    the first of ``_BWD_TILES`` that fits ``VMEM_BUDGET`` and, under a
    window, is no longer than it; no longer than the sequence."""
    for block_q, block_k in _BWD_TILES:
        if window is not None and max(block_q, block_k) > max(window, 128):
            continue
        if bwd_tile_bytes(block_q, block_k, d, dv, itemsize) <= VMEM_BUDGET:
            break
    return min(block_q, tq), min(block_k, tk)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_hbm, dk_ref,
                dv_ref, dk_scr, dv_scr, dq_buf0, dq_buf1, sems, st, *, block_q,
                block_k, seq_q, seq_k, n_q, causal, sm_scale, window, group):
    """One (key block, query head of the group, query block) of the
    backward, the key side's rows down the sublanes: ``s^T = k q^T``, ``p^T
    = exp(s^T - lse)``, ``dp^T = v dO^T``, ``ds^T = p^T (dp^T - delta)``,
    then ``dv += p^T dO``, ``dk += ds^T q`` into VMEM and ``dq += ds k``
    into a float32 block of HBM, read, added to and written back by DMA.
    ``st`` (SMEM): the visited steps so far, the (head, block) of dQ the
    last one wrote, and whether each of the two dQ buffers has a write in
    flight."""
    bn, j, g, s = (pl.program_id(a) for a in range(4))
    n_b, n_k, n_s = pl.num_programs(0), pl.num_programs(1), pl.num_programs(3)
    d = q_ref.shape[2]
    off = seq_k - seq_q
    head = bn * group + g
    if causal:
        qfirst, qlast = _query_span(jnp, j, block_q, block_k, n_q, off,
                                    window)
    else:
        qfirst, qlast = 0, n_q - 1
    qi = qfirst + s
    visible = qi <= qlast

    @pl.when((bn == 0) & (j == 0) & (g == 0) & (s == 0))
    def _():
        st[0], st[1], st[2], st[3], st[4] = 0, -1, -1, 0, 0

    @pl.when((g == 0) & (s == 0))
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if causal:
        # every query row of the block sees every key of the block (the
        # forward's rule, from the key side)
        whole = (j + 1) * block_k - 1 <= qi * block_q + off
        if window is not None:
            whole &= j * block_k > (qi + 1) * block_q - 1 + off - window
        # the first key block that sees this query block writes its dQ
        # block, every later one reads it and adds
        fresh = j == _block_span(jnp, qi, block_q, block_k, n_k, off,
                                 window)[0]
    else:
        whole = True if seq_k % block_k == 0 else (j + 1) * block_k <= seq_k
        fresh = j == 0

    def dq_copy(slot, h, i, read):
        hbm = dq_hbm.at[h, pl.ds(pl.multiple_of(i * block_q, block_q),
                                 block_q)]
        buf = (dq_buf0, dq_buf1)[slot]
        return pltpu.make_async_copy(*((hbm, buf) if read else (buf, hbm)),
                                     sems.at[slot])

    def on_slot(slot, fn):      # fn(the static slot) on the step's buffer
        for static in (0, 1):
            pl.when(slot == static)(functools.partial(fn, static))

    def step(masked):
        slot = st[0] % 2

        # the buffer's last write has landed before it is filled again, and
        # the other's, if it wrote this same block, before the block is read
        def settle(slot):
            @pl.when(st[3 + slot] == 1)
            def _():
                dq_copy(slot, 0, 0, False).wait()
                st[3 + slot] = 0

            @pl.when((st[1] == head) & (st[2] == qi) & (st[4 - slot] == 1))
            def _():
                dq_copy(1 - slot, 0, 0, False).wait()
                st[4 - slot] = 0

            @pl.when(jnp.logical_not(fresh))
            def _():
                dq_copy(slot, head, qi, True).start()
        on_slot(slot, settle)

        q = q_ref[0]                                        # (bq, d)
        k = k_ref[0]                                        # (bk, d)
        v = v_ref[0]                                        # (bk, dv)
        do = do_ref[0]                                      # (bq, dv)
        nt = (((1,), (1,)), ((), ()))
        s_t = jax.lax.dot_general(k, q, nt,                # scores^T
                                  preferred_element_type=jnp.float32)
        p = jnp.exp(s_t * sm_scale - lse_ref[0])            # (bk, bq)
        if masked:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, p.shape, 0)
            mask = k_pos < seq_k
            if causal:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, p.shape, 1)
                mask &= k_pos <= q_pos + off
                if window is not None:
                    mask &= k_pos > q_pos + off - window
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(v, do, nt,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - dl_ref[0])).astype(q.dtype)
        dv_scr[:] += jnp.dot(p.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dk_scr[:] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(ds, k, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

        def land(slot):
            buf = (dq_buf0, dq_buf1)[slot]

            @pl.when(fresh)
            def _():
                buf[:, :d] = dq

            @pl.when(jnp.logical_not(fresh))
            def _():
                dq_copy(slot, head, qi, True).wait()
                buf[:, :d] += dq

            dq_copy(slot, head, qi, False).start()
        on_slot(slot, land)
        st[3 + slot] = 1
        st[0] += 1
        st[1] = head
        st[2] = qi

    if whole is True:
        pl.when(visible)(lambda: step(False))
    else:
        pl.when(visible & whole)(lambda: step(False))
        pl.when(visible & jnp.logical_not(whole))(lambda: step(True))

    @pl.when((g == group - 1) & (s == n_s - 1))
    def _():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when((bn == n_b - 1) & (j == n_k - 1) & (g == group - 1)
             & (s == n_s - 1))
    def _():            # nothing in flight when the kernel ends
        for slot in (0, 1):
            @pl.when(st[3 + slot] == 1)
            def _():
                dq_copy(slot, 0, 0, False).wait()


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def flash_bwd(q, k, v, o, lse, do, causal, interpret, window=None,
              scale=None, block_q=None, block_k=None):
    """The gradients :func:`_flash_bwd` gives, as one Pallas kernel that
    reads the forward's log-sum-exp: grid (batch x KV heads, key blocks,
    the group's query heads, query blocks that see the key block). A key
    block of k and v is fetched once for all of its group's query heads
    and the query blocks that see it; dK and dV accumulate over both in
    VMEM; dQ's float32 blocks are read, added to and written back by DMA,
    the first key block that sees a query block writing it. At most five
    MXU products a visited pair, operands as stored, ``p`` and ``ds`` cast
    to their dtype, float32 accumulation; blocks every row sees whole
    skip the mask. ``rowsum(o dO)`` is taken once, outside. A call of its
    own, so that the compiled program names the kernel ``flash_bwd``."""
    return _bwd_call(q, k, v, o, lse, do, causal, interpret, window, scale,
                     block_q, block_k)


def _bwd_call(q, k, v, o, lse, do, causal, interpret, window, scale,
              block_q, block_k):
    b, h, tq, d = q.shape
    h_kv, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // h_kv
    f32 = jnp.float32
    sm_scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    auto_q, auto_k = bwd_tile_for(tq, tk, d, dv, q.dtype.itemsize, window)
    block_q = auto_q if block_q is None else min(block_q, tq)
    block_k = auto_k if block_k is None else min(block_k, tk)
    pad_q, pad_k = (-tq) % block_q, (-tk) % block_k
    n_q, n_k = (tq + pad_q) // block_q, (tk + pad_k) // block_k
    # dQ's blocks move by DMA as whole tiles of lanes: its width rounded up
    d_dq = -(-d // 128) * 128
    delta = jnp.sum(o.astype(f32) * do.astype(f32), -1)

    def rows(x, pad):       # (B*heads, T + pad, width)
        x = x.reshape((-1,) + x.shape[2:])
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    def lanes(x):           # (B*H, 1, T + pad_q) float32
        x = x.reshape(b * h, 1, tq)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad_q))) if pad_q else x

    if causal:
        first, last = _query_span(np, np.arange(n_k), block_q, block_k, n_q,
                                  tk - tq, window)
        n_steps = max(1, int(np.max(last - first + 1)))
    else:
        n_steps = n_q

    def q_map(bn, kj, g, s):
        if causal:
            first, last = _query_span(jnp, kj, block_q, block_k, n_q,
                                      tk - tq, window)
            s = jnp.minimum(jnp.minimum(first + s, jnp.maximum(last, first)),
                            n_q - 1)
        return bn * group + g, s, 0

    def lse_map(bn, kj, g, s):
        head, qi, _ = q_map(bn, kj, g, s)
        return head, 0, qi

    def kv_map(bn, kj, g, s):
        return bn, kj, 0

    kernel = functools.partial(
        _bwd_kernel, block_q=block_q, block_k=block_k, seq_q=tq, seq_k=tk,
        n_q=n_q, causal=causal, sm_scale=sm_scale, window=window,
        group=group)
    dq, dk, dvv = pl.pallas_call(
        kernel,
        grid=(b * h_kv, n_k, group, n_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, dv), kv_map),
            pl.BlockSpec((1, block_q, dv), q_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, dv), kv_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq + pad_q, d_dq), f32),
            jax.ShapeDtypeStruct((b * h_kv, tk + pad_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h_kv, tk + pad_k, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), f32),
            pltpu.VMEM((block_k, dv), f32),
            pltpu.VMEM((block_q, d_dq), f32),
            pltpu.VMEM((block_q, d_dq), f32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((5,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4),
        interpret=interpret,
        name="flash_bwd",
    )(rows(q, pad_q), rows(k, pad_k), rows(v, pad_k), rows(do, pad_q),
      lanes(lse), lanes(delta))
    dq = (dq[:, :tq, :d] * sm_scale).astype(q.dtype).reshape(b, h, tq, d)
    if tk < tq:     # rows before the first key: no key block wrote them
        dq = dq.at[:, :, :tq - tk].set(0)
    return (dq, dk[:, :tk].reshape(b, h_kv, tk, d),
            dvv[:, :tk].reshape(b, h_kv, tk, dv))


def bwd_eligible(tq, tk, d, dv, group, window, dtype):
    """Whether :func:`flash_bwd` takes a core's backward, from what the op
    sees; every other shape goes to :func:`_flash_bwd`. Each rule stands by
    the forward + backward of one core, ms, timed on a v5e at 8,192 tokens
    (PERF.md §6; kernel / plain on the handed-on log-sum-exp):

    * operands in bfloat16 or float32, widths of whole half-lanes from 128
      to 256: heads of 64 (Granite's, 32 over 8) read 15.00 / 12.56, the
      products half as deep and half as wide as the MXU;
    * grouped heads only under a window: a group that sees every earlier
      key (Trinity's full layer, 32 over 4 heads of 128) reads 15.06 /
      14.94, the plain path's products taking the whole group's rows at
      once; under a window of 2,048, 9.63 / 10.06; one KV head a query
      head, 7.39 / 9.27 (Ouro's 16 of 128) and 24.76 / 27.06 (Kimi's 32
      of 192 / 128);
    * a tile of whole lanes that fits ``VMEM_BUDGET``: a sequence of 128
      or more (one under a tile is one block)."""
    if np.dtype(dtype) not in (np.dtype(jnp.bfloat16), np.dtype(np.float32)):
        return False
    if d % 64 or dv % 64 or min(d, dv) < 128 or max(d, dv) > 256:
        return False
    if group > 1 and window is None:
        return False
    itemsize = np.dtype(dtype).itemsize
    block_q, block_k = bwd_tile_for(tq, tk, d, dv, itemsize, window)
    return (block_q % 128 == 0 and block_k % 128 == 0
            and bwd_tile_bytes(block_q, block_k, d, dv, itemsize)
            <= VMEM_BUDGET)


def _fwd(q, k, v, block_q, block_k, causal, interpret, window, scale):
    if interpret is None:
        from ..kernels.tier import resolve_interpret
        interpret = resolve_interpret()
    # inside a mirror_stage the output and the rows' log-sum-exp are kept,
    # so neither the kernel nor the statistics run again in the backward
    # pass; q, k, v are recomputed like the rest
    o, lse = _flash_fwd(q, k, v, block_q, block_k, causal, interpret, window,
                        scale, with_lse=True)
    o, lse = stage_keep(o), stage_keep(lse)
    return o, (q, k, v, o, lse)


def _takes_kernel(q, k, v, window):
    """:func:`bwd_eligible` on the operands of a core (of one dtype)."""
    return q.dtype == k.dtype == v.dtype and bwd_eligible(
        q.shape[2], k.shape[2], q.shape[3], v.shape[3],
        q.shape[1] // k.shape[1], window, q.dtype)


def _bwd(block_q, block_k, causal, interpret, window, scale, res, g):
    q, k, v, o, lse = res
    if _takes_kernel(q, k, v, window):
        if interpret is None:
            from ..kernels.tier import resolve_interpret
            interpret = resolve_interpret()
        return flash_bwd(q, k, v, o, lse, g, causal, interpret, window, scale)
    return _flash_bwd(q, k, v, o, lse, g, causal, window=window, scale=scale)


flash_attention.defvjp(_fwd, _bwd)


program_gauge("attn/window_layers",
              "attention cores of the training program traced last that "
              "see a window of keys (_contrib_FlashAttention with window)")
program_gauge("attn/full_layers",
              "attention cores of the training program traced last that "
              "see every earlier key")
program_gauge("attn/kv_blocks_visited",
              "key blocks a head's forward visits, summed over the "
              "attention cores of the training program traced last")
program_gauge("attn/kv_blocks_causal",
              "key blocks plain causal attention would visit there: what "
              "the windows save is the difference")
program_gauge("attn/bwd_kernel",
              "attention cores of the training program traced last whose "
              "backward is the kernel flash_bwd (pallas_flash.bwd_eligible)")
program_gauge("attn/bwd_plain",
              "attention cores of the training program traced last whose "
              "backward is the blockwise plain-JAX _flash_bwd")
program_gauge("attn/grid_steps",
              "steps a head's forward grid takes at the tile the op takes "
              "from the shapes, summed over the attention cores of the "
              "training program traced last")


# eager/symbolic surface: mx.nd._contrib_FlashAttention(q, k, v, causal=...)
@_register("_contrib_FlashAttention")
def _contrib_flash_attention(q, k, v, *, causal=False, block_q=None,
                             block_k=None, window=None, scale=None):
    """(B, H, T, D) flash attention as a registered op (pallas on TPU);
    ``v`` may be (B, H, T, Dv) of another width than q and k share, ``k``
    and ``v`` may carry ``H_kv`` heads with ``H % H_kv == 0`` (query head
    ``i`` reads KV head ``i // (H / H_kv)``), ``window`` (with
    ``causal``) hides the keys more than ``window - 1`` behind a query,
    and ``scale`` multiplies the scores before the softmax in the place of
    ``1 / sqrt(D)`` (Granite's ``attention_multiplier``).

    Tier-aware: under ``MXNET_KERNEL_TIER=safe|auto`` the call dispatches
    to the kernel-tier attention (kernels/attention.py — the
    ``mxk_flash_attn`` HLO name the bench census counts, tuning-cache
    tile configs, and a ``custom_vjp`` backward exact against the dense
    reference), so the gluon GPT's hybridized train step picks up the
    tuned kernel with zero model changes. With the tier off (the
    default) it lowers this module's kernel with the caller's explicit
    block sizes, unchanged — eligibility rejections (e.g. causal
    cross-length) take the same legacy path and the reason lands in
    ``tier.stats()['fallback']``. A window or grouped heads are this
    module's kernel's alone, as is a ``scale`` of the caller's."""
    window = None if window is None else int(window)
    scale = None if scale is None else float(scale)
    program_count("attn/full_layers" if window is None
                  else "attn/window_layers")
    tq, tk = q.shape[2], k.shape[2]
    tile = _tile(q, k, v, block_q, block_k, window)
    program_count("attn/grid_steps",
                  grid_steps(tq, tk, *tile, bool(causal), window))
    if causal:
        visited, plain = blocks_visited(tq, tk, *tile, window)
        program_count("attn/kv_blocks_visited", visited)
        program_count("attn/kv_blocks_causal", plain)
    if window is None and scale is None and k.shape[1] == q.shape[1]:
        from ..kernels import attention as _attn
        out = _attn.attend_or_none(q, k, v, causal=bool(causal))
        if out is not None:
            return out
    program_count("attn/bwd_kernel" if _takes_kernel(q, k, v, window)
                  else "attn/bwd_plain")
    return flash_attention(q, k, v, block_q, block_k, bool(causal), None,
                           window, scale)
