"""Long-context attention: blockwise (flash-style) single chip and ring
attention over a sequence-parallel mesh axis.

This is NEW TPU-first scope beyond the 2018-era reference (SURVEY.md §5
records the reference has no sequence/context parallelism), required for
long-context parity with modern frameworks:

* :func:`blockwise_attention` — online-softmax attention over KV blocks via
  ``lax.scan``: O(T) memory instead of O(T^2), XLA fuses the inner matmuls
  onto the MXU. This is the single-chip flash-attention pattern.
* :func:`ring_attention` — shard the sequence over a mesh axis ('sp');
  each step computes attention against the local KV shard then rotates the
  KV shards around the ring with ``ppermute`` (ICI neighbor exchange),
  accumulating with the same online softmax. Communication overlaps the
  next step's compute inside one compiled SPMD program.

Shapes follow (batch, heads, seq, head_dim).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["blockwise_attention", "ring_attention", "attention_reference",
           "make_ring_attention"]

_NEG_INF = -1e30


def attention_reference(q, k, v, causal=False):
    """Dense O(T^2) reference attention (for tests)."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _block_update(q, k_blk, v_blk, m, l, o, mask=None):
    """One online-softmax accumulation step.

    m: running rowmax (B,H,Tq,1); l: running denom; o: running numerator.
    Accumulators are float32 regardless of the input dtype (flash-attention
    discipline): in bf16 the -1e30 init saturates and low-precision
    accumulation loses accuracy; the QK/PV matmuls run on the MXU with f32
    accumulation via preferred_element_type.
    """
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows: exp(-inf - -inf) -> exp(0); use where
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_blk.dtype),
                                  v_blk,
                                  preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def blockwise_attention(q, k, v, block_size=512, causal=False):
    """Memory-efficient attention: scan over KV blocks (flash pattern)."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    block_size = min(block_size, tk)
    n_blocks = (tk + block_size - 1) // block_size
    pad = n_blocks * block_size - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, h, n_blocks, block_size, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, n_blocks, block_size, d).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(t)[:, None]

    def step(carry, inputs):
        m, l, o = carry
        blk_idx, k_blk, v_blk = inputs
        kv_pos = blk_idx * block_size + jnp.arange(block_size)[None, :]
        mask = kv_pos < tk  # padding mask (Tq x block)
        if causal:
            mask = mask & (kv_pos <= q_pos + (tk - t))
        mask = mask[None, None]
        m, l, o = _block_update(q, k_blk, v_blk, m, l, o, mask)
        return (m, l, o), None

    m0 = jnp.full((b, h, t, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t, 1), jnp.float32)
    o0 = jnp.zeros((b, h, t, d), jnp.float32)
    (m, l, o), _ = lax.scan(step, (m0, l0, o0),
                            (jnp.arange(n_blocks), kb, vb))
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(q, k, v, axis_name="sp", causal=False):
    """Ring attention kernel body: call inside shard_map with q/k/v sharded
    on the sequence axis. Accumulates online softmax while rotating KV
    shards around the ring via ppermute."""
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, t_loc, d = q.shape

    q_pos = my_idx * t_loc + jnp.arange(t_loc)[:, None]
    perm = [(j, (j + 1) % n) for j in range(n)]

    m = jnp.full(q[..., :1].shape, _NEG_INF, jnp.float32)
    l = jnp.zeros(q[..., :1].shape, jnp.float32)
    o = jnp.zeros(q.shape, jnp.float32)
    k_cur, v_cur = k, v
    # n is the static ring size, so unroll in python: each step attends to
    # the held KV shard then rotates it one ICI hop — except after the last
    # step, where the shards are back where they started and a final
    # rotation would be a wasted full-shard collective
    for s in range(n):
        # kv shard currently held: originally from device (my_idx - s) % n
        kv_idx = (my_idx - s) % n
        kv_pos = kv_idx * t_loc + jnp.arange(t_loc)[None, :]
        mask = (kv_pos <= q_pos)[None, None] if causal else None
        m, l, o = _block_update(q, k_cur, v_cur, m, l, o, mask)
        if s < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def make_ring_attention(mesh, axis_name="sp", causal=False):
    """Build a jitted ring-attention fn over `mesh`: inputs (B,H,T,D) are
    sharded on T over `axis_name`; output sharded the same way."""
    from jax import shard_map

    spec = P(None, None, axis_name, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    jitted = jax.jit(fn)

    def run(q, k, v):
        sharding = NamedSharding(mesh, spec)
        q = jax.device_put(q, sharding)
        k = jax.device_put(k, sharding)
        v = jax.device_put(v, sharding)
        return jitted(q, k, v)

    return run
