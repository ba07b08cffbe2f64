"""Sequence-parallel attention tests: blockwise (flash-pattern) and ring
attention over an 8-virtual-device CPU mesh (the SURVEY.md §4 stand-in for
an 8-chip ICI ring)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import (attention_reference, blockwise_attention,
                                make_mesh, make_ring_attention)


def _qkv(b=2, h=2, t=64, d=8, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, t, d).astype(np.float32)),
            jnp.asarray(rng.randn(b, h, t, d).astype(np.float32)),
            jnp.asarray(rng.randn(b, h, t, d).astype(np.float32)))


def test_blockwise_matches_dense():
    q, k, v = _qkv()
    ref = attention_reference(q, k, v)
    out = blockwise_attention(q, k, v, block_size=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_blockwise_causal_matches_dense():
    q, k, v = _qkv(t=48)
    ref = attention_reference(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, block_size=16, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_blockwise_unaligned_block():
    q, k, v = _qkv(t=50)  # 50 % 16 != 0 -> padding path
    ref = attention_reference(q, k, v)
    out = blockwise_attention(q, k, v, block_size=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_matches_dense():
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(t=64)
    run = make_ring_attention(mesh, "sp")
    out = run(q, k, v)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_causal_matches_dense():
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(t=64, seed=3)
    run = make_ring_attention(mesh, "sp", causal=True)
    out = run(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_output_stays_sharded():
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(t=32)
    run = make_ring_attention(mesh, "sp")
    out = run(q, k, v)
    assert len(out.sharding.device_set) == 8


def test_ring_attention_grads():
    mesh = make_mesh({"sp": 4}, devices=jax.devices("cpu")[:4])
    q, k, v = _qkv(t=32, seed=5)

    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.ring_attention import ring_attention
    spec = P(None, None, "sp", None)
    fn = shard_map(partial(ring_attention, axis_name="sp"),
                   mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
