"""Pallas flash-attention kernel vs the dense reference (interpreter mode
on CPU; the same kernel lowers via Mosaic on TPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import attention_reference, flash_attention


def _qkv(b=2, h=3, tq=256, tk=256, d=64, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda t: jnp.asarray(
        (rng.randn(b, h, t, d) / np.sqrt(d)).astype(dtype))
    return mk(tq), mk(tk), mk(tk)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, 128, 128, causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_uneven_lengths_and_blocks():
    # T not a multiple of the block sizes: padding paths on both axes
    q, k, v = _qkv(tq=200, tk=328, d=32)
    out = flash_attention(q, k, v, 128, 128, False)
    ref = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_causal_cross_length():
    # decode-style: fewer queries than keys, diagonal offset tk - tq
    q, k, v = _qkv(tq=64, tk=256)
    out = flash_attention(q, k, v, 64, 128, True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16_accumulates_f32():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, 128, 128, False)
    assert out.dtype == jnp.bfloat16
    ref = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients(causal):
    q, k, v = _qkv(tq=128, tk=128, d=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, 64, 64, causal) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_jits():
    q, k, v = _qkv(tq=128, tk=128)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, 64, 64, True))
    out1 = f(q, k, v)
    out2 = f(q, k, v)  # cached trace
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


def test_flash_registered_op_eager():
    import mxnet_tpu as mx
    q, k, v = _qkv(tq=64, tk=64, d=32)
    out = mx.nd._contrib_FlashAttention(
        mx.nd.array(np.asarray(q)), mx.nd.array(np.asarray(k)),
        mx.nd.array(np.asarray(v)), causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_causal_more_queries_than_keys_matches_blockwise():
    """seq_q > seq_k causal: fully-masked leading rows are ZERO (the
    flash/blockwise convention, documented on flash_attention) and the
    visible region matches blockwise numerics."""
    from mxnet_tpu.parallel import blockwise_attention
    q, k, v = _qkv(tq=128, tk=64, d=32)
    out = np.asarray(flash_attention(q, k, v, 64, 64, True))
    blk = np.asarray(blockwise_attention(q, k, v, block_size=64,
                                         causal=True))
    np.testing.assert_allclose(out, blk, rtol=2e-5, atol=2e-5)
    assert np.all(out[:, :, :63] == 0)  # rows before the first visible key


# ---- a window of keys, grouped key and value heads
def _masked_reference(q, k, v, window=None):
    """Dense causal softmax attention, float32 at the highest precision:
    query head ``i`` reads KV head ``i // (H / H_kv)``, key ``j`` visible
    to query ``i`` when ``i - window < j <= i``."""
    h, hk, tq, tk = q.shape[1], k.shape[1], q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, h // hk, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    i = jnp.arange(tq)[:, None] + (tk - tq)
    j = jnp.arange(tk)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _grouped(h, hk, t, d=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(                               # noqa: E731
        (rng.randn(2, n, t, d) * 3 / np.sqrt(d)).astype(np.float32))
    return mk(h), mk(hk), mk(hk)


# (query heads, KV heads, tokens, window, query block, key block): the
# window a multiple of the block and not, T a multiple and not, blocks of
# two sizes, one KV head for all, a window as long as the sequence
_WINDOWS = [(4, 4, 256, 128, 64, 64), (8, 2, 200, 70, 64, 64),
            (8, 2, 200, 64, 64, 32), (4, 1, 256, None, 64, 64),
            (4, 2, 130, 33, 64, 64), (8, 4, 192, 1, 64, 64)]


@pytest.mark.parametrize("h,hk,t,window,bq,bk", _WINDOWS)
def test_window_and_grouped_heads_match_the_dense_masked_softmax(
        h, hk, t, window, bq, bk):
    from mxnet_tpu.ops.pallas_flash import _flash_bwd
    q, k, v = _grouped(h, hk, t)
    want = _masked_reference(q, k, v, window)
    got = flash_attention(q, k, v, bq, bk, True, None, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    do = jnp.asarray(np.random.RandomState(1).randn(*want.shape)
                     .astype(np.float32))
    wants = jax.vjp(lambda *a: _masked_reference(*a, window), q, k, v)[1](do)
    # through the custom_vjp (its backward's own blocks hold the sequence)
    # and with the backward cut into the forward's blocks, so that its
    # loops start behind the window and stop before the future
    for gots in (jax.vjp(lambda *a: flash_attention(
                     *a, bq, bk, True, None, window), q, k, v)[1](do),
                 _flash_bwd(q, k, v, got, do, True, bq, bk, window)):
        for name, a, b in zip("qkv", gots, wants):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)


def test_a_window_as_long_as_the_sequence_is_causal_attention():
    q, k, v = _grouped(4, 2, 192)
    causal = flash_attention(q, k, v, 64, 64, True)
    for window in (192, 500):
        out = flash_attention(q, k, v, 64, 64, True, None, window)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(causal))


# the registered op's lowered program (interpreter mode, no source
# locations in the text), forward and gradients, as the kernel stood before
# it knew of a window or of grouped heads: SHA-256 of the text at commit
# a612b22 under the one installation (JAX 0.9.0)
_BEFORE = {
    ("float32", "fwd"):
        "c993b018dbbf88c0450815a14333c7bf92fbd2612eac14f16c1addaaa9e9430f",
    ("float32", "grad"):
        "3699b2aca1fab4e0be9eae02de65f2ab0fef17668e9ed5eb5014e01e69495db2",
    ("bfloat16", "fwd"):
        "c0a1832400dd482e7a5bf634a85b24f7a3376770cd891ec0f5b39dba9a0cd265",
    ("bfloat16", "grad"):
        "69bcef45b75576c55982c5ab8317db1f77f75f87fedbecc782efad795c6de915",
}


@pytest.mark.parametrize("dtype,which", sorted(_BEFORE))
def test_without_a_window_and_with_every_kv_head_the_program_is_as_before(
        dtype, which):
    """``window=None, H_kv = H``: the program is the earlier kernel's,
    character for character, so its output is bit for bit (a changed
    kernel body, grid or index map would show here; after a deliberate
    change of that case, or another JAX, take the digests anew)."""
    import hashlib
    from mxnet_tpu.ops import registry
    op = registry.get("_contrib_FlashAttention").fn

    def f(q, k, v):      # the name is part of the text
        return op(q, k, v, causal=True, block_q=64, block_k=64)
    grad = jax.grad(lambda *a: (f(*a).astype(jnp.float32) ** 2).sum(),
                    argnums=(0, 1, 2))
    wide = jax.ShapeDtypeStruct((2, 3, 192, 32), jnp.dtype(dtype))
    narrow = jax.ShapeDtypeStruct((2, 3, 192, 16), jnp.dtype(dtype))
    text = jax.jit(f if which == "fwd" else grad).lower(
        wide, wide, narrow).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _BEFORE[dtype, which]


def test_grouped_heads_read_through_the_index_map_are_repeated_heads():
    """Bit for bit: the group's one KV head read by the kernel's index map
    gives what the same head repeated in memory gives."""
    q, k, v = _grouped(8, 2, 192)
    rep = lambda x: jnp.repeat(x, 4, axis=1)                  # noqa: E731
    for window in (None, 70):
        a = flash_attention(q, k, v, 64, 64, True, None, window)
        b = flash_attention(q, rep(k), rep(v), 64, 64, True, None, window)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_blocks_visited_under_a_window():
    from mxnet_tpu.ops.pallas_flash import blocks_visited
    # 64 query blocks: 1 + 2 + ... + 16, then 17 each for the other 48
    assert blocks_visited(8192, 8192, 128, 128, 2048) == (952, 2080)
    assert blocks_visited(8192, 8192, 128, 128, None) == (2080, 2080)
    assert blocks_visited(200, 200, 64, 64, 70) == (9, 10)


def test_a_window_needs_causal_and_heads_that_divide():
    q, k, v = _grouped(4, 2, 64)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, 64, 64, False, None, 16)
    with pytest.raises(ValueError, match="heads"):
        flash_attention(q[:, :3], k, v, 64, 64, True)


def test_registered_op_takes_window_and_grouped_heads():
    import mxnet_tpu as mx
    q, k, v = _grouped(4, 2, 96)
    out = mx.nd._contrib_FlashAttention(
        mx.nd.array(np.asarray(q)), mx.nd.array(np.asarray(k)),
        mx.nd.array(np.asarray(v)), causal=True, window=40)
    np.testing.assert_allclose(
        out.asnumpy(), np.asarray(_masked_reference(q, k, v, 40)),
        rtol=2e-5, atol=2e-5)
