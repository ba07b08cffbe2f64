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
    from mxnet_tpu.ops.pallas_flash import _flash_bwd, _flash_fwd
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
    lse = _flash_fwd(q, k, v, bq, bk, True, True, window, with_lse=True)[1]
    for gots in (jax.vjp(lambda *a: flash_attention(
                     *a, bq, bk, True, None, window), q, k, v)[1](do),
                 _flash_bwd(q, k, v, got, lse, do, True, bq, bk, window)):
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
# locations in the text), forward and gradients, at an explicit 64 x 64:
# SHA-256 of the text under the one installation (JAX 0.9.0). Taken anew
# in PR 32, whose kernel skips the mask on a block that is wholly visible
# and whose causal index map stays on a query block's last visible key
# block (the digests before it, at commit a612b22, were of the kernel as it
# stood before it knew of a window or of grouped heads). The gradients'
# taken anew when the forward began to hand its rows' log-sum-exp to the
# backward, whose own pass for it went; the forward's did not move
_BEFORE = {
    ("float32", "fwd"):
        "074d690d1ebb8044192c61034613b41033ee3aa56894695d64c5835e439d137d",
    ("float32", "grad"):
        "6d3f5fd584c02c812981ed4f22662b2536c6a3a82e0f753397ba5a7f86e3aadf",
    ("bfloat16", "fwd"):
        "826de351ba126f9d34ed700240c6e96fbcf8e01a0a4ce054720f9e3a27e88c0e",
    ("bfloat16", "grad"):
        "afcbe9dce4094cd9496921d81714dfbca2947480c89859a99cbd5c04f190ef61",
}


@pytest.mark.parametrize("dtype,which", sorted(_BEFORE))
def test_without_a_window_and_with_every_kv_head_the_program_is_as_before(
        dtype, which):
    """``window=None, H_kv = H``, an explicit 64 x 64: the program is the
    pinned kernel's, character for character, so its output is bit for bit
    (a changed kernel body, grid or index map would show here; after a
    deliberate change of that case, or another JAX, take the digests anew:
    PR 32 did, for the mask skipped on whole blocks and the clamped causal
    index map)."""
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
    # 16 query blocks: 1 + 2 + 3 + 4, then 5 each for the other 12
    assert blocks_visited(8192, 8192, 512, 512, 2048) == (70, 136)
    assert blocks_visited(8192, 8192, 512, 512, None) == (136, 136)
    # 8 query blocks: 1 + 2, then 3 each for the other 6
    assert blocks_visited(8192, 8192, 1024, 1024, 2048) == (21, 36)
    assert blocks_visited(8192, 8192, 1024, 1024, None) == (36, 36)


# (tokens, window, query block, key block, causal) -> steps of a head's grid
@pytest.mark.parametrize("args,steps", [
    ((8192, 8192, 128, 128, True, None), 4096),
    ((8192, 8192, 128, 128, True, 2048), 1088),      # 64 x 17
    ((8192, 8192, 512, 512, True, None), 256),
    ((8192, 8192, 512, 512, True, 2048), 80),        # 16 x 5
    ((8192, 8192, 1024, 1024, True, None), 64),
    ((8192, 8192, 1024, 1024, True, 2048), 24),      # 8 x 3
    ((200, 328, 128, 128, False, None), 6),          # 2 x 3, padded tails
    ((64, 256, 64, 128, True, None), 2),             # one query block
    ((100, 100, 512, 512, True, None), 1),           # under one tile
])
def test_grid_steps_are_query_blocks_times_the_longest_span(args, steps):
    from mxnet_tpu.ops.pallas_flash import grid_steps
    assert grid_steps(*args) == steps


# (tq, tk, d, dv, itemsize, window) -> (block_q, block_k)
@pytest.mark.parametrize("shape,tile", [
    ((8192, 8192, 128, 128, 2, None), (1024, 1024)),   # Trinity's full layer
    ((8192, 8192, 64, 64, 2, None), (1024, 1024)),     # Granite's: heads of 64
    ((8192, 8192, 64, 64, 4, None), (1024, 1024)),     # float32: 14.8 MiB
    ((8192, 8192, 128, 128, 2, 2048), (1024, 1024)),   # and its window layers
    ((8192, 8192, 192, 128, 2, None), (1024, 1024)),   # Kimi's MLA layer
    ((8192, 8192, 128, 128, 4, None), (1024, 1024)),   # float32: 15.5 MiB
    ((8192, 8192, 192, 128, 4, None), (512, 1024)),    # float32: 16.5 at 1,024
    ((8192, 8192, 576, 512, 2, None), (512, 1024)),    # a wide latent head
    ((8192, 8192, 128, 128, 2, 512), (512, 512)),      # no longer than the window
    ((8192, 8192, 128, 128, 2, 100), (128, 128)),
    ((300, 300, 32, 32, 4, None), (300, 300)),         # under one tile: itself
    ((64, 4096, 128, 128, 2, None), (64, 1024)),
    ((1500, 1500, 128, 128, 2, None), (1024, 1024)),   # the tail is padded
])
def test_tile_for_reads_the_tile_from_the_shapes(shape, tile):
    from mxnet_tpu.ops.pallas_flash import (VMEM_BUDGET, tile_bytes,
                                            tile_for)
    assert tile_for(*shape) == tile
    _tq, _tk, d, dv, itemsize, _window = shape
    assert tile_bytes(*tile, d, dv, itemsize) <= VMEM_BUDGET


def test_an_explicit_tile_is_honoured_as_given():
    """The caller's 64 x 64 is the grid's: 3 x 3 steps a head at 192
    tokens, where the shapes' own tile is the sequence, one step."""
    from mxnet_tpu.ops import registry
    from mxnet_tpu.ops.pallas_flash import _flash_fwd
    q, k, v = _grouped(4, 4, 192)
    op = registry.get("_contrib_FlashAttention").fn
    given, chosen = {}, {}
    with registry.program_counts(given):
        a = op(q, k, v, causal=True, block_q=64, block_k=64)
    with registry.program_counts(chosen):
        b = op(q, k, v, causal=True)
    assert given == {"attn/full_layers": 1, "attn/grid_steps": 9,
                     "attn/kv_blocks_visited": 6, "attn/kv_blocks_causal": 6,
                     "attn/bwd_plain": 1}
    assert chosen == {"attn/full_layers": 1, "attn/grid_steps": 1,
                      "attn/kv_blocks_visited": 1, "attn/kv_blocks_causal": 1,
                      "attn/bwd_plain": 1}
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)
    for blocks, grid in (((64, 64), (8, 3, 3)), ((None, None), (8, 1, 1)),
                         ((64, None), (8, 3, 1))):
        jaxpr = jax.make_jaxpr(lambda *x: _flash_fwd(
            *x, *blocks, True, True))(q, k, v)     # noqa: B023
        assert [tuple(e.params["grid_mapping"].grid) for e in jaxpr.eqns
                if e.primitive.name == "pallas_call"] == [grid]


# (query heads, KV heads, tq, tk, d, dv, window, query block, key block);
# a block of None is the shapes' own (tile_for): 512 under these windows,
# 1,024 without one
_CHOSEN = {
    "a window that is no multiple of the tile":
        (2, 2, 1200, 1200, 16, 16, 700, None, None),
    "block_q != block_k": (2, 2, 320, 320, 16, 16, 100, 128, 64),
    "grouped heads": (4, 2, 1100, 1100, 16, 16, None, None, None),
    "a value width of its own": (2, 2, 1100, 1100, 24, 16, 600, None, None),
    "tq != tk": (2, 1, 600, 1300, 16, 16, None, None, None),
    "a tail that needs padding": (2, 2, 1030, 1030, 16, 16, 520, None, None),
}


@pytest.mark.parametrize("case", sorted(_CHOSEN))
def test_the_chosen_tile_matches_the_dense_masked_softmax(case):
    """Forward (the kernel, interpreted, more than one block on each axis
    but for the query axis of ``tq != tk``) and gradients (through the
    custom_vjp) against the dense form."""
    from mxnet_tpu.ops.pallas_flash import grid_steps, tile_for
    h, hk, tq, tk, d, dv, window, bq, bk = _CHOSEN[case]
    rng = np.random.RandomState(len(case))
    mk = lambda n, t, w: jnp.asarray(                         # noqa: E731
        (rng.randn(1, n, t, w) * 2 / np.sqrt(d)).astype(np.float32))
    q, k, v, do = mk(h, tq, d), mk(hk, tk, d), mk(hk, tk, dv), mk(h, tq, dv)
    tile = tile_for(tq, tk, d, dv, 4, window) if bq is None else (bq, bk)
    assert grid_steps(tq, tk, *tile, True, window) > 1

    def flash(*a):
        return flash_attention(*a, bq, bk, True, None, window)

    def dense(*a):
        return _masked_reference(*a, window)
    got, pull = jax.vjp(flash, q, k, v)
    want, pull_dense = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for name, a, b in zip("qkv", pull(do), pull_dense(do)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


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


# ---- a scale of the caller's (Granite's attention_multiplier)
# (query heads, KV heads, tokens, width, scale, window, query block, key
# block): Granite's 1/64 at heads of 64, four query heads a KV head (its 32
# over 8), more than one block and the shapes' own tile; a scale with a
# window; a scale above 1 / sqrt(d)
_SCALED = [(8, 2, 200, 64, 0.015625, None, 64, 64),
           (32, 8, 130, 64, 0.015625, None, None, None),
           (4, 2, 192, 32, 0.05, 70, 64, 32),
           (4, 4, 256, 16, 1.0, None, 64, 64)]


@pytest.mark.parametrize("h,hk,t,d,scale,window,bq,bk", _SCALED)
def test_a_given_scale_multiplies_the_scores_forward_and_backward(
        h, hk, t, d, scale, window, bq, bk):
    """Against the dense masked softmax of ``scale q k^T``: the output,
    and the three gradients through the custom_vjp and through the
    blockwise backward at the forward's blocks."""
    from mxnet_tpu.ops.pallas_flash import _flash_bwd, _flash_fwd
    q, k, v = _grouped(h, hk, t, d)
    q = q * 4       # scores wide enough that the scale shows

    def dense(q, k, v):     # _masked_reference divides by sqrt(d)
        return _masked_reference(q * (scale * np.sqrt(d)), k, v, window)
    want, pull_dense = jax.vjp(dense, q, k, v)
    got, pull = jax.vjp(lambda *a: flash_attention(
        *a, bq, bk, True, None, window, scale), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    unscaled = flash_attention(q, k, v, bq, bk, True, None, window)
    assert float(jnp.max(jnp.abs(unscaled - want))) > 1e-2
    do = jnp.asarray(np.random.RandomState(1).randn(*want.shape)
                     .astype(np.float32))
    wants = pull_dense(do)
    lse = _flash_fwd(q, k, v, bq, bk, True, True, window, scale,
                     with_lse=True)[1]
    for gots in (pull(do), _flash_bwd(q, k, v, got, lse, do, True, bq or 512,
                                      bk or 512, window, scale)):
        for name, a, b in zip("qkv", gots, wants):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_default_scale_is_one_over_the_root_of_the_width(dtype):
    """``scale=None`` and ``scale = 1 / sqrt(d)`` are one program, bit for
    bit, forward and gradients; the registered op passes the keyword on
    (the pinned digests above hold the default to the kernel of before)."""
    from mxnet_tpu.ops import registry
    op = registry.get("_contrib_FlashAttention").fn
    q, k, v = (x.astype(dtype) for x in _grouped(4, 2, 192, 64))

    def run(**kw):
        out, pull = jax.vjp(lambda *a: op(*a, causal=True, block_q=64,
                                          block_k=64, **kw), q, k, v)
        return (out,) + pull(jnp.ones_like(out))
    for a, b in zip(run(), run(scale=0.125)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    other = run(scale=0.015625)[0]
    assert float(jnp.max(jnp.abs(other.astype(jnp.float32)
                                 - run()[0].astype(jnp.float32)))) > 1e-2


# ---- the backward kernel (flash_bwd) and the log-sum-exp it reads
def _dense_masked(q, k, v, causal=True, window=None, scale=None):
    """Dense softmax attention in float32 at the highest precision, masked
    as the kernels mask (grouped heads, the window, the cross-length
    diagonal); a row that sees no key gives zeros. Also the rows'
    log-sum-exp of the scaled, masked scores."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    h, hk, tq, tk = q.shape[1], k.shape[1], q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, h // hk, axis=1) for x in (k, v))
    scale = 1 / np.sqrt(q.shape[-1]) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    i = jnp.arange(tq)[:, None] + (tk - tq)
    j = jnp.arange(tk)[None, :]
    seen = (j <= i) if causal else jnp.ones((tq, tk), bool)
    if window is not None:
        seen = seen & (j > i - window)
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    p = jnp.where(seen.any(-1, keepdims=True), p, 0.0)
    lse = jax.nn.logsumexp(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest"), lse


# (query heads, KV heads, tq, tk, d, dv, causal, window, scale, query
# block, key block, dtype): causal full and windowed; groups of 1, 4, 8;
# 192-wide q.k over 128-wide v (here 96 / 64); a given scale; lengths no
# multiple of the blocks; the cross-length diagonal both ways; no mask;
# a window of one key; bfloat16
_BWD = [
    (2, 2, 256, 256, 64, 64, True, None, None, 64, 64, "float32"),
    (4, 1, 200, 200, 32, 32, True, None, None, 64, 64, "float32"),
    (8, 2, 200, 200, 32, 32, True, 70, None, 64, 32, "float32"),
    (8, 1, 192, 192, 32, 32, True, 64, None, 64, 64, "float32"),
    (4, 4, 256, 256, 96, 64, True, None, None, 128, 64, "float32"),
    (4, 2, 130, 130, 32, 16, True, 33, 0.05, 64, 64, "float32"),
    (8, 2, 200, 200, 64, 64, True, None, 0.015625, 64, 128, "float32"),
    (2, 2, 64, 256, 48, 32, True, None, None, 64, 128, "float32"),
    (2, 2, 256, 100, 32, 32, True, None, None, 64, 64, "float32"),
    (2, 2, 200, 328, 32, 32, False, None, None, 64, 128, "float32"),
    (8, 4, 192, 192, 64, 64, True, 1, None, 64, 64, "float32"),
    (4, 4, 256, 256, 96, 64, True, None, None, 128, 64, "bfloat16"),
    (8, 2, 256, 256, 64, 64, True, 100, None, 64, 128, "bfloat16"),
]


@pytest.mark.parametrize("h,hk,tq,tk,d,dv,causal,window,scale,bq,bk,dtype",
                         _BWD)
def test_the_backward_kernel_matches_the_plain_backward_and_the_dense(
        h, hk, tq, tk, d, dv, causal, window, scale, bq, bk, dtype):
    """dQ, dK, dV of ``flash_bwd`` (interpreted) against ``_flash_bwd`` on
    the same handed-on log-sum-exp, and against the dense form's
    autodiff; the forward's log-sum-exp against the dense one."""
    from mxnet_tpu.ops.pallas_flash import _flash_bwd, _flash_fwd, flash_bwd
    rng = np.random.RandomState(3)

    def mk(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 2
                           / np.sqrt(d)).astype(dtype)
    q, k, v = mk(2, h, tq, d), mk(2, hk, tk, d), mk(2, hk, tk, dv)
    do = jnp.asarray(rng.randn(2, h, tq, dv).astype(np.float32)).astype(dtype)
    o, lse = _flash_fwd(q, k, v, bq, bk, causal, True, window, scale,
                        with_lse=True)
    want_o, want_lse = _dense_masked(q, k, v, causal, window, scale)
    seen = np.isfinite(np.asarray(want_lse))
    np.testing.assert_allclose(np.asarray(lse)[seen],
                               np.asarray(want_lse)[seen], rtol=1e-5,
                               atol=1e-5)
    got = flash_bwd(q, k, v, o, lse, do, causal, True, window, scale, bq, bk)
    plain = _flash_bwd(q, k, v, o, lse, do, causal, bq, bk, window, scale)
    wants = jax.vjp(lambda *a: _dense_masked(*a, causal, window, scale)[0],
                    q, k, v)[1](do.astype(jnp.float32))
    tol = 2e-4 if dtype == "float32" else 2e-2
    # relative to the largest gradient (a window of one key makes dQ zero)
    top = max(float(np.max(np.abs(c))) for c in wants)
    for name, a, b, c in zip("qkv", got, plain, wants):
        assert a.shape == b.shape == c.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=tol * top, err_msg=name)
        np.testing.assert_allclose(a, np.asarray(c), atol=tol * top,
                                   err_msg=name)


def test_the_key_side_spans_visit_the_forwards_pairs():
    """The backward walks key blocks outermost and, for each, the query
    blocks ``_query_span`` gives: the same (query block, key block) pairs
    as the forward's ``_block_span``, over windows, tiles and lengths."""
    from mxnet_tpu.ops.pallas_flash import _block_span, _query_span
    for tq, tk in ((256, 256), (200, 328), (328, 200), (64, 256), (8192,
                                                                  8192)):
        for bq, bk in ((64, 64), (64, 128), (128, 32), (512, 1024)):
            for window in (None, 1, 33, 128, 500, 2048):
                n_q, n_k = -(-tq // bq), -(-tk // bk)
                first, last = _block_span(np, np.arange(n_q), bq, bk, n_k,
                                          tk - tq, window)
                fwd = {(i, j) for i in range(n_q)
                       for j in range(first[i], last[i] + 1)}
                qf, ql = _query_span(np, np.arange(n_k), bq, bk, n_q,
                                     tk - tq, window)
                bwd = {(i, j) for j in range(n_k)
                       for i in range(qf[j], ql[j] + 1)}
                assert fwd == bwd, (tq, tk, bq, bk, window)


def test_the_gradients_through_the_op_take_the_kernel_where_eligible():
    """Through ``flash_attention``'s custom_vjp at shapes the kernel takes
    (``bwd_eligible``: 256 tokens, heads of 128, each its own KV head or a
    group under a window, the kernel's own tile) and at shapes it declines
    (heads of 64; a group that sees every earlier key): all against the
    dense form, and the registered op counts which backward each core
    took."""
    from mxnet_tpu.ops import registry
    from mxnet_tpu.ops.pallas_flash import bwd_eligible, bwd_tile_for
    op = registry.get("_contrib_FlashAttention").fn
    assert bwd_tile_for(256, 256, 128, 128, 4) == (256, 256)
    for hk, d, window, kernel in ((4, 128, None, True), (2, 128, 100, True),
                                  (4, 64, None, False),
                                  (2, 128, None, False)):
        assert bwd_eligible(256, 256, d, d, 4 // hk, window,
                            jnp.float32) == kernel
        q, k, v = _grouped(4, 2, 256, d)
        k, v = k[:, :hk], v[:, :hk]
        if hk == 4:
            k, v = (jnp.concatenate([x, x[:, ::-1] * 0.5], 1)
                    for x in (k, v))
        do = jnp.asarray(np.random.RandomState(1).randn(*q.shape)
                         .astype(np.float32))
        counts = {}
        with registry.program_counts(counts):
            got = jax.vjp(lambda *a: op(*a, causal=True, window=window),
                          q, k, v)[1](do)
        path = "attn/bwd_kernel" if kernel else "attn/bwd_plain"
        assert counts[path] == 1 and len(
            [c for c in counts if c.startswith("attn/bwd_")]) == 1
        wants = jax.vjp(lambda *a: _masked_reference(*a, window), q, k,
                        v)[1](do)
        for name, a, b in zip("qkv", got, wants):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)


def test_the_forward_alone_hands_on_nothing_and_the_residuals_are_kept():
    """A forward that is not differentiated returns the output alone (the
    eager op, ``parallel.flash_attention``); under a stage the output and
    the rows' log-sum-exp are the values kept."""
    from mxnet_tpu.ops import registry
    q, k, v = _grouped(4, 2, 192)
    out = flash_attention(q, k, v, 64, 64, True)
    assert isinstance(out, jax.Array) and out.shape == q.shape
    kept = []
    with registry.stage_marks(kept):
        jax.vjp(lambda *a: flash_attention(*a, 64, 64, True), q, k, v)
    assert kept == [q.size * 4, q.size // q.shape[3] * 4]


# the attention core of each language-model cell, 8,192 tokens in
# bfloat16: (heads, KV heads, q.k width, v width, window) -> whether the
# backward kernel takes it, and the tile it would take (PERF.md §6:
# the kernel where it was faster on the chip)
_CELL_CORES = {
    "ouro": ((16, 16, 128, 128, None), True, (1024, 1024)),
    "kimi_mla": ((32, 32, 192, 128, None), True, (512, 1024)),
    "trinity_window": ((32, 4, 128, 128, 2048), True, (1024, 1024)),
    "trinity_full": ((32, 4, 128, 128, None), False, (1024, 1024)),
    "granite": ((32, 8, 64, 64, None), False, (1024, 1024)),
}


@pytest.mark.parametrize("cell", sorted(_CELL_CORES))
def test_bwd_eligible_at_the_cells_cores(cell):
    from mxnet_tpu.ops.pallas_flash import (VMEM_BUDGET, bwd_eligible,
                                            bwd_tile_bytes, bwd_tile_for)
    (h, hk, d, dv, window), kernel, tile = _CELL_CORES[cell]
    assert bwd_eligible(8192, 8192, d, dv, h // hk, window,
                        jnp.bfloat16) == kernel
    assert bwd_tile_for(8192, 8192, d, dv, 2, window) == tile
    assert bwd_tile_bytes(*tile, d, dv, 2) <= VMEM_BUDGET
    # float32 operands take the next tile; what fits no tile goes plain
    assert bwd_tile_for(8192, 8192, d, dv, 4, window) == (512, 1024)
    assert not bwd_eligible(8192, 8192, d, dv, h // hk, window, jnp.float16)
