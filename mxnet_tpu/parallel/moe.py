"""The expert layer of a sparse mixture of experts, as one expert-parallel
rank computes it.

Reference role: none — the reference predates sparse experts; this fills
the ``ep`` slot of the framework's parallelism matrix (dp/tp/pp/sp/ep).

The layer is told which of the model's experts it holds (``experts_held =
[lo, hi)``), routes every token over *all* of them (sigmoid scores, the
top ``k`` of ``score + bias``, weights ``scale * s_e / sum_top_k s``:
the DeepSeek-V3 / Kimi router), and computes the part of the result that
its own experts give. What the absent experts would add is another rank's
to compute and to add; on one chip the layer runs without that exchange,
and its partial result is what goes on.

No token is dropped, there is no capacity and no auxiliary loss. The
assignments are sorted by expert, those of absent experts last, and only
the sorted vectors (``N * k`` long) are ever that tall: everything after
the sort walks *blocks of ``B`` consecutive sorted assignments*, and only
the ``ceil(held / B)`` blocks that hold an assignment of a held expert
(:func:`_walk`: a loop whose trip count is read from the routing). A block
gathers its ``B`` tokens, runs each of the three matrices as one grouped
product over the block's own ragged groups (``lax.ragged_dot``: on the TPU
a kernel that visits only the tiles of rows a group holds) and adds its
rows back to their tokens with the router's weights. The loop has a
backward pass of its own over the same blocks, which keeps the layer's
inputs and nothing a block made. ``B`` is the layer's own choice from its
shapes (:func:`block_rows`): twice this rank's even share of the
assignments, and no fewer rows than its experts have hidden units, so
that an even router is one trip, a skewed one takes more and stays exact,
and a rank that holds every expert is one block of ``N * k`` rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["route", "expert_layer", "block_rows", "trips", "swiglu"]

_F32 = jnp.float32
# a block of the walk over the sorted assignments: this many even shares
# of the rank, in whole tiles of this many rows (block_rows)
_SHARES, _TILE = 2, 512


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x Wg^T) * (x Wu^T)) Wd^T``; matrices stored (out, in), a
    leading expert axis on all three batches it over ``x`` (E, rows, d)."""
    g = jnp.einsum("...rd,...hd->...rh", x, w_gate)
    u = jnp.einsum("...rd,...hd->...rh", x, w_up)
    return jnp.einsum("...rh,...dh->...rd", jax.nn.silu(g) * u, w_down)


def route(x, router_weight, router_bias, top_k, scale):
    """(chosen (N, k) int32, weight (N, k) float32): the router in
    float32 at the highest matmul precision, so that rounding moves few
    choices. The bias only selects; the weights come from the scores."""
    with jax.named_scope("mx/moe/route"):
        s = jax.nn.sigmoid(jnp.matmul(
            x.astype(_F32), router_weight.astype(_F32).T,
            precision=lax.Precision.HIGHEST))
        _, chosen = lax.top_k(s + router_bias.astype(_F32), top_k)
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        weight = scale * picked / jnp.sum(picked, -1, keepdims=True)
        return chosen.astype(jnp.int32), weight


def _grouped(rows, w, counts):
    """rows (R, in) x the group's own matrix of w (G, out, in), groups of
    ``counts`` rows in turn; rows past the last group belong to none.
    Traced under ``mx/moe/experts`` like all of the walk, forward and
    backward; the TPU compiler makes each product a kernel call of its
    own and names it itself (``op_name="ragged-dot-none"``), so in the
    compiled text the scope stops here (docs/observability.md)."""
    return lax.ragged_dot(rows, jnp.swapaxes(w, 1, 2), counts)


def block_rows(n, top_k, held, experts, width):
    """Sorted assignments a block of :func:`_walk` holds: ``_SHARES`` times
    the even share of ``n * top_k`` assignments that ``held`` of
    ``experts`` experts receive, and no fewer than the held experts have
    hidden units together (``held * width``: every trip of the backward
    walk passes once over all their gradient matrices, which a shorter
    block's rows would not outweigh); in whole tiles of ``_TILE`` rows and
    never more than there are."""
    even = -(-n * top_k * held // experts)
    rows = max(_SHARES * even, held * width)
    return min(n * top_k, -(-rows // _TILE) * _TILE)


def _block(i, x, w_gate, w_up, w_down, gate, order, counts, top_k, block):
    """(what block ``i`` of the sorted assignments adds to its tokens
    (B, d) float32, the tokens (B,))."""
    first = i * block
    idx = lax.dynamic_slice(order, (first,), (block,))
    token = idx // top_k
    ends = jnp.clip(jnp.cumsum(counts) - first, 0, block)
    sizes = jnp.diff(ends, prepend=0)           # the block's own groups
    # a row past the groups is in no product: what it holds is never
    # read, forward or backward
    ours = (jnp.arange(block) < ends[-1])[:, None]
    rows = jnp.where(ours, x[token], 0)
    act = jax.nn.silu(_grouped(rows, w_gate, sizes)) \
        * _grouped(rows, w_up, sizes)
    out = jnp.where(ours, _grouped(act, w_down, sizes), 0)
    return out.astype(_F32) * gate[idx][:, None], token


def trips(counts, block):
    """Blocks of ``block`` sorted assignments that hold a row of a held
    expert: the walk's trip count, read from the routing."""
    return (jnp.sum(counts) + block - 1) // block


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _walk(x, w_gate, w_up, w_down, gate, order, counts, top_k, block):
    """The held experts' part of the layer (N, d) from the sorted
    assignments: ``order`` (whole blocks long) names them by expert,
    ``gate`` (N * k,) is each one's weight (0 for an absent expert's),
    ``counts`` the rows of each held expert. Only the blocks that hold a
    row of a held expert are visited; the sum over blocks is float32."""
    def body(i, y):
        add, token = _block(i, x, w_gate, w_up, w_down, gate, order,
                            counts, top_k, block)
        return y.at[token].add(add)
    return lax.fori_loop(0, trips(counts, block), body,
                         jnp.zeros(x.shape, _F32)).astype(x.dtype)


def _walk_fwd(x, w_gate, w_up, w_down, gate, order, counts, top_k, block):
    # the inputs are all the backward pass needs: nothing a block made
    # is kept
    return (_walk(x, w_gate, w_up, w_down, gate, order, counts, top_k,
                  block),
            (x, w_gate, w_up, w_down, gate, order, counts))


def _walk_bwd(top_k, block, res, dy):
    """The same blocks again, each one's ``jax.vjp`` at its tokens' rows
    of ``dy``. The gradients add up across blocks in float32 and are cast
    once, so that two trips round no more than one."""
    *diff, order, counts = res

    def body(i, grads):
        _, pull, token = jax.vjp(
            lambda *a: _block(i, *a, order, counts, top_k, block), *diff,
            has_aux=True)
        return tuple(g + d.astype(_F32)
                     for g, d in zip(grads, pull(dy[token].astype(_F32))))
    grads = lax.fori_loop(0, trips(counts, block), body,
                          tuple(jnp.zeros(a.shape, _F32) for a in diff))
    # the casts are made here and not inside the optimizer's fusion, or
    # every expert layer's float32 sums live until the step's last op
    # (0.9 GB of the Kimi step's temporaries, compiled for a v5e)
    return lax.optimization_barrier(
        tuple(g.astype(a.dtype) for g, a in zip(grads, diff))) + (None, None)


_walk.defvjp(_walk_fwd, _walk_bwd)


def expert_layer(x, router_weight, router_bias, w_gate, w_up, w_down, *,
                 experts_held, top_k, scale=1.0):
    """x (N, d) -> (this rank's part of the routed result (N, d), the
    tokens each held expert received (hi - lo,) int32).

    ``router_weight`` (E, d) and ``router_bias`` (E,) span all E experts;
    ``w_gate``, ``w_up`` (hi - lo, h, d) and ``w_down`` (hi - lo, d, h)
    are the experts ``lo .. hi - 1``."""
    n = x.shape[0]
    lo, hi = experts_held
    held = hi - lo
    chosen, weight = route(x, router_weight, router_bias, top_k, scale)

    with jax.named_scope("mx/moe/experts"):
        local = chosen - lo                                      # (N, k)
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held).reshape(-1)
        counts = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
        # sort the assignments by expert; those of absent experts last
        order = jnp.argsort(key, stable=True)
        gate = jnp.where(mine, weight, 0.0).reshape(-1)
        block = block_rows(n, top_k, held, router_weight.shape[0],
                           w_gate.shape[1])
        # whole blocks, so that no slice of ``order`` is clamped; what is
        # added lies past every group
        order = jnp.pad(order, (0, -(n * top_k) % block))
        return _walk(x, w_gate, w_up, w_down, gate, order, counts, top_k,
                     block), counts
