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
assignments are sorted by expert, those of absent experts last; the held
experts' rows then lie in ``hi - lo`` groups of ragged size, and each of
the three matrices is one grouped product over them (``lax.ragged_dot``:
on the TPU a kernel that visits only the tiles of rows a group holds, so
the work follows the assignments held and never a padded block). The rows
are added back to their tokens with the router's weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["route", "expert_layer", "swiglu"]

_F32 = jnp.float32


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
    ``counts`` rows in turn; rows past the last group belong to none."""
    return lax.ragged_dot(rows, jnp.swapaxes(w, 1, 2), counts)


def expert_layer(x, router_weight, router_bias, w_gate, w_up, w_down, *,
                 experts_held, top_k, scale=1.0):
    """x (N, d) -> (this rank's part of the routed result (N, d), the
    tokens each held expert received (hi - lo,) int32).

    ``router_weight`` (E, d) and ``router_bias`` (E,) span all E experts;
    ``w_gate``, ``w_up`` (hi - lo, h, d) and ``w_down`` (hi - lo, d, h)
    are the experts ``lo .. hi - 1``."""
    n, d = x.shape
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
        token = order // top_k
        # a row past the groups is in no product: what it holds is never
        # read, forward or backward
        ours = (jnp.arange(n * top_k) < jnp.sum(counts))[:, None]
        rows = jnp.where(ours, x[token], 0)
        act = jax.nn.silu(_grouped(rows, w_gate, counts)) \
            * _grouped(rows, w_up, counts)
        out = jnp.where(ours, _grouped(act, w_down, counts), 0)
        share = jnp.where(mine, weight, 0.0).reshape(-1)[order]
        y = jnp.zeros((n, d), _F32).at[token].add(
            out.astype(_F32) * share[:, None])
        return y.astype(x.dtype), counts
