"""Ops of a language model built from a Symbol: RMSNorm, the rotary
position embedding, the causal depthwise convolution of linear-attention
and state-space layers, the KDA recurrence (Kimi Delta Attention,
arXiv:2510.26692) and Mamba-2's (arXiv:2405.21060) in chunks, in one frame
of groups of chunks, Mamba-2's gated norm, the head that gives every
token's loss without the tokens x vocabulary array, and a looped model's
exit gate and the loss it weights by the passes.

All are pure JAX but the two halves of the KDA core and Mamba-2's chunks:
KDA's work inside chunks and its scan go each to two Pallas kernels
(``ops/pallas_kda.py``) where a head's tile is one of theirs (heads of 128 or
256), Mamba-2's group of chunks to three (``ops/pallas_ssd.py``) where its core
is (heads of 64 or 128); plain JAX (``_intra_plain``, ``_scan_plain``,
``_ssd_group``: the kernels' oracles) for any other shape. Gradients come from ``jax.vjp`` (the head's from a ``custom_vjp``
that works through the tokens in blocks, the KDA core's from one that walks
its groups of chunks in reverse and marks what a ``mirror_stage`` should
keep; Mamba-2's core runs in the same frame). Each of the
layers a device trace should tell apart carries a ``jax.named_scope``
(``mx/kda`` with ``mx/kda/intra`` and ``mx/kda/scan`` inside it, ``mx/ssm``
with ``mx/ssm/intra`` and ``mx/ssm/scan``, ``mx/rope``, ``mx/lm_head``,
``mx/exit``; docs/observability.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_kda
from .registry import (program_count, program_gauge, program_max, register,
                       set_op_meta, stage_keep)

_F32 = jnp.float32


@register("RMSNorm")
def rms_norm(data, gamma, *, eps=1e-5):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis; the
    statistics in float32 whatever the input's dtype."""
    x = data.astype(_F32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (y * gamma.astype(_F32)).astype(data.dtype)


@register("_contrib_RoPE")
def rope(data, *, theta=10000.0):
    """Rotary position embedding over the last axis of (B, T, H, D), the
    halves split at D / 2 (``x cos + rotate_half(x) sin``, ``rotate_half(x)
    = [-x2, x1]``): pair ``i`` of a token at position ``t`` turns by ``t
    theta^(-2 i / D)``; positions 0 .. T-1, no scaling. Angles in float32
    whatever the input's dtype."""
    with jax.named_scope("mx/rope"):
        t, n = data.shape[1], data.shape[-1] // 2
        ang = jnp.arange(t, dtype=_F32)[:, None] \
            * float(theta) ** (-jnp.arange(n, dtype=_F32) / n)[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = data[..., :n].astype(_F32), data[..., n:].astype(_F32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(data.dtype)


@register("_contrib_CausalConv1D")
def causal_conv1d(data, weight, bias=None, *, act_type="silu", no_bias=True):
    """Depthwise convolution over time that sees no later token:
    ``y_t = sum_i w[:, i] x_{t-(K-1)+i}`` with zeros before the sequence,
    plus ``bias`` (C,) with ``no_bias=False``, then ``act_type`` (``silu``
    or ``none``). data: (B, T, C); weight: (C, K)."""
    k = weight.shape[1]
    t = data.shape[1]
    xp = jnp.pad(data, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + t] * weight[:, i] for i in range(k))
    if bias is not None and not no_bias:
        y = y + bias
    return jax.nn.silu(y) if act_type == "silu" else y


# --------------------------------------------------------------------- KDA
def _tri_solve(a, rhs):
    """``(I + a)^-1 rhs`` for strictly lower triangular ``a`` (..., C, C),
    by forward substitution on blocks of 16 rows: the diagonal blocks are
    inverted through their nilpotent series (exact after four
    multiplications), the rest is matrix products."""
    s = 16
    n = a.shape[-1] // s        # a chunk is a whole number of sub-blocks
    hi = lax.Precision.HIGHEST
    eye = jnp.eye(s, dtype=a.dtype)
    out = []
    for i in range(n):
        r = rhs[..., i * s:(i + 1) * s, :]
        for j in range(i):
            r = r - jnp.matmul(a[..., i * s:(i + 1) * s, j * s:(j + 1) * s],
                               out[j], precision=hi)
        d = a[..., i * s:(i + 1) * s, i * s:(i + 1) * s]
        # (I + d)^-1 = (I - d)(I + d^2)(I + d^4)(I + d^8), d^16 = 0
        inv = eye - d
        p = jnp.matmul(d, d, precision=hi)
        for _ in range(3):
            inv = jnp.matmul(inv, eye + p, precision=hi)
            p = jnp.matmul(p, p, precision=hi)
        out.append(jnp.matmul(inv, r, precision=hi))
    return jnp.concatenate(out, axis=-2)


@jax.checkpoint
def _pair_exact(a, b, g):
    """``sum_d a_t[d] b_s[d] exp(g_t[d] - g_s[d])`` for s <= t of one
    sub-block, channel by channel. Rematerialised: the (t, s, d) array of
    decays is recomputed in the backward pass, never kept."""
    n = a.shape[-2]
    diff = g[..., :, None, :] - g[..., None, :, :]             # t, s, d
    keep = jnp.tril(jnp.ones((n, n), bool))[..., None]
    e = jnp.exp(jnp.where(keep, diff, -jnp.inf))
    return jnp.sum(e * a[..., :, None, :] * b[..., None, :, :], axis=-1)


def _pair_scores(a, b, g, sub):
    """``sum_d a_t[d] b_s[d] exp(G_t[d] - G_s[d])`` for s <= t within a
    chunk, 0 above the diagonal; a, b, g: (..., C, D) with ``g`` the
    cumulative log decay (decreasing along C). No exponent is ever
    positive: rows and columns of one sub-block of ``sub`` tokens are
    paired exactly, channel by channel; a row meets the columns of earlier
    sub-blocks through the decay up to its own sub-block's first token
    (its factor and theirs both at most 1), as a matrix product."""
    c = a.shape[-2]
    n = c // sub
    hi = lax.Precision.HIGHEST
    rows = []
    for i in range(n):
        lo, up = i * sub, (i + 1) * sub
        gi = g[..., lo:up, :]
        # the log decay just before the sub-block: G of the token before
        ref = g[..., lo - 1:lo, :] if i else jnp.zeros_like(g[..., :1, :])
        parts = []
        if i:
            left = a[..., lo:up, :] * jnp.exp(gi - ref)
            right = b[..., :lo, :] * jnp.exp(ref - g[..., :lo, :])
            parts.append(jnp.einsum("...td,...sd->...ts", left, right,
                                    precision=hi))
        parts.append(_pair_exact(a[..., lo:up, :], b[..., lo:up, :], gi))
        if up < c:
            parts.append(jnp.zeros(a.shape[:-2] + (sub, c - up), a.dtype))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _intra_plain(q, k, v, g, beta, chunk, sub):
    """The work inside the chunks of a group in plain JAX, all its chunks
    at once: what the scan over them consumes, each (N, B, H, C, .). The
    path of any tile the kernels do not take, and their oracle."""
    b, t, h = q.shape[:3]
    dv = v.shape[-1]
    n = t // chunk

    def chunks(x):      # (B, T, H, D) -> (N, B, H, C, D)
        x = x.reshape((b, n, chunk, h) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta[..., None])                         # (N, B, H, C, 1)
    gc = jnp.cumsum(g, axis=-2)                            # G_t, <= 0
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strict, _pair_scores(k, k, gc, sub), 0.0) * beta
    m = _pair_scores(q, k, gc, sub)                        # q.k, s <= t
    decay = jnp.exp(gc)                                    # within (0, 1]
    rhs = jnp.concatenate([v * beta, k * decay * beta], -1)
    sol = _tri_solve(a, rhs)
    g_end = gc[..., -1:, :]                                # (N, B, H, 1, dk)
    return (sol[..., :dv], sol[..., dv:], m, q * decay,
            k * jnp.exp(g_end - gc), g_end)                # k decayed to the end


def _scan_plain(s, *xs):
    """The scan from chunk to chunk of a group in plain JAX, from the state
    ``s`` (B, H, d_k, d_v) over the six values ``xs`` of the work inside
    chunks, each (N, B, H, C, .): (the state after the group, o (N, B, H,
    C, d_v)). The path of any tile the kernels do not take, and their
    oracle."""
    hi = lax.Precision.HIGHEST

    def step(s, x):
        u0_c, w_c, m_c, q_c, k_c, ge_c = x
        u = u0_c - jnp.matmul(w_c, s, precision=hi)
        o = jnp.matmul(q_c, s, precision=hi) + jnp.matmul(m_c, u, precision=hi)
        s = s * jnp.exp(jnp.swapaxes(ge_c, -1, -2)) \
            + jnp.matmul(jnp.swapaxes(k_c, -1, -2), u, precision=hi)
        return s, o

    return lax.scan(step, s, xs)


def _kda_group(s, q, k, v, g, beta, chunk, sub):
    """A group of whole chunks from the state ``s`` (B, H, d_k, d_v):
    inside every chunk in matrix form, all the group's chunks at once (the
    pseudo-values ``U = (I + A)^-1 (beta V - beta K+ S_0)``), then from
    chunk to chunk a scan that carries the state. q, k, g: (B, T, H, d_k);
    v: (B, T, H, d_v); beta: (B, T, H), float32, T a multiple of
    ``chunk``. Returns (the state after the group, o (B, T, H, d_v)).
    The two halves run under the scopes ``mx/kda/intra`` (parallel over
    chunks) and ``mx/kda/scan`` (sequential), for a device trace to split
    the core by; each is two Pallas kernels, forward and backward, where a
    head's tile is one of theirs (``pallas_kda.eligible``,
    ``pallas_kda.scan_eligible``), else plain JAX."""
    from ..kernels.tier import resolve_interpret
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    with jax.named_scope("mx/kda/intra"):
        if pallas_kda.eligible(dk, dv, chunk, sub):
            xs = pallas_kda.kda_intra(q, k, v, g, beta, chunk, sub,
                                      resolve_interpret())
        else:
            xs = _intra_plain(q, k, v, g, beta, chunk, sub)
    with jax.named_scope("mx/kda/scan"):
        if pallas_kda.scan_eligible(dk, dv, chunk):
            s, o = pallas_kda.kda_scan(s, *xs, resolve_interpret())
        else:
            s, o = _scan_plain(s, *xs)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)          # (B, N, C, H, dv)
    return s, o.reshape(b, t, h, dv)


def _kda_chunk(t, chunk, sub):
    """The chunk that ``t`` tokens are cut into: ``chunk``, or the whole
    sub-blocks that hold a sequence shorter than one."""
    return min(chunk, -(-t // sub) * sub)


# ------------------------------------------- a recurrence in groups of chunks
# The frame KDA's delta rule and Mamba-2's scalar-decay scan share: a scan
# over groups of whole chunks that keeps the state on entry to each, and a
# backward pass of its own that walks the groups in reverse and recomputes a
# group's interior from its entry state.
def _group_span(t, chunk, group):
    """Tokens in a group: ``group`` chunks, or the chunks that hold a
    sequence shorter than that."""
    return min(group, -(-t // chunk)) * chunk


def _grouped(xs, span):
    """How ``xs`` (B, T, ...) are worked through ``span`` tokens at a time:
    ``(to_groups, from_groups)``. ``to_groups`` pads an array with zero rows
    after the sequence (they change nothing before) and puts the groups
    first, (N, B, span, ...); ``from_groups`` undoes it."""
    b, t = xs[0].shape[:2]
    pad = (-t) % span
    n = (t + pad) // span

    def to_groups(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((b, n, span) + x.shape[2:]), 1, 0)

    def from_groups(x):
        return jnp.moveaxis(x, 0, 1).reshape((b, n * span) + x.shape[3:])[:, :t]

    return to_groups, from_groups


def _groups_fwd_scan(run, span, state, dtype, consts, xs):
    """The scan over groups from a zero state of the shape ``state``:
    (o (B, T, ...) in ``dtype``, the state on entry to every group (N,
    *state)). ``run(consts, s, x)`` is one group from the state ``s``:
    (the state after it, its rows of the output in float32)."""
    to_groups, from_groups = _grouped(xs, span)

    def body(s, x):
        after, o = run(consts, s, x)
        return after, (s, o)

    _, (states, o) = lax.scan(body, jnp.zeros(state, _F32),
                              tuple(to_groups(x) for x in xs))
    return from_groups(o).astype(dtype), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _groups_core(run, span, state, dtype, consts, xs):
    return _groups_fwd_scan(run, span, state, dtype, consts, xs)[0]


def _groups_core_fwd(run, span, state, dtype, consts, xs):
    # inside a mirror_stage the output and the groups' entry states are
    # kept: the stage's backward pass recomputes ``xs`` from the
    # projections and never runs this scan again
    o, states = _groups_fwd_scan(run, span, state, dtype, consts, xs)
    o, states = stage_keep(o), stage_keep(states)
    return o, (consts, xs, states)


def _groups_core_bwd(run, span, state, dtype, res, ct):
    """The groups in reverse, each recomputed from its entry state (what
    bounds memory in T: a group's interior exists one group at a time),
    the state's cotangent carried from group to group."""
    consts, xs, states = res
    to_groups, from_groups = _grouped(xs, span)

    def body(carry, a):
        ds, dconsts = carry
        s, x, do = a
        _, pull = jax.vjp(run, consts, s, x)
        dc, ds, dx = pull((ds, do))
        return (ds, jax.tree.map(jnp.add, dconsts, dc)), dx

    zeros = (jnp.zeros_like(states[0]), jax.tree.map(jnp.zeros_like, consts))
    (_, dconsts), dxs = lax.scan(
        body, zeros,
        (states, tuple(to_groups(x) for x in xs), to_groups(ct.astype(_F32))),
        reverse=True)
    return dconsts, tuple(from_groups(d) for d in dxs)


_groups_core.defvjp(_groups_core_fwd, _groups_core_bwd)


def _kda_run(pre, chunk, sub, consts, s, x):
    return _kda_group(s, *pre(*consts, *x), chunk, sub)


def _as_given(*x):
    return x


def kda_chunked(xs, pre=_as_given, consts=(), *, chunk=64, sub=16, group=16,
                dtype=_F32):
    """The delta rule with a per-channel forget gate,
    ``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T``,
    ``o_t = S_t^T q_t`` from a zero state, in chunks of ``chunk`` tokens
    (:func:`_kda_group`), ``group`` chunks at a time, with a forward and a
    backward pass of its own (``jax.custom_vjp``): the forward is a scan
    over groups that keeps the state on entry to each; the backward walks
    the groups in reverse and recomputes a group's interior (whose own scan
    keeps one state a chunk) from its entry state, so what a step holds
    does not grow with the sequence. Inside a ``mirror_stage`` the output
    and those states are kept (``stage_keep``): the core runs forward once
    for the step and once a group for the backward pass.

    ``xs`` are arrays (B, T, ...) cut along T; ``pre(*consts, *slices)``
    maps a group's slices of them to ``(q, k, v, g, beta)`` in float32 (q,
    k, g: (B, t, H, d_k); v: (B, t, H, d_v); beta: (B, t, H)), and without
    it ``xs`` are those five. ``pre`` closes over no array: what it needs
    beside the slices comes in ``consts``, which get their gradient too.
    Returns o (B, T, H, d_v) in ``dtype``.

    The work inside chunks goes to the two Pallas kernels of
    ``ops/pallas_kda.py`` where a head's tile is one of theirs
    (``pallas_kda.eligible``: d_k and d_v of 128 or 256, a chunk of at
    most 128 in sub-blocks of whole 8-row registers), the scan from chunk
    to chunk to the two that keep the state in VMEM
    (``pallas_kda.scan_eligible``: the same widths, a chunk of whole
    registers), and either through plain JAX for any other shape; a
    training program counts its cores of either kind in the gauges
    ``kda/intra_kernel`` and ``kda/intra_plain``, ``kda/scan_kernel`` and
    ``kda/scan_plain``."""
    q0, _, v0, _, _ = jax.eval_shape(pre, *consts, *(x[:, :1] for x in xs))
    b, t = xs[0].shape[:2]
    chunk = _kda_chunk(t, chunk, sub)
    dk, dv = q0.shape[3], v0.shape[3]
    program_count("kda/intra_kernel" if pallas_kda.eligible(dk, dv, chunk, sub)
                  else "kda/intra_plain")
    program_count("kda/scan_kernel" if pallas_kda.scan_eligible(dk, dv, chunk)
                  else "kda/scan_plain")
    return _groups_core(functools.partial(_kda_run, pre, chunk, sub),
                        _group_span(t, chunk, group),
                        (b, q0.shape[2], q0.shape[3], v0.shape[3]),
                        jnp.dtype(dtype), tuple(consts), tuple(xs))


program_gauge("kda/intra_kernel",
              "KDA cores of the training program traced last whose work "
              "inside chunks went to the Pallas kernels (ops/pallas_kda.py)")
program_gauge("kda/intra_plain",
              "KDA cores of the training program traced last whose work "
              "inside chunks went through plain JAX (a head's tile is none "
              "of the kernels')")
program_gauge("kda/scan_kernel",
              "KDA cores of the training program traced last whose scan "
              "from chunk to chunk went to the Pallas kernels that keep the "
              "state in VMEM (ops/pallas_kda.py)")
program_gauge("kda/scan_plain",
              "KDA cores of the training program traced last whose scan "
              "from chunk to chunk went through lax.scan (a head's tile is "
              "none of the kernels')")


@register("_contrib_KDA")
def kda(q, k, v, f, b, a_log, dt_bias, *, num_heads, chunk=64):
    """Kimi Delta Attention's core. q, k, v: (B, T, H*d) after their
    convolutions; f: (B, T, H*d) the forget gate's projection; b: (B, T,
    H) the logits of beta; a_log: (H,); dt_bias: (H*d,). Per head
    ``q = l2norm(q) d^-1/2``, ``k = l2norm(k)``, ``g = -exp(a_log)
    softplus(f + dt_bias)``, ``beta = sigmoid(b)``, then the recurrence
    of :func:`kda_chunked`. Gate and state are float32 whatever the
    inputs' dtype; the output takes ``v``'s."""
    with jax.named_scope("mx/kda"):
        h = num_heads
        rate = jnp.exp(a_log.astype(_F32))[:, None]
        bias = dt_bias.astype(_F32).reshape(h, -1)

        def l2norm(x):
            return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                 + 1e-6)

        def pre(rate, bias, q, k, v, f, b):    # a group's slices, as given
            def heads(x):
                return x.astype(_F32).reshape(x.shape[:2] + (h, -1))
            qh, kh = l2norm(heads(q)), l2norm(heads(k))
            g = -rate * jax.nn.softplus(heads(f) + bias)
            return (qh * qh.shape[-1] ** -0.5, kh, heads(v), g,
                    jax.nn.sigmoid(b.astype(_F32)))

        o = kda_chunked((q, k, v, f, b), pre, (rate, bias), chunk=chunk,
                        dtype=v.dtype)
        return o.reshape(o.shape[:2] + (-1,))


# ----------------------------------------------------------------- Mamba-2
def _ssd_group(heads, chunk, consts, s, xs):
    """A group of whole chunks of Mamba-2's recurrence from the state ``s``
    (B, H, P, N), in the chunked (SSD) form, all the group's chunks at
    once inside them and a short walk from chunk to chunk for the states.
    ``xs`` are the group's slices of x (B, t, H*P), B and C (B, t, N;
    one group: shared by the heads) and the raw dt (B, t, H); ``consts``
    are dt_bias, A_log and D (H,), float32. Returns (the state after the
    group, y (B, t, H*P) in float32).

    ``delta``, ``log a``, their running sums, the decays and the states
    are float32; the operands of the four products (C B^T, the masked
    decays times it against ``delta x``, a chunk's own state, C against
    the state carried in) are in x's dtype and accumulate in float32. The
    path of any core the kernels do not take, and their oracle."""
    dt_bias, a_log, d_skip = consts
    x, bm, cm, dt = xs
    b, t = x.shape[:2]
    n, cdt = t // chunk, x.dtype
    hi = lax.Precision.HIGHEST          # float32 operands stay float32
    delta = jax.nn.softplus(dt.astype(_F32) + dt_bias)        # (B, t, H)
    la = delta * -jnp.exp(a_log)                              # log a <= 0

    def chunks(v):      # (B, t, ...) -> (B, n, C, ...)
        return v.reshape((b, n, chunk) + v.shape[2:])

    def heads_first(v):     # (B, n, C, H, ...) -> (B, n, H, C, ...)
        return jnp.moveaxis(v, 3, 2)

    xh = heads_first(chunks(x.reshape(b, t, heads, -1)))      # (B, n, H, C, P)
    delta = heads_first(chunks(delta))                        # (B, n, H, C)
    bm, cm = chunks(bm), chunks(cm)                           # (B, n, C, N)
    cs = jnp.cumsum(heads_first(chunks(la)), axis=-1)         # sum_{k<=i} log a
    dx = (delta[..., None] * xh.astype(_F32)).astype(cdt)     # delta x
    with jax.named_scope("mx/ssm/intra"):
        cb = jnp.einsum("bnik,bnjk->bnij", cm, bm, precision=hi,
                        preferred_element_type=_F32)
        seen = jnp.tril(jnp.ones((chunk, chunk), bool))
        # L[i, j] = prod_{j<k<=i} a_k for j <= i: no exponent is positive
        decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                                  -jnp.inf))
        y = jnp.einsum("bnhij,bnhjp->bnhip",
                       (decay * cb[:, :, None]).astype(cdt), dx,
                       precision=hi, preferred_element_type=_F32)
        # a chunk's own state: its tokens decayed to its last one
        to_end = jnp.exp(cs[..., -1:] - cs)
        own = jnp.einsum("bnhjp,bnjk->bnhpk",
                         (dx.astype(_F32) * to_end[..., None]).astype(cdt),
                         bm, precision=hi, preferred_element_type=_F32)
    with jax.named_scope("mx/ssm/scan"):
        whole = jnp.exp(cs[..., -1])[..., None, None]         # (B, n, H, 1, 1)
        entry = []
        for i in range(n):
            entry.append(s)
            s = whole[:, i] * s + own[:, i]
        entry = jnp.stack(entry, 1)                           # (B, n, H, P, N)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "bnik,bnhpk->bnhip", cm, entry.astype(cdt), precision=hi,
            preferred_element_type=_F32)
    y = y + d_skip[:, None, None] * xh.astype(_F32)
    return s, jnp.moveaxis(y, 2, 3).reshape(b, t, -1)


def _ssd_kernel_group(heads, chunk, consts, s, xs):
    """:func:`_ssd_group` where the core's tile is the kernels'
    (``pallas_ssd.eligible``): ``delta``, ``log a`` and its running sums,
    (B, t, H), in XLA under ``mx/ssm/intra``; the chunks, inside each and
    from one to the next, in the kernels of ``ops/pallas_ssd.py`` under
    ``mx/ssm/scan``, with the decays and the state in VMEM."""
    from ..kernels.tier import resolve_interpret
    from . import pallas_ssd
    dt_bias, a_log, d_skip = consts
    x, bm, cm, dt = xs
    b, t = x.shape[:2]
    with jax.named_scope("mx/ssm/intra"):
        delta = jax.nn.softplus(dt.astype(_F32) + dt_bias)    # (B, t, H)
        la = delta * -jnp.exp(a_log)                          # log a <= 0
        # the running sums as one product with the lower triangle of ones
        # (float32 operands kept float32), not a window as long as the
        # chunk over every token
        cs = jnp.einsum("ij,bnjh->bnih", jnp.tril(jnp.ones((chunk, chunk),
                                                           _F32)),
                        la.reshape(b, t // chunk, chunk, heads),
                        precision=lax.Precision.HIGHEST).reshape(b, t, heads)
    with jax.named_scope("mx/ssm/scan"):
        after, y = pallas_ssd.ssd_scan(
            x, bm.astype(x.dtype), cm.astype(x.dtype), delta, cs, d_skip,
            s.reshape(b, -1, s.shape[-1]), chunk, resolve_interpret())
    # y leaves the kernel rounded to x's dtype, as the frame's output is
    return after.reshape(s.shape), y.astype(_F32)


program_gauge("ssm/layers",
              "Mamba-2 cores (_contrib_Mamba2) of the training program "
              "traced last")
program_gauge("ssm/chunks",
              "chunks a sequence is cut into at the op's chunk, the most "
              "of any Mamba-2 core of the training program traced last")
program_gauge("ssm/core_kernel",
              "Mamba-2 cores of the training program traced last whose "
              "chunks went to the Pallas kernels that build the decays and "
              "carry the state in VMEM (ops/pallas_ssd.py)")
program_gauge("ssm/core_plain",
              "Mamba-2 cores of the training program traced last whose "
              "chunks went through plain JAX (the core's tile is none of "
              "the kernels')")
program_gauge("ssm/state_mb",
              "MB of chunk-boundary states (float32, one on entry to each "
              "group of chunks) that the Mamba-2 cores of the training "
              "program traced last keep for their backward pass")


def ssd_chunked(x, b, c, dt, dt_bias, a_log, d, *, num_heads, chunk=256,
                group=8):
    """Mamba-2's core (state-spaces/mamba ``Mamba2``, arXiv:2405.21060; one
    group of B and C). x: (B, T, H*P) and b, c: (B, T, N) after their
    convolution; dt: (B, T, H) as projected; dt_bias, a_log, d: (H,). A
    head ``h`` with ``delta_t = softplus(dt_t + dt_bias_h)`` and ``a_t =
    exp(-delta_t exp(a_log_h))`` carries the state ``S_t = a_t S_{t-1} +
    delta_t x_t b_t^T`` (P, N) from zero and gives ``y_t = S_t c_t + d_h
    x_t``: (B, T, H*P) in x's dtype, before the gated norm.

    Computed in chunks of ``chunk`` tokens, ``group`` chunks at a time, in
    the frame KDA's core runs in (``_groups_core``): a forward scan that
    keeps the float32 state on entry to each group (a ``mirror_stage``
    keeps them and the output), a backward that recomputes a group's
    interior, its (chunk, chunk) decays among it, one group at a time. A
    sequence that is no multiple of the chunk is padded after its end.
    ``delta``, the decays and the states are float32 whatever the inputs'
    dtype; the products' operands are x's dtype.

    A group goes to the Pallas kernels of ``ops/pallas_ssd.py``
    (:func:`_ssd_kernel_group`: the decays and the state in VMEM) where the
    core is one of theirs (``pallas_ssd.eligible``: heads of 64 or 128, a
    state of 128 or 256, a chunk of 128 or 256), and through plain JAX
    (:func:`_ssd_group`) for any other shape; a training program counts
    its cores of either kind in the gauges ``ssm/core_kernel`` and
    ``ssm/core_plain``."""
    # imported here, not above KDA's code: a Pallas kernel's cache key
    # carries the source lines of the frames that called it
    from . import pallas_ssd
    with jax.named_scope("mx/ssm"):
        bsz, t = x.shape[:2]
        heads = int(num_heads)
        chunk = min(int(chunk), -(-t // 8) * 8)
        span = _group_span(t, chunk, int(group))
        state = (bsz, heads, x.shape[2] // heads, b.shape[2])
        program_count("ssm/layers")
        kernel = pallas_ssd.eligible(state[2], state[3], chunk, heads)
        program_count("ssm/core_kernel" if kernel else "ssm/core_plain")
        program_max("ssm/chunks", -(-t // chunk))
        program_count("ssm/state_mb",
                      -(-t // span) * 4 * bsz * heads * state[2] * state[3]
                      / 1e6)
        consts = tuple(v.astype(_F32) for v in (dt_bias, a_log, d))
        run = _ssd_kernel_group if kernel else _ssd_group
        return _groups_core(functools.partial(run, heads, chunk), span,
                            state, x.dtype, consts, (x, b, c, dt))


@register("_contrib_Mamba2")
def mamba2(x, b, c, dt, dt_bias, a_log, d, *, num_heads, chunk=256):
    """:func:`ssd_chunked` as a registered op: Mamba-2's core from the
    convolved x (B, T, H*P), b and c (B, T, N), the projected dt (B, T, H)
    and the per-head dt_bias, a_log and d, in chunks of ``chunk`` tokens."""
    return ssd_chunked(x, b, c, dt, dt_bias, a_log, d, num_heads=num_heads,
                       chunk=chunk)


@register("_contrib_GatedRMSNorm")
def gated_rms_norm(data, gate, gamma, *, eps=1e-5):
    """``RMSNorm(data * silu(gate)) * gamma`` over the last axis (Mamba-2's
    output norm: the gate first, then the norm, one group); the product and
    the statistics in float32 whatever the inputs' dtype."""
    x = data.astype(_F32) * jax.nn.silu(gate.astype(_F32))
    return rms_norm(x, gamma, eps=eps).astype(data.dtype)


# -------------------------------------------------------------------- head
def _head_blocks(n, block):
    block = min(block, n)
    return block, (-n) % block


def _head_rows(x, w, label, block):
    """Every token's ``logsumexp(z) - z[label]``, ``block`` tokens at a
    time, logits in float32."""
    n = x.shape[0]
    block, pad = _head_blocks(n, block)
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[1])
    lb = jnp.pad(label, (0, pad)).reshape(-1, block)

    def rows(a):
        xi, li = a
        z = jnp.matmul(xi, w.T, preferred_element_type=_F32)
        return jax.nn.logsumexp(z, axis=-1) \
            - jnp.take_along_axis(z, li[:, None], axis=-1)[:, 0]

    return lax.map(rows, (xb, lb)).reshape(-1)[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _head_loss(x, w, label, grad_scale, block):
    return _head_rows(x, w, label, block)


def _head_fwd(x, w, label, grad_scale, block):
    return _head_rows(x, w, label, block), (x, w, label)


def _head_bwd(grad_scale, block, res, ct):
    x, w, label = res
    n = x.shape[0]
    block, pad = _head_blocks(n, block)
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[1])
    lb = jnp.pad(label, (0, pad)).reshape(-1, block)
    cb = jnp.pad(ct.astype(_F32) * grad_scale, (0, pad)).reshape(-1, block)

    def rows(dw, a):
        xi, li, ci = a
        z = jnp.matmul(xi, w.T, preferred_element_type=_F32)
        dz = jax.nn.softmax(z, axis=-1) \
            - jax.nn.one_hot(li, w.shape[0], dtype=_F32)
        dz = (dz * ci[:, None]).astype(x.dtype)
        dw = dw + jnp.matmul(dz.T, xi, preferred_element_type=_F32)
        return dw, jnp.matmul(dz, w, preferred_element_type=_F32)

    dw, dx = lax.scan(rows, jnp.zeros(w.shape, _F32), (xb, lb, cb))
    dx = dx.reshape(-1, x.shape[1])[:n]
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_head_loss.defvjp(_head_fwd, _head_bwd)


@register("_contrib_LMHeadLoss")
def lm_head_loss(data, weight, label, *, grad_scale=1.0,
                 normalization="null", block=2048):
    """Every token's cross-entropy of ``label`` under ``softmax(data
    weight^T)``: data (B, T, hidden), weight (vocabulary, hidden), label
    (B, T) of ids; the output is (B, T) of float32 losses and never the B x
    T x vocabulary array (the logits exist ``block`` tokens at a time,
    forward and backward). A token's gradient is ``softmax - onehot`` times
    the cotangent its row receives, times ``grad_scale``, and with
    ``normalization="tokens"`` divided by T. As the graph's output the
    rows receive 1 each (the sum's gradient; with ``Module``'s default
    ``rescale_grad = 1 / B`` the step then follows the mean over all
    tokens); as a value inside the graph they receive what the ops that
    read them give back, such as a looped model's exit weights
    (:func:`loop_exit_loss`), and several calls on one weight add their
    gradients."""
    with jax.named_scope("mx/lm_head"):
        b, t, hid = data.shape
        if normalization == "tokens":
            grad_scale = float(grad_scale) / t
        rows = _head_loss(data.reshape(b * t, hid), weight,
                          label.astype(jnp.int32).reshape(b * t),
                          float(grad_scale), int(block))
        return rows.reshape(b, t)


# -------------------------------------------------------------- exit
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_times(x, scale):
    """``x``, its cotangent times ``scale`` on the way back."""
    return x


_grad_times.defvjp(lambda x, scale: (x, None),
                   lambda scale, _, ct: (ct * scale,))


def exit_distribution(logits):
    """(P, ...) gate logits -> (P, ...) log of the exit distribution:
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for t < P and ``p_P =
    prod_{j<P} (1 - lambda_j)``, ``lambda = sigmoid(logits)``; the last
    pass's gate is not read. Float32."""
    g = logits.astype(_F32)[:-1]
    zero = jnp.zeros((1,) + logits.shape[1:], _F32)
    survive = jnp.concatenate([zero, jnp.cumsum(jax.nn.log_sigmoid(-g), 0)])
    return survive + jnp.concatenate([jax.nn.log_sigmoid(g), zero])


@register("_contrib_ExitGate")
def exit_gate(data, weight, bias):
    """A looped model's exit gate: every token's logit ``w . h + b`` of
    data (B, T, hidden), weight (1, hidden) and bias (1,), in float32
    whatever the data's dtype (a product and a sum, no matrix unit):
    (B, T)."""
    with jax.named_scope("mx/exit"):
        return jnp.sum(data.astype(_F32) * weight.astype(_F32)[0], -1) \
            + bias.astype(_F32)[0]


@register("_contrib_LoopExitLoss", num_outputs=2)
def loop_exit_loss(logits, rows, counters, *, beta=0.1):
    """The loss of a model whose layers run P times, with a head and an
    exit gate after every pass: ``logits`` (P, B, T) the gates' logits,
    ``rows`` (P, B, T) every pass's loss a token. Each token's loss is
    ``sum_t p_t L_t + beta sum_t p_t log p_t`` (the expected loss under
    the exit distribution :func:`exit_distribution` less ``beta`` times
    its entropy), in float32: (B, T). As a loss head its gradient is the
    mean's over the T tokens of a sequence (with ``Module``'s default
    ``rescale_grad = 1 / B`` the step follows the mean over all tokens);
    the passes' rows receive ``p_t / T`` a token and hand it to their
    heads.

    The auxiliary state ``counters`` (4,) is carried on the device: steps
    seen, then a step's running mean of the passes, of the mean exit
    step ``E_p[t]`` (from 1) and of the share that leaves after the last
    pass ``p_P``, each the mean over tokens."""
    with jax.named_scope("mx/exit"):
        p_n, _, t = rows.shape
        log_p = exit_distribution(logits)
        p = jnp.exp(log_p)
        loss = _grad_times(
            jnp.sum(p * (rows.astype(_F32) + float(beta) * log_p), 0), 1.0 / t)
        p = lax.stop_gradient(p)
        steps = counters[:1] + 1
        step_no = jnp.arange(1, p_n + 1, dtype=_F32)[:, None, None]
        seen = jnp.stack([jnp.asarray(p_n, _F32),
                          jnp.mean(jnp.sum(p * step_no, 0)),
                          jnp.mean(p[-1])]).astype(counters.dtype)
        means = counters[1:] + (seen - counters[1:]) / steps
        return loss, jnp.concatenate([steps, means])


# ------------------------------------------------------------ feed-forward
@register("_contrib_SwiGLU")
def _swiglu_op(data, gate_weight, up_weight, down_weight):
    """``(silu(x Wg^T) * (x Wu^T)) Wd^T`` over the last axis; the
    matrices stored (out, in) as FullyConnected's."""
    from ..parallel.moe import swiglu
    # three products a row: two of in x hidden, one of hidden x out
    program_count("dense/flops_fwd", 2 * data.size * (
        gate_weight.shape[0] + up_weight.shape[0]) + 2 * (
            data.size // data.shape[-1]) * down_weight.size)
    return swiglu(data, gate_weight, up_weight, down_weight)


@register("_contrib_MoE", num_outputs=2)
def _moe_op(data, router_weight, router_bias, gate_weight, up_weight,
            down_weight, counters, *, experts_held, top_k, scale=1.0):
    """:func:`mxnet_tpu.parallel.moe.expert_layer` over (B, T, d) as a
    registered op. The auxiliary state ``counters`` (4,) is carried on the
    device and never read by the host in a step: steps seen, then a step's
    running mean of the assignments this rank held, of the largest number
    of tokens one held expert received, and of the sorted assignments the
    layer's blocks passed over (float32 like every auxiliary state: a mean
    stays as exact after a million steps as after one, where a sum would
    pass 2**24 in two thousand)."""
    from ..parallel import moe
    b, t, d = data.shape
    lo, hi = (int(v) for v in experts_held)
    y, counts = moe.expert_layer(
        data.reshape(b * t, d), router_weight, router_bias, gate_weight,
        up_weight, down_weight, experts_held=(lo, hi), top_k=int(top_k),
        scale=float(scale))
    steps = counters[:1] + 1
    block = moe.block_rows(b * t, int(top_k), hi - lo,
                           router_weight.shape[0], gate_weight.shape[1])
    seen = jnp.stack([jnp.sum(counts), jnp.max(counts),
                      moe.trips(counts, block) * block]).astype(
                          counters.dtype)
    means = counters[1:] + (seen - counters[1:]) / steps
    return y.reshape(b, t, d), jnp.concatenate([steps, means])


set_op_meta("RMSNorm", f32_inputs=(1,))
set_op_meta("_contrib_KDA", f32_inputs=(5, 6))
set_op_meta("_contrib_Mamba2", f32_inputs=(4, 5, 6))
set_op_meta("_contrib_GatedRMSNorm", f32_inputs=(2,))
set_op_meta("_contrib_LMHeadLoss", index_inputs=(2,))
set_op_meta("_contrib_ExitGate", f32_inputs=(1, 2))
set_op_meta("_contrib_LoopExitLoss", aux_inputs=(2,), aux_outputs=(1,),
            num_visible_outputs=1,
            counters=((2, (
                ("loop/passes", "passes a step of a looped model's layers"),
                ("exit/mean_step",
                 "the pass a token leaves after, expected under the exit "
                 "distribution (from 1), mean over tokens"),
                ("exit/last_share",
                 "the exit distribution's share on the last pass, mean "
                 "over tokens"))),),
            shape_hook=lambda ins, p: list(ins[:2]) + [ins[2] or (4,)])
set_op_meta("_contrib_MoE", aux_inputs=(6,), aux_outputs=(1,),
            num_visible_outputs=1, f32_inputs=(1, 2),
            counters=((6, (
                ("moe/assignments_held",
                 "token-to-expert assignments a step that fell to the "
                 "experts this rank holds, mean over the expert layers"),
                ("moe/max_expert_tokens",
                 "tokens a step that the busiest held expert received, "
                 "mean over the expert layers"),
                ("moe/rows_visited",
                 "sorted assignments a step that the expert layer's "
                 "blocks passed over (trips x block), mean over the "
                 "expert layers; over moe/assignments_held: 1 is no "
                 "row passed over in vain"))),),
            shape_hook=lambda ins, p: list(ins[:6]) + [ins[6] or (4,)])
