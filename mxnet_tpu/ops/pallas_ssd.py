"""Pallas TPU kernels for Mamba-2's core (``ops/lm_ops.py::_ssd_group``): a
group's chunks, the work inside each and the walk from chunk to chunk, as
one kernel forward and the kernels of its backward.

A program holds a block of heads. Its grid's last axis, sequential, walks
the group's chunks while the state of those heads, (heads * P, N) float32,
stays in a VMEM scratch. At each chunk it reads x (C, heads * P) and the
shared B and C (C, N) as blocks of their (B, T, .) arrays, ``delta`` and the
running sum ``cs`` of ``log a`` as (C, H) columns, ``cs`` again as (heads, C)
rows; makes ``C B^T`` once for its heads, and for each head the masked decay
``exp(cs_i - cs_j)``, j <= i, in VMEM, never in HBM, by blocks of 128 x 128
on and below the diagonal (the mask on the diagonal's alone). Heads go in
groups of 128 lanes (two heads of 64, or one of 128): the group's heads meet
their decays in one product, each head's copy of ``delta x`` zero on the
other heads' lanes, so no value is ever cut below a register's width.

The forward (``ssd_scan_fwd``) writes y in x's dtype a chunk at a time and
the state after the group's last chunk. It keeps nothing: the backward
walks the group's chunks forward once for the state on entry to each
(``ssd_scan_states``: the state's update alone, no decay, no y), then last
to first (``ssd_scan_bwd``) with the state's cotangent in the scratch,
rebuilds a chunk's decays in VMEM and writes the gradients of x, of B and C
(a head block's share: the blocks' are summed after the kernel), of
``delta`` and ``cs`` (columns, a head block's own) and of the skip ``D``.
Where a forward's outputs go unused, as in the group the frame recomputes
for its backward, XLA drops that forward.

The arithmetic is the plain path's: ``delta``, ``cs``, the decays and the
states float32, no exponent ever positive; every product takes its operands
in x's dtype (``Precision.HIGHEST`` where that is float32) and accumulates
in float32. The sums a head's ``cs`` and ``delta`` take their gradient from
come from values the backward has anyway: a decay's share of ``dcs_i`` is
``sum_p dy_ip y_ip - sum_p dx_ip ddx_ip`` over the head's lanes (the rows
of ``dM * M`` less its columns), ``ddelta_i`` is ``sum_p ddx_ip x_ip``. On
the CPU backend the kernels run interpreted (tests), on a TPU through
Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_LANES = 128            # a register's lanes: one group of 128 // P heads
_SUBLANES = 8
_MASKED = -1e30         # an exponent whose exp() is 0
_VMEM = 12 << 20        # bytes of blocks, scratch and (C, C) values a
                        # program may hold: the kernels stay within the 16
                        # MiB of VMEM a kernel scopes by default


def eligible(p, n, chunk, heads):
    """Whether a core of this shape is the kernels': heads of 64 or 128
    (whole 128-lane registers in groups of heads), a state of 128 or 256,
    a chunk of 128 or 256 tokens, whole groups of heads."""
    return (p in (64, 128) and n % _LANES == 0 and n <= 2 * _LANES
            and chunk % _LANES == 0 and chunk <= 2 * _LANES
            and heads % (_LANES // p) == 0)


def _vmem(hb, chunk, p, n, h, itemsize, backward):
    """Bytes a program holds: every block twice, the scratch, a few
    (chunk, chunk) values."""
    lanes = hb * p
    x = chunk * lanes * itemsize
    state = lanes * n * 4
    shared = 2 * chunk * n * itemsize + 2 * chunk * max(h, _LANES) * 4 \
        + hb * chunk * 4 + lanes * 4
    if backward:    # x, dy, dx; entry, ds_end, ds0; dB, dC; two columns
        blocks = 2 * x + chunk * lanes * 4 + 3 * state \
            + 2 * chunk * n * 4 + 2 * chunk * _LANES * 4
    else:           # x, y; s0, s_end (the states' walk: x; s0, entry)
        blocks = 2 * x + 2 * state
    return 2 * (blocks + shared) + state + 16 * chunk * chunk * 4


def _heads_a_program(chunk, p, n, h, itemsize, backward):
    """The most heads (a divisor of ``h``, whole groups of 128 lanes, a
    multiple of 8 or all) whose program fits ``_VMEM``; the fewest
    otherwise."""
    fits = [hb for hb in range(h, 0, -1)
            if h % hb == 0 and hb * p % _LANES == 0
            and (hb % _SUBLANES == 0 or hb == h)]
    return next((hb for hb in fits
                 if _vmem(hb, chunk, p, n, h, itemsize, backward) <= _VMEM),
                fits[-1])


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _mm(a, b, dims, cdt):
    """A product with operands in ``cdt``, accumulated in float32."""
    hi = lax.Precision.HIGHEST if cdt == _F32 else lax.Precision.DEFAULT
    return lax.dot_general(a.astype(cdt), b.astype(cdt), dims, precision=hi,
                           preferred_element_type=_F32)


def _join(parts, axis=0):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)


def _tile(r):
    """Rows (or columns) of block ``r`` of a chunk."""
    return slice(r * _LANES, (r + 1) * _LANES)


def _column(blk, h):
    """Column ``h`` of a (C, H) value, as (C, 1)."""
    return jnp.sum(jnp.where(_iota(blk.shape, 1) == h, blk, 0.0), 1,
                   keepdims=True)


def _cb(cm, bm, cdt):
    """``C B^T`` of a chunk by blocks of 128 rows: block r is (128, 128 (r
    + 1)), what lies on and left of the diagonal (the rest meets only
    masked decays)."""
    return [_mm(cm[_tile(r)], bm[:(r + 1) * _LANES], _NT, cdt)
            for r in range(cm.shape[0] // _LANES)]


class _Group:
    """What a chunk's group of heads (128 lanes of x) needs of the per-head
    vectors: ``delta`` and ``cs`` as columns, spread over the heads' lanes
    (``delta``, ``cs``), and each head's ``cs`` as a column and a row."""

    def __init__(self, dl_ref, cs_ref, csr_ref, first, grp, p):
        c = cs_ref.shape[0]
        self.p, self.g = p, _LANES // p
        self.heads = [grp * self.g + k for k in range(self.g)]
        dl, cs = dl_ref[...], cs_ref[...]
        self.delta_cols = [_column(dl, first + h) for h in self.heads]
        self.cs_cols = [_column(cs, first + h) for h in self.heads]
        if csr_ref is not None:     # the decays' columns
            self.cs_rows = [[csr_ref[h:h + 1, _tile(t)]
                             for t in range(c // _LANES)] for h in self.heads]
        self.delta = self.spread(self.delta_cols, c)
        self.cs = self.spread(self.cs_cols, c)
        self.causal = _iota((_LANES, _LANES), 0) >= _iota((_LANES, _LANES), 1)

    def spread(self, cols, c):
        """(C, 1) a head as (C, 128), each over its own lanes."""
        out = jnp.broadcast_to(cols[0], (c, _LANES))
        for k, col in enumerate(cols[1:], 1):
            out = jnp.where(_iota(out.shape, 1) // self.p == k, col, out)
        return out

    def own(self, k, z):
        """``z`` (., 128) on head k's lanes, 0 on the others'."""
        return z if self.g == 1 else jnp.where(
            _iota(z.shape, 1) // self.p == k, z, 0.0)

    def apart(self, z):
        """``z`` (C, 128) as one copy a head, 0 on the other heads' lanes:
        side by side, the heads' products are one product whose sum keeps
        each head's lanes its own."""
        return [self.own(k, z) for k in range(self.g)]

    def sums(self, z):
        """Each head's sum of ``z`` (C, 128) over its lanes, (C, 1)."""
        return [jnp.sum(self.own(k, z), 1, keepdims=True)
                for k in range(self.g)]

    def decay(self, k, r):
        """Head k's ``exp(cs_i - cs_j)`` for the rows i of block r and the
        columns j up to the block's last, 0 for j > i: (128, 128 (r + 1)),
        float32; no exponent positive, the mask on the diagonal block
        alone."""
        col, row = self.cs_cols[k][_tile(r)], self.cs_rows[k]
        parts = [jnp.exp(col - row[t]) for t in range(r)]
        return _join(parts + [jnp.exp(jnp.where(
            self.causal, col - row[r], _MASKED))], 1)

    def by_rows(self, vals, rows):
        """A value a head as (rows, 1): over the state's rows of each."""
        out = jnp.broadcast_to(vals[0], (rows, 1))
        for k, v in enumerate(vals[1:], 1):
            out = jnp.where(_iota((rows, 1), 0) // self.p == k, v, out)
        return out


def _carry(hd, x, dx, bm, s, cdt):
    """The state after a chunk from ``s`` (the group's 128 rows of it) on
    entry: each head's decayed over the chunk, plus the chunk's ``delta x``
    decayed to its end against B."""
    c = x.shape[0]
    end = hd.cs[c - 1:c, :]
    w = dx.astype(_F32) * jnp.exp(end - hd.cs)              # to the end
    ends = hd.by_rows([jnp.exp(col[c - 1:c, :]) for col in hd.cs_cols],
                      _LANES)
    return ends * s + _mm(w, bm, _TN, cdt)


def _fwd_kernel(x_ref, b_ref, c_ref, dl_ref, cs_ref, csr_ref, d_ref, s0_ref,
                y_ref, send_ref, s_scr, *, p):
    """A chunk of a block of heads: y, and the state carried to the next
    in ``s_scr``."""
    cdt = x_ref.dtype
    c, lanes = x_ref.shape
    first = pl.program_id(1) * (lanes // p)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        s_scr[...] = s0_ref[...]

    bm, cm = b_ref[...], c_ref[...]
    cb = _cb(cm, bm, cdt)
    for grp in range(lanes // _LANES):
        at = slice(grp * _LANES, (grp + 1) * _LANES)
        hd = _Group(dl_ref, cs_ref, csr_ref, first, grp, p)
        x = x_ref[:, at].astype(_F32)
        dx = (hd.delta * x).astype(cdt)                     # delta x
        dxs = hd.apart(dx)
        ys = [_mm(_join([hd.decay(k, r) * cb_r for k in range(hd.g)], 1),
                  _join([v[:(r + 1) * _LANES] for v in dxs]), _NN, cdt)
              for r, cb_r in enumerate(cb)]
        s = s_scr[at, :]
        # C against the state carried in, decayed from the chunk's start
        y_ref[:, at] = (_join(ys) + jnp.exp(hd.cs) * _mm(cm, s, _NT, cdt)
                        + d_ref[:, at] * x).astype(y_ref.dtype)
        s_scr[at, :] = _carry(hd, x, dx, bm, s, cdt)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        send_ref[...] = s_scr[...]


def _states_kernel(x_ref, b_ref, dl_ref, cs_ref, s0_ref, entry_ref, s_scr,
                   *, p):
    """The state on entry to a chunk of a block of heads, and the state
    carried to the next in ``s_scr``: the forward's walk without its
    outputs, what the backward reads."""
    cdt = x_ref.dtype
    lanes = x_ref.shape[1]
    first = pl.program_id(1) * (lanes // p)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = s0_ref[...]

    entry_ref[...] = s_scr[...]
    bm = b_ref[...]
    for grp in range(lanes // _LANES):
        at = slice(grp * _LANES, (grp + 1) * _LANES)
        hd = _Group(dl_ref, cs_ref, None, first, grp, p)
        x = x_ref[:, at].astype(_F32)
        s_scr[at, :] = _carry(hd, x, (hd.delta * x).astype(cdt), bm,
                              s_scr[at, :], cdt)


def _bwd_kernel(x_ref, b_ref, c_ref, dl_ref, cs_ref, csr_ref, d_ref,
                entry_ref, dy_ref, dsend_ref, dx_ref, db_ref, dc_ref,
                ddl_ref, dcs_ref, dd_ref, ds0_ref, ds_scr, *, p):
    """A chunk of a block of heads, the chunks met last to first: the
    forward's values rebuilt from the state on entry, the state's
    cotangent carried in ``ds_scr``."""
    cdt = x_ref.dtype
    c, lanes = x_ref.shape
    hb = lanes // p
    first = pl.program_id(1) * hb
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        ds_scr[...] = dsend_ref[...]
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bm, cm = b_ref[...], c_ref[...]
    cb = _cb(cm, bm, cdt)
    dcb = [jnp.zeros(x.shape, _F32) for x in cb]        # C B^T's cotangent
    db = dc = jnp.zeros(bm.shape, _F32)
    ddl = dcs = jnp.zeros((c, hb), _F32)
    slot = _iota((c, hb), 1)
    last = _iota((c, 1), 0) == c - 1
    for grp in range(lanes // _LANES):
        at = slice(grp * _LANES, (grp + 1) * _LANES)
        hd = _Group(dl_ref, cs_ref, csr_ref, first, grp, p)
        x, dy = x_ref[:, at].astype(_F32), dy_ref[:, at].astype(_F32)
        dx = (hd.delta * x).astype(cdt)
        s, ds = entry_ref[at, :], ds_scr[at, :]
        e = jnp.exp(hd.cs)
        end = hd.cs[c - 1:c, :]
        to_end = jnp.exp(end - hd.cs)
        dq = e * dy
        # dcs's sum a row: the state's term, then each decay's rows less its
        # columns, from the operands the products took (rounded alike, the
        # two sides cancel as the exact ones do)
        z = dq * _mm(cm, s, _NT, cdt)
        dy_c = dy.astype(cdt).astype(_F32)
        zs = [z[_tile(r)] for r in range(len(cb))]
        ddxs = [jnp.zeros((_LANES, _LANES), _F32) for _ in cb]
        dxs, dys = hd.apart(dx), hd.apart(dy_c)
        for r, cb_r in enumerate(cb):
            width = (r + 1) * _LANES
            decays = [hd.decay(k, r) for k in range(hd.g)]
            ms = [d_k * cb_r for d_k in decays]
            dy_r = _join([v[_tile(r)] for v in dys])        # the heads' rows
            zs[r] = zs[r] + dy_c[_tile(r)] * _mm(
                _join(ms, 1), _join([v[:width] for v in dxs]), _NN, cdt)
            mt_dy = _mm(_join(ms), dy_r, _TN, cdt)          # M^T dy
            for t in range(r + 1):
                ddxs[t] = ddxs[t] + mt_dy[_tile(t)]
            dm = _mm(dy_r, dx[:width], _NT, cdt)            # dM, a head's
            for k, d_k in enumerate(decays):                # rows after
                dcb[r] = dcb[r] + dm[_tile(k)] * d_k        # another's
        ddx = _join(ddxs)
        w = (dx.astype(_F32) * to_end).astype(cdt)
        dw = _mm(bm, ds, _NT, cdt)                          # B dS^T
        owned = dw * w.astype(_F32)                         # the decay to
        z = _join(zs) - dx.astype(_F32) * ddx - owned       # the end's
        ddx = ddx + dw * to_end
        dc = dc + _mm(dq, s, _NN, cdt)
        db = db + _mm(w, ds, _NN, cdt)
        ends = [jnp.exp(col[c - 1:c, :]) for col in hd.cs_cols]
        ds_scr[at, :] = _mm(dq, cm, _TN, cdt) + hd.by_rows(ends, _LANES) * ds
        dx_ref[:, at] = (hd.delta * ddx + d_ref[:, at] * dy).astype(
            dx_ref.dtype)
        dd_ref[:, at] += jnp.sum(dy * x, 0, keepdims=True)
        # the per-head vectors: delta's, then cs's, with cs_end's share
        # (the decay to the end, the state's decay) on the last row
        held = ds * s
        for k, (d_delta, d_cs, d_own) in enumerate(zip(
                hd.sums(ddx * x), hd.sums(z), hd.sums(owned))):
            h = hd.heads[k]
            rows = _iota(held.shape, 0) // p == k
            d_end = jnp.sum(d_own, 0, keepdims=True) + ends[k] * jnp.sum(
                jnp.sum(jnp.where(rows, held, 0.0), 1, keepdims=True), 0,
                keepdims=True)
            d_cs = d_cs + jnp.where(last, d_end, 0.0)
            ddl = jnp.where(slot == h, d_delta, ddl)
            dcs = jnp.where(slot == h, d_cs, dcs)
    # C B^T's cotangent, summed over the block's heads, by blocks of rows
    dbs = [db[_tile(r)] for r in range(len(cb))]
    dcs_rows = []
    for r, dcb_r in enumerate(dcb):
        width = (r + 1) * _LANES
        dcs_rows.append(dc[_tile(r)] + _mm(dcb_r, bm[:width], _NN, cdt))
        to_b = _mm(dcb_r, cm[_tile(r)], _TN, cdt)
        for t in range(r + 1):
            dbs[t] = dbs[t] + to_b[_tile(t)]
    db_ref[...] = _join(dbs)
    dc_ref[...] = _join(dcs_rows)
    ddl_ref[...] = ddl
    dcs_ref[...] = dcs

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        ds0_ref[...] = ds_scr[...]


def _specs(n, hb, chunk, p, ns, h, reverse):
    """Block specs over the grid (sequence, block of heads, chunk): x's
    (and y's), B's and C's, the (C, H) columns', the (heads, C) rows',
    the per-lane skip's, a state's, a state's a chunk; the chunks met
    first to last or, with ``reverse``, last to first."""
    def at(i):
        return n - 1 - i if reverse else i
    return (pl.BlockSpec((None, chunk, hb * p), lambda b, j, i: (b, at(i), j)),
            pl.BlockSpec((None, chunk, ns), lambda b, j, i: (b, at(i), 0)),
            pl.BlockSpec((None, chunk, h), lambda b, j, i: (b, at(i), 0)),
            pl.BlockSpec((None, hb, chunk), lambda b, j, i: (b, j, at(i))),
            pl.BlockSpec((1, hb * p), lambda b, j, i: (0, j)),
            pl.BlockSpec((None, hb * p, ns), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, None, hb * p, ns),
                         lambda b, j, i: (b, at(i), j, 0)))


def _call(kernel, name, grid, hb, p, ns, interpret, **specs):
    return pl.pallas_call(
        functools.partial(kernel, p=p), grid=grid,
        scratch_shapes=[pltpu.VMEM((hb * p, ns), _F32)],
        # the state stays in VMEM while a group's chunks pass: the last
        # grid axis is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name, **specs)


def _vectors(cs, d, p):
    """``cs`` as rows (B, H, T) and ``d`` spread over its heads' lanes."""
    return jnp.swapaxes(cs, 1, 2), jnp.repeat(d, p)[None]


@functools.partial(jax.jit, static_argnums=(7, 8))
def scan_fwd(x, bm, cm, delta, cs, d, s0, chunk, interpret):
    """``(s_end, y)`` of a group from the state ``s0`` (B, H * P, N)."""
    b, t, lanes = x.shape
    h, ns = delta.shape[2], bm.shape[2]
    p, n = lanes // h, t // chunk
    args = (chunk, p, ns, h, x.dtype.itemsize, False)
    hb = _heads_a_program(*args)
    xs, bs, cols, rows, skip, state, _ = _specs(n, hb, chunk, p, ns, h,
                                                False)
    y, s_end = _call(
        _fwd_kernel, "ssd_scan_fwd", (b, h // hb, n), hb, p, ns, interpret,
        in_specs=[xs, bs, bs, cols, cols, rows, skip, state],
        out_specs=[xs, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(s0.shape, _F32)],
    )(x, bm, cm, delta, cs, *_vectors(cs, d, p), s0)
    return s_end, y


@functools.partial(jax.jit, static_argnums=(5, 6))
def scan_states(x, bm, delta, cs, s0, chunk, interpret):
    """The state on entry to every chunk of a group, (B, n, H * P, N),
    from the state ``s0`` on entry to the group."""
    b, t, lanes = x.shape
    h, ns = delta.shape[2], bm.shape[2]
    p, n = lanes // h, t // chunk
    args = (chunk, p, ns, h, x.dtype.itemsize, False)
    hb = _heads_a_program(*args)
    xs, bs, cols, _, _, state, states = _specs(n, hb, chunk, p, ns, h,
                                               False)
    return _call(
        _states_kernel, "ssd_scan_states", (b, h // hb, n), hb, p, ns,
        interpret,
        in_specs=[xs, bs, cols, cols, state], out_specs=states,
        out_shape=jax.ShapeDtypeStruct((b, n) + s0.shape[1:], _F32),
    )(x, bm, delta, cs, s0)


@functools.partial(jax.jit, static_argnums=(9, 10))
def scan_bwd(x, bm, cm, delta, cs, d, entry, dy, ds_end, chunk, interpret):
    """The gradients of x, B, C, delta, cs, d and of the state on entry
    from the states on entry to the chunks and the cotangents of y and of
    the last state."""
    b, t, lanes = x.shape
    h, ns = delta.shape[2], bm.shape[2]
    p, n = lanes // h, t // chunk
    args = (chunk, p, ns, h, x.dtype.itemsize, True)
    hb = _heads_a_program(*args)
    blocks = h // hb
    xs, bs, cols, rows, skip, state, states = _specs(n, hb, chunk, p, ns, h,
                                                     True)

    def share(width):   # a head block's (C, width) of a chunk
        return pl.BlockSpec((None, None, chunk, width),
                            lambda b, j, i: (j, b, n - 1 - i, 0))
    dx, db, dc, ddl, dcs, dd, ds0 = _call(
        _bwd_kernel, "ssd_scan_bwd", (b, blocks, n), hb, p, ns,
        interpret,
        in_specs=[xs, bs, bs, cols, cols, rows, skip, states,
                  xs, state],
        out_specs=[xs, share(ns), share(ns), share(hb), share(hb),
                   pl.BlockSpec((None, 1, hb * p), lambda b, j, i: (b, 0, j)),
                   state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)]
        + [jax.ShapeDtypeStruct((blocks, b, t, ns), _F32)] * 2
        + [jax.ShapeDtypeStruct((blocks, b, t, hb), _F32)] * 2
        + [jax.ShapeDtypeStruct((b, 1, lanes), _F32),
           jax.ShapeDtypeStruct(ds_end.shape, _F32)],
    )(x, bm, cm, delta, cs, *_vectors(cs, d, p), entry, dy, ds_end)

    def heads(v):       # (blocks, B, T, hb) -> (B, T, H)
        return jnp.moveaxis(v, 0, 2).reshape(b, t, h)
    return (dx, jnp.sum(db, 0).astype(bm.dtype),
            jnp.sum(dc, 0).astype(cm.dtype), heads(ddl), heads(dcs),
            jnp.sum(dd, (0, 1)).reshape(h, p).sum(1), ds0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def ssd_scan(x, bm, cm, delta, cs, d, s0, chunk, interpret):
    """A group of whole chunks of Mamba-2's recurrence as Pallas kernels.
    x (B, T, H * P) and bm, cm (B, T, N) in the products' dtype; ``delta``
    and ``cs`` (B, T, H), float32, ``cs`` the running sum of ``log a``
    inside each chunk of ``chunk`` tokens; ``d`` (H,) the skip; ``s0`` (B,
    H * P, N) the state on entry, float32. Returns ``(s_end, y)``, y (B, T,
    H * P) in x's dtype. Nothing but the inputs is kept for the backward
    pass: it walks the chunks once forward for the states on entry to each
    (``ssd_scan_states``: no decay, no y), then last to first
    (``ssd_scan_bwd``)."""
    return scan_fwd(x, bm, cm, delta, cs, d, s0, chunk, interpret)


def _fwd_rule(x, bm, cm, delta, cs, d, s0, chunk, interpret):
    return (scan_fwd(x, bm, cm, delta, cs, d, s0, chunk, interpret),
            (x, bm, cm, delta, cs, d, s0))


def _bwd_rule(chunk, interpret, res, cts):
    x, bm, cm, delta, cs, d, s0 = res
    ds_end, dy = cts
    # named here: a backward function is traced outside the scope its
    # forward ran under
    with jax.named_scope("mx/ssm/scan"):
        entry = scan_states(x, bm, delta, cs, s0, chunk, interpret)
        return scan_bwd(x, bm, cm, delta, cs, d, entry, dy, ds_end, chunk,
                        interpret)


ssd_scan.defvjp(_fwd_rule, _bwd_rule)
