"""Pallas TPU kernels for the KDA core (``ops/lm_ops.py::_kda_group``): the
work inside chunks (scope ``mx/kda/intra``) and the scan from chunk to
chunk (``mx/kda/scan``), each as one kernel forward and one backward.

**Inside chunks** a program takes the tiles of up to eight heads of one
chunk. A tile is one head's chunk: q, k, g of (C, d_k), v of (C, d_v) and
beta of (C,), read straight from the (B, T, H, d) arrays as blocks of their
(B, T, H*d) view, and held in VMEM for everything the plain path spreads
over dozens of fusions: the cumulative log decay, the exact ``sub`` x
``sub`` x d_k blocks of decays (made once, shared by the k.k and the q.k
scores), the earlier sub-blocks' scores as MXU products, the solve ``(I +
A)^-1 [beta V, beta K decay]`` by blocks. The forward writes what the scan
consumes, in the (N, B, H, C, .) order it takes. The backward takes the
same tiles and the six cotangents, recomputes the tile's forward in VMEM
(the decay blocks kept there, never re-read from HBM) and writes the
gradients of q, k, v, g and beta.

**From chunk to chunk** (:func:`kda_scan`) a program carries the state of a
block of heads, (heads, d_k, d_v), in a VMEM scratch while the grid's last
axis, sequential, walks the group's chunks: it reads the six values as the
blocks the first kernel wrote (no transpose, no copy between the two),
writes o a chunk at a time and the state once, after the last chunk. Under
``jax.vjp`` the forward also writes the state on entry to every chunk; the
backward walks the chunks last to first with the state's cotangent in the
scratch, recomputes ``u`` and writes the six cotangents in the order the
first kernel's backward reads them.

The arithmetic is the plain path's: float32 throughout, every product at
``Precision.HIGHEST`` (Mosaic's ``contract_precision<fp32>``), no exponent
ever positive. On the CPU backend the kernels run interpreted (tests), on a
TPU through Mosaic.
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
_SUBLANES = 8           # rows of a float32 register
_BLOCK = 8 * 64 * 128   # elements of one array that a program takes at most
_WIDEST, _LONGEST = 256, 128    # head and chunk up to which a tile's values,
                                # the blocks and the decays fit 16 MiB of VMEM
_MASKED = -1e30         # an exponent whose exp() is 0
_SCAN_VMEM = 12 << 20   # bytes of blocks a program of the scan may hold


def _fits(d_k, d_v, chunk):
    """Whole 128-lane registers across a head, and no wider or longer than
    what Mosaic has compiled for a v5e (tests/test_tpu_aot_compile.py
    holds the corners)."""
    return (d_k % 128 == 0 and d_v % 128 == 0 and max(d_k, d_v) <= _WIDEST
            and chunk <= _LONGEST)


def eligible(d_k, d_v, chunk, sub):
    """Whether a tile of this shape is one the kernels of the work inside
    chunks take: :func:`_fits`, in sub-blocks of whole registers."""
    return (_fits(d_k, d_v, chunk) and sub % _SUBLANES == 0
            and chunk % sub == 0)


def scan_eligible(d_k, d_v, chunk):
    """Whether the scan over a group's chunks is the kernels':
    :func:`_fits`, in chunks of whole 8-row registers."""
    return _fits(d_k, d_v, chunk) and chunk % _SUBLANES == 0


def _mm(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _join(parts, axis=0):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)


def _put_column(mat, col, a, b, j):
    """``mat`` with ``col`` (b - a rows, one lane wide) in the rows a..b
    of column j."""
    mid = mat[a:b]
    mid = jnp.where(_iota(mid.shape, 1) == j, col, mid)
    return _join([x for x in (mat[:a], mid, mat[b:]) if x.shape[0]])


def _add_rows(mat, x, a, b):
    """``mat`` with ``x`` added to its rows a..b."""
    return _join([y for y in (mat[:a], mat[a:b] + x, mat[b:]) if y.shape[0]])


def _put_row(mat, row, j):
    return jnp.where(_iota(mat.shape, 0) == j, row, mat)


def _rows(x, c, lo, up, pack):
    """The rows lo..up of every packed head's chunk, a head after a
    head."""
    return _join([x[p * c + lo:p * c + up] for p in range(pack)])


def _spread(x, c, row, n, pack):
    """Row ``row`` of every packed head's chunk over ``n`` rows each."""
    return _join([jnp.broadcast_to(x[p * c + row:p * c + row + 1],
                                   (n, x.shape[1])) for p in range(pack)])


def _by_head(parts, sub, pack):
    """Sub-blocks whose rows run (head, token) as rows (head, sub-block,
    token): the order of the packed tile."""
    return _join([x[p * sub:(p + 1) * sub]
                  for p in range(pack) for x in parts])


def _masks(c, sub, pack):
    """0/1 matrices over the rows of ``pack`` heads' chunks, one head's
    after another's: nothing passes from a head to another."""
    n = pack * c
    r, s = _iota((n, n), 0), _iota((n, n), 1)
    same = r // c == s // c
    return {"lower": (same & (r >= s)).astype(_F32),
            "upper": (same & (r <= s)).astype(_F32),
            "strict": (same & (r > s)).astype(_F32),
            "own": (r // sub == s // sub).astype(_F32),
            "eye": (r == s).astype(_F32)}


def _exact_decay(gi, s):
    """``exp(G_t - G_s)`` of one sub-block for the rows t of the registers
    that hold a t >= s (0 where t < s): (their first row, the block)."""
    r0 = s // _SUBLANES * _SUBLANES
    low = gi[r0:]
    diff = low - gi[s:s + 1]
    return r0, jnp.exp(jnp.where(_iota(low.shape, 0) + r0 >= s, diff,
                                 _MASKED))


def _nilpotent_inverse(d, order, eye):
    """``(I + d)^-1`` where ``d^order = 0``, through the series ``(I -
    d)(I + d^2)(I + d^4)...``, exact once the powers reach ``order``."""
    inv, p, power = eye - d, d, 1
    while 2 * power < order:
        p, power = _mm(p, p), 2 * power
        inv = _mm(inv, eye + p)
    return inv


def _block_inverse(d, sub, mk):
    """:func:`_nilpotent_inverse` of a ``d`` that is 0 outside its
    diagonal blocks of ``sub``: the same products block by block, with the
    blocks side by side as the rows that stream through the MXU (``sub`` of
    them, not all of ``d``'s) against the block-diagonal form as its
    weights."""
    n = d.shape[0] // sub

    def beside(x):      # the diagonal blocks, (sub, n * sub)
        return sum(x[i * sub:(i + 1) * sub] for i in range(n))

    def diagonal(x):    # back on the diagonal
        return _join([x] * n) * mk["own"]

    inv, p, p_diag, power = beside(mk["eye"] - d), beside(d), d, 1
    while 2 * power < sub:
        p, power = _mm(p, p_diag), 2 * power
        p_diag = diagonal(p)
        inv = _mm(inv, mk["eye"] + p_diag)
    return diagonal(inv)


def _tile(q, k, v, g, beta, c, sub, mk, with_q, keep=None):
    """The forward of ``pack`` heads' tiles, on values, their rows one
    head's chunk after another's: q, k, g (pack * C, d_k), v (pack * C,
    d_v), beta (pack * C, 1); ``mk`` from :func:`_masks`. Packed, two
    chunks of 64 fill the 128 x 128 MXU tile that one would leave three
    quarters empty, and every product below serves both. ``with_q`` adds
    the q.k scores ``m`` (the forward kernel wants them, the backward does
    not); ``keep(head, column, first row, block)`` is handed every exact
    block of decays as it is made."""
    rows, dk = k.shape
    pack, n, ps = rows // c, c // sub, rows // c * sub
    gc = _mm(mk["lower"], g)                               # G_t, <= 0
    decay = jnp.exp(gc)
    tok = _join([_iota((c, dk), 0)] * pack)
    blocks, kk, m = [], [], []
    for i in range(n):
        lo, up = i * sub, (i + 1) * sub
        gi, ki = _rows(gc, c, lo, up, pack), _rows(k, c, lo, up, pack)
        qi = _rows(q, c, lo, up, pack) if with_q else None
        blk = {}
        kk_i = m_i = jnp.zeros((ps, rows), _F32)
        if i:
            # earlier sub-blocks through the decay up to this one's first
            # token: both factors at most 1
            el = jnp.exp(gi - _spread(gc, c, lo - 1, sub, pack))
            er = jnp.exp(jnp.where(
                tok < lo, _spread(gc, c, lo - 1, c, pack) - gc, _MASKED))
            right = k * er
            left = ki * el
            mine = (_iota((ps, rows), 0) // sub
                    == _iota((ps, rows), 1) // c).astype(_F32)
            if with_q:
                off = _mm(jnp.concatenate([left, qi * el], 0), right, _NT)
                kk_i, m_i = off[:ps] * mine, off[ps:] * mine
            else:
                kk_i = _mm(left, right, _NT) * mine
            blk.update(el=el, er=er, right=right, left=left)
        # its own sub-block exactly, channel by channel, a column a pass
        for p in range(pack):
            a, b = p * sub, (p + 1) * sub
            for s in range(sub):
                r0, e = _exact_decay(gi[a:b], s)
                if keep is not None:
                    keep(p, lo + s, r0, e)
                col, e = p * c + lo + s, e * ki[a + s:a + s + 1]
                kk_i = _put_column(kk_i, jnp.sum(
                    ki[a + r0:b] * e, 1, keepdims=True), a + r0, b, col)
                if with_q:
                    m_i = _put_column(m_i, jnp.sum(
                        qi[a + r0:b] * e, 1, keepdims=True), a + r0, b, col)
        blocks.append(blk)
        kk.append(kk_i)
        m.append(m_i)
    kk = _by_head(kk, sub, pack) * mk["strict"]
    a = kk * beta
    inv = _block_inverse(a * mk["own"], sub, mk)
    if n > 1:   # (I + A)^-1 = (I + inv A_off)^-1 inv, the first nilpotent
        inv = _mm(_nilpotent_inverse(_mm(inv, a - a * mk["own"]), n,
                                     mk["eye"]), inv)
    rhs = jnp.concatenate([v * beta, k * decay * beta], 1)
    return dict(gc=gc, decay=decay, blocks=blocks, kk=kk, inv=inv,
                sol=_mm(inv, rhs),
                m=_by_head(m, sub, pack) if with_q else None)


def _heads(refs, j, d, pack):
    """Heads j.. of the (1, C, heads * d) blocks ``refs``, each as (pack *
    C, d): one head's chunk after another's."""
    def lanes(p):
        return pl.ds(pl.multiple_of((j + p) * d, 128), d)
    return [_join([r[0, :, lanes(p)] for p in range(pack)]) for r in refs]


def _pick_heads(beta_ref, first, pack):
    """Columns first.. of the (1, C, H) block of beta, as (pack * C, 1)."""
    b = beta_ref[0]
    return _join([jnp.sum(jnp.where(_iota(b.shape, 1) == first + p, b, 0.0),
                          1, keepdims=True) for p in range(pack)])


def _load(q_ref, k_ref, v_ref, g_ref, beta_ref, j, first, dk, dv, pack):
    """q, k, v, g, beta of the heads j.. of a program (the heads first + j..
    of all), packed."""
    q, k, g = _heads((q_ref, k_ref, g_ref), j, dk, pack)
    v, = _heads((v_ref,), j, dv, pack)
    return q, k, v, g, _pick_heads(beta_ref, first + j, pack)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, u0_ref, w_ref, m_ref,
                qin_ref, kout_ref, gend_ref, *, sub, heads, pack):
    dk, dv = q_ref.shape[2] // heads, v_ref.shape[2] // heads
    c = q_ref.shape[1]
    first = pl.program_id(2) * heads
    mk = _masks(c, sub, pack)

    def some(tile, _):
        j = tile * pack
        q, k, v, g, beta = _load(q_ref, k_ref, v_ref, g_ref, beta_ref, j,
                                 first, dk, dv, pack)
        t = _tile(q, k, v, g, beta, c, sub, mk, True)
        gc, sol = t["gc"], t["sol"]
        qin, kout = q * t["decay"], k * jnp.exp(
            _spread(gc, c, c - 1, c, pack) - gc)           # decay to the end
        for p in range(pack):
            own = slice(p * c, (p + 1) * c)
            u0_ref[j + p] = sol[own, :dv]
            w_ref[j + p] = sol[own, dv:]
            m_ref[j + p] = t["m"][own, own]
            qin_ref[j + p] = qin[own]
            kout_ref[j + p] = kout[own]
            gend_ref[j + p] = gc[(p + 1) * c - 1:(p + 1) * c]
        return _

    lax.fori_loop(0, heads // pack, some, None)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, du0_ref, dw_ref, dm_ref,
                dqin_ref, dkout_ref, dgend_ref, dq_ref, dk_ref, dv_ref,
                dg_ref, dbeta_ref, e_scr, *, sub, heads, pack):
    dk, dv = q_ref.shape[2] // heads, v_ref.shape[2] // heads
    c = q_ref.shape[1]
    rows, n, ps = pack * c, c // sub, pack * sub
    first = pl.program_id(2) * heads
    mk = _masks(c, sub, pack)

    @pl.when(first == 0)
    def _():
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

    def keep(p, col, r0, e):
        e_scr[p, col, r0:, :] = e

    def some(tile, _):
        j = tile * pack
        q, k, v, g, beta = _load(q_ref, k_ref, v_ref, g_ref, beta_ref, j,
                                 first, dk, dv, pack)
        t = _tile(q, k, v, g, beta, c, sub, mk, False, keep)
        gc, decay, blocks, sol = t["gc"], t["decay"], t["blocks"], t["sol"]
        eo = jnp.exp(_spread(gc, c, c - 1, c, pack) - gc)

        def stacked(ref):
            return _join([ref[j + p] for p in range(pack)])

        # sol = (I + A)^-1 rhs: the cotangent of rhs, then of A
        drhs = _mm(t["inv"], jnp.concatenate(
            [stacked(du0_ref), stacked(dw_ref)], 1), _TN)
        d_a = -_mm(drhs, sol, _NT) * mk["strict"]
        d_kk = d_a * beta
        d_m = _join([_join([x for x in (
            jnp.zeros((c, p * c), _F32), dm_ref[j + p],
            jnp.zeros((c, rows - (p + 1) * c), _F32)) if x.shape[1]], 1)
            for p in range(pack)])
        drv, drw = drhs[:, :dv], drhs[:, dv:]
        dbeta = jnp.sum(drv * v, 1, keepdims=True) \
            + jnp.sum(drw * k * decay, 1, keepdims=True) \
            + jnp.sum(d_a * t["kk"], 1, keepdims=True)

        # the pair scores: the rows' share (as a), the columns' (as b)
        da_q, da_k, db_exact = [], [], []
        db = jnp.zeros((rows, dk), _F32)
        for i, blk in enumerate(blocks):
            lo, up = i * sub, (i + 1) * sub
            qi, ki = _rows(q, c, lo, up, pack), _rows(k, c, lo, up, pack)
            dm_i = _rows(d_m, c, lo, up, pack)
            dkk_i = _rows(d_kk, c, lo, up, pack)
            aq = ak = dbi = jnp.zeros((ps, dk), _F32)
            if i:
                both = jnp.concatenate([dm_i, dkk_i], 0)
                ta = _mm(both, blk["right"])
                aq, ak = blk["el"] * ta[:ps], blk["el"] * ta[ps:]
                db = db + blk["er"] * _mm(
                    both, jnp.concatenate([qi * blk["el"], blk["left"]], 0),
                    _TN)
            for p in range(pack):
                a, b = p * sub, (p + 1) * sub
                for s in range(sub):
                    r0 = s // _SUBLANES * _SUBLANES
                    e = e_scr[p, lo + s, r0:, :]
                    lane = _iota((sub - r0, rows), 1) == p * c + lo + s
                    cm = jnp.sum(jnp.where(lane, dm_i[a + r0:b], 0.0), 1,
                                 keepdims=True)
                    ck = jnp.sum(jnp.where(lane, dkk_i[a + r0:b], 0.0), 1,
                                 keepdims=True)
                    pe = e * ki[a + s:a + s + 1]
                    aq = _add_rows(aq, cm * pe, a + r0, b)
                    ak = _add_rows(ak, ck * pe, a + r0, b)
                    dbi = _put_row(dbi, jnp.sum(
                        (cm * qi[a + r0:b] + ck * ki[a + r0:b]) * e, 0,
                        keepdims=True), a + s)
            da_q.append(aq)
            da_k.append(ak)
            db_exact.append(dbi)
        da_q, da_k = _by_head(da_q, sub, pack), _by_head(da_k, sub, pack)
        db = db + _by_head(db_exact, sub, pack)

        dqin, dkout = stacked(dqin_ref), stacked(dkout_ref)
        z = dkout * k * eo
        dgc = q * da_q + k * (da_k - db) \
            + (beta * drw * k + dqin * q) * decay - z
        for p in range(pack):       # a chunk's last row takes g_end's
            last = (p + 1) * c - 1
            dgc = _put_row(dgc, dgc[last:last + 1] + dgend_ref[j + p]
                           + jnp.sum(z[p * c:(p + 1) * c], 0, keepdims=True),
                           last)
        d_q = da_q + dqin * decay
        d_k = da_k + db + beta * drw * decay + dkout * eo
        d_v = beta * drv
        d_g = _mm(mk["upper"], dgc)
        for p in range(pack):
            own = slice(p * c, (p + 1) * c)
            lanes = pl.ds(pl.multiple_of((j + p) * dk, 128), dk)
            dq_ref[0, :, lanes] = d_q[own]
            dk_ref[0, :, lanes] = d_k[own]
            dg_ref[0, :, lanes] = d_g[own]
            dv_ref[0, :, pl.ds(pl.multiple_of((j + p) * dv, 128), dv)] = \
                d_v[own]
            dbeta_ref[0] += jnp.where(
                _iota(dbeta_ref.shape[1:], 1) == first + j + p, dbeta[own],
                0.0)
        return _

    lax.fori_loop(0, heads // pack, some, None)


def _tiles(x):
    """(B, T, H, d) as (B, T, H*d): a head's chunk is one block of it."""
    return x.reshape(x.shape[:2] + (-1,))


def _heads_a_program(h, chunk, d):
    """(heads a program takes, heads packed into one tile): rows of a block
    long enough for the DMA, few enough programs, blocks that still fit
    VMEM twice over; as many chunks as fill the 128 rows of an MXU tile."""
    heads = max(n for n in (1, 2, 4, 8)
                if h % n == 0 and n * chunk * d <= _BLOCK)
    return heads, max(n for n in (1, 2, 4, 8)
                      if heads % n == 0 and n * chunk <= max(chunk, 128))


def _in_specs(chunk, h, hb, dk, dv):
    def tile(d):
        return pl.BlockSpec((1, chunk, hb * d), lambda b, n, j: (b, n, j))
    return [tile(dk), tile(dk), tile(dv), tile(dk),
            pl.BlockSpec((1, chunk, h), lambda b, n, j: (b, n, 0))]


def _six(chunk, dk, dv):
    """(rows, width) of the six values of a tile that the scan consumes:
    u0, w, m, q_in, k_out, g_end."""
    return ((chunk, dv), (chunk, dk), (chunk, chunk), (chunk, dk),
            (chunk, dk), (1, dk))


def _by_chunk(b, n, j):
    return n, b, j


def _chunk_block(hb, r, d, at):
    """``hb`` heads' (r, d) of one chunk of an (N, B, H, r, d) array;
    ``at`` maps a grid point to (chunk, sequence, block of heads)."""
    return pl.BlockSpec((None, None, hb, r, d),
                        lambda *grid: at(*grid) + (0, 0))


def _scan_specs(chunk, hb, dk, dv, at=_by_chunk):
    return [_chunk_block(hb, r, d, at) for r, d in _six(chunk, dk, dv)]


def _scan_shapes(b, n, h, chunk, dk, dv):
    return [jax.ShapeDtypeStruct((n, b, h, r, d), _F32)
            for r, d in _six(chunk, dk, dv)]


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def intra_fwd(q, k, v, g, beta, chunk, sub, interpret):
    """The six values the scan over chunks consumes, (N, B, H, C, .): the
    pseudo-values before the state ``u0``, ``w`` (what the state takes
    from them), the q.k scores ``m``, q decayed from the chunk's start,
    k decayed to its end, the chunk's log decay."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk
    hb, pack = _heads_a_program(h, chunk, max(dk, dv))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub, heads=hb, pack=pack),
        grid=(b, n, h // hb),
        in_specs=_in_specs(chunk, h, hb, dk, dv),
        out_specs=_scan_specs(chunk, hb, dk, dv),
        out_shape=_scan_shapes(b, n, h, chunk, dk, dv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="kda_intra_fwd",
    )(_tiles(q), _tiles(k), _tiles(v), _tiles(g), beta)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def intra_bwd(q, k, v, g, beta, cts, chunk, sub, interpret):
    """The gradients of q, k, v, g, beta from the tiles and the cotangents
    of :func:`intra_fwd`'s six values; the tile's forward is recomputed."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk
    hb, pack = _heads_a_program(h, chunk, max(dk, dv))
    ins = _in_specs(chunk, h, hb, dk, dv)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub, heads=hb, pack=pack),
        grid=(b, n, h // hb),
        in_specs=ins + _scan_specs(chunk, hb, dk, dv),
        out_specs=ins,
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32) for x in
                   (_tiles(q), _tiles(k), _tiles(v), _tiles(g), beta)],
        scratch_shapes=[pltpu.VMEM((pack, chunk, sub, dk), _F32)],
        # a head's column of beta's gradient is added to a block that
        # stays while the heads pass: the last grid axis is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_intra_bwd",
    )(_tiles(q), _tiles(k), _tiles(v), _tiles(g), beta, *cts)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def kda_intra(q, k, v, g, beta, chunk, sub, interpret):
    """The work inside the chunks of a group as two kernels: q, k, g (B,
    T, H, d_k), v (B, T, H, d_v), beta (B, T, H), float32, T a multiple of
    ``chunk``; returns ``(u0, w, m, q_in, k_out, g_end)``, each (N, B, H,
    C, .). Nothing but the five inputs is kept for the backward pass."""
    return tuple(intra_fwd(q, k, v, g, beta, chunk, sub, interpret))


def _fwd(q, k, v, g, beta, chunk, sub, interpret):
    return kda_intra(q, k, v, g, beta, chunk, sub, interpret), \
        (q, k, v, g, beta)


def _bwd(chunk, sub, interpret, res, cts):
    # named here: a backward function is traced outside the scope its
    # forward ran under
    with jax.named_scope("mx/kda/intra"):
        return intra_bwd(*res, cts, chunk, sub, interpret)


kda_intra.defvjp(_fwd, _bwd)


# ------------------------------------------------ from chunk to chunk
# The scan that carries the state through a group's chunks, as one kernel
# forward and one backward: a program holds the state of a block of heads
# in VMEM while the chunk axis of the grid, the last and sequential, passes.
def _scan_heads(h, chunk, dk, dv, backward):
    """Heads a program of the scan takes: as many as a program of the
    work inside chunks, or the fewer whose blocks (the six values twice in
    the backward, o or its cotangent, three states) fit ``_SCAN_VMEM``
    twice over beside the state carried."""
    six = chunk * (dv + 3 * dk + chunk) + _SUBLANES * dk
    state = dk * dv
    blocks = (2 if backward else 1) * six + chunk * dv + 3 * state
    return max(n for n in (1, 2, 4, 8)
               if n <= _heads_a_program(h, chunk, max(dk, dv))[0]
               and h % n == 0
               and (n == 1 or 4 * n * (2 * blocks + state) <= _SCAN_VMEM))


def _turned(x):
    """A (1, n) row as the (n, 1) column, an (n, 1) column as the row:
    through the diagonal of an (n, n) value, whole registers."""
    n = x.size
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), x, 0.0),
                   int(x.shape[0] == 1), keepdims=True)


def _scan_fwd_kernel(s0_ref, u0_ref, w_ref, m_ref, qin_ref, kout_ref,
                     gend_ref, send_ref, o_ref, *rest, heads):
    """``_kda_group``'s step for the heads of a program at one chunk; the
    last of ``rest`` is the state carried, before it (under ``jax.vjp``)
    the block that takes the state on entry to the chunk. The heads go a
    stage at a time: while one's products wait for its ``u``, another's
    fill the MXU."""
    s_scr, entry_ref = rest[-1], rest[0] if len(rest) > 1 else None
    c = u0_ref.shape[1]
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        s_scr[...] = s0_ref[...]

    states = [s_scr[i] for i in range(heads)]
    if entry_ref is not None:
        for i, s in enumerate(states):
            entry_ref[i] = s
    # w.s and q.s in one product: the state is the MXU's weights once
    both = [_mm(jnp.concatenate([w_ref[i], qin_ref[i]], 0), s)
            for i, s in enumerate(states)]
    for i, s in enumerate(states):
        u = u0_ref[i] - both[i][:c]
        o_ref[i] = both[i][c:] + _mm(m_ref[i], u)
        s_scr[i] = s * _turned(jnp.exp(gend_ref[i])) \
            + _mm(kout_ref[i], u, _TN)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        send_ref[...] = s_scr[...]


def _scan_bwd_kernel(entry_ref, u0_ref, w_ref, m_ref, qin_ref, kout_ref,
                     gend_ref, do_ref, dsend_ref, ds0_ref, du0_ref, dw_ref,
                     dm_ref, dqin_ref, dkout_ref, dgend_ref, ds_scr, *,
                     heads):
    """The step's transpose for the heads of a program at one chunk, the
    chunks met last to first: ``u`` recomputed from the state on entry,
    the state's cotangent carried in ``ds_scr``; a stage at a time over
    the heads, as the forward."""
    c = u0_ref.shape[1]
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        ds_scr[...] = dsend_ref[...]

    first = []
    for i in range(heads):
        do, ds = do_ref[i], ds_scr[i]
        u = u0_ref[i] - _mm(w_ref[i], entry_ref[i])
        du = _mm(m_ref[i], do, _TN) + _mm(kout_ref[i], ds)
        du0_ref[i] = du
        first.append((do, ds, u, du))
    for i, (do, ds, u, du) in enumerate(first):
        s, w = entry_ref[i], w_ref[i]
        e = _turned(jnp.exp(gend_ref[i]))
        both = jnp.concatenate([do, du], 0)
        by_s = _mm(both, s, _NT)
        dqin_ref[i] = by_s[:c]
        dw_ref[i] = -by_s[c:]
        dm_ref[i] = _mm(do, u, _NT)
        dkout_ref[i] = _mm(u, ds, _NT)
        dgend_ref[i] = _turned(jnp.sum(ds * s, 1, keepdims=True) * e)
        # q^T.do - w^T.du in one product over both chunks' rows
        ds_scr[i] = ds * e + _mm(jnp.concatenate([qin_ref[i], -w], 0), both,
                                 _TN)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        ds0_ref[...] = ds_scr[...]


def _scan_call(kernel, name, b, h, n, hb, dk, dv, interpret, **specs):
    return pl.pallas_call(
        functools.partial(kernel, heads=hb), grid=(b, h // hb, n),
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        # the state stays in VMEM while a group's chunks pass: the last
        # grid axis is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name, **specs)


def _scan_blocks(n, hb, chunk, dk, dv, reverse):
    """Block specs over the grid (sequence, block of heads, chunk): (a
    state's, a state's a chunk, o's, the six values'), the chunks met
    first to last or, with ``reverse``, last to first."""
    def at(b, j, i):
        return (n - 1 - i if reverse else i), b, j
    return (pl.BlockSpec((None, hb, dk, dv), lambda b, j, i: (b, j, 0, 0)),
            _chunk_block(hb, dk, dv, at), _chunk_block(hb, chunk, dv, at),
            _scan_specs(chunk, hb, dk, dv, at))


@functools.partial(jax.jit, static_argnums=(7, 8))
def scan_fwd(s0, u0, w, m, q_in, k_out, g_end, keep, interpret):
    """``(s_end, o)`` of a group from the state ``s0`` (B, H, d_k, d_v) and
    the six values of :func:`intra_fwd`; with ``keep`` also the state on
    entry to every chunk, (N, B, H, d_k, d_v)."""
    n, b, h, chunk, dv = u0.shape
    dk = w.shape[-1]
    hb = _scan_heads(h, chunk, dk, dv, False)
    state, states, o, six = _scan_blocks(n, hb, chunk, dk, dv, False)
    return _scan_call(
        _scan_fwd_kernel, "kda_scan_fwd", b, h, n, hb, dk, dv, interpret,
        in_specs=[state] + six,
        # with ``keep`` (True is 1) one output more: the entry states
        out_specs=[state, o] + [states] * keep,
        out_shape=[jax.ShapeDtypeStruct(s0.shape, _F32),
                   jax.ShapeDtypeStruct(u0.shape, _F32)]
        + [jax.ShapeDtypeStruct((n,) + s0.shape, _F32)] * keep,
    )(s0, u0, w, m, q_in, k_out, g_end)


@functools.partial(jax.jit, static_argnums=(4,))
def scan_bwd(entry, xs, do, ds_end, interpret):
    """``(ds0, *the cotangents of the six values xs)`` from the states on
    entry to the chunks and the cotangents of o and of the last state."""
    n, b, h, chunk, dv = xs[0].shape
    dk = xs[1].shape[-1]
    hb = _scan_heads(h, chunk, dk, dv, True)
    state, states, o, six = _scan_blocks(n, hb, chunk, dk, dv, True)
    return tuple(_scan_call(
        _scan_bwd_kernel, "kda_scan_bwd", b, h, n, hb, dk, dv, interpret,
        in_specs=[states] + six + [o, state],
        out_specs=[state] + six,
        out_shape=[jax.ShapeDtypeStruct(ds_end.shape, _F32)]
        + _scan_shapes(b, n, h, chunk, dk, dv),
    )(entry, *xs, do, ds_end))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def kda_scan(s0, u0, w, m, q_in, k_out, g_end, interpret):
    """The scan from chunk to chunk of a group as two kernels, with the
    arithmetic of ``lm_ops._kda_group``'s step: ``u = u0 - w.s``, ``o =
    q_in.s + m.u``, ``s <- s * exp(g_end)^T + k_out^T.u`` a chunk, float32,
    every product at ``Precision.HIGHEST``. ``s0``: (B, H, d_k, d_v); the
    six values of :func:`kda_intra`: (N, B, H, C, .). Returns ``(s_end,
    o)``, o (N, B, H, C, d_v). The backward reads the six values again and
    the state on entry to every chunk, which the forward writes only under
    ``jax.vjp``."""
    return tuple(scan_fwd(s0, u0, w, m, q_in, k_out, g_end, False,
                          interpret))


def _scan_fwd_rule(*args):
    *given, interpret = args
    s_end, o, entry = scan_fwd(*given, True, interpret)
    return (s_end, o), (entry, tuple(given[1:]))


def _scan_bwd_rule(interpret, res, cts):
    ds_end, do = cts
    # named here: a backward function is traced outside the scope its
    # forward ran under
    with jax.named_scope("mx/kda/scan"):
        return scan_bwd(*res, do, ds_end, interpret)


kda_scan.defvjp(_scan_fwd_rule, _scan_bwd_rule)
