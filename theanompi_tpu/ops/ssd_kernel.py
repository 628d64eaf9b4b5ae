"""The chunked state-space scan (``ops/ssd.py`` ``ssd_scan``) as a pair
of Pallas TPU kernels: everything that lives under a head's DECAY
MASK ``exp(cum_l - cum_s)`` is built, used and dropped in VMEM, in the
forward, the layer's replay and the backward (PERF.md section 6, PR
48).  XLA's form wrote the float32 mask ``[B, H, chunks, chunk,
chunk]`` (537 MB a layer at the benchmark's sizes), the masked scores
and their cotangents to HBM and read them back around every product.

One kernel a direction, SEQUENTIAL over the chunks of a (batch, block
of ``heads`` heads): the running state lives in VMEM scratch, the
forward walks the chunks up and writes the state each chunk starts
from as the backward's residual, the backward walks them down with the
state's cotangent in scratch.  A grid step is one chunk.

Every array has the POSITIONS ON THE LANES: ``x``, ``y`` and their
cotangents are ``[B, H P, T]``, ``B`` and ``C`` ``[B, G N, T]``,
``dt`` and the cumulative ``dt A`` ``[B, H, T]``.  (It is the layout
XLA keeps a one-sequence batch's activations in: the transposes
around the kernels are bitcasts there, where a ``[T, H P]`` operand
cost two re-laying copies a call.)  So a head's ``dt``, ``cum`` and
decays multiply its ``[P, chunk]`` tile as ROWS, the sums over a
head's channels that ``d dt`` and ``d cum`` need run down the
sublanes and come out as rows, and no tile is narrower than a
register.  A grid step:

- ``C B^T`` once (all heads of a group share it) and the products with
  the running state for ALL the block's heads at once (``[heads P,
  N]`` against ``[N, chunk]``);
- per head the mask from the chunk's ``cum`` row and column (the
  column forms of all heads are one transpose a step), a block of 128
  positions against the positions it can see (the blocks above the
  diagonal are never built), and the products under it.

Decays, cumulative sums and states are float32; the products' operands
are in ``x``'s dtype with float32 accumulation (float32 operands at
``highest``).  The backward rebuilds the mask (and ``y``, in float32)
from ``cum``; the gradient of a log-decay needs no ``[chunk, chunk]``
reduction at all: ``d cum_t = sum_p dy_t y_t - sum_p (dt x)_t d(dt
x)_t`` (row sums less column sums of ``d(masked scores) * masked
scores`` are exactly those two inner products), plus the chunk
total's at its last position.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES = 128
_SUB = 128            # positions a block of the mask
_ROWS = 1024          # most rows (heads x channels) of x a grid step
_NEG = -1e30          # the mask's "never": exp gives an exact 0
_NN = (((1,), (0,)), ((), ()))    # a @ b
_NT = (((1,), (1,)), ((), ()))    # a @ b^T
_TN = (((0,), (0,)), ((), ()))    # a^T @ b
_VMEM_LIMIT = 64 * 1024 * 1024


class ScanTiles(NamedTuple):
    """The kernels' tiling for one scan shape: ``chunk`` positions a
    grid step, ``heads`` heads a step, ``sub`` positions a block of
    the mask."""
    chunk: int
    heads: int
    sub: int


def scan_tiles(t, chunk, p, n, heads_a_group, n_heads) -> ScanTiles | None:
    """The tiles for ``T`` positions in chunks of ``chunk``, heads of
    ``p`` channels over a state of ``n``, ``heads_a_group`` of
    ``n_heads`` heads a group — or ``None`` where the kernels do not
    take the shape (``ssd_scan`` then runs XLA's form): a chunk that
    is no multiple of 128 or does not divide ``T`` (no padded tail), a
    state that is no multiple of 128 lanes, a head whose channels do
    not fill 16-row tiles, or no block of heads that both divides a
    group and tiles the rows' ``[heads, chunk]`` blocks."""
    ln = min(int(chunk), t)
    if ln % _SUB or t % ln or n % _LANES or p % 16:
        return None
    blocks = [
        hb for hb in range(1, min(heads_a_group, max(_ROWS // p, 1)) + 1)
        if heads_a_group % hb == 0 and (hb % 8 == 0 or hb == n_heads)
    ]
    if not blocks:
        return None
    return ScanTiles(ln, blocks[-1], _SUB)


def _dot(a, b, dims):
    return lax.dot_general(
        a, b, dims, preferred_element_type=F32,
        precision=lax.Precision.HIGHEST if a.dtype == F32 else None,
    )


def _columns(rows):
    """``[k, chunk]`` row forms -> their column forms ``[chunk, 128]``
    (row ``i`` on lane ``i``): one aligned transpose."""
    k, ln = rows.shape
    pad = -k % _LANES
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, ln), F32)], axis=0)
    return rows.T


def _decay(col, row, rows_are_later, lanes_from):
    """``exp(later - earlier)`` under the triangle for a block of the
    mask: ``col [r, 1]`` holds ``cum`` at the block's rows, ``row [1,
    w]`` at its lanes; lane 0 is position ``lanes_from`` counted from
    row 0's.  ``rows_are_later``: the block is ``[l, s]``."""
    shape = (col.shape[0], row.shape[1])
    on_rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    on_lanes = lax.broadcasted_iota(jnp.int32, shape, 1) + lanes_from
    wide = jnp.broadcast_to(col, shape)
    if rows_are_later:
        seen, diff = on_rows >= on_lanes, wide - row
    else:
        seen, diff = on_lanes >= on_rows, row - wide
    return jnp.exp(jnp.where(seen, diff, _NEG))


def _rows_of(h, p):
    return slice(h * p, (h + 1) * p)


def _chunk_rows(dt_ref, cum_ref, n):
    """A chunk's row forms ``[heads, chunk]``: ``dt``, ``cum``,
    ``exp(cum)`` (what a position keeps of the state the chunk starts
    from), ``exp(total - cum)`` (what the chunk's end keeps of a
    position), and ``exp(total)`` over the state's ``n`` lanes."""
    dt, cum = dt_ref[...], cum_ref[...]
    total = cum[:, cum.shape[1] - 1:]
    return (dt, cum, jnp.exp(cum), jnp.exp(total - cum),
            jnp.exp(jnp.broadcast_to(total, (cum.shape[0], n))))


def _fwd_kernel(x_ref, dt_ref, cum_ref, d_ref, c_ref, b_ref, y_ref, sin_ref,
                st_ref, xw_ref, off_ref, *, tiles, p):
    """One chunk of one block of heads: ``st_ref`` carries the state
    ``[heads P, N]``; ``xw_ref`` gathers ``dt x exp(total - cum)`` and
    ``off_ref`` holds ``S_in C^T`` for all the block's heads."""
    ln, hb, sub = tiles
    cd = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    c, b = c_ref[...], b_ref[...]                        # [N, L]
    scores_t = _dot(b, c, _TN)                           # [s, l]
    dt, cum, e, w, e_end = _chunk_rows(dt_ref, cum_ref, c.shape[0])
    cols, skip = _columns(cum), d_ref[...]
    s_in = st_ref[...]
    sin_ref[...] = s_in
    off_ref[...] = _dot(s_in.astype(cd), c, _NN)         # [hb P, L]

    for h in range(hb):
        rows, row = _rows_of(h, p), slice(h, h + 1)
        x = x_ref[rows, :].astype(F32)                   # [P, L]
        xd = (x * dt[row]).astype(cd)
        xw_ref[rows, :] = (x * (dt[row] * w[row])).astype(cd)
        for lo in range(0, ln, sub):
            hi = lo + sub
            dec = _decay(cols[:hi, row], cum[row, lo:hi], False, lo)
            m_t = (scores_t[:hi, lo:hi] * dec).astype(cd)
            y = off_ref[rows, lo:hi] * e[row, lo:hi] + _dot(
                xd[:, :hi], m_t, _NN) + x[:, lo:hi] * skip[row, lo:hi]
            y_ref[rows, lo:hi] = y.astype(cd)

    step = _dot(xw_ref[...], b, _NT)                     # [hb P, N]
    for h in range(hb):
        rows = _rows_of(h, p)
        st_ref[rows, :] = e_end[h:h + 1] * s_in[rows] + step[rows]


def _bwd_kernel(x_ref, dy_ref, dt_ref, cum_ref, d_ref, c_ref, b_ref, sin_ref,
                dx_ref, ddt_ref, dcum_ref, dd_ref, dtot_ref, dst_out_ref,
                dc_ref, db_ref, dst_ref, dsc_ref, xw_ref, dyo_ref, off_ref,
                dxw_ref, y_ref, *, tiles, p):
    """One chunk of one block of heads, the chunks walked from the
    last: ``dst_ref`` carries the state's cotangent, ``dsc_ref``
    gathers ``d(C B^T)`` over the block's heads, ``xw_ref`` / ``dyo_ref``
    / ``off_ref`` / ``dxw_ref`` hold the operands and results of the
    state's products for all the block's heads, ``y_ref`` one head's
    ``y`` (without its ``D`` skip) again in float32.  The mask is built
    ``[l, s]``, so that ``d(dt x)`` is a plain product.

    Every pair of terms that cancels in a log-decay's gradient is
    formed from the SAME rounded operands (``dy . y`` against ``(dt x)
    . d(dt x)``, both through the products' own operands; the chunk
    total's term through the same ``dt x``): at bfloat16 an operand
    rounded on one side only leaves ``dA`` off by a tenth."""
    ln, hb, sub = tiles
    cd = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    c, b = c_ref[...], b_ref[...]                        # [N, L]
    scores = _dot(c, b, _TN)                             # [l, s]
    dt, cum, e, w, e_end = _chunk_rows(dt_ref, cum_ref, c.shape[0])
    cols, skip = _columns(cum), d_ref[...]
    s_in, ds = sin_ref[...], dst_ref[...]
    s_c, ds_c = s_in.astype(cd), ds.astype(cd)

    for h in range(hb):
        rows, row = _rows_of(h, p), slice(h, h + 1)
        xw_ref[rows, :] = (
            x_ref[rows, :].astype(F32) * (dt[row] * w[row])).astype(cd)
        dyo_ref[rows, :] = (dy_ref[rows, :].astype(F32) * e[row]).astype(cd)
    # the carried state's part of y and the state's own step, all heads
    off_ref[...] = _dot(s_c, c, _NN)                     # [hb P, L]
    dxw_ref[...] = _dot(ds_c, b, _NN)
    dc = _dot(s_c, dyo_ref[...], _TN)                    # [N, L]
    db = _dot(ds_c, xw_ref[...], _TN)
    ds_new = _dot(dyo_ref[...], c, _NT)                  # [hb P, N]
    dsc_ref[...] = jnp.zeros_like(dsc_ref)

    for h in range(hb):
        rows, row = _rows_of(h, p), slice(h, h + 1)
        x = x_ref[rows, :].astype(F32)
        dy_c = dy_ref[rows, :]
        xd = (x * dt[row]).astype(cd)
        dxd_state = dxw_ref[rows, :] * w[row]            # [P, L]
        y_ref[...] = off_ref[rows, :] * e[row]
        # the part under the mask, a block [l at or after s, s]
        parts = []
        for lo in range(0, ln, sub):
            hi = lo + sub
            dec = _decay(cols[lo:, row], cum[row, lo:hi], True, 0)
            m = (scores[lo:, lo:hi] * dec).astype(cd)    # [L - lo, sub]
            parts.append(dxd_state[:, lo:hi] + _dot(dy_c[:, lo:], m, _NN))
            y_ref[:, lo:] += _dot(xd[:, lo:hi], m, _NT)
            dsc_ref[lo:, lo:hi] += _dot(dy_c[:, lo:], xd[:, lo:hi], _TN) * dec
        dxd = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        dy, xd_f = dy_c.astype(F32), xd.astype(F32)
        dx_ref[rows, :] = (dxd * dt[row] + dy * skip[row]).astype(cd)
        ddt_ref[row, :] = jnp.sum(x * dxd, axis=0, keepdims=True)
        dd_ref[row, :] = jnp.sum(x * dy, axis=0, keepdims=True)
        dcum_ref[row, :] = jnp.sum(
            dy * y_ref[...] - xd_f * dxd, axis=0, keepdims=True)
        # the chunk total's: through exp(total - cum) and exp(total)
        dtot_ref[row, :] = jnp.sum(xd_f * dxd_state, axis=0, keepdims=True)
        dst_out_ref[row, :] = e_end[row] * jnp.sum(
            ds[rows] * s_in[rows], axis=0, keepdims=True)
        dst_ref[rows, :] = e_end[row] * ds[rows] + ds_new[rows]

    dsc = dsc_ref[...].astype(cd)                        # [l, s]
    dc_ref[...] = dc + _dot(b, dsc, _NT)                 # dC^T [N, l]
    db_ref[...] = db + _dot(c, dsc, _NN)                 # dB^T [N, s]


def _dims(xt, dt_rows, bt, n):
    b, hp, t = xt.shape
    h = dt_rows.shape[1]
    return b, t, h, hp // h, bt.shape[1] // n


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT,
)


def _skip_rows(d, ln):
    """``D [H] -> float32[H, chunk]``: a head's skip weight as a row."""
    return jnp.broadcast_to(d.astype(F32)[:, None], (d.shape[0], ln))


def _fwd_call(xt, dt_rows, cum_rows, d, bt, ct, n, tiles, interpret):
    b, t, h, p, g = _dims(xt, dt_rows, bt, n)
    ln, hb, _ = tiles
    per_group = h // g // hb
    vma = jax.typeof(xt).vma
    wide = pl.BlockSpec((None, hb * p, ln), lambda i, j, k: (i, j, k))
    rows = pl.BlockSpec((None, hb, ln), lambda i, j, k: (i, j, k))
    skip = pl.BlockSpec((hb, ln), lambda i, j, k: (j, 0))
    group = pl.BlockSpec((None, n, ln), lambda i, j, k: (i, j // per_group, k))
    state = pl.BlockSpec((None, None, hb * p, n), lambda i, j, k: (i, k, j, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles, p=p),
        out_shape=(
            jax.ShapeDtypeStruct(xt.shape, xt.dtype, vma=vma),
            jax.ShapeDtypeStruct((b, t // ln, h * p, n), F32, vma=vma),
        ),
        grid=(b, h // hb, t // ln),
        in_specs=[wide, rows, rows, skip, group, group],
        out_specs=(wide, state),
        scratch_shapes=[
            pltpu.VMEM((hb * p, n), F32),
            pltpu.VMEM((hb * p, ln), xt.dtype),
            pltpu.VMEM((hb * p, ln), F32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd-chunk-fwd",
    )(xt, dt_rows, cum_rows, _skip_rows(d, ln), ct, bt)


def _bwd_call(xt, dyt, dt_rows, cum_rows, d, bt, ct, s_in, n, tiles,
              interpret):
    b, t, h, p, g = _dims(xt, dt_rows, bt, n)
    ln, hb, _ = tiles
    z = t // ln
    per_group = h // g // hb
    vma = jax.typeof(xt).vma
    last = z - 1                    # the chunks are walked from the last
    wide = pl.BlockSpec((None, hb * p, ln), lambda i, j, k: (i, j, last - k))
    rows = pl.BlockSpec((None, hb, ln), lambda i, j, k: (i, j, last - k))
    skip = pl.BlockSpec((hb, ln), lambda i, j, k: (j, 0))
    group = pl.BlockSpec(
        (None, n, ln), lambda i, j, k: (i, j // per_group, last - k))
    state = pl.BlockSpec(
        (None, None, hb * p, n), lambda i, j, k: (i, last - k, j, 0))
    total = pl.BlockSpec((None, None, hb, n), lambda i, j, k: (i, last - k, j, 0))
    # dC and dB of a block of heads: summed over a group's blocks outside
    part = pl.BlockSpec(
        (None, None, n, ln),
        lambda i, j, k: (i, j % per_group, j // per_group, last - k))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=tiles, p=p),
        out_shape=(
            jax.ShapeDtypeStruct(xt.shape, xt.dtype, vma=vma),
            jax.ShapeDtypeStruct(dt_rows.shape, F32, vma=vma),
            jax.ShapeDtypeStruct(dt_rows.shape, F32, vma=vma),
            jax.ShapeDtypeStruct(dt_rows.shape, F32, vma=vma),
            jax.ShapeDtypeStruct(dt_rows.shape, F32, vma=vma),
            jax.ShapeDtypeStruct((b, z, h, n), F32, vma=vma),
            jax.ShapeDtypeStruct((b, per_group, g * n, t), F32, vma=vma),
            jax.ShapeDtypeStruct((b, per_group, g * n, t), F32, vma=vma),
        ),
        grid=(b, h // hb, z),
        in_specs=[wide, wide, rows, rows, skip, group, group, state],
        out_specs=(wide, rows, rows, rows, rows, total, part, part),
        scratch_shapes=[
            pltpu.VMEM((hb * p, n), F32),
            pltpu.VMEM((ln, ln), F32),
            pltpu.VMEM((hb * p, ln), xt.dtype),
            pltpu.VMEM((hb * p, ln), xt.dtype),
            pltpu.VMEM((hb * p, ln), F32),
            pltpu.VMEM((hb * p, ln), F32),
            pltpu.VMEM((p, ln), F32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd-chunk-bwd",
    )(xt, dyt, dt_rows, cum_rows, _skip_rows(d, ln), ct, bt, s_in)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def ssd_chunks(xt, dt_rows, cum_rows, d, bt, ct, n, tiles, interpret):
    """The scan with the positions on the lanes.  ``xt [B, H P, T]``,
    ``dt_rows`` and ``cum_rows`` ``float32[B, H, T]`` (``dt`` and the
    cumulative ``dt A`` inside each chunk), ``d [H]`` (the skip's
    weights), ``bt`` / ``ct`` ``[B, G N, T]``.  Returns ``(y [B, H P,
    T], s_in float32[B, chunks, H P, N])``: ``s_in`` the state each
    chunk starts from — read it under ``stop_gradient`` only: the
    backward takes no cotangent for it."""
    return _fwd_call(xt, dt_rows, cum_rows, d, bt, ct, n, tiles, interpret)


def _chunks_fwd(xt, dt_rows, cum_rows, d, bt, ct, n, tiles, interpret):
    yt, s_in = _fwd_call(
        xt, dt_rows, cum_rows, d, bt, ct, n, tiles, interpret)
    return (yt, s_in), (xt, dt_rows, cum_rows, d, bt, ct, s_in)


def _chunks_bwd(n, tiles, interpret, res, cts):
    xt, dt_rows, cum_rows, d, bt, ct, s_in = res
    dyt, _ = cts
    dxt, ddt, dcum, dd, dtot, dst, dc, db = _bwd_call(
        xt, dyt, dt_rows, cum_rows, d, bt, ct, s_in, n, tiles, interpret)
    b, h, t = dt_rows.shape
    z = t // tiles.chunk
    # the chunk total is the cumulative sum at the chunk's last position
    dtotal = dtot.reshape(b, h, z, -1).sum(-1) + dst.sum(-1).transpose(0, 2, 1)
    dcum = dcum.reshape(b, h, z, -1).at[..., -1].add(dtotal).reshape(b, h, t)
    return (dxt, ddt, dcum, dd.sum((0, 2)).astype(d.dtype),
            db.sum(1).astype(bt.dtype), dc.sum(1).astype(ct.dtype))


ssd_chunks.defvjp(_chunks_fwd, _chunks_bwd)
