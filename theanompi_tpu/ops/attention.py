"""Attention primitives: reference MHA, blockwise online-softmax
update, and a Pallas TPU flash-attention kernel.

New-framework scope (the reference has no attention at all — SURVEY
§2.2 lists ring attention / blockwise as absent upstream, to be built
for the long-context configs).  Design:

- ``mha_reference`` — plain jnp softmax attention; the numerical
  ground truth for every other path and the CPU fallback.
- ``block_attn_update`` — ONE step of the online-softmax recurrence
  (Milakov & Gimelshein 2018; the flash-attention accumulator): takes
  the running ``(acc, m, l)`` carry and folds in one KV block.  Both
  the ring-attention loop (``parallel/ring_attention.py``) and any
  sequential blockwise scan share this exact function, so cross-device
  ring results match single-device attention bit-for-bit in fp32.
- ``flash_attention`` — fused Pallas kernels (forward, and ONE
  backward that builds each score tile once for dQ, dK and dV: a
  block of rows resident, the other axis walked in [rows, sub] score
  tiles, f32 accumulators in scratch, tiles chosen from the shapes by
  ``_flash_tiles``) with the same signature; ``mha_reference``
  off-TPU and at lengths no kernel block tiles (each such choice is
  logged once per shape).  All three take a ``window`` beside the
  causal mask (a query sees itself and the ``window - 1`` keys before
  it): the kernels then walk the band alone.

Shapes follow [B, H, T, D] (head-major, the TPU-friendly layout: the
``[Tq, D] x [D, Tk]`` score matmul and ``[Tq, Tk] x [Tk, D]`` value
matmul both hit the MXU per (batch, head) grid cell).
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

NEG_INF = -1e30  # finite "-inf": keeps exp() NaN-free in masked blocks

# ``jax.ad_checkpoint.checkpoint_name``s of the two residuals the
# forward kernel alone can give the backward rule of ``_flash``: its
# output and the rows' logsumexp.  A ``jax.checkpoint`` whose policy
# saves these two replays no forward kernel (``Llama._forward``).
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                window: int | None = None) -> jnp.ndarray:
    """[Tq, Tk] bool — query may attend to keys at <= its position
    and, under a ``window``, to the last ``window`` of them alone
    (itself and the ``window - 1`` before it)."""
    mask = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    return mask


def mha_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    k_offset: int | jnp.ndarray = 0,
    sm_scale: float | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Dense softmax attention, f32 softmax.  q,k,v: [B, H, T, D].

    ``q_offset``/``k_offset`` are the *global* positions of element 0,
    so sharded callers can mask correctly on local blocks.  ``window``
    (causal only): a query sees itself and the ``window - 1`` keys
    before it (``causal_mask``).
    """
    assert causal or window is None, "a window is a causal mask's"
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[2])
        k_pos = k_offset + jnp.arange(k.shape[2])
        s = jnp.where(causal_mask(q_pos, k_pos, window), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def block_attn_update(
    carry: tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    q: jnp.ndarray,
    k_blk: jnp.ndarray,
    v_blk: jnp.ndarray,
    *,
    q_pos: jnp.ndarray | None,
    k_pos: jnp.ndarray | None,
    sm_scale: float,
    window: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fold one KV block into the online-softmax carry.

    carry = (acc [B,H,Tq,D] f32, m [B,H,Tq] f32 running max,
    l [B,H,Tq] f32 running sum).  Pass ``q_pos``/``k_pos`` (global
    positions) for causal masking (under a ``window`` too:
    ``causal_mask``), or None for full attention.
    """
    acc, m, l = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk).astype(jnp.float32)
    s = s * sm_scale
    if q_pos is not None:
        mask = causal_mask(q_pos, k_pos, window)
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # exp of masked entries: s=NEG_INF, m_new >= old max; use explicit
    # where so fully-masked blocks contribute exact zeros
    p = jnp.exp(s - m_new[..., None])
    if q_pos is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk
    ).astype(jnp.float32)
    return acc_new, m_new, l_new


def block_attn_init(b, h, tq, d):
    """Fresh online-softmax carry."""
    return (
        jnp.zeros((b, h, tq, d), jnp.float32),
        jnp.full((b, h, tq), NEG_INF, jnp.float32),
        jnp.zeros((b, h, tq), jnp.float32),
    )


def block_attn_finish(carry, dtype):
    acc, _, l = carry
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# Pallas TPU flash attention
# ---------------------------------------------------------------------------
#
# Two kernels: forward and backward.  Each keeps a block of ``rows``
# resident (queries in the forward, keys in the backward), fetches the
# other axis ``major`` positions a grid step and folds that block in
# ``sub`` positions at a time, so the live score tile is [rows, sub]
# however large the fetched block.  Under causality a sub-block wholly
# on the visible side of the diagonal takes a body with no mask, one
# the diagonal crosses the masked body, one wholly above it none; a
# grid step with nothing to fold names the block already resident and
# fetches nothing.  ``_flash_tiles`` chooses (rows, major, sub) per
# kernel from the shapes.
#
# Under a WINDOW (a query sees itself and the ``window - 1`` keys
# before it) the visible scores are a band: a sub-block can be crossed
# by the diagonal, by the band's lower edge, or by both, and lies
# outside on either side.  The walked grid axis is then as long as the
# band a row block sees (``_band_steps``), not as the walked axis, and
# starts at the band's first block (``_band``): queries walk the keys
# behind them, keys the queries ahead of them.
#
# The forward holds queries on the tile's sublanes, so its products
# stream the query block against a latched [sub, d] piece of K or V;
# the backward holds the KEYS there (the tile is the transposed
# scores, ``k @ q^T``), so its products for dV and dK stream the key
# block against [sub, d] pieces of Q and dO, and ONE contracts over
# the tile's rows: ``ds^T @ k``, the walked queries' dQ, summed over
# the key blocks in a float32 ``[T_q, d]`` scratch that lives for the
# batch-head.  One tile serves all three gradients: 5 products a tile
# (S, dP, dV, dK, dQ), its ``exp``, mask and ``ds`` once (a dK/dV and
# a dQ kernel, the form until PR 54, computed 7 and both twice).
# The rows' statistics cross the kernels' edge lane-dense,
# ``f32[B*H, 1, T]``: the backward's tile takes them as rows as they
# are.

_LANES = 128      # lanes of a vector register: width of the statistics' scratch
_NT = (((1,), (1,)), ((), ()))    # a @ b^T, the matrix unit's native form
_NN = (((1,), (0,)), ((), ()))    # a @ b
_TN = (((0,), (0,)), ((), ()))    # a^T @ b: Mosaic transposes the tile


class FlashTiles(NamedTuple):
    """One kernel's tiling: ``rows`` of the resident block, ``major``
    positions of the walked axis fetched a grid step, folded ``sub``
    at a time."""
    rows: int
    major: int
    sub: int


class FlashPlan(NamedTuple):
    """The two kernels' tilings for one attention shape."""
    fwd: FlashTiles
    bwd: FlashTiles


def _scaled(x, sm_scale):
    """``x * sm_scale`` in ``x``'s dtype: the scale goes into a
    [block, d] operand once, not into every [rows, sub] score tile."""
    return (x.astype(jnp.float32) * sm_scale).astype(x.dtype)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _lanes(x, n):
    """A lane-replicated [rows, _LANES] statistic at width ``n``."""
    reps, rem = divmod(n, x.shape[1])
    if rem:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return jnp.tile(x, (1, reps)) if reps > 1 else x


def _diagonal(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _as_row(x):
    """Lane-replicated [rows, _LANES] -> lane-dense [1, rows]."""
    rows = x.shape[0]
    if rows % _LANES == 0:
        return x.T[:1]
    # no aligned transpose for a ragged (single, small) block: pick the
    # diagonal of the column broadcast along lanes — exact
    wide = jnp.broadcast_to(x[:, :1], (rows, rows))
    return jnp.sum(jnp.where(_diagonal(rows), wide, 0.0), axis=0,
                   keepdims=True)


def _visible(q_start, k_start, shape, q_axis, window=None):
    """``shape`` bool tile: query position >= key position (and less
    than ``window`` past it), queries along ``q_axis`` of the tile and
    keys along the other."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (q_pos - k_pos < window)


def _sub_block_kind(lo, sub, row_start, rows, rows_are_queries, window=None):
    """(clear, crossed) for the walked sub-block [lo, lo + sub) against
    the resident rows [row_start, row_start + rows): ``clear`` when
    every score of the tile is visible, ``crossed`` when only some are
    (the diagonal runs through it, or under a ``window`` the band's
    lower edge, or both), neither when it lies outside on either side.
    Plain arithmetic: the kernels call it on grid indices, the
    ``flash_tiles`` counter on integers."""
    hi, row_hi = lo + sub - 1, row_start + rows - 1
    if rows_are_queries:          # walked: keys
        if window is None:
            return hi <= row_start, (hi > row_start) & (lo <= row_hi)
        # the last query against the first key is the pair furthest
        # apart, the first query against the last key the nearest
        clear = (hi <= row_start) & (row_hi - lo < window)
        some = (lo <= row_hi) & (row_start - hi < window)
    else:                         # walked: queries
        if window is None:
            return lo >= row_hi, (lo < row_hi) & (hi >= row_start)
        clear = (lo >= row_hi) & (hi - row_start < window)
        some = (hi >= row_start) & (lo - row_hi < window)
    return clear, some != clear     # (clear implies some)


def _least(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both else jnp.minimum(a, b)


def _most(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both else jnp.maximum(a, b)


def _band(r, tiles, rows_are_queries, window, n_walked):
    """``(first, last)`` of the walked axis' ``n_walked`` blocks (of
    ``tiles.major``) that hold a position the resident row block ``r``
    sees through the band: queries ``[lo, hi]`` see the keys
    ``(lo - window, hi]``, keys are seen by the queries ``[lo, hi +
    window)``; both cut to the axis (a key block past the last query
    names the last block, where nothing is visible to fold).  On
    integers or on grid indices."""
    lo = r * tiles.rows
    hi = lo + tiles.rows - 1
    if rows_are_queries:
        first, last = _most(lo - window + 1, 0), hi
    else:
        first, last = lo, hi + window - 1
    last = _least(last // tiles.major, n_walked - 1)
    return _least(first // tiles.major, last), last


def _band_steps(t_rows, t_walk, tiles, rows_are_queries, window) -> int:
    """The walked grid axis under a window: the most blocks any row
    block's band holds (``ceil((rows + window - 1) / major) + 1`` at
    most, and never more than the axis has)."""
    n_walked = t_walk // tiles.major
    spans = (
        _band(r, tiles, rows_are_queries, window, n_walked)
        for r in range(t_rows // tiles.rows)
    )
    return max(last - first + 1 for first, last in spans)


def _walk(fold, n_sub, sub, causal, lo, row_start, rows, rows_are_queries,
          window=None, live=None):
    """Fold the walked block's ``n_sub`` sub-blocks, each through the
    body its place against the diagonal (and the band's lower edge)
    asks for, or none.  ``live``: whether this grid step names a block
    of its own at all (a step past the band's last block names that
    block again and folds nothing)."""
    for c in range(n_sub):
        if not causal:
            fold(c, False)
            continue
        clear, crossed = _sub_block_kind(
            lo + c * sub, sub, row_start, rows, rows_are_queries, window
        )
        if live is not None:
            clear, crossed = clear & live, crossed & live
        pl.when(clear)(functools.partial(fold, c, False))
        pl.when(crossed)(functools.partial(fold, c, True))


def _walked_start(window, n_walked, rows_are_queries, rows, major, sub):
    """``(first position of the walked block this grid step names,
    live)`` for grid cell (row block ``program_id(1)``, step
    ``program_id(2)``): step ``w`` names block ``w``, or under a window
    the band's ``first + w`` (``live`` while that is no further than
    its last)."""
    w = pl.program_id(2)
    if window is None:
        return w * major, None
    first, last = _band(
        pl.program_id(1), FlashTiles(rows, major, sub), rows_are_queries,
        window, n_walked,
    )
    return (first + w) * major, first + w <= last


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, qs_ref, acc_ref, m_ref, l_ref,
    *, sm_scale, causal, sub, window=None, n_walked=None
):
    """One (batch*head, q-block, kv-block) grid cell.

    The kv grid dim is sequential (``ARBITRARY`` semantics): the
    online-softmax carry lives in VMEM scratch across kv steps, the
    running max and sum lane-replicated ([rows, 128]) so a fold
    broadcasts neither along lanes.
    """
    ki = pl.program_id(2)
    rows, d = acc_ref.shape
    major = k_ref.shape[1]
    q_start = pl.program_id(1) * rows
    k_start, live = _walked_start(window, n_walked, True, rows, major, sub)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        qs_ref[...] = _scaled(q_ref[0], sm_scale)

    def fold(c, masked):
        cols = pl.ds(c * sub, sub)
        v_blk = v_ref[0, cols, :]
        s = _dot(qs_ref[...], k_ref[0, cols, :], _NT)      # [rows, sub]
        if masked:
            # one select: every row's first folded key is key 0, which
            # it sees, so ``m`` is finite from the first fold on and
            # exp(NEG_INF - m) is already an exact 0.  (Under a window
            # a row's first tile may hold no key it sees: ``m`` stays
            # NEG_INF, ``p`` is 1 there, and the first key the row does
            # see — itself, at the latest — wipes that with ``alpha =
            # exp(NEG_INF - m)``, an exact 0 too.)
            s = jnp.where(
                _visible(q_start, k_start + c * sub, s.shape, 0, window),
                s, NEG_INF,
            )
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, sub))
        alpha = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + _dot(
            p.astype(v_blk.dtype), v_blk, _NN
        )

    _walk(fold, major // sub, sub, causal, k_start, q_start, rows, True,
          window, live)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / _lanes(l, d)).astype(o_ref.dtype)
        # logsumexp per query row — the backward kernels' residual
        lse_ref[0] = _as_row(m_ref[...] + jnp.log(l))


def _on_tpu() -> bool:
    """True when the devices the framework builds its meshes from
    (``parallel.mesh.default_devices``) are TPUs — read off a device,
    never off a setting alone."""
    from theanompi_tpu.parallel.mesh import default_devices

    return default_devices()[0].platform == "tpu"


def _flash_bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_acc, dk_acc, dv_acc, *, sm_scale, causal, sub, window=None,
    n_walked=None
):
    """The whole backward for one kv block: grid (bh, kv-block,
    q-block), both inner dims sequential.  The tile is the TRANSPOSED
    scores, keys on its sublanes and ``sub`` queries on its lanes:
    logsumexp and delta come as the rows they are stored as, and it is
    built ONCE for all three gradients.  dK and dV sum over the walked
    queries in [rows, d] scratch and leave with their kv block; dQ
    sums over the kv blocks in a float32 [T_q, d] scratch that lives
    for the whole batch-head (``ds^T @ k`` into the walked queries'
    rows of it: the one product that contracts over a tile's rows) and
    leaves, scaled on the float32 sum, at the batch-head's last cell."""
    kj, qi = pl.program_id(1), pl.program_id(2)
    rows = k_ref.shape[1]
    major = q_ref.shape[1]
    k_start = kj * rows
    q_start, live = _walked_start(window, n_walked, False, rows, major, sub)

    @pl.when((kj == 0) & (qi == 0))
    def _init_head():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def fold(c, masked):
        cols = pl.ds(c * sub, sub)
        qs = _scaled(q_ref[0, cols, :], sm_scale)          # [sub, d]
        do = do_ref[0, cols, :]
        p = jnp.exp(_dot(k_ref[0], qs, _NT) - lse_ref[0, :, cols])
        if masked:
            p = jnp.where(
                _visible(q_start + c * sub, k_start, p.shape, 1, window),
                p, 0.0,
            )
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)    # p^T @ dO
        dp = _dot(v_ref[0], do, _NT)                        # (dO @ V^T)^T
        ds = (p * (dp - delta_ref[0, :, cols])).astype(qs.dtype)
        # the scale rides in ``qs``: ds^T @ (scale * Q)
        dk_acc[...] += _dot(ds, qs, _NN)
        at = pl.ds(pl.multiple_of(q_start + c * sub, sub), sub)
        dq_acc[at, :] += _dot(ds, k_ref[0], _TN)            # ds^T @ K

    _walk(fold, major // sub, sub, causal, q_start, k_start, rows, False,
          window, live)

    last = qi == pl.num_programs(2) - 1

    @pl.when(last)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(last & (kj == pl.num_programs(1) - 1))
    def _finish_head():
        # the scale once, on the float32 [T_q, d] sum
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _flash_dims(q, k, rows_of_q: bool, tiles: FlashTiles):
    """Shapes of one kernel call, its tiling checked against them."""
    b, h, t, d = q.shape
    t_k = k.shape[2]
    t_rows, t_walk = (t, t_k) if rows_of_q else (t_k, t)
    if t_rows % tiles.rows or t_walk % tiles.major or tiles.major % tiles.sub:
        raise ValueError(
            f"T={t}/T_k={t_k} not divisible by blocks {tuple(tiles)} "
            f"(rows over {'T' if rows_of_q else 'T_k'})"
        )
    return b, h, t, t_k, d


def _SEM(*names, vmem_limit_bytes=None):
    return pltpu.CompilerParams(
        dimension_semantics=tuple(
            getattr(pltpu.GridDimensionSemantics, n) for n in names
        ),
        vmem_limit_bytes=vmem_limit_bytes,
    )


def _walked_index(causal, tiles: FlashTiles, rows_are_queries: bool, n_walked,
                  window=None):
    """Index of the walked block for grid cell (row block ``r``, step
    ``w`` of ``n_walked``).  Under causality a step with nothing to
    fold names the nearest block that has — the one already resident,
    so nothing is fetched for it: queries see no key block past their
    last row, keys are seen by no query block before their first (nor
    by any, where the keys outrun the queries: the last block then).
    Under a ``window`` step ``w`` (of ``_band_steps``) names the
    band's ``w``-th block, held to its last from above: the band's
    first block bounds it from below by construction."""
    if not causal:
        return lambda r, w: w
    if window is not None:
        def walked(r, w):
            first, last = _band(r, tiles, rows_are_queries, window, n_walked)
            return _least(first + w, last)

        return walked
    if rows_are_queries:
        return lambda r, w: jnp.minimum(
            w, (r * tiles.rows + tiles.rows - 1) // tiles.major
        )
    return lambda r, w: jnp.maximum(
        w, jnp.minimum((r * tiles.rows) // tiles.major, n_walked - 1)
    )


def _walked_axis(t_rows, t_walk, tiles, rows_are_queries, window):
    """The walked grid axis of one kernel call: ``(its length, the
    kernel's keywords to place a step on it)`` — the whole axis in
    blocks of ``tiles.major``, or under a window the band's
    ``_band_steps``."""
    n_walked = t_walk // tiles.major
    if window is None:
        return n_walked, {}
    steps = _band_steps(t_rows, t_walk, tiles, rows_are_queries, window)
    return steps, dict(window=window, n_walked=n_walked)


def _flash_fwd_call(q, k, v, causal, sm_scale, tiles, interpret, window=None):
    """Forward kernel: ``(out [B,H,T,D], logsumexp f32[B,H,T])``."""
    b, h, t, t_k, d = _flash_dims(q, k, True, tiles)
    rows, major, sub = tiles
    qs = q.reshape(b * h, t, d)
    ks = k.reshape(b * h, t_k, d)
    vs = v.reshape(b * h, t_k, d)
    vma = jax.typeof(qs).vma
    steps, band = _walked_axis(t, t_k, tiles, True, window)
    walked = _walked_index(causal, tiles, True, t_k // major, window)
    q_spec = pl.BlockSpec((1, rows, d), lambda i, j, kk: (i, j, 0))
    k_spec = pl.BlockSpec((1, major, d), lambda i, j, kk: (i, walked(j, kk), 0))
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_kernel, sm_scale=sm_scale, causal=causal, sub=sub,
            **band,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32, vma=vma),
        ),
        grid=(b * h, t // rows, steps),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=(
            q_spec,
            pl.BlockSpec((1, 1, rows), lambda i, j, kk: (i, 0, j)),
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, d), q.dtype),
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
        ],
        # kv dim carries the scratch accumulator -> sequential
        compiler_params=_SEM("PARALLEL", "PARALLEL", "ARBITRARY"),
        interpret=interpret,
    )(qs, ks, vs)
    return out.reshape(b, h, t, d), lse.reshape(b, h, t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, plan, interpret, window=None):
    out, _ = _flash_fwd_call(
        q, k, v, causal, sm_scale, plan.fwd, interpret, window
    )
    return out


def _flash_fwd(q, k, v, causal, sm_scale, plan, interpret, window):
    out, lse = _flash_fwd_call(
        q, k, v, causal, sm_scale, plan.fwd, interpret, window
    )
    # named HERE, on the values the backward rule reads: a name on the
    # caller's copy of ``out`` saves an array and still replays the
    # kernel for ``lse`` (PERF.md, PR 29)
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    # lane-dense [B, H, T] as the kernel wrote it: 1 MB a layer at the
    # cells' shape for its forward-to-backward lifetime
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


# The backward keeps a batch-head's dQ sum and its output block whole
# in VMEM: ``[T_q, d]`` in float32, and twice in the activations' dtype
# (Pallas double-buffers an output block), a row of either as wide as
# a register's lanes at least — beside what the default scope already
# holds (the resident and walked blocks, the tiles).  Of a v5e's
# 128 MiB:
_VMEM_SCOPE = 16 << 20          # Mosaic's default scoped limit
_VMEM_MOST = 80 << 20           # the most a backward call may ask for


def _bwd_vmem_limit(t_q, head_dim, dtype) -> int:
    """The scoped VMEM limit a backward call asks for, in bytes."""
    import numpy as np

    row = max(head_dim, _LANES)
    return _VMEM_SCOPE + t_q * row * (4 + 2 * np.dtype(dtype).itemsize)


def _flash_bwd_call(
    q, k, v, g, lse, delta, causal, sm_scale, plan, interpret, window=None,
):
    """The backward kernel (``plan.bwd``) against EXPLICIT (lse,
    delta) residuals (fp32 [B,H,T]).  Factored out of ``_flash_bwd``
    so ring attention can run the same kernel per visiting KV block
    with the GLOBAL logsumexp/delta (the standard ring-attention
    backward)."""
    tiles = plan.bwd
    b, h, t, t_k, d = _flash_dims(q, k, False, tiles)
    vmem_limit = _bwd_vmem_limit(t, d, q.dtype)
    if vmem_limit > _VMEM_MOST:
        raise ValueError(
            f"flash backward keeps a batch-head's dQ [{t}, {d}] in VMEM: "
            f"{vmem_limit} bytes with its block, over {_VMEM_MOST} — shard "
            f"the sequence (ring attention, sp > 1)"
        )
    qs = q.reshape(b * h, t, d)
    ks = k.reshape(b * h, t_k, d)
    vs = v.reshape(b * h, t_k, d)
    dos = g.reshape(b * h, t, d)
    lse = lse.reshape(b * h, 1, t)
    delta = delta.reshape(b * h, 1, t)
    vma = jax.typeof(qs).vma

    rows, major, sub = tiles
    steps, band = _walked_axis(t_k, t, tiles, False, window)
    walked = _walked_index(causal, tiles, False, t // major, window)
    q_spec = pl.BlockSpec(
        (1, major, d), lambda i, kj, qi: (i, walked(kj, qi), 0)
    )
    k_spec = pl.BlockSpec((1, rows, d), lambda i, kj, qi: (i, kj, 0))
    r_spec = pl.BlockSpec(
        (1, 1, major), lambda i, kj, qi: (i, 0, walked(kj, qi))
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, sm_scale=sm_scale, causal=causal, sub=sub,
            **band,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((b * h, t_k, d), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((b * h, t_k, d), v.dtype, vma=vma),
        ),
        grid=(b * h, t_k // rows, steps),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=(
            # one block a batch-head: written at its last cell
            pl.BlockSpec((1, t, d), lambda i, kj, qi: (i, 0, 0)),
            k_spec, k_spec,
        ),
        scratch_shapes=[
            pltpu.VMEM((t, d), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
        # the kv dim carries the dQ scratch, the q dim dK's and dV's
        compiler_params=_SEM(
            "PARALLEL", "ARBITRARY", "ARBITRARY", vmem_limit_bytes=vmem_limit
        ),
        interpret=interpret,
    )(qs, ks, vs, dos, lse, delta)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _flash_bwd(causal, sm_scale, plan, interpret, window, res, g):
    q, k, v, out, lse = res
    # delta_i = rowsum(dO * O): the softmax-jacobian correction term,
    # [B, H, T] like the logsumexp
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return _flash_bwd_call(
        q, k, v, g, lse, delta, causal, sm_scale, plan, interpret, window,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_tpu(
    q, k, v, *, causal=True, sm_scale=None, block_q=None, block_k=None,
    bwd_block_q=None, bwd_block_k=None, interpret=False, window=None,
):
    """Fused flash attention, fully differentiable (custom_vjp with
    one Pallas backward kernel for dQ, dK and dV against the logsumexp
    residual: ``_flash_bwd_kernel``).  q,k,v: [B, H, T, D].  The
    kernels' tiles come from the shapes (``_flash_tiles``); explicit
    blocks are for tests and sweeps and mean un-subdivided blocks of
    that size — T (and T_k) must then be divisible by them.  ``interpret=True`` runs
    the kernels in the Pallas interpreter (any backend; how the tests
    exercise them).  ``window`` (causal only): a query sees itself
    and the ``window - 1`` keys before it; one no query's reach falls
    short of (``window >= T``) is the plain causal call.  The window
    calls stand under a name of their own in a compiled text
    (``_flash_window_jit``; the plain ones under ``_flash_jit``):
    their kernels compute a band, not a triangle, and whoever counts
    a call's operations has to know which (docs/OBSERVABILITY.md)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    t, t_k = q.shape[2], k.shape[2]
    window = _binding_window(window, t, causal)
    explicit = block_q or block_k or bwd_block_q or bwd_block_k
    plan = None if explicit else _flash_tiles(
        t, t_k, q.shape[3], q.dtype, window
    )
    if plan is None:
        if not (explicit or interpret):
            # a length no aligned block divides is rejected HERE with
            # an actionable error, not deep in Mosaic lowering (e.g. a
            # T_loc=68 ring shard; ADVICE r2)
            raise ValueError(
                f"flash kernel needs aligned sequence blocks; "
                f"T_q={t}, T_k={t_k} have none (pad "
                f"the sequence to a multiple of 16 — of 256 beyond "
                f"1024 — or use mha_reference / flash_attention() "
                f"which falls back to dense)"
            )
        # un-subdivided blocks: the caller's, or the whole axes — the
        # interpreter has no Mosaic alignment constraint, which keeps
        # ragged lengths runnable for off-TPU testing
        bq, bk = min(block_q or t, t), min(block_k or t_k, t_k)
        gq, gk = min(bwd_block_q or bq, t), min(bwd_block_k or bk, t_k)
        plan = FlashPlan(
            fwd=FlashTiles(bq, bk, bk), bwd=FlashTiles(gk, gq, gq)
        )
    if window is not None:
        return _flash_window_jit(
            q, k, v, causal, sm_scale, plan, interpret, window
        )
    return _flash_jit(q, k, v, causal, sm_scale, plan, interpret)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _flash_jit(q, k, v, causal, sm_scale, plan, interpret):
    return _flash(q, k, v, causal, sm_scale, plan, interpret)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _flash_window_jit(q, k, v, causal, sm_scale, plan, interpret, window):
    return _flash(q, k, v, causal, sm_scale, plan, interpret, window)


def _binding_window(window, t_q: int, causal: bool) -> int | None:
    """``window`` where it hides a key from some query, else None: the
    furthest pair a causal mask shows is ``t_q - 1`` apart."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(
            f"a window ({window}) is a causal mask's second edge: it "
            f"needs causal=True and at least the query itself"
        )
    return int(window) if window < t_q else None


def _auto_block(t: int, dtype=None) -> int | None:
    """Largest aligned block that tiles a T — the full axis up to
    1024, else the biggest power-of-two divisor from 1024 down to 256 —
    or ``None`` where there is none: callers treat that as "use the
    dense path" (ADVICE r2).  Whether an axis can go through the
    kernels at all; ``_flash_tiles`` sizes the blocks.

    Only sublane-aligned blocks qualify: the block is a Mosaic tile
    dimension, and a ragged size (e.g. a T_loc=68 ring shard) can fail
    lowering instead of falling back.  The sublane tile is dtype-keyed
    (ADVICE r3): 8 rows for fp32, 16 for bf16 — so small fp32
    sequences like T=8/24/40 stay kernel-eligible."""
    import numpy as np

    sub = 8 if dtype is not None and np.dtype(dtype).itemsize >= 4 else 16
    if t <= 1024:
        return t if t % sub == 0 else None
    for s in (1024, 512, 256):
        if t % s == 0:
            return s
    return None


# What ``_flash_tiles`` aims for, the same in both kernels: the
# sweep on a v5e at the cells' shape, 64 x 4096 x 128 bf16 (PERF.md,
# PR 32).  Score tiles of [512, 512]: at 256 columns every kernel is
# 25-40 % slower (twice the folds for the same scores), at 1024 the
# forward computes more above the diagonal than it saves; 256 rows
# lose 8-14 % to shorter products, 1024 compute a quarter of their
# scores above the diagonal (512: an eighth).  The walked axis whole
# where a block of it is at most 1 MiB (4096 x 128 bf16): K and V
# cross HBM once a head, a query block takes one grid step.  The one
# backward kernel read the same at 64 x 4096 x 128, 40 x 8192 x 256
# (PR 53's sweep) and 32 x 8192 x 64 (PERF.md, PR 54) — and TWICE the
# time wherever ``rows * major``, the scores a grid step unrolls,
# passed what 512 rows of a 1 MiB block come to: its walked block
# counts a row as VMEM holds it, a register's lanes wide at least
# (at head dim 64 the whole 8192-row axis reads 19.2 ms, 4096 rows
# 9.2).
_ROWS = 512
_SUB = 512
_MAJOR_BYTES = 1 << 20


def _fit(t: int, cap: int) -> int:
    """The whole axis where it is at most ``cap`` or has no lane-
    aligned divisor; else its largest divisor that is a multiple of
    128 and at most ``cap``."""
    if t <= cap or t % _LANES:
        return t
    return max(
        (s for s in range(_LANES, cap + 1, _LANES) if t % s == 0), default=t
    )


def _flash_tiles(t_q, t_k, head_dim, dtype, window=None) -> FlashPlan | None:
    """The two kernels' tiles for an attention shape, or ``None``
    where an axis has no aligned block (``_auto_block``: the dense
    path).  One rule for every kernel, from what the kernel can see:
    the resident block is the axis cut to the swept row count, the
    walked block as much of the other axis as the swept byte count
    holds at this head dim and dtype (the backward's at a row of a
    register's lanes at least), folded at the swept tile width.
    Under a ``window`` the walked block is at most half the window
    (and a score tile at least): a row block's band is ``rows + window
    - 1`` positions wherever it lies, and a fetched block that
    straddles its edge moves positions nothing folds (at window 1024
    and 512 rows, three ``[512, 512]`` tiles a row block, two crossed
    and one clear, where a block of 4096 would move 4096 keys to fold
    1535)."""
    import numpy as np

    if not _auto_block(t_q, dtype) or not _auto_block(t_k, dtype):
        return None
    itemsize = np.dtype(dtype).itemsize

    def tiles(t_rows, t_walk, row_width):
        most = max(_MAJOR_BYTES // (row_width * itemsize), _LANES)
        if window is not None:
            most = min(most, max(window // 2, _SUB))
        major = _fit(t_walk, most)
        return FlashTiles(_fit(t_rows, _ROWS), major, _fit(major, _SUB))

    return FlashPlan(
        fwd=tiles(t_q, t_k, head_dim),
        bwd=tiles(t_k, t_q, max(head_dim, _LANES)),
    )


def walked_tiles(t_rows, t_walk, tiles: FlashTiles, rows_are_queries,
                 causal=True, window=None) -> list[tuple[int, int, bool]]:
    """``(row block, first position of the sub-block, crossed)`` of
    every score tile one kernel folds, in grid order: the kernel's own
    walk (``_band``'s blocks, ``_walk``'s kinds) on integers."""
    rows, major, sub = tiles
    n_walked = t_walk // major
    out = []
    for r in range(t_rows // rows):
        first, last = (0, n_walked - 1) if window is None else _band(
            r, tiles, rows_are_queries, window, n_walked)
        for block in range(first, last + 1):
            for lo in range(block * major, (block + 1) * major, sub):
                clear, crossed = (
                    _sub_block_kind(lo, sub, r * rows, rows,
                                    rows_are_queries, window)
                    if causal else (True, False)
                )
                if clear or crossed:
                    out.append((r, lo, bool(crossed)))
    return out


def flash_tiles_summary(t_q, t_k, head_dim, dtype, causal=True,
                        window=None) -> dict:
    """For the run summary's ``"flash_tiles"``: per kernel (``fwd``,
    and ``bwd``: the one backward kernel) the outer tile ``[rows,
    major]``, the inner ``[rows, sub]``, the score tiles it visits (a
    batch-head) and the share of them that take the masked body —
    static, from the shapes.  Empty where ``flash_attention`` takes
    the dense path (off the TPU, or a length no block tiles)."""
    window = _binding_window(window, t_q, causal)
    plan = _flash_tiles(t_q, t_k, head_dim, dtype, window)
    if plan is None or not _on_tpu():
        return {}
    out = {}
    for kernel, tiles in plan._asdict().items():
        on_q = kernel == "fwd"
        t_rows, t_walk = (t_q, t_k) if on_q else (t_k, t_q)
        visited = walked_tiles(t_rows, t_walk, tiles, on_q, causal, window)
        out[kernel] = {
            "outer": [tiles.rows, tiles.major],
            "inner": [tiles.rows, tiles.sub],
            "tiles": len(visited),
            "masked_share": round(
                sum(crossed for _, _, crossed in visited) / len(visited), 4
            ),
        }
    return out


@functools.lru_cache(maxsize=None)
def _log_dense_choice(t: int, t_k: int, dtype: str, on_tpu: bool) -> None:
    """One log line per shape — a warning on TPU, where the dense path
    is a performance cliff.  ``lru_cache`` is the once-only latch, and
    counts the calls (``dense_choices``)."""
    logger.log(
        logging.WARNING if on_tpu else logging.INFO,
        "flash_attention: dense mha_reference for T_q=%d T_k=%d %s — %s",
        t, t_k, dtype,
        "no aligned kernel block tiles this length" if on_tpu
        else "not on TPU devices",
    )


def dense_choices() -> int:
    """How many times ``flash_attention`` has chosen the dense path
    in this process (once per trace, not per step)."""
    info = _log_dense_choice.cache_info()
    return info.hits + info.misses


def flash_attention(q, k, v, *, causal=True, window=None, sm_scale=None):
    """Dispatch: Pallas kernels on TPU (shapes permitting), reference
    math elsewhere.  Differentiable on both paths — the TPU kernel
    carries a custom_vjp with Pallas backward kernels.  A dense choice
    is never silent: ``_log_dense_choice`` names the shape and why.
    ``window`` (causal only): a query sees itself and the ``window -
    1`` keys before it, on both paths."""
    t, t_k = q.shape[2], k.shape[2]
    window = _binding_window(window, t, causal)
    on_tpu = _on_tpu()
    if on_tpu and _flash_tiles(t, t_k, q.shape[3], q.dtype):
        return flash_attention_tpu(
            q, k, v, causal=causal, sm_scale=sm_scale, window=window
        )
    _log_dense_choice(t, t_k, str(q.dtype), on_tpu)
    return mha_reference(
        q, k, v, causal=causal, sm_scale=sm_scale, window=window
    )
