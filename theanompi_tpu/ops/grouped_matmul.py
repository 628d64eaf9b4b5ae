"""Grouped matrix products over rows sorted by group: three Pallas TPU
kernels and the one tile plan they share.

The dropless expert layer (``parallel/moe.py``) multiplies ``M`` rows,
sorted by expert, with that expert's weight: ``out[r] = lhs[r] @
rhs[group of r]``.  Eleven such products a step (three forward, two
replayed under the layer's remat, three input-gradient, three
weight-gradient) ran inside XLA's own rewrite of ``lax.ragged_dot`` at
55 % of the matrix unit's rate (PERF.md, PR 31).  Here:

- ``make_tile_plan`` — from ``group_sizes`` alone, ONCE per layer
  call: the rows are cut into tiles of ``block_rows``; a VISIT is one
  (row tile, group) pair with rows in common.  A tile that straddles
  a group boundary is visited once per group, so there are at most
  ``M / block_rows + E - 1`` visits — the kernels' static grid; an
  empty group gets one visit too (its weight gradient has to be
  written: zeros).  Visits are ordered by group and then by tile, so
  both the tile and the group of consecutive visits never decrease:
  an output tile is revisited only consecutively (it stays in VMEM)
  and a weight block is re-read only when the group changes.
- (a) ``lhs [M, C] x rhs [E, C, O] -> [M, O]`` and (b) the same
  against ``rhs [E, O, C]`` read transposed in the kernel — the input
  gradient, with no transposed copy of the weights in HBM — are one
  kernel body (``_rows_kernel``): fp32 accumulation over the
  contracting tiles, and a visit writes only its group's rows: one
  that covers its whole tile is one unmasked product, one that holds
  part of it computes only the 128-row blocks with rows of its group
  (a straddling tile costs little more than once, not twice).
- (c) ``lhs [M, K]^T dout [M, N] -> [E, K, N]`` per group, the weight
  gradient (``_weights_kernel``): contracts over the rows of each
  visit into an fp32 ``[tk, tn]`` accumulator that is zeroed when the
  group changes and written when it ends; the rows of the other group
  in a straddling tile are zeroed in the narrower operand.  No
  ``[K, M]`` copy of the activations is made.
- ``grouped_matmul`` — the differentiable product: one
  ``jax.custom_vjp`` whose backward calls (b) and (c) with the SAME
  plan.  Outputs carry their inputs' ``vma`` (the checked
  ``shard_map`` step).  Each kernel's ``name`` holds ``ragged-dot``,
  which is how the benchmark's readers and
  ``tests/test_chip_compile.py`` find the products in a compiled text
  (docs/OBSERVABILITY.md).

Tiles are a function of shapes and dtype (``tile_rows``,
``_sub_rows``, ``_rows_tiles``, ``_weights_tiles``; measured on the
v5e, PERF.md PR 31), as ``ops.attention._auto_block`` is for flash —
not a knob.
``sum(group_sizes)`` equals ``M`` (every row has a group), as it
does for the expert layer's ``k * N`` picks — or, under a plan made
with ``prefix=True``, is at most ``M``: the groups are a held range of
the experts and ``M`` the layer's static bound on their sorted rows
(``moe.held_rows_bound``; all ``k * N`` past it).  The kernels' time
follows the rows held, not ``M``; (a) and (b) give zeros past them,
(c) never reads past them; the kernels' own text is the same for both.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ``jax.ad_checkpoint.checkpoint_name`` of the plan's arrays: a
# ``jax.checkpoint`` that saves it replays the layer without
# rebuilding the plan (a few KB; ``Llama.remat_saves``)
TILE_PLAN_RESIDUAL = "moe_tile_plan"


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["group_offsets", "group_ids", "tile_ids", "n_visits"],
    meta_fields=["block_rows", "prefix"],
)
@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Which (row tile, group) pairs the kernels visit, in order.

    ``group_offsets [E+1]``: first row of each group, then ``M``;
    ``group_ids [V]`` / ``tile_ids [V]``: the group and the row tile
    of visit ``v``, the last real visit repeated up to the static
    ``V = M / block_rows + E - 1``; ``n_visits [1]``: how many are
    real.  All int32.  ``prefix``: the groups need not cover all the
    rows, only the first ``group_offsets[-1]`` of them (static: it
    decides whether the products mask their outputs)."""

    group_offsets: jax.Array
    group_ids: jax.Array
    tile_ids: jax.Array
    n_visits: jax.Array
    block_rows: int
    prefix: bool = False


def make_tile_plan(group_sizes, n_rows: int, block_rows: int, *,
                   prefix: bool = False) -> TilePlan:
    """The visits for ``n_rows`` rows sorted into groups of
    ``group_sizes [E]`` (int32, summing to ``n_rows``), cut into row
    tiles of ``block_rows`` (which divides ``n_rows``).

    ``prefix``: the sizes may sum to LESS than ``n_rows`` — the groups
    held here are the first of a longer sorted list, and the rows past
    ``sum(group_sizes)`` belong to none of them.  The visits are the
    same function of the sizes: they end with the last group's last
    tile, the static grid's remaining steps repeat that visit (they
    compute nothing and, their blocks' indices unchanged, fetch
    nothing), and no tile past the prefix is visited at all.  What
    the products give for those rows is ``grouped_matmul``'s to say."""
    if n_rows % block_rows:
        raise ValueError(
            f"{n_rows} rows do not divide into tiles of {block_rows}"
        )
    e = group_sizes.shape[0]
    tiles = n_rows // block_rows
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    # a group's tiles: from the one holding its first row to the one
    # holding its last; an empty group visits one tile, the one its
    # neighbours meet in, and computes nothing there
    first = jnp.minimum(starts // block_rows, tiles - 1)
    span = jnp.where(sizes > 0, -(-ends // block_rows) - first, 1)
    visit_ends = jnp.cumsum(span)
    n_visits = visit_ends[-1]
    v = jnp.minimum(jnp.arange(tiles + e - 1, dtype=jnp.int32), n_visits - 1)
    # the group of visit v: how many groups' visits end at or before v
    gid = jnp.sum(v[:, None] >= visit_ends[None, :], axis=1, dtype=jnp.int32)
    tid = first[gid] + v - (visit_ends[gid] - span[gid])
    return TilePlan(
        group_offsets=jnp.concatenate([jnp.zeros_like(ends[:1]), ends]),
        group_ids=gid,
        tile_ids=tid.astype(jnp.int32),
        n_visits=n_visits[None],
        block_rows=block_rows,
        prefix=prefix,
    )


def _visit(plan_refs, v, block_rows):
    """``(first row of the visit's tile, its group's first row, its
    group's end, whether the visit is real and has rows)``."""
    offs, gids, tids, n_visits = plan_refs
    g = gids[v]
    start, end = offs[g], offs[g + 1]
    return tids[v] * block_rows, start, end, (v < n_visits[0]) & (end > start)


def _rows_in_group(row0, start, end, shape):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= start) & (rows < end)


def _partial_blocks(row0, start, end, block_rows, sub_rows, body):
    """A visit whose group ends or begins inside its tile: ``body(rows
    slice, first row)`` for each ``sub_rows`` block of the tile that
    holds rows of the group, and for no other — the rest of the tile
    is another visit's work."""
    for j in range(block_rows // sub_rows):
        first = row0 + j * sub_rows

        @pl.when((first < end) & (first + sub_rows > start))
        def _block(j=j, first=first):
            body(pl.ds(j * sub_rows, sub_rows), first)


def _rows_kernel(offs, gids, tids, n_visits, lhs_ref, rhs_ref, out_ref,
                 *acc, transpose_rhs, sub_rows):
    """One (output-column tile, visit, contracting tile) grid cell of
    (a) / (b): ``out[tile rows of the group] = lhs tile @ rhs[group]``."""
    v, c = pl.program_id(1), pl.program_id(2)
    n_c = pl.num_programs(2)
    block_rows = lhs_ref.shape[0]
    row0, start, end, live = _visit((offs, gids, tids, n_visits), v,
                                    block_rows)
    whole = (start <= row0) & (end >= row0 + block_rows)

    def rows(sl, first=None):
        """The product for the rows ``sl`` of the tile; ``first`` (the
        first of them) where only the group's rows may be written."""
        def store(res):
            if first is not None:
                # the other rows are another visit's: the one before
                # left them in this block, the one after fills them
                res = jnp.where(
                    _rows_in_group(first, start, end, res.shape), res,
                    out_ref[sl, :].astype(jnp.float32),
                )
            out_ref[sl, :] = res.astype(out_ref.dtype)

        res = jax.lax.dot_general(
            lhs_ref[sl, :], rhs_ref[...],
            (((1,), (1 if transpose_rhs else 0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if not acc:              # one contracting tile: no accumulator
            store(res)
            return
        acc_ref, = acc

        @pl.when(c == 0)
        def _first():
            acc_ref[sl, :] = res

        @pl.when(c > 0)
        def _rest():
            acc_ref[sl, :] += res

        @pl.when(c == n_c - 1)
        def _finish():
            store(acc_ref[sl, :])

    @pl.when(live & whole)
    def _all_rows():
        rows(slice(None))

    @pl.when(live & jnp.logical_not(whole))
    def _group_rows():
        _partial_blocks(row0, start, end, block_rows, sub_rows, rows)


def _weights_kernel(offs, gids, tids, n_visits, lhs_ref, dout_ref, out_ref,
                    acc_ref, *, sub_rows):
    """One (K tile, N tile, visit) grid cell of (c): the rows of the
    visit contracted into the group's ``[tk, tn]`` accumulator."""
    v = pl.program_id(2)
    last = pl.num_programs(2) - 1
    block_rows = lhs_ref.shape[0]
    row0, start, end, live = _visit((offs, gids, tids, n_visits), v,
                                    block_rows)
    whole = (start <= row0) & (end >= row0 + block_rows)
    g = gids[v]
    opens = (v == 0) | (gids[jnp.maximum(v - 1, 0)] != g)
    closes = (v == last) | (gids[jnp.minimum(v + 1, last)] != g)

    @pl.when(opens)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def rows(sl, first=None):
        lhs, dout = lhs_ref[sl, :], dout_ref[sl, :]
        if first is not None:
            # zero the other group's rows in the narrower operand: a
            # zero row contributes nothing to lhs^T dout
            if lhs.shape[1] <= dout.shape[1]:
                lhs = jnp.where(
                    _rows_in_group(first, start, end, lhs.shape), lhs, 0
                ).astype(lhs.dtype)
            else:
                dout = jnp.where(
                    _rows_in_group(first, start, end, dout.shape), dout, 0
                ).astype(dout.dtype)
        acc_ref[...] += jax.lax.dot_general(
            lhs, dout, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(live & whole)
    def _all_rows():
        rows(slice(None))

    @pl.when(live & jnp.logical_not(whole))
    def _group_rows():
        _partial_blocks(row0, start, end, block_rows, sub_rows, rows)

    @pl.when(closes)
    def _finish():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# -- tiles --------------------------------------------------------------
#
# Measured on the v5e at the OLMoE cell's shapes (131 072 rows, 64
# experts of 2048 x 1024, bf16; PERF.md, PR 31, has the table):
#
# - the weight block holds the WHOLE contracting dimension (a) / (b)
#   and the whole ``[K, N]`` of an expert (c) where that is 4 MB or
#   less: consecutive visits of one expert then keep the block's
#   index, so the weights cross HBM once per expert and there is no
#   accumulator pass (3.0 ms against 3.9 with the contraction halved);
# - 512 rows a tile: 256 run the matrix unit 15 % slower, 1024 double
#   what a straddling tile costs;
# - a visit that holds only part of its tile computes 128 rows at a
#   time and skips the blocks that hold none of its group (3.3 ms
#   against 3.7 for the whole tile twice; 64 rows stall (c)).
#
# The v5e gives a kernel up to 128 MiB of VMEM; these sizes keep every
# kernel's blocks (double-buffered operands and output, and (c)'s fp32
# accumulator) under 24 MB in bf16 and 48 MB in fp32.

_VMEM_LIMIT = 96 * 1024 * 1024
_LANE = 128
_BLOCK_BYTES = 4 * 1024 * 1024      # a weight block, one buffer


def _divisor_tile(n: int, most: int) -> int | None:
    """Largest multiple of 128 that divides ``n`` and is at most
    ``most`` (``n`` itself when it is smaller)."""
    if n % _LANE:
        return None
    if n <= most:
        return n
    for t in range(most - most % _LANE, 0, -_LANE):
        if n % t == 0:
            return t
    return None


def tile_rows(n_rows: int) -> int | None:
    """The row tile of a layer's plan, or ``None`` where no aligned
    tile divides the rows (the caller then keeps ``lax.ragged_dot``)."""
    for t in (512, 256, 128):
        if n_rows % t == 0:
            return t
    return None


def _sub_rows(block_rows: int) -> int:
    """The rows a visit that holds only part of its tile computes at
    a time (its tile's other rows are skipped block by block)."""
    return min(block_rows, 128)


def _rows_tiles(c: int, o: int, dtype) -> tuple[int, int] | None:
    """``(contracting tile, output-column tile)`` of (a) / (b)'s
    ``[c, o]`` weight block: the whole block where it fits
    ``_BLOCK_BYTES`` (2304 x 896 in bf16: a contraction cut in two
    changes the block's index every grid step, so the weights cross
    HBM once a VISIT and not once an expert; PERF.md, PR 41); else all
    of ``c`` up to 2048, then as much of ``o`` as keeps the block in
    ``_BLOCK_BYTES``."""
    if (c % _LANE == 0 and o % _LANE == 0
            and c * o * jnp.dtype(dtype).itemsize <= _BLOCK_BYTES):
        return c, o
    tc = _divisor_tile(c, 2048)
    if not tc:
        return None
    most = _BLOCK_BYTES // (jnp.dtype(dtype).itemsize * tc)
    to = _divisor_tile(o, max(_LANE, most))
    return (tc, to) if to else None


def _weights_tiles(k: int, n: int, dtype) -> tuple[int, int] | None:
    """``(tk, tn)`` of (c)'s output block, sized as (a)'s weight
    block: the wider dimension whole first, so the narrower operand's
    row tiles are the ones re-read."""
    if n >= k:
        tiles = _rows_tiles(n, k, dtype)
        return tiles and tiles[::-1]
    return _rows_tiles(k, n, dtype)


def shapes_tile(n_rows: int, k: int, n: int, dtype) -> bool:
    """Whether the three kernels can run ``[n_rows, k] x [E, k, n]``
    and its two gradients."""
    return bool(
        tile_rows(n_rows)
        and _rows_tiles(k, n, dtype) and _rows_tiles(n, k, dtype)
        and _weights_tiles(k, n, dtype)
    )


def same_vma(*trees):
    """Every array of ``trees`` typed varying over the union of their
    varying mesh axes (the checked ``shard_map``): a kernel's operands
    and outputs share one type.  Done OUTSIDE the ``custom_vjp``, so
    the cast's own transpose sums an invariant operand's gradient
    over the axes it was made varying on (rows replicated over the
    ``model`` axis against weights sharded over it)."""
    leaves, treedef = jax.tree.flatten(trees)
    vma = frozenset().union(*(jax.typeof(a).vma for a in leaves))
    return jax.tree.unflatten(treedef, [
        jax.lax.pcast(a, tuple(sorted(vma - jax.typeof(a).vma)),
                      to="varying")
        if vma - jax.typeof(a).vma else a
        for a in leaves
    ])


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT,
)


def _plan_arrays(plan):
    return (plan.group_offsets, plan.group_ids, plan.tile_ids,
            plan.n_visits)


def _rows_call(lhs, rhs, plan, *, transpose_rhs, tiles=None,
               sub_rows=None, interpret=False):
    """(a) ``lhs [M, C] x rhs [E, C, O]``, or with ``transpose_rhs``
    (b) ``lhs [M, C] x rhs [E, O, C]^T``; ``-> [M, O]`` in lhs's dtype."""
    m, c = lhs.shape
    o = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = plan.block_rows
    tc, to = tiles or _rows_tiles(c, o, lhs.dtype)
    n_c = c // tc
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, to, tc), lambda j, v, cc, offs, gid, tid, nv:
            (gid[v], j, cc))
    else:
        rhs_spec = pl.BlockSpec(
            (None, tc, to), lambda j, v, cc, offs, gid, tid, nv:
            (gid[v], cc, j))
    return pl.pallas_call(
        functools.partial(
            _rows_kernel, transpose_rhs=transpose_rhs,
            sub_rows=sub_rows or _sub_rows(tm),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (m, o), lhs.dtype, vma=jax.typeof(lhs).vma
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(o // to, plan.group_ids.shape[0], n_c),
            in_specs=[
                pl.BlockSpec(
                    (tm, tc), lambda j, v, cc, offs, gid, tid, nv:
                    (tid[v], cc)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, to), lambda j, v, cc, offs, gid, tid, nv: (tid[v], j)),
            scratch_shapes=(
                [pltpu.VMEM((tm, to), jnp.float32)] if n_c > 1 else []
            ),
        ),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ragged-dot-dlhs" if transpose_rhs else "ragged-dot-fwd",
    )(*_plan_arrays(plan), lhs, rhs)


def _weights_call(lhs, dout, plan, out_dtype, *, tiles=None,
                  sub_rows=None, interpret=False):
    """(c) per group ``lhs [M, K]^T dout [M, N] -> [E, K, N]``."""
    k, n = lhs.shape[1], dout.shape[1]
    e = plan.group_offsets.shape[0] - 1
    tm = plan.block_rows
    tk, tn = tiles or _weights_tiles(k, n, lhs.dtype)
    return pl.pallas_call(
        functools.partial(
            _weights_kernel, sub_rows=sub_rows or _sub_rows(tm)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (e, k, n), out_dtype, vma=jax.typeof(lhs).vma
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, plan.group_ids.shape[0]),
            in_specs=[
                pl.BlockSpec(
                    (tm, tk), lambda i, j, v, offs, gid, tid, nv:
                    (tid[v], i)),
                pl.BlockSpec(
                    (tm, tn), lambda i, j, v, offs, gid, tid, nv:
                    (tid[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda i, j, v, offs, gid, tid, nv:
                (gid[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ragged-dot-drhs",
    )(*_plan_arrays(plan), lhs, dout)


def held_rows(out, plan):
    """``out [M, .]`` of (a) / (b) under a prefix plan: the kernels
    never visit a tile past the groups' rows and write only a group's
    rows of the tile the prefix ends in, so what lies past it is
    whatever the buffer held; zeros, by a select the consumer fuses
    (a product of a NaN there would not do).  (c) needs none: a row
    past the prefix is in no visit's group."""
    if not plan.prefix:
        return out
    rows = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    return jnp.where(rows < plan.group_offsets[-1], out,
                     jnp.zeros((), out.dtype))


def _product(lhs, rhs, plan, interpret, raw):
    out = _rows_call(lhs, rhs, plan, transpose_rhs=False, interpret=interpret)
    return out if raw else held_rows(out, plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(lhs, rhs, plan, interpret, raw):
    return _product(lhs, rhs, plan, interpret, raw)


def _grouped_fwd(lhs, rhs, plan, interpret, raw):
    return _product(lhs, rhs, plan, interpret, raw), (lhs, rhs, plan)


def _grouped_bwd(interpret, raw, res, g):
    lhs, rhs, plan = res
    return (
        held_rows(
            _rows_call(g, rhs, plan, transpose_rhs=True,
                       interpret=interpret),
            plan,
        ),
        _weights_call(lhs, g, plan, rhs.dtype, interpret=interpret),
        None,
    )


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _grouped_jit(lhs, rhs, plan, interpret, raw):
    return _grouped(lhs, rhs, plan, interpret, raw)


def grouped_matmul(lhs, rhs, plan: TilePlan, *, interpret: bool = False,
                   raw: bool = False):
    """``out[r] = lhs[r] @ rhs[group of r]`` for ``lhs [M, K]`` sorted
    by group, ``rhs [E, K, N]`` and the layer's ``plan``; ``[M, N]`` in
    ``lhs``'s dtype, fp32 accumulation.  Differentiable in ``lhs`` and
    ``rhs``; the backward reuses ``plan``.  ``interpret=True`` runs
    the kernels in the Pallas interpreter (how the CPU tests do).
    ``raw``: under a prefix plan the rows past the prefix stay whatever
    the kernel's buffer held, for a caller that reads the product
    through ``held_rows`` itself (one that keeps the product for its
    backward pass keeps the kernel's own output that way, not a second
    copy behind the select)."""
    return _grouped_jit(
        *same_vma(lhs, rhs.astype(lhs.dtype), plan), interpret, raw
    )
