"""Each token's rows summed out of a window of sorted expert rows — a
Pallas TPU kernel for the expert layer's combine under a held range
(``parallel/moe.py`` ``_sum_picks``; PERF.md, PR 44).

``rows [R, D]`` are a window of the picks' rows sorted by (expert,
token); ``tok [R]`` names each row's token, ``-1`` for a row that is no
held pick.  Wanted: ``y[t] = sum of rows[r] over tok[r] == t``, ``[N,
D]`` in fp32.  XLA forms it from ``k`` gathers of ``N`` rows, and
gathers at the memory's rate only from a source it can keep on the
chip (about 110 MB; 41 ns a row past that, real or masked).  Here the
sort does the work: inside an expert the rows of ``TILE_TOKENS``
consecutive tokens are consecutive rows, so a token tile's rows lie in
a few chunks of ``CHUNK_ROWS`` sorted rows an expert.  The kernel
walks those (tile, chunk) pairs — the *visits* of a ``SumPlan`` made
on the device from ``tok``, tile by tile — and adds ``hot @ chunk`` to
the tile's block of ``y``, ``hot [TILE_TOKENS, CHUNK_ROWS]`` the 0/1
matrix ``tok[r] == t``: a row times one is exact on the matrix unit,
and the sum runs in fp32 in sorted (expert) order, which hangs on no
tie-break.  Every row is read once a tile that has a token in its
chunk, each block of ``y`` is written once.

The grid is static: ``R / CHUNK_ROWS + (groups + 1) * N / TILE_TOKENS``
steps (``n_visits_bound``), which no routing passes — walking the
real rows in order, the pair (chunk, tile) changes when the chunk does,
or the tile, which inside one of the ``groups`` experts only rises —
and one visit a tile more, so that every block of ``y`` is written.
The steps past the last real visit repeat it, fetch nothing and add
nothing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_TOKENS = 256
CHUNK_ROWS = 128


def shapes_tile(n_rows: int, n_tokens: int, d: int) -> bool:
    """Whether the kernel's blocks divide ``rows [n_rows, d]`` and ``y
    [n_tokens, d]``."""
    return (n_rows % CHUNK_ROWS == 0 and n_tokens % TILE_TOKENS == 0
            and d % 128 == 0)


def n_visits_bound(n_rows: int, n_tokens: int, groups: int) -> int:
    """The static number of grid steps (module docstring)."""
    return n_rows // CHUNK_ROWS + (groups + 1) * (n_tokens // TILE_TOKENS)


class SumPlan(NamedTuple):
    """``tiles [V]`` / ``chunks [V]``: the token tile and the chunk of
    sorted rows of visit ``v``, tile by tile, the last real visit
    repeated up to the static ``V``; ``n_visits [1]``: how many are
    real.  All int32."""

    tiles: jax.Array
    chunks: jax.Array
    n_visits: jax.Array


def make_sum_plan(tok, n_tokens: int, groups: int) -> SumPlan:
    """The visits for ``tok [R]`` (each sorted row's token, ``-1`` for
    none; the real rows sorted by (group, token) over at most
    ``groups`` groups): every (tile, chunk) with a token of the tile in
    the chunk, and (tile, chunk 0) for every tile, so that each is
    visited."""
    n_rows = tok.shape[0]
    n_tiles, n_chunks = n_tokens // TILE_TOKENS, n_rows // CHUNK_ROWS
    tile_of = jnp.where(tok >= 0, tok // TILE_TOKENS, n_tiles)
    has = jnp.any(
        tile_of.reshape(n_chunks, CHUNK_ROWS, 1)
        == jnp.arange(n_tiles, dtype=jnp.int32), axis=1,
    ).T                                                 # [tiles, chunks]
    has = (has | (jnp.arange(n_chunks) == 0)).astype(jnp.int32)
    ends = jnp.cumsum(jnp.sum(has, axis=1))
    n_visits = ends[-1]
    v = jnp.minimum(
        jnp.arange(n_visits_bound(n_rows, n_tokens, groups),
                   dtype=jnp.int32),
        n_visits - 1,
    )
    # the tile of visit v: how many tiles' visits end at or before v;
    # its chunk: the tile's (v - first visit of the tile)-th
    tiles = jnp.sum(v[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    nth = v - (ends - jnp.sum(has, axis=1))[tiles]
    chunks = jnp.sum(jnp.cumsum(has, axis=1)[tiles] <= nth[:, None], axis=1,
                     dtype=jnp.int32)
    return SumPlan(tiles=tiles, chunks=chunks,
                   n_visits=n_visits[None].astype(jnp.int32))


def _kernel(tiles, chunks, n_visits, tok_ref, rows_ref, out_ref):
    v = pl.program_id(0)
    tile = tiles[v]

    @pl.when((v == 0) | (tiles[jnp.maximum(v - 1, 0)] != tile))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(v < n_visits[0])
    def _():
        rows = rows_ref[...]
        token = tile * TILE_TOKENS + lax.broadcasted_iota(
            jnp.int32, (TILE_TOKENS, CHUNK_ROWS), 0)
        hot = jnp.where(token == tok_ref[...], 1.0, 0.0).astype(rows.dtype)
        out_ref[...] += jnp.dot(
            hot, rows, preferred_element_type=jnp.float32,
            precision=(lax.Precision.HIGHEST
                       if rows.dtype == jnp.float32 else None),
        )


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _sum_jit(rows, tok, n_tokens, groups, interpret):
    # (plan and kernel in ONE jitted function: traced once a process
    # and lowered once a program for all the layer calls' sums)
    n_rows, d = rows.shape
    plan = make_sum_plan(tok, n_tokens, groups)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(
            (n_tokens, d), jnp.float32, vma=jax.typeof(rows).vma
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(plan.tiles.shape[0],),
            in_specs=[
                pl.BlockSpec((None, 1, CHUNK_ROWS),
                             lambda v, tiles, chunks, nv: (chunks[v], 0, 0)),
                pl.BlockSpec((CHUNK_ROWS, d),
                             lambda v, tiles, chunks, nv: (chunks[v], 0)),
            ],
            out_specs=pl.BlockSpec(
                (TILE_TOKENS, d), lambda v, tiles, chunks, nv: (tiles[v], 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="held-rows-sum",
    )(*plan, tok.reshape(n_rows // CHUNK_ROWS, 1, CHUNK_ROWS), rows)


def held_rows_sum(rows, tok, n_tokens: int, groups: int, *,
                  interpret: bool = False):
    """``y [n_tokens, D]`` fp32, ``y[t]`` the sum of the ``rows [R, D]``
    with ``tok [R] == t`` (module docstring).  ``interpret=True`` runs
    the kernel in the Pallas interpreter (how the CPU tests do)."""
    return _sum_jit(rows, tok, n_tokens, groups, interpret)
