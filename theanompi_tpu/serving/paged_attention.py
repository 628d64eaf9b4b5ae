"""Fused gather+attend Pallas kernel for paged KV-cache decode
(serving speed-of-light, ROADMAP item 1b).

The jnp gather path (``PagedLlamaDecoder._gather_kv``) materializes
the block-table read as a ``[S, Hkv, MB*bs, hd]`` tensor per layer —
PR 6's decode-cost attribution (``paged_attend_frac``, from a trace
of the CPU mesh) puts most of decode time there, and on
real hardware that tensor is an HBM round trip: the pool rows are
READ, WRITTEN back as the gathered copy, and READ again by the
attention matmuls (~3x the padded window's bytes).  This kernel fuses
the walk: the grid is (slot, kv-head, block), and the K/V pool
BlockSpecs pick each step's block THROUGH THE BLOCK TABLE in their
index maps, so the Pallas pipeline DMAs the slot's blocks from the
HBM pools into VMEM one grid step ahead of the body.  The body
appends the block to a contiguous VMEM window and, on the slot's last
block, computes the attention against it in place — the gathered
history never exists in HBM.

Why BlockSpec-driven and not hand-issued DMAs: Mosaic presents an HBM
operand whose minor dim is under 128 lanes (hd 64 — the Llama proxy)
as a memref padded to 128, and refuses any ``memref_slice`` of it
("Slice shape along dimension 3 must be aligned to tiling (128), but
is 64" — v5e compiler, jax 0.9.0).  A pipelined block whose trailing
dims are the full ``(bs, hd)`` is legal at every head dim.

Exactness contract: the kernel mirrors the gather oracle's op
sequence — same contraction, the score rounded to the pool dtype
before the ``astype(f32) * hd**-0.5`` scale (what the oracle's
compute-dtype einsum does), same ``where(pos-mask, ·, NEG_INF)`` +
``jax.nn.softmax``, PV as mult+reduce — so for fp32 pools the outputs
are BITWISE equal to the jitted gather path in the interpreter
(tests/test_paged_attention.py asserts exact equality across
block-boundary, ragged-length and trash-padding cases).  For bf16
pools the score matmul accumulates in f32 (Mosaic requires a 32-bit
accumulator) and the PV product is formed in f32: the same values up
to the last bf16 rounding, not bitwise.  ``interpret=True`` runs the
kernel through the Pallas interpreter (any backend; what the CPU
tests pass explicitly); the default compiles through Mosaic and so
needs a TPU.

Shapes (all per tp shard — the decoder calls this inside shard_map,
so ``hkv``/``rep`` are the LOCAL head counts):

- ``q``      ``[S, Q, Hkv, rep, hd]`` — Q query rows per slot (1 for
  plain decode, ``k`` for a speculative verify step);
- ``k_pool``/``v_pool`` ``[n_blocks + 1, Hkv, bs, hd]`` (last row =
  trash block);
- ``tables`` ``[S, MB]`` int32 (trash-padded past the owned prefix);
- ``pos``    ``[S, Q]`` int32 — row (s, q) attends positions
  ``<= pos[s, q]``.

Table entries and positions are SCALAR-PREFETCH arguments
(``PrefetchScalarGridSpec``): the block ids must be known before the
body runs, to program the pipeline's DMAs.  Trash-padded table
entries are walked too — their positions sit past every ``pos``, so
the mask kills them (the same branch-free discipline as the gather
path).

VMEM per grid cell: the K and V windows, ``2 * MB * bs *
max(hd, 128) * itemsize`` (lanes pad to 128: 1 MiB at ctx 2048 in
bf16, 4 MiB at ctx 8192), plus two pipeline buffers per pool block.
Longer contexts want the softmax split over the window, which changes
its association and therefore the exactness bar (documented, not
built).  What ``memory_analysis()`` shows is HBM, not VMEM: at hd 64
the chip's preferred layout of a ``[.., bs, 64]`` pool puts the BLOCK
axis minor, and XLA relayouts both pools in front of the kernel (a
standalone call at 1025 blocks x 8 heads, fp32: 67 MB of temporaries,
none at hd 128) — a cost of the pool layout to measure on the chip,
not of this kernel's VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops.attention import NEG_INF

IMPLS = ("gather", "pallas")


def _paged_attend_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref,
                         o_ref, ks, vs, *, bs: int, nq: int,
                         scale: float):
    """One (slot, kv-head, block) grid step: the pipeline has fetched
    this step's block (chosen by the block table in the pool
    index_map); append it to the contiguous VMEM window, and on the
    last block attend each of the ``nq`` query rows against the whole
    window under its own position mask."""
    s = pl.program_id(0)
    b = pl.program_id(2)
    del tables_ref  # consumed by the pool index_map

    row = pl.multiple_of(b * bs, bs)
    ks[pl.ds(row, bs), :] = k_ref[0, 0]
    vs[pl.ds(row, bs), :] = v_ref[0, 0]

    @pl.when(b == pl.num_programs(2) - 1)
    def _attend():
        kg = ks[...]                                 # [MB*bs, hd]
        vg = vs[...]
        # The gather oracle's op sequence (decoder `paged_attend`
        # scope): the score contraction over ALL query rows at once
        # (so the matmul's row count matches the oracle's per-(slot,
        # head) row group — XLA's matvec lowering is row-count
        # sensitive), rounded to the compute dtype as the oracle's
        # einsum output is, f32 cast, scale, per-row position mask,
        # softmax, then prob-weighted V as mult+reduce (NOT a
        # dot_general): reduce lowering is association-stable across
        # batching, matmul is not.  The fp32-bitwise-equality contract
        # with the gather path lives here; decoder._paged_attend
        # documents the other half.
        rep = q_ref.shape[3]
        q2 = q_ref[0, :, 0].reshape(nq * rep, -1)    # [nq*rep, hd]
        sc = jax.lax.dot_general(
            q2, kg, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(q2.dtype).astype(jnp.float32) * scale
        t_idx = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        pos_col = jnp.concatenate(
            [jnp.full((rep, 1), pos_ref[s, j], jnp.int32)
             for j in range(nq)], axis=0,
        )                                            # [nq*rep, 1]
        sc = jnp.where(t_idx <= pos_col, sc, NEG_INF)
        probs = jax.nn.softmax(sc, axis=-1)
        # the product in f32: Mosaic has no [r, T] -> [r, T, 1]
        # reshape for 16-bit vectors, and for fp32 pools both casts
        # are the identity
        o = jnp.sum(
            probs.astype(vg.dtype).astype(jnp.float32)[..., None]
            * vg.astype(jnp.float32)[None, :, :],
            axis=-2,
        )                                            # [nq*rep, hd]
        o_ref[0, :, 0] = o.reshape(nq, rep, -1).astype(o_ref.dtype)


def paged_attend(q, k_pool, v_pool, tables, pos, *,
                 interpret: bool = False):
    """Fused block-table attention: ``q`` [S, Q, Hkv, rep, hd] against
    the paged pools through ``tables`` [S, MB] with per-row position
    masks ``pos`` [S, Q].  Returns [S, Q, Hkv, rep, hd] in the pool
    dtype — bitwise-equal to the decoder's gather path for fp32."""
    s, nq, hkv, rep, hd = q.shape
    nb1, hkv_p, bs, hd_p = k_pool.shape
    if (hkv, hd) != (hkv_p, hd_p) or k_pool.shape != v_pool.shape:
        raise ValueError(
            f"q {q.shape} does not match the pools "
            f"{k_pool.shape} / {v_pool.shape}"
        )
    mb = tables.shape[1]
    if tables.shape != (s, mb) or pos.shape != (s, nq):
        raise ValueError(
            f"tables {tables.shape} / pos {pos.shape} do not match "
            f"q {q.shape}"
        )
    t_pad = mb * bs

    q_spec = pl.BlockSpec(
        (1, nq, 1, rep, hd), lambda i, j, b, tb, ps: (i, 0, j, 0, 0)
    )
    # the block-table walk: grid step (i, j, b) reads pool block
    # tables[i, b], kv-head j
    pool_spec = pl.BlockSpec(
        (1, 1, bs, hd), lambda i, j, b, tb, ps: (tb[i, b], j, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # tables, pos
        grid=(s, hkv, mb),
        in_specs=[q_spec, pool_spec, pool_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((t_pad, hd), k_pool.dtype),
            pltpu.VMEM((t_pad, hd), v_pool.dtype),
        ],
    )
    kernel = functools.partial(
        _paged_attend_kernel, bs=bs, nq=nq, scale=hd ** -0.5
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, v_pool.dtype),
        # the block axis carries the window scratch -> sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(tables, pos, q, k_pool, v_pool)
