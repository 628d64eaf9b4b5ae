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
- ``flash_attention`` — fused Pallas kernel (grid over heads × query
  blocks, KV streamed through VMEM, f32 accumulators in scratch) with
  the same signature; ``mha_reference`` off-TPU and at lengths no
  kernel block tiles (each such choice is logged once per shape).

Shapes follow [B, H, T, D] (head-major, the TPU-friendly layout: the
``[Tq, D] x [D, Tk]`` score matmul and ``[Tq, Tk] x [Tk, D]`` value
matmul both hit the MXU per (batch, head) grid cell).
"""

from __future__ import annotations

import functools
import logging

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


def causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray) -> jnp.ndarray:
    """[Tq, Tk] bool — query may attend to keys at <= its position."""
    return q_pos[:, None] >= k_pos[None, :]


def mha_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    k_offset: int | jnp.ndarray = 0,
    sm_scale: float | None = None,
) -> jnp.ndarray:
    """Dense softmax attention, f32 softmax.  q,k,v: [B, H, T, D].

    ``q_offset``/``k_offset`` are the *global* positions of element 0,
    so sharded callers can mask correctly on local blocks.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[2])
        k_pos = k_offset + jnp.arange(k.shape[2])
        s = jnp.where(causal_mask(q_pos, k_pos), s, NEG_INF)
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
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fold one KV block into the online-softmax carry.

    carry = (acc [B,H,Tq,D] f32, m [B,H,Tq] f32 running max,
    l [B,H,Tq] f32 running sum).  Pass ``q_pos``/``k_pos`` (global
    positions) for causal masking, or None for full attention.
    """
    acc, m, l = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk).astype(jnp.float32)
    s = s * sm_scale
    if q_pos is not None:
        mask = causal_mask(q_pos, k_pos)
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

def _block_causal_mask(q_start, k_start, block_q, block_k):
    """[block_q, block_k] bool mask from global block offsets."""
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return q_pos >= k_pos


def _recompute_p(q, k, lse, q_start, k_start, sm_scale, causal):
    """Backward-pass recompute of the normalized softmax block:
    p = exp(s − lse) with the causal mask re-applied."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale                                  # [block_q, block_k]
    p = jnp.exp(s - lse)
    if causal:
        p = jnp.where(
            _block_causal_mask(q_start, k_start, *p.shape), p, 0.0
        )
    return p


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, sm_scale, causal
):
    """One (batch*head, q-block, kv-block) grid cell.

    The kv grid dim is sequential (``ARBITRARY`` semantics), so only a
    ``block_k`` KV slice is VMEM-resident at a time — VMEM stays
    O(block_q*d + block_k*d) however long the context — and the
    online-softmax carry lives in VMEM scratch across kv steps.
    """
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # [block_q, d]
    block_q, d = q.shape
    block_k = k_ref.shape[1]
    q_start = pl.program_id(1) * block_q
    k_start = ki * block_k

    # causal: blocks fully above the diagonal fold in nothing
    needed = (not causal) or (q_start + block_q > k_start)

    @pl.when(needed)
    def _fold():
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                               # [block_q, block_k]
        if causal:
            mask = _block_causal_mask(q_start, k_start, block_q, block_k)
            s = jnp.where(mask, s, NEG_INF)
        m, l = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)
        # logsumexp per query row — the backward kernels' residual
        # (kept [block_q, 1]: Mosaic wants block dims (8k, 128k)-
        # aligned or full, and a trailing singleton is always full)
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _on_tpu() -> bool:
    """True when the devices the framework builds its meshes from
    (``parallel.mesh.default_devices``) are TPUs — read off a device,
    never off a setting alone."""
    from theanompi_tpu.parallel.mesh import default_devices

    return default_devices()[0].platform == "tpu"


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, sm_scale, causal
):
    """dK/dV for one kv block: grid (bh, kv-block, q-block), the q dim
    sequential so the [block_k, d] accumulators live in scratch."""
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    q_start = qi * block_q
    k_start = pl.program_id(1) * block_k

    # causal: a kv block whose keys are all in this q block's future
    # contributes nothing to these dK/dV rows
    needed = (not causal) or (q_start + block_q > k_start)

    @pl.when(needed)
    def _fold():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        delta = delta_ref[0]                      # [block_q, 1]
        p = _recompute_p(
            q, k, lse_ref[0], q_start, k_start, sm_scale, causal
        )
        dv_acc[...] += jax.lax.dot_general(
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # p^T @ dO
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # dO @ V^T
        ds = p * (dp - delta) * sm_scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # ds^T @ Q

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, sm_scale, causal
):
    """dQ for one q block: grid (bh, q-block, kv-block), kv sequential."""
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    q_start = pl.program_id(1) * block_q
    k_start = ki * block_k
    needed = (not causal) or (q_start + block_q > k_start)

    @pl.when(needed)
    def _fold():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        delta = delta_ref[0]                      # [block_q, 1]
        p = _recompute_p(
            q, k, lse_ref[0], q_start, k_start, sm_scale, causal
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * sm_scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # ds @ K

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_dims(q, k, block_q, block_k):
    b, h, t, d = q.shape
    t_k = k.shape[2]
    block_q = min(block_q, t)
    block_k = min(block_k, t_k)
    if t % block_q or t_k % block_k:
        raise ValueError(
            f"T={t}/T_k={t_k} not divisible by blocks ({block_q},{block_k})"
        )
    return b, h, t, t_k, d, block_q, block_k


_SEM = lambda *names: pltpu.CompilerParams(  # noqa: E731
    dimension_semantics=tuple(
        getattr(pltpu.GridDimensionSemantics, n) for n in names
    )
)


def _flash_fwd_call(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    b, h, t, t_k, d, block_q, block_k = _flash_dims(q, k, block_q, block_k)
    qs = q.reshape(b * h, t, d)
    ks = k.reshape(b * h, t_k, d)
    vs = v.reshape(b * h, t_k, d)
    vma = jax.typeof(qs).vma
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=sm_scale, causal=causal),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32, vma=vma),
        ),
        grid=(b * h, t // block_q, t_k // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        # kv dim carries the scratch accumulator -> sequential
        compiler_params=_SEM("PARALLEL", "PARALLEL", "ARBITRARY"),
        interpret=interpret,
    )(qs, ks, vs)
    return out.reshape(b, h, t, d), lse


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9)
)
def _flash(q, k, v, causal, sm_scale, block_q, block_k,
           bwd_block_q, bwd_block_k, interpret):
    out, _ = _flash_fwd_call(
        q, k, v, causal, sm_scale, block_q, block_k, interpret
    )
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
               bwd_block_q, bwd_block_k, interpret):
    out, lse = _flash_fwd_call(
        q, k, v, causal, sm_scale, block_q, block_k, interpret
    )
    # named HERE, on the values the backward rule reads: a name on the
    # caller's copy of ``out`` saves an array and still replays the
    # kernel for ``lse`` (PERF.md, PR 29)
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    # kept lane-dense, [B, H, T]: the kernel's [B*H, T, 1] tiles its
    # trailing 1 to 128 lanes on the chip, 128x the bytes for every
    # layer's forward-to-backward lifetime; ``_flash_bwd`` gives the
    # kernels their view back
    lse = checkpoint_name(lse.reshape(q.shape[:3]), FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _flash_bwd_call(
    q, k, v, g, lse, delta, causal, sm_scale, block_q, block_k, interpret
):
    """Backward kernels against EXPLICIT (lse, delta) residuals
    ([B,H,T,1] fp32).  Factored out of ``_flash_bwd`` so ring
    attention can run the same kernels per visiting KV block with the
    GLOBAL logsumexp/delta (the standard ring-attention backward)."""
    b, h, t, t_k, d, block_q, block_k = _flash_dims(q, k, block_q, block_k)
    qs = q.reshape(b * h, t, d)
    ks = k.reshape(b * h, t_k, d)
    vs = v.reshape(b * h, t_k, d)
    dos = g.reshape(b * h, t, d)
    lse = lse.reshape(b * h, t, 1)
    delta = delta.reshape(b * h, t, 1)
    vma = jax.typeof(qs).vma
    q_spec = pl.BlockSpec((1, block_q, d), lambda i, kj, qi: (i, qi, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda i, kj, qi: (i, kj, 0))
    r_spec = pl.BlockSpec((1, block_q, 1), lambda i, kj, qi: (i, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, t_k, d), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((b * h, t_k, d), v.dtype, vma=vma),
        ),
        grid=(b * h, t_k // block_k, t // block_q),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda i, kj, qi: (i, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kj, qi: (i, kj, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_SEM("PARALLEL", "PARALLEL", "ARBITRARY"),
        interpret=interpret,
    )(qs, ks, vs, dos, lse, delta)

    q_spec2 = pl.BlockSpec((1, block_q, d), lambda i, qi, kj: (i, qi, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda i, qi, kj: (i, kj, 0))
    r_spec2 = pl.BlockSpec((1, block_q, 1), lambda i, qi, kj: (i, qi, 0))
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype, vma=vma),
        grid=(b * h, t // block_q, t_k // block_k),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, qi, kj: (i, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_SEM("PARALLEL", "PARALLEL", "ARBITRARY"),
        interpret=interpret,
    )(qs, ks, vs, dos, lse, delta)
    return (
        dq.reshape(q.shape),
        dk.reshape(k.shape),
        dv.reshape(v.shape),
    )


def _flash_bwd(causal, sm_scale, block_q, block_k,
               bwd_block_q, bwd_block_k, interpret, res, g):
    q, k, v, out, lse = res
    # delta_i = rowsum(dO * O): the softmax-jacobian correction term
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32),
        axis=-1, keepdims=True,
    )                                             # [b, h, t, 1], like lse
    # backward kernels may tile differently from the forward: they
    # hold more live VMEM per cell (dK/dV accumulators + 6 operand
    # blocks), so their optimum can sit below the forward's
    return _flash_bwd_call(
        q, k, v, g, lse.reshape(q.shape[:3] + (1,)), delta,
        causal, sm_scale, bwd_block_q or block_q,
        bwd_block_k or block_k, interpret,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def _bwd_blocks_env():
    """TM_FLASH_BWD_BLOCKS="q,k" (or one number for both): override
    the BACKWARD kernel block sizes without touching the forward's
    (sweep knob; VERDICT r3 #6).  Empty/unset = backward shares the
    forward blocks."""
    import os

    v = os.environ.get("TM_FLASH_BWD_BLOCKS", "")
    if not v:
        return None, None
    parts = v.split(",")
    if len(parts) == 1:
        parts = [v, v]
    if len(parts) != 2 or not all(p.strip().isdigit() for p in parts):
        raise ValueError(
            f"TM_FLASH_BWD_BLOCKS must be 'q,k' integers (got {v!r})"
        )
    return int(parts[0]), int(parts[1])


def flash_attention_tpu(
    q, k, v, *, causal=True, sm_scale=None, block_q=None, block_k=None,
    bwd_block_q=None, bwd_block_k=None, interpret=False,
):
    """Fused flash attention, fully differentiable (custom_vjp with
    Pallas dQ and dK/dV kernels — the standard two-kernel backward with
    the logsumexp residual).  q,k,v: [B, H, T, D]; T (and T_k) must be
    divisible by the block sizes — ``flash_attention`` dispatches away
    otherwise.  ``interpret=True`` runs the kernels in the Pallas
    interpreter (any backend; how the tests exercise them)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    # default blocks: largest that tile this T.  A length no aligned
    # block divides is rejected HERE with an actionable error — the
    # old `or 256` default let `min(block, t)` clamp back to the
    # ragged t (e.g. a T_loc=68 ring shard) and fail deep in Mosaic
    # lowering instead (ADVICE r2).
    if block_q is None:
        block_q = _auto_block(q.shape[2], q.dtype)
    if block_k is None:
        block_k = _auto_block(k.shape[2], k.dtype)
    if not block_q or not block_k:
        if interpret:
            # the interpreter has no Mosaic alignment constraint; the
            # full axis is always a valid (single) block, keeping
            # ragged lengths runnable for off-TPU testing
            block_q = block_q or q.shape[2]
            block_k = block_k or k.shape[2]
        else:
            raise ValueError(
                f"flash kernel needs aligned sequence blocks; "
                f"T_q={q.shape[2]}, T_k={k.shape[2]} have none (pad "
                f"the sequence to a multiple of 16 — of 256 beyond "
                f"1024 — or use mha_reference / flash_attention() "
                f"which falls back to dense)"
            )
    # the env override resolves HERE, outside the jitted body: read
    # inside a traced function it would be captured at first trace and
    # the jit cache (keyed on the static block args, not the env)
    # would silently replay stale values across a sweep
    if bwd_block_q is None and bwd_block_k is None:
        bwd_block_q, bwd_block_k = _bwd_blocks_env()
    if bwd_block_q:
        bwd_block_q = min(int(bwd_block_q), q.shape[2])
    if bwd_block_k:
        bwd_block_k = min(int(bwd_block_k), k.shape[2])
    return _flash_jit(q, k, v, causal, sm_scale, block_q, block_k,
                      bwd_block_q, bwd_block_k, interpret)


@functools.partial(
    jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9),
)
def _flash_jit(q, k, v, causal, sm_scale, block_q, block_k,
               bwd_block_q, bwd_block_k, interpret):
    return _flash(q, k, v, causal, sm_scale, block_q, block_k,
                  bwd_block_q, bwd_block_k, interpret)


def _auto_block(t: int, dtype=None) -> int | None:
    """Largest kernel block for a T: the full axis when it fits in one
    block, else the biggest power-of-two divisor — measured on v5e
    (8L/1024d, T2048): 1024-blocks run the train step 1.5x faster
    than 256-blocks (110 vs 169 ms/step); 2048-blocks exceed VMEM.

    Only sublane-aligned blocks qualify: the block is a Mosaic tile
    dimension, and a ragged size (e.g. a T_loc=68 ring shard) can fail
    lowering instead of falling back — callers treat ``None`` as "use
    the dense path" (ADVICE r2).  The sublane tile is dtype-keyed
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


def flash_attention(q, k, v, *, causal=True, sm_scale=None):
    """Dispatch: Pallas kernels on TPU (shapes permitting), reference
    math elsewhere.  Differentiable on both paths — the TPU kernel
    carries a custom_vjp with Pallas backward kernels.  A dense choice
    is never silent: ``_log_dense_choice`` names the shape and why."""
    t, t_k = q.shape[2], k.shape[2]
    bq, bk = _auto_block(t, q.dtype), _auto_block(t_k, k.dtype)
    on_tpu = _on_tpu()
    if on_tpu and bq and bk:
        return flash_attention_tpu(
            q, k, v, causal=causal, sm_scale=sm_scale,
            block_q=bq, block_k=bk,
        )
    _log_dense_choice(t, t_k, str(q.dtype), on_tpu)
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
