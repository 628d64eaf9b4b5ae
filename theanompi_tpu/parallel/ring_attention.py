"""Ring attention: sequence/context parallelism over the ``seq`` mesh
axis (new-framework scope — SURVEY §2.2 row "Ring attention", absent
upstream; the TPU-native answer to long-context training).

Each device holds a contiguous sequence shard of Q, K, V.  The KV pair
rotates around the ring (one ``lax.ppermute`` neighbor hop per step —
nearest-neighbour ICI traffic, the pattern the TPU torus is built
for), while every device folds the visiting KV block into its local
queries' online-softmax carry (``ops.attention.block_attn_update`` —
the same accumulator flash attention uses, so the distributed result
equals single-device attention in fp32).

XLA overlaps the next ppermute with the current block's compute
(they're independent in the dataflow graph), which is the
communication-hiding property the ring schedule exists for
(Liu et al. 2023, Ring Attention with Blockwise Transformers).

Causality: block pairs are masked by *global* positions.  A fully
future KV block still costs one rotation hop (the ring must complete)
but its scores are masked; the per-block einsums remain static-shaped,
which is what keeps the whole loop one compiled XLA program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops.attention import (
    _flash_bwd_call,
    _flash_fwd_call,
    _flash_tiles,
    _on_tpu,
    block_attn_finish,
    block_attn_init,
    block_attn_update,
)


def _rep(x, r: int):
    return jnp.repeat(x, r, axis=1) if r != 1 else x


def _unrep(dx, r: int):
    """Fold full-head grads back onto compact GQA heads (transpose of
    ``_rep``: the repeated groups' grads sum)."""
    if r == 1:
        return dx
    b, hr, t, d = dx.shape
    return dx.reshape(b, hr // r, r, t, d).sum(axis=2)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q, k, v, axis_name, causal, sm_scale, kv_rep, plan,
                interpret):
    o, _ = _ring_flash_fwd(
        q, k, v, axis_name, causal, sm_scale, kv_rep, plan, interpret
    )
    return o


def _hop_visible(my_idx, src, causal):
    """Whether the block that started at ``src`` is (at all) visible
    to this device's queries under causality."""
    return jnp.logical_or(jnp.asarray(not causal), src <= my_idx)


def _ring_flash_fwd(q, k, v, axis_name, causal, sm_scale, kv_rep, plan,
                    interpret):
    """Per-hop Pallas flash fwd + online logsumexp merge.

    The hop triad under causality: the diagonal block — which is
    STATICALLY hop 0 (src == my_idx iff step == 0) — is causal flash,
    earlier blocks are full flash, future blocks are
    computed-but-masked (SPMD: every device must run the same
    program; the dense path wastes the same flops).
    """
    b, h, t_loc, d = q.shape
    s_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % s_size) for i in range(s_size)]

    m = jnp.full((b, h, t_loc, 1), -jnp.inf, jnp.float32)
    num = jnp.zeros((b, h, t_loc, d), jnp.float32)
    den = jnp.zeros((b, h, t_loc, 1), jnp.float32)
    k_cur, v_cur = k, v
    for step in range(s_size):
        src = (my_idx - step) % s_size
        visible = _hop_visible(my_idx, src, causal)
        o_i, lse_i = _flash_fwd_call(
            q, _rep(k_cur, kv_rep), _rep(v_cur, kv_rep),
            causal and step == 0, sm_scale, plan.fwd, interpret,
        )
        lse_i = lse_i[..., None]
        # merge: future blocks weigh 0; exp(m - m_new) is 0 on the
        # first (always-visible diagonal) fold, so no -inf arithmetic
        lse_eff = jnp.where(visible, lse_i, -jnp.inf)
        m_new = jnp.maximum(m, lse_eff)
        alpha = jnp.exp(m - m_new)
        w = jnp.where(visible, jnp.exp(lse_i - m_new), 0.0)
        num = num * alpha + w * o_i.astype(jnp.float32)
        den = den * alpha + w
        m = m_new
        if step != s_size - 1:
            k_cur, v_cur = jax.tree.map(
                lambda x: lax.ppermute(x, axis_name, perm),
                (k_cur, v_cur),
            )
    o = (num / jnp.maximum(den, 1e-30)).astype(q.dtype)
    lse_global = m + jnp.log(jnp.maximum(den, 1e-30))
    return o, (q, k, v, o, lse_global)


def _ring_flash_bwd(axis_name, causal, sm_scale, kv_rep, plan,
                    interpret, res, g):
    """Ring backward: each hop runs the flash backward kernel
    against the GLOBAL (lse, delta) residuals; dK/dV accumulators
    circulate WITH the KV blocks, so after the full ring each block's
    gradient arrives home with all devices' contributions summed."""
    q, k, v, o, lse = res
    s_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % s_size) for i in range(s_size)]
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32),
        axis=-1, keepdims=True,
    )

    dq = jnp.zeros_like(q, jnp.float32)
    k_cur, v_cur = k, v
    dk_cur = jnp.zeros_like(k, jnp.float32)
    dv_cur = jnp.zeros_like(v, jnp.float32)
    for step in range(s_size):
        src = (my_idx - step) % s_size
        visible = _hop_visible(my_idx, src, causal)
        dq_i, dk_i, dv_i = _flash_bwd_call(
            q, _rep(k_cur, kv_rep), _rep(v_cur, kv_rep), g, lse, delta,
            causal and step == 0, sm_scale, plan, interpret,
        )
        dq = dq + jnp.where(visible, dq_i.astype(jnp.float32), 0.0)
        dk_cur = dk_cur + jnp.where(
            visible, _unrep(dk_i.astype(jnp.float32), kv_rep), 0.0
        )
        dv_cur = dv_cur + jnp.where(
            visible, _unrep(dv_i.astype(jnp.float32), kv_rep), 0.0
        )
        # rotate EVERY step (s rotations total): the accumulators ride
        # the full ring and land back on their block's owner
        k_cur, v_cur, dk_cur, dv_cur = jax.tree.map(
            lambda x: lax.ppermute(x, axis_name, perm),
            (k_cur, v_cur, dk_cur, dv_cur),
        )
    return (
        dq.astype(q.dtype), dk_cur.astype(k.dtype), dv_cur.astype(v.dtype)
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    kv_rep: int = 1,
    impl: str | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Attention over a sequence sharded on ``axis_name``.

    Must be called inside ``shard_map``; q,k,v are the LOCAL shards
    [B, H, T_loc, D] (sequence dim pre-sharded).  Returns the local
    output shard [B, H, T_loc, D].

    ``kv_rep`` > 1 is GQA: K/V carry H/kv_rep heads and circulate the
    ring in that compact form (the expensive part — ppermute bytes on
    the ICI seq axis); each fold repeats the *visiting* block up to H
    heads locally, which is free relative to the hop it avoids fattening.

    ``impl``: ``"flash"`` folds each visiting block with the Pallas
    kernels (per-hop flash + logsumexp merge; backward rides the flash
    backward kernels with global residuals, accumulating dK/dV around
    the ring) — scores never materialize in HBM.  ``"dense"`` is the
    jnp online-softmax path.  Default: flash on TPU when the shard
    length blocks, else dense.
    """
    b, h, t_loc, d = q.shape
    if sm_scale is None:
        sm_scale = d**-0.5
    # one plan for every hop: a visiting block is the shard's length
    plan = _flash_tiles(t_loc, t_loc, d, q.dtype)
    if impl is None:
        impl = "flash" if (_on_tpu() and plan) else "dense"
    if impl == "flash":
        if plan is None:
            raise ValueError(
                f"impl='flash' needs a blockable shard length; "
                f"T_loc={t_loc} has no power-of-two kernel block "
                f"(use impl='dense' or pad the sequence)"
            )
        return _ring_flash(
            q, k, v, axis_name, causal, sm_scale, kv_rep, plan,
            interpret,
        )
    s_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    q_pos = my_idx * t_loc + jnp.arange(t_loc) if causal else None
    perm = [(i, (i + 1) % s_size) for i in range(s_size)]

    def body(step, carry):
        acc_m_l, k_cur, v_cur = carry
        # the block visiting us at `step` started at device my_idx-step
        src = (my_idx - step) % s_size
        k_pos = src * t_loc + jnp.arange(k_cur.shape[2]) if causal else None
        k_use, v_use = (
            (jnp.repeat(k_cur, kv_rep, axis=1),
             jnp.repeat(v_cur, kv_rep, axis=1))
            if kv_rep != 1 else (k_cur, v_cur)
        )
        acc_m_l = block_attn_update(
            acc_m_l, q, k_use, v_use,
            q_pos=q_pos, k_pos=k_pos, sm_scale=sm_scale,
        )
        if step == s_size - 1:  # last fold: no hop left to feed
            return acc_m_l, k_cur, v_cur
        # rotate compact KV to the next device
        k_nxt, v_nxt = jax.tree.map(
            lambda x: lax.ppermute(x, axis_name, perm), (k_cur, v_cur)
        )
        return acc_m_l, k_nxt, v_nxt

    carry = (block_attn_init(b, h, t_loc, d), k, v)
    # unrolled python loop: s_size is static and small; lets XLA
    # overlap each hop's ppermute with the next block's matmuls
    for step in range(s_size):
        carry = body(step, carry)
    return block_attn_finish(carry[0], q.dtype)


def ring_attention_sharded(
    q, k, v, mesh, axis_name: str = "seq", *, causal: bool = True
):
    """Convenience wrapper: shard_map ``ring_attention`` alone over
    ``mesh`` for [B, H, T, D] inputs sharded on T (testing/standalone
    use; models call ``ring_attention`` inside their own shard_map)."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    fn = partial(ring_attention, axis_name=axis_name, causal=causal)
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)
    )(q, k, v)
