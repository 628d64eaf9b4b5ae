"""Ring + Ulysses attention and blockwise/flash primitives vs dense
reference.

New-framework scope — SURVEY §2.2 rows "Ring attention / blockwise",
"Ulysses (attention head all-to-all)" and "Sequence/context parallel"
(all absent upstream).  Every sharded path must match single-device
dense attention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from theanompi_tpu.ops.attention import (
    block_attn_finish,
    block_attn_init,
    block_attn_update,
    mha_reference,
)
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.parallel.ring_attention import (
    ring_attention,
    ring_attention_sharded,
)

B, H, T, D = 2, 4, 64, 16


def qkv(rng, t=T):
    shape = (B, H, t, D)
    return tuple(
        jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for _ in range(3)
    )


class TestBlockwise:
    @pytest.mark.parametrize("causal", [False, True])
    def test_sequential_blocks_match_dense(self, rng, causal):
        q, k, v = qkv(rng)
        blk = 16
        sm = D**-0.5
        carry = block_attn_init(B, H, T, D)
        q_pos = jnp.arange(T) if causal else None
        for i in range(0, T, blk):
            k_pos = i + jnp.arange(blk) if causal else None
            carry = block_attn_update(
                carry, q, k[:, :, i : i + blk], v[:, :, i : i + blk],
                q_pos=q_pos, k_pos=k_pos, sm_scale=sm,
            )
        out = block_attn_finish(carry, q.dtype)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


class TestFlashKernel:
    """Pallas kernel in interpreter mode (runs on any backend)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_multiblock(self, rng, causal):
        from theanompi_tpu.ops.attention import flash_attention_tpu

        q, k, v = qkv(rng)
        out = flash_attention_tpu(
            q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
        )
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_rejects_indivisible_shapes(self, rng):
        from theanompi_tpu.ops.attention import flash_attention_tpu

        q = k = v = jnp.zeros((1, 1, 60, 16), jnp.float32)
        with pytest.raises(ValueError, match="not divisible"):
            flash_attention_tpu(
                q, k, v, block_q=16, block_k=16, interpret=True
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense_multiblock(self, rng, causal):
        """custom_vjp backward kernels (dQ and dK/dV) vs autodiff of
        the dense reference, multiple blocks in both grid dims."""
        from theanompi_tpu.ops.attention import flash_attention_tpu

        q, k, v = qkv(rng)

        def loss_flash(q, k, v):
            o = flash_attention_tpu(
                q, k, v, causal=causal, block_q=16, block_k=16,
                interpret=True,
            )
            return jnp.sum(o * o)

        def loss_dense(q, k, v):
            o = mha_reference(q, k, v, causal=causal)
            return jnp.sum(o * o)

        g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_f, g_d):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name} mismatch",
            )

    def test_independent_backward_blocks_same_grads(self, rng):
        """bwd_block_q/bwd_block_k (VERDICT r3 #6 sweep knob) retile
        the backward kernels only — gradients must be identical to the
        shared-block path."""
        from theanompi_tpu.ops.attention import flash_attention_tpu

        q, k, v = qkv(rng)

        def loss(bq, bk):
            def f(q, k, v):
                o = flash_attention_tpu(
                    q, k, v, causal=True, block_q=16, block_k=16,
                    bwd_block_q=bq, bwd_block_k=bk, interpret=True,
                )
                return jnp.sum(o * o)
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        g_shared = loss(None, None)
        g_retiled = loss(8, 32)
        for name, a, b in zip("qkv", g_shared, g_retiled):
            # different tile orders reassociate the fp32 accumulators:
            # identical math, ~1e-6 absolute float noise
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=f"d{name} mismatch",
            )


class TestFlashDispatch:
    """``flash_attention`` picks its path from a device, and a dense
    choice is logged once per shape (ISSUE 22 item 3)."""

    def test_dense_choice_is_logged_once_per_shape(self, rng, caplog):
        import logging

        from theanompi_tpu.ops import attention

        q, k, v = qkv(rng, t=40)
        attention._log_dense_choice.cache_clear()
        before = attention.dense_choices()
        with caplog.at_level(logging.INFO, logger=attention.__name__):
            for _ in range(2):
                out = attention.flash_attention(q, k, v)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(mha_reference(q, k, v))
        )
        lines = [r.getMessage() for r in caplog.records]
        assert len(lines) == 1, lines
        assert "T_q=40" in lines[0] and "not on TPU devices" in lines[0]
        assert attention.dense_choices() - before == 2

    def test_setting_alone_never_reports_a_tpu(self, monkeypatch):
        """``TM_TPU_PLATFORM=tpu`` where JAX has no TPU: the platform
        is read off a device, so the claim fails instead of steering
        the kernels onto CPU devices."""
        from theanompi_tpu.ops import attention

        assert attention._on_tpu() is False
        monkeypatch.setenv("TM_TPU_PLATFORM", "tpu")
        with pytest.raises(RuntimeError):
            attention._on_tpu()


class TestRingFlash:
    """Flash-backed ring attention (per-hop Pallas kernels + logsumexp
    merge, ring-accumulated dK/dV backward) vs the dense ring path.

    check_vma=False harness: the Pallas HLO *interpreter* (how these
    kernels run off-TPU) rejects vma-carrying operands inside its loop
    machinery; on real TPU hardware the kernels lower through Mosaic,
    where the vma-checked path is exercised by the sp=1 flash dispatch
    in the Llama bench."""

    def _outputs(self, q, k, v, impl, causal, kv_rep, devices8):
        mesh = make_mesh(data=1, seq=4, devices=devices8[:4])
        spec = P(None, None, "seq", None)

        def fn(q, k, v):
            return ring_attention(
                q, k, v, "seq", causal=causal, kv_rep=kv_rep,
                impl=impl, interpret=True,
            )

        return jax.jit(
            jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                          out_specs=spec, check_vma=False)
        )(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("kv_rep", [1, 2])
    def test_forward_matches_dense_ring(self, rng, causal, kv_rep,
                                        devices8):
        q = jnp.asarray(rng.standard_normal((B, H, 2 * T, D)), jnp.float32)
        kv_shape = (B, H // kv_rep, 2 * T, D)
        k = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)
        v = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)
        od = self._outputs(q, k, v, "dense", causal, kv_rep, devices8)
        of = self._outputs(q, k, v, "flash", causal, kv_rep, devices8)
        np.testing.assert_allclose(
            np.asarray(of), np.asarray(od), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense_ring(self, rng, causal, devices8):
        """The custom backward (flash dQ/dKV kernels per hop with
        global residuals, accumulators riding the full ring) equals
        autodiff of the dense ring."""
        q = jnp.asarray(rng.standard_normal((B, H, 2 * T, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, H // 2, 2 * T, D)),
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, H // 2, 2 * T, D)),
                        jnp.float32)
        mesh = make_mesh(data=1, seq=4, devices=devices8[:4])
        spec = P(None, None, "seq", None)

        def grads(impl):
            def loss_fn(q, k, v):
                o = ring_attention(
                    q, k, v, "seq", causal=causal, kv_rep=2,
                    impl=impl, interpret=True,
                )
                w = jnp.cos(jnp.arange(o.size).reshape(o.shape) / 777.0)
                return jax.lax.psum((o * w).sum(), "seq")

            f = jax.jit(jax.shard_map(
                jax.grad(loss_fn, argnums=(0, 1, 2)),
                mesh=mesh, in_specs=(spec,) * 3,
                out_specs=(spec,) * 3, check_vma=False,
            ))
            return f(q, k, v)

        gd, gf = grads("dense"), grads("flash")
        for name, a, b in zip("qkv", gf, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4,
                err_msg=f"d{name} mismatch",
            )


class TestUlysses:
    @pytest.mark.parametrize("n_seq", [2, 4])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, devices8, rng, n_seq, causal):
        from theanompi_tpu.parallel.ulysses import ulysses_attention_sharded

        mesh = make_mesh(data=1, seq=n_seq, devices=devices8[:n_seq])
        q, k, v = qkv(rng)
        out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_gqa_compact_kv(self, devices8, rng):
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from theanompi_tpu.parallel.ulysses import ulysses_attention

        n_seq, rep = 2, 2
        mesh = make_mesh(data=1, seq=n_seq, devices=devices8[:n_seq])
        q = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
        kv_shape = (B, H // rep, T, D)
        k = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)
        v = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)
        spec = P(None, None, "seq", None)
        out = jax.jit(
            jax.shard_map(
                partial(ulysses_attention, axis_name="seq", causal=True,
                        kv_rep=rep),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            )
        )(q, k, v)
        want = mha_reference(
            q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
            causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_rejects_indivisible_heads(self, devices8, rng):
        from theanompi_tpu.parallel.ulysses import ulysses_attention_sharded

        mesh = make_mesh(data=1, seq=8, devices=devices8)
        q, k, v = qkv(rng)  # H=4 < sp=8
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention_sharded(q, k, v, mesh)


class TestRing:
    @pytest.mark.parametrize("n_seq", [2, 4, 8])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, devices8, rng, n_seq, causal):
        mesh = make_mesh(data=1, seq=n_seq, devices=devices8[:n_seq])
        q, k, v = qkv(rng)
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_gqa_compact_kv_matches_repeated(self, devices8, rng):
        """kv_rep ring (compact KV on the wire) == dense attention on
        pre-repeated KV."""
        from functools import partial

        import jax
        from jax.sharding import PartitionSpec as P

        from theanompi_tpu.parallel.ring_attention import ring_attention

        n_seq, rep = 4, 2
        mesh = make_mesh(data=1, seq=n_seq, devices=devices8[:n_seq])
        q = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
        kv_shape = (B, H // rep, T, D)
        k = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)
        v = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)

        spec = P(None, None, "seq", None)
        out = jax.jit(
            jax.shard_map(
                partial(ring_attention, axis_name="seq", causal=True,
                        kv_rep=rep),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            )
        )(q, k, v)
        want = mha_reference(
            q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
            causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_grads_match_dense(self, devices8, rng):
        """d(loss)/d(q,k,v) through the ring == through dense attention."""
        n_seq = 4
        mesh = make_mesh(data=1, seq=n_seq, devices=devices8[:n_seq])
        q, k, v = qkv(rng, t=32)

        def loss_ring(q, k, v):
            return jnp.sum(
                ring_attention_sharded(q, k, v, mesh, causal=True) ** 2
            )

        def loss_dense(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_dense):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
            )
