"""Fused Pallas paged-attention kernel (serving/paged_attention.py)
vs the jnp gather oracle — the interpreter-mode testing story: the
gather path IS the reference, the kernel must match it EXACTLY for
fp32 (same op sequence by construction).  The interpreter is asked
for explicitly (``interpret=True`` / ``pallas_interpret=True``); that
the same kernel compiles for the chip is tests/test_chip_compile.py's
to show.

Covers the cases the block-table layout makes dangerous: positions
ON block boundaries, ragged per-row lengths, trash-padded tables
(walked but masked), multi-row query windows (the speculative verify
shape), and the full decoder path end-to-end at tp=1 and tp=2.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.ops.attention import NEG_INF
from theanompi_tpu.serving import Engine
from theanompi_tpu.serving.paged_attention import paged_attend

from test_serving_paged import PROMPTS, build_paged, serve_one

pytestmark = pytest.mark.serving


@jax.jit
def gather_oracle(q, kp, vp, tables, pos):
    """The decoder's gather path, op for op
    (``PagedLlamaDecoder._paged_attend``'s else-branch) — jitted, as
    the decoder always runs it: op-by-op dispatch fuses differently
    and is off by an ulp on this XLA."""
    s, nq, hkv, rep, hd = q.shape
    mb = tables.shape[1]
    bs = kp.shape[2]
    t = mb * bs

    def one(arr):
        g = arr[tables]                        # [S, MB, Hkv, bs, hd]
        g = g.transpose(0, 2, 1, 3, 4)
        return g.reshape(s, hkv, t, hd)

    kg, vg = one(kp), one(vp)
    scores = jnp.einsum("sjkrd,sktd->sjkrt", q, kg).astype(
        jnp.float32
    ) * (hd ** -0.5)
    valid = (
        jnp.arange(t)[None, None, :] <= pos[:, :, None]
    )[:, :, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.sum(
        probs.astype(vg.dtype)[..., None] * vg[:, None, :, None, :, :],
        axis=-2,
    )


def make_case(rng, *, s=3, nq=2, hkv=2, rep=3, hd=8, bs=4, mb=4,
              n_blocks=9, pos=None, tables=None):
    kp = jnp.asarray(
        rng.normal(size=(n_blocks + 1, hkv, bs, hd)), jnp.float32
    )
    vp = jnp.asarray(
        rng.normal(size=(n_blocks + 1, hkv, bs, hd)), jnp.float32
    )
    q = jnp.asarray(rng.normal(size=(s, nq, hkv, rep, hd)), jnp.float32)
    if tables is None:
        tables = rng.integers(0, n_blocks, size=(s, mb))
    tables = jnp.asarray(tables, jnp.int32)
    if pos is None:
        pos = rng.integers(0, mb * bs, size=(s, nq))
    pos = jnp.asarray(pos, jnp.int32)
    return q, kp, vp, tables, pos


class TestKernelVsOracle:
    def test_exact_fp32_random(self):
        rng = np.random.default_rng(0)
        q, kp, vp, tables, pos = make_case(rng)
        ref = np.asarray(gather_oracle(q, kp, vp, tables, pos))
        got = np.asarray(
            paged_attend(q, kp, vp, tables, pos, interpret=True)
        )
        assert np.array_equal(ref, got)

    def test_block_boundary_positions_exact(self):
        """pos exactly on / one before / one past each block edge —
        where an off-by-one in the walk or the mask shows up."""
        rng = np.random.default_rng(1)
        bs, mb = 4, 4
        edges = [0, bs - 1, bs, bs + 1, 2 * bs - 1, mb * bs - 1]
        pos = np.array([edges[:2], edges[2:4], edges[4:]], np.int32)
        q, kp, vp, tables, pos = make_case(
            rng, s=3, nq=2, bs=bs, mb=mb, pos=pos
        )
        ref = np.asarray(gather_oracle(q, kp, vp, tables, pos))
        got = np.asarray(
            paged_attend(q, kp, vp, tables, pos, interpret=True)
        )
        assert np.array_equal(ref, got)

    def test_trash_padded_tables_masked_exact(self):
        """Ragged ownership: rows own 1..MB blocks, the rest padded
        with the trash id.  The kernel WALKS the trash blocks (the
        branch-free discipline) but every trash position sits past
        pos, so the mask kills them — outputs must still be exact."""
        rng = np.random.default_rng(2)
        bs, mb, n_blocks = 4, 4, 9
        trash = n_blocks
        tables = np.full((3, mb), trash, np.int64)
        tables[0, :1] = [0]
        tables[1, :2] = [3, 1]
        tables[2, :4] = [2, 5, 7, 8]
        pos = np.array([[0, 1], [5, 7], [12, 15]], np.int32)
        q, kp, vp, tables, pos = make_case(
            rng, bs=bs, mb=mb, n_blocks=n_blocks,
            tables=tables, pos=pos,
        )
        ref = np.asarray(gather_oracle(q, kp, vp, tables, pos))
        got = np.asarray(
            paged_attend(q, kp, vp, tables, pos, interpret=True)
        )
        assert np.array_equal(ref, got)

    def test_single_row_decode_shape(self):
        """hkv=1, rep=1, nq=1 — the tp=8 decode shape, where a
        batched matvec lowering would reassociate the reduction (the
        reason both paths use mult+reduce for PV)."""
        rng = np.random.default_rng(3)
        q, kp, vp, tables, pos = make_case(rng, nq=1, rep=1, hkv=1)
        ref = np.asarray(gather_oracle(q, kp, vp, tables, pos))
        got = np.asarray(
            paged_attend(q, kp, vp, tables, pos, interpret=True)
        )
        assert np.array_equal(ref, got)

    def test_degenerate_heads_verify_window(self):
        """hkv=1, rep=1, nq=4 — a tp=8 speculative verify step."""
        rng = np.random.default_rng(5)
        q, kp, vp, tables, pos = make_case(rng, nq=4, rep=1, hkv=1)
        ref = np.asarray(gather_oracle(q, kp, vp, tables, pos))
        got = np.asarray(
            paged_attend(q, kp, vp, tables, pos, interpret=True)
        )
        assert np.array_equal(ref, got)

    def test_exact_under_jit(self):
        rng = np.random.default_rng(4)
        args = make_case(rng)
        ref = np.asarray(gather_oracle(*args))
        got = np.asarray(
            jax.jit(
                lambda *a: paged_attend(*a, interpret=True)
            )(*args)
        )
        assert np.array_equal(ref, got)


class TestDecoderIntegration:
    def test_impl_knob_validated(self, devices8):
        with pytest.raises(ValueError, match="paged_attend_impl"):
            build_paged(devices8, paged_attend_impl="fused")

    def test_mosaic_kernel_refused_off_tpu(self, devices8):
        """No interpreter by autodetect: on a CPU mesh the compiled
        kernel is refused at construction, never swapped for the
        interpreter or the gather path."""
        with pytest.raises(ValueError, match="pallas_interpret=True"):
            build_paged(devices8, paged_attend_impl="pallas")

    @pytest.mark.parametrize("tp", [1, 2])
    def test_pallas_decoder_matches_gather_end_to_end(
        self, devices8, tp
    ):
        """The whole serve path (prefill → block growth → CoW →
        decode) through the kernel emits bitwise the gather
        decoder's tokens — greedy and temperature."""
        dec_g = build_paged(devices8, tp=tp, max_slots=2)
        dec_p = build_paged(
            devices8, tp=tp, max_slots=2, paged_attend_impl="pallas",
            pallas_interpret=True,
        )
        for seed, temp in ((0, 0.0), (7, 0.9)):
            ref = serve_one(
                dec_g, [3, 11, 2, 9, 30], max_tokens=6, seed=seed,
                temperature=temp,
            )
            got = serve_one(
                dec_p, [3, 11, 2, 9, 30], max_tokens=6, seed=seed,
                temperature=temp,
            )
            assert got == ref

    def test_pallas_batched_equals_single(self, devices8):
        dec = build_paged(
            devices8, paged_attend_impl="pallas", pallas_interpret=True
        )
        ref = [serve_one(dec, PROMPTS[i], seed=i) for i in range(4)]
        eng = Engine(dec, prefix_caching=False)
        futs = [
            eng.submit(PROMPTS[i], max_tokens=5, seed=i)
            for i in range(4)
        ]
        eng.run_until_idle()
        assert [f.result(timeout=0).tokens for f in futs] == ref

    def test_pallas_hlo_carries_paged_attend_scope(self, devices8):
        """The bench's decode-cost attribution needs the kernel's
        inlined (interpreter-mode) ops under the ``paged_attend``
        named scope — the before/after ``paged_attend_frac`` datum
        depends on it."""
        dec = build_paged(
            devices8, paged_attend_impl="pallas", pallas_interpret=True
        )
        ops = dec.decode_scope_op_names(("paged_attend",))
        assert ops, "pallas decode HLO lost the paged_attend scope"

    def test_compile_counters_stable(self, devices8):
        dec = build_paged(
            devices8, paged_attend_impl="pallas", pallas_interpret=True
        )
        for i in range(3):
            serve_one(dec, PROMPTS[i], seed=i)
        assert dec.n_decode_compiles <= 2
        assert dec.n_prefill_compiles <= 2
