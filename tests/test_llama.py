"""Llama model: 3-D parallel (DP x TP x SP) correctness and training.

New-framework scope — the BASELINE Llama stretch config (SURVEY §2.2,
§7 step 7).  Key invariant: the SAME seed must give the SAME loss
whatever the mesh layout, because parallelism is a layout choice, not
a math choice.
"""

import numpy as np
import pytest

from theanompi_tpu.models.llama import Llama
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.utils import Recorder

SMALL = dict(
    dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
    vocab=32, seq_len=32, batch_size=4, lr=1e-2,
    n_train=64, n_val=32, compute_dtype="float32", remat=False,
)


def build(devices, *, data=1, tp=1, sp=1, pp=1, **over):
    cfg = dict(SMALL, tp=tp, sp=sp, pp=pp, **over)
    m = Llama(cfg)
    m.build_model(n_replicas=data)
    mesh = make_mesh(
        data=data, model=tp, seq=sp, pipe=pp,
        devices=devices[: data * tp * sp * pp],
    )
    m.compile_iter_fns(mesh=mesh)
    return m


class TestLayoutInvariance:
    def test_val_loss_same_on_1x1x1_and_2x2x2(self, devices8):
        """Same seed, same data, different mesh -> same numbers."""
        rec = Recorder(rank=0)
        m1 = build(devices8, data=1, tp=1, sp=1)
        # global batch must match: 4*1 vs 2*2 replicas
        m8 = build(devices8, data=2, tp=2, sp=2, batch_size=2)
        l1, e1, e5_1 = m1.val_iter(0, rec)
        l8, e8, e5_8 = m8.val_iter(0, rec)
        assert np.isclose(l1, l8, rtol=1e-4), (l1, l8)
        assert np.isclose(e1, e8, rtol=1e-4), (e1, e8)
        assert np.isclose(e5_1, e5_8, rtol=1e-4), (e5_1, e5_8)

    def test_val_loss_same_with_pipeline_parallel(self, devices8):
        """pp is a layout choice: dp=2 x tp=2 x pp=2 must reproduce the
        1x1x1x1 numbers exactly (GPipe microbatching reorders only the
        summation, fp32 here)."""
        rec = Recorder(rank=0)
        m1 = build(devices8, data=1)
        mp = build(devices8, data=2, tp=2, pp=2, batch_size=2)
        l1, e1, e5_1 = m1.val_iter(0, rec)
        lp, ep, e5_p = mp.val_iter(0, rec)
        assert np.isclose(l1, lp, rtol=1e-4), (l1, lp)
        assert np.isclose(e1, ep, rtol=1e-4), (e1, ep)
        assert np.isclose(e5_1, e5_p, rtol=1e-4), (e5_1, e5_p)

    def test_first_step_loss_matches_full_4d_layout(self, devices8):
        """VERDICT r2 item 5: the gate's COMPOSED 4-D layout — dp=2 x
        tp=2 x sp=1 x pp=2 on 8 devices, ring SP mode active — must
        reproduce the 1x1x1x1 first-step training loss (same seed,
        same global batch; parallelism is layout, not math)."""
        m1 = build(devices8, data=1, optimizer="sgd", lr=0.5)
        m4 = build(
            devices8, data=2, tp=2, sp=1, pp=2, batch_size=2,
            optimizer="sgd", lr=0.5, sp_mode="ring",
        )
        r1, r4 = Recorder(rank=0), Recorder(rank=0)
        m1.train_iter(0, r1)
        m4.train_iter(0, r4)
        r1.flush()
        r4.flush()
        np.testing.assert_allclose(
            r1.train_losses, r4.train_losses, rtol=1e-4
        )

    def test_chunked_head_matches_dense(self, devices8):
        """The streamed unembed+xent head (tp.chunked_unembed_xent,
        r4) is a layout/scheduling choice, not a math choice: forced
        chunking must reproduce the dense head's first training-step
        loss exactly — at tp=1 and with the vocab sharded tp=2."""
        m_dense = build(devices8, data=1, optimizer="sgd", lr=0.5,
                        xent_chunks=0)
        m_chunk = build(devices8, data=1, optimizer="sgd", lr=0.5,
                        xent_chunks=4)
        m_tp = build(devices8, data=2, tp=2, batch_size=2,
                     optimizer="sgd", lr=0.5, xent_chunks=4)
        # sp=2: the chunked backward's dW is a per-seq-shard partial
        # that must psum over the seq axis (the cotangent reduction)
        m_sp = build(devices8, data=2, sp=2, batch_size=2,
                     optimizer="sgd", lr=0.5, xent_chunks=4)
        recs = [Recorder(rank=0) for _ in range(4)]
        for m, r in zip((m_dense, m_chunk, m_tp, m_sp), recs):
            m.train_iter(0, r)
            r.flush()
        assert m_chunk._n_xent_chunks == 4
        np.testing.assert_allclose(
            recs[0].train_losses, recs[1].train_losses, rtol=1e-5
        )
        for other in (2, 3):
            np.testing.assert_allclose(
                recs[0].train_losses, recs[other].train_losses,
                rtol=1e-4,
            )
        np.testing.assert_allclose(
            recs[0].train_errors, recs[1].train_errors, rtol=1e-6
        )

    def test_ragged_xent_chunks_rejected(self, devices8):
        """An explicit chunk count that doesn't divide the local
        vocab would silently drop tail vocab columns from the loss —
        refused at compile time (r4 code-review find)."""
        with pytest.raises(ValueError, match="xent_chunks"):
            build(devices8, data=1, xent_chunks=3)  # vocab 32, 32%3!=0

    @pytest.mark.slow
    def test_first_step_loss_matches_true_4d_16dev(self, devices16):
        """VERDICT r3 #3: the TRUE 4-D product — every axis >= 2
        (dp=2 x tp=2 x sp=2 x pp=2 on 16 devices) — with ring SP
        running INSIDE the pipeline stage scan, the one axis
        interaction no 8-device layout can exercise.  Must reproduce
        the 1x1x1x1 first-step training loss."""
        m1 = build(devices16, data=1, optimizer="sgd", lr=0.5)
        m16 = build(
            devices16, data=2, tp=2, sp=2, pp=2, batch_size=2,
            optimizer="sgd", lr=0.5, sp_mode="ring",
        )
        r1, r16 = Recorder(rank=0), Recorder(rank=0)
        m1.train_iter(0, r1)
        m16.train_iter(0, r16)
        r1.flush()
        r16.flush()
        np.testing.assert_allclose(
            r1.train_losses, r16.train_losses, rtol=1e-4
        )

    @pytest.mark.slow
    def test_sgd_training_matches_with_pipeline_parallel(self, devices8):
        """VERDICT r1 item 2: Llama trains under dp x tp x pp and the
        SGD loss curve coincides with the unpipelined 1x1x1x1 run
        (catches any microbatch/injection/grad-masking bug — backward
        through the pipeline must be exact, not approximate)."""
        m1 = build(devices8, data=1, optimizer="sgd", lr=0.5)
        mp = build(
            devices8, data=2, tp=2, pp=2, batch_size=2,
            optimizer="sgd", lr=0.5,
        )
        r1, rp = Recorder(rank=0), Recorder(rank=0)
        for i in range(4):
            m1.train_iter(i, r1)
            mp.train_iter(i, rp)
        r1.flush()
        rp.flush()
        np.testing.assert_allclose(
            r1.train_losses, rp.train_losses, rtol=1e-3
        )

    @pytest.mark.slow
    def test_device_cache_scan_matches_per_step(self, devices8):
        """The HBM-resident K-step scan path (device_data_cache +
        steps_per_call) is the SAME math as per-step train_iter —
        device-side batch indexing included."""
        m1 = build(devices8, data=2, tp=2, sp=1, batch_size=2,
                   optimizer="sgd", lr=0.3, n_train=32)
        m2 = build(devices8, data=2, tp=2, sp=1, batch_size=2,
                   optimizer="sgd", lr=0.3, n_train=32,
                   device_data_cache=True, steps_per_call=4)
        r1, r2 = Recorder(rank=0), Recorder(rank=0)
        for i in range(4):
            m1.train_iter(i, r1)
        assert m2.preferred_chunk(8) == 4
        m2.train_chunk(0, 4, r2)
        r1.flush()
        r2.flush()
        np.testing.assert_allclose(
            r1.train_losses, r2.train_losses, rtol=1e-4
        )

    @pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
    @pytest.mark.slow
    def test_sgd_training_matches_across_meshes(self, devices8, sp_mode):
        """SGD training curves must coincide on 1x1x1 and 2x2x2 — this
        catches any layout-dependent gradient scaling (unlike Adam,
        SGD is not invariant to per-leaf grad rescaling)."""
        # ulysses needs (heads/tp) % sp == 0, so widen the head config
        heads = (
            dict(n_heads=8, n_kv_heads=4) if sp_mode == "ulysses" else {}
        )
        m1 = build(
            devices8, data=1, tp=1, sp=1, optimizer="sgd", lr=0.5, **heads
        )
        m8 = build(
            devices8, data=2, tp=2, sp=2, batch_size=2,
            optimizer="sgd", lr=0.5, sp_mode=sp_mode, **heads,
        )
        r1, r8 = Recorder(rank=0), Recorder(rank=0)
        for i in range(4):
            m1.train_iter(i, r1)
            m8.train_iter(i, r8)
        # large lr amplifies any grad-scale mismatch step over step
        np.testing.assert_allclose(
            r1.train_losses, r8.train_losses, rtol=1e-3
        )


@pytest.mark.slow
class TestPipelineHeadCost:
    def test_head_flops_scale_inverse_with_stages(self, devices8):
        """VERDICT r2 item 6: with the scattered head, each pipeline
        stage computes the lm head on 1/S of the tokens — XLA's own
        cost_analysis of the per-device module must show the masked
        path paying ~one full head more than the scattered path."""
        vocab, dim, b, t = 2048, 64, 8, 64
        over = dict(
            vocab=vocab, dim=dim, seq_len=t, batch_size=b,
            n_train=b * 8, n_val=b,
        )
        flops = {}
        for scatter in (True, False):
            m = build(devices8, data=1, pp=2, pp_microbatches=8,
                      pp_head_scatter=scatter, **over)
            ca = m.train_step_cost_analysis()
            flops[scatter] = float(ca.get("flops", 0))
        assert m._pp_scatter is False  # knob respected on last build
        # per-device head cost (fwd matmul): 2 * n_tok * D * V; bwd
        # roughly doubles-to-triples it.  Scatter halves it at S=2, so
        # the masked module must carry at least ~one fwd-head more.
        head_fwd = 2.0 * b * t * dim * vocab
        assert flops[True] < flops[False] - head_fwd, flops

    def test_scattered_head_matches_masked(self, devices8):
        """Both head placements are the same math: identical first
        train-step loss (scatter is a cost layout, not a model)."""
        kw = dict(data=2, tp=1, sp=1, pp=2, batch_size=2,
                  optimizer="sgd", lr=0.5)
        ms = build(devices8, pp_head_scatter=True, **kw)
        mm = build(devices8, pp_head_scatter=False, **kw)
        assert ms._pp_scatter and not mm._pp_scatter
        rs, rm = Recorder(rank=0), Recorder(rank=0)
        for i in range(3):
            ms.train_iter(i, rs)
            mm.train_iter(i, rm)
        rs.flush()
        rm.flush()
        np.testing.assert_allclose(
            rs.train_losses, rm.train_losses, rtol=1e-4
        )


@pytest.mark.slow
class TestTraining:
    def test_full_4d_parallel_step(self, devices8):
        """tp x sp x pp all active at once (dp=1 on 8 devices): the
        axes compose — ring attention inside pipelined stages inside
        the vma-checked shard_map."""
        m = build(devices8, data=1, tp=2, sp=2, pp=2, batch_size=4)
        rec = Recorder(rank=0)
        for i in range(2):
            m.train_iter(i, rec)
        rec.flush()
        assert np.isfinite(rec.train_losses).all()

    def test_loss_decreases_3d_parallel(self, devices8):
        m = build(devices8, data=2, tp=2, sp=2, batch_size=2)
        rec = Recorder(rank=0)
        for i in range(m.data.n_batch_train):
            m.train_iter(i, rec)
        first, last = rec.train_losses[0], rec.train_losses[-1]
        assert last < first, (first, last)

    def test_gqa_repeat_consistency(self, devices8):
        """n_kv_heads == n_heads and GQA path agree at tp=1 given the
        same KV weights (repeat of identical groups is a no-op)."""
        m = build(devices8, data=1, tp=1, sp=1)
        rec = Recorder(rank=0)
        loss, _, _ = m.val_iter(0, rec)
        assert np.isfinite(loss)


@pytest.mark.slow
class TestCheckpoint:
    def test_save_load_roundtrip(self, devices8, tmp_path):
        m = build(devices8, data=2, tp=2, sp=1, batch_size=2)
        rec = Recorder(rank=0)
        m.train_iter(0, rec)
        m.epoch = 3
        m.save(str(tmp_path), rec)

        m2 = build(devices8, data=2, tp=2, sp=1, batch_size=2)
        assert m2.load(str(tmp_path), Recorder(rank=0))
        assert m2.epoch == 3
        l_a = m.val_iter(0, rec)[0]
        l_b = m2.val_iter(0, rec)[0]
        assert np.isclose(l_a, l_b, rtol=1e-5)
