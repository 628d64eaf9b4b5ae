"""Elastic resharding (ISSUE 8): flat-layout permutation primitives,
the model-level reshard-load round trip, and the refusal surface.

The sharp acceptance criterion lives here: a checkpoint saved under
(dp=8, bucketed, zero1, int8-EF) loads at dp=4 with params BITWISE
equal and the gathered optimizer/EF state exactly conserved — then
grows back to dp=8 the same way.  The supervised end-to-end drill
(kill one of 8 → resume at dp=4, loss matches an uninterrupted
equal-batch run) is in ``test_fault_recovery.py``.
"""

import numpy as np
import pytest

import jax

from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.parallel.exchange import flat_layout
from theanompi_tpu.utils import Recorder
from theanompi_tpu.utils import reshard as rs

_WRN = {
    "batch_size": 4, "depth": 10, "widen": 1, "n_train": 4 * 8 * 2,
    "n_val": 32, "n_epochs": 1, "lr": 0.01, "seed": 3,
}


def _wresnet(dp, devices8, extra=None, strategy="zero1"):
    from theanompi_tpu.models.wresnet import WResNet

    m = WResNet(dict(_WRN, **(extra or {})))
    m.build_model(n_replicas=dp)
    m.compile_iter_fns(
        mesh=make_mesh(data=dp, devices=devices8[:dp]),
        exch_strategy=strategy,
    )
    return m


def _train(m, k=3):
    rec = Recorder(verbose=False)
    nb = m.data.n_batch_train
    for i in range(k):
        m.train_iter(i % nb, rec)
    rec.flush()
    return m


def _psize(m) -> int:
    return sum(
        int(np.prod(np.shape(l))) for l in jax.tree.leaves(m.params)
    )


def _gathered_opt(m, dp) -> list:
    """Every flat opt-state leaf in master (pack) order, live region
    only; non-flat leaves (scalars) pass through."""
    padded, bl = m.exchange.zero1_layout
    size = _psize(m)
    out = []
    for leaf in jax.tree.leaves(m.opt_state):
        a = np.asarray(leaf)
        if a.ndim == 1 and a.shape == (padded,):
            out.append(rs.storage_to_pack(a, dp, bl)[:size])
        else:
            out.append(a)
    return out


def _assert_params_equal(a, b):
    la = jax.tree_util.tree_flatten_with_path(a.params)[0]
    lb = jax.tree_util.tree_flatten_with_path(b.params)[0]
    assert [str(p) for p, _ in la] == [str(p) for p, _ in lb]
    for (p, x), (_, y) in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=str(p)
        )


# ---------------------------------------------------------------------------
# permutation primitives (pure host math)
# ---------------------------------------------------------------------------


class TestPrimitives:
    @pytest.mark.parametrize("n,target", [(4, 24), (8, 40), (6, 36)])
    def test_pack_storage_against_direct_construction(self, n, target):
        """``pack_to_storage`` must equal the storage order built
        directly from the definition: device d's shard is the concat
        over buckets i of pack[i*bl + d*bs : i*bl + (d+1)*bs]."""
        size = 301
        padded, bl = flat_layout(size, n, target)
        assert bl > 0, "grid point must actually bucket"
        pack = np.arange(padded, dtype=np.float32)
        bs = bl // n
        direct = np.concatenate([
            np.concatenate([
                pack[i * bl + d * bs: i * bl + (d + 1) * bs]
                for i in range(padded // bl)
            ])
            for d in range(n)
        ])
        np.testing.assert_array_equal(
            rs.pack_to_storage(pack, n, bl), direct
        )
        np.testing.assert_array_equal(
            rs.storage_to_pack(direct, n, bl), pack
        )

    def test_monolithic_is_identity(self):
        buf = np.arange(24, dtype=np.float32)
        np.testing.assert_array_equal(rs.storage_to_pack(buf, 4, 0), buf)
        np.testing.assert_array_equal(rs.pack_to_storage(buf, 4, 0), buf)

    @pytest.mark.parametrize("old_n,new_n", [(8, 4), (4, 8), (8, 6)])
    def test_reshard_flat_round_trip(self, old_n, new_n):
        """old → new → old is the identity on the live region (dp=6
        covers the uneven-padding case the ISSUE motivates)."""
        size = 233
        old = (old_n, *flat_layout(size, old_n, 40))
        new = (new_n, *flat_layout(size, new_n, 56))
        buf_pack = np.zeros(old[1], np.float32)
        buf_pack[:size] = np.random.default_rng(0).normal(size=size)
        buf = rs.pack_to_storage(buf_pack, old[0], old[2])
        there = rs.reshard_flat(buf, size=size, old=old, new=new)
        back = rs.reshard_flat(there, size=size, old=new, new=old)
        np.testing.assert_array_equal(back, buf)
        # and the new storage gathers to the same live pack
        np.testing.assert_array_equal(
            rs.storage_to_pack(there, new[0], new[2])[:size],
            buf_pack[:size],
        )

    def test_bucketed_needs_world_stamp(self):
        padded, bl = flat_layout(100, 4, 32)
        with pytest.raises(ValueError, match="world_size stamp"):
            rs.reshard_flat(
                np.zeros(padded, np.float32), size=100,
                old=(None, padded, bl), new=(8, *flat_layout(100, 8, 0)),
            )

    def test_multi_axis_flat_refuses(self):
        """A flat buffer whose saved length isn't the stamped padded
        (a tp/pp-spanning zero1 pack) refuses with a pointer."""
        with pytest.raises(ValueError, match="model/pipe"):
            rs.reshard_flat(
                np.zeros(64, np.float32), size=30,
                old=(4, 32, 0), new=(8, 32, 0),
            )


# ---------------------------------------------------------------------------
# model-level round trip: the acceptance (bucketed, zero1, int8-EF) arm
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def saved8(devices8, tmp_path_factory):
    """dp=8 wresnet under the acceptance config — zero1 + 0.05 MiB
    buckets + int8 EF wire — trained 3 steps and checkpointed (the
    partitioned zero1 state auto-picks the .shards format)."""
    m = _train(_wresnet(8, devices8, {
        "exchange_bucket_mb": 0.05, "exch_compression": "int8",
    }))
    d = tmp_path_factory.mktemp("ck8")
    m.save(str(d))
    return m, d


class TestModelReshard:
    def test_shrink_grow_round_trip_bitwise(self, saved8, devices8,
                                            tmp_path):
        m8, ck8 = saved8
        size = _psize(m8)
        # -- shrink: dp=8 checkpoint loads at dp=4 via reshard=True
        m4 = _wresnet(4, devices8, {
            "exchange_bucket_mb": 0.05, "exch_compression": "int8",
            "elastic": True,
        })
        assert m4.load(str(ck8))
        assert m4.resharded_from == {
            "world_size": 8, "groups": ["ef_state", "opt_state"],
        }
        _assert_params_equal(m8, m4)
        # optimizer state: exactly conserved under the gather
        for a, b in zip(_gathered_opt(m8, 8), _gathered_opt(m4, 4)):
            np.testing.assert_array_equal(a, b)
        # EF residual: the MEAN-reduce contribution is conserved
        # bitwise — the loader moves total * (n_new/n_old) onto
        # shard 0, so the next exchange injects total/n_old exactly
        # as the old world would have (the /n_new in the mean)
        p8 = m8.exchange.ef_layout[1]
        p4 = m4.exchange.ef_layout[1]
        r1_8 = np.asarray(m8.ef_state["r1"]).reshape(8, p8)
        r1_4 = np.asarray(m4.ef_state["r1"]).reshape(4, p4)
        np.testing.assert_array_equal(
            np.sum(r1_8[:, :size], axis=0) * np.float32(4 / 8),
            np.sum(r1_4[:, :size], axis=0),
        )
        # epoch/lr metadata rode along, and the model still trains
        assert m4.epoch == m8.epoch
        _train(m4, k=1)

        # -- grow back: dp=4 save loads at dp=8 the same way
        m4.save(str(tmp_path))
        m8b = _wresnet(8, devices8, {
            "exchange_bucket_mb": 0.05, "exch_compression": "int8",
            "elastic": True,
        })
        assert m8b.load(str(tmp_path))
        assert m8b.resharded_from["world_size"] == 4
        _assert_params_equal(m4, m8b)
        for a, b in zip(_gathered_opt(m4, 4), _gathered_opt(m8b, 8)):
            np.testing.assert_array_equal(a, b)

    def test_mismatch_refusal_names_escape_hatch(self, saved8,
                                                 devices8):
        """The layout-mismatch refusal is no longer a dead end: it
        names reshard=True / config['elastic'].  The same model with
        reshard=True then loads (same dp, different bucket layout —
        elasticity also unlocks bucket-knob changes)."""
        m8, ck8 = saved8
        mono = _wresnet(8, devices8, {
            "exchange_bucket_mb": 0, "exch_compression": "int8",
        })
        with pytest.raises(ValueError, match="reshard=True"):
            mono.load(str(ck8))
        assert mono.load(str(ck8), reshard=True)
        for a, b in zip(_gathered_opt(m8, 8), _gathered_opt(mono, 8)):
            np.testing.assert_array_equal(a, b)

    def test_cross_compression_reshard_refuses(self, saved8, devices8):
        m8, ck8 = saved8
        m4 = _wresnet(4, devices8, {
            "exchange_bucket_mb": 0.05, "exch_compression": "fp8",
            "elastic": True,
        })
        with pytest.raises(ValueError, match="across wire formats"):
            m4.load(str(ck8))


# ---------------------------------------------------------------------------
# slow tier: the independent ground-truth anchor + the r2 residual arm
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestGroundTruth:
    def test_storage_to_pack_matches_monolithic_layout(self, devices8):
        """Independent anchor for the permutation: bucketed and
        monolithic zero1 runs are bitwise-equal in PARAMS (the PR 2
        guarantee), and the monolithic optimizer shard IS pack order —
        so storage_to_pack of the bucketed shard must equal the
        monolithic shard on the live region."""
        cfg = {"exch_compression": "none"}
        mono = _train(_wresnet(8, devices8, {
            **cfg, "exchange_bucket_mb": 0,
        }))
        buck = _train(_wresnet(8, devices8, {
            **cfg, "exchange_bucket_mb": 0.05,
        }))
        _assert_params_equal(mono, buck)
        size = _psize(mono)
        _, bl = buck.exchange.zero1_layout
        assert bl > 0
        for a, b in zip(
            jax.tree.leaves(mono.opt_state),
            jax.tree.leaves(buck.opt_state),
        ):
            a, b = np.asarray(a), np.asarray(b)
            if a.ndim != 1:
                np.testing.assert_array_equal(a, b)
                continue
            np.testing.assert_array_equal(
                a[:size], rs.storage_to_pack(b, 8, bl)[:size]
            )

    def test_non_zero1_ef_r2_reshards(self, devices8, tmp_path):
        """asa32 + fp8: the opt state is a regular replicated tree
        (loads at any dp untouched); only the EF residuals reshard —
        r1 by mass, r2 (the shard-owner reduced-mean residual, absent
        under zero1) by exact permutation."""
        m8 = _train(_wresnet(8, devices8, {
            "exchange_bucket_mb": 0.05, "exch_compression": "fp8",
        }, strategy="asa32"))
        m8.save(str(tmp_path))
        size = _psize(m8)
        m4 = _wresnet(4, devices8, {
            "exchange_bucket_mb": 0.05, "exch_compression": "fp8",
            "elastic": True,
        }, strategy="asa32")
        assert m4.load(str(tmp_path))
        assert m4.resharded_from["groups"] == ["ef_state"]
        _assert_params_equal(m8, m4)
        _, p8, b8 = m8.exchange.ef_layout
        _, p4, b4 = m4.exchange.ef_layout
        np.testing.assert_array_equal(
            np.sum(
                np.asarray(m8.ef_state["r1"]).reshape(8, p8)[:, :size],
                axis=0,
            ) * np.float32(4 / 8),
            np.sum(
                np.asarray(m4.ef_state["r1"]).reshape(4, p4)[:, :size],
                axis=0,
            ),
        )
        np.testing.assert_array_equal(
            rs.storage_to_pack(
                np.asarray(m8.ef_state["r2"]), 8, b8
            )[:size],
            rs.storage_to_pack(
                np.asarray(m4.ef_state["r2"]), 4, b4
            )[:size],
        )
        _train(m4, k=1)


class TestWorldChangeHazards:
    """Review-found hazards: layout stamps that COINCIDE across
    worlds, and the lr restore undoing the per-replica scaling."""

    def test_coinciding_stamps_still_reshard(self, devices8, tmp_path):
        """(padded, bucket_len) both round to multiples of n, so a
        bucket size that is a multiple of 8 ELEMENTS yields the
        IDENTICAL stamp at dp=8 and dp=4 — but the bucket-major
        storage permutation is n-dependent.  The world_size stamp
        must force the refusal (non-elastic) and the reshard
        (elastic); loading as-is would silently pair adam/momentum
        rows with the wrong parameters."""
        # 0.03125 MiB = 8192 elements — a multiple of both 8 and 4
        cfg = {"exchange_bucket_mb": 0.03125}
        m8 = _train(_wresnet(8, devices8, cfg))
        m8.save(str(tmp_path))
        m4 = _wresnet(4, devices8, cfg)
        assert tuple(m8.exchange.zero1_layout) == tuple(m4.exchange.zero1_layout)
        with pytest.raises(ValueError, match="reshard=True"):
            m4.load(str(tmp_path))
        assert m4.load(str(tmp_path), reshard=True)
        assert m4.resharded_from["groups"] == ["opt_state"]
        _assert_params_equal(m8, m4)
        for a, b in zip(_gathered_opt(m8, 8), _gathered_opt(m4, 4)):
            np.testing.assert_array_equal(a, b)

    def test_per_replica_lr_rescale_survives_restore(self, devices8,
                                                     tmp_path):
        """model.load() restores the OLD world's lr from the meta;
        the worker must re-apply the linear scale to the restored
        value or the policy is silently a no-op."""
        from theanompi_tpu.workers import bsp_worker

        base = dict(_WRN, lr=0.08, n_epochs=1,
                    exch_strategy="asa32")
        bsp_worker.run(
            devices=list(range(8)),
            modelfile="theanompi_tpu.models.wresnet",
            modelclass="WResNet",
            config=dict(base),
            checkpoint_dir=str(tmp_path),
            verbose=False,
        )
        out = bsp_worker.run(
            devices=list(range(4)),
            modelfile="theanompi_tpu.models.wresnet",
            modelclass="WResNet",
            config=dict(base, n_epochs=2, elastic=True,
                        elastic_batch_policy="per_replica"),
            checkpoint_dir=str(tmp_path),
            resume=True,
            verbose=False,
        )
        assert out["elastic_resume"]["lr_scale"] == pytest.approx(0.5)
        # the epoch that actually trained after the resume ran at the
        # scaled lr (restored 0.08 * 4/8), not the restored one
        assert out["model"].current_lr == pytest.approx(0.04)
        assert out["world_size"] == 4
