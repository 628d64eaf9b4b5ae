"""Disaggregated prefill/decode (serving v4,
theanompi_tpu/serving/kv_transfer.py + replica roles).

The contract under test, layer by layer:

- TRANSFER: a handoff record round-trips — blocks exported from the
  prefiller's pools import into another decoder's pools bit-for-bit;
  ``compatible`` refuses geometry mismatches loudly.
- ENGINE: a ``prefill_only`` request resolves ``"prefilled"`` with
  the KV record attached; edge cases (eos on the first token,
  ``max_tokens<=1``) finish normally with no handoff.
- FLEET: a prompt prefilled on replica A and decoded on replica B
  produces greedy ids BITWISE-equal to the same prompt served
  end-to-end on one unified replica — including across a tp-width
  mismatch (prefill tp=1 → decode tp=2, the cross-layout
  ``model.load`` discipline applied to KV blocks).
- FALLBACK: no healthy decode-capable member → the prefill
  specialist serves end-to-end; a receiver that cannot take the
  handoff (different block size) sheds ``"handoff_failed"`` and the
  router retries the FULL prompt — token-exact either way.
- DRILL: the ``die_replica`` fault kills the prefill specialist
  mid-handoff (requests in flight on its busy-iteration clock); the
  kill-one-of-3 failover guarantee extends — every request completes
  token-exact via requeue.
"""

import time

import numpy as np
import pytest

from theanompi_tpu.models.llama import Llama
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.serving import (
    Engine,
    InProcessReplica,
    ReplicaServer,
    Request,
    Router,
    TCPReplicaClient,
)
from theanompi_tpu.serving import kv_transfer
from theanompi_tpu.utils.faults import reset_fault_cache

pytestmark = pytest.mark.serving

SMALL = dict(
    dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
    vocab=64, seq_len=64, batch_size=4, lr=1e-2,
    n_train=64, n_val=32, compute_dtype="float32", remat=False,
)

# two blocks' worth at block_size=8, so handoffs carry a multi-block
# table with a partial tail block
PROMPTS = [
    [1 + i, 5, 9, 3 + i, 17, 2, 4, 8, 6, 11 + i] for i in range(6)
]

DEC_KW = dict(max_slots=2, max_seq=48, block_size=8, prefill_chunk=8)


@pytest.fixture(scope="module")
def models(devices8, tmp_path_factory):
    """One weight set served at tp=1 and tp=2 (the tp=2 copy restores
    the tp=1 checkpoint through the cross-layout loader)."""
    m1 = Llama(dict(SMALL, tp=1))
    m1.build_model(n_replicas=1)
    m1.compile_iter_fns(
        mesh=make_mesh(data=1, model=1, devices=devices8[:1])
    )
    ck = str(tmp_path_factory.mktemp("disagg_ck"))
    m1.save(ck)
    m2 = Llama(dict(SMALL, tp=2))
    m2.build_model(n_replicas=1)
    m2.compile_iter_fns(
        mesh=make_mesh(data=1, model=2, devices=devices8[:2])
    )
    assert m2.load(ck)
    return m1, m2


def paged_decoder(model, **kw):
    return model.make_decoder(paged=True, **{**DEC_KW, **kw})


def run_fleet(router, n=4, max_tokens=6, timeout=240.0):
    futs = [
        router.submit(PROMPTS[i], max_tokens=max_tokens, seed=i)
        for i in range(n)
    ]
    return [f.result(timeout=timeout) for f in futs]


def make_router(reps, **kw):
    kw.setdefault("policy", "round_robin")
    kw.setdefault("health_interval_s", 0.005)
    kw.setdefault("startup_grace_s", 60.0)
    return Router(reps, **kw).start()


def teardown(router, reps):
    router.stop(drain_s=5.0)
    for r in reps:
        r.stop()


@pytest.fixture(scope="module")
def unified_ref(models):
    """Greedy ids for PROMPTS served end-to-end on one unified
    replica — the bitwise anchor every disaggregated arm must
    match."""
    m1, _ = models
    rep = InProcessReplica(
        Engine(paged_decoder(m1)), name="ref0"
    ).start()
    router = make_router([rep])
    try:
        rs = run_fleet(router, n=6)
        assert all(r.status == "ok" for r in rs)
        return [r.tokens for r in rs]
    finally:
        teardown(router, [rep])


# -- transfer layer ----------------------------------------------------------


class TestKVTransfer:
    def test_handoff_round_trips_bitwise(self, models):
        """Blocks exported from one decoder import into another
        decoder's pools and export back IDENTICAL — the device
        gather/scatter pair is lossless."""
        m1, _ = models
        src = paged_decoder(m1)
        dst = paged_decoder(m1)
        eng = Engine(src)
        fut = eng.submit(
            Request(prompt=list(PROMPTS[0]), max_tokens=6,
                    prefill_only=True)
        )
        eng.run_until_idle()
        res = fut.result(timeout=0)
        assert res.finish_reason == "prefilled"
        h = res.handoff
        assert h["n_prompt"] == len(PROMPTS[0])
        assert h["n_blocks"] == 2 and h["block_size"] == 8
        assert len(h["layers"]) == SMALL["n_layers"]
        assert h["layers"][0]["k"].shape == (2, 2, 8, 8)
        assert kv_transfer.handoff_bytes(h) == 2 * 2 * (2 * 2 * 8 * 8 * 4)

        ok, why = kv_transfer.compatible(dst, h)
        assert ok, why
        dst.manager.assign(0, [], h["n_blocks"])
        kv_transfer.inject_handoff(dst, dst.manager, 0, h)
        back = dst.export_blocks(dst.manager.slot_blocks(0, 2))
        for a, b in zip(h["layers"], back):
            np.testing.assert_array_equal(a["k"], b["k"])
            np.testing.assert_array_equal(a["v"], b["v"])

    def test_geometry_mismatch_is_refused(self, models):
        m1, _ = models
        dec8 = paged_decoder(m1)
        dec16 = paged_decoder(m1, block_size=16)
        v1 = m1.make_decoder(paged=False, max_slots=2, max_seq=48)
        h = {
            "version": kv_transfer.HANDOFF_VERSION, "n_prompt": 10,
            "first_token": 3, "block_size": 8, "n_blocks": 2,
            "n_layers": 2, "n_kv_heads": 2, "head_dim": 8,
            "dtype": "float32", "layers": [],
        }
        ok, _ = kv_transfer.compatible(dec8, h)
        assert ok
        ok, why = kv_transfer.compatible(dec16, h)
        assert not ok and "block_size" in why
        ok, why = kv_transfer.compatible(v1, h)
        assert not ok and "paged" in why
        ok, why = kv_transfer.compatible(dec8, dict(h, version=99))
        assert not ok and "version" in why
        bad = dict(h)
        del bad["first_token"]
        ok, why = kv_transfer.compatible(dec8, bad)
        assert not ok and "missing" in why
        ok, why = kv_transfer.compatible(dec8, dict(h, n_blocks=99))
        assert not ok and "blocks" in why


class TestPrefillOnlyEngine:
    def test_prefill_only_skips_decode(self, models):
        m1, _ = models
        # prefix_caching off so the block accounting below is exact
        # (the radix insert would pin the prompt's blocks — by design)
        eng = Engine(paged_decoder(m1), prefix_caching=False)
        fut = eng.submit(Request(
            prompt=list(PROMPTS[1]), max_tokens=6, prefill_only=True
        ))
        eng.run_until_idle()
        res = fut.result(timeout=0)
        assert res.status == "ok"
        assert res.finish_reason == "prefilled"
        assert len(res.tokens) == 1   # the first sampled token only
        assert res.ttft_s is not None
        assert res.handoff["first_token"] == res.tokens[0]
        # the engine's slots and blocks are free again
        assert eng.active_slots() == 0
        assert eng.paging_stats()["allocator"]["blocks_in_use"] == 0

    def test_handoff_admission_reserves_first_decode_block(
        self, models
    ):
        """A prompt ending exactly on a block boundary ships
        blocks_for(plen) blocks, but admission must reserve
        blocks_for(plen+1) — the NORMAL admission contract — so the
        guaranteed first decode write can never hit a dry pool and
        silently truncate an 'ok' result to one token."""
        m1, _ = models
        src = Engine(paged_decoder(m1), prefix_caching=False)
        prompt = list(range(1, 17))          # 16 = 2 full blocks
        fut = src.submit(Request(
            prompt=prompt, max_tokens=4, prefill_only=True
        ))
        src.run_until_idle()
        h = fut.result(timeout=0).handoff
        assert h["n_blocks"] == 2
        dst = Engine(paged_decoder(m1), prefix_caching=False)
        fut = dst.submit(Request(
            prompt=prompt, max_tokens=4, handoff=h
        ))
        dst._admit(time.monotonic())
        slot = next(
            i for i, s in enumerate(dst._slots) if s is not None
        )
        assert dst._mgr.n_owned[slot] == 3   # blocks_for(16 + 1)
        dst.run_until_idle()
        assert len(fut.result(timeout=0).tokens) == 4

    def test_max_tokens_one_finishes_without_handoff(self, models):
        m1, _ = models
        eng = Engine(paged_decoder(m1))
        fut = eng.submit(Request(
            prompt=list(PROMPTS[1]), max_tokens=1, prefill_only=True
        ))
        eng.run_until_idle()
        res = fut.result(timeout=0)
        assert res.finish_reason == "max_tokens"
        assert res.handoff is None


# -- fleet layer -------------------------------------------------------------


class TestDisaggregatedFleet:
    def test_prefill_a_decode_b_bitwise_equals_unified(
        self, models, unified_ref
    ):
        """THE acceptance bar: prefill on A, decode on B, greedy ids
        bitwise-equal to the unified run; every request reports a
        TTFT and the handoffs are counted."""
        m1, _ = models
        pre = InProcessReplica(
            Engine(paged_decoder(m1)), name="p0", role="prefill"
        ).start()
        dec = InProcessReplica(
            Engine(paged_decoder(m1)), name="d0", index=1,
            role="decode",
        ).start()
        router = make_router([pre, dec])
        try:
            rs = run_fleet(router, n=6)
            assert all(r.status == "ok" for r in rs)
            assert [r.tokens for r in rs] == unified_ref
            assert all(r.ttft_s is not None for r in rs)
            summ = router.fleet_summary()
            assert summ["n_handoffs"] == 6
            assert summ["dispatched"]["p0"] == 6
            assert summ["dispatched"]["d0"] == 6
            # the decode specialist never ran a prefill: its replica-
            # side completions all report the handoff admission path
            assert summ["members"]["p0"]["role"] == "prefill"
        finally:
            teardown(router, [pre, dec])

    def test_tp_width_mismatch_prefill1_decode2(
        self, models, unified_ref
    ):
        """Prefill at tp=1, decode at tp=2: the handoff's GLOBAL
        kv-head layout re-splits over the receiver's mesh — ids stay
        bitwise-equal to the tp=1 unified run (the samplers are
        layout-invariant, and now the transferred KV is too)."""
        m1, m2 = models
        pre = InProcessReplica(
            Engine(paged_decoder(m1)), name="p0", role="prefill"
        ).start()
        dec = InProcessReplica(
            Engine(paged_decoder(m2)), name="d0", index=1,
            role="decode",
        ).start()
        router = make_router([pre, dec])
        try:
            rs = run_fleet(router, n=4)
            assert all(r.status == "ok" for r in rs)
            assert [r.tokens for r in rs] == unified_ref[:4]
            assert router.fleet_summary()["n_handoffs"] == 4
        finally:
            teardown(router, [pre, dec])

    def test_prefiller_alone_serves_end_to_end(
        self, models, unified_ref
    ):
        """Role purity yields to availability: with no decode-capable
        member, the prefill specialist serves the request fully
        (unified-mode dispatch, no handoff)."""
        m1, _ = models
        pre = InProcessReplica(
            Engine(paged_decoder(m1)), name="p0", role="prefill"
        ).start()
        router = make_router([pre])
        try:
            rs = run_fleet(router, n=3)
            assert all(r.status == "ok" for r in rs)
            assert [r.tokens for r in rs] == unified_ref[:3]
            assert router.fleet_summary()["n_handoffs"] == 0
        finally:
            teardown(router, [pre])

    def test_incompatible_receiver_falls_back_token_exact(
        self, models, unified_ref
    ):
        """The decode specialist's block size differs: its engine
        sheds the handoff ("handoff_failed"), the router drops the
        record and the FULL prompt retries end-to-end — token-exact,
        nothing lost."""
        m1, _ = models
        pre = InProcessReplica(
            Engine(paged_decoder(m1)), name="p0", role="prefill"
        ).start()
        dec = InProcessReplica(
            Engine(paged_decoder(m1, block_size=16)), name="d0",
            index=1, role="decode",
        ).start()
        router = make_router([pre, dec])
        try:
            rs = run_fleet(router, n=4)
            assert all(r.status == "ok" for r in rs)
            assert [r.tokens for r in rs] == unified_ref[:4]
            summ = router.fleet_summary()
            assert summ["n_handoffs"] >= 1     # the attempt happened
            assert summ["n_requeues"] >= 1     # and fell back
        finally:
            teardown(router, [pre, dec])

    def test_kill_prefiller_mid_handoff_token_exact(
        self, models, unified_ref, monkeypatch
    ):
        """Extend the kill-one-of-3 drill to the disaggregated fleet:
        ``die_replica`` kills the PREFILL specialist on its busy-
        iteration clock (prefill chunks in flight).  The router
        requeues its work; with no prefiller left the fleet falls
        back to unified service — every request completes with the
        unified run's exact ids and the failover is recorded."""
        m1, _ = models
        reset_fault_cache()
        monkeypatch.setenv("TM_FAULT_AT", "0:2:die_replica")
        try:
            pre = InProcessReplica(
                Engine(paged_decoder(m1)), name="p0", index=0,
                role="prefill",
            ).start()
            d1 = InProcessReplica(
                Engine(paged_decoder(m1)), name="d1", index=1,
                role="decode",
            ).start()
            d2 = InProcessReplica(
                Engine(paged_decoder(m1)), name="d2", index=2,
                role="decode",
            ).start()
            router = make_router([pre, d1, d2])
            try:
                rs = run_fleet(router, n=6)
                assert all(r.status == "ok" for r in rs)
                assert [r.tokens for r in rs] == unified_ref
                assert pre.dead
                assert "ReplicaDied" in pre.death_cause
                summ = router.fleet_summary()
                assert summ["n_requeues"] >= 1
                assert summ["n_failovers"] >= 1
                assert summ["n_completed"] == 6
                assert summ["members"]["p0"]["healthy"] is False
            finally:
                teardown(router, [pre, d1, d2])
        finally:
            reset_fault_cache()

    def test_handoff_crosses_tcp_wire_bitwise(
        self, models, unified_ref
    ):
        """The deployment shape: prefiller and decoder in (thread-
        hosted) TCP replica servers — the KV payload rides the
        center-server pickle frames both ways and ids stay
        bitwise-equal."""
        m1, _ = models
        srv_p = ReplicaServer(
            Engine(paged_decoder(m1)), name="p0", index=0,
            role="prefill",
        ).start()
        srv_d = ReplicaServer(
            Engine(paged_decoder(m1)), name="d0", index=1,
            role="decode",
        ).start()
        cp = TCPReplicaClient(srv_p.address, name="p0",
                              role="prefill", ping_interval_s=0.01)
        cd = TCPReplicaClient(srv_d.address, name="d0",
                              role="decode", ping_interval_s=0.01)
        router = make_router([cp, cd])
        try:
            rs = run_fleet(router, n=4)
            assert all(r.status == "ok" for r in rs)
            assert [r.tokens for r in rs] == unified_ref[:4]
            assert router.fleet_summary()["n_handoffs"] == 4
        finally:
            router.stop(drain_s=5.0)
            cp.close()
            cd.close()
            srv_p.stop()
            srv_d.stop()

    def test_drained_decode_specialist_never_drops(
        self, models, unified_ref
    ):
        """Scale-down drain mid-stream: the decode specialist holding
        in-flight handoff work drains (requeued UNCHARGED — even
        max_requeues=0 must not shed "failover") and the fleet
        completes token-exact on the survivor."""
        m1, _ = models
        pre = InProcessReplica(
            Engine(paged_decoder(m1)), name="p0", role="prefill"
        ).start()
        d1 = InProcessReplica(
            Engine(paged_decoder(m1)), name="d1", index=1,
            role="decode",
        ).start()
        d2 = InProcessReplica(
            Engine(paged_decoder(m1)), name="d2", index=2,
            role="decode",
        ).start()
        router = make_router([pre, d1, d2], max_requeues=0)
        try:
            futs = [
                router.submit(PROMPTS[i], max_tokens=6, seed=i)
                for i in range(6)
            ]
            # let dispatches land, then retire d1 mid-stream
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline and \
                    router.recorder.dispatched["d1"] == 0:
                time.sleep(0.005)
            router.drain_replica("d1")
            router.remove_replica("d1")
            rs = [f.result(timeout=240.0) for f in futs]
            assert all(r.status == "ok" for r in rs)
            assert [r.tokens for r in rs] == unified_ref
            assert "d1" not in router.members()
            # the retired member's final telemetry snapshot survives
            # in the fleet recorder (conservation across membership
            # change)
            assert "d1" in router.fleet_summary()["per_replica"]
        finally:
            teardown(router, [pre, d1, d2])
