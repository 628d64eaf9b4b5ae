"""Trace integrity on the REAL serving stack (engine → fleet →
disaggregation → faults): every completed request yields ONE
connected span tree at its dispatcher, rooted at submit, with
requeue generations ordered — through the kill-one-of-3
(``die_replica``) and kill-the-prefiller drills, and (slow tier)
across two real replica PROCESSES over the TCP wire with the prefill
specialist killed mid-handoff — the ISSUE 14 acceptance drill.
``critical_path`` must attribute ≥95% of each request's wall time to
named legs.
"""

import time

import pytest

from theanompi_tpu.models.llama import Llama
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.obs import (
    Tracer,
    chrome_trace,
    critical_path,
    span_tree,
)
from theanompi_tpu.serving import Engine, InProcessReplica, Router
from theanompi_tpu.utils.faults import reset_fault_cache

pytestmark = pytest.mark.serving

SMALL = dict(
    dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
    vocab=64, seq_len=64, batch_size=4, lr=1e-2,
    n_train=64, n_val=32, compute_dtype="float32", remat=False,
)

PROMPTS = [
    [1 + i, 5, 9, 3 + i, 17, 2, 4, 8, 6, 11 + i] for i in range(6)
]

DEC_KW = dict(max_slots=2, max_seq=48, block_size=8, prefill_chunk=8)


@pytest.fixture(scope="module")
def model1(devices8, tmp_path_factory):
    m = Llama(dict(SMALL, tp=1))
    m.build_model(n_replicas=1)
    m.compile_iter_fns(
        mesh=make_mesh(data=1, model=1, devices=devices8[:1])
    )
    return m


def traced_engine(model, sample=1, **ekw):
    tr = Tracer(process="engine0", sample=sample)
    dec = model.make_decoder(paged=True, **DEC_KW)
    return Engine(dec, tracer=tr, **ekw)


def traced_replicas(model, n, roles=None):
    reps = []
    for i in range(n):
        dec = model.make_decoder(paged=True, **DEC_KW)
        tr = Tracer(process=f"replica{i}", sample=1)
        reps.append(InProcessReplica(
            Engine(dec, tracer=tr), name=f"replica{i}", index=i,
            role=(roles[i] if roles else "unified"),
        ).start())
    return reps


def traced_router(reps, **kw):
    kw.setdefault("policy", "round_robin")
    kw.setdefault("health_interval_s", 0.005)
    kw.setdefault("startup_grace_s", 120.0)
    kw.setdefault("trace_sample", 1)
    return Router(reps, **kw).start()


def teardown(router, reps):
    router.stop(drain_s=5.0)
    for r in reps:
        r.stop()


def assert_connected(spans, trace_id, min_coverage=0.95):
    rep = span_tree(spans, trace_id)
    assert rep["connected"], rep
    assert rep["root_name"] == "request"
    cp = critical_path(spans, trace_id)
    assert cp["coverage"] >= min_coverage, cp
    return rep, cp


def assert_generations_ordered(spans, trace_id):
    """Requeue generations must be ordered: later dispatch spans
    start no earlier than earlier generations."""
    dispatches = sorted(
        (s for s in spans
         if s["trace_id"] == trace_id and s["name"] == "dispatch"),
        key=lambda s: s["attrs"]["gen"],
    )
    gens = [s["attrs"]["gen"] for s in dispatches]
    assert gens == sorted(gens) and len(set(gens)) == len(gens)
    for a, b in zip(dispatches, dispatches[1:]):
        assert a["t0"] <= b["t0"] + 1e-6


class TestEngineTracing:
    def test_each_request_yields_connected_tree(self, model1):
        eng = traced_engine(model1)
        futs = [eng.submit(PROMPTS[i], max_tokens=5, seed=i)
                for i in range(4)]
        eng.run_until_idle()
        for f in futs:
            r = f.result(timeout=5)
            assert r.status == "ok"
            tids = {s["trace_id"] for s in r.spans}
            assert len(tids) == 1
            assert_connected(r.spans, tids.pop())
            names = {s["name"] for s in r.spans}
            assert {"request", "engine_queue", "prefill",
                    "prefill_chunk", "decode"} <= names
        # span-count conservation: one root per request, none lost
        roots = [s for s in eng.tracer.spans()
                 if s["parent_id"] is None]
        assert len(roots) == 4

    def test_chunk_spans_parent_under_prefill(self, model1):
        eng = traced_engine(model1)
        fut = eng.submit(PROMPTS[0], max_tokens=3)
        eng.run_until_idle()
        spans = fut.result(5).spans
        pf = next(s for s in spans if s["name"] == "prefill")
        chunks = [s for s in spans if s["name"] == "prefill_chunk"]
        assert chunks and all(
            c["parent_id"] == pf["span_id"] for c in chunks
        )
        # 10-token prompt, chunk 8 -> 2 chunks
        assert len(chunks) == 2

    def test_shed_flight_record_forced(self, model1):
        eng = traced_engine(model1, sample=10_000)
        # structurally oversized prompt sheds at submit — and the
        # shed is force-sampled despite the 1/10k rate
        fut = eng.submit([1] * 100, max_tokens=2)
        r = fut.result(timeout=5)
        assert r.status == "shed"
        assert any(s["name"] == "engine_queue" for s in r.spans)

    def test_untraced_engine_has_no_spans(self, model1):
        dec = model1.make_decoder(paged=True, **DEC_KW)
        eng = Engine(dec)
        fut = eng.submit(PROMPTS[0], max_tokens=3)
        eng.run_until_idle()
        assert fut.result(5).spans == []
        assert eng.tracer is None


class TestFleetTraceIntegrity:
    def test_kill_one_of_three_trees_survive(self, model1,
                                             monkeypatch):
        monkeypatch.setenv("TM_FAULT_AT", "1:2:die_replica")
        reset_fault_cache()
        reps = traced_replicas(model1, 3)
        router = traced_router(reps)
        try:
            futs = [
                router.submit(PROMPTS[i], max_tokens=5, seed=i)
                for i in range(6)
            ]
            rs = [f.result(timeout=180) for f in futs]
            assert all(r.status == "ok" for r in rs)
            assert router.recorder.n_failovers >= 1
            spans = router.collect_spans()
            requeued = 0
            for f in futs:
                assert_connected(spans, f.trace_id)
                assert_generations_ordered(spans, f.trace_id)
                names = {s["name"] for s in spans
                         if s["trace_id"] == f.trace_id}
                if "requeue" in names:
                    requeued += 1
                    procs = span_tree(spans, f.trace_id)["processes"]
                    # the failover trace covers the dead member's
                    # salvaged leg AND the retry member
                    assert len([p for p in procs
                                if p.startswith("replica")]) >= 2
            assert requeued >= 1
            # span-count conservation at the router: one root per
            # submitted request
            roots = [s for s in spans if s["parent_id"] is None]
            assert len(roots) == len(futs)
            # the export parses end to end
            import json

            json.loads(json.dumps(chrome_trace(spans)))
        finally:
            # teardown FIRST: the replica loops' last iterations
            # still parse TM_FAULT_AT, so resetting the cache before
            # they stop would let them re-cache the stale spec past
            # monkeypatch's env restore (it then fires in the NEXT
            # test that reaches the same (index, tick))
            teardown(router, reps)
            reset_fault_cache()

    def test_kill_the_prefiller_mid_handoff(self, model1,
                                            monkeypatch):
        """Disaggregated requests: prefill specialist killed on its
        busy-iteration clock with handoffs in flight — every tree
        stays connected; at least one covers the prefill leg, the
        decode leg, and a requeue."""
        monkeypatch.setenv("TM_FAULT_AT", "0:4:die_replica")
        reset_fault_cache()
        reps = traced_replicas(model1, 3,
                               roles=["prefill", "decode", "unified"])
        router = traced_router(reps)
        try:
            futs = [
                router.submit(PROMPTS[i], max_tokens=5, seed=i)
                for i in range(6)
            ]
            rs = [f.result(timeout=180) for f in futs]
            assert all(r.status == "ok" for r in rs)
            assert router.recorder.n_handoffs >= 1
            assert reps[0].dead          # the drill fired
            spans = router.collect_spans()
            disagg = requeued = 0
            for f in futs:
                assert_connected(spans, f.trace_id)
                assert_generations_ordered(spans, f.trace_id)
                names = {s["name"] for s in spans
                         if s["trace_id"] == f.trace_id}
                if "handoff" in names:
                    disagg += 1
                if "requeue" in names:
                    requeued += 1
            assert disagg >= 1 and requeued >= 1
        finally:
            # teardown FIRST: the replica loops' last iterations
            # still parse TM_FAULT_AT, so resetting the cache before
            # they stop would let them re-cache the stale spec past
            # monkeypatch's env restore (it then fires in the NEXT
            # test that reaches the same (index, tick))
            teardown(router, reps)
            reset_fault_cache()


@pytest.mark.slow
class TestTCPAcceptanceDrill:
    def test_disagg_over_tcp_with_prefiller_killed(
        self, devices8, tmp_path, monkeypatch
    ):
        """ISSUE 14 acceptance: prefill-on-A / decode-on-B over the
        real TCP wire (two replica PROCESSES), prefill replica killed
        mid-handoff → ONE connected span tree at the router covering
        both processes and the requeue; ``critical_path`` attributes
        ≥95% of wall time to named legs.  Also drives the ``trace``
        and ``metrics`` frames."""
        import json
        import os
        import subprocess
        import sys

        m = Llama(dict(SMALL, tp=1))
        m.build_model(n_replicas=1)
        m.compile_iter_fns(
            mesh=make_mesh(data=1, model=1, devices=devices8[:1])
        )
        ck = tmp_path / "ck"
        m.save(str(ck))

        from theanompi_tpu.serving import TCPReplicaClient

        def spawn(index, role, extra_env=None):
            spec = {
                "config": dict(SMALL, tp=1),
                "checkpoint": str(ck),
                "paged": True,
                "decoder": DEC_KW,
                "name": f"proc{index}", "index": index,
                "role": role, "trace_sample": 1,
                # the child sees all 16 virtual devices: each
                # replica names its own
                "devices": [index],
            }
            env = dict(os.environ)
            env.update(JAX_PLATFORMS="cpu", **(extra_env or {}))
            env.pop("TM_FAULT_STATE", None)
            p = subprocess.Popen(
                [sys.executable, "-m",
                 "theanompi_tpu.serving.replica", "--spec-json",
                 json.dumps(spec)],
                env=env, stdout=subprocess.PIPE, text=True,
            )
            for line in p.stdout:
                if line.startswith("REPLICA_READY"):
                    port = int(line.split()[1])
                    return p, TCPReplicaClient(
                        ("127.0.0.1", port), name=f"proc{index}",
                        role=role,
                    )
            raise RuntimeError("replica child died before ready")

        # A: prefill specialist with the kill drill on its busy
        # clock; B: decode specialist
        pa, ca = spawn(0, "prefill",
                       {"TM_FAULT_AT": "0:6:die_replica"})
        pb, cb = spawn(1, "decode")
        router = Router(
            [ca, cb], policy="round_robin",
            health_interval_s=0.02, startup_grace_s=300.0,
            trace_sample=1,
        ).start()
        try:
            futs = [
                router.submit(PROMPTS[i], max_tokens=5, seed=i)
                for i in range(6)
            ]
            rs = [f.result(timeout=300) for f in futs]
            assert all(r.status == "ok" for r in rs)
            assert router.recorder.n_handoffs >= 1
            assert router.recorder.n_requeues >= 1
            spans = router.collect_spans()
            covering = 0
            for f in futs:
                rep, cp = assert_connected(spans, f.trace_id)
                assert_generations_ordered(spans, f.trace_id)
                names = {s["name"] for s in spans
                         if s["trace_id"] == f.trace_id}
                procs = set(rep["processes"])
                if {"proc0", "proc1"} <= procs \
                        and "requeue" in names:
                    covering += 1
                    assert cp["coverage"] >= 0.95
            # the acceptance tree: both processes AND the requeue
            assert covering >= 1
            # the export parses; metrics ride the wire
            out = tmp_path / "trace.json"
            router.export_trace(out)
            json.loads(out.read_text())
            txt = cb.metrics_txt()
            assert "tm_serving_requests_total" in txt
            assert "tm_fleet_requeues_total" in router.metrics_txt()
        finally:
            router.stop(drain_s=5.0)
            for proc, client in ((pa, ca), (pb, cb)):
                client.shutdown()
                client.close()
                proc.terminate()
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()


class TestHandoffCarriesTrace:
    def test_routerless_handoff_joins_prefill_trace(self, model1):
        """A handoff consumed WITHOUT a router: the record's embedded
        context still joins the decode leg to the prefill trace."""
        from theanompi_tpu.serving.engine import Request

        pre = traced_engine(model1)
        fut = pre.submit(Request(prompt=PROMPTS[0], max_tokens=5,
                                 prefill_only=True))
        pre.run_until_idle()
        r = fut.result(5)
        assert r.finish_reason == "prefilled"
        assert r.handoff.get("trace") is not None
        dec_eng = traced_engine(model1)
        fut2 = dec_eng.submit(Request(
            prompt=PROMPTS[0], max_tokens=5, handoff=r.handoff,
        ))
        dec_eng.run_until_idle()
        r2 = fut2.result(5)
        assert r2.status == "ok"
        tids = {s["trace_id"] for s in r2.spans}
        assert tids == {r.handoff["trace"]["trace_id"]}
        assert any(s["name"] == "handoff_import" for s in r2.spans)
        # the stitched two-engine trace is ONE connected tree: the
        # handoff context is re-parented under the prefill root, so
        # the decode leg's spans hang off it instead of floating
        combined = {s["span_id"]: s for s in r.spans + r2.spans}
        assert_connected(list(combined.values()), tids.pop())


class TestV1EngineTracing:
    def test_slot_contiguous_decoder_traces_too(self, model1):
        """The v1 (non-paged) engine path: fenced prefill span +
        decode span, one connected tree per request."""
        tr = Tracer(process="v1", sample=1)
        dec = model1.make_decoder(max_slots=2, max_seq=48)
        eng = Engine(dec, tracer=tr)
        futs = [eng.submit(PROMPTS[i], max_tokens=4, seed=i)
                for i in range(3)]
        eng.run_until_idle()
        for f in futs:
            r = f.result(timeout=5)
            assert r.status == "ok"
            tid = {s["trace_id"] for s in r.spans}.pop()
            assert_connected(r.spans, tid)
            names = {s["name"] for s in r.spans}
            assert {"request", "engine_queue", "prefill",
                    "decode"} <= names
            assert "prefill_chunk" not in names   # v1 has no chunks
