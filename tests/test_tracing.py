"""Distributed request tracing (theanompi_tpu/obs) + bounded
recorder satellites.

The contract under test, layer by layer:

- TRACER: bounded ring (overflow drops the OLDEST WHOLE TRACE, never
  a partial tree; stragglers of a dropped trace are dropped too),
  1/N sampling with mid-flight forcing, open-span snapshots
  (children of a still-open span never orphan), ingest dedup with
  closed-beats-open replacement.
- EXPORT: Chrome-trace/Perfetto JSON parses with process/thread
  lanes; ``critical_path`` attributes ~100% of a root interval to
  named legs in time order.
- ENGINE/ROUTER: every sampled request yields ONE connected span
  tree at the dispatcher; span context rides ``Request.trace`` and
  the results' flight records stitch replica spans under the
  router's dispatch spans; shed/failover force-sample.
- FAULT INTEGRITY: kill-one-of-3 (``die_replica``) and
  kill-the-prefiller drills — every completed request's tree is
  connected, rooted at submit, requeue generations ordered; the
  dead member's in-flight spans are salvaged from the wreck.
- BOUNDED RECORDER: aggregates stay exact past the sample cap;
  merged fleet percentiles track the pooled distribution on a known
  distribution; Prometheus text exposition parses with stable names.
"""

import json
import re
import threading
import time

import numpy as np
import pytest

from theanompi_tpu.obs import (
    Tracer,
    child_context,
    critical_path,
    force_sample,
    make_context,
    render_metrics,
    span_tree,
    write_chrome_trace,
)
from theanompi_tpu.serving.engine import Request, Result, ServingFuture
from theanompi_tpu.serving.router import Router
from theanompi_tpu.utils.recorder import (
    FleetRecorder,
    Reservoir,
    ServingRecorder,
)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


class TestTracer:
    def test_sampling_every_nth_trace(self):
        tr = Tracer(sample=3)
        flags = [tr.new_context()["sampled"] for _ in range(9)]
        assert flags == [True, False, False] * 3

    def test_force_overrides_sampling(self):
        tr = Tracer(sample=1000)
        assert tr.new_context(force=True)["sampled"]

    def test_unsampled_spans_not_recorded_until_forced(self):
        tr = Tracer(sample=2)
        tr.new_context()                    # burn the sampled slot
        ctx = tr.new_context()
        assert not ctx["sampled"]
        h = tr.start_span(ctx, "a")
        assert tr.end_span(h) is None
        assert tr.spans(ctx["trace_id"]) == []
        # forcing mid-flight records everything that ends AFTER
        h2 = tr.start_span(ctx, "b")
        force_sample(ctx)
        assert tr.end_span(h2) is not None
        assert [s["name"] for s in tr.spans(ctx["trace_id"])] == ["b"]

    def test_record_span_retroactive(self):
        tr = Tracer()
        ctx = tr.new_context()
        t = tr.clock()
        sid = tr.record_span(ctx, "request", t - 1.0, t, status="shed")
        (s,) = tr.spans(ctx["trace_id"])
        assert s["span_id"] == sid and s["attrs"]["status"] == "shed"
        assert s["t1"] - s["t0"] == pytest.approx(1.0)

    def test_context_helpers_are_wire_shaped(self):
        ctx = make_context(7, None, True)
        child = child_context(ctx, 42)
        assert child == {"trace_id": 7, "parent_id": 42,
                         "sampled": True}
        json.dumps(child)   # rides the TCP frames as-is

    def test_ring_overflow_drops_oldest_whole_trace(self):
        tr = Tracer(capacity=6)
        ctxs = [tr.new_context() for _ in range(3)]
        for ctx in ctxs:
            for i in range(3):
                t = tr.clock()
                tr.record_span(ctx, f"s{i}", t, t)
        # the 7th span tips past capacity: the OLDEST trace is
        # evicted whole (3 spans at once), never span-by-span
        ids = tr.trace_ids()
        assert ids == [ctxs[1]["trace_id"], ctxs[2]["trace_id"]]
        assert len(tr.spans()) == 6
        assert tr.stats()["n_dropped_traces"] == 1
        assert tr.stats()["n_dropped_spans"] == 3
        # surviving traces are complete trees, not fragments
        for ctx in ctxs[1:]:
            assert len(tr.spans(ctx["trace_id"])) == 3

    def test_straggler_of_dropped_trace_stays_dropped(self):
        tr = Tracer(capacity=2)
        old = tr.new_context()
        t = tr.clock()
        tr.record_span(old, "a", t, t)
        new = tr.new_context()
        tr.record_span(new, "b", t, t)
        tr.record_span(new, "c", t, t)   # evicts `old` whole
        assert old["trace_id"] not in tr.trace_ids()
        # a late span of the dropped trace must not resurrect a
        # partial tree
        tr.record_span(old, "late", t, t)
        assert old["trace_id"] not in tr.trace_ids()

    def test_current_trace_never_evicted(self):
        tr = Tracer(capacity=2)
        ctx = tr.new_context()
        t = tr.clock()
        for i in range(5):   # one trace larger than the ring: kept
            tr.record_span(ctx, f"s{i}", t, t)
        assert len(tr.spans(ctx["trace_id"])) == 5

    def test_ingest_dedup_and_closed_beats_open(self):
        a, b = Tracer(process="a"), Tracer(process="b")
        ctx = a.new_context()
        h = a.start_span(ctx, "work")
        open_snapshot = a.spans(ctx["trace_id"])
        assert open_snapshot[0]["attrs"]["open"] is True
        b.ingest(open_snapshot)
        b.ingest(open_snapshot)              # dedup: no double
        assert len(b.spans(ctx["trace_id"])) == 1
        a.end_span(h)
        closed = a.spans(ctx["trace_id"])
        assert "open" not in closed[0]["attrs"]
        b.ingest(closed)                     # closed replaces open
        (s,) = b.spans(ctx["trace_id"])
        assert "open" not in s["attrs"]

    def test_open_span_children_never_orphan(self):
        tr = Tracer()
        ctx = tr.new_context()
        root = tr.start_span(ctx, "request")
        t = tr.clock()
        tr.record_span(ctx, "child", t, t,
                       parent_id=root["span_id"])
        # root still open — the snapshot keeps the tree connected
        rep = span_tree(tr.spans(), ctx["trace_id"])
        assert rep["connected"] and rep["root_name"] == "request"

    def test_thread_safety_smoke(self):
        tr = Tracer(capacity=256)

        def worker(k):
            for _ in range(200):
                ctx = tr.new_context()
                with tr.span(ctx, f"w{k}"):
                    pass

        ts = [threading.Thread(target=worker, args=(k,))
              for k in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert tr.stats()["n_spans"] <= 256


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _mk(tid, sid, parent, name, t0, t1, process="p", lane=None):
    return {"trace_id": tid, "span_id": sid, "parent_id": parent,
            "name": name, "t0": t0, "t1": t1, "process": process,
            "lane": lane or process, "attrs": {}}


class TestExport:
    def test_chrome_trace_parses_with_lanes(self, tmp_path):
        spans = [
            _mk(1, 10, None, "request", 0.0, 1.0, "router"),
            _mk(1, 11, 10, "dispatch", 0.1, 0.9, "router"),
            _mk(1, 12, 11, "decode", 0.2, 0.8, "replica0", "decode"),
        ]
        path = tmp_path / "trace.json"
        write_chrome_trace(spans, path)
        d = json.loads(path.read_text())
        events = d["traceEvents"]
        names = {e["args"]["name"] for e in events
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {"router", "replica0"}
        xs = [e for e in events if e["ph"] == "X"]
        assert len(xs) == 3
        assert all(e["dur"] >= 0 for e in xs)
        # two distinct process lanes
        assert len({e["pid"] for e in xs}) == 2

    def test_span_tree_detects_orphans_and_roots(self):
        spans = [
            _mk(1, 10, None, "request", 0.0, 1.0),
            _mk(1, 11, 10, "a", 0.1, 0.5),
            _mk(1, 12, 99, "lost", 0.6, 0.7),
        ]
        rep = span_tree(spans, 1)
        assert not rep["connected"] and rep["orphans"] == [12]
        rep2 = span_tree(spans[:2], 1)
        assert rep2["connected"] and rep2["root_name"] == "request"

    def test_critical_path_serial_chain(self):
        spans = [
            _mk(1, 10, None, "request", 0.0, 10.0, "router"),
            _mk(1, 11, 10, "dispatch", 1.0, 9.0, "router"),
            _mk(1, 12, 11, "prefill", 1.5, 4.0, "rep0"),
            _mk(1, 13, 11, "decode", 4.5, 8.5, "rep0"),
        ]
        rep = critical_path(spans, 1)
        assert rep["coverage"] == pytest.approx(1.0)
        names = [leg["name"] for leg in rep["legs"]]
        assert names == [
            "request:self", "dispatch:self", "prefill",
            "dispatch:self", "decode", "dispatch:self",
            "request:self",
        ]
        # legs are in time order and partition the root interval
        assert [round(leg["dur_s"], 6) for leg in rep["legs"]] == [
            1.0, 0.5, 2.5, 0.5, 4.0, 0.5, 1.0,
        ]

    def test_critical_path_follows_last_finishing_overlap(self):
        # two overlapping children: the chain follows the one whose
        # completion gated the parent
        spans = [
            _mk(1, 10, None, "request", 0.0, 10.0),
            _mk(1, 11, 10, "fast", 1.0, 4.0),
            _mk(1, 12, 10, "slow", 2.0, 9.0),
        ]
        rep = critical_path(spans, 1)
        names = [leg["name"] for leg in rep["legs"]]
        assert "slow" in names
        slow = next(leg for leg in rep["legs"]
                    if leg["name"] == "slow")
        assert slow["dur_s"] == pytest.approx(7.0)

    def test_critical_path_clamps_clock_skew(self):
        # a child slightly exceeding its parent (cross-process wall
        # offset error) is clamped, never inflates coverage past 1
        spans = [
            _mk(1, 10, None, "request", 0.0, 1.0),
            _mk(1, 11, 10, "decode", 0.5, 1.002),
        ]
        rep = critical_path(spans, 1)
        assert rep["coverage"] <= 1.0 + 1e-6


# ---------------------------------------------------------------------------
# bounded recorder (satellite)
# ---------------------------------------------------------------------------


class TestReservoir:
    def test_exact_below_cap(self):
        r = Reservoir(cap=100)
        for x in range(50):
            r.add(float(x))
        assert sorted(r.xs) == [float(x) for x in range(50)]
        assert r.percentile(50) == pytest.approx(24.5)

    def test_bounded_past_cap(self):
        r = Reservoir(cap=64)
        for x in range(10_000):
            r.add(float(x))
        assert len(r.xs) == 64 and r.n == 10_000

    def test_merge_tracks_pooled_distribution(self):
        # the satellite's acceptance: merged fleet percentiles stay
        # within tolerance of exact on a known distribution
        rng = np.random.default_rng(7)
        xs = rng.lognormal(0.0, 1.0, 24_000)
        parts = np.array_split(xs, 3)
        fleet = ServingRecorder(max_slots=0, max_samples=1024)
        for i, part in enumerate(parts):
            r = ServingRecorder(max_slots=1, max_samples=1024,
                                seed=i + 1)
            for x in part:
                r.record_request(
                    status="ok", finish_reason="eos", n_prompt=1,
                    n_generated=1, ttft_s=float(x),
                )
            fleet.merge(r)
        s = fleet.summary()
        assert s["n_completed"] == 24_000        # counters exact
        for q, key in ((50, "ttft_p50_s"), (95, "ttft_p95_s")):
            exact = float(np.percentile(xs, q))
            assert abs(s[key] - exact) / exact < 0.10, (q, s[key],
                                                        exact)


class TestBoundedServingRecorder:
    def fill(self, r, n):
        for i in range(n):
            r.record_request(
                status="ok", finish_reason="eos", n_prompt=4,
                n_generated=2, ttft_s=0.01 * (i + 1), tpot_s=0.001,
                n_prefix_hit=1,
            )
            r.record_step(active_slots=1, queue_depth=i % 3,
                          dt_s=0.5, tokens=1)

    def test_raw_windows_bounded_counters_exact(self):
        r = ServingRecorder(max_slots=2, max_samples=32)
        self.fill(r, 500)
        assert len(r.requests) == 32 and len(r.steps) == 32
        s = r.summary()
        assert s["n_completed"] == 500
        assert s["tokens_completed"] == 1000
        assert s["tokens_generated"] == 500
        assert s["decode_s"] == pytest.approx(250.0)
        assert s["slot_occupancy"] == pytest.approx(0.5)
        assert s["prefix_hit_rate"] == pytest.approx(0.25)
        assert s["queue_depth_max"] == 2

    def test_state_dict_round_trip_preserves_aggregates(self):
        r = ServingRecorder(max_slots=2, max_samples=16)
        self.fill(r, 100)
        d = json.loads(json.dumps(r.state_dict()))
        r2 = ServingRecorder()
        r2.load_state_dict(d)
        assert r2.summary()["n_completed"] == 100
        assert r2.summary()["tokens_generated"] == 100

    def test_old_format_state_still_loads_and_merges(self):
        # a pre-bounding peer ships raw lists only
        old = {
            "max_slots": 2,
            "requests": [
                {"status": "ok", "finish_reason": "eos",
                 "n_prompt": 3, "n_generated": 2, "ttft_s": 0.5,
                 "tpot_s": 0.01, "queued_s": None, "e2e_s": 0.6,
                 "n_prefix_hit": 0},
            ],
            "steps": [
                {"active_slots": 1, "queue_depth": 0, "dt_s": 1.0,
                 "tokens": 2, "blocks_in_use": None,
                 "blocks_free": None, "drafted": None,
                 "accepted": None},
            ],
            "blocks_in_use_max": None, "blocks_free_min": None,
        }
        r = ServingRecorder()
        r.load_state_dict(dict(old))
        assert r.summary()["n_completed"] == 1
        assert r.summary()["ttft_p50_s"] == pytest.approx(0.5)
        m = ServingRecorder(max_slots=0).merge(dict(old))
        assert m.summary()["tokens_generated"] == 2
        assert m.summary()["slot_occupancy"] == pytest.approx(0.5)


_METRIC_LINE = re.compile(
    r"^[a-z_][a-z0-9_]*(\{[^}]*\})? -?[0-9].*$|^# TYPE .+$"
)


def assert_prometheus_text(txt: str, must_have: tuple):
    assert txt.endswith("\n")
    for line in txt.strip().splitlines():
        assert _METRIC_LINE.match(line), line
    for name in must_have:
        assert name in txt, f"missing {name}:\n{txt}"


class TestMetricsTxt:
    def test_render_metrics_drops_none_and_escapes(self):
        txt = render_metrics([
            ("tm_x_total", "counter", [({"r": 'a"b'}, 2), (None, None)]),
            ("tm_gone", "gauge", [(None, None)]),
        ])
        assert 'tm_x_total{r="a\\"b"} 2' in txt
        assert "tm_gone" not in txt

    def test_serving_recorder_exposition(self):
        r = ServingRecorder(max_slots=2)
        r.record_request(status="ok", finish_reason="eos", n_prompt=4,
                         n_generated=3, ttft_s=0.1, tpot_s=0.01)
        r.record_request(status="shed", finish_reason="queue_full",
                         n_prompt=4, n_generated=0)
        r.record_step(active_slots=1, queue_depth=2, dt_s=0.5,
                      tokens=1)
        assert_prometheus_text(r.metrics_txt(), (
            'tm_serving_requests_total{status="ok"} 1',
            'tm_serving_sheds_total{reason="queue_full"} 1',
            "tm_serving_tokens_generated_total 1",
            'tm_serving_ttft_seconds{quantile="0.95"}',
            "tm_serving_slot_occupancy 0.5",
        ))

    def test_fleet_recorder_exposition(self):
        f = FleetRecorder()
        f.record_request(status="ok", finish_reason="eos", n_prompt=2,
                         n_generated=2, ttft_s=0.2)
        f.record_dispatch("r0")
        f.record_requeue(3)
        f.record_spawn("r0", t=0.0)
        f.record_retire("r0", t=2.0)
        r = ServingRecorder(max_slots=2)
        r.record_step(active_slots=2, queue_depth=0, dt_s=1.0,
                      tokens=4)
        f.attach_replica("r0", r.state_dict())
        assert_prometheus_text(f.metrics_txt(), (
            'tm_fleet_requests_total{status="ok"} 1',
            "tm_fleet_requeues_total 3",
            'tm_fleet_dispatched_total{replica="r0"} 1',
            "tm_fleet_replica_seconds 2.0",
            'tm_fleet_replica_tokens_per_sec{replica="r0"} 4.0',
        ))


# ---------------------------------------------------------------------------
# router tracing over scripted replicas (jax-free)
# ---------------------------------------------------------------------------


class FakeReplica:
    def __init__(self, name):
        self.name = name
        self._hb = {"progress": 0, "time": 0.0, "status": "running"}
        self._alive = True
        self.submitted = []

    def beat(self):
        self._hb = {"progress": self._hb["progress"] + 1,
                    "time": time.time(), "status": "running"}

    def submit(self, request):
        fut = ServingFuture()
        self.submitted.append((request, fut))
        return fut

    def resolve_all(self, spans=None):
        for req, fut in self.submitted:
            if not fut.done():
                fut._set(Result(
                    status="ok", finish_reason="max_tokens",
                    tokens=[1, 2], ttft_s=0.01, tpot_s=0.001,
                    e2e_s=0.02, spans=list(spans or ()),
                ))

    def load(self):
        return 0

    def heartbeat(self):
        return dict(self._hb)

    def alive(self):
        return self._alive

    def recorder_state(self):
        return ServingRecorder(max_slots=2).state_dict()

    def paging_stats(self):
        return None


def traced_router(fakes, **kw):
    kw.setdefault("policy", "round_robin")
    kw.setdefault("startup_grace_s", 60.0)
    kw.setdefault("trace_sample", 1)
    r = Router(fakes, **kw)
    for f in fakes:
        f.beat()
    r.check_health()
    return r


class TestRouterTracing:
    def test_dispatch_stamps_child_context_on_request(self):
        rep = FakeReplica("r0")
        router = traced_router([rep])
        fut = router.submit([1, 2, 3], max_tokens=2)
        req, _ = rep.submitted[0]
        assert req.trace is not None
        assert req.trace["trace_id"] == fut.trace_id
        assert req.trace["sampled"] is True
        # the stamped parent is the dispatch span's id
        spans = router.tracer.spans(fut.trace_id)
        dsp = next(s for s in spans if s["name"] == "dispatch")
        assert req.trace["parent_id"] == dsp["span_id"]
        rep.resolve_all()
        assert fut.result(5).status == "ok"
        rep2 = span_tree(router.tracer.spans(), fut.trace_id)
        assert rep2["connected"] and rep2["root_name"] == "request"

    def test_replica_flight_record_is_ingested(self):
        rep = FakeReplica("r0")
        router = traced_router([rep])
        fut = router.submit([1, 2, 3], max_tokens=2)
        req, _ = rep.submitted[0]
        foreign = [_mk(req.trace["trace_id"], 777,
                       req.trace["parent_id"], "decode", 0.0, 1.0,
                       "r0")]
        rep.resolve_all(spans=foreign)
        fut.result(5)
        names = {s["name"]
                 for s in router.tracer.spans(fut.trace_id)}
        assert "decode" in names
        assert span_tree(router.tracer.spans(),
                         fut.trace_id)["connected"]

    def test_shed_is_force_sampled(self):
        rep = FakeReplica("r0")
        # sample=1000: only the very first trace samples organically
        router = traced_router([rep], trace_sample=1000,
                               fleet_queue_cap=2)
        fut0 = router.submit([9, 9], max_tokens=2)   # the 1-in-N one
        fut1 = router.submit([1, 2], max_tokens=2)   # unsampled
        fut2 = router.submit([3, 4], max_tokens=2)   # over the cap
        assert fut2.result(5).finish_reason == "queue_full"
        spans = router.tracer.spans(fut2.trace_id)
        (root,) = [s for s in spans if s["name"] == "request"]
        assert root["attrs"]["finish_reason"] == "queue_full"
        # the served unsampled request left nothing in the ring
        rep.resolve_all()
        fut0.result(5)
        fut1.result(5)
        assert router.tracer.spans(fut1.trace_id) == []
        assert router.tracer.spans(fut0.trace_id) != []

    def test_failover_forces_sampling_and_orders_generations(self):
        a, b = FakeReplica("a"), FakeReplica("b")
        router = traced_router([a, b], trace_sample=1000,
                               policy="round_robin")
        fut = router.submit([1, 2, 3], max_tokens=2)
        assert len(a.submitted) == 1
        a._alive = False                 # kill the first member
        router.check_health()            # requeue -> b
        router._pump_queue()
        assert len(b.submitted) == 1
        # forced: the replayed dispatch rides sampled=True
        assert b.submitted[0][0].trace["sampled"] is True
        b.resolve_all()
        assert fut.result(5).status == "ok"
        spans = router.tracer.spans(fut.trace_id)
        names = [s["name"] for s in spans]
        assert "requeue" in names and "request" in names
        tree = span_tree(spans, fut.trace_id)
        assert tree["connected"]
        # dispatch generations are ordered in time
        dispatches = sorted(
            (s for s in spans if s["name"] == "dispatch"),
            key=lambda s: s["attrs"]["gen"],
        )
        gens = [s["attrs"]["gen"] for s in dispatches]
        assert gens == sorted(gens) and len(set(gens)) == len(gens)
        assert all(
            x.get("t0") <= y.get("t0")
            for x, y in zip(dispatches, dispatches[1:])
        )

    def test_salvage_pulls_wreck_spans(self):
        a, b = FakeReplica("a"), FakeReplica("b")
        router = traced_router([a, b])
        fut = router.submit([1, 2, 3], max_tokens=2)
        req, _ = a.submitted[0]
        # the member dies with unsent spans in its ring
        wreck = Tracer(process="a")
        wctx = dict(req.trace)
        t = wreck.clock()
        wreck.record_span(wctx, "prefill_chunk", t - 0.1, t)
        a.trace_state = lambda: wreck.spans()
        a._alive = False
        router.check_health()
        spans = router.tracer.spans(fut.trace_id)
        assert any(s["name"] == "prefill_chunk" and
                   s["process"] == "a" for s in spans)
        router._pump_queue()
        b.resolve_all()
        fut.result(5)
        assert span_tree(router.tracer.spans(),
                         fut.trace_id)["connected"]

    def test_slo_miss_forces_root_span(self):
        rep = FakeReplica("r0")
        router = traced_router([rep], trace_sample=1000,
                               trace_slo_ttft_s=0.001)
        router.submit([9, 9], max_tokens=2)   # burns the 1-in-N slot
        fut = router.submit([1, 2], max_tokens=2)   # unsampled
        rep.resolve_all()        # scripted ttft 0.01 > SLO 0.001
        fut.result(5)
        spans = router.tracer.spans(fut.trace_id)
        (root,) = [s for s in spans if s["name"] == "request"]
        assert root["attrs"]["slo_miss"] is True
        # the forced tail keeps its dispatch leg (member/mode), not
        # just the bare root — forcing happens BEFORE the still-open
        # dispatch span ends
        (dsp,) = [s for s in spans if s["name"] == "dispatch"]
        assert dsp["attrs"]["member"] == "r0"
        assert span_tree(spans, fut.trace_id)["connected"]

    def test_untraced_router_unchanged(self):
        rep = FakeReplica("r0")
        router = traced_router([rep], trace_sample=0)
        assert router.tracer is None
        fut = router.submit([1, 2], max_tokens=2)
        assert not hasattr(fut, "trace_id")
        rep.resolve_all()
        assert fut.result(5).status == "ok"


# ---------------------------------------------------------------------------
# training-loop tracing (utils/recorder.Recorder)
# ---------------------------------------------------------------------------


class TestTrainingRecorderTracing:
    def test_iteration_phases_become_spans(self):
        from theanompi_tpu.utils.recorder import Recorder

        rec = Recorder(verbose=False)
        tr = Tracer(process="bsp_worker", sample=1)
        rec.attach_tracer(tr)
        rec.trace_boundary(0)
        for i in range(3):
            rec.start()
            rec.end("wait")
            rec.start()
            rec.end("calc")
            rec.trace_boundary(i + 1)
        rec.finish_trace()
        spans = tr.spans()
        names = [s["name"] for s in spans]
        assert names.count("iteration") == 4
        assert names.count("dispatch") == 3 and names.count("load") == 3
        # each phase span parents under its iteration root
        for tid in {s["trace_id"] for s in spans}:
            assert span_tree(spans, tid)["connected"]

    def test_sampled_iterations_only(self):
        from theanompi_tpu.utils.recorder import Recorder

        rec = Recorder(verbose=False)
        tr = Tracer(process="bsp_worker", sample=4)
        rec.attach_tracer(tr)
        for i in range(8):
            rec.trace_boundary(i)
            rec.start()
            rec.end("calc")
        rec.finish_trace()
        names = [s["name"] for s in tr.spans()]
        assert names.count("iteration") == 2    # 8 / sample 4


# ---------------------------------------------------------------------------
# supervisor life spans
# ---------------------------------------------------------------------------


class TestSupervisorTracing:
    def test_lives_recorded_per_launch(self, tmp_path):
        import sys

        from theanompi_tpu.utils.supervisor import Supervisor

        # first launch crashes, relaunch exits clean
        marker = tmp_path / "ran_once"
        child = tmp_path / "child.py"
        child.write_text(
            "import pathlib, sys\n"
            f"m = pathlib.Path({str(marker)!r})\n"
            "if m.exists():\n"
            "    sys.exit(0)\n"
            "m.write_text('x')\n"
            "sys.exit(9)\n"
        )
        tr = Tracer(process="supervisor")
        sup = Supervisor(
            cmd_for=lambda r: [sys.executable, str(child)],
            checkpoint_dir=str(tmp_path / "ck"),
            max_restarts=2, backoff_base_s=0.01, backoff_cap_s=0.02,
            poll_interval_s=0.02, startup_grace_s=30.0,
            verbose=False, seed=0, tracer=tr,
        )
        report = sup.run()
        assert report["completed"]
        spans = tr.spans()
        lives = [s for s in spans if s["name"] == "life"]
        assert [s["attrs"]["cause"] for s in lives] == ["crash",
                                                        "clean"]
        (root,) = [s for s in spans if s["name"] == "supervised_run"]
        assert root["attrs"]["completed"] is True
        tid = root["trace_id"]
        assert span_tree(spans, tid)["connected"]


# ---------------------------------------------------------------------------
# autoscaler scale-action spans
# ---------------------------------------------------------------------------


class TestAutoscalerTracing:
    def test_scale_actions_record_spans(self):
        from theanompi_tpu.serving import Autoscaler

        reps = [FakeReplica("r0")]
        router = traced_router(reps)
        spawned = []

        def spawn(i):
            rep = FakeReplica(f"spawn{i}")
            rep.beat()
            spawned.append(rep)
            return rep

        auto = Autoscaler(
            router, spawn, min_replicas=1, max_replicas=2,
            scale_up_at=1.0, scale_down_at=0.25,
            up_hold_s=0.0, down_hold_s=0.0, cooldown_s=0.0,
        )
        assert auto.tracer is router.tracer   # inherits the router's
        futs = [router.submit([1, 2], max_tokens=2)
                for _ in range(6)]
        auto.tick()                           # pressure -> scale-up
        assert auto.summary()["n_scale_ups"] == 1
        for rep in reps + spawned:
            rep.resolve_all()
        for f in futs:
            f.result(5)
        router.check_health()
        auto.tick()                           # lull -> scale-down
        assert auto.summary()["n_scale_downs"] == 1
        names = [s["name"] for s in router.tracer.spans()]
        assert "scale_up" in names and "scale_down" in names
        up = next(s for s in router.tracer.spans()
                  if s["name"] == "scale_up")
        assert up["lane"] == "autoscaler"
        assert up["attrs"]["replica"] in {r.name for r in spawned}
        assert_prometheus_text(auto.metrics_txt(), (
            "tm_autoscaler_scale_ups_total 1",
            "tm_autoscaler_scale_downs_total 1",
            "tm_autoscaler_ticks_total 2",
        ))


class TestCriticalPathUnsampled:
    def test_router_critical_path_none_for_unsampled_trace(self):
        # the README's happy path at 1/N sampling: most futures have
        # a trace_id whose trace was never recorded — the report is
        # None, not a crash
        rep = FakeReplica("r0")
        router = traced_router([rep], trace_sample=1000)
        router.submit([9], max_tokens=1)          # burns sample slot
        fut = router.submit([1, 2], max_tokens=2)  # unsampled
        rep.resolve_all()
        fut.result(5)
        assert router.critical_path(fut.trace_id) is None


class TestOldFormatLargerThanWindow:
    def test_load_state_dict_folds_from_source_lists(self):
        # a pre-bounding state LARGER than max_samples: counters must
        # come from the full source lists, not the truncated window
        old = {
            "max_slots": 1,
            "requests": [
                {"status": "ok", "finish_reason": "eos",
                 "n_prompt": 1, "n_generated": 2,
                 "ttft_s": 0.1 * (i + 1), "tpot_s": None,
                 "queued_s": None, "e2e_s": None, "n_prefix_hit": 0}
                for i in range(20)
            ],
            "steps": [
                {"active_slots": 1, "queue_depth": 0, "dt_s": 1.0,
                 "tokens": 1, "blocks_in_use": None,
                 "blocks_free": None, "drafted": None,
                 "accepted": None}
                for _ in range(20)
            ],
            "blocks_in_use_max": None, "blocks_free_min": None,
        }
        r = ServingRecorder(max_slots=1, max_samples=8)
        r.load_state_dict(old)
        s = r.summary()
        assert s["n_completed"] == 20          # not 8
        assert s["tokens_generated"] == 20
        assert len(r.requests) == 8            # window still bounded

    def test_critical_path_none_on_tracerless_router(self):
        rep = FakeReplica("r0")
        router = traced_router([rep], trace_sample=0)
        assert router.tracer is None
        assert router.critical_path(123) is None
