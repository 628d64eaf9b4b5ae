"""Analytical scaling predictor + 8B operational sizing (VERDICT r3
items 7 and 10).  Mostly pure shape/datasheet math (no devices, no
jit) — EXCEPT the slow-tier 8B dress rehearsal at the end, which
compiles and runs a real training step on the 16-device virtual
mesh."""

import math

import pytest

from theanompi_tpu.models.llama import LLAMA3_8B
from theanompi_tpu.utils.scaling_model import (
    allreduce_time,
    bsp_efficiency,
    ici_links_used,
    llama_hbm_per_chip,
    llama_param_count,
    llama_step_flops,
    llama_step_time,
    predict_table,
)

# single-chip step times from before PR 1 that the predictions are
# anchored to; the arithmetic under test does not depend on them.
RESNET50 = dict(step_time=128 / 2642.97, param_bytes=25.6e6 * 4)
ALEXNET = dict(step_time=128 / 8521.7, param_bytes=61e6 * 4)


def test_allreduce_time_closed_form():
    # 8 chips ring over one axis: 2 links * 45 GB/s
    b = 100 * 2**20
    t = allreduce_time(b, 8)
    expect = 2 * b * (7 / 8) / (2 * 45e9)
    assert math.isclose(t, expect, rel_tol=1e-12)
    assert allreduce_time(b, 1) == 0.0
    # 64 chips uses both torus axes -> 2x the bandwidth
    assert ici_links_used(64) == 4
    assert allreduce_time(b, 64) < allreduce_time(b, 16)


def test_bsp_efficiency_bounds_and_monotonicity():
    rows = predict_table(
        step_time_1chip=RESNET50["step_time"],
        param_bytes=RESNET50["param_bytes"],
    )
    for r in rows:
        assert 0.0 < r["efficiency_no_overlap"] <= 1.0
        assert r["efficiency_no_overlap"] <= r["efficiency_overlap"] <= 1.0
    # the north-star claim (BASELINE §A): ResNet-50 b128 predicts
    # >=90% linear BSP scaling on v5e-64 even with ZERO overlap
    r64 = [r for r in rows if r["n_chips"] == 64][0]
    assert r64["efficiency_no_overlap"] >= 0.90
    # with XLA's backward overlap the allreduce hides entirely
    assert r64["efficiency_overlap"] >= 0.99


def test_wire_dtype_halves_bytes():
    e32 = bsp_efficiency(
        step_time_1chip=RESNET50["step_time"],
        param_bytes=RESNET50["param_bytes"],
        wire_dtype_bytes=4, n_chips=8,
    )
    e16 = bsp_efficiency(
        step_time_1chip=RESNET50["step_time"],
        param_bytes=RESNET50["param_bytes"],
        wire_dtype_bytes=2, n_chips=8,
    )
    assert math.isclose(e16["wire_mb"], e32["wire_mb"] / 2, rel_tol=1e-12)
    assert e16["efficiency_no_overlap"] > e32["efficiency_no_overlap"]


def test_llama8b_param_count():
    p = llama_param_count(LLAMA3_8B)
    # Llama-3-8B is ~8.0B params; the exact layout here gives ~8.03B
    assert 7.8e9 < p < 8.3e9


def test_llama8b_hbm_sizing():
    """BASELINE config 5 sizing, from shapes (VERDICT r3 #10).

    The HONEST answer from the arithmetic: fp32-Adam 8B at tp=4,pp=1
    is 24 GB/chip of optimizer+master alone — it does NOT fit a 16 GiB
    v5e chip; the judged-round assumption (tp=4, sp=2 fitting) fails
    on datasheet math.  The smallest power-of-two layout that fits
    with full fp32 Adam is a 16-way model shard (tp=4 x pp=4, or
    tp=8 x pp=2), with activations at T=2048 a rounding error next to
    the optimizer tensors."""
    tight = llama_hbm_per_chip(
        LLAMA3_8B, tp=4, sp=2, pp=1, batch_per_replica=1, seq_len=2048
    )
    assert not tight["fits_16g"]  # 8B * 16 B/param / 4 chips = ~30 GB

    fits = llama_hbm_per_chip(
        LLAMA3_8B, tp=4, sp=2, pp=4, batch_per_replica=1, seq_len=2048
    )
    assert fits["fits_16g"], fits
    assert fits["total_gb"] < 10.0
    # activations are negligible vs optimizer state under remat
    assert fits["acts_gb"] < 0.5
    # and the un-rematerialized variant still fits at this T
    no_remat = llama_hbm_per_chip(
        LLAMA3_8B, tp=4, sp=2, pp=4, batch_per_replica=1,
        seq_len=2048, remat=False,
    )
    assert no_remat["total_gb"] < 16.0


def test_zero1_hbm_accounting():
    """ZeRO-1 (exch_strategy='zero1') shards fp32 adam m+v 1/dp over
    the data axis: opt bytes divide by dp, everything else is
    unchanged, and what did not fit the chip at batch 1 now does."""
    base = llama_hbm_per_chip(
        LLAMA3_8B, tp=8, batch_per_replica=1, seq_len=2048
    )
    z8 = llama_hbm_per_chip(
        LLAMA3_8B, tp=8, dp=8, zero1=True,
        batch_per_replica=1, seq_len=2048,
    )
    assert z8["opt_gb"] == pytest.approx(base["opt_gb"] / 8)
    for k in ("params_gb", "grads_gb", "acts_gb"):
        assert z8[k] == base[k]
    # zero1=False ignores dp entirely (replicated state)
    same = llama_hbm_per_chip(
        LLAMA3_8B, tp=8, dp=64, zero1=False,
        batch_per_replica=1, seq_len=2048,
    )
    assert same["opt_gb"] == base["opt_gb"]

    # the 8B-at-tp8 headline at the config's own sequence length:
    # replicated adam does not fit the chip even at batch 1; zero1's
    # freed optimizer bytes make room for a real batch
    kw = dict(tp=8, dp=8)
    assert not llama_hbm_per_chip(
        LLAMA3_8B, zero1=False, batch_per_replica=1, **kw)["fits_16g"]
    assert llama_hbm_per_chip(
        LLAMA3_8B, zero1=True, batch_per_replica=2, **kw)["fits_16g"]


def test_bucketed_overlap_predictor():
    """ISSUE 2: the bucket-count / per-bucket-wire-time overlap model
    (scaling_model.bucketed_overlap) — the analytical half of the
    bucketed-vs-monolithic A/B."""
    from theanompi_tpu.utils.scaling_model import bucketed_overlap

    wire = 100e6          # ~100 MB of fp32 grads
    step = 0.050
    mono = bucketed_overlap(
        wire_bytes=wire, n_chips=8, step_time_1chip=step,
        bucket_bytes=0,
    )
    buck = bucketed_overlap(
        wire_bytes=wire, n_chips=8, step_time_1chip=step,
        bucket_bytes=4 * 2**20,
    )
    # monolithic = one bucket, fully exposed tail
    assert mono["n_buckets"] == 1
    assert mono["t_exposed_monolithic_ms"] == pytest.approx(
        mono["t_exposed_bucketed_ms"]
    )
    assert buck["n_buckets"] == math.ceil(wire / (4 * 2**20))
    # bucketing can only reduce the exposed tail, never grow it past
    # the monolithic bound, and the floor is one bucket's wire time
    assert (buck["t_exposed_bucketed_ms"]
            <= buck["t_exposed_monolithic_ms"])
    assert buck["overlap_win_ms"] >= 0.0
    assert (buck["exposed_comm_frac_bucketed"]
            <= buck["exposed_comm_frac_monolithic"])
    # with a generous compute budget only the tail bucket is exposed
    roomy = bucketed_overlap(
        wire_bytes=wire, n_chips=8, step_time_1chip=10.0,
        bucket_bytes=4 * 2**20,
    )
    per_bucket_ms = roomy["t_wire_ms"] / roomy["n_buckets"]
    assert roomy["t_exposed_bucketed_ms"] == pytest.approx(
        per_bucket_ms
    )
    # launch overhead: absurdly small buckets pay n_buckets * launch
    # and the model says so (total wire GROWS as buckets shrink)
    tiny = bucketed_overlap(
        wire_bytes=wire, n_chips=8, step_time_1chip=step,
        bucket_bytes=2**14,
    )
    assert tiny["t_wire_ms"] > buck["t_wire_ms"]
    # degenerate inputs: single chip / zero wire are all-zero rows
    z = bucketed_overlap(
        wire_bytes=wire, n_chips=1, step_time_1chip=step,
        bucket_bytes=4 * 2**20,
    )
    assert z["t_exposed_bucketed_ms"] == 0.0
    assert z["exposed_comm_frac_monolithic"] == 0.0


def test_llama8b_step_time_prediction():
    """Predicted 8B step time at the r3 measured proxy MFU: the
    PODS.md number a future pod run is checked against."""
    t = llama_step_time(
        LLAMA3_8B, batch=16, seq_len=2048, mfu=0.36, n_chips_compute=16
    )
    fl = llama_step_flops(LLAMA3_8B, 16, 2048)
    # 6*8e9*32k tokens ~ 1.6 PFLOP + attention + remat ~ 2.3 PFLOP
    assert 1.5e15 < fl < 3.5e15
    # 16 chips at 36% MFU: ~2 s/step -> sanity band, not a benchmark
    assert 0.5 < t < 5.0


def test_predict_table_runs_for_all_flagships():
    for m in (RESNET50, ALEXNET):
        rows = predict_table(
            step_time_1chip=m["step_time"], param_bytes=m["param_bytes"]
        )
        assert [r["n_chips"] for r in rows] == [8, 16, 64]


def test_moe_param_count_vs_dense():
    """E experts of width f hold E x the dense FFN params (+ router);
    the attention/embed terms match the dense count exactly."""
    from theanompi_tpu.utils.scaling_model import moe_param_count

    cfg = dict(dim=1024, n_layers=8, n_heads=16, n_kv_heads=8,
               ffn_dim=2816, vocab=32000, seq_len=2048)
    moe = dict(cfg, n_experts=8, moe_top_k=2)
    dense = llama_param_count(cfg)
    total = moe_param_count(moe)
    ffn_dense = 8 * 3 * 1024 * 2816
    router = 8 * 1024 * 8
    assert total == dense - ffn_dense + 8 * ffn_dense + router


def test_moe_alltoall_bytes_and_overhead():
    """EP exchange model: zero at ep=1; scales with the remote
    fraction; overhead fraction small for the benched proxy at ep=8
    (the dispatch ships activations, the experts crunch D*F FLOPs)."""
    from theanompi_tpu.utils.scaling_model import (
        moe_alltoall_bytes,
        moe_ep_overhead,
    )

    cfg = dict(dim=1024, n_layers=8, n_heads=16, n_kv_heads=8,
               ffn_dim=1408, vocab=32000, seq_len=2048,
               n_experts=8, moe_top_k=2)
    assert moe_alltoall_bytes(cfg, batch_per_replica=4, ep=1) == 0.0
    b2 = moe_alltoall_bytes(cfg, batch_per_replica=4, ep=2)
    b8 = moe_alltoall_bytes(cfg, batch_per_replica=4, ep=8)
    # (ep-1)/ep remote fraction: 8-way ships 7/4 x the 2-way bytes
    assert math.isclose(b8 / b2, (7 / 8) / (1 / 2), rel_tol=1e-12)
    # r4 measured MoE proxy step: 4*2048 tokens / 55.2k tok/s
    ov = moe_ep_overhead(
        cfg, batch_per_replica=4, ep=8,
        step_time_1chip=4 * 2048 / 55237.0,
    )
    assert 0 < ov["frac_of_step"] < 0.2
    assert ov["efficiency_no_overlap"] > 0.8


@pytest.mark.slow
def test_llama8b_dress_rehearsal_tp4_pp4(devices16, tmp_path):
    """BASELINE config 5 as an EXECUTED program (VERDICT r4 next #8):
    ``test_llama8b_hbm_sizing`` proves tp=4 x pp=4 fits the 8B at
    ~7.6 GB/chip; this runs a real training step of a
    dimension-scaled model carrying the true 8B RATIOS — head_dim=128
    (16 heads x 2048d), GQA 4:1 (4 KV heads), ffn/dim = 3.5,
    vocab-sharded head — on the 16-device virtual mesh at EXACTLY
    that layout (model=4, pipe=4), then round-trips a sharded
    checkpoint at the same layout."""
    import numpy as np

    import jax

    from theanompi_tpu.models.llama import Llama
    from theanompi_tpu.parallel import make_mesh
    from theanompi_tpu.utils import Recorder

    cfg = dict(
        dim=2048, n_layers=4, n_heads=16, n_kv_heads=4,
        ffn_dim=7168, vocab=2048, seq_len=64, batch_size=8,
        tp=4, pp=4, remat=True, compute_dtype="float32",
        lr=1e-2, n_train=16, n_val=8,
    )
    assert cfg["dim"] // cfg["n_heads"] == 128          # 8B head_dim
    assert cfg["n_heads"] // cfg["n_kv_heads"] == 4     # 8B GQA ratio
    assert cfg["ffn_dim"] / cfg["dim"] == 3.5           # 8B FFN ratio
    mesh = make_mesh(data=1, model=4, pipe=4, devices=devices16)
    model = Llama(cfg)
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=mesh)
    rec = Recorder(rank=0)
    model.train_iter(0, rec)
    rec.flush()
    assert rec.n_iter == 1
    loss0 = rec.train_losses[-1]
    assert np.isfinite(loss0) and 0.0 < loss0 < 20.0, loss0

    # sharded save/restore at the SAME 16-way layout
    model.save(str(tmp_path), rec)
    m2 = Llama(dict(cfg, seed=model.seed + 1))  # different init
    m2.build_model(n_replicas=1)
    m2.compile_iter_fns(mesh=mesh)
    assert m2.load(str(tmp_path))
    for a, b in zip(
        jax.tree.leaves(model.params), jax.tree.leaves(m2.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_exchange_wire_bytes_compression_factor():
    """int8/fp8 wire ships ~4x fewer bytes than fp32 for MB-scale
    packs (ISSUE 4 acceptance: >= 3.5x in the accounting) — the
    per-chunk scale overhead only matters for pathological tiny
    buckets."""
    from theanompi_tpu.utils.scaling_model import exchange_wire_bytes

    pb = 100 * 2**20                       # 100 MB fp32 grads
    fp32 = exchange_wire_bytes(pb, wire="fp32", n_shards=64)
    bf16 = exchange_wire_bytes(pb, wire="bf16", n_shards=64)
    int8 = exchange_wire_bytes(pb, wire="int8", n_shards=64)
    fp8 = exchange_wire_bytes(pb, wire="fp8", n_shards=64)
    assert fp32 == pb
    assert bf16 == pb / 2
    assert fp32 / int8 >= 3.5
    assert fp32 / fp8 >= 3.5
    # tiny buckets: scale overhead grows (one f32 per bucket x shard)
    tiny = exchange_wire_bytes(pb, wire="int8", n_shards=64,
                               bucket_bytes=2**12)
    assert tiny > int8


def test_bsp_efficiency_compression_kwarg():
    from theanompi_tpu.utils.scaling_model import bsp_efficiency

    base = dict(step_time_1chip=0.1, param_bytes=100 * 2**20,
                n_chips=64)
    fp32 = bsp_efficiency(**base)
    int8 = bsp_efficiency(**base, compression="int8")
    assert int8["wire_mb"] < fp32["wire_mb"] / 3.5
    assert int8["efficiency_overlap"] >= fp32["efficiency_overlap"]


def test_elastic_resume_cost():
    """The elastic-resume predictor (ISSUE 8): resharding pays a
    one-time gather+rescatter through host bandwidth, then trains at
    n_new/n_old throughput — it beats waiting for replacement
    hardware for any outage longer than the reshard itself."""
    from theanompi_tpu.utils.scaling_model import elastic_resume_cost

    base = dict(
        param_bytes=4 * 25e6, step_time_s=0.1, n_old=8, n_new=4,
    )
    adam = elastic_resume_cost(**base, optimizer="adam")
    mom = elastic_resume_cost(**base, optimizer="momentum")
    # adam carries m+v (2x), momentum velocity alone (1x)
    assert adam["state_bytes"] == pytest.approx(2 * mom["state_bytes"])
    # every byte crosses host memory twice (gather + re-scatter)
    assert adam["moved_bytes"] == pytest.approx(2 * adam["state_bytes"])
    assert adam["reshard_s"] > 0
    assert adam["reshard_steps_equiv"] == pytest.approx(
        adam["reshard_s"] / 0.1
    )
    assert adam["throughput_frac"] == pytest.approx(0.5)
    # elastic wins for any outage longer than the reshard pause
    assert adam["break_even_outage_s"] == pytest.approx(
        adam["reshard_s"]
    )
    # error feedback adds the n_old per-device r1 residuals — the
    # dominant term at wide worlds
    ef = elastic_resume_cost(**base, error_feedback=True)
    assert ef["state_bytes"] > adam["state_bytes"] + 7 * base["param_bytes"]
    # sgd has no optimizer state but EF still moves bytes
    sgd = elastic_resume_cost(**base, optimizer="sgd")
    assert sgd["state_bytes"] == 0 and sgd["reshard_s"] == 0


# ---------------------------------------------------------------------------
# measured anchor: bsp_efficiency vs trace_comm on real BSP runs
# (ROADMAP 3c / VERDICT #6 — the predictor family the fleet/elastic/
# autoscaler items lean on gets one measured data point)
# ---------------------------------------------------------------------------


def _measure_bsp_world(n: int, devices) -> dict:
    """One BSP training run at data-parallel width ``n`` on the
    virtual CPU mesh, with a ``trace_comm`` collective attribution
    of K fenced steps."""
    import jax

    from theanompi_tpu.models.llama import Llama
    from theanompi_tpu.parallel import make_mesh
    from theanompi_tpu.utils import Recorder
    from theanompi_tpu.utils.trace_comm import report_of

    cfg = dict(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=4, ffn_dim=176,
        vocab=512, seq_len=128, batch_size=2, lr=1e-3, seed=3,
        compute_dtype="float32",
    )
    m = Llama(cfg)
    m.build_model(n_replicas=n)
    m.compile_iter_fns(mesh=make_mesh(data=n, devices=devices[:n]))
    rec = Recorder(verbose=False)
    for i in range(3):
        m.train_iter(i, rec)
    rec.flush()                  # warmup fence (compiles done)
    k = 10

    def steps():
        for i in range(k):
            m.train_iter(100 + i, rec)
        rec.flush()              # reading the losses IS the fence

    rep = report_of(steps)
    return {
        "n": n, "k_steps": k, "trace": rep,
        "param_bytes": 4 * sum(
            x.size for x in jax.tree_util.tree_leaves(m.params)
        ),
    }


@pytest.mark.slow
def test_bsp_efficiency_measured_anchor(devices8):
    """Validate ``bsp_efficiency`` against ``trace_comm``-measured
    BSP runs at worlds of 1/2/4 on this host (ROADMAP 3c /
    VERDICT #6).

    The measured worlds are the repo's standard stand-in for
    multi-PROCESS runs on the CPU backend: the virtual
    CPU mesh at 1/2/4 devices, which dispatches the IDENTICAL XLA
    collectives (``TestRealCollectives`` proves they are trace-
    attributable on this mesh).  On hardware the same protocol runs
    over real processes unchanged.

    Protocol: each world runs the same tiny-Llama BSP config
    (per-replica batch constant — weak scaling) and captures a
    profiler trace of K fenced steps.  The n=2 run CALIBRATES the
    effective exchange bandwidth (ring bytes over measured
    collective seconds — the one anchor a datasheet ChipSpec cannot
    provide for this wire); the predictor then PREDICTS the n=4
    efficiency from that calibration, and the prediction must land
    within ±0.25 ABSOLUTE efficiency of the n=4 run's own measured
    value.  The tolerance is stated wide on purpose: the virtual
    mesh shares 2 physical cores, so collective stalls carry
    scheduler jitter — the anchor validates the predictor's FORM
    (wire term scaling 2*B*(n-1)/n, efficiency composition) to
    first order, not datasheet precision.  ``overlap_frac=0``
    matches the serial-tail efficiency ``1 - comm_frac`` the trace
    measures (the overlap term is separately exercised by the
    bucketed-exchange trace tests)."""
    m1 = _measure_bsp_world(1, devices8)
    m2 = _measure_bsp_world(2, devices8)
    m4 = _measure_bsp_world(4, devices8)

    # n=1: no collective to expose — efficiency is structurally 1
    t1 = m1["trace"]
    assert t1["comm_frac"] < 0.05, t1

    def per_step(rec, key):
        t = rec["trace"]
        return t[key] / max(1, t["n_cores"]) / rec["k_steps"]

    pb = m4["param_bytes"]
    assert pb == m2["param_bytes"]

    # calibrate the wire from n=2: allreduce_time's ring formula
    # inverted on the measured per-step collective seconds
    t_coll_2 = per_step(m2, "collective_s")
    assert t_coll_2 > 0, m2
    bw = (2.0 * pb * (2 - 1) / 2) / t_coll_2

    # predict n=4 from the calibration + n=4's own compute time
    t_comp_4 = per_step(m4, "device_busy_s") - per_step(
        m4, "collective_s"
    )
    assert t_comp_4 > 0, m4
    pred = bsp_efficiency(
        step_time_1chip=t_comp_4, param_bytes=pb, n_chips=4,
        overlap_frac=0.0, bw=bw,
    )
    eff_pred = pred["efficiency_no_overlap"]
    eff_meas = 1.0 - m4["trace"]["comm_frac"]
    assert 0.0 < eff_meas <= 1.0
    tol = 0.25
    assert abs(eff_pred - eff_meas) <= tol, (
        f"predicted BSP efficiency {eff_pred:.3f} vs measured "
        f"{eff_meas:.3f} at n=4 (calibrated bw {bw / 1e6:.1f} MB/s "
        f"from n=2) — outside +/-{tol}"
    )
    # and the directional law the autoscaler's fleet_roofline leans
    # on: efficiency does not improve as the world grows
    eff_meas_2 = 1.0 - m2["trace"]["comm_frac"]
    assert eff_meas <= eff_meas_2 + 0.10, (eff_meas, eff_meas_2)


def test_serving_roofline_paged_attend_intensity():
    """The fused-kernel arithmetic-intensity line (serving v5): the
    kernel is bandwidth-bound by construction (intensity far under
    the ridge), and the gather path's materialized window costs ~3x
    the PADDED window's bytes — the predicted HBM win (not
    measured on the chip: serving has no cell)."""
    from theanompi_tpu.utils import scaling_model as sm

    r = sm.serving_roofline(
        LLAMA3_8B, batch=8, context=1024, tp=8, max_seq=8192,
        block_size=16,
    )
    assert r["paged_attend_intensity"] < r["ridge_intensity"]
    assert r["paged_attend_bytes_fused"] > 0
    # gather reads+writes+rereads the PADDED window (max_seq-sized
    # here), fused reads context once: speedup > 3x padding ratio
    assert r["paged_attend_hbm_speedup"] == pytest.approx(
        3.0 * 8192 / 1024
    )
    # no block_size -> no kernel line
    r2 = sm.serving_roofline(LLAMA3_8B, batch=8, context=1024, tp=8)
    assert "paged_attend_intensity" not in r2


def test_speculation_speedup_forms():
    from theanompi_tpu.utils import scaling_model as sm

    # conditional=True: geometric per-draft probability
    s = sm.speculation_speedup(k=6, accept_rate=0.8, conditional=True)
    want = sum(0.8 ** i for i in range(6))
    assert s["tokens_per_step"] == pytest.approx(want)
    assert s["speedup"] == pytest.approx(want)
    # default: unconditional accepted/drafted (the recorder datum) —
    # linear, and always >= the geometric form at the same a
    u = sm.speculation_speedup(k=6, accept_rate=0.8)
    assert u["tokens_per_step"] == pytest.approx(1.0 + 0.8 * 5)
    assert u["tokens_per_step"] > s["tokens_per_step"]
    for kw in ({}, {"conditional": True}):
        assert sm.speculation_speedup(k=5, accept_rate=1.0, **kw)[
            "tokens_per_step"] == 5.0
        assert sm.speculation_speedup(k=5, accept_rate=0.0, **kw)[
            "speedup"] == 1.0


def test_loader_pipeline_predictor():
    from theanompi_tpu.utils import scaling_model as sm

    # compute-bound: host work fits under the step — pipelined
    # host_gap is exactly zero and the win is the whole host leg
    r = sm.loader_pipeline(
        batch_bytes=32 * 3 * 32 * 32 * 4, step_time_s=0.1,
        host_bw=2e9,
    )
    assert not r["producer_bound"]
    assert r["host_gap_frac_pipelined"] == 0.0
    assert r["t_step_pipelined_ms"] == pytest.approx(100.0)
    assert r["overlap_win_ms"] == pytest.approx(r["t_host_ms"])
    assert 0.0 < r["host_gap_frac_sync"] < 1.0

    # producer-bound: host work exceeds the step — the exposed
    # remainder is priced, and more ring depth cannot hide it
    b = sm.loader_pipeline(
        batch_bytes=4e9, step_time_s=0.1, host_bw=2e9, fetch_s=0.05,
    )
    assert b["producer_bound"]
    assert b["t_step_pipelined_ms"] == pytest.approx(
        b["t_host_ms"]
    )
    assert b["starved_frac"] > 0.5

    # sync cost is monotone in fetch time; the pipelined arm only
    # pays what the step cannot cover
    lo = sm.loader_pipeline(
        batch_bytes=1e6, step_time_s=0.1, fetch_s=0.0)
    hi = sm.loader_pipeline(
        batch_bytes=1e6, step_time_s=0.1, fetch_s=0.02)
    assert hi["t_step_sync_ms"] > lo["t_step_sync_ms"]
    assert hi["t_step_pipelined_ms"] == lo["t_step_pipelined_ms"]

    with pytest.raises(ValueError):
        sm.loader_pipeline(
            batch_bytes=1e6, step_time_s=0.1, depth=1)
