#!/usr/bin/env bash
# Bench smokes on the virtual 8-device CPU mesh, for CI and
# pre-commit use:
#
# 1. compressed-exchange: the bench.py `compressed` A/B arm at
#    5 steps x 4 arms (fp32 / int8+EF / fp8+EF / zero1+int8) — a
#    ~2-minute signal that the quantized wire still compiles, runs,
#    traces, and tracks the fp32 loss.  The full 50-step protocol is
#    the bench row (TM_BENCH_MODEL=compressed) and the slow-tier
#    tests (tests/test_compression.py --runslow).
# 2. serving: the bench.py `serving` row in smoke shape — 4
#    concurrent prompts through the continuous batcher at 8 tokens
#    each off a just-saved training checkpoint; asserts every
#    request completes (none shed, none hung) and tokens flowed.
# 3. serving_paged: the v2 paged-KV row in smoke shape — 4 requests
#    sharing a 40-token system prompt against a primed radix cache;
#    asserts prefix hit rate > 0, every request completes, token
#    accounting is exact, and the decode executable never recompiled
#    (the in-child compile-counter assertions also gate this).  The
#    v5 SPECULATIVE arm rides the same child: the same prompts served
#    non-speculative then with speculate_k=4 must be BITWISE equal,
#    with accept_rate > 0, tokens/slot-step > 1, and <= 2 decode
#    compiles (decode + verify share the budget).  The TRACING arm
#    (ISSUE 14) rides it too: a sample=1 pass asserts one connected
#    span tree per request + root-span-count conservation + the
#    Perfetto export parses, then traced-vs-untraced interleaved
#    repeats assert < 2% wall overhead at the default 1/N rate.
# 4. serving_fleet: the fleet router in smoke shape — 2 replica
#    PROCESSES behind the TCP wire, one carrying a
#    TM_FAULT_AT=1:4:die_replica drill that kills it mid-generation;
#    asserts every request completes with exact token accounting and
#    at least one failover requeue was recorded (zero lost futures).
# 5. elastic: shrink-resume — a supervised zero1+int8 run loses half
#    its 8-device world mid-run and completes at 4 after a resharded
#    resume; asserts resumed progress and the [8, 4] world-size
#    history in the supervisor report (docs/RESILIENCE.md).
# 6. serving_autoscale: the control-plane row in smoke shape — a
#    short diurnal ramp over 2 TCP replica processes behind the
#    autoscaler; asserts ≥1 scale-up AND ≥1 drained scale-down with
#    every request completing under exact token accounting (zero
#    dropped across the membership changes), SLOs held.
# 7. profile + regression gate (ISSUE 15): the step-phase profiler
#    row in smoke shape (Llama proxy only) — asserts the per-scope
#    decomposition sums (coverage within 5%), the exchange
#    decomposed per bucket, and a PROFILED child's timed windows
#    stay within the overhead bound of unprofiled ones (PR 12's
#    tracing-overhead protocol; smoke bound proportionally looser
#    than the full row's 2% — ~1 s windows on a 2-core host are
#    scheduler-noise-bound).  Then `bench_diff --gate` must run
#    GREEN over the repo's real BENCH_* trajectory.
# 8. loader (ISSUE 16): the streaming-loader data-plane row — the
#    sync-vs-pipelined WResNet A/B child self-asserts bitwise-equal
#    losses, StepProfile coverage, pipelined exposed data wait ≈ 0,
#    host_gap no worse than the synchronous arm's, the
#    stall_loader starvation degrade, and the elastic 8→4 sample-id
#    accounting; this gate re-asserts the reported fields landed.
#
# Usage: bash scripts/bench_smoke.sh

set -euo pipefail
cd "$(dirname "$0")/.."

out=$(TM_COMPRESSED_AB_STEPS=${TM_COMPRESSED_AB_STEPS:-5} \
      TM_BENCH_MODEL=compressed python bench.py)
printf '%s\n' "$out" | python -c '
import json, sys
row = json.loads(sys.stdin.readline())
deltas = row.get("loss_delta_vs_fp32", {})
print("rates      ", row.get("rates"))
print("loss deltas", deltas)
print("wire x     ", row.get("wire_reduction"))
bad = {k: v for k, v in deltas.items() if not v < 0.05}
if bad:
    sys.exit("bench_smoke: loss drifted past 5%% of fp32 wire: %s" % bad)
wr = row.get("wire_reduction", 0)
if not wr >= 3.5:
    sys.exit("bench_smoke: wire_reduction below 3.5x: %s" % wr)
print("bench_smoke: compressed OK")
'

out=$(TM_SERVING_SMOKE=1 TM_BENCH_MODEL=serving python bench.py)
printf '%s\n' "$out" | python -c '
import json, sys
row = json.loads(sys.stdin.readline())
arm = row["arms"]["offered_4"]
print("serving tokens/s", arm.get("tokens_per_sec"),
      "ttft p50/p95", arm.get("ttft_p50_s"), arm.get("ttft_p95_s"))
if arm["n_completed"] != 4 or arm["n_shed"] != 0:
    sys.exit("bench_smoke: serving arm did not complete all 4 "
             "requests: %s" % arm)
if not (arm["tokens_completed"] == 4 * 8 and arm["tokens_per_sec"] > 0):
    sys.exit("bench_smoke: serving arm token accounting off: %s" % arm)
print("bench_smoke: serving OK")
'

out=$(TM_SERVING_SMOKE=1 TM_BENCH_MODEL=serving_paged python bench.py)
printf '%s\n' "$out" | python -c '
import json, sys
row = json.loads(sys.stdin.readline())
arm = row["arms"]["paged_shared_warm"]
print("paged tokens/s", arm.get("tokens_per_sec"),
      "prefix hit rate", row.get("prefix_hit_rate"),
      "decode compiles", row.get("n_decode_compiles"))
if not (row.get("prefix_hit_rate") or 0) > 0:
    sys.exit("bench_smoke: shared-prefix arm saw no radix hits: %s" % row)
if arm["n_completed"] != 4 or arm["n_shed"] != 0 or not arm["all_ok"]:
    sys.exit("bench_smoke: paged arm did not complete all 4 "
             "requests: %s" % arm)
if arm["tokens_completed"] != 4 * 8:
    sys.exit("bench_smoke: paged arm token accounting off: %s" % arm)
if row["n_decode_compiles"] > 2 or row["n_prefill_compiles"] > 2:
    sys.exit("bench_smoke: paged executables recompiled: %s" % row)
sd = row.get("spec_decode") or {}
print("spec decode bitwise", sd.get("bitwise_equal"),
      "accept_rate", sd.get("accept_rate"),
      "tokens/step", sd.get("tokens_per_step"))
if not sd.get("bitwise_equal"):
    sys.exit("bench_smoke: speculative decode diverged from the "
             "non-speculative stream: %s" % sd)
if not (sd.get("accept_rate") or 0) > 0:
    sys.exit("bench_smoke: speculative arm accepted no drafts: %s" % sd)
if not (sd.get("tokens_per_step") or 0) > 1:
    sys.exit("bench_smoke: speculative arm stayed at one "
             "token/step: %s" % sd)
tr = row.get("tracing") or {}
print("tracing overhead", tr.get("overhead_ratio"),
      "root spans", tr.get("n_root_spans"), "/", tr.get("n_requests"))
if not tr:
    sys.exit("bench_smoke: serving_paged child carried no tracing "
             "A/B: %s" % sorted(row))
if tr["n_root_spans"] != tr["n_requests"]:
    sys.exit("bench_smoke: span-count conservation off — %s root "
             "spans for %s requests"
             % (tr["n_root_spans"], tr["n_requests"]))
if not tr["overhead_ratio"] < tr["overhead_bound"]:
    sys.exit("bench_smoke: traced arm overhead %s past the %s bound"
             % (tr["overhead_ratio"], tr["overhead_bound"]))
print("bench_smoke: serving_paged OK")
'

out=$(TM_SERVING_SMOKE=1 TM_BENCH_MODEL=serving_fleet python bench.py)
printf '%s\n' "$out" | python -c '
import json, sys
row = json.loads(sys.stdin.readline())
arm = row["arms"]["kill_one_of_2"]
print("fleet tokens/s", arm.get("agg_tokens_per_sec_wall"),
      "requeues", arm.get("n_requeues"),
      "failovers", arm.get("n_failovers"))
if not arm["all_ok"] or arm["n_completed"] != 6:
    sys.exit("bench_smoke: fleet kill arm did not complete all 6 "
             "requests: %s" % arm)
if arm["tokens_completed"] != 6 * 8:
    sys.exit("bench_smoke: fleet token accounting off: %s" % arm)
if not arm["n_requeues"] >= 1:
    sys.exit("bench_smoke: fleet kill arm recorded no requeue: %s" % arm)
print("bench_smoke: serving_fleet OK")
'

# 5. elastic shrink-resume: a supervised 8-device wresnet run under
#    the full acceptance config (zero1 + bucketed + int8-EF) loses
#    half its world mid-run (TM_FAULT_AT=1:1:shrink_world), resumes
#    at 4 devices with the checkpoint resharded, and completes —
#    asserts resumed progress (full loss curve, no step lost) and
#    the world-size history [8, 4] in the supervisor report.
python - <<'PYEOF'
import json, os, sys, tempfile
from pathlib import Path
sys.path.insert(0, os.getcwd())
from theanompi_tpu import launcher

ckpt = Path(tempfile.mkdtemp()) / "ck"
env = dict(os.environ)
env.update(
    JAX_PLATFORMS="cpu",
    XLA_FLAGS="--xla_force_host_platform_device_count=8",
    PYTHONPATH=os.getcwd(),
    TM_FAULT_AT="1:1:shrink_world",
)
n_epochs, nb = 3, 4
h = launcher.launch(
    "theanompi_tpu.workers.bsp_worker",
    devices=list(range(8)),
    modelfile="theanompi_tpu.models.wresnet",
    modelclass="WResNet",
    rule_kwargs=dict(
        config={"batch_size": 4, "n_epochs": n_epochs, "depth": 10,
                "widen": 1, "lr": 0.05, "lr_schedule": None,
                "n_train": 128, "n_val": 32, "exch_strategy": "zero1",
                "exchange_bucket_mb": 0.05, "exch_compression": "int8"},
        checkpoint_dir=str(ckpt),
        verbose=True,
    ),
    supervise=dict(max_restarts=3, stall_timeout_s=120.0,
                   startup_grace_s=600.0, backoff_base_s=0.2,
                   backoff_cap_s=1.0, poll_interval_s=0.25, seed=0,
                   env=env),
    elastic={"min_dp": 2},
)
report = h.wait()
print("world history", report.get("world_size_history"),
      "restarts", report["n_restarts"], "mttr", report["mttr_s"])
if not report["completed"]:
    sys.exit("bench_smoke: elastic run did not complete: %s" % report)
if report.get("world_size_history") != [8, 4]:
    sys.exit("bench_smoke: expected world history [8, 4], got %s"
             % report.get("world_size_history"))
ev = report["restarts"][0]
if not (ev["cause"] == "preemption" and ev["world_size"] == 4
        and ev["resharded"] is True):
    sys.exit("bench_smoke: elastic restart event off: %s" % ev)
from theanompi_tpu.utils import checkpoint_meta, latest_checkpoint
meta = checkpoint_meta(latest_checkpoint(ckpt, validate=True))
losses = meta["recorder"]["train_losses"]
if meta.get("world_size") != 4 or meta["epoch"] != n_epochs - 1:
    sys.exit("bench_smoke: final checkpoint not from the resized "
             "world: %s" % {k: meta.get(k) for k in
                            ("world_size", "epoch")})
if len(losses) != n_epochs * nb:
    sys.exit("bench_smoke: resumed progress off — %d losses, want %d"
             % (len(losses), n_epochs * nb))
print("bench_smoke: elastic shrink-resume OK")
PYEOF

# 6. serving_autoscale: control-plane smoke — short diurnal ramp over
#    2 TCP replica processes; the child itself asserts exact token
#    accounting and SLOs, this gate re-asserts the membership churn
#    (≥1 scale-up, ≥1 drained scale-down, zero sheds).
out=$(TM_SERVING_SMOKE=1 TM_BENCH_MODEL=serving_autoscale python bench.py)
printf '%s\n' "$out" | python -c '
import json, sys
row = json.loads(sys.stdin.readline())
auto = row["arms"]["autoscaled"]
print("autoscale saving", row.get("value"),
      "spawns", auto.get("n_spawns"), "retires", auto.get("n_retires"),
      "events", auto.get("scale_events"))
if not auto["all_ok"] or auto["n_shed"] != 0:
    sys.exit("bench_smoke: autoscale arm shed/failed requests: %s" % auto)
if auto["tokens_completed"] != auto["n_completed"] * row["max_tokens"]:
    sys.exit("bench_smoke: autoscale token accounting off: %s" % auto)
if not (auto["n_spawns"] >= 2 and auto["n_retires"] >= 1):
    sys.exit("bench_smoke: autoscale arm saw no scale-up+drained "
             "scale-down: %s" % auto)
print("bench_smoke: serving_autoscale OK")
'

# 7. step-phase profiler smoke + trajectory regression gate
out=$(TM_PROFILE_SMOKE=1 TM_BENCH_MODEL=profile python bench.py)
printf '%s\n' "$out" | python -c '
import json, sys
row = json.loads(sys.stdin.readline())
prof = row.get("llama_proxy") or {}
ov = row.get("profiler_overhead") or {}
print("profile coverage", prof.get("coverage"),
      "exchange legs", prof.get("n_exchange_legs"),
      "overhead", ov.get("worst_ratio"), "bound", ov.get("bound"))
if not prof:
    sys.exit("bench_smoke: profile row carried no llama_proxy "
             "decomposition: %s" % sorted(row))
if not abs(prof["coverage"] - 1.0) <= 0.05:
    sys.exit("bench_smoke: per-scope times do not sum to the step "
             "(coverage %s)" % prof["coverage"])
if not prof["n_exchange_legs"] >= 2:
    sys.exit("bench_smoke: exchange not decomposed per bucket: %s"
             % prof)
if not (ov and ov["worst_ratio"] < ov["bound"]):
    sys.exit("bench_smoke: profiled child wall past the overhead "
             "bound: %s" % ov)
# the gap against the speed of light needs the device's peak: named
# legs on the chip, null (with the MFU) on the CPU mesh
if (prof.get("measured_mfu") is None) != (prof.get("gap") is None):
    sys.exit("bench_smoke: MFU and gap attribution disagree on "
             "whether a peak is known: %s" % prof)
if prof.get("gap") is not None and not prof["gap"].get("legs"):
    sys.exit("bench_smoke: gap attribution missing named legs: %s"
             % prof.get("gap"))
print("bench_smoke: profile OK")
'

# 8. streaming-loader data plane (ISSUE 16): A/B + drills, all
#    asserted in the child; re-assert the row surfaced them.
out=$(TM_BENCH_MODEL=loader python bench.py)
printf '%s\n' "$out" | python -c '
import json, sys
row = json.loads(sys.stdin.readline())
ab = row.get("pipeline_ab") or {}
print("loader A/B bitwise", ab.get("bitwise_equal"),
      "wait sync/pipelined", ab.get("wait_frac_sync"),
      ab.get("wait_frac_pipelined"),
      "starved", ab.get("starved"),
      "elastic", ab.get("elastic_8to4"))
if "error" in ab:
    sys.exit("bench_smoke: loader pipeline A/B errored: %s"
             % ab["error"])
if ab.get("bitwise_equal") is not True:
    sys.exit("bench_smoke: pipelined feed not bitwise-equal to the "
             "synchronous feed: %s" % ab)
if not ab.get("wait_frac_pipelined", 1.0) <= 0.05:
    sys.exit("bench_smoke: pipelined feed exposed data wait not "
             "within noise of zero: %s" % ab)
if not (ab.get("starved") or 0) >= 1:
    sys.exit("bench_smoke: starvation drill recorded no degrade: %s"
             % ab)
el = ab.get("elastic_8to4") or {}
if el.get("lost") != 0 or el.get("dup") != 0 \
        or el.get("worlds") != [8, 4]:
    sys.exit("bench_smoke: elastic 8->4 sample accounting off: %s"
             % el)
sub = row.get("subrows") or {}
if not ("sync" in sub and "pipelined" in sub):
    sys.exit("bench_smoke: loader row carried no sync/pipelined "
             "subrows: %s" % sorted(sub))
print("bench_smoke: loader OK")
'

python scripts/bench_diff.py --gate
echo "bench_smoke: bench_diff --gate OK"
