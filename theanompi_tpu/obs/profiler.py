"""Step-phase profiler: per-scope decomposition of a training step
with MFU/roofline gap attribution (ISSUE 15; ROADMAP 3a/3b's lever-
retirement artifact).

``utils/trace_comm`` answers ONE question — how much collective time
is exposed.  This module generalizes its HLO/named-scope attribution
into the full decomposition Theano-MPI's per-phase timing motivated:
every second of a measured training step lands in a NAMED leg,

- ``compute``       — the model forward/backward (the unscoped
  remainder of device busy time),
- ``exchange_b{i}`` — the gradient exchange, one leg per bucket
  (the ``jax.named_scope`` labels the exchange paths carry —
  registered in ``analysis/registry.PROFILE_SCOPES``, enforced by
  tmcheck rule TM107),
- ``quantize``      — the compressed wire's codec compute
  (``quantize_wire``/``dequantize_wire``),
- ``optimizer``     — the ``opt_update`` scope,
- ``host_gap``      — wall time no device op covers (dispatch
  latency, host-side staging),

each with the time measured from a device trace and — where the
caller's cost model prices them — FLOPs and bytes, yielding a
MEASURED MFU and arithmetic intensity per scope.

**Gap attribution** then splits predicted-vs-measured against
``scaling_model``'s speed-of-light: with ``ideal_s = flops / (n_dev *
peak)``, the step's gap ``measured - ideal`` decomposes into

- ``geometry``     — compute time beyond the ideal (MXU underfill,
  memory-bound ops, non-matmul time: the shape-vs-hardware story
  ROADMAP 3a/3b need proven or disproven),
- ``exposed_comm`` — collective time with no compute under it (the
  ``trace_comm`` figure; ``scaling_model.bsp_efficiency`` predicts
  it, and the report carries predicted-vs-measured when given),
- ``quantize`` / ``optimizer`` — priced overhead legs,
- ``host``         — the host gap.

Every leg is measured, so the attribution SUMS: ``coverage`` ≈ 1 is
asserted by ``tests/test_profiler.py``.

The profile exports into the PR-12 Perfetto timeline: ``spans()``
renders the decomposition as one span tree and ``counter_tracks()``
as Chrome-trace counter series, so a bench run's StepProfile and its
request traces open as ONE view (``obs/export.chrome_trace``).
"""

from __future__ import annotations

import itertools
import os
import re
import time
from collections import OrderedDict
from dataclasses import dataclass, field

#: profile span-id allocator: (pid-tagged counter) << 12 leaves room
#: for 4095 leg ids under each root — wall-clock-derived ids collide
#: when two profiles are built in the same microsecond (the bench
#: child builds llama + googlenet back to back)
_PROFILE_IDS = itertools.count(1)


def _new_profile_id() -> int:
    return (
        ((os.getpid() & 0xFFFF) << 32)
        | ((next(_PROFILE_IDS) & 0xFFFFF) << 12)
    )

from theanompi_tpu.analysis.registry import (
    PROFILE_SCOPE_PREFIXES,
    PROFILE_SCOPES,
)

#: leg-name ordering for reports (scope legs sort between these)
_LEG_HEAD = ("compute",)
_LEG_TAIL = ("host_gap",)


def _scope_label_re():
    """One regex matching any registered scope label inside an HLO
    ``op_name`` string: exact labels and prefix families (longest
    match first so ``exchange_b12`` beats ``exchange_b1``)."""
    exact = sorted(PROFILE_SCOPES, key=len, reverse=True)
    pref = [p + r"\d+" for p in PROFILE_SCOPE_PREFIXES]
    return re.compile(
        "(" + "|".join(pref + [re.escape(x) for x in exact]) + ")"
    )


def profile_scope_sets(
    hlo_text: str, aux_hlo_texts=(),
) -> "OrderedDict[str, set]":
    """Ordered ``{leg_name: set(instruction names)}`` extracted from
    optimized-HLO text — the ``scopes=`` argument for
    ``trace_comm.comm_report``.

    Exact labels group under their registered leg (both codec halves
    land in ``quantize``); prefix-family labels keep the full label
    as the leg name (``exchange_b0``, ``exchange_b1``, …).  Leg order
    is exact-label legs first: attribution is first-match-wins, so a
    nested ``exchange_b0/quantize_wire`` op counts as ``quantize``,
    not as bucket wire time.

    ``aux_hlo_texts`` — optimized HLO of OTHER executables that run
    inside the profiled window (the batch-staging ``host_load``
    module: ``device_put`` is not a traced op, so the feed's device
    cost can only carry a scope through its own tiny executable).
    HLO instruction names are module-unique, not trace-unique — an
    aux module's ``fusion.1`` would claim the main step's ``fusion.1``
    events — so aux marker names colliding with ANY main-module
    instruction name are dropped (the PR 6 collision lesson)."""
    from theanompi_tpu.utils.trace_comm import hlo_instr_re

    instr_re = hlo_instr_re()
    label_re = _scope_label_re()
    exact_legs: OrderedDict[str, set] = OrderedDict(
        (leg, set()) for leg in dict.fromkeys(PROFILE_SCOPES.values())
    )
    prefix_legs: OrderedDict[str, set] = OrderedDict()
    for m in instr_re.finditer(hlo_text):
        name, op_name = m.group(1), m.group(2)
        # the op_name is the name STACK (outer/inner); the INNERMOST
        # registered scope is the specific one — a nested
        # exchange_b0/quantize_wire op is quantize compute, not
        # bucket wire time
        lms = list(label_re.finditer(op_name))
        if not lms:
            continue
        label = lms[-1].group(1)
        if label in PROFILE_SCOPES:
            exact_legs[PROFILE_SCOPES[label]].add(name)
        else:
            prefix_legs.setdefault(label, set()).add(name)
    out: OrderedDict[str, set] = OrderedDict(
        (leg, ops) for leg, ops in exact_legs.items() if ops
    )
    for label in sorted(prefix_legs, key=_bucket_sort_key):
        out[label] = prefix_legs[label]
    if aux_hlo_texts:
        from theanompi_tpu.utils.trace_comm import (
            hlo_instruction_names,
        )

        main_names = hlo_instruction_names(hlo_text)
        for aux in aux_hlo_texts:
            if not aux:
                continue
            for leg, ops in profile_scope_sets(aux).items():
                out.setdefault(leg, set()).update(ops - main_names)
    return out


def _bucket_sort_key(label: str):
    m = re.search(r"(\d+)$", label)
    return (label[: m.start()] if m else label,
            int(m.group(1)) if m else -1)


@dataclass
class StepProfile:
    """One profiled training-step decomposition (see module doc).

    Times are PER STEP: ``legs[name]["time_s"]`` is the per-core
    average (core-seconds / n_cores / n_steps), so the legs sum to
    the measured step wall; ``core_s`` keeps the raw core-seconds."""

    name: str
    n_steps: int
    n_devices: int
    n_cores: int
    step_s: float                     # measured wall per step
    device_busy_s: float              # core-seconds over the window
    legs: "OrderedDict[str, dict]"
    exposed_comm_s: float = 0.0       # per step, per-core average
    collective_s: float = 0.0         # per step, per-core average
    peak_flops: float | None = None   # per device
    step_flops: float | None = None   # per step, all devices
    step_bytes: float | None = None
    measured_mfu: float | None = None
    gap: dict | None = None
    trace_report: dict = field(default_factory=dict, repr=False)

    @property
    def coverage(self) -> float:
        """Σ legs / measured step wall (≈ 1.0 — the 5% acceptance
        bar; host_gap is a measured remainder, never negative, so
        over-1 coverage means trace events exceeded the wall)."""
        total = sum(v["time_s"] for v in self.legs.values())
        return total / self.step_s if self.step_s else 0.0

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            "n_steps": self.n_steps,
            "n_devices": self.n_devices,
            "n_cores": self.n_cores,
            "step_s": self.step_s,
            "device_busy_s": self.device_busy_s,
            "legs": {k: dict(v) for k, v in self.legs.items()},
            "coverage": self.coverage,
            "exposed_comm_s": self.exposed_comm_s,
            "collective_s": self.collective_s,
            "measured_mfu": self.measured_mfu,
            "step_flops": self.step_flops,
            "step_bytes": self.step_bytes,
            "gap": self.gap,
        }

    # -- Perfetto export (obs/export.py) -----------------------------------

    def spans(self, *, process: str = "profiler",
              t0: float | None = None) -> list:
        """The decomposition as one span tree (plain span dicts, the
        tracer's schema): a ``step_profile:<name>`` root spanning one
        measured step, with each leg a child laid out serially in
        report order — so the profile opens in the SAME Perfetto view
        as the request traces (``chrome_trace(spans + profile.spans()
        , counters=profile.counter_tracks())``)."""
        t0 = time.time() if t0 is None else float(t0)
        tid = _new_profile_id()
        root = {
            "trace_id": tid, "span_id": tid, "parent_id": None,
            "name": f"step_profile:{self.name}",
            "t0": t0, "t1": t0 + self.step_s,
            "process": process, "lane": self.name,
            "attrs": {
                "coverage": round(self.coverage, 4),
                "measured_mfu": self.measured_mfu,
                "n_steps": self.n_steps,
            },
        }
        out = [root]
        cur = t0
        for i, (leg, v) in enumerate(self.legs.items()):
            out.append({
                "trace_id": tid, "span_id": tid + i + 1,
                "parent_id": tid, "name": leg,
                "t0": cur, "t1": cur + v["time_s"],
                "process": process, "lane": self.name,
                "attrs": {
                    k: v[k] for k in ("mfu", "intensity", "flops",
                                      "bytes", "comm_s")
                    if v.get(k) is not None
                },
            })
            cur += v["time_s"]
        return out

    def counter_tracks(self, *, process: str = "profiler",
                       t: float | None = None) -> list:
        """Chrome-trace counter samples (``obs/export.chrome_trace``'s
        ``counters=``): one ``step_phase_s`` track with a series per
        leg, plus ``mfu`` tracks for the legs that price one — the
        gauges that ride next to the serving recorder's queue/block
        tracks in the single-view export."""
        t = time.time() if t is None else float(t)
        out = [{
            "process": process,
            "name": f"step_phase_s:{self.name}",
            "t": t,
            "values": {
                leg: round(v["time_s"], 6)
                for leg, v in self.legs.items()
            },
        }]
        mfus = {
            leg: round(v["mfu"], 4)
            for leg, v in self.legs.items() if v.get("mfu") is not None
        }
        if self.measured_mfu is not None:
            mfus["step"] = round(self.measured_mfu, 4)
        if mfus:
            out.append({
                "process": process,
                "name": f"mfu:{self.name}",
                "t": t,
                "values": mfus,
            })
        return out


def _normalize_leg_costs(leg_costs: dict | None,
                         step_flops: float | None,
                         step_bytes: float | None) -> dict:
    """Deep-copy the caller's per-leg cost dict and inject the step's
    FLOPs/bytes as the compute leg's defaults.  The COPY is the
    contract: an A/B harness reusing one dict across profiles must
    never see model A's flops priced into model B's compute leg."""
    out = {k: dict(v) for k, v in (leg_costs or {}).items()}
    if step_flops is not None:
        out.setdefault("compute", {})
        out["compute"].setdefault("flops", step_flops)
        if step_bytes is not None:
            out["compute"].setdefault("bytes", step_bytes)
    return out


def step_profile(
    run_fn,
    *,
    hlo_text: str,
    aux_hlo_texts=(),
    n_steps: int,
    n_devices: int,
    name: str = "train_step",
    peak_flops: float | None = None,
    step_flops: float | None = None,
    step_bytes: float | None = None,
    leg_costs: dict | None = None,
    predicted: dict | None = None,
    trace_dir: str | None = None,
) -> StepProfile:
    """Capture ONE profiled window of ``run_fn`` (which must run
    ``n_steps`` training steps and fence its own device work by a
    value read) and decompose it.

    ``hlo_text`` — optimized HLO of the step executable
    (``trace_comm.compiled_hlo_text``), the source of the per-scope
    instruction-name sets; ``aux_hlo_texts`` — HLO of other
    executables in the window (batch staging: ``model.
    stage_hlo_text()``), collision-filtered per
    ``profile_scope_sets``.  ``peak_flops`` — per-device peak (the
    MFU denominator); ``step_flops``/``step_bytes`` — one step's
    total FLOPs/bytes across devices (XLA ``cost_analysis``).

    ``leg_costs`` — optional ``{leg: {"flops": f, "bytes": b}}``
    pricing individual legs (wire bytes from
    ``scaling_model.exchange_wire_bytes``, optimizer/quantize from
    the element counts); the ``compute`` leg defaults to
    ``step_flops``/``step_bytes`` minus nothing — the model body IS
    the flops carrier.

    ``predicted`` — a ``scaling_model`` row to attribute the gap
    against; recognized keys: ``t_exposed_ms`` (``bsp_efficiency`` /
    ``bucketed_overlap``'s ``t_exposed_bucketed_ms``) and ``mfu``.
    """
    import tempfile

    from theanompi_tpu.utils import trace_comm

    scopes = profile_scope_sets(hlo_text, aux_hlo_texts)
    wall_box: list[float] = []

    def timed():
        t0 = time.perf_counter()
        out = run_fn()
        wall_box.append(time.perf_counter() - t0)
        return out

    if trace_dir is not None:
        trace_comm.capture_trace(timed, trace_dir)
        rep = trace_comm.comm_report(trace_dir, scopes=scopes)
    else:
        with tempfile.TemporaryDirectory() as td:
            trace_comm.capture_trace(timed, td)
            rep = trace_comm.comm_report(td, scopes=scopes)

    wall = wall_box[0]
    step_s = wall / n_steps
    n_cores = max(1, rep["n_cores"])
    per_step_core = 1.0 / (n_cores * n_steps)

    legs: OrderedDict[str, dict] = OrderedDict()
    leg_costs = _normalize_leg_costs(leg_costs, step_flops, step_bytes)

    def _leg(leg_name, time_s, comm_s=None, core_s=None):
        c = leg_costs.get(leg_name, {})
        flops, bts = c.get("flops"), c.get("bytes")
        row = {
            "time_s": time_s,
            "core_s": core_s if core_s is not None
            else time_s * n_cores * n_steps,
        }
        if comm_s is not None:
            row["comm_s"] = comm_s
        if flops is not None:
            row["flops"] = flops
            if peak_flops and time_s > 0:
                # scope flops are per step across devices; scope time
                # is per-core-average — MFU over the whole slice
                row["mfu"] = flops / (time_s * n_devices * peak_flops)
        if bts is not None:
            row["bytes"] = bts
        if flops is not None and bts:
            row["intensity"] = flops / bts
        return row

    scoped_core_s = 0.0
    for leg_name in scopes:
        core_s = rep["scope_s"].get(leg_name, 0.0)
        scoped_core_s += core_s
        legs[leg_name] = _leg(
            leg_name,
            core_s * per_step_core,
            comm_s=rep["scope_comm_s"].get(leg_name, 0.0)
            * per_step_core,
            core_s=core_s,
        )

    # collectives OUTSIDE any exchange scope (loss/err pmean, BN-stat
    # sync) — their own leg so the exchange buckets stay pure
    unscoped_comm = rep["collective_s"] - sum(
        rep["scope_comm_s"].values()
    )
    if unscoped_comm > 1e-12:
        legs["exchange_other"] = _leg(
            "exchange_other", unscoped_comm * per_step_core,
            comm_s=unscoped_comm * per_step_core, core_s=unscoped_comm,
        )
        scoped_core_s += unscoped_comm

    # the model body: busy time no scope (and no bare collective)
    # claimed — the leg the step's FLOPs live in (cost injection
    # happened in _normalize_leg_costs)
    compute_core_s = max(0.0, rep["device_busy_s"] - scoped_core_s)
    compute = _leg("compute", compute_core_s * per_step_core,
                   core_s=compute_core_s)
    # wall no device op covers: dispatch latency, host staging
    host_s = max(0.0, step_s - rep["device_busy_s"] * per_step_core)
    ordered: OrderedDict[str, dict] = OrderedDict()
    ordered["compute"] = compute
    for k, v in legs.items():
        ordered[k] = v
    ordered["host_gap"] = _leg("host_gap", host_s, core_s=host_s)

    exposed = rep["exposed_comm_s"] * per_step_core
    prof = StepProfile(
        name=name,
        n_steps=n_steps,
        n_devices=n_devices,
        n_cores=n_cores,
        step_s=step_s,
        device_busy_s=rep["device_busy_s"],
        legs=ordered,
        exposed_comm_s=exposed,
        collective_s=rep["collective_s"] * per_step_core,
        peak_flops=peak_flops,
        step_flops=step_flops,
        step_bytes=step_bytes,
        trace_report=rep,
    )
    if step_flops and peak_flops:
        prof.measured_mfu = step_flops / (
            step_s * n_devices * peak_flops
        )
    prof.gap = gap_attribution(prof, predicted=predicted)
    return prof


def gap_attribution(profile: StepProfile,
                    predicted: dict | None = None) -> dict | None:
    """Split the measured step's gap against the speed-of-light into
    named legs (module doc): geometry vs exposed comm vs priced
    overheads vs host.  Needs ``step_flops`` + ``peak_flops`` (the
    ideal-time denominator); returns None without them."""
    if not (profile.step_flops and profile.peak_flops):
        return None
    ideal = profile.step_flops / (
        profile.n_devices * profile.peak_flops
    )
    overhead_legs = {
        leg: v["time_s"] for leg, v in profile.legs.items()
        if leg in ("quantize", "optimizer")
    }
    host = profile.legs.get("host_gap", {}).get("time_s", 0.0)
    exposed = profile.exposed_comm_s
    compute_s = profile.legs.get("compute", {}).get("time_s", 0.0)
    # hidden comm overlaps compute on the same core and never extends
    # the wall; geometry is the compute leg's excess over ideal
    geometry = max(0.0, compute_s - ideal)
    legs = {
        "geometry_s": geometry,
        "exposed_comm_s": exposed,
        **{f"{k}_s": v for k, v in overhead_legs.items()},
        "host_s": host,
    }
    attributed = ideal + sum(legs.values())
    out = {
        "measured_step_s": profile.step_s,
        "ideal_step_s": ideal,
        "measured_mfu": profile.measured_mfu,
        "gap_s": profile.step_s - ideal,
        "legs": legs,
        "coverage": attributed / profile.step_s
        if profile.step_s else None,
    }
    if predicted:
        if predicted.get("t_exposed_ms") is not None:
            out["predicted_exposed_comm_s"] = (
                predicted["t_exposed_ms"] / 1e3
            )
        for k in ("t_exposed_bucketed_ms",):
            if predicted.get(k) is not None:
                out["predicted_exposed_comm_s"] = predicted[k] / 1e3
        if predicted.get("mfu") is not None:
            out["predicted_mfu"] = predicted["mfu"]
        out["predicted"] = dict(predicted)
    return out


def format_profile(profile: StepProfile) -> str:
    """Human-readable one-leg-per-line rendering."""
    lines = [
        f"step profile {profile.name}: {profile.step_s * 1e3:.2f} ms/"
        f"step x {profile.n_steps} steps, {profile.n_cores} op "
        f"timelines, coverage {profile.coverage:.3f}"
        + (f", MFU {profile.measured_mfu:.4f}"
           if profile.measured_mfu is not None else "")
    ]
    for leg, v in profile.legs.items():
        extra = ""
        if v.get("mfu") is not None:
            extra += f"  mfu={v['mfu']:.4f}"
        if v.get("intensity") is not None:
            extra += f"  flops/byte={v['intensity']:.1f}"
        if v.get("comm_s") is not None:
            extra += f"  comm={v['comm_s'] * 1e3:.3f}ms"
        lines.append(
            f"  {v['time_s'] * 1e3:9.3f} ms  "
            f"{v['time_s'] / profile.step_s if profile.step_s else 0:6.1%}"
            f"  {leg}{extra}"
        )
    gap = profile.gap
    if gap:
        lines.append(
            f"gap vs speed-of-light: ideal "
            f"{gap['ideal_step_s'] * 1e3:.3f} ms, gap "
            f"{gap['gap_s'] * 1e3:.3f} ms"
        )
        for leg, v in gap["legs"].items():
            lines.append(f"  {v * 1e3:9.3f} ms  {leg}")
    return "\n".join(lines)
