"""Profiler-trace-derived comm/calc attribution (SURVEY §5.1, §7).

With the exchange fused INSIDE the jitted train step (the whole point
of the TPU-native design), wall-clock fencing around host calls can no
longer see communication: the Recorder's ``comm`` segment is
structurally zero for BSP.  The honest split comes from the device
trace: capture a ``jax.profiler`` trace of a few steps, parse the
XLA op timeline per core, and classify op intervals as collective
(all-reduce / all-gather / reduce-scatter / collective-permute /
all-to-all / send / recv) or compute.

The report is OVERLAP-AWARE: collective time that runs concurrently
with compute on the same core is "hidden"; only collective time with
no compute under it is "exposed" (what a user actually pays).  The
reference measured comm by fencing MPI calls between train steps —
here the equivalent number is ``exposed_comm_frac``.

Parsing uses the ``xplane_pb2`` proto bundled with tensorflow (this
image ships it); the import is lazy so the training path never pays
for it.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Callable, Iterable

COLLECTIVE_MARKERS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
    "ragged-all-to-all",
    "send",
    "recv",
    # jax-derived HLO instruction names: manual-mode (shard_map)
    # collectives keep the primitive's name, e.g. "psum_invariant.7"
    # on the XLA:CPU thunk timeline (verified on this image)
    "psum",
    "pmean",
    "ppermute",
    "all_to_all",
    "all_gather",
    "reduce_scatter",
)

# XLA:CPU collective *coordination* events: the executing thread is
# stalled waiting for the other devices' threads — exposed comm time
# by definition (there is no separate device timeline on CPU).
CPU_WAIT_MARKERS = (
    "rendezvous",
    "wait: pending_threads",
    "wait for rendezvous",
)

# XLA:CPU executor scaffolding: these events SPAN the real thunk
# events on the same thread (ThunkExecutor::Execute covers the whole
# program), so counting them as compute would shadow every collective
# into "hidden".  They are scheduling wrappers, not op work — skipped.
CPU_WRAPPER_MARKERS = (
    "thunkexecutor::",
    "pjrtcpuexecutable::",
    "executehelper",
    "threadpoollistener",
)

# XLA:CPU execution-lane prefixes (the per-device client threads and
# the intra-op pools where warm thunks actually run).  The client
# class name varies with the runtime build (PjRtCpuClient,
# TfrtCpuClient); a spelling missing here shows as CPU-mesh traces
# reporting n_cores == 0, so every known one is matched.
CPU_LANE_PREFIXES = (
    "tf_xlapjrtcpuclient",
    "tf_xlatfrtcpuclient",
    "tf_xlaeigen",
)


def _xplane_pb2():
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:  # pragma: no cover
        raise ImportError(
            "trace parsing needs the xplane proto (bundled with "
            "tensorflow on this image)"
        ) from e
    return xplane_pb2


def capture_trace(fn: Callable[[], Any], trace_dir: str) -> Any:
    """Run ``fn`` under ``jax.profiler.trace`` writing to
    ``trace_dir``; returns ``fn``'s result."""
    import jax

    with jax.profiler.trace(trace_dir):
        out = fn()
        jax.block_until_ready(out) if out is not None else None
    return out


def report_of(fn: Callable[[], Any], top_n: int = 15,
              quant_ops: set | None = None,
              scopes: dict | None = None) -> dict:
    """Capture ``fn`` into a temp dir and return its ``comm_report``
    — the one-shot capture-and-attribute recipe of the multichip
    gate (``fn`` must fence its own device work,
    e.g. by a value read).  ``quant_ops`` — instruction names from
    ``scope_op_names`` to attribute as quantize/dequantize compute;
    ``scopes`` — the profiler's ordered per-leg op-name sets."""
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        capture_trace(fn, td)
        return comm_report(td, top_n=top_n, quant_ops=quant_ops,
                           scopes=scopes)


# -- quantize/dequantize attribution (exch_compression) ---------------------
#
# The quantize/dequantize of the compressed exchange lowers to fused
# elementwise HLO whose instruction names carry no hint of their
# origin ("convert_slice_fusion.2") — but the OPTIMIZED HLO keeps
# per-instruction metadata with the jax name-stack, and exchange.py
# wraps both codec halves in jax.named_scope("quantize_wire" /
# "dequantize_wire").  So the recipe is: extract the instruction
# names whose metadata op_name mentions those scopes from the
# compiled module's text, then hand the set to comm_report — trace
# events matching it are summed as ``quant_s`` (still compute for
# the hidden/exposed split: quantize work genuinely hides wire time).

QUANT_SCOPE_MARKERS = ("quantize_wire", "dequantize_wire")

_HLO_INSTR_RE = None


def hlo_instr_re():
    """The compiled instruction-metadata regex (public accessor —
    the step-phase profiler's per-scope extraction walks the same
    ``(name, op_name)`` pairs ``scope_op_names`` does)."""
    global _HLO_INSTR_RE
    import re

    if _HLO_INSTR_RE is None:
        _HLO_INSTR_RE = re.compile(
            r"%([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\""
        )
    return _HLO_INSTR_RE


def scope_op_names(hlo_text: str,
                   markers: tuple = QUANT_SCOPE_MARKERS) -> set[str]:
    """Instruction names (no ``%``) whose ``metadata={op_name=...}``
    mentions any of ``markers`` — matches the event names the
    profiler emits for those instructions.  Names from inside fused
    computations are included too; they never collide with top-level
    names (HLO instruction names are module-unique), so the extras
    are harmless.

    Module-unique is NOT trace-unique: every executable has its own
    ``fusion.1``.  When the traced run interleaves several
    executables, subtract ``hlo_instruction_names`` of the OTHER
    modules from the returned set, or their events get attributed
    here."""
    out = set()
    for m in hlo_instr_re().finditer(hlo_text):
        name, op_name = m.group(1), m.group(2)
        if any(mk in op_name for mk in markers):
            out.add(name)
    return out


def hlo_instruction_names(hlo_text: str) -> set[str]:
    """EVERY instruction name (no ``%``) in ``hlo_text``, op_name
    metadata or not — the subtrahend for cross-module collision
    filtering (see ``scope_op_names``): profiler events carry the
    bare instruction name, and an unrelated executable's
    ``fusion.1`` would otherwise count toward a marker set extracted
    from a different module."""
    import re

    return {
        m.group(1)
        for m in re.finditer(r"%([\w.\-]+)\s*=", hlo_text)
    }


def compiled_hlo_text(compiled) -> str:
    """Optimized-HLO text of a jax ``Compiled``."""
    return compiled.as_text()


def _latest_xplanes(trace_dir: str) -> list[str]:
    pattern = os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    )
    files = glob.glob(pattern)
    if not files:
        raise FileNotFoundError(
            f"no xplane.pb under {trace_dir!r} (pattern {pattern})"
        )
    # newest run only (trace() creates a timestamped run dir per call)
    runs: dict[str, list[str]] = {}
    for f in files:
        runs.setdefault(os.path.dirname(f), []).append(f)
    latest = max(runs, key=os.path.getmtime)
    return runs[latest]


def is_collective(op_name: str) -> bool:
    name = op_name.lower()
    # fusions are compute even when the fused producer's name embeds a
    # collective token (e.g. an "all_gather...fusion" elementwise
    # epilogue is mostly compute — counting it as comm skews the
    # attribution, ADVICE r3); real collective ops are never fusions
    if "fusion" in name:
        return False
    # anchor on the HLO instruction-name prefix ("psum_invariant.7" ->
    # "psum_invariant"), so a compute op whose suffix merely mentions
    # a collective doesn't misclassify
    prefix = name.split(".", 1)[0]
    return any(m in prefix for m in COLLECTIVE_MARKERS)


def _merge_intervals(iv: list[tuple[int, int]]) -> list[tuple[int, int]]:
    if not iv:
        return []
    iv.sort()
    out = [iv[0]]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _span(iv: Iterable[tuple[int, int]]) -> int:
    return sum(e - s for s, e in iv)


def _subtract(a: list[tuple[int, int]],
              b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Interval-set difference a - b (both merged/sorted)."""
    out = []
    bi = 0
    for s, e in a:
        cur = s
        while bi < len(b) and b[bi][1] <= cur:
            bi += 1
        j = bi
        while cur < e:
            if j >= len(b) or b[j][0] >= e:
                out.append((cur, e))
                break
            bs, be = b[j]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            j += 1
    return out


def comm_report(trace_dir: str, top_n: int = 15,
                quant_ops: set | None = None,
                scopes: dict | None = None) -> dict:
    """Parse the newest trace run under ``trace_dir`` into an
    overlap-aware comm/compute attribution.

    Two timeline layouts are understood (both verified on this image):

    - **TPU device planes** (``/device:TPU:N``): the sync ``XLA Ops``
      line is the core's op timeline; the ``Async XLA Ops`` line holds
      DMA/collective activity that OVERLAPS it.  Only collective
      events are taken from the async line — counting its prefetch
      copies as busy time would double-count the core (they run on
      DMA engines while the core computes).
    - **XLA:CPU host threads** (``/host:CPU`` plane,
      ``tf_XLAPjRtCpuClient/...`` lines — one per virtual device):
      thunk-level events carry HLO instruction names; ``Rendezvous`` /
      ``Wait: pending_threads`` events are cross-device coordination
      stalls and classify as collective time.

    Returns per-core-aggregated::

        {"device_busy_s", "collective_s", "exposed_comm_s",
         "exposed_comm_frac", "hidden_comm_s", "comm_frac",
         "overlapped_comm_s", "overlapped_comm_frac",
         "quant_s", "quant_frac",
         "n_cores", "top_collectives": [(name, seconds), ...]}

    ``overlapped_comm_s`` is collective time running CONCURRENTLY with
    compute on the same core (== ``hidden_comm_s``; the explicit name
    for the bucketed-exchange A/B, where the claim under test is
    precisely "wire time moved from exposed to overlapped");
    ``overlapped_comm_frac`` is its share of total collective time —
    1.0 means every collective second was hidden behind compute, 0.0
    means the exchange ran as a fully serialized tail.

    ``quant_ops`` (from ``scope_op_names``): instruction names of the
    compressed exchange's quantize/dequantize — their time is summed
    as ``quant_s``/``quant_frac`` (share of busy), the compute the
    wire compression COSTS, reported alongside what it saves.  Quant
    events still count as compute in the hidden/exposed split.

    ``scopes`` (the step-phase profiler's generalization,
    ``obs/profiler.py``): an ORDERED ``{leg_name: set(instruction
    names)}`` — every event is attributed to the FIRST scope whose
    set contains its op (first-match-wins, so a nested scope like
    ``exchange_b0/quantize_wire`` lands in whichever leg the caller
    lists first), summed into ``scope_s`` (all events) and
    ``scope_comm_s`` (the collective share), both in core-seconds.
    Events matching no scope are the unscoped remainder the caller
    derives from ``device_busy_s``.
    """
    xplane_pb2 = _xplane_pb2()

    # PER-CORE interval sets: an op timeline line is one core.  The
    # hidden/exposed split must be computed on the SAME core — a
    # collective stalling core A is exposed time even if core B is
    # computing, so pooling cores before the subtraction would
    # under-report exposure.  Totals are per-core sums (core-seconds).
    cores: dict[tuple[int, str, int], dict[str, list]] = {}
    per_op: dict[str, int] = {}
    per_op_all: dict[str, int] = {}
    quant_ps_box = [0]
    quant_ops = quant_ops or set()
    scopes = scopes or {}
    scope_ps = {name: 0 for name in scopes}
    scope_comm_ps = {name: 0 for name in scopes}

    def _record(core, op, s, e, *, comm):
        per_op_all[op] = per_op_all.get(op, 0) + (e - s)
        for name, ops in scopes.items():     # first match wins
            if op in ops:
                scope_ps[name] += e - s
                if comm:
                    scope_comm_ps[name] += e - s
                break
        if comm:
            core["comm"].append((s, e))
            per_op[op] = per_op.get(op, 0) + (e - s)
        else:
            core["compute"].append((s, e))
            if op in quant_ops:
                quant_ps_box[0] += e - s

    for pi, path in enumerate(_latest_xplanes(trace_dir)):
        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            name = plane.name
            is_host_cpu = name == "/host:CPU"
            if not (name.startswith("/device:") or "TPU" in name
                    or "XLA" in name or is_host_cpu):
                continue
            metadata = plane.event_metadata
            sync_lines, async_lines = [], []
            for li, line in enumerate(plane.lines):
                lname = (line.display_name or line.name or "").lower()
                if is_host_cpu:
                    # XLA:CPU execution lanes: per-device client
                    # threads (cold/inline thunks) AND the Eigen
                    # intra-op pool threads, where warm executions
                    # actually run their thunks (verified: convolution
                    # / all-reduce / Rendezvous events live on
                    # tf_XLAEigen lines once the executable is warm)
                    if lname.startswith(CPU_LANE_PREFIXES):
                        sync_lines.append((li, line, "cpu_thread"))
                elif "async" in lname and "xla ops" in lname:
                    async_lines.append((li, line))
                elif "xla ops" in lname or lname == "ops":
                    sync_lines.append((li, line, "sync"))

            first_core = None
            for li, line, mode in sync_lines:
                # positional key: line ids are not guaranteed distinct
                core = cores.setdefault(
                    (pi, name, li), {"comm": [], "compute": []}
                )
                first_core = first_core or core
                t0 = line.timestamp_ns
                for ev in line.events:
                    md = metadata.get(ev.metadata_id)
                    op = md.name if md is not None else ""
                    s = t0 * 1000 + ev.offset_ps
                    e = s + ev.duration_ps
                    if e <= s:
                        continue
                    oplow = op.lower()
                    if mode == "cpu_thread" and any(
                        m in oplow for m in CPU_WRAPPER_MARKERS
                    ):
                        continue
                    comm = is_collective(op) or (
                        mode == "cpu_thread"
                        and any(m in oplow for m in CPU_WAIT_MARKERS)
                    )
                    _record(core, op, s, e, comm=comm)
            # async-line events OVERLAP the plane's core (a real TPU
            # plane is one core: one sync + one async line).  Only
            # collective activity is taken — counting the async DMA
            # prefetches as busy time would double-count the core.
            for li, line in async_lines:
                if first_core is None:
                    first_core = cores.setdefault(
                        (pi, name, f"async{li}"),
                        {"comm": [], "compute": []},
                    )
                t0 = line.timestamp_ns
                for ev in line.events:
                    md = metadata.get(ev.metadata_id)
                    op = md.name if md is not None else ""
                    s = t0 * 1000 + ev.offset_ps
                    e = s + ev.duration_ps
                    if e <= s or not is_collective(op):
                        continue
                    _record(first_core, op, s, e, comm=True)

    busy_ps = comm_ps = exposed_ps = 0
    for core in cores.values():
        comm_m = _merge_intervals(core["comm"])
        compute_m = _merge_intervals(core["compute"])
        busy_m = _merge_intervals(comm_m + compute_m)
        exposed = _subtract(comm_m, compute_m)
        busy_ps += _span(busy_m)
        comm_ps += _span(comm_m)
        exposed_ps += _span(exposed)

    ps = 1e-12  # durations are picoseconds in the xplane
    busy_s = busy_ps * ps
    comm_s = comm_ps * ps
    exposed_s = exposed_ps * ps
    quant_s = quant_ps_box[0] * ps
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:8]
    return {
        "device_busy_s": busy_s,
        "collective_s": comm_s,
        "exposed_comm_s": exposed_s,
        "hidden_comm_s": comm_s - exposed_s,
        "overlapped_comm_s": comm_s - exposed_s,
        "quant_s": quant_s,
        "quant_frac": (quant_s / busy_s) if busy_s else 0.0,
        "comm_frac": (comm_s / busy_s) if busy_s else 0.0,
        "exposed_comm_frac": (exposed_s / busy_s) if busy_s else 0.0,
        "overlapped_comm_frac": (
            (comm_s - exposed_s) / comm_s if comm_s else 0.0
        ),
        "n_cores": len(cores),
        "scope_s": {k: v * ps for k, v in scope_ps.items()},
        "scope_comm_s": {k: v * ps for k, v in scope_comm_ps.items()},
        "top_collectives": [(k, v * ps) for k, v in top],
        "top_ops": [
            (k, v * ps)
            for k, v in sorted(
                per_op_all.items(), key=lambda kv: -kv[1]
            )[:top_n]
        ],
    }


def _main(argv) -> int:
    """CLI: ``python -m theanompi_tpu.utils.trace_comm <trace_dir>`` —
    print the overlap-aware comm/compute attribution + top ops of the
    newest profiler run under ``trace_dir``."""
    if len(argv) != 1:
        print("usage: python -m theanompi_tpu.utils.trace_comm "
              "<trace_dir>")
        return 2
    rep = comm_report(argv[0])
    print(f"device busy       {rep['device_busy_s']:.4f} core-seconds "
          f"({rep['n_cores']} op timelines)")
    print(f"collective        {rep['collective_s']:.4f}s "
          f"({rep['comm_frac']:.1%} of busy)")
    print(f"  exposed         {rep['exposed_comm_s']:.4f}s "
          f"({rep['exposed_comm_frac']:.1%} of busy)")
    print(f"  overlapped      {rep['overlapped_comm_s']:.4f}s "
          f"({rep['overlapped_comm_frac']:.1%} of collective time "
          f"hidden under compute)")
    if rep["top_collectives"]:
        print("top collectives:")
        for name, sec in rep["top_collectives"]:
            print(f"  {sec * 1e3:9.2f} ms  {name[:70]}")
    print("top ops:")
    busy = rep["device_busy_s"] or 1.0
    for name, sec in rep["top_ops"]:
        print(f"  {sec / busy:6.1%} {sec * 1e3:9.2f} ms  {name[:70]}")
    return 0


if __name__ == "__main__":  # pragma: no cover
    import sys

    raise SystemExit(_main(sys.argv[1:]))
