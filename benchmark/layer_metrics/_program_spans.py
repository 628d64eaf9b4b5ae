"""What the readers of the PROGRAM's own spans and counters share.

Since PR 24 the training path brackets its host work with
``jax.profiler.TraceAnnotation`` spans named ``tm:<layer>.<name>``
(``theanompi_tpu/utils/recorder.py``: ``Recorder.phase``), which land
in the host planes of the same ``.xplane.pb`` as the device's
"XLA Modules" line, on one clock; and it keeps a record of its set-up
phases with the seconds its compile counter saw in each
(``theanompi_tpu/obs/setup.py``).  ``trace_reduce.load_xplane`` keeps
only the benchmark's own ``bench:`` spans, so the ``tm:`` events are
read here: from the trace the run just wrote
(``<root>/.bench_scratch/<cell>/trace``), or from the ``"program"``
list of a recorded trace.

Every function returns ``None`` where there is nothing to read — a
program from before PR 24, a rehearsal on the CPU (its profiler
session has no device plane of the recorded chip trace it reduces),
a recorded trace without program spans — and never raises for that.
"""

from __future__ import annotations

import functools
import os
from pathlib import Path

from .. import trace_reduce as tr
from ._common import step_runs

ROOT = Path(__file__).resolve().parents[2]
PROGRAM_SPAN_PREFIX = "tm:"
FENCE = ("tm:worker.fence",)
#: the host's own work at an epoch boundary (the benchmark's hook
#: runs inside ``adjust_hyperp``)
BOUNDARY_HOST = ("tm:worker.end_epoch", "tm:worker.adjust_hyperp",
                 "tm:worker.shuffle", "tm:worker.load")
DISPATCH = ("tm:worker.dispatch",)


# -- reading -----------------------------------------------------------------


@functools.lru_cache(maxsize=2)
def _read_xplane(path: str, mtime_ns: int) -> tuple[frozenset, tuple]:
    """``(device plane names, tm: events of the host planes)`` of an
    ``.xplane.pb``; ``mtime_ns`` only keys the cache."""
    from jax.profiler import ProfileData

    devices, spans = set(), []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PLANE_PREFIX):
            devices.add(plane.name)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_SPAN_PREFIX):
                        start = int(round(e.start_ns * 1000))
                        spans.append((
                            e.name, start,
                            start + int(round(e.duration_ns * 1000)),
                        ))
    return frozenset(devices), tuple(sorted(spans, key=lambda s: s[1]))


def _spans_of_this_run(facts: dict, trace: dict) -> list:
    """The ``tm:`` events of the profiler session this run made, if
    that session is the one ``trace`` was read from (it holds the
    trace's device planes)."""
    directory = ROOT / ".bench_scratch" / facts["cell"]["name"] / "trace"
    try:
        path = tr.find_xplane(str(directory))
    except FileNotFoundError:
        return []
    devices, spans = _read_xplane(path, os.stat(path).st_mtime_ns)
    if not set(trace["devices"]) <= devices:
        return []
    return [list(s) for s in spans]


def program_spans(facts: dict) -> list | None:
    """``[[name, start_ps, end_ps], ...]`` of the program's spans
    that overlap the trace's device window, in order of their start."""
    trace = facts.get("trace")
    if not trace or not trace.get("devices"):
        return None
    spans = trace.get("program")
    if spans is None:
        spans = _spans_of_this_run(facts, trace)
    w0, w1 = tr.window_ps(trace)
    inside = [s for s in spans if s[2] > w0 and s[1] < w1]
    return sorted(inside, key=lambda s: s[1]) or None


def setup_phases(facts: dict) -> dict | None:
    """The set-up record of the training run the facts are of (the
    form of ``theanompi_tpu.obs.last_setup_phases``): a recorded
    trace's own where it has one, else this process's newest."""
    if "scan_k" not in facts:       # not a training run's facts
        return None
    recorded = (facts.get("trace") or {}).get("setup_phases")
    if recorded is None:
        try:
            from theanompi_tpu.obs import last_setup_phases
        except ImportError:         # a program from before PR 24
            return None
        recorded = last_setup_phases()
    return recorded if recorded and "setup" in recorded else None


# -- reductions --------------------------------------------------------------


def setup_seconds(facts: dict, names: tuple[str, ...]) -> float | None:
    """Seconds the named set-up phases spent themselves: less the
    phases nested in them and less the compile and cache-load seconds
    inside them, which ``setup_compile_s`` reports."""
    phases = setup_phases(facts)
    if phases is None:
        return None
    return sum(
        phases[n]["self_s"] - phases[n]["compile_s"]
        for n in names if n in phases
    )


def _overlap_ps(a: int, b: int, cover: list) -> int:
    return sum(max(0, min(b, e) - max(a, s)) for s, e in cover)


def boundary_ms(facts: dict, names: tuple[str, ...]) -> float | None:
    """Median, over the gaps between two runs of the step program on
    the first device, of the part of the gap under the named spans."""
    runs = step_runs(facts)
    spans = program_spans(facts)
    if not runs or len(runs) < 2 or spans is None:
        return None
    cover = tr.merge((s, e) for name, s, e in spans if name in names)
    parts = [
        _overlap_ps(a[2], b[1], cover) * tr.PS
        for a, b in zip(runs, runs[1:])
    ]
    return 1e3 * tr.median(parts)


def span_ms(facts: dict, names: tuple[str, ...]) -> float | None:
    """Median length of the named spans."""
    spans = program_spans(facts)
    lengths = [(e - s) * tr.PS for name, s, e in spans or () if name in names]
    return 1e3 * tr.median(lengths) if lengths else None


def leaves(spans: list) -> list:
    """The spans that hold no other span."""
    return [
        a for a in spans
        if not any(a[1] <= b[1] and b[2] <= a[2] and b[1:3] != a[1:3]
                   for b in spans)
    ]


def gap_named_share(facts: dict, floor_ps: int = 2_000_000) -> float | None:
    """The rule of ``trace_reduce.idle_gaps_by_host_span`` over the
    program's spans: of the first device's idle time in gaps of at
    least ``floor_ps``, the share in gaps whose midpoint lies under a
    leaf span of the program."""
    spans = program_spans(facts)
    if spans is None:
        return None
    trace = facts["trace"]
    first = trace["devices"][sorted(trace["devices"])[0]]
    busy = tr.merge((e[1], e[2]) for e in first["ops"])
    leaf = leaves(spans)
    named = total = 0
    for (_, a_end), (b_start, _) in zip(busy, busy[1:]):
        gap = b_start - a_end
        if gap < floor_ps:
            continue
        total += gap
        mid = (a_end + b_start) // 2
        if any(s <= mid <= e for _, s, e in leaf):
            named += gap
    return named / total if total else None
