"""From a profiler trace to numbers: the one reduction every PR uses.

A trace is read into a neutral form first::

    {"devices": {plane: {"ops": [[name, start_ps, end_ps], ...],
                         "modules": [[name, start_ps, end_ps], ...]}},
     "host": [[name, start_ps, end_ps], ...]}

``ops`` are the events of a device plane's "XLA Ops" line (one per
executed HLO instruction, under its instruction name, nested where a
``while`` or a ``call`` holds others), ``modules`` those of its "XLA
Modules" line (one per executed program) and ``host`` the benchmark's
own ``jax.profiler.TraceAnnotation`` spans (names starting with
``bench:``).  The profiler names an op event by the instruction's
whole text; the neutral form keeps the instruction name and, once per
name under ``"text"``, the text without layouts, cut to 240
characters: result shapes and operands say what a ``fusion.754`` is.  ``load_xplane`` makes that form from an ``.xplane.pb``
with nothing but JAX; ``recorded/`` holds a trace of the chip in the
same form, which ``tests/test_trace_reduce.py`` reduces to known values.

The interval arithmetic (`merge`, `subtract`, `is_collective`) is a
copy of ``theanompi_tpu/utils/trace_comm.py``'s, kept here so that no
PR that claims a gain can change how its gain is computed.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import statistics
from typing import Iterable

PS = 1e-12
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_SPAN_PREFIX = "bench:"
COLLECTIVE_MARKERS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
    "all_reduce", "all_gather", "reduce_scatter", "all_to_all",
    "collective_permute", "psum", "pmean", "pmax",
)
# instructions that only hold other instructions: their own interval
# says the program is running, not which unit is at work
CONTAINER_PREFIXES = ("while", "conditional", "call")

Interval = tuple[int, int]


# -- reading -----------------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """Neutral form of an ``.xplane.pb`` (see the module docstring)."""
    from jax.profiler import ProfileData

    def ps(e) -> tuple[int, int]:
        start = int(round(e.start_ns * 1000))
        return start, start + int(round(e.duration_ns * 1000))

    devices: dict = {}
    host: list = []
    text: dict = {}

    def op(e) -> list:
        name = instruction_name(e.name)
        if name not in text and name != e.name:
            text[name] = _LAYOUT.sub("", e.name)[:240]
        return [name, *ps(e)]
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            if " " in plane.name[len(DEVICE_PLANE_PREFIX):]:
                continue    # e.g. "/device:TPU:0 SparseCore": not the core
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key == "ops":
                    dev[key].extend(op(e) for e in line.events)
                elif key == "modules":
                    dev[key].extend([e.name, *ps(e)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, *ps(e)] for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX)
                )
    for dev in devices.values():
        dev["ops"].sort(key=lambda e: (e[1], -e[2]))
        dev["modules"].sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host, "text": text}


_LAYOUT = re.compile(r"\{[^{}]*\}")


def instruction_name(event_name: str) -> str:
    """``%fusion.7 = f32[8]{0} fusion(...)`` -> ``fusion.7``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_recorded(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def clip(trace: dict, start_ps: int, end_ps: int,
         cut_programs: bool = False) -> dict:
    """The events that lie wholly inside ``[start_ps, end_ps]``; with
    ``cut_programs`` a program run that overlaps the interval is kept,
    cut to it (a slice of one long run, for a small recorded trace)."""
    def keep(events):
        return [e for e in events if e[1] >= start_ps and e[2] <= end_ps]

    def cut(events):
        return [[e[0], max(e[1], start_ps), min(e[2], end_ps)]
                for e in events if e[1] < end_ps and e[2] > start_ps]

    return {
        "devices": {
            name: {"ops": keep(d["ops"]),
                   "modules": (cut if cut_programs else keep)(d["modules"])}
            for name, d in trace["devices"].items()
        },
        "host": keep(trace["host"]),
        "text": trace.get("text", {}),
    }


# -- interval arithmetic (copied from utils/trace_comm.py) -------------------


def merge(iv: Iterable[Interval]) -> list[Interval]:
    iv = sorted(iv)
    if not iv:
        return []
    out = [iv[0]]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def span(iv: Iterable[Interval]) -> int:
    return sum(e - s for s, e in iv)


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """Interval-set difference a - b (both merged and sorted)."""
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


def is_collective(op_name: str) -> bool:
    name = op_name.lower()
    if "fusion" in name:        # a fused epilogue is compute
        return False
    prefix = name.split(".", 1)[0]
    return any(m in prefix for m in COLLECTIVE_MARKERS)


def is_container(op_name: str) -> bool:
    prefix = op_name.lower().lstrip("%").split(".", 1)[0]
    return prefix in CONTAINER_PREFIXES


# -- reductions --------------------------------------------------------------


def window_ps(trace: dict) -> Interval:
    """First start to last end over every device event."""
    starts, ends = [], []
    for d in trace["devices"].values():
        for e in d["ops"] + d["modules"]:
            starts.append(e[1])
            ends.append(e[2])
    if not starts:
        raise ValueError("no device event in the trace")
    return min(starts), max(ends)


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices:
    the union of each device's op intervals."""
    per_dev = [
        span(merge((e[1], e[2]) for e in d["ops"])) * PS
        for d in trace["devices"].values()
    ]
    return sum(per_dev) / len(per_dev)


def self_seconds(ops: list) -> dict[str, float]:
    """Seconds per instruction name, each event less the events
    nested inside it (events of one line nest properly)."""
    out: dict[str, float] = {}
    stack: list = []        # [name, end, self_ps]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, _, self_ps = stack.pop()
            out[name] = out.get(name, 0.0) + self_ps * PS

    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(1 << 62)
    return out


def self_seconds_by_name(trace: dict) -> dict[str, float]:
    """Self time per instruction name, summed over the devices and
    divided by their number."""
    total: dict[str, float] = {}
    for d in trace["devices"].values():
        for name, sec in self_seconds(d["ops"]).items():
            total[name] = total.get(name, 0.0) + sec
    n_dev = len(trace["devices"])
    return {name: sec / n_dev for name, sec in total.items()}


def top_device_ops(by_name: dict[str, float], text: dict,
                   n: int = 4) -> list[list]:
    """The single instructions that took most device time, under
    their text where the trace kept it."""
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[text.get(name, name), sec] for name, sec in ranked]


def top_device_kinds(by_name: dict[str, float], n: int = 6) -> list[list]:
    """Device time by KIND of instruction: the instructions that share
    a name up to its number (XLA names a fusion after what it fused:
    ``convolution_convert_fusion``, ``multiply_reduce_fusion``), as
    ``kind (k instructions)``."""
    total: dict[str, float] = {}
    members: dict[str, int] = {}
    for name, sec in by_name.items():
        stem, _, number = name.rpartition(".")
        kind = stem if number.isdigit() and stem else name
        total[kind] = total.get(kind, 0.0) + sec
        members[kind] = members.get(kind, 0) + 1
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[f"{kind} ({members[kind]} instructions)", sec]
            for kind, sec in ranked]


def op_seconds_matching(trace: dict, match) -> tuple[float, int]:
    """``(seconds, events)`` of the ops whose name ``match`` accepts,
    per device (mean)."""
    sec, count = 0.0, 0
    for d in trace["devices"].values():
        for name, s, e in d["ops"]:
            if match(name):
                sec += (e - s) * PS
                count += 1
    n_dev = len(trace["devices"])
    return sec / n_dev, count // n_dev


def exposed_collective_seconds(trace: dict) -> tuple[float, float]:
    """``(collective, exposed)`` seconds per device (mean): the time
    inside collective instructions, and the part of it during which no
    compute instruction ran on that device."""
    tot, exposed = 0.0, 0.0
    for d in trace["devices"].values():
        comm, compute = [], []
        for name, s, e in d["ops"]:
            if is_container(name):
                continue
            (comm if is_collective(name) else compute).append((s, e))
        comm_m, compute_m = merge(comm), merge(compute)
        tot += span(comm_m) * PS
        exposed += span(subtract(comm_m, compute_m)) * PS
    n_dev = len(trace["devices"])
    return tot / n_dev, exposed / n_dev


def module_runs(trace: dict, name_part: str | None = None) -> list[list]:
    """Executed programs of the first device, in order, optionally
    only those whose name holds ``name_part``."""
    first = trace["devices"][sorted(trace["devices"])[0]]
    return [
        m for m in first["modules"]
        if name_part is None or name_part in m[0]
    ]


def busiest_module(trace: dict) -> str:
    """Name of the program that took most device time."""
    tot: dict[str, int] = {}
    for name, s, e in module_runs(trace):
        tot[name] = tot.get(name, 0) + e - s
    if not tot:
        raise ValueError("no program on the device's module line")
    return max(tot, key=tot.get)


def gaps_between(runs: list[list]) -> list[float]:
    """Idle seconds between consecutive program runs."""
    return [
        max(0, b[1] - a[2]) * PS for a, b in zip(runs, runs[1:])
    ]


def idle_gaps_by_host_span(trace: dict, n: int = 10,
                           floor_ps: int = 2_000_000) -> list[list]:
    """Idle time of the first device, summed by what the host was
    doing: each gap between device ops of at least ``floor_ps`` (2 us)
    goes to the innermost of the benchmark's host spans that covers
    its midpoint, or to ``unattributed``; the shorter ones, which no
    host action can explain, are summed as ``between_ops``.  Device
    and host events share the profiler's clock to within its alignment
    of the two."""
    first = trace["devices"][sorted(trace["devices"])[0]]
    busy = merge((e[1], e[2]) for e in first["ops"])
    host = sorted(trace["host"], key=lambda h: h[1])
    starts = [h[1] for h in host]
    total: dict[str, float] = {}
    for (_, a_end), (b_start, _) in zip(busy, busy[1:]):
        gap = b_start - a_end
        if gap < floor_ps:
            name = "between_ops"
        else:
            mid = (a_end + b_start) // 2
            hi = bisect.bisect_right(starts, mid)
            # spans nest a few deep at most: the innermost cover is
            # among the last few that started before the midpoint
            covering = [h for h in host[max(0, hi - 64):hi] if h[2] >= mid]
            name = (
                min(covering, key=lambda h: h[2] - h[1])[0]
                if covering else "unattributed"
            )
        total[name] = total.get(name, 0.0) + gap * PS
    return [
        [k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]
    ]


def summarize(trace: dict) -> dict:
    """What every traced run reports, whatever its cell."""
    w0, w1 = window_ps(trace)
    by_name = self_seconds_by_name(trace)
    return {
        "busy_s": busy_seconds(trace),
        "window_s": (w1 - w0) * PS,
        # six kinds of instruction, then the four single instructions
        # that took most: a step's time is spread over hundreds
        "device_ops": (top_device_kinds(by_name)
                       + top_device_ops(by_name, trace.get("text", {}))),
        "idle_gaps": idle_gaps_by_host_span(trace),
    }


def median(values: list[float]) -> float | None:
    return statistics.median(values) if values else None
