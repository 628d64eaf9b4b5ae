"""What several readers share.  A reader is ``read(facts) -> float |
None``; ``None`` means there was nothing to read, and the harness then
leaves the metric out of the line."""

from __future__ import annotations

from .. import trace_reduce as tr


def idle_share(facts: dict) -> float | None:
    """1 - union of device-op intervals over the traced window."""
    trace = facts.get("trace")
    if not trace or not trace["devices"]:
        return None
    w0, w1 = tr.window_ps(trace)
    return 1.0 - tr.busy_seconds(trace) / ((w1 - w0) * tr.PS)


def peak_gib(facts: dict) -> float | None:
    peak = facts.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None


def step_runs(facts: dict) -> list | None:
    """Runs of the training step program in the trace (the program
    that took most device time), or None without one."""
    trace = facts.get("trace")
    if not trace or not trace["devices"] or "scan_k" not in facts:
        return None
    return tr.module_runs(trace, tr.busiest_module(trace))


def program_runs(facts: dict, which: str) -> list | None:
    """Runs of the serving program the configuration names under
    ``serving.trace_names[which]``."""
    trace = facts.get("trace")
    names = facts["cell"]["config"].get("serving", {}).get("trace_names")
    if not trace or not trace["devices"] or not names:
        return None
    return tr.module_runs(trace, names[which]) or None
