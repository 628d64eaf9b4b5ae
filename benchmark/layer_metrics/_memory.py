"""What the readers of the PROGRAM's memory account share.

Since PR 52 a training run writes one account of its device memory
(``theanompi_tpu/obs/memory.py``; the run summary's ``"memory"``,
``theanompi_tpu.obs.last_memory_account()``): the keep rule's side
from shapes (``rule``: the estimate's terms, the bytes kept and
unkept) and the runtime's side from ``memory_stats()`` samples at the
ends of the set-up phases, at the first fence and at the summary
(``resident_bytes``, ``step_peak_bytes``).

Every function returns ``None`` where there is nothing to read — facts
that are not a training run's, a program from before PR 52, a run
whose runtime gave no statistics (the CPU: a rehearsal), a model
without a keep rule — and never raises for that.
"""

from __future__ import annotations

GIB = 2 ** 30


def account(facts: dict) -> dict | None:
    """The memory account of the training run the facts are of: a
    recorded trace's own where it has one, else this process's newest;
    ``None`` without its runtime side."""
    if "scan_k" not in facts:       # not a training run's facts
        return None
    recorded = (facts.get("trace") or {}).get("memory")
    if recorded is None:
        try:
            from theanompi_tpu.obs import last_memory_account
        except ImportError:         # a program from before PR 52
            return None
        recorded = last_memory_account()
    if not recorded or recorded.get("step_peak_bytes") is None:
        return None
    return recorded


def runtime_gib(facts: dict, key: str) -> float | None:
    """GiB of one of the account's derived bytes (``resident_bytes``,
    ``step_peak_bytes``)."""
    got = account(facts)
    return None if got is None or got.get(key) is None else got[key] / GIB


def rule(facts: dict) -> dict | None:
    """The keep rule's side; ``None`` for a model without such a rule
    and where it did not run."""
    got = account(facts)
    return None if got is None else got.get("rule") or None


def rule_gib(facts: dict, key: str) -> float | None:
    """GiB of one of the rule's bytes (``kept_bytes``,
    ``unkept_bytes``, ``free_bytes``)."""
    got = rule(facts)
    return None if got is None else got[key] / GIB


def account_over_peak_gib(facts: dict) -> float | None:
    """GiB by which the rule's account of the step's peak (its terms
    and what it kept) stands over the peak the runtime read; signed:
    negative where the rule counts less than the step holds, a fault."""
    got = rule(facts)
    if got is None:
        return None
    counted = sum(got["terms"].values()) + got["kept_bytes"]
    return (counted - account(facts)["step_peak_bytes"]) / GIB
