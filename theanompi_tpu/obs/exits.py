"""Exit counters of a looped decoder's training step.

A ``Llama`` with ``ut_steps`` R > 1 gives out, beside its loss,
``[2R + 1]`` numbers a step, token means over the global batch: the
exit distribution's mass ``q_t`` at each of the R exits, each exit's
cross-entropy, and the mean exit step ``sum_t t * q_t``.
``Recorder.ut_exits`` holds the device value and reads it with the
losses at its next fence — no fence and no host sync of its own —
then keeps the LAST step's counters here:

- ``ut_exit_mass`` ``[R]`` — sums to 1;
- ``ut_exit_loss`` ``[R]`` — a later exit's is lower once the passes
  refine the state;
- ``ut_mean_exit_step`` — 1..R; 1.875 of 4 at a zero gate (every
  exit but the last keeps half of what reaches it).

The run summary carries them (``"ut_counters"``) and they stay
readable afterwards with :func:`last_ut_counters`.  Names are a
contract (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import numpy as np

_LAST: dict | None = None


def ut_counters(vec) -> dict:
    """``vec [2R + 1]`` (see the module docstring) of one step -> the
    counters' dict; also kept as the process's newest."""
    global _LAST
    a = np.asarray(vec, np.float64)
    r = (a.shape[0] - 1) // 2
    _LAST = {
        "ut_exit_mass": a[:r].tolist(),
        "ut_exit_loss": a[r:2 * r].tolist(),
        "ut_mean_exit_step": float(a[-1]),
    }
    return _LAST


def last_ut_counters() -> dict | None:
    """The exit counters of the newest fenced step of a looped decoder
    in this process, or None before any."""
    return _LAST
