"""Scan counters of a training step with state-space (mamba) layers.

A ``Llama`` whose ``layer_types`` hold ``"mamba"`` gives out, beside
its loss, ``[L_mamba, 2]`` numbers a step (``ops/ssd.py``
``ssd_scan``).  ``Recorder.ssm_scan`` holds the device value and reads
it with the losses at its next fence — no fence and no host sync of
its own — then keeps the LAST step's counters here, a value a mamba
layer, in layer order:

- ``ssm_log_decay_min`` ``[L_mamba]`` — the most negative cumulative
  ``dt A`` inside one chunk (over the batch, the heads and the
  chunks): how near the chunked form's exponentials come to float32's
  range (``exp(-87)`` is its smallest normal number; a decay below it
  reads 0, which the recurrence's own product would too);
- ``ssm_state_rms`` ``[L_mamba]`` — RMS of the state the last chunk
  starts from: zero says the carry between chunks is dead (or the
  sequence is one chunk).

The run summary carries them (``"ssm_counters"``) and they stay
readable afterwards with :func:`last_ssm_counters`.  Names are a
contract (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import numpy as np

_LAST: dict | None = None


def ssm_counters(stats) -> dict:
    """``stats [L_mamba, 2]`` of one step -> the counters' dict; also
    kept as the process's newest."""
    global _LAST
    a = np.asarray(stats, np.float64)
    _LAST = {
        "ssm_log_decay_min": a[:, 0].tolist(),
        "ssm_state_rms": a[:, 1].tolist(),
    }
    return _LAST


def last_ssm_counters() -> dict | None:
    """The scan counters of the newest fenced step of a model with
    mamba layers in this process, or None before any."""
    return _LAST
