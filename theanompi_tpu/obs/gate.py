"""Gate counters of a training step with gated attention.

A ``Llama`` built with ``attention_gate`` gives out, beside its loss,
``[calls]`` numbers a step (``models/llama.py`` ``_attn_gate``).
``Recorder.attn_gate`` holds the device value and reads it with the
losses at its next fence — no fence and no host sync of its own — then
keeps the LAST step's counters here, a value a gated attention call,
in call order:

- ``attn_gate_open`` ``[calls]`` — the mean, over the step's tokens
  and the layer's query heads, of the sigmoid gate that multiplies the
  attention kernels' output before ``wo``: 0.5 at a seed's weights
  (the gate's product is zero-mean); a layer at 0 has switched its
  attention block off, one at 1 gates nothing.

The run summary carries them (``"attn_gate_counters"``) and they stay
readable afterwards with :func:`last_gate_counters`.  Names are a
contract (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import numpy as np

_LAST: dict | None = None


def gate_counters(means) -> dict:
    """``means [calls]`` of one step -> the counters' dict; also kept
    as the process's newest."""
    global _LAST
    _LAST = {"attn_gate_open": np.asarray(means, np.float64).tolist()}
    return _LAST


def last_gate_counters() -> dict | None:
    """The gate counters of the newest fenced step of a model with an
    attention gate in this process, or None before any."""
    return _LAST
