"""model step (models/llama.py ``Llama.step_peak_estimate``): GiB by
which the keep rule's account of the step's peak — its estimate's
terms and the bytes it kept — stands over the peak the runtime read
(``step_peak_bytes``): room the rule does not see, beside its
reserve.  The gap is signed and its target is a small positive
value: lower is better only down to 0, and a NEGATIVE reading is a
fault, not a gain — the rule then counts less than the step holds,
the direction that runs out of memory.  ``None`` for a model without
the rule."""
from ._memory import account_over_peak_gib as read  # noqa: F401
