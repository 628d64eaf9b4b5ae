"""moe (parallel/moe.py ``_dropless_experts`` with a held range): the
rows the held experts computed in the run's last fenced step over the
layer's ``k * N`` picks, worst (fullest) expert layer — the program's
own counter (``moe_rows_held``, ``moe_picks_per_step``); ``held / E``
(0.125 for 8 of 64) at balance.  The grouped kernels' time follows
it, so across seeds it is the witness of the step time's scatter."""
from ._scopes import moe_counters


def read(facts):
    counters = moe_counters(facts)
    if not counters or "moe_rows_held" not in counters:
        return None         # every expert is held, or no expert layer
    return max(counters["moe_rows_held"]) / counters["moe_picks_per_step"]
