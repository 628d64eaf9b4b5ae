"""model step (models/base.py, models/llama.py): device time of one
optimizer step — the step program's runs in the trace, start to end,
over the steps they hold (a K-step scan is divided by K)."""
from .. import trace_reduce as tr
from ._common import step_runs


def read(facts):
    runs = step_runs(facts)
    if not runs:
        return None
    total = sum(e - s for _, s, e in runs) * tr.PS
    return 1e3 * total / (len(runs) * facts["scan_k"])
