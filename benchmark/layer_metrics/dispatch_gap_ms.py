"""worker loop (workers/bsp_worker.py): median device-idle gap between
consecutive runs of the step program.  The benchmark lays a run out as
epochs of one scan chunk, so every gap is an EPOCH BOUNDARY: the
recorder's fence, shuffle, ``adjust_hyperp`` and the next dispatch.
The loop's back-to-back dispatch inside an epoch runs in no cell yet
(PERF.md, Open questions)."""
from .. import trace_reduce as tr
from ._common import step_runs


def read(facts):
    runs = step_runs(facts)
    if not runs or len(runs) < 2:
        return None
    return 1e3 * tr.median(tr.gaps_between(runs))
