"""exchange (parallel/exchange.py, strategies.py): per optimizer step,
time inside collective instructions during which no compute
instruction ran on that device.  A program without a collective in
its HLO (one chip) has nothing to read."""
from .. import hlo_read
from .. import trace_reduce as tr
from ._common import step_runs


def read(facts):
    runs = step_runs(facts)
    if not runs or not hlo_read.collectives(facts.get("hlo_text", "")):
        return None
    _, exposed = tr.exposed_collective_seconds(facts["trace"])
    return 1e3 * exposed / (len(runs) * facts["scan_k"])
