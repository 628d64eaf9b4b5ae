"""exchange: bytes the step's collectives carry, from the compiled
HLO (a count: the instructions of the scan body run once a step)."""
from .. import hlo_read


def read(facts):
    found = hlo_read.collectives(facts.get("hlo_text", ""))
    if not found:
        return None
    return sum(c["bytes"] for c in found) / 1e6
