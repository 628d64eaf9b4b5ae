"""worker loop: of the same gaps, the part under the host's own work
at the epoch boundary — ``tm:worker.end_epoch``, ``adjust_hyperp``
(with the benchmark's hook inside it), ``shuffle`` and ``load`` (the
permutation staged for the next chunk); median over the gaps."""
from ._program_spans import BOUNDARY_HOST, boundary_ms


def read(facts):
    return boundary_ms(facts, BOUNDARY_HOST)
