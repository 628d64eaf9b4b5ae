"""moe (parallel/moe.py): share of the step program's device time in
instructions of the expert layer — router, sort and gathers, grouped
products, combine, forward, recomputed and backward (Adam over the
expert weights is the optimizer's, not the layer's)."""
from ._moe import scope_seconds


def read(facts):
    got = scope_seconds(facts)
    if got is None:
        return None
    by_scope, program_s, _ = got
    return sum(by_scope.values()) / program_s
