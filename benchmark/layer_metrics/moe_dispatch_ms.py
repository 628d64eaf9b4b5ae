"""moe (parallel/moe.py): device milliseconds a step in what is not
matrix work of the expert layer — ``moe_route`` (router, softmax,
top-k, aux moments), ``moe_dispatch`` (sort, row gather) and
``moe_combine`` (un-sort, sum of a token's rows), forward, recomputed and
backward."""
from ._moe import scope_seconds


def read(facts):
    got = scope_seconds(facts)
    if got is None:
        return None
    by_scope, _, steps = got
    other = sum(s for k, s in by_scope.items() if k != "moe_experts")
    return 1e3 * other / steps
