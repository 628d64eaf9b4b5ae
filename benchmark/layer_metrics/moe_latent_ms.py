"""moe (parallel/moe.py ``moe_ffn`` with ``latent``): device
milliseconds a step in instructions under ``moe_latent`` — the two
projections around the routed experts of a layer whose experts live in
a latent: ``[N, D] x [D, D_lat]`` before the dispatch (the gathered
rows are ``D_lat`` wide) and ``[N, D_lat] x [D_lat, D]`` after the
picks' sum; forward, replay and backward, their weight-gradient
products with the Adam update XLA fused into them.  Inside ``blk_ffn``,
outside the routed path's four scopes (``moe_step_share``) and
``moe_shared``.  ``None`` for a program without the scope (every
program from before PR 55, every model whose experts read the full
width)."""
from ._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "moe_latent")
