"""moe (parallel/moe.py ``shared_expert``): device milliseconds a
step in instructions under ``moe_shared`` — the dense SwiGLU every
token goes through beside the routed experts (three products, no
routing), forward, replay and backward with its weight gradients.
Part of ``ffn_block_ms``; not part of ``moe_step_share``, which
counts the routed path's four scopes."""
from ._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "moe_shared")
