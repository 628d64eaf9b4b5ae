"""model step (models/llama.py ``_gqa``): device milliseconds a step in
instructions under ``attn_sliding`` — the window layers' attention
between ``attn_norm`` and the output projection: the three
projections, the rotation by the window layers' table, the GQA repeat
and the band's flash kernels (whose calls carry the scope in their
``op_name``) — forward, replay and backward, their weight-gradient
products with the Adam update XLA fused into them.  Part of
``attn_block_ms``; the full layers' part stands under ``attn_full``
and is, the norms and output projections apart, the difference."""
from ._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "attn_sliding")
