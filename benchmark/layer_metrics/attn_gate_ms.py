"""model step (models/llama.py ``Llama._attn_gate``): device
milliseconds a step in instructions under ``attn_gate`` — the sigmoid
gate a query head between the attention kernels and the output
projection: the product of the block's normed input with the gate's
``[D, H]`` leaf, the sigmoid, and the multiply on the kernels' output
— forward, replay and backward, the leaf's weight-gradient product
with the Adam update XLA fused into it.  Inside ``blk_attn`` and the
layer kind's ``attn_sliding`` / ``attn_full``, outside ``gqa_proj``.
``None`` for a program without the scope (every program from before
PR 50, every model without a gate)."""
from ._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "attn_gate")
