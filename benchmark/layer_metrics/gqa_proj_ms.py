"""model step (models/llama.py ``Llama._gqa_kind``): device
milliseconds a step in instructions under ``gqa_proj`` — grouped-query
attention between ``attn_norm`` and the flash kernels: the three
products that write q, k and v in the kernels' layout, QK-norm where
the model has it, the two rotation passes and the GQA repeat of k and
v — forward, replay and backward, their weight-gradient products with
the Adam update XLA fused into them.  Not the kernels, not ``wo``.
Part of ``attn_block_ms``; ``None`` for a program without the scope
(every program from before PR 45)."""
from ._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "gqa_proj")
