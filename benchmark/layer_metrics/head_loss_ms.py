"""loss (models/llama.py ``loss_fn``): device milliseconds a step in
instructions under ``blk_head`` — a plain decoder's final norm, the
head's products and the cross-entropy (a looped decoder's R exits,
``ut_exit`` nests inside it), the means after them; forward and
backward."""
from ._blocks import block_ms


def read(facts):
    return block_ms(facts, "blk_head")
