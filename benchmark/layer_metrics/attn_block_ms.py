"""model step (models/llama.py ``_layer``): device milliseconds a step
in instructions under ``blk_attn`` — from ``attn_norm`` to the
residual add: the norms, QK-norm, the four projections, RoPE, the GQA
repeat, the flash kernels, ``attn_out_norm``; forward, replay and
backward, their weight-gradient products with the Adam update XLA
fused into them."""
from ._blocks import block_ms


def read(facts):
    return block_ms(facts, "blk_attn")
