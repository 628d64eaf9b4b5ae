"""model step (models/llama.py ``_mamba_block``): device milliseconds a
step in instructions under ``blk_ssm`` — a mamba layer's mixer from
``attn_norm`` (the published ``input_layernorm``) to the residual add:
the norm, the input projection's three products, the convolution, the
chunked scan, the gated norm, the output projection; forward, replay
and backward, their weight-gradient products with the Adam update XLA
fused into them.  The nine mamba layers' sum; the attention layer
stays under ``blk_attn`` (``attn_block_ms``)."""
from ._blocks import block_ms


def read(facts):
    return block_ms(facts, "blk_ssm")
