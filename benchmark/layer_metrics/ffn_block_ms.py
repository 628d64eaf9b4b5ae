"""model step (models/llama.py ``_layer``): device milliseconds a step
in instructions under ``blk_ffn`` — from ``mlp_norm`` to the residual
add: the dense gated MLP or the whole expert layer (the ``moe_*``
scopes nest inside it), forward, replay and backward, their
weight-gradient products with the Adam update XLA fused into them."""
from ._blocks import block_ms


def read(facts):
    return block_ms(facts, "blk_ffn")
