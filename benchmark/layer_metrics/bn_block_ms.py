"""model step (ops/layers.py ``BN.apply``): device milliseconds a step
in instructions under ``blk_bn`` — the batch statistics, the
normalisation and the running averages, forward and backward (what
XLA fused into a convolution counts under ``blk_conv``: the product
decides a fusion's block)."""
from ._blocks import block_ms


def read(facts):
    return block_ms(facts, "blk_bn")
