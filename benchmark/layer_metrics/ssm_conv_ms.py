"""model step (ops/ssd.py ``causal_conv_silu``): device milliseconds a
step in instructions under ``ssm_conv`` — the depthwise causal
convolution of width 4 over ``x | B | C`` and its SiLU, forward,
replay and backward (the taps' and the bias's gradients with their
Adam update).  Memory-bound; part of ``ssm_block_ms``."""
from ._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "ssm_conv")
