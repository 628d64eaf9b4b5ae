"""model step (models/llama.py ``_forward``): device milliseconds a
step in instructions of the ``jax.checkpoint`` replay — ``op_name``s
that hold ``rematted_computation`` — whatever their block: the layer
calls' forward run a second time in the backward (the flash forward
kernel is kept, not replayed)."""
from ._blocks import phase_ms


def read(facts):
    return phase_ms(facts, "replay")
