"""loss (models/llama.py ``_exit_loss``): share of the step program's
device time in instructions under ``ut_exit`` — the looped decoder's
R heads and cross-entropies (forward, the replay of an exit's logits
and backward), its exit gate and exit distribution."""
from ._ut import scope_seconds


def read(facts):
    got = scope_seconds(facts)
    if got is None:
        return None
    by_scope, program_s, _ = got
    return by_scope["ut_exit"] / program_s
