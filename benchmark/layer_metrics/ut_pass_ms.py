"""model step (models/llama.py ``_forward``): device milliseconds of
ONE pass over the looped decoder's stack — what runs under
``ut_stack`` a step (the L layer calls of each pass with their flash
kernels, forward, replayed and backward, and each pass's closing
norm), over the configuration's ``total_ut_steps`` passes."""
from ._ut import scope_seconds


def read(facts):
    got = scope_seconds(facts)
    passes = facts["cell"]["config"].get("total_ut_steps")
    if got is None or not passes:
        return None
    by_scope, _, steps = got
    return 1e3 * by_scope["ut_stack"] / steps / passes
