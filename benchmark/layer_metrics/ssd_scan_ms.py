"""model step (ops/ssd.py ``mamba_mixer``): device milliseconds a step
in instructions under ``ssd_scan`` — from ``dt``'s softplus to ``y``:
the decays and their cumulative sums, ``C B^T`` under the decay mask
times ``dt x``, the chunks' states, the carry between chunks, the
carried state's part of the output, the ``D`` skip — forward, replay
and backward.  Part of ``ssm_block_ms``."""
from ._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "ssd_scan")
