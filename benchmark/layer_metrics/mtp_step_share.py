"""loss (models/llama.py ``_mtp_hidden``): share of the step
program's device time in instructions under ``mtp`` — the
multi-token-prediction module: the next token's embedding lookup, its
two norms and ``eh_proj``, its whole block (attention and expert
layer, with their kernels; also counted under ``attn_block_ms`` /
``ffn_block_ms``) and its closing norm, forward, replay and backward.
Its pass through the shared head runs in the one loop over both exits
under ``blk_head`` and is ``head_loss_ms``'s."""
from ._scopes import scope_seconds


def read(facts):
    got = scope_seconds(facts, "mtp")
    return None if got is None else got[0] / got[1]
